"""Seconds of set-up that no span of the program names: ``setup_s``
minus the union, over ``[t0 - setup_s, t0]``, of the process trace's
spans under its root (``import``, ``datagen``, ...) and of every
statement's ``admission`` and root ``query`` span. By construction it
holds what the harness does itself before the window: the runtime's
start inside ``run.device_stamp`` (``jax.devices()``), the schedule,
the child's start-up (1 s) and an open loop's ramp where no statement
runs.

``setup_spans`` below is what the six ``setup.*`` span readers share:
they take the spans from the program's ``TRACER`` itself, and read
nothing, with a line on stderr, where the store has evicted a trace (no
number is better than one over a part of set-up) or where the program
has no process trace (a tree from before PR 36)."""

import sys

import tracered


def setup_spans(metric):
    """(spans of the process trace under its root, the retained
    statement traces as (root, spans) pairs) with ``t0``/``t1`` on the
    harness's clock (``time.monotonic()``, as ``ctx.t0``), or None."""
    from presto_tpu.obs import trace as OT
    from presto_tpu.obs.metrics import REGISTRY
    if not hasattr(OT.TRACER, "trace_ids"):
        return None
    evicted = REGISTRY.counter("presto_tpu_trace_evictions_total").value()
    if evicted:
        print(f"benchmark: {metric}: the span store evicted "
              f"{evicted:.0f} traces, so part of set-up is gone: no "
              f"number", file=sys.stderr)
        return None
    at = OT.to_monotonic(OT.now())

    def on_the_harness_clock(s):
        return {"name": s.name, "parent": s.parent_id, "attrs": s.attrs,
                "t0": OT.to_monotonic(s.t0),
                "t1": OT.to_monotonic(s.t1) if s.t1 is not None else at}

    process = [on_the_harness_clock(s)
               for s in OT.TRACER.spans(OT.PROCESS_TRACE_ID)
               if s.parent_id is not None]
    statements = [(on_the_harness_clock(root),
                   [on_the_harness_clock(s) for s in OT.TRACER.spans(tid)])
                  for tid, root in OT.TRACER.trace_ids() if root is not None]
    return process, statements


def closed_before_t0(ctx, metric, names):
    """The spans called one of ``names`` that closed before the window
    opened, in the statements whose root did; None as above."""
    found = setup_spans(metric)
    if found is None:
        return None
    return [s for root, spans in found[1] if root["t1"] <= ctx.t0
            for s in spans if s["name"] in names and s["t1"] <= ctx.t0]


def phase_seconds(ctx, metric, *attrs):
    """``attrs`` summed over set-up's ``compile`` spans; None where
    there is none that carries them."""
    built = closed_before_t0(ctx, metric, ("compile",))
    seconds = [s["attrs"][a] for s in built or () for a in attrs
               if a in s["attrs"]]
    return sum(seconds) if seconds else None


def read(ctx):
    found = setup_spans("setup.unattributed_s")
    if found is None:
        return None
    process, statements = found
    named = [(s["t0"], s["t1"]) for s in process]
    for root, spans in statements:
        named.append((root["t0"], root["t1"]))
        named += [(s["t0"], s["t1"]) for s in spans
                  if s["name"] == "admission"]
    lo = ctx.t0 - ctx.setup_s
    return ctx.setup_s - tracered.covered(tracered.merge(named), lo, ctx.t0)
