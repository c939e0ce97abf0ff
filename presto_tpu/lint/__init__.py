"""Engine-specific static analysis (stdlib ``ast`` only).

Twelve rule families guard the places where this engine's bugs ship
silently (the reference defends the analogous seams with its
PlanSanityChecker pipeline, sql/planner/sanity/PlanSanityChecker.java):

- **tracer hygiene** (``lint/tracer.py``): inside ``@jax.jit``-reachable
  functions, Python-level inspection of traced values either crashes at
  trace time on a rarely-hit path or silently forces a retrace per call.
- **lock discipline** (``lint/locks.py``): an attribute written under
  ``with self._lock`` in one method and read bare in another is a latent
  race that only fires under load. The same lockset analysis powers
  **blocking-under-lock**: no network round-trip, plan compile, or
  device sync while holding a lock in ``server/``/``parallel/``/
  ``ft/`` — a multi-second XLA compile inside a coordinator lock
  serializes the whole serve path.
- **dispatch exhaustiveness** (``lint/dispatch.py``): a new ``PlanNode``
  subclass that one of the visitors (serde, printer, sanity,
  fingerprint, executor) forgets fails only on the query shape that
  reaches it.
- **metric naming** (``lint/metrics.py``): registrations against the
  obs/metrics registry checked statically with the registry's own
  validator — a bad name on a rarely-hit path would otherwise only
  raise in production.
- **timeout discipline** (``lint/timeouts.py``): every
  ``urlopen``/``_urlopen`` call site must pass an explicit
  ``timeout=`` — an internal HTTP call without a deadline turns one
  dead peer into a hung thread the failure detector cannot see.
- **span discipline** (``lint/spans.py``): every ``obs.trace`` span
  must be opened via ``with`` (or ``ExitStack.enter_context``) — a
  hand-entered span leaks both an unfinished span and the ambient
  trace context on any exception before close.
- **pool discipline** (``lint/pools.py``): every ``MemoryPool.reserve``
  call site must pair with a ``free`` on all exit paths (a ``finally``
  in the same function) — a leaked reservation permanently shrinks the
  pool under exactly the load it governs.
- **field-level locksets** (``lint/races.py``): the Eraser-style
  refinement of lock discipline — every field's read/write sites must
  agree on WHICH lock guards it; written-under-A-read-under-B races
  are invisible to the boolean rule.
- **ambient-context handoff** (``lint/handoff.py``): thread-spawn
  sites in modules using ambient contextvars/thread-locals (trace
  context, cancel token, stats recorder, session override) must hand
  the state over explicitly or document why the thread is
  context-free.
- **trace-key provenance** (``lint/tracekey.py``): every ambient
  input trace-reachable code reads (session property, env var,
  mutable module global — tracked across aliases, parameters, and
  helper calls) must participate in the program-cache key or carry a
  justified ``TRACE_KEY_EXEMPT`` entry, and every
  ``TRACE_RELEVANT_PROPERTIES`` entry must be genuinely read — the
  compile-cache soundness contract, machine-checked both ways.
- **device-sync boundary** (``lint/devicesync.py``): every
  host-blocking device read reachable from the execute-path roots
  (``.item()``, ``np.asarray`` of a jit output, ``jax.device_get``,
  ``block_until_ready``, ``int()`` of a device scalar) must go through
  the counted ``exec/hostsync`` boundary or carry a justified
  ``DEVICE_SYNC_EXEMPT`` entry — one stray sync in a stage walk
  serializes every dispatch behind a device round-trip.
- **retrace hazards** (``lint/retrace.py``): data-dependent integers
  (``bincount().max()``, ``fetch_int`` readbacks) must pass through
  ``next_pow2``/``bucket_*`` before reaching a shape constructor, a
  Python branch, or a cache-key component — an unbucketed value
  compiles one program per dataset and the cache never hits.

Run ``python -m presto_tpu.lint presto_tpu/`` (exits nonzero on
findings; ``--changed`` scopes reporting to files changed since HEAD
for pre-commit runs; ``--sarif`` emits a SARIF 2.1.0 log for CI
diff annotation, in-source waivers exported as suppressed results);
suppress a single line with ``# lint: disable=rule-name`` plus a
comment saying why. Stale suppressions — disables that no longer
suppress anything — are reported as ``stale-suppression`` findings by
the runner itself.
"""

from presto_tpu.lint.core import (Finding, Project, available_rules,
                                  run_lint)

# rule modules self-register on import
from presto_tpu.lint import tracer as _tracer  # noqa: E402,F401
from presto_tpu.lint import locks as _locks  # noqa: E402,F401
from presto_tpu.lint import dispatch as _dispatch  # noqa: E402,F401
from presto_tpu.lint import metrics as _metrics  # noqa: E402,F401
from presto_tpu.lint import timeouts as _timeouts  # noqa: E402,F401
from presto_tpu.lint import pools as _pools  # noqa: E402,F401
from presto_tpu.lint import spans as _spans  # noqa: E402,F401
from presto_tpu.lint import races as _races  # noqa: E402,F401
from presto_tpu.lint import handoff as _handoff  # noqa: E402,F401
from presto_tpu.lint import tracekey as _tracekey  # noqa: E402,F401
from presto_tpu.lint import devicesync as _devicesync  # noqa: E402,F401
from presto_tpu.lint import retrace as _retrace  # noqa: E402,F401

__all__ = ["Finding", "Project", "available_rules", "run_lint"]
