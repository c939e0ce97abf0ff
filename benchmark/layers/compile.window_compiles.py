"""Programs compiled inside the window (a count): 0 where warm-up
covered every shape the traffic uses."""


def read(ctx):
    return ctx.counters.get("presto_tpu_programs_compiled_total", 0.0)
