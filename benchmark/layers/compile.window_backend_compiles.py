"""Programs the backend was handed inside the window (a count): the
window's delta of ``presto_tpu_jax_backend_compiles_total``, which
JAX's own monitoring events feed, so it counts every XLA compile and
every load from the persistent cache in the process, whichever code
built the program (``compile.window_compiles`` counts what went through
``exec/executor.compiling``). None where the program has no such
counter."""


def read(ctx):
    return ctx.counters.get("presto_tpu_jax_backend_compiles_total")
