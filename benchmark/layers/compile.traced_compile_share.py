"""Share of the traced sub-window in which a host thread was inside
XLA's ``backend_compile_and_load``, from the profiler trace's host
plane. It sees compiles the program's own counter does not count (the
streamed scan builds a program per statement)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.host_work_s("XLA compile") / ctx.trace.window_s
