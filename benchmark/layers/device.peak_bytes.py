"""Peak bytes in use on the fullest chip after the window, as the
runtime counts them; guards the cell's size."""


def read(ctx):
    return ctx.device.get("memory_peak_bytes") or None
