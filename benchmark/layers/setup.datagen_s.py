"""Seconds the connector took to generate the cell's tables on the host."""


def read(ctx):
    return ctx.setup.get("datagen_s")
