"""A per-layer metric added as a new file: the window's block count."""


def read(ctx):
    return ctx.blocks
