"""End to end: the input rows of every shuffle the window completed, over
the window's whole elapsed time (client work between calls included)."""


def read(ctx):
    return ctx["completed"] * ctx["work"]["rows_in"] / ctx["elapsed"]
