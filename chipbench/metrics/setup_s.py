"""End to end: everything before the window, from the process's first line:
start-up, data, the instantiating call and the first replay (which compiles,
or loads from the persistent cache)."""


def read(ctx):
    return ctx["setup_s"]
