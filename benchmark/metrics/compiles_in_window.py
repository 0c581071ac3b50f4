"""Programs traced and compiled inside the window: growth of the amprof
observatory's per-program jit caches from the first window delivery to
the window's close."""


def read(ctx):
    return ctx["compiles_in_window"]
