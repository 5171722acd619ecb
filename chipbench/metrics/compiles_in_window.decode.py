"""Programs lowered inside the measured window of the traced run (each a
compile or a load from the cache, in the window's time). Reads 0 when
set-up warmed every shape the traffic uses."""


def read(ctx):
    return float(ctx.compiles)
