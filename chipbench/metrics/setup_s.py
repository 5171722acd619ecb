"""Set-up: from the start of the process to the start of the window
(loading, weights built on the device, every program compiled or loaded
and warmed)."""


def read(ctx):
    return ctx.setup_s
