"""``peak_bytes_in_use`` of the chip after the window, in GiB."""


def read(ctx):
    return ctx.peak_bytes / 2**30 if ctx.peak_bytes else None
