"""The whole decode step's share of the chip: model operations of every
token the traced chunks decoded (all matmuls and the head, and attention
at each row's live length) over the traced window, over the int8 peak,
in percent. It bounds every kernel's gain: a kernel taken off the path
leaves its own roofline silent, not this."""

from chipbench import roofline


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    ops = 0.0
    for _, k, live, traced in ctx.spans.chunks:
        if traced:
            ops += sum(roofline.decode_token_ops(ctx.cfg, n + j + 1)
                       for n in live for j in range(k))
    if ops == 0:
        return None
    return 100.0 * ops / t.window_s / ctx.peaks["int8_ops"]
