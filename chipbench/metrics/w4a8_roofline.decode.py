"""W4A8 kernel inside the decode chunks: the least time its calls could
take on the chip (the larger of bytes over HBM bandwidth and integer ops
over the int8 peak), over the device time its events took, in percent.
Every call of every traced chunk runs all engine rows."""

from chipbench import roofline


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    secs = t.kernel_seconds("w4a8", inside="chunk")
    steps = sum(k for _, k, _, traced in ctx.spans.chunks if traced)
    if secs <= 0 or steps == 0:
        return None
    ops, byt = roofline.w4a8_step(ctx.cfg, ctx.spans.rows)
    least = max(byt * steps / ctx.peaks["hbm_bytes_per_s"],
                ops * steps / ctx.peaks["int8_ops"])
    return 100.0 * least / secs
