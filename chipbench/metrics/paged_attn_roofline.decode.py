"""Paged attention inside the decode chunks: the least time its calls
could take, with the KV bytes of each row's live context (never the block
table's width), over the device time its events took, in percent."""

from chipbench import roofline


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    secs = t.kernel_seconds("paged_attn", inside="chunk")
    kv = ctx.cfg["serving"]["kv_dtype"]
    ops = byt = 0.0
    for _, k, live, traced in ctx.spans.chunks:
        if not traced:
            continue
        for j in range(k):
            o, b = roofline.attn_call(ctx.cfg, [n + j + 1 for n in live], kv,
                                      ctx.cfg["serving"]["block_size"])
            ops, byt = ops + o, byt + b
    if secs <= 0 or byt == 0:
        return None
    layers = ctx.cfg["num_hidden_layers"]
    least = max(byt * layers / ctx.peaks["hbm_bytes_per_s"],
                ops * layers / ctx.peaks["bf16_flops"])
    return 100.0 * least / secs
