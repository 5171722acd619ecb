"""Share of the KV pages the paged-attention grid walks that hold live
KV, over the traced passes, in percent: the engine's ``attn_pages_live``
(for each decode step and decoding row, ``ceil(len / block_size)``) over
its ``attn_pages_grid`` (rows x block-table width per step), both read
from the counters the ``serve/pass`` spans carry (``scopes.py``). None
where the program keeps no such counters."""

from chipbench import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    c = scopes.counters_of(ctx)
    if not c or c.get("attn_pages_grid", 0) <= 0:
        return None
    return 100.0 * c["attn_pages_live"] / c["attn_pages_grid"]
