"""Operations and bytes of the served kernels and of the model: the
yardstick the roofline and MFU readers divide by time.

What depends on the model's shape (its matmul sites, its layers of
attention, its head size) comes from the configuration's architecture
module (``arch/<arch>.py``); the functions here that take a configuration
read it from there. What stays here is shape-free.

Bytes are what a call must move between HBM and the chip at the least:
its operands read once and its result written once. For paged attention
the KV bytes are those of the live context of each row, never of the
block table's width: a kernel that fetches pages past a row's length
does work no user asked for, and its share drops.
"""

from __future__ import annotations

from chipbench import arch

LANE = 128


def _up(x: int, m: int = LANE) -> int:
    return -(-x // m) * m


def w4a8_weight_bytes(k: int, n: int) -> int:
    """A packed leaf as the kernel reads it: int4 codes padded to whole
    128-lane blocks, bf16 per-channel scales, int32 column sums."""
    return _up(k) * _up(n) // 2 + 2 * n + 4 * n


def w4a8_call(m: int, k: int, n: int) -> tuple[float, float]:
    """(integer ops, bytes) of one W4A8 call: ``m`` rows of 8-bit codes
    against a packed (k, n) leaf, bf16 out."""
    return 2.0 * m * k * n, float(w4a8_weight_bytes(k, n) + m * k + 2 * m * n)


def w4a8_step(cfg: dict, m: int) -> tuple[float, float]:
    """(ops, bytes) of every W4A8 call of one forward step over ``m``
    rows, all layers."""
    return arch.of(cfg).w4a8_step(cfg, m)


def decode_token_ops(cfg: dict, length: int) -> float:
    """Model operations of one decoded token whose live context (itself
    included) is ``length``."""
    return arch.of(cfg).decode_token_ops(cfg, length)


def attn_layers(cfg: dict) -> int:
    """Layers of the stack that hold attention and KV pages."""
    return arch.of(cfg).attn_layers(cfg)


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """K and V of one position in one attention layer."""
    a = arch.of(cfg)
    item = 1 if kv_dtype == "int8" else 2
    return 2 * a.attn_heads(cfg)[1] * a.head_dim(cfg) * item


def attn_call(cfg: dict, lens, kv_dtype: str, block: int = 64):
    """(ops, bytes) of one layer's paged decode attention over rows whose
    live context lengths (the new token included) are ``lens``."""
    a = arch.of(cfg)
    nh, nkv = a.attn_heads(cfg)
    hd = a.head_dim(cfg)
    per_tok = kv_bytes_per_token(cfg, kv_dtype)
    total = sum(lens)
    byt = per_tok * total + len(lens) * 2 * (2 * nh * hd)  # q in, out
    if kv_dtype == "int8":  # one f32 scale per (page, kv head), K and V
        byt += sum(-(-n // block) for n in lens) * 2 * nkv * 4
    ops = 2.0 * 2 * nh * hd * total  # QK^T and PV
    return ops, float(byt)
