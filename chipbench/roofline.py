"""Operations and bytes of the served kernels and of the model, from
shapes alone: the yardstick the roofline and MFU readers divide by time.

Bytes are what a call must move between HBM and the chip at the least:
its operands read once and its result written once. For paged attention
the KV bytes are those of the live context of each row, never of the
block table's width: a kernel that fetches pages past a row's length
does work no user asked for, and its share drops.
"""

from __future__ import annotations

LANE = 128


def _up(x: int, m: int = LANE) -> int:
    return -(-x // m) * m


def sites(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one layer's packed matmuls."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("wg", d, f), ("wu", d, f), ("wd", f, d)]


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
    ops = byt = 0.0
    for _, k, n in sites(cfg):
        o, b = w4a8_call(m, k, n)
        ops, byt = ops + o, byt + b
    L = cfg["num_hidden_layers"]
    return ops * L, byt * L


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """K and V of one position in one layer."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    item = 1 if kv_dtype == "int8" else 2
    return 2 * cfg["num_key_value_heads"] * hd * item


def attn_call(cfg: dict, lens, kv_dtype: str, block: int = 64):
    """(ops, bytes) of one layer's paged decode attention over rows whose
    live context lengths (the new token included) are ``lens``."""
    d = cfg["hidden_size"]
    nh = cfg["num_attention_heads"]
    hd = d // nh
    nkv = cfg["num_key_value_heads"]
    per_tok = kv_bytes_per_token(cfg, kv_dtype)
    total = sum(lens)
    byt = per_tok * total + len(lens) * 2 * (2 * nh * hd)  # q in, out
    if kv_dtype == "int8":  # one f32 scale per (page, kv head), K and V
        byt += sum(-(-n // block) for n in lens) * 2 * nkv * 4
    ops = 2.0 * 2 * nh * hd * total  # QK^T and PV
    return ops, float(byt)


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    the head."""
    per_layer = sum(k * n for _, k, n in sites(cfg))
    return per_layer * cfg["num_hidden_layers"] + (
        cfg["vocab_size"] * cfg["hidden_size"])


def decode_token_ops(cfg: dict, length: int) -> float:
    """Model operations of one decoded token whose live context (itself
    included) is ``length``."""
    d = cfg["hidden_size"]
    return (2.0 * matmul_params(cfg)
            + 2.0 * 2 * d * length * cfg["num_hidden_layers"])


def prefill_ops(cfg: dict, start: int, end: int) -> float:
    """Model operations of prefilling prompt positions [start, end) with
    positions [0, start) already cached; the head runs for the last
    position only."""
    d = cfg["hidden_size"]
    per_layer = sum(k * n for _, k, n in sites(cfg))
    n = end - start
    # causal attention: position p attends to p + 1 keys
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return (2.0 * n * per_layer * cfg["num_hidden_layers"]
            + 2.0 * 2 * d * keys * cfg["num_hidden_layers"]
            + 2.0 * cfg["vocab_size"] * d)
