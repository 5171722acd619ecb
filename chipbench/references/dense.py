"""Plain reference of a dense decoder served at W4A8: float32, no kernels,
no cache, no batching tricks.

It follows the configuration as run (``configs/<name>.json``): RMSNorm with
unit gains, grouped-query attention with full rotary embeddings, SwiGLU,
a tied head. Its weights are the benchmark's own seeded draws
(``seeded.py``), rounded to the nearest 4-bit code per output channel
(scale = max|w| / 7, kept in bfloat16 as the served scales are). It
imports nothing of the program under test and takes nothing it made.

Departures from the served datapath, on purpose:

- activations stay float32 (the served kernel quantizes each matmul's
  input to 8-bit codes over the whole call, that is over every row in
  flight, which a per-request reference cannot know);
- KV stays float32, attention runs in one exact softmax;
- every matmul runs at ``precision="highest"``.

``act_bits`` / ``kv_bits`` put those roundings back in, coarser, for the
controls (``tests/test_control.py``): activations per row, KV per
(64-token page, kv head), as the served int8 pages do.

The work runs layer by layer, each layer's weights drawn and dropped
inside one jitted call, so the float model is never whole on the device.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeded

HI = jax.lax.Precision.HIGHEST
PAGE = 64


def rtn4(w):
    """(K, N) -> the float32 weight a 4-bit code per entry serves."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0,
                        1e-8)
    q = jnp.clip(jnp.round(w / scale), -7, 7)
    return q * scale.astype(jnp.bfloat16).astype(jnp.float32)


def fake_quant_rows(x, bits):
    """Asymmetric unsigned codes per row, as a dynamic activation
    quantizer makes them (zero always representable)."""
    levels = 2.0 ** bits - 1
    lo = jnp.minimum(jnp.min(x, axis=-1, keepdims=True), 0.0)
    hi = jnp.maximum(jnp.max(x, axis=-1, keepdims=True), 0.0)
    scale = jnp.maximum((hi - lo) / levels, 1e-8)
    zp = jnp.clip(jnp.round(-lo / scale), 0, levels)
    codes = jnp.clip(jnp.round(x / scale) + zp, 0, levels)
    return (codes - zp) * scale


def fake_quant_pages(x, bits):
    """(B, S, nkv, hd) with S a whole number of pages: symmetric codes per
    (page, kv head), as the quantized KV pool stores them."""
    B, S, nkv, hd = x.shape
    qmax = 2.0 ** (bits - 1) - 1
    p = x.reshape(B, S // PAGE, PAGE, nkv, hd)
    scale = jnp.maximum(jnp.max(jnp.abs(p), axis=(2, 4), keepdims=True)
                        / qmax, 1e-8)
    return (jnp.clip(jnp.round(p / scale), -qmax, qmax) * scale).reshape(
        x.shape)


def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
    """(B, S, H, hd), rotate-half layout, positions 0..S-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, w, cfg, act_bits, kv_bits):
    B, S, d = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    eps = cfg["rms_norm_eps"]

    def mm(a, name):
        if act_bits is not None:
            a = fake_quant_rows(a, act_bits)
        return jnp.matmul(a, w[name], precision=HI)

    h = rms_norm(x, eps)
    q = rope(mm(h, "wq").reshape(B, S, nh, hd), cfg["rope_theta"])
    k = rope(mm(h, "wk").reshape(B, S, nkv, hd), cfg["rope_theta"])
    v = mm(h, "wv").reshape(B, S, nkv, hd)
    if kv_bits is not None:
        k, v = fake_quant_pages(k, kv_bits), fake_quant_pages(v, kv_bits)
    qg = q.reshape(B, S, nkv, nh // nkv, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, precision=HI) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(B, S, nh * hd)
    x = x + mm(o, "wo")
    h = rms_norm(x, eps)
    return x + mm(jax.nn.silu(mm(h, "wg")) * mm(h, "wu"), "wd")


@partial(jax.jit, static_argnames=("cfg_items", "act_bits", "kv_bits"))
def _layer(key, x, cfg_items, act_bits, kv_bits):
    cfg = dict(cfg_items)
    w = {n: rtn4(a) for n, a in seeded.layer_weights(key, cfg).items()}
    return _block(x, w, cfg, act_bits, kv_bits)


@partial(jax.jit, static_argnames=("cfg_items",))
def _embed(key, tokens, cfg_items):
    return seeded.embedding(key, dict(cfg_items))[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps",))
def _final(x, eps):
    return rms_norm(x, eps)


def _items(cfg: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "vocab_size", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def hidden(cfg: dict, seed: int, tokens, *, act_bits=None, kv_bits=None):
    """Final-normed hidden states (B, S, d) float32 of ``tokens`` (B, S);
    S a whole number of 64-token pages. Causal, so trailing padding does
    not touch earlier positions."""
    items = _items(cfg)
    key = seeded.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), items)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer(seeded.layer_key(key, layer), x, items, act_bits, kv_bits)
    return _final(x, cfg["rms_norm_eps"])


@partial(jax.jit, static_argnames=("cfg_items",))
def _table(key, cfg_items):
    return seeded.embedding(key, dict(cfg_items))


@jax.jit
def _head_stats(emb, h):
    logits = jnp.matmul(h, emb.astype(jnp.float32).T, precision=HI)
    return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1)


@jax.jit
def _token_logits(emb, h, toks):
    return jnp.sum(h * emb[toks].astype(jnp.float32), axis=-1)


def head(cfg: dict, seed: int, h, toks, block: int = 256):
    """For every row of ``h`` (N, d): the best logit, its token, and the
    logit of ``toks`` (N,), ``block`` rows at a time so the logits of a
    200k-entry vocabulary never exist for all rows at once."""
    emb = _table(seeded.seed_key(seed), _items(cfg))
    h = jnp.asarray(h, jnp.float32)
    n = h.shape[0]
    pad = (-n) % block
    hp = jnp.pad(h, ((0, pad), (0, 0)))
    best, arg = [], []
    for i in range(0, n + pad, block):
        b, a = _head_stats(emb, hp[i:i + block])
        best.append(np.asarray(b))
        arg.append(np.asarray(a))
    tok = np.asarray(_token_logits(emb, h, jnp.asarray(toks, jnp.int32)))
    return np.concatenate(best)[:n], np.concatenate(arg)[:n], tok
