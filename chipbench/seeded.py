"""Seeded float weights of a dense decoder, one layer at a time.

Both the served model (``weights.py`` packs these) and the plain reference
(``references/dense.py``) draw their weights from here, so neither takes
anything the program made. Every layer comes from its own key, so the
float model never has to exist whole: a layer is drawn, used and dropped.

Scales follow a standard initialisation: projections into the residual
width at 1/sqrt(fan_in), the embedding (which is also the tied head) at
0.02. Norm gains are ones. Weights are drawn in float32 and stored in
bfloat16, the type they are served in before packing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: (site, fan-in key, fan-out key) of a dense block's projections, in the
#: order their keys are split from the layer key
SITES = (("wq", "d", "q"), ("wk", "d", "kv"), ("wv", "d", "kv"),
         ("wo", "q", "d"), ("wg", "d", "f"), ("wu", "d", "f"),
         ("wd", "f", "d"))
MIXER = ("wq", "wk", "wv", "wo")


def dims(cfg: dict) -> dict:
    """Widths of the projections from a configuration file's numbers."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return {"d": d, "q": cfg["num_attention_heads"] * hd,
            "kv": cfg["num_key_value_heads"] * hd,
            "f": cfg["intermediate_size"]}


def layer_key(seed_key, layer):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)


def embed_key(seed_key):
    return jax.random.fold_in(seed_key, 0)


def layer_weights(key, cfg: dict) -> dict:
    """The seven projections of one layer, (fan_in, fan_out) bfloat16."""
    w = dims(cfg)
    keys = jax.random.split(key, len(SITES))
    out = {}
    for k, (name, fi, fo) in zip(keys, SITES):
        x = jax.random.normal(k, (w[fi], w[fo]), jnp.float32)
        out[name] = (x / math.sqrt(w[fi])).astype(jnp.bfloat16)
    return out


def embedding(seed_key, cfg: dict):
    """(vocab, d) bfloat16; also the LM head (tied)."""
    x = jax.random.normal(embed_key(seed_key),
                          (cfg["vocab_size"], cfg["hidden_size"]), jnp.float32)
    return (x * 0.02).astype(jnp.bfloat16)


def seed_key(seed: int):
    """A key from any whole number that fits 64 bits. ``jax.random.key``
    keeps only the low 32 bits of its seed, so the high word is folded in
    on top: seeds that differ only above bit 31 draw different weights."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
