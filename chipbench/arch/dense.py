"""A dense grouped-query decoder: every layer attention and a SwiGLU FFN,
the head size ``hidden_size / num_attention_heads``, the head tied to the
embedding.

The served model's weights are made on the device from the seed: one
jitted call draws each layer's float weights (``seeded.py``), packs them
with the program's own ``pack_decode_params`` at the configuration's
datapath (RTN 4-bit codes, the layout and bytes a calibrated artifact
has) and stacks the packed layers. ``lax.map`` runs one layer at a time,
so the float model is never whole on the device: a bfloat16 Phi-4-mini
alone would take 7.7 GB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import seeded
from chipbench.roofline import w4a8_call
from chipbench.weights import ptq_config
from repro.models.config import ModelConfig, uniform_pattern
from repro.quant.serve_packed import pack_decode_params


def model_config(cfg: dict) -> ModelConfig:
    """The program's configuration object for a configuration file."""
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        pattern=uniform_pattern("attn", "mlp"),
        rope_theta=float(cfg["rope_theta"]),
        max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype="bfloat16", act_dtype="bfloat16")


def small(cfg: dict) -> dict:
    """The configuration at 2 layers and widths a CPU test holds."""
    return {**cfg, "num_hidden_layers": 2, "hidden_size": 128,
            "intermediate_size": 256, "num_attention_heads": 4,
            "num_key_value_heads": 2, "vocab_size": 512}


def _float_layer(key, cfg: dict, layer):
    """One layer's float tree in the program's layout, stacked over a
    leading repeat axis of 1."""
    w = seeded.layer_weights(seeded.layer_key(key, layer), cfg)
    ones = jnp.ones((1, cfg["hidden_size"]), jnp.bfloat16)
    return {"norm1": {"w": ones},
            "mixer": {n: w[n][None] for n in seeded.MIXER},
            "norm2": {"w": ones},
            "ffn": {n: w[n][None] for n in ("wg", "wu", "wd")}}


def _outer(key, cfg: dict, layers) -> dict:
    return {"embedding": {"embed": seeded.embedding(key, cfg)},
            "layers": (layers,),
            "final_norm": {"w": jnp.ones((cfg["hidden_size"],),
                                         jnp.bfloat16)}}


def float_params(key, cfg: dict) -> dict:
    """The whole float model in the program's layout (tests only: this is
    what the layer-at-a-time build must equal once packed)."""
    layers = [_float_layer(key, cfg, i)
              for i in range(cfg["num_hidden_layers"])]
    return _outer(key, cfg, jax.tree.map(lambda *a: jnp.concatenate(a),
                                         *layers))


def build_fn(cfg: dict):
    """The served tree as a function of a seed key: packed layers,
    bfloat16 embedding (the tied head) and final norm."""
    one = model_config({**cfg, "num_hidden_layers": 1})
    ptq = ptq_config(cfg)

    def build(key):
        def layer(i):
            tree = {"embedding": {}, "layers": (_float_layer(key, cfg, i),),
                    "final_norm": {}}
            packed = pack_decode_params(tree, one, ptq)["layers"][0]
            return jax.tree.map(lambda a: a[0], packed)

        return _outer(key, cfg, jax.lax.map(
            layer, jnp.arange(cfg["num_hidden_layers"])))

    return build


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attn_heads(cfg: dict) -> tuple[int, int]:
    """(query heads, KV heads) of an attention layer."""
    return cfg["num_attention_heads"], cfg["num_key_value_heads"]


def attn_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def _layer_sites(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one layer's packed matmuls."""
    d = cfg["hidden_size"]
    hd = head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("wg", d, f), ("wu", d, f), ("wd", f, d)]


def sites(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every packed matmul of the model, layer by layer."""
    return _layer_sites(cfg) * cfg["num_hidden_layers"]


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    the head."""
    per_layer = sum(k * n for _, k, n in _layer_sites(cfg))
    return per_layer * cfg["num_hidden_layers"] + (
        cfg["vocab_size"] * cfg["hidden_size"])


def w4a8_step(cfg: dict, m: int) -> tuple[float, float]:
    """(ops, bytes) of every W4A8 call of one forward step over ``m``
    rows, all layers."""
    ops = byt = 0.0
    for _, k, n in _layer_sites(cfg):
        o, b = w4a8_call(m, k, n)
        ops, byt = ops + o, byt + b
    L = cfg["num_hidden_layers"]
    return ops * L, byt * L


def _attn_width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * head_dim(cfg)


def decode_token_ops(cfg: dict, length: int) -> float:
    """Model operations of one decoded token whose live context (itself
    included) is ``length``."""
    return (2.0 * matmul_params(cfg)
            + 2.0 * 2 * _attn_width(cfg) * length * attn_layers(cfg))


def prefill_ops(cfg: dict, start: int, end: int) -> float:
    """Model operations of prefilling prompt positions [start, end) with
    positions [0, start) already cached; the head runs for the last
    position only."""
    d = cfg["hidden_size"]
    per_layer = sum(k * n for _, k, n in _layer_sites(cfg))
    n = end - start
    # causal attention: position p attends to p + 1 keys
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return (2.0 * n * per_layer * cfg["num_hidden_layers"]
            + 2.0 * 2 * _attn_width(cfg) * keys * attn_layers(cfg)
            + 2.0 * cfg["vocab_size"] * d)
