"""The served model's weights, made on the device from ``--seed``.

One jitted call draws each layer's float weights (``seeded.py``), packs
them with the program's own ``pack_decode_params`` at the configuration's
datapath (RTN 4-bit codes, the layout and bytes a calibrated artifact
has) and stacks the packed layers. ``lax.map`` runs one layer at a time,
so the float model is never whole on the device: a bfloat16 Phi-4-mini
alone would take 7.7 GB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import seeded
from repro.core import PTQConfig
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


def ptq_config(cfg: dict) -> PTQConfig:
    dp = cfg["datapath"]
    return PTQConfig(w_bits=dp["w_bits"], act_bits=dp["act_bits"],
                     p_bits=dp["p_bits"], tile=dp["tile"], algorithm="rtn")


def _float_layer(key, cfg: dict, layer):
    """One layer's float tree in the program's layout, stacked over a
    leading repeat axis of 1."""
    w = seeded.layer_weights(seeded.layer_key(key, layer), cfg)
    ones = jnp.ones((1, cfg["hidden_size"]), jnp.bfloat16)
    return {"norm1": {"w": ones},
            "mixer": {n: w[n][None] for n in seeded.MIXER},
            "norm2": {"w": ones},
            "ffn": {n: w[n][None] for n in ("wg", "wu", "wd")}}


def float_params(key, cfg: dict) -> dict:
    """The whole float model in the program's layout (tests only: this is
    what the layer-at-a-time build must equal once packed)."""
    layers = [_float_layer(key, cfg, i)
              for i in range(cfg["num_hidden_layers"])]
    stacked = jax.tree.map(lambda *a: jnp.concatenate(a), *layers)
    return {"embedding": {"embed": seeded.embedding(key, cfg)},
            "layers": (stacked,),
            "final_norm": {"w": jnp.ones((cfg["hidden_size"],),
                                         jnp.bfloat16)}}


def packed_params(cfg: dict, seed: int) -> dict:
    """The served tree: packed layers, bfloat16 embedding (the tied head)
    and final norm, made on the device in one call."""
    return jax.jit(build_fn(cfg))(seeded.seed_key(seed))


def build_fn(cfg: dict):
    """The function of a key that :func:`packed_params` jits."""
    one = model_config({**cfg, "num_hidden_layers": 1})
    ptq = ptq_config(cfg)

    def build(key):
        def layer(i):
            tree = {"embedding": {}, "layers": (_float_layer(key, cfg, i),),
                    "final_norm": {}}
            packed = pack_decode_params(tree, one, ptq)["layers"][0]
            return jax.tree.map(lambda a: a[0], packed)

        layers = jax.lax.map(layer, jnp.arange(cfg["num_hidden_layers"]))
        return {"embedding": {"embed": seeded.embedding(key, cfg)},
                "layers": (layers,),
                "final_norm": {"w": jnp.ones((cfg["hidden_size"],),
                                             jnp.bfloat16)}}

    return build
