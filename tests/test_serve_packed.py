"""Packed-int4 serving path: correctness vs float, abstract tracing,
sharding rules for packed leaves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import transformer as T
from repro.quant.serve_packed import pack_decode_params, packed_weight_bytes


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("smollm-360m").scaled(n_layers=2, vocab=128)
    params = T.init_model(jax.random.key(0), cfg)
    return cfg, params


def test_packed_decode_tracks_float(setup):
    cfg, params = setup
    pparams = pack_decode_params(params, cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 12), 0, 128)}
    _, cache_f = T.prefill(params, batch, cfg, max_len=16)
    _, cache_q = T.prefill(pparams, batch, cfg, max_len=16)
    tok = jnp.ones((2, 1), jnp.int32)
    l_f, _ = T.decode_step(params, tok, cache_f, jnp.int32(12), cfg)
    l_q, _ = T.decode_step(pparams, tok, cache_q, jnp.int32(12), cfg)
    corr = float(jnp.corrcoef(l_f.ravel(), l_q.ravel())[0, 1])
    assert corr > 0.85, corr  # int4 RTN on random weights
    assert bool(jnp.all(jnp.isfinite(l_q)))


def test_pack_works_under_eval_shape(setup):
    cfg, _ = setup
    abstract = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.key(0))
    packed = jax.eval_shape(lambda p: pack_decode_params(p, cfg), abstract)
    leaf = packed["layers"][0]["mixer"]["wq"]
    k = cfg.d_model
    assert leaf["packed"].dtype == jnp.int8
    # 2 codes per byte, K zero-padded to whole 128-lane blocks
    assert leaf["packed"].shape[-2] == -(-k // 128) * 128 // 2


def test_packed_param_shardings_resolve(setup):
    from repro.launch.mesh import make_mesh
    from repro.runtime.sharding import SERVING_QUANT_RULES, param_shardings

    cfg, params = setup
    pparams = pack_decode_params(params, cfg)
    mesh = make_mesh((1, 1))
    sh = param_shardings(pparams, mesh, SERVING_QUANT_RULES)
    # every packed/scale leaf got a sharding (no KeyErrors / rank mismatches)
    n = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n == len(jax.tree.leaves(pparams))


def test_packed_weight_bytes_accounting(setup):
    """The analytic accounting matches the *actual* packed tree byte for
    byte, per leaf kind — codes, per-channel scales, the col_sums
    zero-point term and the spec twin all counted (the old accounting
    undercounted by omitting everything but the codes)."""
    cfg, params = setup
    wb = packed_weight_bytes(cfg)
    # int4 codes are a quarter of bf16, before the 128-lane padding
    assert wb["packed_code_bytes"] * 4 >= wb["bf16_bytes"]
    assert wb["weight_elems"] > 0
    assert wb["packed_bytes"] == sum(
        wb[k] for k in ("packed_code_bytes", "scale_bytes", "col_sums_bytes",
                        "spec_bytes", "act_bytes", "bias_bytes")
    )

    pparams = pack_decode_params(params, cfg)
    actual = {"packed_code_bytes": 0, "scale_bytes": 0, "col_sums_bytes": 0,
              "spec_bytes": 0, "act_bytes": 0, "bias_bytes": 0}
    key_map = {"packed": "packed_code_bytes", "scale": "scale_bytes",
               "col_sums": "col_sums_bytes", "spec_arr": "spec_bytes",
               "act_scale": "act_bytes", "act_zp": "act_bytes",
               "bias": "bias_bytes"}

    def walk(node):
        if isinstance(node, dict):
            if "packed" in node:
                for k, v in node.items():
                    if k != "spec":
                        actual[key_map[k]] += v.size * v.dtype.itemsize
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(pparams["layers"])
    for k, v in actual.items():
        assert wb[k] == v, (k, wb[k], v)
    assert sum(actual.values()) == wb["packed_bytes"]


def test_packed_weight_bytes_static_act_and_bias(setup):
    """Calibrated artifacts (f32 scales, static act quantizers, corrected
    biases on the output projections) are counted exactly too."""
    import jax.numpy as jnp

    from repro.core import PTQConfig
    from repro.quant import calibrate_and_quantize
    from repro.quant.serve_packed import serving_params_from_quantized

    cfg, params = setup
    batches = [{"tokens": jax.random.randint(jax.random.key(3), (2, 16), 0, 128)}]
    qm = calibrate_and_quantize(params, cfg, batches, PTQConfig(algorithm="rtn"))
    sp = serving_params_from_quantized(qm)
    wb = packed_weight_bytes(cfg, scale_bytes_per=4, static_act=True,
                             with_bias=True)

    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, dict):
            if "packed" in node:
                total += sum(v.size * v.dtype.itemsize
                             for k, v in node.items() if k != "spec")
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(sp["layers"])
    assert total == wb["packed_bytes"], (total, wb["packed_bytes"])


def test_hybrid_family_packs_under_eval_shape():
    """Registry-driven packing covers the Jamba-style hybrid (mamba + moe +
    attn + mlp) that the hardcoded PACKABLE tuple used to reject."""
    cfg = get_smoke("jamba-1.5-large-398b")
    abstract = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.key(0))
    packed = jax.eval_shape(lambda p: pack_decode_params(p, cfg), abstract)
    # a mamba in_proj and a stacked moe expert weight both got packed
    slot0 = packed["layers"][0]
    assert "packed" in slot0["mixer"]["in_proj"]
    moe_slot = next(
        s for s, spec in zip(packed["layers"], cfg.pattern) if spec.ffn == "moe"
    )
    wd = moe_slot["ffn"]["wd"]
    assert wd["packed"].dtype == jnp.int8
    assert wd["packed"].shape[-2] * 2 == cfg.moe.d_ff_expert


def test_packed_ssm_forward_tracks_float():
    from repro.configs import get_config

    cfg = get_config("tiny-ssm")
    params = T.init_model(jax.random.key(0), cfg)
    pparams = pack_decode_params(params, cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab)}
    l_f, _ = T.forward(params, batch, cfg)
    l_q, _ = T.forward(pparams, batch, cfg)
    corr = float(jnp.corrcoef(l_f.ravel(), l_q.ravel())[0, 1])
    assert corr > 0.85, corr
    assert bool(jnp.all(jnp.isfinite(l_q)))


def test_vocab_padding_masks_pad_logits():
    cfg = get_smoke("smollm-360m").scaled(n_layers=1, vocab=100,
                                          vocab_pad_multiple=128)
    assert cfg.vocab_padded == 128
    params = T.init_model(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 8), 0, 100)}
    logits, _ = T.forward(params, batch, cfg)
    assert logits.shape[-1] == 128
    pad = np.asarray(logits[..., 100:])
    real = np.asarray(logits[..., :100])
    assert pad.max() < real.min()  # -inf-masked: never selected
