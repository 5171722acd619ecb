"""Device-resident decode datapath: fused W4A8 kernel dispatch through the
model forwards (dense / MoE / Mamba / xLSTM / hybrid), jaxpr hygiene (the
kernel path must never materialize the full bf16 weight), and the packed
artifact's pack-time ``col_sums`` term."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.models import transformer as T
from repro.models.layers import (
    packed_linear,
    use_packed_backend,
)
from repro.quant.serve_packed import _pack_leaf, pack_decode_params

FAMILY_ARCHS = ["tiny-moe", "tiny-ssm", "tiny-xlstm", "tiny-hybrid"]


def _corr(a, b) -> float:
    return float(jnp.corrcoef(jnp.ravel(a), jnp.ravel(b))[0, 1])


# ---------------------------------------------------------------------------
# Site-level dispatch
# ---------------------------------------------------------------------------
def test_packed_linear_kernel_matches_dequant(rng):
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    leaf = _pack_leaf(w)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.float32)
    with use_packed_backend("dequant"):
        yd = packed_linear(x, leaf)
    with use_packed_backend("interpret"):
        yk = packed_linear(x, leaf)
    assert yk.shape == yd.shape == (3, 5, 48)
    # only difference is the dynamic int8 activation quantization
    assert _corr(yd, yk) > 0.999


def test_packed_artifact_col_sums_matches_codes(rng):
    from repro.kernels.w4a8_mm import unpack_int4

    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    leaf = _pack_leaf(w)
    assert leaf["col_sums"].dtype == jnp.int32
    assert leaf["col_sums"].shape == (1, 24)
    # the codes are stored padded to whole 128-lane blocks with zero codes
    assert leaf["packed"].shape == (64, 128)
    expect = jnp.sum(unpack_int4(leaf["packed"]).astype(jnp.int32), axis=-2)
    np.testing.assert_array_equal(
        np.asarray(leaf["col_sums"][0]), np.asarray(expect[:24])
    )
    assert not np.asarray(expect[24:]).any()


def test_packed_linear_legacy_artifact_without_col_sums(rng):
    """Artifacts packed before this PR (no col_sums leaf) still dispatch."""
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    leaf = {k: v for k, v in _pack_leaf(w).items() if k != "col_sums"}
    x = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)
    with use_packed_backend("interpret"):
        yk = packed_linear(x, leaf)
    with use_packed_backend("dequant"):
        yd = packed_linear(x, leaf)
    assert _corr(yd, yk) > 0.999


def test_ensure_col_sums_fills_legacy_leaves(rng):
    """One-time load-path fix for legacy artifacts: missing col_sums leaves
    are filled (exactly), complete leaves and float leaves are untouched."""
    from repro.quant.serve_packed import ensure_col_sums

    full = _pack_leaf(jnp.asarray(rng.normal(size=(32, 16)), jnp.float32))
    legacy = {k: v for k, v in full.items() if k != "col_sums"}
    tree = {
        "layers": ({"mixer": {"wq": legacy, "wo": jnp.ones((4, 4))}},),
        "embedding": {"embed": jnp.ones((8, 4))},
    }
    fixed = ensure_col_sums(tree)
    got = fixed["layers"][0]["mixer"]["wq"]["col_sums"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full["col_sums"]))
    assert fixed["layers"][0]["mixer"]["wo"] is tree["layers"][0]["mixer"]["wo"]
    assert fixed["embedding"]["embed"] is tree["embedding"]["embed"]


def test_engine_backend_switch_retraces():
    """The resolved packed backend is part of the engine's jit cache key:
    switching backends between calls retraces instead of silently reusing
    the previously compiled datapath."""
    from repro.serving import GenerationEngine, SamplerConfig

    cfg = get_smoke("smollm-360m").scaled(n_layers=1, vocab=64)
    params = T.init_model(jax.random.key(0), cfg)
    pparams = pack_decode_params(params, cfg)
    prompts = np.random.default_rng(0).integers(0, 64, size=(2, 4)).astype(np.int32)
    eng = GenerationEngine(pparams, cfg, SamplerConfig(temperature=0.0))
    with use_packed_backend("dequant"):
        eng.generate(prompts, 2)
        eng.generate(prompts, 2)
    assert eng.gen_traces == 1
    with use_packed_backend("interpret"):
        eng.generate(prompts, 2)  # same shapes, new backend -> new trace
    assert eng.gen_traces == 2
    with use_packed_backend("dequant"):
        eng.generate(prompts, 2)  # first backend's compile is still cached
    assert eng.gen_traces == 2


# ---------------------------------------------------------------------------
# Jaxpr hygiene: the kernel path must not dequantize the full weight
# ---------------------------------------------------------------------------
def _all_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for x in vals:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    _all_eqns(inner, out)
    return out


def test_kernel_path_jaxpr_has_no_full_weight_dequant(rng):
    """On the kernel path the packed codes are only ever touched inside the
    pallas call, block by block: no (K, N)-shaped tensor — float dequant or
    int unpack — may appear anywhere in the jaxpr. (The dequant fallback
    does produce one; that asserts the detector actually detects.)"""
    K, N = 256, 256
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    leaf = _pack_leaf(w)
    x = jnp.asarray(rng.normal(size=(4, K)), jnp.float32)

    def full_weight_eqns(backend):
        with use_packed_backend(backend):
            # fresh lambda: make_jaxpr caches traces per function object,
            # which would hide the backend switch
            jaxpr = jax.make_jaxpr(lambda a, l: packed_linear(a, l))(x, leaf).jaxpr
        eqns = _all_eqns(jaxpr, [])
        hits = [
            e for e in eqns
            for ov in e.outvars
            if getattr(ov.aval, "shape", None) == (K, N)
        ]
        has_pallas = any("pallas" in e.primitive.name for e in eqns)
        return hits, has_pallas

    hits, has_pallas = full_weight_eqns("interpret")
    assert has_pallas, "kernel path must lower to a pallas_call"
    assert not hits, f"full-weight tensors on the kernel path: {hits}"

    hits_dq, _ = full_weight_eqns("dequant")
    assert hits_dq, "detector sanity: dequant fallback materializes (K, N)"


# ---------------------------------------------------------------------------
# Family coverage: packed decode rides the integer datapath everywhere
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_decode_step_kernel_vs_dequant(arch):
    """decode_step with packed params: fused-kernel (interpret) logits track
    the in-graph dequant fallback on every family tiny config."""
    cfg = get_config(arch)
    params = T.init_model(jax.random.key(0), cfg)
    pparams = pack_decode_params(params, cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)}
    tok = jnp.ones((2, 1), jnp.int32)
    outs = {}
    for backend in ("dequant", "interpret"):
        with use_packed_backend(backend):
            _, cache = T.prefill(pparams, batch, cfg, max_len=12)
            logits, _ = T.decode_step(pparams, tok, cache, jnp.int32(8), cfg)
            outs[backend] = logits
    c = _corr(outs["dequant"], outs["interpret"])
    assert c > 0.99, (arch, c)
    assert bool(jnp.all(jnp.isfinite(outs["interpret"])))


def test_dense_prefill_kernel_vs_dequant():
    """The prefill-shaped path (M = B*S, ragged) through the same dispatch."""
    cfg = get_smoke("smollm-360m").scaled(n_layers=2, vocab=128)
    params = T.init_model(jax.random.key(0), cfg)
    pparams = pack_decode_params(params, cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (3, 7), 0, 128)}
    with use_packed_backend("dequant"):
        ld, _ = T.forward(pparams, batch, cfg)
    with use_packed_backend("interpret"):
        lk, _ = T.forward(pparams, batch, cfg)
    assert _corr(ld, lk) > 0.99


def test_fused_generate_on_kernel_backend():
    """End to end: the on-device generation loop with every packed matmul
    dispatched to the (interpret-mode) W4A8 kernel."""
    from repro.serving import GenerationEngine, SamplerConfig

    cfg = get_smoke("smollm-360m").scaled(n_layers=1, vocab=64)
    params = T.init_model(jax.random.key(0), cfg)
    pparams = pack_decode_params(params, cfg)
    prompts = np.random.default_rng(0).integers(0, 64, size=(2, 4)).astype(np.int32)
    eng = GenerationEngine(pparams, cfg, SamplerConfig(temperature=0.0))
    with use_packed_backend("interpret"):
        out_k = eng.generate(prompts, 3)
    assert out_k.shape == (2, 7)
    eng_d = GenerationEngine(pparams, cfg, SamplerConfig(temperature=0.0))
    with use_packed_backend("dequant"):
        out_d = eng_d.generate(prompts, 3)
    # greedy argmax over near-identical logits: tokens rarely diverge on a
    # 3-token horizon; require exact prompt echo + valid token range
    np.testing.assert_array_equal(out_k[:, :4], prompts)
    assert out_d.shape == out_k.shape
