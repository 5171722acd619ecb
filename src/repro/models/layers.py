"""Core layers: norms, RoPE, GQA attention (full / online-softmax chunked /
decode), MLPs, embeddings. Pure-JAX pytree parameters (dicts of arrays) —
no framework dependency, fully shardable under pjit.

Activation sharding annotations go through
:func:`repro.runtime.sharding.logical_constraint` so the same model code runs
single-device (tests) and on the production mesh (dry-run / launcher).
"""

from __future__ import annotations

import math
import os
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp

from .config import ModelConfig


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Packed-weight matmul dispatch (the W4A8 serving datapath)
# ---------------------------------------------------------------------------
# Backends:
#   dequant   — unpack int4 -> bf16 in-graph, then a dense matmul. The
#               CPU / interpretability fallback (and the pre-kernel
#               behavior); XLA fuses the unpack into the consumer, but the
#               weights still transit the matmul at full width.
#   kernel    — the fused repro.kernels.w4a8_mm Pallas datapath: dynamic
#               int8 activation quantization + packed-int4 integer GEMM
#               with dequant fused in the epilogue. TPU only.
#   interpret — the kernel path with pallas interpret=True: exact same
#               graph/dataflow, runs anywhere (tests, CPU validation).
#   auto      — kernel on TPU, dequant elsewhere (the default).
_PACKED_BACKENDS = ("auto", "dequant", "kernel", "interpret")
_packed_state = threading.local()


def packed_backend() -> str:
    """Resolve the active packed-matmul backend to a concrete one."""
    mode = getattr(_packed_state, "override", None) or os.environ.get(
        "REPRO_PACKED_BACKEND", "auto"
    )
    if mode not in _PACKED_BACKENDS:
        raise ValueError(f"packed backend {mode!r} not in {_PACKED_BACKENDS}")
    if mode == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "dequant"
    return mode


@contextmanager
def use_packed_backend(mode: str):
    """Force a packed-matmul backend for the enclosed trace (tests/benches)."""
    prev = getattr(_packed_state, "override", None)
    _packed_state.override = mode
    try:
        yield
    finally:
        _packed_state.override = prev


# ---------------------------------------------------------------------------
# Serving-side observation (saturation counters)
# ---------------------------------------------------------------------------
# Off-hot-path observer seam: when BOTH an observer is attached
# (attach_observer) AND a site scope is active (site_scope — the paged
# decode body sets one per pattern slot), pmm reports each packed site's
# static-quantizer clip count and activation-code extrema to the observer
# through jax.debug.callback. With no observer attached (the default) the
# checks are plain-Python None tests at trace time: the serving jaxpr is
# byte-identical — asserted by PagedEngine.assert_observation_transparent.
# Prefill/admit traces never set a scope, so they stay clean even while
# observing (the counters are a *decode* telemetry channel).
_observe_state = threading.local()


def active_observer():
    """The attached SaturationCounters-like observer, or None."""
    return getattr(_observe_state, "observer", None)


@contextmanager
def attach_observer(obs):
    """Attach a serving observer (repro.quant.observe.SaturationCounters)
    for the enclosed traces/executions."""
    prev = getattr(_observe_state, "observer", None)
    _observe_state.observer = obs
    try:
        yield
    finally:
        _observe_state.observer = prev


@contextmanager
def site_scope(label: str):
    """Name the current component ("slot0/mixer") so packed sites report
    under slot-granular labels matching the mixed-precision plan keys. The
    label is also a ``jax.named_scope``: the component's ops carry it in
    their name stack, and so in a profiler trace."""
    prev = getattr(_observe_state, "scope", None)
    _observe_state.scope = label
    try:
        with jax.named_scope(label):
            yield
    finally:
        _observe_state.scope = prev


def _record_site_observation(obs, label: str, x, leaf) -> None:
    """Emit one site's observation into the traced graph: static-quantizer
    pre-clip count + code extrema, delivered host-side via debug.callback
    (nothing heavier — watermark math runs at report time)."""
    from functools import partial

    spec = leaf_spec(leaf)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    if spec.static_act and "act_scale" in leaf:
        from repro.core.alphabet import act_alphabet

        alpha = act_alphabet(spec.act_bits, signed=spec.act_signed)
        scale = leaf["act_scale"].astype(jnp.float32).reshape(())
        zp = leaf["act_zp"].astype(jnp.float32).reshape(())
        raw = jnp.rint(x2 / scale) + zp
        n_clip = jnp.sum(((raw < alpha.qmin) | (raw > alpha.qmax)).astype(jnp.int32))
        codes = jnp.clip(raw, alpha.qmin, alpha.qmax)
    else:
        from repro.kernels.ops import quantize_activations

        codes, _, _ = quantize_activations(x2)
        codes = codes.astype(jnp.float32)
        n_clip = jnp.zeros((), jnp.int32)
    jax.debug.callback(
        partial(obs.record, label, int(x2.size)),
        n_clip, jnp.min(codes), jnp.max(codes),
    )


def is_packed(v) -> bool:
    return isinstance(v, dict) and "packed" in v


def is_dequant_site(v) -> bool:
    """A high-precision site leaf from a calibrated artifact whose codes
    have no int4 container (w_bits > 4 / odd K): {"w": dequantized weight,
    "bias": corrected bias}. Serves in float, but keeps the bias-corrected
    function the certificate was issued for."""
    return isinstance(v, dict) and "w" in v and "packed" not in v


def dequant_weight(leaf, k: int):
    """In-graph dequantization of a packed leaf (the fallback datapath) to
    its logical (..., k, N) weight: dense codes are stored zero-padded to
    whole 128-lane blocks, so the rows past the activation's depth ``k`` and
    the columns past the ``scale`` leaf's N are sliced off.

    2:4 sparse-compressed leaves (a ``meta`` index leaf beside the packed
    codes) expand through the gather reference — bit-identical integer
    codes to the dense-with-zeros layout they were compressed from."""
    from repro.kernels.w4a8_mm import unpack_int4, unpack_sparse24

    if "meta" in leaf:
        q = unpack_sparse24(leaf["packed"], leaf["meta"])
    else:
        q = unpack_int4(leaf["packed"])
    q = q[..., :k, :leaf["scale"].shape[-1]]
    return q.astype(leaf["scale"].dtype) * leaf["scale"]


def leaf_spec(leaf):
    """The :class:`~repro.quant.spec.DatapathSpec` governing a packed leaf.

    Trace-safe: only the static ``spec`` node is consulted (the ``spec_arr``
    array twin is for persistence — decode it outside traces via
    ``repro.quant.spec.leaf_datapath`` / ``serve_packed.ensure_datapath_spec``).
    Legacy leaves without a spec get the default recipe datapath, which is
    exactly the behavior they were packed under.
    """
    from repro.quant.spec import DatapathSpec

    spec = leaf.get("spec")
    return spec if spec is not None else DatapathSpec()


def _static_act_codes(x2, leaf, spec):
    """Activation codes from the artifact's calibrated static quantizer —
    pure elementwise ops, no data-dependent max/min reduction in the graph
    (the serving-time half of the end-to-end certificate). The code range
    comes from the same alphabet the certificate math used
    (repro.core.alphabet), so serving cannot diverge from certification."""
    from repro.core.alphabet import act_alphabet

    scale = leaf["act_scale"].astype(jnp.float32).reshape(())
    zp = leaf["act_zp"].astype(jnp.float32).reshape(())
    alpha = act_alphabet(spec.act_bits, signed=spec.act_signed)
    codes = jnp.clip(jnp.rint(x2.astype(jnp.float32) / scale) + zp,
                     alpha.qmin, alpha.qmax)
    return codes.astype(jnp.int8 if spec.act_signed else jnp.uint8), scale, zp


def packed_linear(x, leaf, *, spec=None, assert_inner: bool = False):
    """x: (..., K) @ packed leaf (K//2, N) -> (..., N), dispatched to the
    fused W4A8 kernel (kernel/interpret backends) or the in-graph dequant
    fallback. The kernel path never materializes the full bf16 weight: the
    zero-point ``col_sums`` term comes precomputed from the packed artifact
    and the int4 codes are unpacked block-by-block inside the epilogue.

    The accumulation datapath — K-tile size T, inner width P_I — and the
    activation quantizer come from the leaf's embedded
    :class:`~repro.quant.spec.DatapathSpec`, NOT from kwargs: the artifact
    is the single source of truth for what was certified. Passing ``spec``
    here is a *request*, and a request that disagrees with the artifact
    raises :class:`~repro.quant.spec.DatapathMismatchError` instead of
    silently preferring either side. When the artifact carries calibrated
    ``act_scale``/``act_zp`` leaves, activations are quantized statically
    (no dynamic per-tensor max reduction in the serving graph); otherwise
    the dynamic ``quantize_activations`` fallback runs.

    The P_I bound is only a *guarantee* for AXE-constrained codes
    (launch.quantize artifacts) — RTN-packed leaves carry no l1 budget and
    can trip ``assert_inner``. NOTE: the backend and the spec are read at
    trace time; any jit wrapping this must put the resolved
    ``packed_backend()`` and the tree's datapath fingerprint in its cache
    key (GenerationEngine does) or retrace when either changes.
    """
    embedded = leaf.get("spec")
    if spec is not None and embedded is not None:
        embedded.require_matches(spec, context="packed_linear")
    resolved = embedded if embedded is not None else spec
    if resolved is None:
        resolved = leaf_spec(leaf)

    # A 2:4-compressed leaf carries a "meta" index leaf; the spec and the
    # leaf layout must agree or the decode would silently mis-expand.
    if (resolved.sparsity is not None) != ("meta" in leaf):
        from repro.quant.spec import DatapathMismatchError

        raise DatapathMismatchError(
            "packed_linear: datapath field 'sparsity' disagrees with the leaf "
            f"layout (spec sparsity={resolved.sparsity!r}, leaf "
            f"{'carries' if 'meta' in leaf else 'lacks'} a 2:4 metadata leaf)"
        )

    backend = packed_backend()
    if backend == "dequant":
        # calibrated leaves carry f32 scales: the product comes back in the
        # activation dtype, as on the kernel path
        y = (x @ dequant_weight(leaf, x.shape[-1])).astype(x.dtype)
        if "bias" in leaf:
            y = y + leaf["bias"].reshape(-1).astype(y.dtype)
        return y

    from repro.kernels.w4a8_mm import (
        datapath_kernel_args,
        unpack_int4,
        unpack_sparse24,
        w4a8_decode_matmul,
        w4a8_sparse_decode_matmul,
    )

    *lead, k = x.shape
    x2 = x.reshape(-1, k)
    if resolved.static_act and "act_scale" in leaf:
        codes, act_scale, act_zp = _static_act_codes(x2, leaf, resolved)
    else:
        from repro.kernels.ops import quantize_activations

        codes, act_scale, act_zp = quantize_activations(x2)
    col_sums = leaf.get("col_sums")
    if col_sums is None:  # legacy artifact without the pack-time term
        if "meta" in leaf:
            col_sums = jnp.sum(
                unpack_sparse24(leaf["packed"], leaf["meta"]).astype(jnp.int32),
                axis=-2,
            )
        else:
            col_sums = jnp.sum(
                unpack_int4(leaf["packed"]).astype(jnp.int32), axis=-2
            )
    if "meta" in leaf:
        y = w4a8_sparse_decode_matmul(
            codes,
            leaf["packed"],
            leaf["meta"],
            leaf["scale"].reshape(-1).astype(jnp.float32),
            col_sums.reshape(-1),
            act_scale,
            act_zp,
            **datapath_kernel_args(resolved),
            assert_inner=assert_inner,
            interpret=(backend == "interpret"),
            out_dtype=x.dtype,
        )
    else:
        y = w4a8_decode_matmul(
            codes,
            leaf["packed"],
            leaf["scale"].reshape(-1).astype(jnp.float32),
            col_sums.reshape(-1),
            act_scale,
            act_zp,
            **datapath_kernel_args(resolved),
            assert_inner=assert_inner,
            interpret=(backend == "interpret"),
            out_dtype=x.dtype,
        )
    y = y.reshape(*lead, y.shape[-1])
    if "bias" in leaf:
        y = y + leaf["bias"].reshape(-1).astype(y.dtype)
    return y


def pmm(params, name, x):
    """Packed-aware matmul: ``x @ params[name]`` with transparent dispatch
    when the leaf is a packed-int4 serving artifact. The single seam every
    quantizable-site matmul in the model forwards goes through — which is
    what routes dense, MoE, Mamba and xLSTM packed decode onto the integer
    datapath at once."""
    v = params[name]
    if is_packed(v):
        obs = active_observer()
        scope = getattr(_observe_state, "scope", None)
        if obs is not None and scope is not None:
            _record_site_observation(obs, f"{scope}.{name}", x, v)
        return packed_linear(x, v)
    if is_dequant_site(v):
        y = x @ v["w"]
        if "bias" in v:
            y = y + v["bias"].reshape(-1).astype(y.dtype)
        return y
    return x @ v


def constraint(x, names):
    """Logical sharding constraint (no-op without an active mesh)."""
    from repro.runtime.sharding import logical_constraint

    return logical_constraint(x, names)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(cfg: ModelConfig):
    p = {"w": jnp.ones((cfg.d_model,), _dtype(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((cfg.d_model,), _dtype(cfg.param_dtype))
    return p


def norm(params, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        out = xf * scale * params["w"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * params["w"].astype(jnp.float32) + params["b"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA)
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "wq": (jax.random.normal(ks[0], (d, nh * hd)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, nkv * hd)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, nkv * hd)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (nh * hd, d)) * s).astype(dt),
    }


def resolve_weight(params, name, k: int):
    """Weight accessor that transparently dequantizes packed-int4 leaves
    (the W4A8 serving artifact — see repro.quant.serve_packed) to the
    logical reduction depth ``k``. Call sites
    that are plain matmuls should prefer :func:`pmm`, which can route the
    packed leaf through the fused w4a8_mm kernel instead of materializing
    the full-width weight; resolve_weight remains for consumers that need
    the dense array (einsums, analysis, the dequant fallback)."""
    v = params[name]
    if is_packed(v):
        return dequant_weight(v, k)
    if is_dequant_site(v):
        # NOTE: the dense weight only — callers needing the corrected bias
        # (pmm, moe._expert_matmul) apply it at the matmul
        return v["w"]
    return v


def _qkv(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = pmm(params, "wq", x).reshape(B, S, nh, hd)
    k = pmm(params, "wk", x).reshape(B, S, nkv, hd)
    v = pmm(params, "wv", x).reshape(B, S, nkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constraint(q, ("batch", None, "heads", None))
    k = constraint(k, ("batch", None, "kv_heads", None))
    v = constraint(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _softcap(scores, cap):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _full_causal_attention(q, k, v, cfg: ModelConfig):
    """Materialized causal attention (S <= attn_chunk_threshold).

    ``k``/``v`` may carry ``T >= S`` positions: the leading ``T - S`` keys
    are a *prefix context* every query attends to (the shared-prefix
    suffix-prefill path — see :func:`attention`'s ``prefix_kv``); query
    row ``i`` sits at absolute position ``(T - S) + i``, so the mask is
    the usual causal triangle shifted by the prefix length. ``T == S``
    reduces to the plain causal mask."""
    B, S, nh, hd = q.shape
    T = k.shape[1]
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(B, S, nkv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = _softcap(scores / math.sqrt(hd), cfg.attn_logit_softcap)
    causal = jnp.arange(T)[None, :] <= (jnp.arange(S) + (T - S))[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, S, nh, hd)


def _chunked_causal_attention(q, k, v, cfg: ModelConfig):
    """Online-softmax attention, scanned over KV chunks — O(S * chunk)
    peak memory instead of O(S^2). The pure-JAX flash-attention analogue
    (the TPU-kernel version of this belongs in repro.kernels if attention
    ever becomes the quantization target; for this paper it is not)."""
    B, S0, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    chunk = cfg.attn_chunk
    pad = (-S0) % chunk
    if pad:  # ragged tail: causal mask keeps padded KV unattended
        padding = [(0, 0), (0, pad), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(t, padding) for t in (q, k, v))
    S = S0 + pad
    n_chunks = S // chunk
    qg = q.reshape(B, S, nkv, g, hd)
    k_ch = k.reshape(B, n_chunks, chunk, nkv, hd).transpose(1, 0, 2, 3, 4)
    v_ch = v.reshape(B, n_chunks, chunk, nkv, hd).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(S)

    def body(carry, kv):
        m, l, acc, idx = carry
        kc, vc = kv  # (B, chunk, nkv, hd)
        kv_pos = idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc).astype(jnp.float32)
        s = _softcap(s / math.sqrt(hd), cfg.attn_logit_softcap)
        mask = q_pos[:, None] >= kv_pos[None, :]  # (S, chunk)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (future chunks): keep m finite
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(q.dtype), vc
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new, idx + 1), None

    m0 = jnp.full((B, nkv, g, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, nkv, g, S), jnp.float32)
    acc0 = jnp.zeros((B, nkv, g, S, hd), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, acc0, 0), (k_ch, v_ch))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, nh, hd)
    return out[:, :S0].astype(q.dtype)


def attention(params, x, cfg: ModelConfig, positions, prefix_kv=None):
    """Training / prefill attention. Returns (y, (k, v)) — k/v for caching.

    ``prefix_kv``: optional ``(prefix_k, prefix_v)`` of shape
    ``(B, L, nkv, hd)`` — already-RoPE'd KV for a cached prompt prefix
    (the prefix-cache suffix-prefill path). Queries attend over
    ``concat(prefix, suffix)`` with the rectangular causal mask;
    ``positions`` must then carry the absolute offsets (``L + i``). The
    returned ``(k, v)`` stay suffix-only — that is what gets scattered
    into fresh pages (the prefix pages already exist and are shared)."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, x, cfg, positions)
    if prefix_kv is not None:
        pk, pv = prefix_kv
        k_all = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        # suffixes are short by construction (the cached prefix absorbed
        # the bulk); the materialized rectangular path is the right tool
        out = _full_causal_attention(q, k_all, v_all, cfg)
    elif S > cfg.attn_chunk_threshold:
        out = _chunked_causal_attention(q, k, v, cfg)
    else:
        out = _full_causal_attention(q, k, v, cfg)
    y = pmm(params, "wo", out.reshape(B, S, cfg.n_heads * cfg.head_dim))
    return constraint(y, ("batch", None, "residual")), (k, v)


_PAGED_ATTN_IMPLS = ("auto", "ref", "kernel", "interpret")


def resolve_paged_attn_impl(impl: str = "auto") -> str:
    """Resolve the paged decode-attention implementation: the Pallas
    block-table kernel on TPU, the gather reference elsewhere. "interpret"
    runs the kernel path under pallas interpret mode (tests/validation)."""
    if impl not in _PAGED_ATTN_IMPLS:
        raise ValueError(f"paged attn impl {impl!r} not in {_PAGED_ATTN_IMPLS}")
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    return impl


def _append_kv_page_quant(pages, scales, page, off, x, kv_bits: int = 8):
    """Quantize-on-append into an int8 page pool with per-(page, kv-head)
    scales. ``x``: (B, nkv, hd) — the new token's K or V rows, landing at
    ``(page[b], off[b])``. The page scale *grows monotonically*: when the
    new token's magnitude exceeds the page's current scale, the existing
    codes rescale in place (one bounded extra rounding of at most half a
    step at the new scale); an ``off == 0`` write is the page's first
    token (fresh or recycled), so the stale scale resets — whatever codes
    the page held belong to a freed sequence and are past-length-masked
    anyway. Inactive rows carry the ``page >= num_blocks`` sentinel: the
    whole page/scale write drops, so idle slots never corrupt live pages.
    """
    qmax = 2 ** (kv_bits - 1) - 1
    nb, bs = pages.shape[0], pages.shape[1]
    p_idx = jnp.minimum(page, nb - 1)
    old = jnp.where((off == 0)[:, None], 0.0, scales[p_idx])  # (B, nkv)
    tok = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / qmax  # (B, nkv)
    new = jnp.maximum(jnp.maximum(old, tok), 1e-8)
    codes = pages[p_idx].astype(jnp.float32)  # (B, bs, nkv, hd)
    codes = jnp.rint(codes * (old / new)[:, None, :, None])
    tok_codes = jnp.rint(x.astype(jnp.float32) / new[..., None])  # (B, nkv, hd)
    sel = (jnp.arange(bs)[None, :] == off[:, None])[..., None, None]
    codes = jnp.clip(jnp.where(sel, tok_codes[:, None], codes), -qmax, qmax)
    pages = pages.at[page].set(codes.astype(pages.dtype), mode="drop")
    scales = scales.at[page].set(new, mode="drop")
    return pages, scales


def _append_kv_page_static(pages, scales, page, off, x, scale_static):
    """Append into an int8 page pool under *calibrated static* per-kv-head
    scales (``scale_static``: (nkv,) f32 — see repro.quant.observe.kv).

    The requantize-on-append machinery of :func:`_append_kv_page_quant` is
    gone: no scale growth, no in-place rescale of existing codes, every
    code rounded exactly once. The page's scale leaf is stamped with the
    static value so gather/dequant consumers (and the quantized attention
    kernel) read the pool identically to the dynamic path. Codes hard-clip
    at the int8 container limit: out-of-calibration drift saturates (the
    serving saturation counters measure it) instead of overflowing, so the
    8-bit :class:`~repro.quant.spec.AttnDatapathSpec` bound still holds.
    Inactive rows use the same ``page >= num_blocks`` drop sentinel.
    """
    nb = pages.shape[0]
    qmax = 127  # int8 container limit (alphabet may be coarser via scale)
    tok_codes = jnp.clip(
        jnp.rint(x.astype(jnp.float32) / scale_static[None, :, None]),
        -qmax, qmax,
    )  # (B, nkv, hd)
    pages = pages.at[page, off].set(tok_codes.astype(pages.dtype), mode="drop")
    stamp = jnp.broadcast_to(scale_static[None, :], (x.shape[0], x.shape[1]))
    scales = scales.at[page].set(stamp, mode="drop")
    return pages, scales


def paged_attention_decode(params, x, cfg: ModelConfig, pool,
                           block_table, seq_lens, active, *,
                           impl: str = "ref", attn_spec=None,
                           static_kv_scales=None):
    """Single-token decode against a *paged* KV cache.

    x: (B, 1, d) — B is the engine's slot count. ``pool`` is the layer's
    page-pool dict: ``k_pages``/``v_pages`` are
    ``(num_blocks, block_size, nkv, hd)`` (``cfg.act_dtype`` float, or
    int8 codes when the pool also carries ``k_scales``/``v_scales``
    per-(page, kv-head) scale leaves — the quantized layout of
    ``init_paged_cache(kv_dtype="int8")``); ``block_table`` (B, P) int32
    maps logical pages to pool pages (entries ``>= num_blocks`` are
    free-slot sentinels); ``seq_lens`` (B,) int32 is each slot's current
    length — the new token's KV lands at logical position ``seq_lens[b]``
    and attention covers positions ``<= seq_lens[b]``. ``active`` (B,)
    bool masks the page write for idle slots (their table rows may point
    at pages since re-allocated to other sequences — the write is routed
    out of bounds and dropped, so an idle slot can never corrupt a live
    one). Idle rows still produce (garbage) outputs; the engine discards
    them. ``attn_spec`` is the optional
    :class:`~repro.quant.spec.AttnDatapathSpec` request forwarded to the
    quantized kernel for validation against the pool layout.
    ``static_kv_scales``: optional ``{"k": (nkv,), "v": (nkv,)}`` f32 —
    calibrated static page scales from a mixed-precision plan; appends
    then take the :func:`_append_kv_page_static` path (no requantize-on-
    append). Only valid for quantized pools.

    Returns (y, new_pool).
    """
    from repro.kernels.paged_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    B = x.shape[0]
    positions = seq_lens[:, None]  # (B, 1) — per-slot RoPE positions
    q, k, v = _qkv(params, x, cfg, positions)
    k_pages, v_pages = pool["k_pages"], pool["v_pages"]
    quantized = "k_scales" in pool
    nb, bs = k_pages.shape[0], k_pages.shape[1]
    if attn_spec is not None:
        # validate the request against the pool-derived record on EVERY
        # impl (the gather reference included) — a disagreeing record must
        # raise here too, never silently serve (the validate_datapath
        # contract; float pools count as "no record")
        from repro.quant.spec import AttnDatapathSpec, validate_attn_datapath

        derived = (
            AttnDatapathSpec.for_cache(
                cfg.head_dim, bs, kv_bits=8 * k_pages.dtype.itemsize)
            if quantized else None
        )
        validate_attn_datapath(derived, attn_spec)
    page = jnp.where(active, block_table[jnp.arange(B), seq_lens // bs], nb)
    off = seq_lens % bs
    with jax.named_scope("kv_append"):
        if quantized:
            if static_kv_scales is not None:
                k_pages, k_scales = _append_kv_page_static(
                    k_pages, pool["k_scales"], page, off, k[:, 0],
                    static_kv_scales["k"])
                v_pages, v_scales = _append_kv_page_static(
                    v_pages, pool["v_scales"], page, off, v[:, 0],
                    static_kv_scales["v"])
            else:
                k_pages, k_scales = _append_kv_page_quant(
                    k_pages, pool["k_scales"], page, off, k[:, 0])
                v_pages, v_scales = _append_kv_page_quant(
                    v_pages, pool["v_scales"], page, off, v[:, 0])
            new_pool = {"k_pages": k_pages, "v_pages": v_pages,
                        "k_scales": k_scales, "v_scales": v_scales}
            scale_kw = {"k_scales": k_scales, "v_scales": v_scales}
        else:
            k_pages = k_pages.at[page, off].set(
                k[:, 0].astype(k_pages.dtype), mode="drop")
            v_pages = v_pages.at[page, off].set(
                v[:, 0].astype(v_pages.dtype), mode="drop")
            new_pool = {"k_pages": k_pages, "v_pages": v_pages}
            scale_kw = {}
    lens_now = seq_lens + 1  # attend over positions < lens_now (self incl.)
    if impl == "ref":
        out = paged_attention_reference(
            q[:, 0], k_pages, v_pages, block_table, lens_now,
            softcap=cfg.attn_logit_softcap, **scale_kw,
        )
    else:
        out = paged_decode_attention(
            q[:, 0], k_pages, v_pages, block_table, lens_now,
            softcap=cfg.attn_logit_softcap, attn_spec=attn_spec,
            interpret=(impl == "interpret"), **scale_kw,
        )
    y = pmm(params, "wo", out.reshape(B, 1, cfg.n_heads * cfg.head_dim))
    return y, new_pool


def attention_decode(params, x, cfg: ModelConfig, cache_k, cache_v, index):
    """Single-token decode against a (B, S_max, nkv, hd) KV cache.

    ``index``: scalar int32 — current position (cache fill level).
    Returns (y, new_k, new_v).
    """
    B, S1, _ = x.shape  # S1 == 1
    positions = jnp.full((B, S1), index, jnp.int32)
    q, k, v = _qkv(params, x, cfg, positions)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, index, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, index, 0, 0))
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = nh // nkv
    qg = q.reshape(B, nkv, g, hd)  # S1 == 1 squeezed
    s = jnp.einsum("bkgd,bskd->bkgs", qg, cache_k).astype(jnp.float32)
    s = _softcap(s / math.sqrt(hd), cfg.attn_logit_softcap)
    valid = jnp.arange(cache_k.shape[1])[None, :] <= index  # (1, S_max)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p, cache_v)
    y = pmm(params, "wo", out.reshape(B, 1, nh * hd))
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = _dtype(cfg.param_dtype)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "wg": (jax.random.normal(ks[0], (d, f)) * s_in).astype(dt),
            "wu": (jax.random.normal(ks[1], (d, f)) * s_in).astype(dt),
            "wd": (jax.random.normal(ks[2], (f, d)) * s_out).astype(dt),
        }
    return {
        "wi": (jax.random.normal(ks[0], (d, f)) * s_in).astype(dt),
        "wd": (jax.random.normal(ks[1], (f, d)) * s_out).astype(dt),
    }


def mlp(params, x, cfg: ModelConfig):
    if cfg.act == "swiglu":
        h = jax.nn.silu(pmm(params, "wg", x)) * pmm(params, "wu", x)
    else:
        h = jax.nn.gelu(pmm(params, "wi", x))
    h = constraint(h, ("batch", None, "ffn"))
    return constraint(pmm(params, "wd", h), ("batch", None, "residual"))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def init_embedding(key, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 2)
    v = cfg.vocab_padded  # padded so the vocab dim always TP-shards
    p = {"embed": (jax.random.normal(ks[0], (v, cfg.d_model)) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["head"] = (
            jax.random.normal(ks[1], (v, cfg.d_model)) / math.sqrt(cfg.d_model)
        ).astype(dt)
    return p


def embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constraint(x, ("batch", None, "residual"))


def lm_logits(params, x, cfg: ModelConfig):
    head = params.get("head", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", x, head)
    if cfg.vocab_padded != cfg.vocab:
        # mask pad rows so softmax/logsumexp are exact over the real vocab
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.finfo(logits.dtype).min, logits)
    return constraint(logits, ("batch", None, "vocab"))
