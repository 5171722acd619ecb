"""Mixture-of-Experts FFN with GShard-style grouped dispatch (top-k routing,
capacity-bounded, einsum dispatch/combine) — the classic TPU-shardable MoE
formulation (GShard arXiv:2006.16668, Switch arXiv:2101.03961).

Expert parallelism: the expert axis of the stacked weights is sharded over
the mesh's ``data`` axis when divisible (EP), with tensor parallelism over
``model`` inside each expert; XLA SPMD inserts the dispatch/combine
all-to-alls from the sharding constraints on the (E, G, C, D) tensors.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from .config import ModelConfig

# §Perf A/B switch: "1" restores the pre-iteration-2 sharding behavior
# (unconstrained dispatch tensors, expert-axis-or-nothing) for the
# EXPERIMENTS.md before/after measurements.
_PERF_BASELINE = os.environ.get("REPRO_PERF_BASELINE") == "1"


def init_moe(key, cfg: ModelConfig):
    mo = cfg.moe
    d, f, e = cfg.d_model, mo.d_ff_expert, mo.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    p = {"router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(dt)}
    if cfg.act == "swiglu":
        p["wg"] = (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dt)
        p["wu"] = (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dt)
    else:
        p["wi"] = (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dt)
    p["wd"] = (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dt)
    return p


def expert_capacity(cfg: ModelConfig, group: int) -> int:
    mo = cfg.moe
    c = int(math.ceil(group * mo.top_k * mo.capacity_factor / mo.n_experts))
    return max(4, min(c, group))


def _dispatch_combine(gates_topv, gates_topi, e: int, c: int):
    """Build (G, g, E, C) dispatch (0/1) and combine (gate-weighted) arrays.

    gates_topv/topi: (G, g, k). Token-major priority: earlier tokens in the
    group win capacity slots (standard GShard tie-break). Assignments beyond
    capacity are dropped (their gate mass is simply lost, as usual).
    """
    G, g, k = gates_topi.shape
    # (G, g, k, E) one-hot of expert choice
    onehot = jax.nn.one_hot(gates_topi, e, dtype=jnp.int32)
    # flatten (token, slot) in token-major order to rank assignments
    flat = onehot.reshape(G, g * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat  # rank within expert queue
    keep = (pos < c) & (flat > 0)
    pos = pos.reshape(G, g, k, e)
    keep = keep.reshape(G, g, k, e)
    # (G, g, k, E, C) one-hot position, reduced over the slot axis k so the
    # persistent tensors are only (G, g, E, C)
    pos_oh = jax.nn.one_hot(pos, c, dtype=gates_topv.dtype) * keep[..., None]
    combine = jnp.einsum("gsk,gskec->gsec", gates_topv, pos_oh)
    dispatch = (combine > 0).astype(gates_topv.dtype)
    return dispatch, combine


def route(router_w, x, cfg: ModelConfig):
    """Group tokens and build the routing tensors (shared by the float model
    and the PTQ families adapter, which must route identically).

    x: (B, S, d). Returns (xf (G, g, d), dispatch, combine, gates, topi, c).
    """
    mo = cfg.moe
    B, S, d = x.shape
    n_tok = B * S
    g = min(mo.group_size, n_tok)
    if n_tok % g:
        g = math.gcd(n_tok, g)
    G = n_tok // g
    c = expert_capacity(cfg, g)
    xf = x.reshape(G, g, d)

    logits = (xf @ router_w).astype(jnp.float32)  # (G, g, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, mo.top_k)  # (G, g, k)
    topv = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    dispatch, combine = _dispatch_combine(topv.astype(x.dtype), topi, mo.n_experts, c)
    return xf, dispatch, combine, gates, topi, c


def _expert_matmul(params, name, xe):
    """Per-expert stacked matmul ``einsum("egcd,edf->egcf")`` with packed
    dispatch: when the (E, K//2, N) leaf is a packed artifact and the W4A8
    kernel backend is active, vmap the fused kernel over the expert axis
    (activation quantization per expert — static when the leaf carries the
    calibrated stacked ``act_scale``/``act_zp``, dynamic otherwise)
    instead of dequantizing the whole expert stack in-graph."""
    from .layers import (
        is_dequant_site,
        is_packed,
        packed_backend,
        packed_linear,
        resolve_weight,
    )

    leaf = params[name]
    if not (is_packed(leaf) and packed_backend() != "dequant"):
        out = jnp.einsum("egcd,edf->egcf", xe,
                         resolve_weight(params, name, xe.shape[-1]))
        out = out.astype(xe.dtype)
        if (is_packed(leaf) or is_dequant_site(leaf)) and "bias" in leaf:
            # calibrated artifacts carry the bias-corrected bias (E, 1, C);
            # apply it here too so both backends compute the same function
            out = out + leaf["bias"][:, None].astype(out.dtype)
        return out
    E, G, C, D = xe.shape
    out = jax.vmap(packed_linear)(xe.reshape(E, G * C, D), leaf)
    return out.reshape(E, G, C, -1)


def moe(params, x, cfg: ModelConfig):
    """x: (B, S, d_model) -> (B, S, d_model), plus aux losses in out dict."""
    from .layers import constraint

    mo = cfg.moe
    B, S, d = x.shape
    xf, dispatch, combine, gates, topi, c = route(params["router"], x, cfg)
    # token-side tensors stay sharded with the tokens (unconstrained they
    # were replicated by SPMD -> TB-scale all-gathers; §Perf iteration 2)
    if not _PERF_BASELINE:
        dispatch = constraint(dispatch, ("batch", None, None, None))
        combine = constraint(combine, ("batch", None, None, None))

    from repro.runtime.sharding import prefer_expert_sharding

    if _PERF_BASELINE or prefer_expert_sharding(mo.n_experts):
        # EP: all-to-all from token-sharded G to expert-sharded E
        exp_names = ("expert", None, None, None)
        hid_names = ("expert", None, None, "ffn")
    else:
        # expert count does not divide the data axis (granite 40e on 16):
        # keep tokens sharded, experts via FSDP-gathered weights, no a2a
        exp_names = (None, "batch", None, None)
        hid_names = (None, "batch", None, "ffn")

    xe = jnp.einsum("gsec,gsd->egcd", dispatch, xf)
    xe = constraint(xe, exp_names)
    if cfg.act == "swiglu":
        h = jax.nn.silu(_expert_matmul(params, "wg", xe))
        h = h * _expert_matmul(params, "wu", xe)
    else:
        h = jax.nn.gelu(_expert_matmul(params, "wi", xe))
    h = constraint(h, hid_names)
    ye = _expert_matmul(params, "wd", h)
    ye = constraint(ye, exp_names)
    y = jnp.einsum("gsec,egcd->gsd", combine, ye)

    # Switch-style load-balance auxiliary loss
    density = jnp.mean(jax.nn.one_hot(topi[..., 0], mo.n_experts), axis=(0, 1))
    router_prob = jnp.mean(gates, axis=(0, 1))
    aux_loss = mo.n_experts * jnp.sum(density * router_prob)

    y = y.reshape(B, S, d).astype(x.dtype)
    return constraint(y, ("batch", None, "residual")), aux_loss
