"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to True off a TPU and False on one
(:func:`default_interpret`), so these wrappers run anywhere. They are off
the served path: ``repro.models.layers.packed_linear`` and the paged
engine pick the kernel or interpret mode from an explicit backend
(``packed_backend()``, ``resolve_paged_attn_impl``), never from this
default, so a served run on a machine without a TPU cannot silently
interpret the kernels it claims to compile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .gpfq_solve import gpfq_solve
from .quant_rmsnorm import quant_rmsnorm
from .w4a8_mm import pack_int4, unpack_int4, w4a8_decode_matmul, w4a8_matmul


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def quantize_activations(x: jax.Array):
    """Dynamic per-tensor asymmetric int8 activation quantization (the
    serving-path A8 half of W4A8 when no calibrated activation quantizer is
    attached to the artifact). Returns (codes uint8, scale f32, zp f32) —
    all traced, so the whole thing stays on device.
    """
    xf = x.astype(jnp.float32)
    lo = jnp.minimum(jnp.min(xf), 0.0)
    hi = jnp.maximum(jnp.max(xf), 0.0)
    scale = jnp.maximum((hi - lo) / 255.0, 1e-8)
    zp = jnp.clip(jnp.rint(-lo / scale), 0.0, 255.0)
    codes = jnp.clip(jnp.rint(xf / scale) + zp, 0.0, 255.0).astype(jnp.uint8)
    return codes, scale, zp


def quantized_linear_w4a8(
    x_codes: jax.Array,
    w_packed: jax.Array,
    w_scale: jax.Array,
    act_scale: float,
    act_zp: int,
    **kw,
):
    """Serving-path W4A8 linear: integer GEMM + dequant epilogue."""
    kw.setdefault("interpret", default_interpret())
    return w4a8_matmul(x_codes, w_packed, w_scale, act_scale, act_zp, **kw)


def norm_and_quantize(x, gamma, act_scale, act_zp, **kw):
    kw.setdefault("interpret", default_interpret())
    return quant_rmsnorm(x, gamma, act_scale, act_zp, **kw)


def gpfq_quantize_panel(w_int, xg, xh, lam, budget_b, **kw):
    kw.setdefault("interpret", default_interpret())
    return gpfq_solve(w_int, xg, xh, lam, budget_b, **kw)


__all__ = [
    "default_interpret",
    "gpfq_quantize_panel",
    "norm_and_quantize",
    "pack_int4",
    "quantize_activations",
    "quantized_linear_w4a8",
    "unpack_int4",
    "w4a8_decode_matmul",
    "w4a8_matmul",
]
