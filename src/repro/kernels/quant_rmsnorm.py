"""Fused RMSNorm + asymmetric int8 activation quantization.

In the W4A8 serving path every norm output is immediately quantized to int8
codes (paper §C.1: per-tensor asymmetric activations). Fusing the norm with
the quantizer keeps the fp32 intermediate in VMEM and writes only the 1-byte
codes back to HBM — a 4x cut of the layer-boundary write traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, g_ref, qp_ref, out_ref, *, eps: float, qmax: int):
    x = x_ref[...].astype(jnp.float32)  # (bm, D)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * g_ref[...].astype(jnp.float32)
    inv_scale, zp = qp_ref[0, 0], qp_ref[0, 1]
    q = jnp.rint(y * inv_scale) + zp
    out_ref[...] = jnp.clip(q, 0, qmax).astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("block_m", "bits", "eps", "interpret")
)
def quant_rmsnorm(
    x: jax.Array,  # (M, D)
    gamma: jax.Array,  # (D,)
    act_scale: float,
    act_zp: int,
    *,
    block_m: int = 256,
    bits: int = 8,
    eps: float = 1e-6,
    interpret: bool = False,
):
    m, d = x.shape
    assert m % block_m == 0, (m, block_m)
    kernel = functools.partial(_kernel, eps=eps, qmax=2**bits - 1)
    qp = jnp.stack(
        [1.0 / jnp.asarray(act_scale, jnp.float32), jnp.asarray(act_zp, jnp.float32)]
    )[None, :]  # (1, 2) quantizer params (traced-safe)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, gamma[None, :], qp)
