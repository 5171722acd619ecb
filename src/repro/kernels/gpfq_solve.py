"""Memory-efficient GPFQ panel solver as a Pallas kernel.

GPFQ is sequential in K (each weight's correction depends on all previous
quantization errors), so a GPU implementation runs a Python loop with
batched channel updates. The TPU-idiomatic equivalent is a *sequential grid
dimension* with the running error matrix U resident in VMEM scratch — the
systolic analogue of a persistent CUDA block (DESIGN.md §3):

    grid = (C / block_c, K)       # channels parallel, K sequential
    per step k: stream one row of H and one row of G H^-1 from HBM,
                compute v = w_k * (<h_k, g_k>/|h_k|^2) + (h_k U)/|h_k|^2,
                soft-threshold (Pi_lambda), clip to the running AXE budgets
                (Psi_{a,b}, Eqs. 19-21), round, commit, rank-1-update U.

The AXE budget state (pos/neg committed mass per (tile, channel)) also lives
in VMEM scratch. Work per step is O(K * block_c): the matvec h_k @ U and the
two rank-1 updates — MXU-friendly contractions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    w_ref,  # (K, bc) integer-domain weights
    xg_ref,  # (1, K) row k of G H^-1
    xh_ref,  # (1, K) row k of H
    hg_ref,  # (1, 1) <h_k, g_k>
    hn_ref,  # (1, 1) |h_k|^2
    lam_ref,  # (n_tiles, bc) soft thresholds
    tid_ref,  # (1, 1) tile id of step k
    q_ref,  # out: (K, bc)
    u_ref,  # scratch: (K, bc) running error
    pos_ref,  # scratch: (n_tiles, bc)
    neg_ref,  # scratch: (n_tiles, bc)
    *,
    n_k: int,
    qmin: float,
    qmax: float,
    budget_b: float,
    rounding: str,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)
        pos_ref[...] = jnp.zeros_like(pos_ref)
        neg_ref[...] = jnp.zeros_like(neg_ref)

    h = xh_ref[...]  # (1, K)
    g = xg_ref[...]
    denom = jnp.maximum(hn_ref[0, 0], 1e-20)
    w_k = w_ref[k, :]  # (bc,)
    v = w_k * (hg_ref[0, 0] / denom) + (h @ u_ref[...])[0] / denom  # (bc,)

    t = tid_ref[0, 0]
    lam = lam_ref[t, :]
    v = jnp.sign(v) * jnp.maximum(jnp.abs(v) - lam, 0.0)  # Pi_lambda
    lo = jnp.minimum(-budget_b - neg_ref[t, :], 0.0)  # Psi_{a,b}
    hi = jnp.maximum(budget_b - pos_ref[t, :], 0.0)
    v = jnp.clip(v, lo, hi)
    if rounding == "nearest":
        q = jnp.clip(jnp.rint(v), qmin, qmax)
    else:  # round-to-zero
        q = jnp.clip(jnp.trunc(v), qmin, qmax)

    pos_ref[t, :] += jnp.maximum(q, 0.0)
    neg_ref[t, :] += jnp.minimum(q, 0.0)
    # U += g^T w_k - h^T q   (two rank-1 updates, (K, bc))
    u_ref[...] += g.T @ w_k[None, :] - h.T @ q[None, :]
    q_ref[k, :] = q


@functools.partial(
    jax.jit,
    static_argnames=(
        "budget_b", "w_bits", "tile", "block_c", "rounding", "interpret",
    ),
)
def gpfq_solve(
    w_int: jax.Array,  # (K, C) integer-domain weights
    xg: jax.Array,  # (K, K) = G H^-1
    xh: jax.Array,  # (K, K) = H
    lam: jax.Array,  # (n_tiles, C) soft thresholds (zeros disable)
    budget_b: float,  # strict budget B of Eq. 21 (inf disables)
    *,
    w_bits: int = 4,
    tile: int = 128,
    block_c: int = 128,
    rounding: str = "nearest",
    interpret: bool = False,
):
    k, c = w_int.shape
    assert xg.shape == (k, k) and xh.shape == (k, k)
    assert c % block_c == 0, (c, block_c)
    n_tiles = (k + tile - 1) // tile
    assert lam.shape == (n_tiles, c), (lam.shape, (n_tiles, c))

    hn = jnp.sum(xh * xh, axis=1).reshape(k, 1)  # |h_k|^2
    hg = jnp.sum(xh * xg, axis=1).reshape(k, 1)  # <h_k, g_k>
    tids = (jnp.arange(k, dtype=jnp.int32) // tile).reshape(k, 1)

    qmax = float(2 ** (w_bits - 1) - 1)
    kernel = functools.partial(
        _kernel,
        n_k=k,
        qmin=-qmax,
        qmax=qmax,
        budget_b=float(budget_b),
        rounding=rounding,
    )
    return pl.pallas_call(
        kernel,
        grid=(c // block_c, k),
        in_specs=[
            pl.BlockSpec((k, block_c), lambda ci, kk: (0, ci)),
            pl.BlockSpec((1, k), lambda ci, kk: (kk, 0)),
            pl.BlockSpec((1, k), lambda ci, kk: (kk, 0)),
            pl.BlockSpec((1, 1), lambda ci, kk: (kk, 0)),
            pl.BlockSpec((1, 1), lambda ci, kk: (kk, 0)),
            pl.BlockSpec((n_tiles, block_c), lambda ci, kk: (0, ci)),
            pl.BlockSpec((1, 1), lambda ci, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((k, block_c), lambda ci, kk: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((k, c), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((k, block_c), jnp.float32),
            pltpu.VMEM((n_tiles, block_c), jnp.float32),
            pltpu.VMEM((n_tiles, block_c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(w_int.astype(jnp.float32), xg, xh, hg, hn, lam, tids)
