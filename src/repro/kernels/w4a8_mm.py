"""W4A8 integer GEMM with multi-stage accumulation — the inference hot-spot
AXE certifies (paper §3.3 / §4.2), as a Pallas TPU kernel.

Datapath (Figure 2 of the paper, mapped to the TPU memory hierarchy):

  * weights arrive int4-PACKED (two codes per int8 byte along K) — half the
    HBM->VMEM traffic of int8 weights — and are unpacked in VMEM with int32
    shifts (Mosaic legalizes no int8 shift) into an int8 MXU operand;
  * activations arrive as 8-bit codes (asymmetric, zero-point handled by a
    per-channel correction term computed once outside the kernel). Unsigned
    codes are shifted by -128 into int8 outside the kernel, so the MXU sees
    int8 x int8 with int32 accumulation; ``128 * col_sums`` folds into the
    zero-point term, which keeps the result exact;
  * the K axis is processed in tiles of T = ``block_k`` (128 = one MXU pass,
    the paper's T): each tile's dot product is the *inner* accumulator —
    AXE guarantees it fits P_I bits (16 in the LLM recipe), which is what
    would let a hypothetical int16 systolic datapath run at 2x throughput;
  * per-tile partials are accumulated across the sequential K grid dimension
    into a VMEM int32 scratch — the *outer* accumulator (P_O of Eq. 22);
  * the epilogue applies s_x * s_w[n] and the zero-point correction, and
    writes bf16/f32.

Validated against ref.py in interpret mode over shape/dtype sweeps
(tests/test_kernels.py) and compiled for a described TPU v5e
(tests/test_tpu_compile.py); the ``assert_inner`` flag additionally checks
the certified *unsigned-code* P_I partial inside the kernel on every tile
(interpret mode only — on hardware the bound is a theorem, not a runtime
check).

Alignment: on the chip every K and N block is a multiple of the 128-lane
width. Packed serving leaves are zero-padded to that width at pack time
(:func:`pad_packed`); any other operand is padded here at the call and the
output sliced back. Zero codes add nothing to a tile partial or to
``col_sums``, and the certified tiles start at K = 0, so every 128-tile holds
the nonzeros it was certified on. A block that is not lane-aligned raises
instead of being silently shrunk.

In-kernel unpack order: a packed block row ``r`` holds K rows ``2r`` (low
nibble) and ``2r + 1`` (high nibble). The kernel stacks all low nibbles
above all high nibbles (no interleave across sublanes), and the wrapper
permutes each K tile of the activation codes to the same [even | odd]
order — the same products, summed over the same tile.

Two shape regimes share the kernel body:

  * prefill-shaped (M = B*S, hundreds+): the classic 128x128x128 grid; a
    ragged last M block is padded internally and sliced off after the call;
  * decode-shaped (M = batch, often < 8): :func:`w4a8_decode_matmul` —
    GEMV-style grid with a single sub-128 M block (rounded up to the 8-row
    sublane), N x K tiled as in prefill, and the per-channel ``col_sums``
    zero-point term taken from the packed artifact instead of recomputed
    from a full ``unpack_int4`` on every call (that unpack would re-read
    the whole weight, exactly the HBM traffic packing exists to avoid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: TPU lane width: every K / N block of the compiled kernel is a multiple
LANE = 128


def unpack_int4(packed: jax.Array) -> jax.Array:
    """(..., K//2, N) int8 -> (..., K, N) int8 in [-8, 7]; row 2k = low
    nibble. Leading dims (repeat stacks, MoE expert stacks) pass through."""
    low = jnp.left_shift(packed, 4)
    low = jnp.right_shift(low, 4)  # arithmetic: sign-extends
    high = jnp.right_shift(packed, 4)
    *lead, k2, n = packed.shape
    out = jnp.stack([low, high], axis=-2)  # (..., K//2, 2, N)
    return out.reshape(*lead, 2 * k2, n)


def pack_int4(q: jax.Array) -> jax.Array:
    """(..., K, N) int codes in [-8, 7] -> (..., K//2, N) int8 packed."""
    q = q.astype(jnp.int8)
    *lead, k, n = q.shape
    assert k % 2 == 0, "K must be even to pack int4"
    pairs = q.reshape(*lead, k // 2, 2, n)
    low = jnp.bitwise_and(pairs[..., 0, :], 0x0F)
    high = jnp.left_shift(jnp.bitwise_and(pairs[..., 1, :], 0x0F), 4)
    return jnp.bitwise_or(low, high).astype(jnp.int8)


# ---------------------------------------------------------------------------
# 2:4 semi-structured compression (codes + metadata indices).
#
# Layout: per group of 4 rows along K, per column, the (<= 2) surviving codes
# are stored as one packed int4 byte (low nibble = first kept code, high =
# second) and one metadata byte (bits 0-1 = in-group position of the first,
# bits 2-3 = of the second). Groups with fewer than 2 nonzeros pad with
# zero-valued codes pointing at unused slots — expansion is insensitive to
# which slots because a zero code contributes zero. Weight HBM traffic is
# K/4 + K/4 bytes per column vs K/2 dense-packed: a further 2x reduction.
# ---------------------------------------------------------------------------
def compress_2to4(q: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., K, N) 2:4-sparse int codes -> (packed (..., K//4, N) int8,
    meta (..., K//4, N) int8). Traceable (works under ``jax.eval_shape``);
    the 2:4 property itself is validated by ``certify`` at quantization
    time, not here."""
    q = q.astype(jnp.int8)
    *lead, k, n = q.shape
    if k % 4:
        raise ValueError(f"2:4 compression needs K % 4 == 0, got K={k}")
    g = q.reshape(*lead, k // 4, 4, n)
    # nonzero slots first (stable: ties keep in-group index order)
    order = jnp.argsort(g == 0, axis=-2, stable=True)
    idx = order[..., :2, :]  # (..., k//4, 2, n) positions of kept codes
    vals = jnp.take_along_axis(g, idx, axis=-2)  # (..., k//4, 2, n)
    meta = jnp.bitwise_or(
        idx[..., 0, :].astype(jnp.int8),
        jnp.left_shift(idx[..., 1, :].astype(jnp.int8), 2),
    )
    return pack_int4(vals.reshape(*lead, k // 2, n)), meta


def unpack_sparse24(packed: jax.Array, meta: jax.Array) -> jax.Array:
    """Gather-reference expansion: (..., K//4, N) packed + meta ->
    (..., K, N) int8 dense-with-zeros, bit-identical to the codes that were
    compressed. Leading dims (repeat/expert stacks) pass through."""
    vals = unpack_int4(packed)  # (..., K//2, N)
    *lead, k2, n = vals.shape
    g4 = k2 // 2
    v = vals.reshape(*lead, g4, 2, n)
    m = meta.astype(jnp.int32)
    i0 = jnp.bitwise_and(m, 3)[..., :, None, :]  # (..., g4, 1, n)
    i1 = jnp.bitwise_and(jnp.right_shift(m, 2), 3)[..., :, None, :]
    pos = jnp.arange(4, dtype=jnp.int32).reshape(
        *(1,) * len(lead), 1, 4, 1
    )  # in-group slot ids
    dense = jnp.where(pos == i0, v[..., 0:1, :], jnp.int8(0)) + jnp.where(
        pos == i1, v[..., 1:2, :], jnp.int8(0)
    )
    return dense.reshape(*lead, g4 * 4, n).astype(jnp.int8)


def _expand_sparse24_block(wp, meta):
    """In-kernel expansion of one (bk//4, bn) packed+meta block to a dense
    (bk, bn) int32 block. Mirrors :func:`unpack_sparse24` exactly (same
    nibble decode, same position compare), so the kernel matmul consumes
    bit-identical codes to the gather reference."""
    vals = unpack_int4(wp)  # (bk//2, bn) int8
    g4, bn = meta.shape
    v = vals.reshape(g4, 2, bn).astype(jnp.int32)
    m = meta.astype(jnp.int32)
    i0 = jnp.bitwise_and(m, 3)[:, None, :]
    i1 = jnp.bitwise_and(jnp.right_shift(m, 2), 3)[:, None, :]
    pos = jax.lax.broadcasted_iota(jnp.int32, (g4, 4, bn), 1)
    dense = jnp.where(pos == i0, v[:, 0:1, :], 0) + jnp.where(pos == i1, v[:, 1:2, :], 0)
    return dense.reshape(g4 * 4, bn)


def _unpack_tile(wp):
    """(bk//2, bn) packed int8 block -> (bk, bn) int8 codes in split order:
    rows [0, bk/2) are the low nibbles (even K of the tile), rows
    [bk/2, bk) the high nibbles (odd K). int32 shifts sign-extend each
    nibble; the result narrows to int8 for the MXU."""
    w = wp.astype(jnp.int32)
    low = jnp.right_shift(jnp.left_shift(w, 28), 28)
    high = jnp.right_shift(w, 4)
    return jnp.concatenate([low, high], axis=0).astype(jnp.int8)


def _split_tiles(x, bk: int):
    """Permute each bk-wide K tile of (M, K) codes to [even | odd] order —
    the row order :func:`_unpack_tile` produces. Tile membership is
    unchanged, so every tile partial sums the same products."""
    m, k = x.shape
    return x.reshape(m, k // bk, bk // 2, 2).swapaxes(-1, -2).reshape(m, k)


def _signed_codes(x):
    """8-bit activation codes -> (int8 codes, shift) with x = codes + shift:
    unsigned codes move down by 128 so the MXU runs int8 x int8."""
    if x.dtype == jnp.uint8:
        return (x.astype(jnp.int32) - 128).astype(jnp.int8), 128
    if x.dtype == jnp.int8:
        return x, 0
    raise TypeError(f"activation codes must be uint8 or int8, got {x.dtype}")


def _check_inner(partial, w, shift: int, p_inner: int):
    """Interpret-mode check of the certified P_I bound on the *unsigned*
    code partial (the shifted MXU partial plus ``shift * sum_k w``)."""
    if shift:
        partial = partial + shift * jnp.sum(
            w.astype(jnp.int32), axis=0, keepdims=True)
    limit = 2 ** (p_inner - 1) - 1
    pl.debug_check(jnp.max(jnp.abs(partial)) <= limit,
                   "inner accumulator overflow")


def _kernel(x_ref, wp_ref, sw_ref, corr_ref, out_ref, acc_ref, *,
            n_k: int, p_inner: int, assert_inner: bool, shift: int,
            out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_tile(wp_ref[...])  # (bk, bn) int8, split order
    # inner accumulator: one K-tile MAC — AXE certifies |partial| < 2^(P_I-1)
    partial = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    if assert_inner:  # interpret-mode verification of the paper's guarantee
        _check_inner(partial, w, shift, p_inner)
    # outer accumulator (P_O of Eq. 22)
    acc_ref[...] += partial

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        # zero-point correction ((zp - shift) * sum_k q[k,n], precomputed per
        # channel) then the fused dequant scale s_x * s_w[n]
        out_ref[...] = ((acc - corr_ref[...]) * sw_ref[...]).astype(out_dtype)


def datapath_kernel_args(spec) -> dict:
    """Map a :class:`~repro.quant.spec.DatapathSpec` onto the kernel's
    accumulator knobs. This is the only place the translation lives: the
    K-tile size is the certified T (monolithic specs keep the 128-lane MXU
    tile — any K-subset partial of an l1-budgeted row is bounded by the
    full-K bound, so P_I stays a valid per-tile certificate) and the inner
    accumulator width is the certified P_I."""
    return {"block_k": spec.block_k(), "p_inner": spec.p_inner}


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def pad_packed(packed: jax.Array) -> jax.Array:
    """Zero-pad a (..., K//2, N) packed leaf so K and N are multiples of
    :data:`LANE` — the pack-time half of the kernel's alignment contract
    (zero codes contribute nothing; the call slices the output back to the
    leaf's logical N, which its ``scale`` carries)."""
    *lead, k2, n = packed.shape
    pk = _round_up(2 * k2, LANE) // 2 - k2
    pn = _round_up(n, LANE) - n
    if not (pk or pn):
        return packed
    return jnp.pad(packed, [(0, 0)] * len(lead) + [(0, pk), (0, pn)])


def _pick_bm(m: int, block_m: int) -> int:
    """M block: one sub-block_m block (rounded up to the 8-row sublane) in
    the decode regime; otherwise the largest power-of-two shrink of block_m
    whose ragged-tail padding stays small (<= max(bm/4, 8) rows) instead of
    paying up to a whole extra block of wasted MXU work (m=130 with bm=128
    would pad to 256; an 8-row block pads to 136)."""
    if m <= block_m:
        return _round_up(m, 8)
    c = block_m
    while c >= 8:
        if _round_up(m, c) - m <= max(c // 4, 8):
            return c
        c //= 2
    return 8


def _check_lanes(bk: int, bn: int, interpret: bool) -> None:
    """Compiled kernels take lane-aligned K and N blocks only — an
    unaligned block is an error, never a silently shrunk divisor."""
    if not interpret and (bk % LANE or bn % LANE):
        raise ValueError(
            f"W4A8 kernel blocks must be multiples of {LANE} lanes on the "
            f"chip, got block_k={bk} block_n={bn}")


def _pad_to(a: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    pads = [(0, t - s) for s, t in zip(a.shape, shape)]
    return jnp.pad(a, pads) if any(p for _, p in pads) else a


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "p_inner",
                     "assert_inner", "interpret", "out_dtype"),
)
def w4a8_matmul(
    x_int8: jax.Array,  # (M, K) uint8 / int8 activation codes
    w_packed: jax.Array,  # (K'//2, N') int8 packed int4 weights, K' >= K, N' >= N
    w_scale: jax.Array,  # (N,) f32 per-channel weight scales (logical N)
    act_scale: float,
    act_zp: int,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,  # the paper's tile size T
    p_inner: int = 16,
    assert_inner: bool = False,
    interpret: bool = False,
    out_dtype=jnp.float32,
    col_sums: jax.Array | None = None,  # (N,) or (1, N) int32, pack-time
):
    m, k = x_int8.shape
    k2, n_w = w_packed.shape
    n = w_scale.size
    assert k <= 2 * k2 and n <= n_w, (x_int8.shape, w_packed.shape, n)
    _check_lanes(block_k, block_n, interpret)
    assert block_k % 2 == 0, f"K tile {block_k} must be even for packed int4"

    # per-channel zero-point correction: zp * sum_k q[k, n] (int32), and the
    # fused dequant scale s_x * s_w — both computed once outside the kernel.
    # col_sums is precomputed at pack time on the decode path; the fallback
    # unpack here is the prefill/one-off path.
    if col_sums is None:
        col_sums = jnp.sum(unpack_int4(w_packed).astype(jnp.int32), axis=0)
    col_sums = col_sums.reshape(-1)[:n]
    codes, shift = _signed_codes(x_int8)
    corr = (col_sums.astype(jnp.float32) * (act_zp - shift))[None, :]
    sw = (w_scale.reshape(-1).astype(jnp.float32) * act_scale)[None, :]

    # Ragged shapes: M pads with zero rows (garbage rows sliced off after
    # the call — the zero-point correction makes them nonzero, but they are
    # never read); K and N pad with zero codes to whole blocks (a no-op for
    # leaves padded at pack time).
    bm, bk, bn = _pick_bm(m, block_m), block_k, block_n
    m_pad = _round_up(m, bm)
    k_pad = _round_up(2 * k2, bk)
    n_pad = _round_up(n_w, bn)
    codes = _split_tiles(_pad_to(codes, (m_pad, k_pad)), bk)
    w_packed = _pad_to(w_packed, (k_pad // 2, n_pad))
    corr = _pad_to(corr, (1, n_pad))
    sw = _pad_to(sw, (1, n_pad))

    n_k = k_pad // bk
    grid = (m_pad // bm, n_pad // bn, n_k)
    kernel = functools.partial(
        _kernel,
        n_k=n_k,
        p_inner=p_inner,
        assert_inner=assert_inner,
        shift=shift,
        out_dtype=out_dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(codes, w_packed, sw, corr)
    return out[:m, :n]


def w4a8_decode_matmul(
    x_int8: jax.Array,  # (B, K) activation codes — M = decode batch
    w_packed: jax.Array,  # (K'//2, N')
    w_scale: jax.Array,  # (N,) or (1, N)
    col_sums: jax.Array,  # (N,) or (1, N) int32 — REQUIRED, from pack time
    act_scale,
    act_zp,
    **kw,
):
    """Decode-shaped W4A8 GEMM: single sub-128 M block (padded up to the
    8-row sublane), N x K tiled as in prefill, int4 unpack + zero-point
    correction + per-channel dequant fused in the epilogue. Same
    ``p_inner``/``assert_inner`` certificate semantics as the prefill path.

    Requiring ``col_sums`` (stored in the packed serving artifact) is what
    keeps this path free of any full-weight ``unpack_int4``: the jaxpr
    touches the packed codes only inside the kernel, block by block.
    """
    assert col_sums is not None
    kw.setdefault("block_m", 128)  # min() against M inside w4a8_matmul
    return w4a8_matmul(
        x_int8, w_packed, w_scale, act_scale, act_zp, col_sums=col_sums, **kw
    )


# ---------------------------------------------------------------------------
# Sparse (2:4) decode path.
# ---------------------------------------------------------------------------
def _sparse_kernel(x_ref, wp_ref, meta_ref, sw_ref, corr_ref, out_ref, acc_ref, *,
                   n_k: int, p_inner: int, assert_inner: bool, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)  # (bm, bk) 8-bit codes
    # expand the compressed block in VMEM: the HBM->VMEM weight traffic is
    # bk/4 + bk/4 bytes per column (codes + metadata) instead of bk/2 dense
    w = _expand_sparse24_block(wp_ref[...], meta_ref[...])  # (bk, bn) int32
    partial = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    if assert_inner:  # interpret-mode verification (2:4 tightens the bound)
        _check_inner(partial, w, 0, p_inner)
    acc_ref[...] += partial

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out_ref[...] = ((acc - corr_ref[...]) * sw_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "p_inner",
                     "assert_inner", "interpret", "out_dtype"),
)
def w4a8_sparse_matmul(
    x_int8: jax.Array,  # (M, K) int8 activation codes
    w_packed: jax.Array,  # (K//4, N) int8 packed 2:4 codes (2 nibbles/group)
    w_meta: jax.Array,  # (K//4, N) int8 in-group position metadata
    w_scale: jax.Array,  # (N,) f32 per-channel weight scales
    act_scale: float,
    act_zp: int,
    *,
    col_sums: jax.Array,  # (N,) or (1, N) int32 — REQUIRED, from pack time
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    p_inner: int = 16,
    assert_inner: bool = False,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """W4A8 GEMM over 2:4-compressed weights, bit-identical to running
    :func:`w4a8_matmul` on the dense-with-zeros codes: the in-kernel
    expansion reconstructs the exact same int values, the MXU partial sums
    the exact same int32 integers, and the epilogue applies the exact same
    float math in the same order. ``col_sums`` must be the dense codes'
    per-channel sums (= the sums of the kept codes — zeros add nothing).

    Same ragged-shape handling as the dense kernel; decode batches (M < 8)
    round up to the 8-row sublane. This kernel is validated in interpret
    mode only: it keeps the interleaved unpack and an int32 MXU dot.
    """
    m, k = x_int8.shape
    k4, n_w = w_packed.shape
    n = w_scale.size
    assert k <= 4 * k4 and n <= n_w, (x_int8.shape, w_packed.shape, n)
    assert w_meta.shape == w_packed.shape, (w_meta.shape, w_packed.shape)
    _check_lanes(block_k, block_n, interpret)
    assert block_k % 4 == 0, f"K tile {block_k} must be a multiple of 4 for 2:4 codes"

    # same ragged handling as the dense kernel: zero rows / zero codes (a
    # zero metadata byte points both kept slots at position 0 with value 0)
    bm, bk, bn = _pick_bm(m, block_m), block_k, block_n
    m_pad = _round_up(m, bm)
    k_pad = _round_up(4 * k4, bk)
    n_pad = _round_up(n_w, bn)
    x_int8 = _pad_to(x_int8, (m_pad, k_pad))
    w_packed = _pad_to(w_packed, (k_pad // 4, n_pad))
    w_meta = _pad_to(w_meta, (k_pad // 4, n_pad))

    corr = (col_sums.reshape(-1)[:n].astype(jnp.float32) * act_zp)[None, :]
    sw = (w_scale.reshape(-1).astype(jnp.float32) * act_scale)[None, :]
    corr = _pad_to(corr, (1, n_pad))
    sw = _pad_to(sw, (1, n_pad))

    n_k = k_pad // bk
    grid = (m_pad // bm, n_pad // bn, n_k)
    kernel = functools.partial(
        _sparse_kernel,
        n_k=n_k,
        p_inner=p_inner,
        assert_inner=assert_inner,
        out_dtype=out_dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 4, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // 4, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_int8, w_packed, w_meta, sw, corr)
    return out[:m, :n]


def w4a8_sparse_decode_matmul(
    x_int8: jax.Array,  # (B, K)
    w_packed: jax.Array,  # (K//4, N)
    w_meta: jax.Array,  # (K//4, N)
    w_scale: jax.Array,
    col_sums: jax.Array,
    act_scale,
    act_zp,
    **kw,
):
    """Decode-shaped counterpart of :func:`w4a8_sparse_matmul` — the sparse
    analogue of :func:`w4a8_decode_matmul` (col_sums required, packed codes
    and metadata only ever touched block-by-block inside the kernel)."""
    assert col_sums is not None
    kw.setdefault("block_m", 128)
    return w4a8_sparse_matmul(
        x_int8, w_packed, w_meta, w_scale, act_scale, act_zp,
        col_sums=col_sums, **kw
    )
