"""Block-table-aware decode attention over a paged KV cache — Pallas TPU.

The serving engine stores KV state in fixed-size *pages* of ``block_size``
tokens drawn from one global pool per layer (see
``docs/serving_scheduler.md``); a per-sequence block table maps logical
position ``p`` to physical page ``table[b, p // block_size]``. Decode
attention therefore cannot stream the cache contiguously — it must chase
the block table. Two implementations share one contract:

* :func:`paged_attention_reference` — gather the sequence's pages into a
  dense ``(B, P*bs, nkv, hd)`` view and run exactly the math of
  ``repro.models.layers.attention_decode`` (same op order, same f32
  score path). This is the CPU/serving fallback AND the oracle: for a
  table whose capacity equals the dense engine's ``S_max`` it is
  bit-identical to the dense-slab path, which is what the engine golden
  tests pin.
* :func:`paged_decode_attention` — the Pallas kernel. Grid ``(B, P)``
  with the page axis sequential; the block table and sequence lengths
  ride in as *scalar prefetch* operands so each page's BlockSpec
  index_map can dereference ``table[b, j]`` before the body runs. Work
  and HBM traffic per row are O(live pages), not O(pool size) nor
  O(table width): a grid step whose page lies wholly past the row's
  length skips the body (the online-softmax update of a fully masked
  page is the identity), and its index_map repeats the row's last live
  page, a block the pipeline already holds. A dead table entry costs a
  grid step's overhead, not a page's work, whatever it holds: a
  sentinel, or a real page reserved for the row's worst case. Scores
  accumulate via online softmax (running max / normalizer / weighted
  accumulator in VMEM scratch, exactly the ``_chunked_causal_attention``
  recurrence), so kernel-vs-reference agreement is to float tolerance,
  not bitwise.

Both implementations additionally serve **int8 quantized KV pages**
(``kv_dtype=int8`` in ``transformer.init_paged_cache``): the pools hold
int8 codes plus per-(page, kv-head) symmetric scales
(:func:`quantize_kv_pages`), halving pool HBM. The reference dequantizes
per page and runs exactly the float math (the correctness anchor —
bit-identical to quantize→dequantize applied to the dense-slab math);
the kernel runs the *integer* datapath the
:class:`~repro.quant.spec.AttnDatapathSpec` record certifies: an
``hd``-deep int8×int8 QK^T dot held in a ``P_qk``-bit register and a
per-page ``block_size``-deep prob×value dot held in a ``P_pv``-bit
register, each page draining into the float online-softmax outer
accumulator (the attention analogue of Eq. 22's inner/outer split, with
the page as the tile). ``assert_bounds=True`` verifies the register
watermarks against the record in interpret mode, mirroring
``w4a8_mm``'s ``assert_inner``.

Validated against the reference in interpret mode over shape/raggedness
sweeps (``tests/test_paged_attention.py``) — the same testing pattern as
``w4a8_mm`` — and compiled for a described TPU v5e over float and int8
pages (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: f32 adds integers exactly below 2^24, so an integer register of at most
#: this many signed bits is exact when the MXU accumulates it in f32
_F32_EXACT_BITS = 24


def _softcap(scores, cap):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


# ---------------------------------------------------------------------------
# int8 KV page quantization (per-page, per-kv-head symmetric scales)
# ---------------------------------------------------------------------------
def quantize_kv_pages(pages, kv_bits: int = 8):
    """Symmetric per-(page, kv-head) quantization of float KV pages.

    pages: (..., block_size, nkv, hd) float -> (codes int8 of the same
    shape, scales (..., nkv) f32). The scale is shared by every position
    and head-dim lane of a page (constant over the PV reduction — that is
    what keeps the per-page PV accumulation a pure integer dot, see
    :class:`~repro.quant.spec.AttnDatapathSpec`); never-written positions
    are zeros and cannot raise the max.
    """
    qmax = 2 ** (kv_bits - 1) - 1
    xf = pages.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-3, -1))  # reduce (block_size, hd)
    scales = jnp.maximum(amax / qmax, 1e-8)
    codes = jnp.clip(jnp.rint(xf / scales[..., None, :, None]), -qmax, qmax)
    return codes.astype(jnp.int8), scales


def quantize_kv_pages_static(pages, scales):
    """Quantize float KV pages under *calibrated static* per-kv-head scales
    (``scales``: broadcastable to the pages' (..., nkv) page-scale shape —
    see ``repro.quant.observe.kv``). Unlike :func:`quantize_kv_pages` no
    per-page max reduction runs: the scale is a constant, codes hard-clip
    at the int8 container limit (out-of-calibration drift saturates — the
    serving saturation counters measure it), and the returned scales leaf
    is just the broadcast stamp, so pool consumers are unchanged."""
    qmax = 127
    xf = pages.astype(jnp.float32)
    stamp = jnp.broadcast_to(scales, (*pages.shape[:-3], pages.shape[-2]))
    codes = jnp.clip(jnp.rint(xf / stamp[..., None, :, None]), -qmax, qmax)
    return codes.astype(jnp.int8), stamp.astype(jnp.float32)


def dequantize_kv_pages(codes, scales):
    """Inverse of :func:`quantize_kv_pages` (always f32 — the score math's
    dtype, so reference and dense-slab paths see identical values)."""
    return codes.astype(jnp.float32) * scales[..., None, :, None]


def paged_attention_reference(q, k_pages, v_pages, block_table, seq_lens, *,
                              softcap=None, k_scales=None, v_scales=None):
    """Gather-based paged decode attention (the oracle + CPU path).

    q: (B, nh, hd) — the current token's query rows.
    k_pages / v_pages: (num_blocks, block_size, nkv, hd) — the layer's pool.
    block_table: (B, P) int32 — physical page per logical page; entries
        ``>= num_blocks`` are free-slot sentinels (clamped; masked anyway).
    seq_lens: (B,) int32 — valid positions per row (the just-written token
        included), i.e. attend over positions ``< seq_lens[b]``.
    k_scales / v_scales: (num_blocks, nkv) f32 — present iff the pool holds
        int8 codes; pages dequantize per page and the math below is
        exactly the float path (the int8 correctness anchor).
    """
    B, nh, hd = q.shape
    nb, bs, nkv, _ = k_pages.shape
    g = nh // nkv
    tab = jnp.minimum(block_table, nb - 1)
    if k_scales is not None:
        k = dequantize_kv_pages(k_pages[tab], k_scales[tab]).reshape(
            B, -1, nkv, hd)
        v = dequantize_kv_pages(v_pages[tab], v_scales[tab]).reshape(
            B, -1, nkv, hd)
    else:
        k = k_pages[tab].reshape(B, -1, nkv, hd)  # (B, P*bs, nkv, hd)
        v = v_pages[tab].reshape(B, -1, nkv, hd)
    qg = q.reshape(B, nkv, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32)
    s = _softcap(s / math.sqrt(hd), softcap)
    valid = jnp.arange(k.shape[1])[None, :] < seq_lens[:, None]  # (B, P*bs)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # dequantized int8 pages are f32: the output keeps the query's dtype,
    # as the kernel's does
    out = jnp.einsum("bkgs,bskd->bkgd", p, v).astype(q.dtype)
    return out.reshape(B, nh, hd)


def _live_entry(b, j, tab, lens, *, bs: int, nb: int):
    """The pool page grid step ``(b, j)`` fetches: its own table entry
    while that page holds live positions, and past the row's length the
    row's last live page again: the block the pipeline already holds, so
    a dead step has nothing new to fetch. Sentinels clamp to page
    ``nb-1``; a length-0 row reads entry 0."""
    last = jnp.maximum(lens[b] - 1, 0) // bs
    return jnp.minimum(tab[b, jnp.minimum(j, last)], nb - 1)


def _init_softmax_state(j, m_ref, l_ref, acc_ref):
    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _when_live(b, j, lens_ref, bs):
    """Run the decorated page body only on grid steps whose page holds
    positions below row ``b``'s length. Keyed on the length, never on the
    table entry: under worst-case reservation the entries past it are
    real pages."""
    return pl.when(j * bs < lens_ref[b])


def _mask_scores(s, j, b, lens_ref, bs, nh):
    """Length-mask one page's (nkv, g, bs) scores -> (nh, bs)."""
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)  # (1, bs)
    valid = pos < lens_ref[b]
    return jnp.where(valid[None], s, -jnp.inf).reshape(nh, bs)


def _softmax_accumulate(s, m_ref, l_ref, acc_ref, pv_of):
    """One page's online-softmax update (the ``_chunked_causal_attention``
    carry), shared by the float and int8 kernel bodies. ``pv_of(p)`` maps
    the page's probabilities (nh, bs) to (effective weights for the
    normalizer, PV numerator (nh, hd)) — the float body uses p itself,
    the int8 body its quantized codes, keeping numerator and denominator
    consistent by construction.

    Only live pages run it (:func:`_when_live`), and a live page holds at
    least one unmasked score, so ``m_new`` is finite: masked scores give
    ``p = 0``, and the first page's ``corr = exp(-inf) = 0`` clears the
    empty state."""
    m_prev = m_ref[...]  # (nh, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    p_eff, pv = pv_of(p)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p_eff, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new


def _finalize_output(j, n_pages, o_ref, m_ref, l_ref, acc_ref, out_dtype):
    @pl.when(j == n_pages - 1)
    def _epilogue():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom).astype(out_dtype)


def _kernel(tab_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, bs: int, nkv: int, g: int, hd: int, n_pages: int,
            softcap, out_dtype):
    b, j = pl.program_id(0), pl.program_id(1)
    nh = nkv * g
    _init_softmax_state(j, m_ref, l_ref, acc_ref)

    @_when_live(b, j, lens_ref, bs)
    def _page():
        q = q_ref[0].astype(jnp.float32)  # (nh, hd)
        k = k_ref[0].astype(jnp.float32)  # (bs, nkv, hd)
        v = v_ref[0].astype(jnp.float32)
        qg = q.reshape(nkv, g, hd)
        s = jnp.einsum("kgd,skd->kgs", qg, k).astype(jnp.float32)
        s = _softcap(s / math.sqrt(hd), softcap)
        s = _mask_scores(s, j, b, lens_ref, bs, nh)

        def pv_of(p):
            pv = jnp.einsum("kgs,skd->kgd", p.reshape(nkv, g, bs), v)
            return p, pv.reshape(nh, hd)

        _softmax_accumulate(s, m_ref, l_ref, acc_ref, pv_of)

    _finalize_output(j, n_pages, o_ref, m_ref, l_ref, acc_ref, out_dtype)


def _register_check(watermark, p_bits: int, what: str):
    """Interpret-mode verification that an integer register watermark stays
    inside its certified P-bit range (the w4a8_mm ``assert_inner`` idiom)."""
    limit = 2 ** (p_bits - 1) - 1
    pl.debug_check(watermark <= limit, f"{what} accumulator overflow")


def _quant_kernel(tab_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, bs: int, nkv: int, g: int,
                  hd: int, n_pages: int, softcap, out_dtype, spec,
                  assert_bounds: bool):
    """The int8-KV body: same online-softmax recurrence as :func:`_kernel`,
    but both reductions run over integer codes the ``spec``
    (:class:`~repro.quant.spec.AttnDatapathSpec`) certifies — QK^T as an
    hd-deep q-code × k-code dot in a P_qk-bit register, PV as a per-page
    block_size-deep prob-code × v-code dot in a P_pv-bit register, with
    scales applied once per page on the way into the float outer state.

    The codes ride the MXU as bf16 with f32 accumulation: bf16 holds every
    integer up to 256 (prob codes reach 255, which int8 cannot carry) and
    f32 sums integers exactly below 2^24, which P_qk, P_pv <= 24 guarantees
    (checked when the kernel is built) — so each dot is the exact integer
    register value the record certifies."""
    b, j = pl.program_id(0), pl.program_id(1)
    nh = nkv * g
    _init_softmax_state(j, m_ref, l_ref, acc_ref)

    @_when_live(b, j, lens_ref, bs)
    def _page():
        q = q_ref[0].astype(jnp.float32)  # (nh, hd)
        # per-head symmetric quantization of the query rows (the A-side codes)
        q_amax = jnp.max(jnp.abs(q), axis=-1, keepdims=True)  # (nh, 1)
        q_scale = jnp.maximum(q_amax / spec.q_qmax, 1e-8)
        q_codes = jnp.clip(jnp.rint(q / q_scale), -spec.q_qmax, spec.q_qmax)
        k_codes = k_ref[0].astype(jnp.float32)  # (bs, nkv, hd) int8 codes
        k_scale = ks_ref[0]  # (nkv, 1) f32 — this page's per-head scale

        # hd-deep integer QK^T dot, held in the P_qk register
        s_int = jnp.einsum("kgd,skd->kgs",
                           q_codes.reshape(nkv, g, hd).astype(jnp.bfloat16),
                           k_codes.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        if assert_bounds:
            _register_check(jnp.max(jnp.abs(s_int)), spec.p_qk, "QK^T")
        s = (s_int * q_scale.reshape(nkv, g, 1)
             * k_scale[:, :, None])
        s = _softcap(s / math.sqrt(hd), softcap)
        s = _mask_scores(s, j, b, lens_ref, bs, nh)

        def pv_of(p):
            # probability codes (unsigned prob_bits) — the PV A-side operand;
            # the normalizer accumulates the *quantized* probabilities so the
            # final weighted average stays consistent with the PV numerator
            p_codes = jnp.rint(p * spec.prob_qmax)  # (nh, bs), 0..prob_qmax
            v_codes = v_ref[0].astype(jnp.float32)  # (bs, nkv, hd)
            v_scale = vs_ref[0]  # (nkv, 1)
            # per-page block_size-deep integer PV dot, held in the P_pv
            # register — the page is the tile; partials drain scaled into the
            # f32 outer accumulator
            pv_int = jnp.einsum("kgs,skd->kgd",
                                p_codes.reshape(nkv, g, bs).astype(jnp.bfloat16),
                                v_codes.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
            if assert_bounds:
                _register_check(jnp.max(jnp.abs(pv_int)), spec.p_pv, "PV")
            pv = pv_int * (v_scale[:, :, None] / spec.prob_qmax)
            return p_codes / spec.prob_qmax, pv.reshape(nh, hd)

        _softmax_accumulate(s, m_ref, l_ref, acc_ref, pv_of)

    _finalize_output(j, n_pages, o_ref, m_ref, l_ref, acc_ref, out_dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret",
                                             "attn_spec", "assert_bounds"))
def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens, *,
                           k_scales=None, v_scales=None, attn_spec=None,
                           softcap: float | None = None,
                           interpret: bool = False,
                           assert_bounds: bool = False):
    """Paged decode attention as a Pallas kernel; same contract as
    :func:`paged_attention_reference`. The block table and lengths are
    scalar-prefetched so the K/V BlockSpec index_maps can walk
    ``table[b, j]`` — only the row's own live pages transit HBM->VMEM,
    and only they run the body.

    Passing ``k_scales``/``v_scales`` selects the int8 body, whose QK^T /
    PV registers are certified by an
    :class:`~repro.quant.spec.AttnDatapathSpec`; ``attn_spec`` is a
    *request* validated against the record derived from the pool layout
    (a disagreement raises ``DatapathMismatchError``, never a silent
    fallback — the ``validate_datapath`` contract). ``assert_bounds``
    checks the register watermarks in interpret mode."""
    from repro.quant.spec import AttnDatapathSpec, validate_attn_datapath

    B, nh, hd = q.shape
    nb, bs, nkv, _ = k_pages.shape
    _, n_pages = block_table.shape
    g = nh // nkv
    assert nh == nkv * g, (nh, nkv)
    quantized = k_scales is not None
    if attn_spec is not None and not quantized:
        # absence of a record (float pages) is a mismatch, not a match —
        # the same contract as validate_datapath on unpacked leaves
        validate_attn_datapath(None, attn_spec)

    live_entry = functools.partial(_live_entry, bs=bs, nb=nb)

    def page_idx(b, j, tab, lens):
        return (live_entry(b, j, tab, lens), 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, nh, hd), lambda b, j, tab, lens: (b, 0, 0)),
        pl.BlockSpec((1, bs, nkv, hd), page_idx),
        pl.BlockSpec((1, bs, nkv, hd), page_idx),
    ]
    operands = [block_table, seq_lens, q, k_pages, v_pages]
    if quantized:
        def scale_idx(b, j, tab, lens):
            return (live_entry(b, j, tab, lens), 0, 0)

        # (nb, nkv, 1): the block's last two dims are the array's own (a
        # legal block), and the kernel reads the scales one head per sublane
        in_specs += [pl.BlockSpec((1, nkv, 1), scale_idx)] * 2
        operands += [k_scales.astype(jnp.float32).reshape(nb, nkv, 1),
                     v_scales.astype(jnp.float32).reshape(nb, nkv, 1)]
        derived = AttnDatapathSpec.for_cache(
            hd, bs, kv_bits=8 * k_pages.dtype.itemsize)
        if attn_spec is not None:
            derived.require_matches(attn_spec, context="paged_decode_attention")
        if max(derived.p_qk, derived.p_pv) > _F32_EXACT_BITS:
            raise ValueError(
                f"int8 paged attention accumulates its integer registers in "
                f"f32, exact only up to {_F32_EXACT_BITS} bits; this cache "
                f"needs P_qk={derived.p_qk}, P_pv={derived.p_pv}")
        kernel = functools.partial(
            _quant_kernel, bs=bs, nkv=nkv, g=g, hd=hd, n_pages=n_pages,
            softcap=softcap, out_dtype=q.dtype, spec=derived,
            assert_bounds=assert_bounds,
        )
    else:
        kernel = functools.partial(
            _kernel, bs=bs, nkv=nkv, g=g, hd=hd, n_pages=n_pages,
            softcap=softcap, out_dtype=q.dtype,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, hd), lambda b, j, tab, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),  # running max m
            pltpu.VMEM((nh, 1), jnp.float32),  # running normalizer l
            pltpu.VMEM((nh, hd), jnp.float32),  # weighted accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
