"""Paged decode-attention kernel vs the gather reference — interpret-mode
shape/raggedness sweeps (the w4a8_mm testing pattern), plus agreement of
the gather reference with the dense-slab ``attention_decode`` math, for
both the float and the int8-quantized KV paths."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (
    _live_entry,
    dequantize_kv_pages,
    paged_attention_reference,
    paged_decode_attention,
    quantize_kv_pages,
)


def _random_case(rng, B, nkv, g, hd, bs, P, extra_blocks=4, dtype=jnp.float32,
                 lens=None, tail="sentinel"):
    """Distinct pages per row, ragged lengths (random unless given). Table
    entries past a row's length are sentinels, or with ``tail="reserved"``
    (worst-case reservation) real pages of the row's own. Page ``nb-1``,
    where sentinels clamp, never holds live KV."""
    nb = B * P + extra_blocks
    nh = nkv * g
    q = jnp.asarray(rng.normal(size=(B, nh, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(nb, bs, nkv, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, nkv, hd)), dtype)
    tab = np.full((B, P), nb, np.int32)  # sentinel = nb
    perm = rng.permutation(nb)
    perm = perm[perm != nb - 1]
    if lens is None:
        lens = rng.integers(1, P * bs + 1, size=B)
    lens = np.asarray(lens, np.int32)
    o = 0
    for b in range(B):
        n_pages = P if tail == "reserved" else -(-int(lens[b]) // bs)
        tab[b, :n_pages] = perm[o:o + n_pages]
        o += n_pages
    return q, kp, vp, jnp.asarray(tab), jnp.asarray(lens)


def _dead_entries(tab, lens, bs):
    """(B, P) mask of the table entries wholly past each row's length."""
    P = tab.shape[1]
    return np.arange(P)[None, :] * bs >= np.asarray(lens)[:, None]


def _quantize_case(kp, vp):
    (kc, ks), (vc, vs) = quantize_kv_pages(kp), quantize_kv_pages(vp)
    return kc, vc, {"k_scales": ks, "v_scales": vs}


@pytest.mark.parametrize(
    "B,nkv,g,hd,bs,P",
    [
        (1, 1, 1, 8, 4, 2),
        (3, 2, 2, 16, 8, 4),  # GQA
        (4, 2, 1, 32, 16, 3),  # MHA-as-GQA
        (2, 4, 4, 8, 8, 2),
    ],
)
def test_kernel_matches_reference(rng, B, nkv, g, hd, bs, P):
    q, kp, vp, tab, lens = _random_case(rng, B, nkv, g, hd, bs, P)
    ref = paged_attention_reference(q, kp, vp, tab, lens)
    ker = paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_matches_reference_softcap(rng):
    q, kp, vp, tab, lens = _random_case(rng, 3, 2, 2, 16, 8, 4)
    ref = paged_attention_reference(q, kp, vp, tab, lens, softcap=10.0)
    ker = paged_decode_attention(q, kp, vp, tab, lens, softcap=10.0,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_exact_page_boundary_lengths(rng):
    """Lengths exactly on page boundaries (incl. the full table) — the
    last-page-exactly-full edge the scheduler also exercises."""
    B, nkv, g, hd, bs, P = 3, 2, 2, 8, 8, 2
    q, kp, vp, tab, _ = _random_case(rng, B, nkv, g, hd, bs, P)
    lens = jnp.asarray([bs, 2 * bs, 1], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, tab, lens)
    ker = paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tail", ["sentinel", "reserved"])
def test_dead_steps_fetch_the_last_live_page(rng, tail):
    """The K/V index_map walks a row's live pages, then repeats the last:
    one block per live page (one for an idle row) crosses HBM, and a dead
    step asks for the block already resident — also under worst-case
    reservation, where the entries past the length are real pages."""
    B, bs, P = 4, 8, 4
    lens = np.asarray([0, 1, 2 * bs, P * bs], np.int32)
    _, kp, _, tab, lens = _random_case(rng, B, 1, 1, 8, bs, P, lens=lens,
                                       tail=tail)
    nb = kp.shape[0]
    for b in range(B):
        n_live = max(-(-int(lens[b]) // bs), 1)
        walk = [int(_live_entry(b, j, tab, lens, bs=bs, nb=nb))
                for j in range(P)]
        live = np.minimum(np.asarray(tab)[b, :n_live], nb - 1).tolist()
        assert walk == live + live[-1:] * (P - n_live)


def test_reference_matches_dense_slab_math(rng):
    """The gather reference is bit-identical to the dense ``(B, S, nkv,
    hd)`` decode-attention math when pages are laid out contiguously —
    the property the engine golden tests build on."""
    B, nkv, g, hd, bs, P = 2, 2, 2, 16, 8, 2
    nh = nkv * g
    S = P * bs
    q = jnp.asarray(rng.normal(size=(B, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, nkv, hd)), jnp.float32)
    lens = jnp.asarray([5, 13], jnp.int32)

    # dense-slab math (attention_decode's score path, index = lens - 1)
    qg = q.reshape(B, nkv, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32) / math.sqrt(hd)
    valid = jnp.arange(S)[None, :] < lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    dense = jnp.einsum("bkgs,bskd->bkgd", p, v).reshape(B, nh, hd)

    # the same KV as per-row contiguous pages
    kp = k.reshape(B * P, bs, nkv, hd)
    vp = v.reshape(B * P, bs, nkv, hd)
    tab = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    ref = paged_attention_reference(q, kp, vp, tab, lens)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))


# ---------------------------------------------------------------------------
# Ragged-shape parity sweep: interpret-mode kernel vs gather reference over
# page-boundary lengths and awkward head shapes — float AND int8 KV paths.
# ---------------------------------------------------------------------------
def _sweep_lens(rng, B, bs, P, mode):
    """Row lengths exercising the mode: not-divisible, exact last block,
    the ±1 brackets around an exact last block; "idle" puts a length-0
    row beside the full table, an exactly full last page and one past it,
    and "reserved" serves the same lengths under worst-case reservation."""
    full = P * bs
    if mode == "ragged":  # S % bs != 0 everywhere
        lens = [(i * bs + 1 + int(rng.integers(0, bs - 1))) % full or 1
                for i in range(B)]
        lens = [ln if ln % bs else ln - 1 or 1 for ln in lens]
    elif mode == "exact":  # every row ends exactly on a page boundary
        lens = [((i % P) + 1) * bs for i in range(B)]
    elif mode == "exact±1":  # brackets around the boundary (and full table)
        lens = [max(1, bs - 1), bs + 1, full, max(1, full - 1)][:B]
    else:  # "idle" / "reserved": an idle slot among live rows
        lens = [0, full, bs, bs + 1][:B]
    return np.asarray(lens, np.int32)


@pytest.mark.parametrize("kv", ["float", "int8"])
@pytest.mark.parametrize("bs", [8, 16, 128])
@pytest.mark.parametrize("mode",
                         ["ragged", "exact", "exact±1", "idle", "reserved"])
def test_kernel_parity_sweep_block_sizes(rng, kv, bs, mode):
    B, nkv, g, hd, P = 4, 2, 2, 16, 2 if bs == 128 else 3
    lens = _sweep_lens(rng, B, bs, P, mode)
    tail = "reserved" if mode == "reserved" else "sentinel"
    q, kp, vp, tab, lens = _random_case(rng, B, nkv, g, hd, bs, P,
                                        lens=lens, tail=tail)
    if kv == "float":
        ref = paged_attention_reference(q, kp, vp, tab, lens)
        ker = paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
        tol = dict(rtol=1e-5, atol=1e-5)
    else:
        kc, vc, scales = _quantize_case(kp, vp)
        ref = paged_attention_reference(q, kc, vc, tab, lens, **scales)
        ker = paged_decode_attention(q, kc, vc, tab, lens, interpret=True,
                                     assert_bounds=True, **scales)
        # the kernel runs the integer datapath (q and softmax probabilities
        # quantized on top of the shared KV codes); the reference dequantizes
        # and runs float math — agreement is to quantization tolerance
        tol = dict(rtol=5e-2, atol=5e-2)
    # an idle row attends over nothing: the kernel writes zeros (the
    # reference's softmax over no position is undefined)
    live = np.asarray(lens) > 0
    np.testing.assert_array_equal(np.asarray(ker)[~live], 0.0)
    np.testing.assert_allclose(np.asarray(ker)[live], np.asarray(ref)[live],
                               **tol)


@pytest.mark.parametrize("kv", ["float", "int8"])
@pytest.mark.parametrize("tail", ["sentinel", "reserved"])
def test_kernel_never_reads_dead_pages(rng, kv, tail):
    """Every page a row's table points at past its length, and page nb-1
    where sentinels clamp, holds NaN: the kernel's output stays finite and
    equal to the reference on the clean pool, and is bit-equal whether
    the dead entries are sentinels, reserved pages or other rows' live
    pages."""
    B, nkv, g, hd, bs, P = 4, 2, 2, 16, 8, 4
    lens = np.asarray([1, 2 * bs, 3 * bs + 3, 0], np.int32)
    q, kp, vp, tab, lens = _random_case(rng, B, nkv, g, hd, bs, P,
                                        lens=lens, tail=tail)
    nb = kp.shape[0]
    dead = _dead_entries(tab, lens, bs)
    poisoned = np.unique(np.append(np.minimum(np.asarray(tab)[dead], nb - 1),
                                   nb - 1))
    live_pages = np.asarray(tab)[~dead]
    assert not np.isin(poisoned, live_pages).any()
    # the same rows with their dead entries aimed at live pages instead
    aliased = np.where(dead, rng.choice(live_pages, size=tab.shape), tab)

    if kv == "float":
        ref = paged_attention_reference(q, kp, vp, tab, lens)
        kp, vp = kp.at[poisoned].set(jnp.nan), vp.at[poisoned].set(jnp.nan)
        kw, tol = {}, dict(rtol=1e-5, atol=1e-5)
    else:
        kp, vp, kw = _quantize_case(kp, vp)
        ref = paged_attention_reference(q, kp, vp, tab, lens, **kw)
        # int8 codes hold no NaN: poison the dead pages' scales
        kw = {name: sc.at[poisoned].set(jnp.nan) for name, sc in kw.items()}
        kw["assert_bounds"] = True
        tol = dict(rtol=5e-2, atol=5e-2)

    ker = np.asarray(paged_decode_attention(q, kp, vp, tab, lens,
                                            interpret=True, **kw))
    assert np.isfinite(ker).all()
    live = np.asarray(lens) > 0
    np.testing.assert_array_equal(ker[~live], 0.0)
    np.testing.assert_allclose(ker[live], np.asarray(ref)[live], **tol)
    ker_aliased = paged_decode_attention(q, kp, vp, jnp.asarray(aliased),
                                         lens, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(ker_aliased), ker)


@pytest.mark.parametrize("kv", ["float", "int8"])
@pytest.mark.parametrize(
    "B,nkv,g,hd,bs,P",
    [
        (3, 1, 3, 7, 8, 3),   # odd nh (3) and odd hd (7)
        (2, 3, 1, 16, 8, 2),  # odd nkv == nh
        (3, 1, 5, 11, 16, 2),  # odd everything, MQA grouping
    ],
)
def test_kernel_parity_odd_heads(rng, kv, B, nkv, g, hd, bs, P):
    q, kp, vp, tab, lens = _random_case(rng, B, nkv, g, hd, bs, P)
    if kv == "float":
        ref = paged_attention_reference(q, kp, vp, tab, lens)
        ker = paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
        tol = dict(rtol=1e-5, atol=1e-5)
    else:
        kc, vc, scales = _quantize_case(kp, vp)
        ref = paged_attention_reference(q, kc, vc, tab, lens, **scales)
        ker = paged_decode_attention(q, kc, vc, tab, lens, interpret=True,
                                     assert_bounds=True, **scales)
        tol = dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), **tol)


# ---------------------------------------------------------------------------
# int8 KV: the quantized gather reference is the quantize→dequantize image
# of the dense-slab math — bit-identical (the golden anchor the engine
# accuracy test builds on).
# ---------------------------------------------------------------------------
def test_quantized_reference_is_dequantized_dense_math(rng):
    B, nkv, g, hd, bs, P = 2, 2, 2, 16, 8, 2
    nh = nkv * g
    S = P * bs
    q = jnp.asarray(rng.normal(size=(B, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, nkv, hd)), jnp.float32)
    lens = jnp.asarray([5, 13], jnp.int32)

    # quantize per page, then lay the *dequantized* values back into a
    # dense slab and run the dense-slab decode-attention math on them
    kc, ks = quantize_kv_pages(k.reshape(B * P, bs, nkv, hd))
    vc, vs = quantize_kv_pages(v.reshape(B * P, bs, nkv, hd))
    k_dq = dequantize_kv_pages(kc, ks).reshape(B, S, nkv, hd)
    v_dq = dequantize_kv_pages(vc, vs).reshape(B, S, nkv, hd)
    qg = q.reshape(B, nkv, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_dq).astype(jnp.float32)
    s = s / math.sqrt(hd)
    valid = jnp.arange(S)[None, :] < lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    dense = jnp.einsum("bkgs,bskd->bkgd", p, v_dq).reshape(B, nh, hd)

    tab = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    ref = paged_attention_reference(q, kc, vc, tab, lens,
                                    k_scales=ks, v_scales=vs)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))


def test_quantize_kv_pages_roundtrip(rng):
    """Per-(page, head) symmetric quantization: codes bounded by the int8
    alphabet, round-trip error within half a step of each page's scale,
    and all-zero pages keep the 1e-8 floor scale (no NaNs)."""
    pages = jnp.asarray(rng.normal(size=(5, 8, 3, 16)) * 4.0, jnp.float32)
    pages = pages.at[0].set(0.0)
    codes, scales = quantize_kv_pages(pages)
    assert codes.dtype == jnp.int8 and scales.shape == (5, 3)
    assert int(jnp.max(jnp.abs(codes))) <= 127
    assert float(scales[0].min()) == pytest.approx(1e-8)
    err = jnp.abs(dequantize_kv_pages(codes, scales) - pages)
    assert float(jnp.max(err / scales[:, None, :, None])) <= 0.5 + 1e-6


def test_quantized_scale_indexing_follows_block_table(rng):
    """Pages with wildly different magnitudes: the reference must pair each
    gathered page with *its* scale through the same table indirection (a
    mispairing is off by orders of magnitude, not tolerance)."""
    B, nkv, g, hd, bs, P = 2, 2, 1, 8, 4, 2
    q, kp, vp, tab, lens = _random_case(rng, B, nkv, g, hd, bs, P)
    # scale page magnitudes by their pool index so every page differs
    mags = jnp.exp(jnp.linspace(0.0, 4.0, kp.shape[0]))[:, None, None, None]
    kc, vc, scales = _quantize_case(kp * mags, vp * mags)
    ref = paged_attention_reference(q, kc, vc, tab, lens, **scales)
    ker = paged_decode_attention(q, kc, vc, tab, lens, interpret=True,
                                 **scales)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)
