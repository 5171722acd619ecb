"""Continuous-batching generation engine over a paged KV cache.

Where :class:`~repro.serving.engine.GenerationEngine` is fixed-slot (all
sequences enter and leave together, against a dense ``(B, S_max, ...)``
cache slab), this engine runs an open system: requests queue in a
host-side :class:`~repro.serving.scheduler.Scheduler`, are admitted into
whichever engine slot is free, decode together regardless of phase, and
free their KV pages the moment they finish — so a skewed-length trace
keeps every slot busy instead of idling behind the batch's longest
member, and HBM holds ``num_blocks`` pages instead of
``max_concurrency * S_max`` dense rows.

Three jitted device programs, all operating on one cache pytree
(:func:`repro.models.transformer.init_paged_cache`):

* **admit** — pop ``n_pages`` from the device free-list stack, prefill
  the prompt (B=1), scatter its KV into the popped pages, splice
  recurrent-mixer state into the slot via ``dynamic_update_slice``, and
  sample the first token. One trace per (prompt_len, n_pages) bucket.
* **decode chunk** — up to ``chunk_max`` fused decode steps
  (``lax.while_loop`` with a *dynamic* trip count ``k``, so one trace
  serves every chunk length); every live slot advances at its own
  length. The host syncs once per chunk, not once per token.
* **release** — drop one reader from each of the slot's pages
  (``page_refcounts`` leaf) and push the ones that hit zero back onto the
  free-list stack (dynamic count — one trace for every page mix), then
  clear the active bit.

With ``PagedConfig.prefix_cache=True`` two more admit variants join:
**suffix admit** (block table points at cached prefix pages, prefill runs
only the uncached tail against the gathered prefix KV) and **cached
admit** (fully cached prompt: no prefill forward pass at all — the
program takes no params and is structurally FLOP-free; the first token
defers to the next decode chunk with an unchanged sampling stream). See
``repro.serving.prefix_cache`` and docs/serving_scheduler.md.

Sampling is per-request deterministic: slot ``b``'s step ``t`` key is
``fold_in(fold_in(key(seed), uid_b), t)``, so a request's sampled tokens
do not depend on what else happens to be in flight. Greedy decode is
bit-identical to the fixed-slot engine (golden-pinned in
``tests/test_paged_engine.py``).
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass
from functools import partial

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.config import ModelConfig
from repro.models.layers import (
    attach_observer,
    packed_backend,
    resolve_paged_attn_impl,
    use_packed_backend,
)
from repro.quant.observe import SaturationCounters, plan_kv_scales
from repro.models.transformer import (
    decode_step_paged,
    init_paged_cache,
    prefill,
)
from repro.quant.serve_packed import upgrade_packed_params
from repro.runtime import sharding as shardlib
from repro.quant.spec import (
    AttnDatapathSpec,
    tree_datapath_fingerprint,
    validate_attn_datapath,
    validate_datapath,
)
from repro.serving.engine import SamplerConfig, _sample
from repro.serving.metrics import (
    SPAN_ADMIT,
    SPAN_DECODE,
    SPAN_GROW,
    SPAN_LATE,
    SPAN_PASS,
    SPAN_PLAN,
    SPAN_PREFILL,
    SPAN_READBACK,
    SPAN_RECORD,
    SPAN_RELEASE,
    ServeCounters,
    ServeMetrics,
    pages_filled,
)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import (
    PoolState,
    Request,
    Scheduler,
    SchedulerPolicy,
)


@dataclass(frozen=True)
class PagedConfig:
    """Paged-cache + continuous-batching knobs.

    ``num_blocks`` sizes the shared KV pool (HBM bytes scale with it —
    see docs/serving_scheduler.md for the accounting); ``block_size`` is
    the page granularity; ``max_pages_per_seq`` caps one sequence's block
    table row (defaults to ``ceil(max_seq_len / block_size)``);
    ``chunk_max`` bounds how many decode steps run per host sync.
    ``kv_dtype="int8"`` stores *quantized* KV pages (int8 codes +
    per-(page, kv-head) scale leaves — pool HBM halves, so an HBM budget
    admits ~2x the sequences; see ``scheduler.blocks_for_budget``) and
    attention runs the AttnDatapathSpec-certified integer datapath;
    ``"act"`` keeps ``cfg.act_dtype`` float pages.
    """

    block_size: int = 64
    num_blocks: int = 256
    max_concurrency: int = 8
    max_pages_per_seq: int | None = None
    chunk_max: int = 32
    attn_impl: str = "auto"  # auto | ref | kernel | interpret
    kv_dtype: str = "act"  # act (= cfg.act_dtype) | int8 (quantized pages)
    #: share full, immutable prompt blocks across requests through a
    #: host-side radix map over block digests (repro.serving.prefix_cache)
    #: plus per-page refcounts; repeated prefixes prefill only their
    #: uncached suffix (a fully cached prompt runs NO prefill forward
    #: pass). Requires an attention-only pattern: recurrent mixers keep
    #: dense per-slot state that is not paged and cannot be shared.
    prefix_cache: bool = False
    #: admission/decode policy (repro.serving.scheduler.SchedulerPolicy).
    #: The default is legacy FIFO — bit-compatible with prior releases
    #: and the baseline the latency bench compares against. Any
    #: non-default field (windowed/batched admission, chunked prefill,
    #: watermark + preemption) switches serve() to the throughput loop.
    sched: SchedulerPolicy = SchedulerPolicy()


def _fold_keys(seed: int, uids, steps):
    base = jax.random.key(seed)
    return jax.vmap(
        lambda u, t: jax.random.fold_in(jax.random.fold_in(base, u), t)
    )(uids, steps)


def _sample_rows(logits, temperature: float, keys):
    """Per-row sampling with per-slot keys (request-deterministic) —
    vmaps the fixed-slot engine's ``_sample`` so both engines share one
    sampler (the greedy bit-identity guarantee rests on this)."""
    if temperature <= 0.0:
        return _sample(logits, temperature, None)
    return jax.vmap(lambda k, row: _sample(row, temperature, k))(keys, logits)


class PagedEngine:
    def __init__(self, params, cfg: ModelConfig, paged: PagedConfig = PagedConfig(),
                 sampler: SamplerConfig = SamplerConfig(), datapath=None,
                 attn_datapath=None, observe: bool = False, kv_scales=None,
                 mesh=None, shard_rules=None):
        self.params = upgrade_packed_params(params)
        if datapath is not None:
            validate_datapath(self.params, datapath)
        self.datapath_fingerprint = tree_datapath_fingerprint(self.params)
        self.cfg = cfg
        self.sampler = sampler
        if paged.kv_dtype not in ("act", "int8"):
            raise ValueError(f"kv_dtype {paged.kv_dtype!r} not in ('act', 'int8')")
        max_pages = paged.max_pages_per_seq or -(-cfg.max_seq_len // paged.block_size)
        self.paged = paged = PagedConfig(
            block_size=paged.block_size, num_blocks=paged.num_blocks,
            max_concurrency=paged.max_concurrency, max_pages_per_seq=max_pages,
            chunk_max=paged.chunk_max, attn_impl=paged.attn_impl,
            kv_dtype=paged.kv_dtype, prefix_cache=paged.prefix_cache,
            sched=paged.sched,
        )
        recurrent = sorted({s.mixer for s in cfg.pattern
                            if s.mixer not in ("attn", "none")})
        if paged.prefix_cache and recurrent:
            raise ValueError(
                f"prefix_cache=True needs an attention-only pattern: "
                f"{recurrent} mixers keep dense per-slot state that is "
                f"not paged and cannot be shared across requests"
            )
        pol = paged.sched
        if pol.batch_max > 1 or pol.prefill_chunk is not None:
            # batched rows compete for MoE expert capacity (per-row
            # routing is not independent of co-batched traffic), and
            # recurrent mixers step state through pad tokens — both break
            # the per-request bit-identity guarantee, so the policy
            # refuses them rather than silently drifting
            has_moe = any(s.ffn == "moe" for s in cfg.pattern)
            if recurrent or has_moe:
                what = "batched admission" if pol.batch_max > 1 else \
                    "chunked prefill"
                raise ValueError(
                    f"{what} needs an attention-only, MoE-free pattern "
                    f"(recurrent mixers {recurrent or 'none'}, moe ffn "
                    f"{has_moe}): padded multi-row / chunked prefill would "
                    f"change routing or stepwise state and break greedy "
                    f"bit-identity with the FIFO engine"
                )
        if pol.prefill_chunk is not None and (
                pol.prefill_chunk % paged.block_size != 0):
            raise ValueError(
                f"prefill_chunk {pol.prefill_chunk} must be a multiple of "
                f"block_size {paged.block_size} (chunks scatter whole pages)")
        if pol.watermark is not None and pol.watermark[1] > paged.num_blocks:
            raise ValueError(
                f"watermark high {pol.watermark[1]} > num_blocks "
                f"{paged.num_blocks} — admission could never resume")
        self.prefix_cache = (
            PrefixCache(paged.num_blocks, paged.block_size)
            if paged.prefix_cache else None
        )
        #: host mirror of the device page allocator + refcounts — persists
        #: across serve() calls (cached pages stay out of the free stack)
        self.pool_state = PoolState.fresh(paged.num_blocks)
        #: the attention accumulator record the quantized kernel serves
        #: (None for float KV) — the attention analogue of the per-site
        #: DatapathSpec; ``attn_datapath`` is a *request* validated
        #: against it exactly like ``datapath`` against the packed leaves
        self.attn_spec = (
            AttnDatapathSpec.for_cache(cfg.head_dim, paged.block_size)
            if paged.kv_dtype == "int8" else None
        )
        if attn_datapath is not None:
            validate_attn_datapath(self.attn_spec, attn_datapath)
        #: serving-side observer (repro.quant.observe.saturation): host
        #: state fed through ``jax.debug.callback``. None (the default)
        #: keeps every serving jaxpr callback-free — structurally
        #: certified by :meth:`assert_observation_transparent`.
        self.observer = SaturationCounters() if observe else None
        #: calibrated static KV page scales from a mixed-precision plan's
        #: ``kv`` section (repro.quant.observe.kv): {slot: {"k": (R, nkv)
        #: f32, "v": ...}}. Appends and prefill scatters quantize against
        #: these constants — no per-page max reduction, no
        #: requantize-on-append on the decode hot path.
        if kv_scales is not None and "slots" in kv_scales:
            kv_scales = plan_kv_scales(kv_scales)
        if kv_scales and paged.kv_dtype != "int8":
            raise ValueError(
                "static kv_scales need kv_dtype='int8' (float pools carry "
                "no scale leaves)")
        self.kv_scales = kv_scales or None
        #: pattern-aligned tuple joined to decode_step_paged's scan xs
        #: (empty dicts contribute no scan leaves; None = fully dynamic,
        #: which leaves the decode jaxpr byte-identical to the baseline)
        self._kv_scales_seq = None
        if self.kv_scales:
            for slot in self.kv_scales:
                if not (0 <= slot < len(cfg.pattern)
                        and cfg.pattern[slot].mixer == "attn"):
                    raise ValueError(
                        f"kv_scales names slot {slot}, which is not an "
                        f"attention slot of the {len(cfg.pattern)}-slot "
                        f"pattern")
            self._kv_scales_seq = tuple(
                {"k": jnp.asarray(self.kv_scales[i]["k"], jnp.float32),
                 "v": jnp.asarray(self.kv_scales[i]["v"], jnp.float32)}
                if i in self.kv_scales else {}
                for i in range(len(cfg.pattern)))
        # observation and static KV participate in trace identity: suffix
        # the jit cache key so a plan-bearing engine never reuses a
        # dynamic-scale trace (and vice versa)
        if observe:
            self.datapath_fingerprint += "+obs"
        if self._kv_scales_seq is not None:
            self.datapath_fingerprint += "+kv-static"
        self.cache = init_paged_cache(
            cfg, paged.max_concurrency, paged.num_blocks, paged.block_size,
            max_pages,
            kv_dtype="int8" if paged.kv_dtype == "int8" else None,
        )
        #: SPMD mesh for the three program families (docs/multihost.md):
        #: pools shard kv_heads, admin leaves replicate, every host-read
        #: output is fully replicated. None = the single-controller engine.
        self.mesh = mesh
        self._out_params = self._out_cache = self._out_rep = None
        if mesh is not None:
            resolved = resolve_paged_attn_impl(paged.attn_impl)
            if resolved != "ref":
                raise ValueError(
                    f"mesh-native serving requires the partitionable 'ref' "
                    f"attention impl (resolved {resolved!r}): the Pallas "
                    f"block-table kernel is a single-device program until "
                    f"the TPU pass wraps it in shard_map (ROADMAP item 4)")
            if observe and jax.process_count() > 1:
                raise ValueError(
                    "observe=True is single-controller: the saturation "
                    "debug_callback would fire per-process on partial "
                    "shards — run observation on a one-process mesh")
            self._out_params, self._out_cache = shardlib.paged_engine_shardings(
                self.params, self.cache, cfg, mesh, shard_rules)
            self._out_rep = shardlib.replicated(mesh)
            # global placement: every process holds the identical full
            # value (seed-deterministic init), so the host copy IS the
            # global value — multihost-safe by construction
            self.params = shardlib.host_to_global(self.params,
                                                  self._out_params)
            self.cache = shardlib.host_to_global(self.cache, self._out_cache)
        #: trace counters (python side effects — bump at trace time only)
        self.admit_traces = 0
        self.suffix_traces = 0
        self.cached_traces = 0
        self.chunk_traces = 0
        self.release_traces = 0
        self.batch_traces = 0
        self.stub_traces = 0
        self.prefill_chunk_traces = 0
        self.grow_traces = 0
        #: what the serve loops have done across serve() calls
        self.counters = ServeCounters()
        self._uid_gen = 0

        def _osh(*out):
            """Explicit out_shardings for a mesh-native program: the cache
            operand comes back under exactly its input shardings (donation
            stays alias-exact) and every token output fully replicated —
            the contract that keeps host reads local on every process.
            Empty under the single-controller engine (XLA default)."""
            if mesh is None:
                return {}
            return {"out_shardings": out[0] if len(out) == 1 else out}

        _cache_sh, _rep = self._out_cache, self._out_rep

        # the cache pytree is DONATED to every program: it crosses the jit
        # boundary once per chunk/admit (unlike the dense engine, whose
        # cache lives inside one fused generate call), and without
        # donation each call would materialize a second full copy of the
        # KV page pools — 2x the HBM the pool was sized for
        @partial(jax.jit, static_argnames=("n_pages", "backend", "attn_impl",
                                           "datapath"),
                 donate_argnames=("cache",), **_osh(_cache_sh, _rep))
        def _admit(params, cache, prompt, slot, uid, incs, n_pages, backend,
                   attn_impl, datapath):
            with use_packed_backend(backend):
                return self._admit_impl(params, cache, prompt, slot, uid,
                                        incs, n_pages)

        @partial(jax.jit, static_argnames=("n_pages", "n_shared", "backend",
                                           "attn_impl", "datapath"),
                 donate_argnames=("cache",), **_osh(_cache_sh, _rep))
        def _admit_suffix(params, cache, suffix, shared_pages, slot, uid,
                          incs, n_pages, n_shared, backend, attn_impl,
                          datapath):
            with use_packed_backend(backend):
                return self._admit_suffix_impl(params, cache, suffix,
                                               shared_pages, slot, uid, incs,
                                               n_pages, n_shared)

        @partial(jax.jit, static_argnames=("n_pages", "n_shared"),
                 donate_argnames=("cache",), **_osh(_cache_sh))
        def _admit_cached(cache, shared_pages, cow_src, slot, uid, s0,
                          last_tok, incs, n_pages, n_shared):
            return self._admit_cached_impl(cache, shared_pages, cow_src,
                                           slot, uid, s0, last_tok, incs,
                                           n_pages, n_shared)

        @partial(jax.jit, static_argnames=("backend", "attn_impl", "datapath",
                                           "attn_spec"),
                 donate_argnames=("cache",), **_osh(_cache_sh, _rep))
        def _chunk(params, cache, k, backend, attn_impl, datapath, attn_spec):
            with use_packed_backend(backend):
                return self._chunk_impl(params, cache, k, attn_impl, attn_spec)

        @partial(jax.jit, donate_argnames=("cache",), **_osh(_cache_sh))
        def _release(cache, slot, pages, n):
            return self._release_impl(cache, slot, pages, n)

        @partial(jax.jit, static_argnames=("n_rows", "n_prompt_pages",
                                           "backend", "attn_impl",
                                           "datapath"),
                 donate_argnames=("cache",), **_osh(_cache_sh, _rep))
        def _admit_batch(params, cache, tokens, s0s, slots, uids, rows,
                         scatter_idx, incs, total_pop, n_rows,
                         n_prompt_pages, backend, attn_impl, datapath):
            with use_packed_backend(backend):
                return self._admit_batch_impl(params, cache, tokens, s0s,
                                              slots, uids, rows, scatter_idx,
                                              incs, total_pop, n_prompt_pages)

        @partial(jax.jit, donate_argnames=("cache",), **_osh(_cache_sh))
        def _admit_stub(cache, row, slot, uid, incs, n_pages):
            return self._admit_stub_impl(cache, row, slot, uid, incs, n_pages)

        @partial(jax.jit, donate_argnames=("cache",), **_osh(_cache_sh))
        def _grow(cache, slot, row, add, n_new):
            return self._grow_impl(cache, slot, row, add, n_new)

        @partial(jax.jit, static_argnames=("n_prior", "n_chunk_pages",
                                           "final", "backend", "attn_impl",
                                           "datapath"),
                 donate_argnames=("cache",), **_osh(_cache_sh, _rep))
        def _prefill_chunk(params, cache, tokens, slot, uid, s0, incs,
                           n_prior, n_chunk_pages, final, backend, attn_impl,
                           datapath):
            with use_packed_backend(backend):
                return self._prefill_chunk_impl(params, cache, tokens, slot,
                                                uid, s0, incs, n_prior,
                                                n_chunk_pages, final)

        self._admit = _admit
        self._admit_suffix = _admit_suffix
        self._admit_cached = _admit_cached
        self._chunk = _chunk
        self._release = _release
        self._admit_batch = _admit_batch
        self._admit_stub = _admit_stub
        self._grow = _grow
        self._prefill_chunk = _prefill_chunk

    # ------------------------------------------------------------------
    # Device programs (traced bodies)
    # ------------------------------------------------------------------
    def _quantize_pages(self, slot: int, k_pages, v_pages):
        """Quantize dense KV pages for pool slot ``slot`` — against the
        plan's calibrated static per-kv-head scales when the engine holds
        them (constant stamp, no per-page max reduction), else the dynamic
        per-(page, head) abs-max path. Returns (kc, ks, vc, vs)."""
        from repro.kernels.paged_attention import (
            quantize_kv_pages,
            quantize_kv_pages_static,
        )

        sks = self._kv_scales_seq[slot] if self._kv_scales_seq else None
        if sks:
            kc, ks = quantize_kv_pages_static(k_pages, sks["k"][:, None, :])
            vc, vs = quantize_kv_pages_static(v_pages, sks["v"][:, None, :])
        else:
            kc, ks = quantize_kv_pages(k_pages)
            vc, vs = quantize_kv_pages(v_pages)
        return kc, ks, vc, vs

    def _admit_impl(self, params, cache, prompt, slot, uid, incs,
                    n_pages: int):
        """Admit one request into ``slot``: allocate pages, prefill, splice
        state, sample the generation's first token. ``incs`` is the host's
        per-row-position refcount increment vector (1 per entry, +1 extra
        for fresh blocks the prefix cache registers)."""
        self.admit_traces += 1
        cfg, paged = self.cfg, self.paged
        bs = paged.block_size
        _, s0 = prompt.shape  # (1, S0)
        n_prompt_pages = -(-s0 // bs)
        prefill_len = n_prompt_pages * bs

        # pop n_pages off the free-list stack (host guarantees capacity)
        top = cache["free_top"]
        pages = jax.lax.dynamic_slice(cache["free_list"], (top,), (n_pages,))
        row = jnp.full((paged.max_pages_per_seq,), paged.num_blocks, jnp.int32)
        row = row.at[:n_pages].set(pages)
        table = jax.lax.dynamic_update_slice(
            cache["block_table"], row[None], (slot, jnp.int32(0))
        )

        logits, dense = prefill(params, {"tokens": prompt}, cfg, prefill_len)
        prompt_pages = pages[:n_prompt_pages]
        pools = []
        for i, spec in enumerate(cfg.pattern):
            c = cache["pools"][i]
            d = dense[i]
            if spec.mixer == "attn":
                # (R, 1, prefill_len, nkv, hd) -> per-page scatter into pool
                def to_pages(a):
                    r, _, _, nkv, hd = a.shape
                    return a.reshape(r, n_prompt_pages, bs, nkv, hd)

                if "k_scales" in c:
                    # quantize-on-scatter: codes + per-(page, head) scales
                    # stamped together (padded tail positions are zeros and
                    # cannot raise a page's max)
                    kc, ks, vc, vs = self._quantize_pages(i, to_pages(d["k"]),
                                                          to_pages(d["v"]))
                    pools.append({
                        "k_pages": c["k_pages"].at[:, prompt_pages].set(kc),
                        "v_pages": c["v_pages"].at[:, prompt_pages].set(vc),
                        "k_scales": c["k_scales"].at[:, prompt_pages].set(ks),
                        "v_scales": c["v_scales"].at[:, prompt_pages].set(vs),
                    })
                else:
                    kp = c["k_pages"].at[:, prompt_pages].set(
                        to_pages(d["k"]).astype(c["k_pages"].dtype))
                    vp = c["v_pages"].at[:, prompt_pages].set(
                        to_pages(d["v"]).astype(c["v_pages"].dtype))
                    pools.append({"k_pages": kp, "v_pages": vp})
            elif spec.mixer != "none":
                # recurrent state: splice the (R, 1, ...) prefill state into
                # the slot's lane of the (R, num_slots, ...) batch
                merged = {}
                for k, leaf in c.items():
                    idx = (jnp.int32(0), slot) + (jnp.int32(0),) * (leaf.ndim - 2)
                    merged[k] = jax.lax.dynamic_update_slice(
                        leaf, d[k].astype(leaf.dtype), idx)
                pools.append(merged)
            else:
                pools.append(c)

        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(self.sampler.seed), uid),
            jnp.int32(0))
        nxt = _sample(logits[:, -1], self.sampler.temperature, key)  # (1,)

        new = dict(cache)
        new["pools"] = tuple(pools)
        new["block_table"] = table
        new["free_top"] = top + n_pages
        new["page_refcounts"] = cache["page_refcounts"].at[row].add(
            incs, mode="drop")  # sentinel row entries drop
        new["seq_lens"] = cache["seq_lens"].at[slot].set(s0)
        new["active"] = cache["active"].at[slot].set(True)
        new["uids"] = cache["uids"].at[slot].set(uid)
        new["steps"] = cache["steps"].at[slot].set(1)
        new["last_tok"] = cache["last_tok"].at[slot].set(nxt[0])
        return new, nxt[0]

    def _gather_prefix_kv(self, cache, pages, prefix_len: int):
        """Gather the KV held in ``pages`` — dequantized, for int8 pools
        (page-exact: codes x per-(page, head) scale) — as dense
        ``(R, 1, prefix_len, nkv, hd)`` prefix tensors for a suffix or
        chunked prefill. Returns a tuple aligned with ``cfg.pattern``
        (non-attention entries empty — the engine gates recurrent
        patterns off every path that calls this)."""

        def gather(p, scales=None):
            g = p[:, pages]  # (R, n_pages, bs, nkv, hd)
            if scales is not None:  # int8 codes -> float (page-exact)
                g = g.astype(jnp.float32) * (
                    scales[:, pages][..., None, :, None])
            r, _, _, nkv, hd = g.shape
            return g.reshape(r, 1, prefix_len, nkv, hd)

        prefix_kv = []
        for i, spec in enumerate(self.cfg.pattern):
            if spec.mixer != "attn":
                prefix_kv.append({})
                continue
            c = cache["pools"][i]
            if "k_scales" in c:
                prefix_kv.append(
                    {"k": gather(c["k_pages"], c["k_scales"]),
                     "v": gather(c["v_pages"], c["v_scales"])})
            else:
                prefix_kv.append({"k": gather(c["k_pages"]),
                                  "v": gather(c["v_pages"])})
        return tuple(prefix_kv)

    def _scatter_dense_pages(self, cache, dense, pages, n_pages: int):
        """Scatter a B=1 prefill's dense KV into ``pages`` (quantize-on-
        scatter for int8 pools: codes + per-(page, head) scales stamped
        together). Non-attention pools pass through untouched ("none"
        mixers only — the engine gates recurrent patterns)."""
        bs = self.paged.block_size
        pools = []
        for i, spec in enumerate(self.cfg.pattern):
            c = cache["pools"][i]
            if spec.mixer != "attn":
                pools.append(c)
                continue
            d = dense[i]

            def to_pages(a):
                r, _, _, nkv, hd = a.shape
                return a.reshape(r, n_pages, bs, nkv, hd)

            if "k_scales" in c:
                kc, ks, vc, vs = self._quantize_pages(i, to_pages(d["k"]),
                                                      to_pages(d["v"]))
                pools.append({
                    "k_pages": c["k_pages"].at[:, pages].set(kc),
                    "v_pages": c["v_pages"].at[:, pages].set(vc),
                    "k_scales": c["k_scales"].at[:, pages].set(ks),
                    "v_scales": c["v_scales"].at[:, pages].set(vs),
                })
            else:
                kp = c["k_pages"].at[:, pages].set(
                    to_pages(d["k"]).astype(c["k_pages"].dtype))
                vp = c["v_pages"].at[:, pages].set(
                    to_pages(d["v"]).astype(c["v_pages"].dtype))
                pools.append({"k_pages": kp, "v_pages": vp})
        return tuple(pools)

    def _admit_suffix_impl(self, params, cache, suffix, shared_pages, slot,
                           uid, incs, n_pages: int, n_shared: int):
        """Shared-prefix admit: the request's first ``n_shared`` logical
        blocks point at existing (refcounted, immutable) pages; only the
        uncached suffix runs a prefill forward pass, attending over the
        cached prefix KV gathered — and dequantized, for int8 pools —
        straight out of the shared pages. One trace per
        (suffix_len, n_pages, n_shared) bucket."""
        self.suffix_traces += 1
        cfg, paged = self.cfg, self.paged
        bs = paged.block_size
        _, t = suffix.shape  # (1, T) — the uncached prompt tail
        prefix_len = n_shared * bs
        s0 = prefix_len + t
        n_suffix_pages = -(-t // bs)
        prefill_len = n_suffix_pages * bs
        n_pop = n_pages - n_shared

        top = cache["free_top"]
        popped = jax.lax.dynamic_slice(cache["free_list"], (top,), (n_pop,))
        row = jnp.full((paged.max_pages_per_seq,), paged.num_blocks, jnp.int32)
        row = row.at[:n_shared].set(shared_pages)
        row = row.at[n_shared:n_shared + n_pop].set(popped)
        table = jax.lax.dynamic_update_slice(
            cache["block_table"], row[None], (slot, jnp.int32(0)))

        prefix_kv = self._gather_prefix_kv(cache, shared_pages, prefix_len)
        logits, dense = prefill(params, {"tokens": suffix}, cfg, prefill_len,
                                prefix_kv=prefix_kv, pos_offset=prefix_len)
        pools = self._scatter_dense_pages(cache, dense,
                                          popped[:n_suffix_pages],
                                          n_suffix_pages)

        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(self.sampler.seed), uid),
            jnp.int32(0))
        nxt = _sample(logits[:, -1], self.sampler.temperature, key)  # (1,)

        new = dict(cache)
        new["pools"] = tuple(pools)
        new["block_table"] = table
        new["free_top"] = top + n_pop
        new["page_refcounts"] = cache["page_refcounts"].at[row].add(
            incs, mode="drop")
        new["seq_lens"] = cache["seq_lens"].at[slot].set(s0)
        new["active"] = cache["active"].at[slot].set(True)
        new["uids"] = cache["uids"].at[slot].set(uid)
        new["steps"] = cache["steps"].at[slot].set(1)
        new["last_tok"] = cache["last_tok"].at[slot].set(nxt[0])
        return new, nxt[0]

    def _admit_cached_impl(self, cache, shared_pages, cow_src, slot, uid, s0,
                           last_tok, incs, n_pages: int, n_shared: int):
        """Fully-cached admit: NO prefill forward pass (takes no params at
        all — structurally FLOP-free, see :meth:`cached_admit_primitives`).
        The prompt's blocks are all cached; the last one is copied into a
        freshly popped private page (copy-on-write: decode appends rewrite
        position ``s0 - 1`` and grow page scales, which must never touch a
        shared page). The first token is *deferred*: ``seq_lens = s0 - 1``,
        ``steps = 0`` and ``last_tok = prompt[-1]`` hand the last prompt
        token to the next decode chunk, whose first step computes exactly
        the cold prefill's final-position logits and samples with the same
        ``fold_in(uid, 0)`` key — the sampling stream is unchanged."""
        self.cached_traces += 1
        paged = self.paged
        n_pop = n_pages - n_shared
        top = cache["free_top"]
        popped = jax.lax.dynamic_slice(cache["free_list"], (top,), (n_pop,))
        dest = popped[0]
        row = jnp.full((paged.max_pages_per_seq,), paged.num_blocks, jnp.int32)
        row = row.at[:n_shared].set(shared_pages)
        row = row.at[n_shared:n_shared + n_pop].set(popped)
        table = jax.lax.dynamic_update_slice(
            cache["block_table"], row[None], (slot, jnp.int32(0)))

        pools = []
        for i, spec in enumerate(self.cfg.pattern):
            c = cache["pools"][i]
            if spec.mixer == "attn":
                # CoW: copy codes AND scales — the private copy must
                # dequantize identically until the first append
                pools.append({k: leaf.at[:, dest].set(leaf[:, cow_src])
                              for k, leaf in c.items()})
            else:
                pools.append(c)

        new = dict(cache)
        new["pools"] = tuple(pools)
        new["block_table"] = table
        new["free_top"] = top + n_pop
        new["page_refcounts"] = cache["page_refcounts"].at[row].add(
            incs, mode="drop")
        new["seq_lens"] = cache["seq_lens"].at[slot].set(s0 - 1)
        new["active"] = cache["active"].at[slot].set(True)
        new["uids"] = cache["uids"].at[slot].set(uid)
        new["steps"] = cache["steps"].at[slot].set(0)
        new["last_tok"] = cache["last_tok"].at[slot].set(last_tok)
        return new

    def _chunk_impl(self, params, cache, k, attn_impl: str, attn_spec):
        """Up to ``chunk_max`` decode steps; ``k`` is a *dynamic* trip
        count so every chunk length reuses one trace."""
        self.chunk_traces += 1
        cfg, samp = self.cfg, self.sampler
        n_slots, chunk_max = self.paged.max_concurrency, self.paged.chunk_max
        buf = jnp.zeros((n_slots, chunk_max), jnp.int32)

        def cond(st):
            t, _, _ = st
            return t < k

        def body(st):
            t, cache, buf = st
            logits, cache = decode_step_paged(
                params, cache["last_tok"][:, None], cache, cfg,
                attn_impl=attn_impl, attn_spec=attn_spec,
                kv_scales=self._kv_scales_seq)
            with jax.named_scope("sample"):
                keys = _fold_keys(samp.seed, cache["uids"], cache["steps"])
                nxt = _sample_rows(logits[:, -1], samp.temperature, keys)
            active = cache["active"]
            cache = dict(cache)
            cache["last_tok"] = jnp.where(active, nxt, cache["last_tok"])
            cache["steps"] = cache["steps"] + active.astype(jnp.int32)
            buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, t))
            return t + 1, cache, buf

        _, cache, buf = jax.lax.while_loop(cond, body, (jnp.int32(0), cache, buf))
        return cache, buf

    def _release_impl(self, cache, slot, pages, n):
        """Refcount-aware subset-push release: drop one reader from the
        first ``n`` of ``pages`` (a sentinel-padded ``max_pages_per_seq``-
        wide list) and push only the pages whose count hits zero — shared
        prefix pages stay resident for their other readers (or for the
        cache itself). ``n`` is *dynamic*: one trace serves every page
        count (and, with ``slot = max_concurrency``, the prefix cache's
        own evictions — the slot scatter drops)."""
        self.release_traces += 1
        W = self.paged.max_pages_per_seq
        nb = self.paged.num_blocks
        valid = jnp.arange(W) < n
        idx = jnp.where(valid, pages, nb)  # sentinel -> dropped scatters
        rc = cache["page_refcounts"].at[idx].add(
            -valid.astype(jnp.int32), mode="drop")
        freed = valid & (rc[jnp.minimum(idx, nb - 1)] == 0)
        count = jnp.sum(freed.astype(jnp.int32))
        # compact freed pages to the front in row order (stable sort on
        # the not-freed flag) and push them at [top - count, top)
        order = jnp.argsort(~freed, stable=True)
        push = idx[order]
        top = cache["free_top"] - count
        dest = jnp.where(jnp.arange(W) < count, top + jnp.arange(W), nb)
        new = dict(cache)
        new["free_list"] = cache["free_list"].at[dest].set(push, mode="drop")
        new["free_top"] = top
        new["page_refcounts"] = rc
        new["active"] = cache["active"].at[slot].set(False, mode="drop")
        return new

    def _admit_batch_impl(self, params, cache, tokens, s0s, slots, uids,
                          rows, scatter_idx, incs, total_pop,
                          n_prompt_pages: int):
        """Co-admit ``n`` cold requests in ONE padded multi-row prefill.
        ``tokens`` is ``(n, n_prompt_pages * bs)`` zero-padded; per-row KV
        at positions ``>= s0s[r]`` is zero-masked before the page scatter
        so every page (codes *and* int8 scales — pad zeros cannot raise a
        page max) is bit-identical to the B=1 admit's, and each row's
        first token is sampled from its own last-prompt-position logits
        with the same ``fold_in(uid, 0)`` key. Rows/pages are
        host-computed (the host free-list mirror pops in device order);
        the device just stamps them and advances ``free_top``. One trace
        per (n_rows, n_prompt_pages) bucket."""
        self.batch_traces += 1
        cfg, paged = self.cfg, self.paged
        bs = paged.block_size
        n, prefill_len = tokens.shape
        assert prefill_len == n_prompt_pages * bs

        logits, dense = prefill(params, {"tokens": tokens}, cfg, prefill_len)
        # (n, L): True at real prompt positions, False at pad positions
        pos_valid = jnp.arange(prefill_len)[None, :] < s0s[:, None]
        idx_flat = scatter_idx.reshape(-1)  # (n * P,) sentinel-masked
        pools = []
        for i, spec in enumerate(cfg.pattern):
            c = cache["pools"][i]
            if spec.mixer != "attn":  # "none" only — engine gates batching
                pools.append(c)
                continue
            d = dense[i]

            def to_pages(a):
                # zero-mask pad positions (matches the B=1 jnp.pad zeros),
                # then (R, n, L, nkv, hd) -> (R, n*P, bs, nkv, hd)
                a = jnp.where(pos_valid[None, :, :, None, None], a, 0)
                r, _, _, nkv, hd = a.shape
                return a.reshape(r, n * n_prompt_pages, bs, nkv, hd)

            if "k_scales" in c:
                kc, ks, vc, vs = self._quantize_pages(i, to_pages(d["k"]),
                                                      to_pages(d["v"]))
                pools.append({
                    "k_pages": c["k_pages"].at[:, idx_flat].set(
                        kc, mode="drop"),
                    "v_pages": c["v_pages"].at[:, idx_flat].set(
                        vc, mode="drop"),
                    "k_scales": c["k_scales"].at[:, idx_flat].set(
                        ks, mode="drop"),
                    "v_scales": c["v_scales"].at[:, idx_flat].set(
                        vs, mode="drop"),
                })
            else:
                kp = c["k_pages"].at[:, idx_flat].set(
                    to_pages(d["k"]).astype(c["k_pages"].dtype), mode="drop")
                vp = c["v_pages"].at[:, idx_flat].set(
                    to_pages(d["v"]).astype(c["v_pages"].dtype), mode="drop")
                pools.append({"k_pages": kp, "v_pages": vp})

        # each row's logits at its own last prompt position
        l_last = jnp.take_along_axis(
            logits, (s0s - 1)[:, None, None], axis=1)[:, 0]  # (n, V)
        keys = _fold_keys(self.sampler.seed, uids, jnp.zeros_like(uids))
        nxt = _sample_rows(l_last, self.sampler.temperature, keys)  # (n,)

        new = dict(cache)
        new["pools"] = tuple(pools)
        new["block_table"] = cache["block_table"].at[slots].set(rows)
        new["free_top"] = cache["free_top"] + total_pop
        new["page_refcounts"] = cache["page_refcounts"].at[
            rows.reshape(-1)].add(incs.reshape(-1), mode="drop")
        new["seq_lens"] = cache["seq_lens"].at[slots].set(s0s)
        new["active"] = cache["active"].at[slots].set(True)
        new["uids"] = cache["uids"].at[slots].set(uids)
        new["steps"] = cache["steps"].at[slots].set(1)
        new["last_tok"] = cache["last_tok"].at[slots].set(nxt)
        return new, nxt

    def _admit_stub_impl(self, cache, row, slot, uid, incs, n_pages):
        """Claim a slot + its full page row for a chunked prefill without
        touching the model: ``active = False`` (decode chunks skip the
        slot), ``seq_lens = steps = 0``. FLOP-free by construction;
        ``n_pages`` is dynamic — one trace serves every row size."""
        self.stub_traces += 1
        new = dict(cache)
        new["block_table"] = cache["block_table"].at[slot].set(row)
        new["free_top"] = cache["free_top"] + n_pages
        new["page_refcounts"] = cache["page_refcounts"].at[row].add(
            incs, mode="drop")
        new["seq_lens"] = cache["seq_lens"].at[slot].set(0)
        new["active"] = cache["active"].at[slot].set(False)
        new["uids"] = cache["uids"].at[slot].set(uid)
        new["steps"] = cache["steps"].at[slot].set(0)
        new["last_tok"] = cache["last_tok"].at[slot].set(0)
        return new

    def _grow_impl(self, cache, slot, row, add, n_new):
        """Watermark growth: stamp the slot's extended (host-computed) row,
        bump refcounts on exactly the new pages (``add`` is 1 there, 0
        elsewhere) and advance ``free_top``. Dynamic ``n_new`` — one trace
        serves every growth size."""
        self.grow_traces += 1
        new = dict(cache)
        new["block_table"] = cache["block_table"].at[slot].set(row)
        new["free_top"] = cache["free_top"] + n_new
        new["page_refcounts"] = cache["page_refcounts"].at[row].add(
            add, mode="drop")
        return new

    def _prefill_chunk_impl(self, params, cache, tokens, slot, uid, s0,
                            incs, n_prior: int, n_chunk_pages: int,
                            final: bool):
        """One page-aligned prefill chunk for a stub-admitted slot: gather
        the slot's first ``n_prior`` pages as dense prefix KV (the PR 6
        ``pos_offset`` suffix machinery), prefill this chunk's tokens at
        offset ``n_prior * bs`` and scatter them into the row's next
        ``n_chunk_pages`` pages. The ``final`` chunk samples the first
        token with the cold admit's exact ``fold_in(uid, 0)`` key and
        flips the slot live (``seq_lens = s0``, ``steps = 1``); earlier
        chunks leave the slot inactive so interleaved decode chunks skip
        it. One trace per (chunk_len, n_prior, final) bucket. Always
        returns ``(cache, tok)`` — ``tok = -1`` on non-final chunks — so
        the program's output pytree (and its mesh out_shardings) is
        identical across the final/non-final traces."""
        self.prefill_chunk_traces += 1
        cfg, paged = self.cfg, self.paged
        bs = paged.block_size
        _, t = tokens.shape  # (1, T) — this chunk's prompt tokens
        prefix_len = n_prior * bs
        prefill_len = n_chunk_pages * bs

        row = cache["block_table"][slot]  # (W,) — stamped at stub admit
        prefix_kv = (self._gather_prefix_kv(cache, row[:n_prior], prefix_len)
                     if n_prior else None)
        logits, dense = prefill(params, {"tokens": tokens}, cfg, prefill_len,
                                prefix_kv=prefix_kv, pos_offset=prefix_len)
        pools = self._scatter_dense_pages(
            cache, dense, row[n_prior:n_prior + n_chunk_pages], n_chunk_pages)

        new = dict(cache)
        new["pools"] = tuple(pools)
        if not final:
            return new, jnp.int32(-1)

        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(self.sampler.seed), uid),
            jnp.int32(0))
        nxt = _sample(logits[:, -1], self.sampler.temperature, key)  # (1,)
        # deferred prefix-cache registration lands with the final chunk
        new["page_refcounts"] = cache["page_refcounts"].at[row].add(
            incs, mode="drop")
        new["seq_lens"] = cache["seq_lens"].at[slot].set(s0)
        new["active"] = cache["active"].at[slot].set(True)
        new["steps"] = cache["steps"].at[slot].set(1)
        new["last_tok"] = cache["last_tok"].at[slot].set(nxt[0])
        return new, nxt[0]

    # ------------------------------------------------------------------
    # Host loop
    # ------------------------------------------------------------------
    @property
    def preemptions(self) -> int:
        """Preemptions across serve() calls."""
        return self.counters.preemptions

    def _read(self, x) -> np.ndarray:
        """The serve loop's blocking read of device results, timed into
        ``counters.readback_s``."""
        t = time.perf_counter()
        with TraceAnnotation(SPAN_READBACK):
            out = np.asarray(shardlib.host_read(x))
        self.counters.readback_s += time.perf_counter() - t
        return out

    def _release_pages(self, slot: int, pages) -> None:
        """Drop one reader from each of a slot's ``pages`` (its row; or
        evicted cache pages, with ``slot = max_concurrency``)."""
        with TraceAnnotation(SPAN_RELEASE):
            self.cache = self._release(self.cache, np.int32(slot),
                                       self._pad_row(pages),
                                       np.int32(len(pages)))

    def _decode(self, sched, slots, k: int, backend, attn_impl) -> np.ndarray:
        """Dispatch one ``k``-step decode chunk over the decoding ``slots``
        and read its tokens back (the chunk's one host sync)."""
        c, pc = self.counters, self.paged
        st = sched.active
        lens = [st[s].req.prompt.size + st[s].produced - 1 for s in slots]
        c.decode_steps += k
        c.attn_pages_live += pages_filled(lens, k, pc.block_size)
        c.attn_pages_grid += k * pc.max_concurrency * pc.max_pages_per_seq
        with TraceAnnotation(SPAN_DECODE):
            self.cache, buf = self._chunk(
                self.params, self.cache, np.int32(k), backend, attn_impl,
                self.datapath_fingerprint, self.attn_spec)
        # buf is fully replicated by the out_shardings contract, so this
        # read is local on every process (docs/multihost.md)
        return self._read(buf)

    def _make_scheduler(self) -> Scheduler:
        paged = self.paged
        return Scheduler(paged.max_concurrency, paged.num_blocks,
                         paged.block_size, paged.max_pages_per_seq,
                         prefix_cache=self.prefix_cache,
                         pool_state=self.pool_state,
                         policy=paged.sched)

    def submit_all(self, requests) -> Scheduler:
        sched = self._make_scheduler()
        for r in requests:
            sched.submit(r)
        return sched

    def _pad_row(self, pages) -> jnp.ndarray:
        """Sentinel-pad a physical page list to the block-table width (the
        release/evict programs take one fixed-width dynamic-count list)."""
        out = np.full(self.paged.max_pages_per_seq, self.paged.num_blocks,
                      np.int32)
        out[:len(pages)] = pages
        return out

    def _do_admit(self, adm, backend, attn_impl):
        """Run one admission's device programs (evict, then the admit
        variant the scheduler picked). Returns the request's first sampled
        token, or None for a fully cached prompt — its first sample is
        deferred to the next decode chunk."""
        if adm.evict_pages is not None and adm.evict_pages.size:
            self._release_pages(self.paged.max_concurrency, adm.evict_pages)
        req = adm.req
        incs = np.asarray(adm.incs, np.int32)
        if adm.chunked:
            # stub admit: claim the slot + full row FLOP-free; the prompt
            # prefills later, one page-aligned chunk per scheduler pass
            self.cache = self._admit_stub(
                self.cache, self._pad_row(adm.row), np.int32(adm.slot),
                np.int32(req.uid), incs, np.int32(adm.n_pages))
            return None
        shared = np.asarray(adm.shared_pages, np.int32)
        if adm.cow_src is not None:
            self.cache = self._admit_cached(
                self.cache, shared, np.int32(adm.cow_src),
                np.int32(adm.slot), np.int32(req.uid),
                np.int32(req.prompt.size), np.int32(req.prompt[-1]),
                incs, adm.n_pages, adm.n_shared)
            return None
        if adm.n_shared:
            suffix = req.prompt[adm.n_shared * self.paged.block_size:]
            self.counters.prefill_tokens += suffix.size
            self.cache, tok0 = self._admit_suffix(
                self.params, self.cache,
                np.asarray(suffix, np.int32)[None],
                shared, np.int32(adm.slot), np.int32(req.uid), incs,
                adm.n_pages, adm.n_shared, backend, attn_impl,
                self.datapath_fingerprint)
        else:
            self.counters.prefill_tokens += req.prompt.size
            self.cache, tok0 = self._admit(
                self.params, self.cache,
                np.asarray(req.prompt, np.int32)[None], np.int32(adm.slot),
                np.int32(req.uid), incs, adm.n_pages, backend, attn_impl,
                self.datapath_fingerprint)
        return int(self._read(tok0))

    def _do_admit_batch(self, group, backend, attn_impl) -> np.ndarray:
        """Run one batched-admission group (>= 2 cold requests) through a
        single padded multi-row prefill program. Returns the first sampled
        token per group member, in group order."""
        paged = self.paged
        bs, W = paged.block_size, paged.max_pages_per_seq
        n = len(group)
        s0s = np.asarray([a.req.prompt.size for a in group], np.int32)
        P = max(-(-int(s) // bs) for s in s0s)
        tokens = np.zeros((n, P * bs), np.int32)
        rows = np.full((n, W), paged.num_blocks, np.int32)
        scat = np.full((n, P), paged.num_blocks, np.int32)
        incs = np.zeros((n, W), np.int32)
        total_pop = 0
        for j, a in enumerate(group):
            tokens[j, :s0s[j]] = a.req.prompt
            rows[j, :a.n_pages] = a.row
            scat[j, :-(-int(s0s[j]) // bs)] = a.row[:-(-int(s0s[j]) // bs)]
            incs[j] = a.incs
            total_pop += a.n_pages  # cold: every row page freshly popped
        slots = np.asarray([a.slot for a in group], np.int32)
        uids = np.asarray([a.req.uid for a in group], np.int32)
        if self.mesh is not None:
            # per-host prompt sharding: the padded token block splits by
            # row over the data axis when the group size divides it
            # (divisibility fallback -> replicated); the per-row admin
            # vectors stay replicated host inputs
            tokens = shardlib.host_to_global(
                tokens, shardlib.rows_sharding(tokens.shape, self.mesh))
        self.counters.prefill_tokens += int(s0s.sum())
        self.cache, toks = self._admit_batch(
            self.params, self.cache, tokens, s0s,
            slots, uids, rows,
            scat, incs, np.int32(total_pop),
            n, P, backend, attn_impl, self.datapath_fingerprint)
        return self._read(toks)

    def _do_prefill_chunk(self, slot, sched, backend, attn_impl):
        """Advance one stub-admitted slot by one page-aligned prefill
        chunk. Returns the request's first sampled token when this chunk
        completed the prompt, else None."""
        tokens, n_prior, final, incs = sched.take_prefill_chunk(slot)
        st = sched.active[slot]
        n_chunk_pages = -(-tokens.size // self.paged.block_size)
        self.counters.prefill_tokens += tokens.size
        self.cache, tok0 = self._prefill_chunk(
            self.params, self.cache, np.asarray(tokens, np.int32)[None],
            np.int32(slot), np.int32(st.req.uid),
            np.int32(st.req.prompt.size), np.asarray(incs, np.int32),
            n_prior, n_chunk_pages, final, backend, attn_impl,
            self.datapath_fingerprint)
        if final:
            return int(self._read(tok0))
        return None

    @staticmethod
    def _arrival_feed(requests, arrivals):
        """Sort an arrival-time trace into a (time, request) deque —
        ``None`` when the whole list is submitted up front."""
        if arrivals is None:
            return None
        if len(arrivals) != len(requests):
            raise ValueError(
                f"arrivals has {len(arrivals)} entries for {len(requests)} "
                f"requests")
        order = sorted(range(len(requests)),
                       key=lambda i: (float(arrivals[i]), i))
        return deque((float(arrivals[i]), requests[i]) for i in order)

    def serve(self, requests, *, arrivals=None, metrics=None,
              _probe=None, _late=None) -> dict[int, np.ndarray]:
        """Run a request list to completion under continuous batching.

        Returns {uid: (S0_uid + n_generated,) int32} — generation is
        trimmed at the first EOS (when the sampler sets one), matching the
        fixed-slot engine's post-EOS padding semantics after re-padding.

        ``arrivals`` (optional, seconds, aligned with ``requests``) paces
        submission on the wall clock instead of submitting everything up
        front; ``metrics`` (a :class:`~repro.serving.metrics.ServeMetrics`)
        collects per-request TTFT / inter-token timestamps. Greedy results
        are identical either way — timing changes *when* work runs, never
        what any request's token stream is.

        The serve loop is picked by ``PagedConfig.sched``: the default
        legacy-FIFO policy runs the original head-of-line loop
        (bit-compatible, trace-shape-compatible); any other policy runs
        the throughput loop (windowed/batched admission, chunked prefill,
        watermark growth + preempt-and-requeue).

        ``_probe(engine, sched)`` (tests) runs after every admit/chunk/
        release transition; ``_late(sched, pass_idx)`` runs once per
        scheduler pass (after the decode chunk, when one ran) and may
        submit mid-flight arrivals — even when the pass drained every
        active request at admission, so injected work is never stranded.
        """
        # the observer must be attached when the decode chunk *traces*
        # (the callback is baked into the jaxpr); it is engine-constant
        # (observe=True at construction), so every trace under this
        # engine's "+obs" fingerprint is consistently observing
        if (arrivals is not None and self.mesh is not None
                and jax.process_count() > 1):
            # wall-clock pacing is single-controller: two processes would
            # observe different clocks, submit in different orders, and
            # issue diverging device programs (the SPMD deadlock class).
            # Multi-process traffic must arrive deterministically — all up
            # front, or through the pass-indexed ``_late`` hook.
            raise ValueError(
                "arrivals= is wall-clock-paced and single-controller; "
                "multi-process serving needs deterministic submission "
                "(submit everything up front or use the _late hook)")
        ctx = (attach_observer(self.observer) if self.observer is not None
               else nullcontext())
        with ctx:
            if self.paged.sched.is_legacy:
                return self._serve_legacy(requests, arrivals, metrics,
                                          _probe, _late)
            return self._serve_throughput(requests, arrivals, metrics,
                                          _probe, _late)

    def _serve_legacy(self, requests, arrivals, metrics, _probe, _late):
        sched = self._make_scheduler()
        pending = self._arrival_feed(requests, arrivals)
        if pending is None:
            for r in requests:
                sched.submit(r)
                if metrics is not None:
                    metrics.submitted(r.uid, r.priority, 0.0)
        backend = packed_backend()
        attn_impl = resolve_paged_attn_impl(self.paged.attn_impl)
        eos = self.sampler.eos_id
        counters = self.counters
        results: dict[int, np.ndarray] = {}
        chunk_idx = 0
        t0 = time.perf_counter()

        def now():
            return time.perf_counter() - t0

        def submit_due():
            while pending and pending[0][0] <= now():
                t, r = pending.popleft()
                sched.submit(r)
                if metrics is not None:
                    metrics.submitted(r.uid, r.priority, t)

        def note(slot, toks):
            sched.record(slot, toks)
            counters.tokens_out += len(toks)
            if metrics is not None and toks:
                metrics.tokens(sched.active[slot].req.uid, len(toks), now())

        def finish(slot):
            st = sched.finish(slot)
            self._release_pages(slot, st.row)
            results[st.req.uid] = np.concatenate(
                [st.req.prompt, np.asarray(st.tokens, np.int32)])
            if _probe is not None:
                _probe(self, sched)

        while sched.has_work or pending:
            if pending:
                submit_due()
                if not sched.has_work:
                    time.sleep(max(0.0, pending[0][0] - now()))
                    continue
            t_pass = time.perf_counter()
            with TraceAnnotation(SPAN_PASS, index=chunk_idx,
                                 **counters.snapshot()):
                adm = sched.try_admit()
                while adm is not None:
                    with TraceAnnotation(SPAN_ADMIT, uid=adm.req.uid):
                        tok0 = self._do_admit(adm, backend, attn_impl)
                        if tok0 is not None:
                            note(adm.slot, [tok0])
                        if _probe is not None:
                            _probe(self, sched)
                        if tok0 is not None and (
                                sched.remaining(adm.slot) == 0 or tok0 == eos):
                            finish(adm.slot)
                    adm = sched.try_admit()
                if sched.active:
                    k = min(self.paged.chunk_max, sched.min_remaining())
                    buf = self._decode(sched, list(sched.active), k, backend,
                                       attn_impl)
                    if _probe is not None:
                        _probe(self, sched)
                    with TraceAnnotation(SPAN_RECORD):
                        for slot in list(sched.active):
                            toks = buf[slot, :k].tolist()[
                                : sched.remaining(slot)]
                            if eos is not None and eos in toks:
                                toks = toks[: toks.index(eos) + 1]
                            note(slot, toks)
                            if sched.remaining(slot) == 0 or (
                                    eos is not None and toks
                                    and toks[-1] == eos):
                                finish(slot)
                elif sched.queue:  # cannot happen: submit() validates fit
                    raise RuntimeError("queued requests can never be admitted")
                if _late is not None:
                    with TraceAnnotation(SPAN_LATE):
                        _late(sched, chunk_idx)
                counters.passes += 1
                counters.pass_s += time.perf_counter() - t_pass
            chunk_idx += 1
        return results

    def _serve_throughput(self, requests, arrivals, metrics, _probe, _late):
        """Throughput-mode serve loop. One pass = (1) one page-aligned
        prefill chunk per already-prefilling slot — in-flight prompts are
        older than anything queued, so they advance ahead of fresh
        admissions and a burst of arrivals cannot starve a long prompt's
        final chunk; (2) an admission pass — windowed, priority-ordered,
        cold arrivals co-admitted through the batched prefill program
        (slots stubbed here get their first chunk at the end of the same
        pass); (3) a planned decode chunk — cache eviction / preemption /
        watermark growth committed in plan order, then ``k`` fused steps.
        Token streams are bit-identical to the legacy loop: admission
        variants write identical pages and the per-request
        ``fold_in(uid, step)`` sampling stream is order-free."""
        sched = self._make_scheduler()
        pending = self._arrival_feed(requests, arrivals)
        if pending is None:
            for r in requests:
                sched.submit(r)
                if metrics is not None:
                    metrics.submitted(r.uid, r.priority, 0.0)
        backend = packed_backend()
        attn_impl = resolve_paged_attn_impl(self.paged.attn_impl)
        eos = self.sampler.eos_id
        counters = self.counters
        results: dict[int, np.ndarray] = {}
        pass_idx = 0
        t0 = time.perf_counter()

        def now():
            return time.perf_counter() - t0

        def submit_due():
            while pending and pending[0][0] <= now():
                t, r = pending.popleft()
                sched.submit(r)
                if metrics is not None:
                    metrics.submitted(r.uid, r.priority, t)

        def note(slot, toks):
            sched.record(slot, toks)
            counters.tokens_out += len(toks)
            if metrics is not None and toks:
                metrics.tokens(sched.active[slot].req.uid, len(toks), now())

        def finish(slot):
            st = sched.finish(slot)
            self._release_pages(slot, st.row)
            results[st.req.uid] = np.concatenate(
                [st.req.prompt, np.asarray(st.tokens, np.int32)])
            if _probe is not None:
                _probe(self, sched)

        def maybe_finish(slot):
            st = sched.active.get(slot)
            if st is None or st.prefilling:
                return
            if sched.remaining(slot) == 0 or (
                    eos is not None and st.tokens and st.tokens[-1] == eos):
                finish(slot)

        def take_chunk(slot):
            with TraceAnnotation(SPAN_PREFILL,
                                 uid=sched.active[slot].req.uid):
                tok0 = self._do_prefill_chunk(slot, sched, backend, attn_impl)
                if _probe is not None:
                    _probe(self, sched)
                if tok0 is not None:
                    note(slot, [tok0])
                    maybe_finish(slot)

        while sched.has_work or pending:
            if pending:
                submit_due()
                if not sched.has_work:
                    time.sleep(max(0.0, pending[0][0] - now()))
                    continue
            t_pass = time.perf_counter()
            with TraceAnnotation(SPAN_PASS, index=pass_idx,
                                 **counters.snapshot()):
                progressed = False
                # In-flight prefills advance *before* new admissions: a
                # prefilling slot is older than anything still queued, and
                # a burst of batched admits must not starve its next chunk
                # (the final chunk is the request's first token).
                chunked_first = sched.prefilling_slots()
                for slot in chunked_first:
                    progressed = True
                    take_chunk(slot)
                # ``admit_pass`` commits every group host-side up front;
                # the device only catches up as each group's program runs,
                # so probes and releases (a finish's device push must not
                # interleave with this pass's remaining device pops — the
                # free-list replay order is the lockstep contract) wait
                # until the whole pass has executed.
                admitted = []
                for group in sched.admit_pass():
                    progressed = True
                    if len(group) == 1:
                        adm = group[0]
                        with TraceAnnotation(SPAN_ADMIT, uid=adm.req.uid):
                            tok0 = self._do_admit(adm, backend, attn_impl)
                        if tok0 is not None:
                            note(adm.slot, [tok0])
                    else:
                        uids = ",".join(str(a.req.uid) for a in group)
                        with TraceAnnotation(SPAN_ADMIT, uids=uids):
                            toks = self._do_admit_batch(group, backend,
                                                        attn_impl)
                        for adm, t in zip(group, toks):
                            note(adm.slot, [int(t)])
                    admitted.extend(group)
                if admitted:
                    if _probe is not None:
                        _probe(self, sched)
                    for adm in admitted:
                        maybe_finish(adm.slot)
                for slot in sched.prefilling_slots():
                    if slot in chunked_first:
                        continue  # one chunk per slot per pass
                    progressed = True
                    take_chunk(slot)
                with TraceAnnotation(SPAN_PLAN):
                    plan = sched.plan_chunk(self.paged.chunk_max)
                    if plan is not None:
                        for v in plan.victims:
                            progressed = True  # freed pages: replanned
                            st = sched.preempt(v)
                            counters.preemptions += 1
                            self._release_pages(v, st.row)
                            if metrics is not None:
                                metrics.preempted(st.req.uid)
                            if _probe is not None:
                                _probe(self, sched)
                        if plan.evict_nodes:
                            self._release_pages(
                                self.paged.max_concurrency,
                                sched._commit_evict(plan.evict_nodes))
                            if _probe is not None:
                                _probe(self, sched)
                if plan is not None and plan.grow:
                    with TraceAnnotation(SPAN_GROW):
                        for slot, n_new in plan.grow:
                            pages, held = sched.commit_grow(slot, n_new)
                            add = np.zeros(self.paged.max_pages_per_seq,
                                           np.int32)
                            add[held:held + n_new] = 1
                            self.cache = self._grow(
                                self.cache, np.int32(slot),
                                self._pad_row(sched.active[slot].row),
                                add, np.int32(n_new))
                            if _probe is not None:
                                _probe(self, sched)
                if plan is not None and plan.slots:
                    progressed = True
                    buf = self._decode(sched, plan.slots, plan.k, backend,
                                       attn_impl)
                    sched.advance_decode(plan.k)
                    if _probe is not None:
                        _probe(self, sched)
                    with TraceAnnotation(SPAN_RECORD):
                        for slot in plan.slots:
                            toks = buf[slot, :plan.k].tolist()[
                                : sched.remaining(slot)]
                            if eos is not None and eos in toks:
                                toks = toks[: toks.index(eos) + 1]
                            note(slot, toks)
                            maybe_finish(slot)
                if not progressed and not sched.active and sched.queue \
                        and not pending:
                    raise RuntimeError(
                        "queued requests can never be admitted")
                if _late is not None:
                    with TraceAnnotation(SPAN_LATE):
                        _late(sched, pass_idx)
                counters.passes += 1
                counters.pass_s += time.perf_counter() - t_pass
            pass_idx += 1
        return results

    # ------------------------------------------------------------------
    # Structural zero-FLOP certificate for the fully-cached admit
    # ------------------------------------------------------------------
    _FLOP_PRIMITIVES = frozenset({"dot_general", "conv_general_dilated"})

    def cached_admit_primitives(self, n_pages: int = 2,
                                n_shared: int = 1) -> set[str]:
        """All primitives (recursively) in the fully-cached admit jaxpr.
        The program takes no model params, so a single matmul appearing
        here would be a bug — :meth:`assert_cached_admit_flop_free` gates
        on the intersection with ``_FLOP_PRIMITIVES``."""
        W = self.paged.max_pages_per_seq
        i32 = jnp.int32
        traces = self.cached_traces  # make_jaxpr retraces; don't count it
        closed = jax.make_jaxpr(
            partial(self._admit_cached_impl, n_pages=n_pages,
                    n_shared=n_shared)
        )(self.cache, jnp.zeros((n_shared,), i32), i32(0), i32(0), i32(0),
          i32(1), i32(0), jnp.zeros((W,), i32))
        self.cached_traces = traces
        prims: set[str] = set()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                prims.add(eqn.primitive.name)
                for v in eqn.params.values():
                    for sub in jax.tree.leaves(
                            v, is_leaf=lambda x: isinstance(
                                x, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
                        if isinstance(sub, jex_core.ClosedJaxpr):
                            walk(sub.jaxpr)
                        elif isinstance(sub, jex_core.Jaxpr):
                            walk(sub)

        walk(closed.jaxpr)
        return prims

    def assert_cached_admit_flop_free(self) -> None:
        """Admitting a fully cached prompt must run zero prefill FLOPs:
        its program is gathers/scatters only (no dot_general, no conv)."""
        hot = self.cached_admit_primitives() & self._FLOP_PRIMITIVES
        if hot:
            raise AssertionError(
                f"fully-cached admit contains FLOP primitives {sorted(hot)}")

    # ------------------------------------------------------------------
    # Observation (repro.quant.observe) — structural transparency
    # ------------------------------------------------------------------
    def decode_chunk_jaxpr(self, observer=None):
        """jaxpr of one decode chunk, traced fresh (the serving trace's
        exact body under the resolved backend). Default: NO observer
        attached — the baseline serving program. Pass a
        :class:`~repro.quant.observe.SaturationCounters` to trace the
        observing variant (adds ``debug_callback`` equations, nothing
        else)."""
        traces = self.chunk_traces  # make_jaxpr retraces; don't count it
        attn_impl = resolve_paged_attn_impl(self.paged.attn_impl)
        ctx = (attach_observer(observer) if observer is not None
               else nullcontext())
        with ctx, use_packed_backend(packed_backend()):
            closed = jax.make_jaxpr(
                partial(self._chunk_impl, attn_impl=attn_impl,
                        attn_spec=self.attn_spec)
            )(self.params, self.cache, jnp.int32(1))
        self.chunk_traces = traces
        return closed

    def assert_observation_transparent(self) -> None:
        """Observation must be free when off: the decode-chunk jaxpr with
        no observer attached contains no callback equation (it is exactly
        what an ``observe=False`` engine traces); with one attached, the
        callbacks appear. Raises AssertionError otherwise."""
        bare = str(self.decode_chunk_jaxpr())
        if "debug_callback" in bare:
            raise AssertionError(
                "decode chunk contains debug_callback with no observer "
                "attached — observation is not transparent")

        def has_packed(node):
            # the pmm hook only fires on packed integer leaves; a float
            # engine legitimately records nothing
            if isinstance(node, dict):
                return "packed" in node or any(
                    has_packed(v) for v in node.values())
            if isinstance(node, (list, tuple)):
                return any(has_packed(v) for v in node)
            return False

        if self.observer is not None and has_packed(self.params):
            observed = str(self.decode_chunk_jaxpr(self.observer))
            if "debug_callback" not in observed:
                raise AssertionError(
                    "observer attached but the decode chunk records "
                    "nothing (no debug_callback in the jaxpr)")

    def saturation_report(self) -> dict:
        """ServeMetrics-style saturation/watermark report from the
        serving observer (see ``repro.quant.observe.saturation``): per-site
        static-quantizer clip counts, code extrema, accumulator watermarks
        against the packed leaves, and per-KV-head attention watermarks
        for int8 pools. Requires ``observe=True`` at construction."""
        if self.observer is None:
            raise ValueError(
                "engine was built with observe=False — no counters to "
                "report; rebuild with PagedEngine(..., observe=True)")
        return self.observer.report(params=self.params,
                                    pools=self.cache["pools"],
                                    attn_spec=self.attn_spec)

    def assert_sampling_keys_collective_safe(self) -> None:
        """The per-request sampling stream must be identical on every
        device and process: keys derive in-graph as
        ``fold_in(fold_in(key(seed), uid), step)`` from *replicated* admin
        leaves, so the SPMD program — forced to return fully replicated
        key data — must agree bit-exactly with the eager single-device
        computation on the same (uids, steps). Mesh engines only; raises
        AssertionError on any divergence."""
        if self.mesh is None:
            raise ValueError(
                "engine has no mesh — the single-controller sampling "
                "stream is trivially host-consistent")
        uids = np.asarray(shardlib.host_read(self.cache["uids"]), np.int32)
        steps = np.asarray(shardlib.host_read(self.cache["steps"]), np.int32)
        seed = self.sampler.seed
        fn = jax.jit(lambda u, t: jax.random.key_data(_fold_keys(seed, u, t)),
                     out_shardings=self._out_rep)
        got = np.asarray(shardlib.host_read(fn(uids, steps)))
        want = np.asarray(jax.device_get(jax.random.key_data(
            _fold_keys(seed, jnp.asarray(uids), jnp.asarray(steps)))))
        np.testing.assert_array_equal(
            got, want,
            err_msg="SPMD sampling keys diverge from the single-device "
                    "stream — per-request determinism is broken")

    def generate(self, prompts: np.ndarray, max_new_tokens: int) -> np.ndarray:
        """Fixed-slot-compatible entry: prompts (B, S0) -> (B, S0 + max_new).

        Post-EOS positions are EOS-padded, matching
        :meth:`GenerationEngine.generate` exactly (greedy decode of an
        equal-length batch is bit-identical — golden-pinned)."""
        prompts = np.asarray(prompts, np.int32)
        reqs = []
        for row in prompts:
            reqs.append(Request(uid=self._uid_gen, prompt=row,
                                max_new=max_new_tokens))
            self._uid_gen += 1
        results = self.serve(reqs)
        eos = self.sampler.eos_id
        s_out = prompts.shape[1] + max_new_tokens
        out = np.full((len(reqs), s_out), 0 if eos is None else eos, np.int32)
        for i, r in enumerate(reqs):
            seq = results[r.uid]
            out[i, :seq.size] = seq
        return out
