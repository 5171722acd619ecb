"""Model assembly: heterogeneous layer stacks with scan-over-repeats.

The stack is ``repeats`` x ``pattern`` (see config.py). Parameters for each
pattern slot are stacked over repeats (leading axis R) and the forward pass
is a single ``lax.scan`` whose body unrolls the period — compiled HLO size is
O(period), independent of depth (126-layer LLaMA-405B compiles the same body
as a 2-layer smoke model). Decode threads per-layer recurrent state (KV
caches / SSM states) through the same scan as stacked xs/ys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import LayerSpec, ModelConfig
from .layers import (
    attention,
    attention_decode,
    constraint,
    embed,
    init_attention,
    init_embedding,
    init_mlp,
    init_norm,
    lm_logits,
    mlp,
    norm,
)
from .moe import init_moe, moe
from .ssm import init_mamba, mamba, mamba_decode, mamba_state_shapes
from .xlstm import (
    init_mlstm,
    init_slstm,
    mlstm,
    mlstm_decode,
    mlstm_state_shapes,
    slstm,
    slstm_decode,
    slstm_state_shapes,
)

AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_slot(key, spec: LayerSpec, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg)}
    if spec.mixer == "attn":
        p["mixer"] = init_attention(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mixer"] = init_mamba(ks[0], cfg)
    elif spec.mixer == "mlstm":
        p["mixer"] = init_mlstm(ks[0], cfg)
    elif spec.mixer == "slstm":
        p["mixer"] = init_slstm(ks[0], cfg)
    elif spec.mixer != "none":
        raise ValueError(f"unknown mixer {spec.mixer}")
    if spec.ffn != "none":
        p["norm2"] = init_norm(cfg)
        p["ffn"] = init_moe(ks[1], cfg) if spec.ffn == "moe" else init_mlp(ks[1], cfg)
    return p


def init_model(key, cfg: ModelConfig):
    k_embed, k_layers = jax.random.split(key)
    slot_keys = jax.random.split(k_layers, cfg.period * cfg.repeats).reshape(
        cfg.period, cfg.repeats
    )
    layers = tuple(
        jax.vmap(lambda k, s=spec: _init_slot(k, s, cfg))(slot_keys[i])
        for i, spec in enumerate(cfg.pattern)
    )
    return {
        "embedding": init_embedding(k_embed, cfg),
        "layers": layers,
        "final_norm": init_norm(cfg),
    }


def abstract_params(cfg: ModelConfig, seed: int = 0):
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(lambda k: init_model(k, cfg), jax.random.key(seed))


# ---------------------------------------------------------------------------
# Training / scoring forward
# ---------------------------------------------------------------------------
def _apply_block(p, spec: LayerSpec, cfg: ModelConfig, x, positions):
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer != "none":
        h = norm(p["norm1"], x, cfg.norm)
        if spec.mixer == "attn":
            y, _ = attention(p["mixer"], h, cfg, positions)
        elif spec.mixer == "mamba":
            y = mamba(p["mixer"], h, cfg)
        elif spec.mixer == "mlstm":
            y = mlstm(p["mixer"], h, cfg)
        else:
            y = slstm(p["mixer"], h, cfg)
        x = x + y
    if spec.ffn != "none":
        h = norm(p["norm2"], x, cfg.norm)
        if spec.ffn == "moe":
            y, a = moe(p["ffn"], h, cfg)
            aux = aux + a
        else:
            y = mlp(p["ffn"], h, cfg)
        x = x + y
    return x, aux


def _embed_inputs(params, batch, cfg: ModelConfig, pos_offset: int = 0):
    """Token embedding plus the (stub) modality frontend prefix.

    ``pos_offset`` shifts the RoPE positions — nonzero only on the
    prefix-cache suffix-prefill path, where ``batch["tokens"]`` is the
    uncached tail of a prompt whose first ``pos_offset`` tokens already
    sit in shared KV pages."""
    x = embed(params["embedding"], batch["tokens"], cfg)
    if cfg.frontend == "vision_stub" and cfg.frontend_tokens:
        # precomputed patch embeddings arrive as inputs (assignment spec)
        x = jnp.concatenate([batch["pixel_embeds"].astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(
        pos_offset + jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, positions


def forward(params, batch, cfg: ModelConfig):
    """batch: {"tokens": (B, S_text) int32, ["pixel_embeds": (B, P, D)]}.

    Returns (logits over the full sequence incl. frontend prefix, aux_loss).
    """
    x, positions = _embed_inputs(params, batch, cfg)
    x = constraint(x, ("batch", None, "residual"))

    def body(carry, layer_params):
        x, aux = carry
        for i, spec in enumerate(cfg.pattern):
            x, a = _apply_block(layer_params[i], spec, cfg, x, positions)
            aux = aux + a
        return (x, aux), None

    if cfg.remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)
    carry = (x, jnp.zeros((), jnp.float32))
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, carry, params["layers"])
    else:  # unrolled (validation of the trip-count cost model)
        for r in range(cfg.repeats):
            layer_r = jax.tree.map(lambda p: p[r], params["layers"])
            carry, _ = body(carry, layer_r)
        x, aux = carry
    x = norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embedding"], x, cfg), aux


def loss_fn(params, batch, cfg: ModelConfig):
    """Next-token cross entropy over text positions (frontend prefix masked)."""
    logits, aux = forward(params, batch, cfg)
    p = cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0
    text_logits = logits[:, p:, :]
    pred = text_logits[:, :-1].astype(jnp.float32)
    labels = batch["tokens"][:, 1:]
    logz = jax.nn.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = batch.get("mask")
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        nll = nll * m
        denom = jnp.maximum(jnp.sum(m), 1.0)
    else:
        denom = jnp.asarray(nll.size, jnp.float32)
    ce = jnp.sum(nll) / denom
    total = ce + AUX_LOSS_WEIGHT * aux
    return total, {"ce": ce, "aux": aux, "ppl": jnp.exp(ce)}


# ---------------------------------------------------------------------------
# KV / recurrent caches
# ---------------------------------------------------------------------------
def _slot_cache_shapes(spec: LayerSpec, cfg: ModelConfig, batch: int, max_len: int):
    if spec.mixer == "attn":
        hd = cfg.head_dim
        kv = jax.ShapeDtypeStruct(
            (batch, max_len, cfg.n_kv_heads, hd), jnp.dtype(cfg.act_dtype)
        )
        return {"k": kv, "v": kv}
    if spec.mixer == "mamba":
        return mamba_state_shapes(cfg, batch)
    if spec.mixer == "mlstm":
        return mlstm_state_shapes(cfg, batch)
    if spec.mixer == "slstm":
        return slstm_state_shapes(cfg, batch)
    return {}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, abstract: bool = False):
    """Stacked (R, ...) cache pytree per pattern slot."""

    def make(sds):
        shape = (cfg.repeats, *sds.shape)
        if abstract:
            return jax.ShapeDtypeStruct(shape, sds.dtype)
        return jnp.zeros(shape, sds.dtype)

    return tuple(
        {k: make(v) for k, v in _slot_cache_shapes(spec, cfg, batch, max_len).items()}
        for spec in cfg.pattern
    )


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_blocks: int,
                     block_size: int, max_pages: int, abstract: bool = False,
                     kv_dtype: str | None = None):
    """Paged decode cache: one KV *page pool* per attention slot plus the
    shared continuous-batching state (see docs/serving_scheduler.md).

    Attention KV lives in ``(R, num_blocks, block_size, nkv, hd)`` pools
    indexed through a per-slot ``block_table`` — HBM scales with the pool,
    not with ``num_slots * max_seq_len``. Recurrent mixers (Mamba/xLSTM)
    keep their O(1)-per-sequence dense state, batched over ``num_slots``
    (continuous batching swaps a slot's state wholesale at admission).
    Page allocation state (``free_list`` stack + ``free_top``) is part of
    the pytree so pop/push happen inside the jitted admit/release programs.

    ``kv_dtype`` selects the pool element type (default ``cfg.act_dtype``).
    ``kv_dtype="int8"`` stores *quantized* pages: int8 codes plus
    per-(page, kv-head) symmetric ``k_scales``/``v_scales`` f32 leaves
    (``(R, num_blocks, nkv)``) — pool HBM halves vs bf16 and attention
    runs the :class:`~repro.quant.spec.AttnDatapathSpec`-certified integer
    datapath (see ``repro.kernels.paged_attention``). Scales start at
    zero; admission stamps them per scattered page and the decode append
    resets them on a page's first write, so recycled pages can never leak
    a stale scale into a live sequence.

    ``page_refcounts`` counts readers per physical page: the live
    block-table rows containing it, plus one when the prefix cache holds
    it (docs/serving_scheduler.md, "Prefix cache"). A page returns to the
    free-list stack only when the count drops to zero — the refcount-aware
    subset-push release program. All-zero init preserves the original
    exclusive-ownership semantics (cold admits set each popped page to 1).
    """

    def make(shape, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
        return jnp.zeros(shape, dtype)

    kv_dtype = kv_dtype or cfg.act_dtype
    kv_quantized = jnp.dtype(kv_dtype) == jnp.int8
    pools = []
    for spec in cfg.pattern:
        if spec.mixer == "attn":
            kv = (cfg.repeats, num_blocks, block_size, cfg.n_kv_heads,
                  cfg.head_dim)
            pool = {"k_pages": make(kv, kv_dtype),
                    "v_pages": make(kv, kv_dtype)}
            if kv_quantized:
                sc = (cfg.repeats, num_blocks, cfg.n_kv_heads)
                pool["k_scales"] = make(sc, jnp.float32)
                pool["v_scales"] = make(sc, jnp.float32)
            pools.append(pool)
        else:
            shapes = _slot_cache_shapes(spec, cfg, num_slots, block_size)
            pools.append({
                k: make((cfg.repeats, *v.shape), v.dtype)
                for k, v in shapes.items()
            })
    if abstract:
        free_list = jax.ShapeDtypeStruct((num_blocks,), jnp.int32)
        table = jax.ShapeDtypeStruct((num_slots, max_pages), jnp.int32)
    else:
        free_list = jnp.arange(num_blocks, dtype=jnp.int32)
        # entries == num_blocks are "no page" sentinels (clamped on gather,
        # dropped on scatter)
        table = jnp.full((num_slots, max_pages), num_blocks, jnp.int32)
    return {
        "pools": tuple(pools),
        "block_table": table,
        "seq_lens": make((num_slots,), jnp.int32),
        "active": make((num_slots,), bool),
        "uids": make((num_slots,), jnp.int32),
        "steps": make((num_slots,), jnp.int32),
        "last_tok": make((num_slots,), jnp.int32),
        "free_list": free_list,
        "free_top": make((), jnp.int32),
        "page_refcounts": make((num_blocks,), jnp.int32),
    }


def _named_layer(body):
    """A layer scan's body under ``jax.named_scope("layer")``. The scan's
    own work (slicing the stacked weights and pools per layer, writing the
    stacked outputs, the carry) stays outside it, under the caller's
    ``layer_scan`` scope: a profiler trace then tells the two apart."""
    def named(carry, xs):
        with jax.named_scope("layer"):
            return body(carry, xs)
    return named


def decode_step_paged(params, tokens, cache, cfg: ModelConfig, *,
                      attn_impl: str = "ref", attn_spec=None,
                      kv_scales=None):
    """One decode step over the paged cache. tokens: (num_slots, 1) int32.

    Unlike :func:`decode_step`'s single scalar ``index``, every slot
    advances at its own ``cache["seq_lens"]`` position (heterogeneous
    lengths are the point of paging); idle slots (``active`` False) compute
    but write nothing and do not advance. ``attn_spec`` is the optional
    :class:`~repro.quant.spec.AttnDatapathSpec` request, forwarded when
    the pools hold int8 quantized pages. ``kv_scales``: optional tuple
    aligned with ``cfg.pattern`` of calibrated static KV page scales
    (attention slots: ``{"k": (R, nkv), "v": (R, nkv)}`` f32; others:
    ``{}``) — joined to the scan xs only when present, so the default
    jaxpr is unchanged. Returns (logits, new_cache).

    Each pattern slot's component runs under a ``site_scope`` label
    ("slot0/mixer"), so an attached serving observer receives slot-granular
    site reports matching mixed-precision plan keys (repeats fold into the
    scan and aggregate under one label).
    """
    from repro.models.layers import paged_attention_decode, site_scope

    x = embed(params["embedding"], tokens, cfg)
    table = cache["block_table"]
    lens = cache["seq_lens"]
    active = cache["active"]

    def body(x, xs):
        if kv_scales is not None:
            layer_params, slot_caches, slot_kv = xs
        else:
            (layer_params, slot_caches), slot_kv = xs, None
        new_caches = []
        for i, spec in enumerate(cfg.pattern):
            p = layer_params[i]
            c_in = slot_caches[i]
            if spec.mixer == "attn":
                h = norm(p["norm1"], x, cfg.norm)
                sks = slot_kv[i] if slot_kv is not None and slot_kv[i] else None
                with site_scope(f"slot{i}/mixer"):
                    y, c_out = paged_attention_decode(
                        p["mixer"], h, cfg, c_in, table, lens, active,
                        impl=attn_impl, attn_spec=attn_spec,
                        static_kv_scales=sks,
                    )
                x = x + y
            elif spec.mixer != "none":
                h = norm(p["norm1"], x, cfg.norm)
                with site_scope(f"slot{i}/mixer"):
                    y, c_out = _mixer_decode(p, spec, cfg, h, c_in, 0)
                x = x + y
            else:
                c_out = c_in
            if spec.ffn != "none":
                h = norm(p["norm2"], x, cfg.norm)
                with site_scope(f"slot{i}/ffn"):
                    if spec.ffn == "moe":
                        y, _ = moe(p["ffn"], h, cfg)
                    else:
                        y = mlp(p["ffn"], h, cfg)
                x = x + y
            new_caches.append(c_out)
        return x, tuple(new_caches)

    xs = (params["layers"], cache["pools"])
    if kv_scales is not None:
        xs = (*xs, tuple(kv_scales))
    with jax.named_scope("layer_scan"):
        x, pools = jax.lax.scan(_named_layer(body), x, xs)
    with jax.named_scope("head"):
        x = norm(params["final_norm"], x, cfg.norm)
        logits = lm_logits(params["embedding"], x, cfg)
    new_cache = dict(cache)
    new_cache["pools"] = pools
    new_cache["seq_lens"] = lens + active.astype(lens.dtype)
    return logits, new_cache


def _mixer_decode(p, spec: LayerSpec, cfg: ModelConfig, h, cache, index):
    if spec.mixer == "attn":
        y, ck, cv = attention_decode(p["mixer"], h, cfg, cache["k"], cache["v"], index)
        return y, {"k": ck, "v": cv}
    if spec.mixer == "mamba":
        y, conv, ssm = mamba_decode(p["mixer"], h, cfg, cache["conv"], cache["ssm"])
        return y, {"conv": conv, "ssm": ssm}
    if spec.mixer == "mlstm":
        y, conv, C, n, m = mlstm_decode(
            p["mixer"], h, cfg, cache["conv"], cache["C"], cache["n"], cache["m"]
        )
        return y, {"conv": conv, "C": C, "n": n, "m": m}
    if spec.mixer == "slstm":
        y, hh, c, n, m = slstm_decode(
            p["mixer"], h, cfg, cache["h"], cache["c"], cache["n"], cache["m"]
        )
        return y, {"h": hh, "c": c, "n": n, "m": m}
    return jnp.zeros_like(h), {}


def decode_step(params, tokens, cache, index, cfg: ModelConfig):
    """One decode step. tokens: (B, 1) int32; index: scalar int32 position.

    Returns (logits (B, 1, V), new_cache).
    """
    x = embed(params["embedding"], tokens, cfg)

    def body(x, xs):
        layer_params, slot_caches = xs
        new_caches = []
        for i, spec in enumerate(cfg.pattern):
            p = layer_params[i]
            c_in = slot_caches[i]
            if spec.mixer != "none":
                h = norm(p["norm1"], x, cfg.norm)
                y, c_out = _mixer_decode(p, spec, cfg, h, c_in, index)
                x = x + y
            else:
                c_out = c_in
            if spec.ffn != "none":
                h = norm(p["norm2"], x, cfg.norm)
                if spec.ffn == "moe":
                    y, _ = moe(p["ffn"], h, cfg)
                else:
                    y = mlp(p["ffn"], h, cfg)
                x = x + y
            new_caches.append(c_out)
        return x, tuple(new_caches)

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embedding"], x, cfg), new_cache


# ---------------------------------------------------------------------------
# Prefill (forward + state emission for subsequent decode)
# ---------------------------------------------------------------------------
def _mixer_prefill(p, spec: LayerSpec, cfg: ModelConfig, h, positions, max_len,
                   prefix_kv=None):
    """Returns (y, cache_dict) with states positioned for decode at index S."""
    B, S, _ = h.shape
    if spec.mixer == "attn":
        y, (k, v) = attention(p["mixer"], h, cfg, positions,
                              prefix_kv=prefix_kv)
        pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
        return y, {
            "k": jnp.pad(k.astype(jnp.dtype(cfg.act_dtype)), pad),
            "v": jnp.pad(v.astype(jnp.dtype(cfg.act_dtype)), pad),
        }
    if cfg.prefill_mode == "parallel":
        # chunkwise-parallel prefill: the training-path kernels emit the
        # end-of-sequence state directly (§Perf iteration 1 — replaces the
        # O(S)-sequential stepwise fallback below; ~1000x memory-term win
        # on the 32k prefill cells, see EXPERIMENTS.md §Perf)
        if spec.mixer == "mamba":
            return mamba(p["mixer"], h, cfg, return_state=True)
        if spec.mixer == "mlstm":
            return mlstm(p["mixer"], h, cfg, return_state=True)
        if spec.mixer == "slstm":
            return slstm(p["mixer"], h, cfg, return_state=True)

    # stepwise fallback: rerun the sequence through the decode recurrence —
    # state-exact but sequential (kept as the §Perf baseline)
    cache = {
        k: jnp.zeros(v.shape, v.dtype)
        for k, v in _slot_cache_shapes(spec, cfg, B, max_len).items()
    }

    def step(carry, xt):
        x_t = xt[:, None, :]  # (B, 1, D)
        y_t, c_out = _mixer_decode(p, spec, cfg, x_t, carry, 0)
        return c_out, y_t[:, 0]

    cache, ys = jax.lax.scan(step, cache, h.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), cache


def prefill(params, batch, cfg: ModelConfig, max_len: int,
            prefix_kv=None, pos_offset: int = 0):
    """Run the prompt, returning (logits, cache ready for decode at index S).

    ``prefix_kv`` enables *suffix prefill* against a cached prompt prefix
    (the prefix-cache admit path): a tuple aligned with ``cfg.pattern``
    whose attention entries are ``{"k", "v"}`` of shape
    ``(R, B, L, nkv, hd)`` — per-repeat RoPE'd KV for the first ``L``
    prompt tokens, gathered (and dequantized, for int8 pools) from shared
    pages — and whose other entries are ``{}``. ``batch["tokens"]`` then
    carries only the uncached suffix and ``pos_offset`` must equal ``L``.
    The returned cache stays suffix-only: exactly what gets scattered
    into the request's *fresh* pages. With ``prefix_kv=None`` this is the
    original cold prefill, bit for bit (separate scan branch)."""
    x, positions = _embed_inputs(params, batch, cfg, pos_offset)

    def body(x, xs):
        layer_params = xs[0] if prefix_kv is not None else xs
        caches = []
        for i, spec in enumerate(cfg.pattern):
            p = layer_params[i]
            if spec.mixer != "none":
                h = norm(p["norm1"], x, cfg.norm)
                pkv = None
                if prefix_kv is not None and spec.mixer == "attn":
                    pkv = (xs[1][i]["k"], xs[1][i]["v"])
                y, c = _mixer_prefill(p, spec, cfg, h, positions, max_len,
                                      prefix_kv=pkv)
                x = x + y
            else:
                c = {}
            if spec.ffn != "none":
                h = norm(p["norm2"], x, cfg.norm)
                if spec.ffn == "moe":
                    y, _ = moe(p["ffn"], h, cfg)
                else:
                    y = mlp(p["ffn"], h, cfg)
                x = x + y
            caches.append(c)
        return x, tuple(caches)

    xs = params["layers"] if prefix_kv is None else (params["layers"],
                                                     tuple(prefix_kv))
    with jax.named_scope("layer_scan"):
        x, cache = jax.lax.scan(_named_layer(body), x, xs)
    with jax.named_scope("head"):
        x = norm(params["final_norm"], x, cfg.norm)
        return lm_logits(params["embedding"], x, cfg), cache
