"""Logical-axis sharding rules (DP/FSDP/TP/EP/SP) with divisibility fallbacks.

The model code annotates activations with *logical* axis names and the
parameter pytree is matched by leaf name; this module resolves both to
``NamedSharding``s on whatever mesh is active. Every resolution checks
divisibility (tensor dim % product of mesh axis sizes) and silently drops
the annotation when it does not divide — the degrade-gracefully property
that lets one set of rules serve 10 architectures and any mesh shape
(elastic restarts included).

Logical axes:
  batch   -> ("pod", "data")   pure data parallel (pod = DCN axis)
  expert  -> "data"            expert parallelism for MoE stacks
  model / heads / kv_heads / ffn / vocab -> "model"   tensor parallelism
  data_in -> "data"            FSDP-style weight sharding (row dim)
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "expert": ("data",),
    "data_in": ("data",),
    "model": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "seq": ("model",),  # sequence parallelism (opt-in annotations)
}

# Serving rules for packed-int4 decode (§Perf-3): weights are 4x smaller so
# they fit *without* the FSDP dim — TP over both mesh axes, keeping weights
# stationary (no per-token all-gather; activations, which are tiny at
# decode, move instead).
SERVING_QUANT_RULES: dict[str, tuple[str, ...]] = {
    **DEFAULT_RULES,
    "data_in": (),
    "model": ("model", "data"),
    "heads": ("model", "data"),
    "kv_heads": ("model",),
    "ffn": ("model", "data"),
    "vocab": ("model", "data"),
}

_state = threading.local()


def _current() -> tuple[Mesh, dict] | None:
    return getattr(_state, "active", None)


@contextmanager
def axis_rules(mesh: Mesh, rules: dict[str, tuple[str, ...]] | None = None):
    """Activate logical-axis resolution against ``mesh`` for model code."""
    prev = _current()
    _state.active = (mesh, rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _state.active = prev


def _manual_axes() -> frozenset:
    """Mesh axes currently under manual (shard_map) control — they must not
    appear in sharding constraints issued from inside the region."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return frozenset()
    return frozenset(
        n for n in am.axis_names
        if am._name_to_type[n] == jax.sharding.AxisType.Manual
    )


def _mesh_axes_for(logical: str | None, mesh: Mesh, rules: dict) -> tuple[str, ...]:
    if logical is None:
        return ()
    manual = _manual_axes()
    return tuple(
        a for a in rules.get(logical, ()) if a in mesh.shape and a not in manual
    )


def resolve_spec(shape: tuple[int, ...], names, mesh: Mesh, rules: dict) -> P:
    """Logical names -> PartitionSpec with per-dim divisibility fallback.

    A mesh axis consumed by an earlier dim is unavailable to later dims
    (PartitionSpec forbids reuse) — this is what makes compound rules like
    MoE ("expert", "data_in", ...) degrade to FSDP row-sharding exactly when
    the expert count does not divide the data axis (granite's 40 experts),
    and to expert-parallel when it does (dbrx's 16).
    """
    spec = []
    used: set[str] = set()
    for dim, name in zip(shape, names):
        axes = tuple(a for a in _mesh_axes_for(name, mesh, rules) if a not in used)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if axes and dim % size == 0 and dim > 0:
            spec.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            spec.append(None)
    return P(*spec)


def prefer_expert_sharding(n_experts: int) -> bool:
    """True when the expert axis can actually shard ``n_experts`` on the
    active mesh (EP); False -> MoE activations stay token-sharded and the
    experts compute replicated-weightless via FSDP gathers (§Perf-2)."""
    active = _current()
    if active is None:
        return True
    mesh, rules = active
    axes = _mesh_axes_for("expert", mesh, rules)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return bool(axes) and size > 1 and n_experts % size == 0


def logical_constraint(x, names):
    """with_sharding_constraint by logical names; no-op without active rules."""
    active = _current()
    if active is None:
        return x
    mesh, rules = active
    if len(names) != x.ndim:
        raise ValueError(f"names {names} rank != array rank {x.ndim}")
    spec = resolve_spec(x.shape, names, mesh, rules)
    # inside a partial-manual shard_map the context abstract mesh carries
    # Manual axis types — shardings must be built against it, not the
    # outer concrete mesh, or broadcast/constraint ops reject the mix
    target = mesh
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is not None and not am.empty:
            target = am
    except Exception:
        pass
    return jax.lax.with_sharding_constraint(x, NamedSharding(target, spec))


# ---------------------------------------------------------------------------
# Parameter sharding by leaf name
# ---------------------------------------------------------------------------
# 2D weights, (in, out) convention: name -> logical names per dim.
_W2 = {
    # row-parallel producers: input dim FSDP-sharded, output dim TP-sharded
    "wq": ("data_in", "model"),
    "wk": ("data_in", "model"),
    "wv": ("data_in", "model"),
    "wg": ("data_in", "model"),
    "wu": ("data_in", "model"),
    "wi": ("data_in", "model"),
    "up": ("data_in", "model"),
    "in_proj": ("data_in", "model"),
    "w_in": ("data_in", "model"),
    # column-parallel consumers: input dim TP-sharded, output dim FSDP-sharded
    "wo": ("model", "data_in"),
    "wd": ("model", "data_in"),
    "down": ("model", "data_in"),
    "out_proj": ("model", "data_in"),
    # vocab-parallel embeddings (rows = vocab)
    "embed": ("vocab", "data_in"),
    "head": ("vocab", "data_in"),
    # mamba inner projections (d_in is the TP dim)
    "x_proj": ("model", None),
    "dt_proj": (None, "model"),
    "A_log": ("model", None),
    "conv_w": (None, "model"),
    "router": (None, None),
}
# 3D MoE expert stacks: EP over data when the expert count divides it,
# otherwise (dedup/divisibility fallback in resolve_spec) FSDP row-sharding
# over data — replicated expert weights were the §Perf-2 baseline pathology
# (full-gradient all-reduce every microbatch).
_W3 = {
    "wg": ("expert", "data_in", "model"),
    "wu": ("expert", "data_in", "model"),
    "wi": ("expert", "data_in", "model"),
    "wd": ("expert", "model", "data_in"),
}
_W1 = {
    "conv_b": ("model",),
    "D": ("model",),
    "dt_bias": ("model",),
    "skip": ("model",),
    "f_bias": (None,),
    "norm_w": (None,),
    "w": (None,),
    "b": (None,),
}
_W4 = {
    "r": (None, None, None, "model"),  # sLSTM block-diag recurrent
}


_PACKED_LEAF_SUFFIXES = (
    "packed", "meta", "scale", "col_sums", "bias", "act_scale", "act_zp",
    "spec_arr",
)
#: packed-leaf members that are tiny per-site metadata (static activation
#: quantizer scalars, the serialized DatapathSpec twin): always replicated
_REPLICATED_SUFFIXES = ("act_scale", "act_zp", "spec_arr")


def _leaf_logical_names(path, leaf) -> tuple:
    keys = [e.key for e in path if hasattr(e, "key")]
    name = keys[-1] if keys else None
    # packed-int4 serving artifacts: {"packed", "meta", "scale", "col_sums",
    # "bias", "act_scale", "act_zp", "spec_arr"} under the weight name.
    # "meta" (2:4 sparse index leaf, (K//4, N)) co-shards with "packed":
    # both fall through to the weight-name table below, so a device holding
    # a shard of the codes holds the matching shard of the indices.
    suffix = None
    if name in _PACKED_LEAF_SUFFIXES and len(keys) >= 2:
        suffix, name = name, keys[-2]
    if suffix in _REPLICATED_SUFFIXES:
        return (None,) * leaf.ndim
    ndim = leaf.ndim
    stacked = _is_stacked(path)
    base = ndim - (1 if stacked else 0)
    table = {1: _W1, 2: _W2, 3: _W3, 4: _W4}.get(base, {})
    names = table.get(name, (None,) * base)
    if suffix in ("scale", "col_sums", "bias"):
        # (1, N) / (N,) per-channel vectors: shard only the channel dim —
        # except the expert axis of MoE stacks, which must co-shard with
        # the packed codes (expert-parallel decode: a device holding an
        # expert's codes must hold its scales/col_sums, or every EP
        # matmul pays a cross-device gather of the dequant metadata)
        chan = names[-1] if names else None
        mids = [None] * (base - 1)
        if mids and names and names[0] == "expert":
            mids[0] = "expert"
        names = (*mids, chan)
    if stacked:
        names = (None, *names)  # leading repeats axis: never sharded
    return names


def _is_stacked(path) -> bool:
    """Leaves under params["layers"] are stacked over repeats."""
    for entry in path:
        if hasattr(entry, "key") and entry.key == "layers":
            return True
    return False


def param_shardings(params, mesh: Mesh, rules: dict | None = None):
    """NamedSharding pytree for a parameter (or optimizer-state) pytree."""
    rules = rules or DEFAULT_RULES

    def one(path, leaf):
        names = _leaf_logical_names(path, leaf)
        spec = resolve_spec(leaf.shape, names, mesh, rules)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def batch_shardings(batch, mesh: Mesh, rules: dict | None = None):
    """Batch dict: dim 0 = global batch -> ("pod", "data")."""
    rules = rules or DEFAULT_RULES

    def one(leaf):
        names = ("batch",) + (None,) * (leaf.ndim - 1)
        return NamedSharding(mesh, resolve_spec(leaf.shape, names, mesh, rules))

    return jax.tree_util.tree_map(one, batch)


#: paged-cache bookkeeping leaves (block tables, free-list stack, per-slot
#: scalars): tiny int32/bool state that every device must see in full —
#: always replicated. The page *pools* shard like dense KV (kv_heads dim);
#: the page axis itself never shards: pages are dynamically indexed across
#: sequences, so splitting it would turn every block-table chase into a
#: cross-device gather.
_PAGED_ADMIN_LEAVES = (
    "block_table", "seq_lens", "active", "uids", "steps", "last_tok",
    "free_list", "free_top", "page_refcounts",
)


def cache_shardings(cache, cfg, mesh: Mesh, rules: dict | None = None):
    """Decode caches: (R, B, ...) — batch on dim 1, trailing dims by kind.

    KV caches prefer head sharding over ``model``; when kv_heads does not
    divide the model axis (GQA kv=8 on a 16-wide TP axis — llama3/dbrx/
    granite/jamba), fall back to *sequence-sharded* KV (context-parallel
    decode: XLA reduces the attention softmax/contraction over the sharded
    sequence dim). That is what keeps a 126-layer 32k-deep cache inside
    16 GB/chip — see EXPERIMENTS.md §Dry-run.
    """
    rules = rules or DEFAULT_RULES
    model_size = mesh.shape.get("model", 1)
    paged = isinstance(cache, dict) and "free_list" in cache

    def one(path, leaf):
        name = None
        for entry in reversed(path):
            if hasattr(entry, "key"):
                name = entry.key
                break
        if paged:
            top = next((e.key for e in path if hasattr(e, "key")), None)
            if top != "pools" and top not in _PAGED_ADMIN_LEAVES:
                # loud by design: a silently-replicated new pool leaf is
                # exactly the bug class the mesh CI lane exists to catch —
                # every paged top-level leaf must be either under "pools"
                # (sharding decided by kind below) or a declared admin leaf
                raise ValueError(
                    f"unknown paged cache leaf {top!r}: not under 'pools' and "
                    f"not in _PAGED_ADMIN_LEAVES {_PAGED_ADMIN_LEAVES}; declare "
                    "its sharding explicitly in runtime.sharding"
                )
        if name in _PAGED_ADMIN_LEAVES:
            return NamedSharding(mesh, P())
        if name in ("k_pages", "v_pages") and leaf.ndim == 5:
            # (R, num_blocks, block_size, nkv, hd): shard kv heads only
            # (divisibility fallback in resolve_spec -> replicated)
            names = (None, None, None, "kv_heads", None)
            return NamedSharding(mesh, resolve_spec(leaf.shape, names, mesh, rules))
        if name in ("k_scales", "v_scales") and leaf.ndim == 3:
            # (R, num_blocks, nkv): int8-KV per-(page, head) scales — must
            # co-shard with the pools' kv_heads axis so a device holding a
            # head's codes also holds its scales; page axis never shards
            names = (None, None, "kv_heads")
            return NamedSharding(mesh, resolve_spec(leaf.shape, names, mesh, rules))
        if name in ("k", "v") and leaf.ndim == 5:
            nkv = leaf.shape[3]
            if nkv % model_size == 0:
                trailing = (None, "kv_heads", None)
            else:
                # seq-sharded KV fallback. Measured alternative (§Perf-3,
                # REFUTED): sharding head_dim instead keeps the per-token
                # cache write local, but the partitioner then all-gathers
                # the hd-sharded cache for the score contraction — coll
                # 5.1 s vs the 2.6 s select-rewrite this avoids. The real
                # fix is a two-level (prefix + append-buffer) cache,
                # documented in EXPERIMENTS.md §Perf as future work.
                trailing = ("seq", None, None)
        else:
            trailing = {
                "conv": (None, "ffn"),
                "ssm": ("ffn", None),
                "C": ("heads", None, None),
                "n": ("heads", None),
                "m": ("heads",),
                "h": (None,),
                "c": (None,),
            }.get(name, (None,) * (leaf.ndim - 2))
        names = (None, "batch", *trailing)
        names = names[: leaf.ndim]
        return NamedSharding(mesh, resolve_spec(leaf.shape, names, mesh, rules))

    return jax.tree_util.tree_map_with_path(one, cache)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Mesh-native paged serving (docs/multihost.md)
# ---------------------------------------------------------------------------


def paged_engine_shardings(params, cache, cfg, mesh: Mesh,
                           rules: dict | None = None):
    """(param_shardings, cache_shardings) for the paged engine's jitted
    programs — the out_shardings contract: every program returns its cache
    operand under exactly these shardings (pools kv-head-sharded, admin
    leaves replicated) and every token/stream output fully replicated, so
    a host read of any output touches only local shards."""
    rules = SERVING_QUANT_RULES if rules is None else rules
    return (
        param_shardings(params, mesh, rules),
        cache_shardings(cache, cfg, mesh, rules),
    )


def rows_sharding(shape: tuple[int, ...], mesh: Mesh,
                  rules: dict | None = None) -> NamedSharding:
    """Row (dim 0) sharding for per-request host inputs — batched-admit
    prompt blocks shard per-host over the data axis when the row count
    divides it (divisibility fallback -> replicated)."""
    rules = DEFAULT_RULES if rules is None else rules
    names = ("batch",) + (None,) * (len(shape) - 1)
    return NamedSharding(mesh, resolve_spec(shape, names, mesh, rules))


def host_to_global(tree, shardings):
    """Place a host (or local single-device) pytree onto global shardings.

    Multihost-safe: built via ``jax.make_array_from_callback`` from the
    host copy, so it works whether the sharding spans one process or many.
    Every process must hold the identical full value (true for the paged
    engine: params/cache init is seed-deterministic on every host)."""

    def put(x, sh):
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx, a=arr: a[idx]
        )

    return jax.tree.map(put, tree, shardings)


def host_read(x):
    """Fetch an array to host memory, multihost-safe.

    ``jax.device_get`` refuses non-fully-addressable arrays (any replicated
    output of a multi-process computation). Replicated means every shard
    holds the full value, so reading one local shard *is* the global read —
    this is what makes the engine's one-device_get-per-chunk rule hold
    unchanged under a multi-process mesh."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return np.asarray(x.addressable_data(0))
    return jax.device_get(x)
