"""Packed-int4 serving parameters (the §Perf-3 / beyond-paper decode path).

``pack_decode_params`` transforms a model's layer weights into packed
artifact leaves; the model layers dequantize transparently via
``resolve_weight`` or ride the fused W4A8 kernel via ``packed_linear``.
Decode at large batch is weight-traffic-bound, so int4 packing cuts the
dominant HBM term ~4x vs bf16 (the paper's W4A8 + AXE certificate is what
makes the low-precision *accumulation* of this datapath safe — see
repro.kernels.w4a8_mm for the true-integer TPU kernel).

Every packed leaf embeds the :class:`~repro.quant.spec.DatapathSpec` it was
packed for — tile T, inner/outer accumulator widths, activation-quantizer
kind — as a static ``spec`` node plus a persistable ``spec_arr`` array
twin, and (for calibrated artifacts) the static activation quantizer as
``act_scale``/``act_zp`` leaves. The kernel dispatch reads all of its
accumulator knobs from the spec; nothing is re-declared as kwargs
downstream. See docs/datapath.md for the schema and version history.

Which leaves get packed is *not* hardcoded: the quantizable-site registry
(:mod:`repro.quant.families`) enumerates every family's sites from the
model config alone, so dense, MoE (expert-stacked), Mamba and xLSTM stacks
— and hybrids like Jamba — all pack through the same transform. Sites
whose reduction depth K is odd (e.g. an odd Mamba dt_rank) are left in
high precision rather than padded.

Works under ``jax.eval_shape`` (all ops traceable), so the 405B dry-run can
lower the quantized decode graph without materializing weights. For real
deployments the packed codes come from the AXE pipeline
(:func:`serving_params_from_quantized` in memory, or
``repro.launch.quantize`` -> :func:`packed_params_from_artifact` via disk);
the RTN packing in ``pack_decode_params`` is the shape-compatible fallback
used when no calibrated artifact is supplied.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.w4a8_mm import (
    LANE,
    _round_up,
    compress_2to4,
    pack_int4,
    pad_packed,
    unpack_int4,
    unpack_sparse24,
)
from repro.models.config import ModelConfig

from .families import SiteSpec, check_supported, get_adapter
from .spec import (
    ARTIFACT_VERSION,
    _SPEC_ARR_LEN,
    DatapathMismatchError,
    DatapathSpec,
    is_packed_leaf,
    leaf_datapath,
)

__all__ = [
    "ensure_col_sums",
    "ensure_datapath_spec",
    "export_quantized_artifact",
    "load_flat_artifact",
    "pack_decode_params",
    "packable_sites",
    "packed_params_from_artifact",
    "packed_weight_bytes",
    "plan_expected_specs",
    "serving_params_from_quantized",
    "upgrade_packed_params",
]


def packable_sites(cfg: ModelConfig):
    """Per pattern slot: {"mixer": (SiteSpec...), "ffn": (SiteSpec...)} of
    sites with an even (packable) reduction depth."""
    slots = []
    for spec in cfg.pattern:
        slot = {}
        for kind, name in (("mixer", spec.mixer), ("ffn", spec.ffn)):
            if name == "none":
                slot[kind] = ()
                continue
            sites = get_adapter(kind, name).enumerate_sites(cfg)
            slot[kind] = tuple(s for s in sites if s.k % 2 == 0)
        slots.append(slot)
    return slots


def _spec_arr_leaf(spec: DatapathSpec, lead: tuple[int, ...]) -> jax.Array:
    """The persistable array twin of the static spec node, broadcast over
    the leaf's leading stack axes (repeats / experts). Stored f32 — every
    field is a small integer or an activation scale, and f32 keeps the
    leaf dtype independent of the jax x64 flag."""
    arr = jnp.asarray(spec.to_array(), jnp.float32)
    return jnp.broadcast_to(arr, (*lead, arr.shape[0]))


def _rtn_codes(w: jax.Array, w_bits: int) -> tuple[jax.Array, jax.Array]:
    """Round-to-nearest integer codes + per-channel scales for a
    (..., K, N) weight, via the same symmetric alphabet/quantizer the
    calibration path uses (repro.core.quantizers) — stack-aware (the
    channel reduction runs over axis -2) and with the serving-side 1e-8
    scale floor."""
    from repro.core.alphabet import weight_alphabet
    from repro.core.quantizers import quantize_int, weight_scales

    alpha = weight_alphabet(w_bits)
    scale = weight_scales(w.astype(jnp.float32), alpha, axis=-2, eps=1e-8)
    return quantize_int(w.astype(jnp.float32) / scale, alpha), scale


def _pack_leaf(w: jax.Array, spec: DatapathSpec | None = None) -> dict:
    """(..., K, N) -> packed int4 + per-channel scale (stack-aware: leading
    repeat/expert axes pass straight through). Dense codes are zero-padded
    to whole 128-lane K and N blocks (``pad_packed``; the logical N is the
    ``scale`` leaf's, the logical K the activation's). ``col_sums`` is the
    per-channel sum of int4 codes over K, precomputed here once so the
    decode kernel's zero-point correction never needs a full
    ``unpack_int4`` of the weights at serving time (repro.kernels.w4a8_mm
    epilogue: corr[n] = act_zp * col_sums[n]). The leaf embeds ``spec``
    (static node + ``spec_arr`` array twin); RTN packing never ships
    static activation quantizers — those come from calibration
    (:func:`serving_params_from_quantized`) — so ``static_act`` is cleared
    here: the embedded record must describe the datapath this leaf
    actually serves, not the one the caller wished for."""
    from dataclasses import replace

    spec = replace((spec or DatapathSpec()).leaf_spec(), static_act=False)
    if spec.w_bits > 4:
        # pack_int4 would mask codes to 4 bits and silently corrupt the
        # weights — callers must keep such sites in high precision
        # (pack_decode_params / _site_rec_leaf fall back to a dequantized
        # float leaf)
        raise ValueError(
            f"int4 packing supports w_bits <= 4, got {spec.w_bits}; "
            f"serve this site as a high-precision leaf instead"
        )
    q, scale = _rtn_codes(w, spec.w_bits)
    lead = w.shape[:-2]
    if spec.sparsity is not None:
        # mask-then-round 2:4 baseline (no error feedback — calibrated
        # sparse codes come from the AXE pipeline's mask-aware GPFQ/OPTQ).
        # Traceable, so eval_shape dry-runs still lower the sparse graph.
        from repro.core.sparsity import mask_2to4

        if w.shape[-2] % 4 != 0:
            raise ValueError(
                f"2:4 sparsity needs K % 4 == 0, got K={w.shape[-2]}; "
                f"serve this site dense or in high precision instead"
            )
        q = q * mask_2to4(q)
        packed, meta = compress_2to4(q)
        return {
            "packed": packed,
            "meta": meta,
            "scale": scale.astype(jnp.bfloat16),
            "col_sums": jnp.sum(q, axis=-2, keepdims=True).astype(jnp.int32),
            "spec": spec,
            "spec_arr": _spec_arr_leaf(spec, lead),
        }
    return {
        "packed": pad_packed(pack_int4(q)),
        "scale": scale.astype(jnp.bfloat16),
        "col_sums": jnp.sum(q, axis=-2, keepdims=True).astype(jnp.int32),
        "spec": spec,
        "spec_arr": _spec_arr_leaf(spec, lead),
    }


def pack_decode_params(params, cfg: ModelConfig, ptq=None):
    """Replace every registered quantizable-site weight with its packed
    artifact (RTN codes — the shape-compatible fallback when no calibrated
    artifact is supplied). ``ptq`` (a :class:`~repro.core.PTQConfig` or a
    base :class:`~repro.quant.spec.DatapathSpec`) selects the datapath each
    leaf is stamped with, specialized per site depth via
    ``SiteSpec.datapath_for``; default is the recipe datapath. Raises
    NotImplementedError (listing the registry) when the pattern contains a
    component with no family adapter."""
    check_supported(cfg)
    new_layers = []
    for slot_params, slot_sites in zip(params["layers"], packable_sites(cfg)):
        new_slot = dict(slot_params)
        for kind in ("mixer", "ffn"):
            if kind not in new_slot:
                continue
            by_name = {s.path[-1]: s for s in slot_sites[kind]}

            def leaf_for(k, v):
                if k not in by_name:
                    return v
                site = by_name[k]
                spec = (site.datapath_for(ptq) if ptq is not None
                        else site.datapath) or DatapathSpec()
                if spec.sparsity is not None and site.k % 4 != 0:
                    # 2:4 groups need K % 4 == 0; Eq. 22 halves depth and
                    # tile together, so stripping sparsity leaves p_outer
                    # valid for the dense codes this site actually serves
                    from dataclasses import replace

                    spec = replace(spec, sparsity=None)
                if spec.w_bits > 4:
                    # no int4 container for these codes: serve the site as
                    # an RTN-dequantized high-precision leaf instead
                    q, s = _rtn_codes(v, spec.w_bits)
                    return (q * s).astype(v.dtype)
                return _pack_leaf(v, spec)

            new_slot[kind] = {
                k: leaf_for(k, v) for k, v in slot_params[kind].items()
            }
        new_layers.append(new_slot)
    return {
        "embedding": params["embedding"],
        "layers": tuple(new_layers),
        "final_norm": params["final_norm"],
    }


# ---------------------------------------------------------------------------
# Calibrated artifacts: QuantizedModel -> serving tree, and the disk format
# ---------------------------------------------------------------------------
def _site_rec_leaf(recs: list[dict], site: SiteSpec, name: str):
    """Stack per-repeat site records into one serving leaf.

    Each record: {"q": (…, K, C) int8-valued codes, "scale": (…, 1, C),
    "spec": DatapathSpec (with act numerics), "bias": optional}. Returns a
    packed leaf dict, or a plain dequantized float array when the site
    cannot ride the int4 datapath (w_bits > 4 / odd K).
    """
    spec0 = recs[0]["spec"]
    for r, rec in enumerate(recs):
        if not spec0.matches(rec["spec"]):
            raise DatapathMismatchError(
                f"site {name}: repeat 0 certified {spec0.describe()} but "
                f"repeat {r} certified {rec['spec'].describe()} — one leaf "
                f"cannot serve two datapaths"
            )
    if spec0.w_bits > 4 or site.k % 2 != 0 or (
        spec0.sparsity is not None and site.k % 4 != 0
    ):
        # no int4 container (wide codes / odd K): serve the dequantized
        # weight in high precision. The corrected bias is part of the
        # certified function, so it rides along in a {"w", "bias"} leaf
        # (repro.models.layers.pmm dispatches it) instead of being dropped.
        w_q = jnp.stack(
            [jnp.asarray(r["q"], jnp.float32) * jnp.asarray(r["scale"], jnp.float32)
             for r in recs]
        )
        if site.use_bias and recs[0].get("bias") is not None:
            return {
                "w": w_q,
                "bias": jnp.stack(
                    [jnp.asarray(r["bias"], jnp.float32) for r in recs]
                ),
            }
        return w_q
    lead = (len(recs),) + ((site.stacked,) if site.stacked else ())
    q = jnp.stack([jnp.asarray(r["q"], jnp.float32) for r in recs])
    if spec0.sparsity is not None:
        # the certificate was issued against the 2:4 effective depth —
        # codes that are not actually 2:4 would be served under a bound
        # they do not satisfy, so refuse loudly at pack/load time
        from repro.core.sparsity import is_2to4

        if not is_2to4(np.asarray(q)):
            raise DatapathMismatchError(
                f"site {name}: certified sparsity={spec0.sparsity!r} but the "
                f"codes are not 2:4 (some group of 4 along K has more than "
                f"2 nonzeros) — the certificate's effective-depth bound "
                f"would not hold for these weights"
            )
        packed_codes, meta = compress_2to4(q)
    else:
        packed_codes, meta = pad_packed(pack_int4(q)), None
    leaf = {
        "packed": packed_codes,
        "scale": jnp.stack([jnp.asarray(r["scale"], jnp.float32) for r in recs]),
        "col_sums": jnp.sum(q, axis=-2, keepdims=True).astype(jnp.int32),
        "spec": spec0.leaf_spec(),
        "spec_arr": jnp.stack(
            [
                jnp.broadcast_to(
                    arr := jnp.asarray(r["spec"].to_array(), jnp.float32),
                    (*lead[1:], arr.shape[0]),
                )
                for r in recs
            ]
        ),
    }
    if meta is not None:
        leaf["meta"] = meta
    if spec0.static_act:
        # stacked scales: one scalar per repeat, broadcast per expert for
        # MoE stacks so the vmapped kernel maps a per-expert quantizer
        leaf["act_scale"] = jnp.stack(
            [jnp.full(lead[1:], r["spec"].act_scale, jnp.float32) for r in recs]
        )
        leaf["act_zp"] = jnp.stack(
            [jnp.full(lead[1:], float(r["spec"].act_zp), jnp.float32) for r in recs]
        )
    if site.use_bias and recs[0].get("bias") is not None:
        leaf["bias"] = jnp.stack(
            [jnp.asarray(r["bias"], jnp.float32) for r in recs]
        )
    return leaf


def _set_path(tree: dict, path: tuple[str, ...], value) -> None:
    d = tree
    for key in path[:-1]:
        d[key] = dict(d[key])
        d = d[key]
    d[path[-1]] = value


def serving_params_from_quantized(qm) -> dict:
    """Build the packed serving tree straight from a calibrated
    :class:`~repro.quant.QuantizedModel` — codes, per-channel scales,
    *static* activation quantizers, corrected biases and the per-site
    :class:`~repro.quant.spec.DatapathSpec`, with no kwarg re-specification
    anywhere downstream. Float leaves (norms — equalization-folded —
    routers, conv/SSM parameters) come from the quantized model too, so
    the tree is faithful to what calibration certified."""
    cfg = qm.cfg
    new_layers = []
    for s in range(cfg.period):
        blocks = [qm.blocks[r * cfg.period + s] for r in range(cfg.repeats)]
        slot: dict = {}
        for norm_name in ("norm1", "norm2"):
            norms = [getattr(b, norm_name) for b in blocks]
            if norms[0] is not None:
                slot[norm_name] = {
                    k: jnp.stack([jnp.asarray(n[k]) for n in norms])
                    for k in norms[0]
                }
        for kind in ("mixer", "ffn"):
            comps = [getattr(b, kind) for b in blocks]
            if comps[0] is None:
                continue
            out = {
                k: jnp.stack([jnp.asarray(c.params[k]) for c in comps])
                for k, v in comps[0].params.items()
                if v is not None
            }
            for site in comps[0].specs.values():
                recs = [
                    {
                        "q": c.linears[site.name].q_int,
                        "scale": c.linears[site.name].scale,
                        "spec": c.linears[site.name].spec,
                        "bias": c.linears[site.name].bias,
                    }
                    for c in comps
                ]
                _set_path(out, site.path,
                          _site_rec_leaf(recs, site, f"slot{s}/{kind}.{site.name}"))
            slot[kind] = out
        new_layers.append(slot)
    return {
        "embedding": qm.embedding,
        "layers": tuple(new_layers),
        "final_norm": qm.final_norm,
    }


def export_quantized_artifact(qm) -> tuple[dict, dict]:
    """Flatten a calibrated QuantizedModel into the versioned on-disk
    artifact: {"layer{i}/{kind}.{site}/{q,scale,bias,spec}"} numpy leaves
    plus the equalization-touched float leaves (norms, MoE routers), and a
    meta dict carrying the schema version. Codes are stored raw int8
    (packing happens at load, where the serving layout is known)."""
    artifact: dict[str, np.ndarray] = {}
    site_specs = []
    for name, ql in qm.quantized_linears():
        artifact[f"{name}/q"] = np.asarray(ql.q_int, np.int8)
        artifact[f"{name}/scale"] = np.asarray(ql.scale, np.float32)
        if ql.bias is not None:
            artifact[f"{name}/bias"] = np.asarray(ql.bias, np.float32)
        spec = ql.spec if ql.spec is not None else ql.cfg.to_datapath_spec(
            ql.q_int.shape[-2], ql.act
        )
        artifact[f"{name}/spec"] = spec.to_array()
        site_specs.append(spec)
    site_keys = {s.key() for s in site_specs}
    for i, b in enumerate(qm.blocks):
        for norm_name in ("norm1", "norm2"):
            nrm = getattr(b, norm_name)
            if nrm is not None:
                for k, v in nrm.items():
                    artifact[f"layer{i}/{norm_name}/{k}"] = np.asarray(v)
        # the MoE router consumes the equalized input: its folded weights
        # must travel with the artifact or routing diverges at serving
        if b.ffn is not None and b.ffn.params.get("router") is not None:
            artifact[f"layer{i}/ffn.float/router"] = np.asarray(
                b.ffn.params["router"]
            )
    meta = {
        "artifact_version": ARTIFACT_VERSION,
        "arch": qm.cfg.name,
        "n_layers": qm.cfg.n_layers,
        # heterogeneous per-site datapaths: the loader switches to strict
        # site accounting (a dropped site would silently change which
        # datapath serves — satellite of the mixed-precision search)
        "mixed_precision": len(site_keys) > 1,
        "datapath": (
            site_specs[0].describe() if len(site_keys) == 1
            else f"mixed: {len(site_keys)} site datapaths"
        ) if site_specs else "empty",
    }
    return artifact, meta


def load_flat_artifact(directory: str) -> tuple[dict, dict]:
    """Template-free load of a flat artifact directory written by
    ``repro.checkpoint.save_pytree`` on a flat dict: parse the manifest
    directly instead of requiring a matching target pytree."""
    import json
    import os

    with open(os.path.join(directory, "manifest.json")) as f:
        manifest = json.load(f)
    flat = {}
    for entry in manifest["leaves"]:
        name = entry["name"]
        # keystr of a flat string key: "['layer0/mixer.wq/q']"
        if name.startswith("['") and name.endswith("']"):
            name = name[2:-2]
        arr = np.load(os.path.join(directory, entry["file"]))
        if arr.dtype.kind == "V":
            # np.save keeps bfloat16 leaves (the norms of a bf16 model) as
            # raw 2-byte records; the manifest names their dtype
            arr = arr.view(jnp.dtype(entry["dtype"]))
        flat[name] = arr
    return flat, manifest.get("meta", {})


def packed_params_from_artifact(flat: dict, params, cfg: ModelConfig,
                                meta: dict | None = None,
                                strict: bool | None = None):
    """Rebuild the packed serving tree from a saved AXE artifact.

    ``params`` supplies the high-precision leaves the artifact does not
    carry (embedding, non-equalized component floats); quantized sites,
    norms and routers are overridden from the artifact. Validates the
    artifact schema version loudly — a mismatched or unversioned artifact
    raises :class:`~repro.quant.spec.DatapathMismatchError` instead of
    being served with guessed semantics.

    ``strict`` (default: the artifact meta's ``mixed_precision`` flag)
    refuses *partial* coverage: a site the model enumerates but the
    artifact does not carry raises instead of silently staying float.
    Quantized artifact keys that match **no** enumerated site always
    raise — the artifact and the model disagree about what the model is.
    """
    if meta is not None:
        v = meta.get("artifact_version")
        if v != ARTIFACT_VERSION:
            raise DatapathMismatchError(
                f"artifact schema version {v!r} != supported "
                f"{ARTIFACT_VERSION}; re-export with repro.launch.quantize "
                f"(see docs/datapath.md for the version history)"
            )
        for field, want in (("arch", cfg.name), ("n_layers", cfg.n_layers)):
            got = meta.get(field)
            if got is not None and got != want:
                raise DatapathMismatchError(
                    f"artifact was exported for {field}={got!r} but the "
                    f"serving config is {field}={want!r} — an arch-"
                    f"mismatched artifact would silently serve float "
                    f"weights instead of the certified codes"
                )
    check_supported(cfg)
    if strict is None:
        strict = bool(meta and meta.get("mixed_precision"))
    n_sites_loaded = 0
    consumed: set[str] = set()
    missing: list[str] = []
    new_layers = []
    for s, pattern_spec in enumerate(cfg.pattern):
        slot = dict(params["layers"][s])
        layer_ids = [r * cfg.period + s for r in range(cfg.repeats)]
        for norm_name in ("norm1", "norm2"):
            key0 = f"layer{layer_ids[0]}/{norm_name}/w"
            if key0 in flat and norm_name in slot:
                slot[norm_name] = {
                    k: jnp.stack([
                        jnp.asarray(flat[f"layer{i}/{norm_name}/{k}"])
                        for i in layer_ids
                    ])
                    for k in slot[norm_name]
                }
        for kind, fam in (("mixer", pattern_spec.mixer), ("ffn", pattern_spec.ffn)):
            if fam == "none" or kind not in slot:
                continue
            out = dict(slot[kind])
            if f"layer{layer_ids[0]}/ffn.float/router" in flat and kind == "ffn":
                out["router"] = jnp.stack([
                    jnp.asarray(flat[f"layer{i}/ffn.float/router"])
                    for i in layer_ids
                ])
            for site in get_adapter(kind, fam).enumerate_sites(cfg):
                names = [f"layer{i}/{kind}.{site.name}" for i in layer_ids]
                present = [n for n in names if f"{n}/q" in flat]
                consumed.update(f"{n}/q" for n in present)
                if len(present) != len(names):
                    # all-or-nothing per slot: a partially covered slot can
                    # never stack one leaf, and silent float fallback is
                    # exactly what strict loading forbids
                    if present or strict:
                        missing.append(
                            f"slot{s}/{kind}.{site.name} (have "
                            f"{len(present)}/{len(names)} repeats)")
                    continue
                recs = [
                    {
                        "q": flat[f"{n}/q"],
                        "scale": flat[f"{n}/scale"],
                        "spec": DatapathSpec.from_array(flat[f"{n}/spec"]),
                        "bias": flat.get(f"{n}/bias"),
                    }
                    for n in names
                ]
                _set_path(out, site.path, _site_rec_leaf(recs, site, names[0]))
                n_sites_loaded += 1
            slot[kind] = out
        new_layers.append(slot)
    if missing:
        raise DatapathMismatchError(
            f"artifact does not cover {len(missing)} site(s) the model "
            f"enumerates: {missing} — refusing the silent float fallback "
            f"(strict={strict}; pass strict=False only for deliberately "
            f"partial uniform artifacts)"
        )
    unknown = sorted(
        k for k in flat if k.endswith("/q") and k not in consumed)
    if unknown:
        raise DatapathMismatchError(
            f"artifact carries quantized sites this model does not "
            f"enumerate: {unknown} — the artifact and the serving config "
            f"disagree about the model's site set"
        )
    if n_sites_loaded == 0:
        raise DatapathMismatchError(
            "no quantized site in the artifact matched this model config — "
            "refusing to silently serve the float weights (wrong --arch, "
            "or an empty/foreign artifact directory?)"
        )
    return {
        "embedding": params["embedding"],
        "layers": tuple(new_layers),
        "final_norm": params["final_norm"],
    }


def plan_expected_specs(cfg: ModelConfig, plan, base: DatapathSpec) -> dict:
    """Total ``site-key -> DatapathSpec`` map for
    :func:`repro.quant.spec.validate_datapath`: every *packed* site the
    model enumerates, valued by the mixed-precision plan's override when
    present, else the uniform ``base``. Sites that cannot ride the int4
    container (w_bits > 4 — e.g. plan-promoted w8 sites — or an odd
    reduction depth) serve dequantized float leaves, not packed ones, and
    are excluded, mirroring ``_site_rec_leaf``. A plan key naming a site
    the model does not enumerate raises here, before anything serves."""
    expected: dict[str, DatapathSpec] = {}
    known: set[str] = set()
    plan = plan if plan is not None else {}
    for s, pattern_spec in enumerate(cfg.pattern):
        for kind, fam in (("mixer", pattern_spec.mixer),
                          ("ffn", pattern_spec.ffn)):
            if fam == "none":
                continue
            for site in get_adapter(kind, fam).enumerate_sites(cfg):
                key = f"slot{s}/{kind}.{site.name}"
                known.add(key)
                spec = plan.get(key)
                spec = base if spec is None else spec
                if spec.w_bits > 4 or site.k % 2 != 0 or (
                    spec.sparsity is not None and site.k % 4 != 0
                ):
                    continue
                expected[key] = spec
    unknown = sorted(set(plan) - known)
    if unknown:
        raise DatapathMismatchError(
            f"mixed-precision plan names sites this model does not "
            f"enumerate: {unknown}; model sites: {sorted(known)}")
    return expected


# ---------------------------------------------------------------------------
# Legacy-artifact upgrade shims (one-time, outside any trace)
# ---------------------------------------------------------------------------
def ensure_col_sums(params):
    """Fill the pack-time ``col_sums`` term into packed leaves that predate
    it (artifacts packed before the decode-kernel PR). One full unpack per
    leaf, once, outside any trace — the alternative (the in-graph fallback
    in ``packed_linear``) re-reads the whole weight on every decode step.
    Float trees pass through untouched."""

    def fix(node):
        if isinstance(node, dict):
            if "packed" in node and "col_sums" not in node:
                if "meta" in node:  # 2:4 sparse leaf: expand via the gather
                    q = unpack_sparse24(node["packed"], node["meta"])
                else:
                    q = unpack_int4(node["packed"])
                col = jnp.sum(
                    q.astype(jnp.int32), axis=-2, keepdims=True,
                )[..., :node["scale"].shape[-1]]  # drop the lane padding
                return {**node, "col_sums": col}
            return {k: fix(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(fix(v) for v in node)
        return node

    return fix(params)


def ensure_datapath_spec(params, default: DatapathSpec | None = None):
    """Attach a :class:`DatapathSpec` to packed leaves that predate the
    spec schema: decoded from the leaf's ``spec_arr`` array twin when one
    survived an array-only round trip, else ``default`` (the recipe
    datapath, stamped with the legacy schema version so the upgrade is
    visible). Runs once, outside any trace; complete leaves pass through
    with their spec object untouched."""

    def fix(node):
        if isinstance(node, dict):
            if is_packed_leaf(node) and "spec" not in node:
                spec = leaf_datapath(node)  # decodes spec_arr when present
                if spec is not None:
                    # the array twin is authoritative (it may carry
                    # per-repeat act numerics); only the static node is
                    # rebuilt, in its numerics-free leaf form so the
                    # treedef matches a natively packed leaf
                    return {**node, "spec": spec.leaf_spec()}
                from dataclasses import replace

                spec = replace(
                    (default or DatapathSpec()).leaf_spec(),
                    version=1 if "col_sums" in node else 0,
                )
                lead = node["packed"].shape[:-2]
                return {**node, "spec": spec,
                        "spec_arr": _spec_arr_leaf(spec, lead)}
            return {k: fix(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(fix(v) for v in node)
        return node

    return fix(params)


def upgrade_packed_params(params, default: DatapathSpec | None = None):
    """The full legacy-artifact upgrade: ``ensure_datapath_spec`` +
    ``ensure_col_sums``. The spec shim runs first so the stamped legacy
    version reflects the schema the leaf actually arrived with (a
    pre-col_sums leaf is v0, not "v1 because the other shim already ran").
    Idempotent on complete artifacts (leaf arrays and spec nodes pass
    through by identity)."""
    return ensure_col_sums(ensure_datapath_spec(params, default))


# ---------------------------------------------------------------------------
# Byte accounting
# ---------------------------------------------------------------------------
def packed_weight_bytes(cfg: ModelConfig, *, scale_bytes_per: int = 2,
                        static_act: bool = False,
                        with_bias: bool = False,
                        sparsity: str | None = None) -> dict:
    """Analytic per-step artifact traffic for the roofline correction:
    bf16 baseline vs the full packed artifact (codes + per-channel scale +
    ``col_sums`` zero-point term + spec twin + optional static-act and
    bias leaves). Site-enumeration-driven, so MoE/SSM/xLSTM stacks are
    counted too. Defaults describe the RTN ``pack_decode_params`` tree
    (bf16 scales, dynamic act, no bias); calibrated trees
    (:func:`serving_params_from_quantized`) use f32 scales, static act and
    biases on the output projections.

    ``sparsity="2:4"`` counts the compressed layout for every eligible
    site (K % 4 == 0): K/4 code bytes (the 2 kept int4 codes per group
    packed into one byte) plus K/4 metadata bytes (2-bit index pairs).
    At int4 the total weight stream matches dense (codes halve, metadata
    takes the other half) — the compressed layout's win is the halved
    *effective accumulation depth* (docs/datapath.md), not bytes.
    Ineligible sites are counted dense. Dense codes are counted at their
    served size, zero-padded to whole 128-lane K and N blocks."""
    elems = code = scale = col = spec_b = act = bias = meta_b = 0
    for slot in packable_sites(cfg):
        for kind in ("mixer", "ffn"):
            for s in slot[kind]:
                st = s.stacked or 1
                elems += s.k * s.c * st
                if sparsity is not None and s.k % 4 == 0:
                    code += s.k * s.c * st // 4  # 2 kept codes per group
                    meta_b += s.k * s.c * st // 4  # int8 index pair per group
                else:
                    # int8 byte holds 2 codes; K and N padded to whole lanes
                    code += (_round_up(s.k, LANE) * _round_up(s.c, LANE)
                             * st // 2)
                scale += s.c * st * scale_bytes_per
                col += s.c * st * 4  # int32
                spec_b += st * _SPEC_ARR_LEN * 4  # f32 spec_arr twin
                if static_act:
                    act += st * (4 + 4)  # f32 act_scale + act_zp
                if with_bias and s.use_bias:
                    bias += s.c * st * 4
    r = cfg.repeats
    total = (code + meta_b + scale + col + spec_b + act + bias) * r
    return {
        "weight_elems": elems * r,
        "bf16_bytes": 2 * elems * r,
        "packed_code_bytes": code * r,
        "meta_bytes": meta_b * r,
        "scale_bytes": scale * r,
        "col_sums_bytes": col * r,
        "spec_bytes": spec_b * r,
        "act_bytes": act * r,
        "bias_bytes": bias * r,
        "packed_bytes": total,
    }
