"""End-to-end AXE PTQ pipeline for decoder LMs (paper §4 recipe):

  load float params -> [SmoothQuant equalization] -> layer-by-layer
  calibration with *lockstep analog/quantized propagation* (GPFQ's
  "first l-1 layers quantized" setup, Eq. 9) -> AXE-GPFQ / AXE-OPTQ per
  linear site -> bias correction -> overflow certification -> quantized
  model.

The pipeline is family-agnostic: every block component (mixer or ffn of a
:class:`~repro.models.config.LayerSpec`) is handled by a registered
:class:`~repro.quant.families.base.BlockAdapter` that enumerates its
quantizable (K, C) linear sites and expresses its forward over *paired*
(analog, quantized) activation streams with each site routed through a tap.
Dense attn+mlp, MoE, Mamba and mLSTM/sLSTM adapters ship by default
(hybrid patterns like Jamba's compose for free); see
:mod:`repro.quant.families` to register more.

Embedding and LM head stay high-precision per the paper (§C.1). The
quantized forward has two execution paths:

  * simulation (fake-quant weights + activations, CPU/test path) — exactly
    the integer semantics, carried in fp32;
  * kernel (packed int4 + uint8 codes through repro.kernels.w4a8_mm) — the
    TPU path, interpret-mode on CPU (see repro.quant.serve_packed).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Iterator

import jax
import jax.numpy as jnp

from repro.core import (
    LayerStats,
    PTQConfig,
    QuantizedLinear,
    quantize_linear,
    smoothquant_scales,
    sweep_config,
)
from repro.core.quantizers import fake_quantize_act
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import embed, lm_logits, norm

from .families import SiteSpec, TapContext, check_supported, get_adapter
from .spec import DatapathMismatchError


@dataclass
class QuantizedComponent:
    """One quantized block component (mixer or ffn).

    ``params`` keeps the component's high-precision leaves (norms excluded —
    they live on the block); leaves consumed by quantized sites are replaced
    with ``None`` (adapters only reach weights through taps, so the float
    originals can be dropped). ``linears`` maps site name ->
    :class:`~repro.core.QuantizedLinear`, ``specs`` site name -> its spec.
    """

    adapter: str
    kind: str
    params: dict
    linears: dict[str, QuantizedLinear]
    specs: dict[str, SiteSpec]


@dataclass
class QuantizedBlock:
    """One decoder layer: generic site-name -> QuantizedLinear mappings plus
    the float norms, for any registered family."""

    spec: LayerSpec
    norm1: dict | None = None
    norm2: dict | None = None
    mixer: QuantizedComponent | None = None
    ffn: QuantizedComponent | None = None

    def quantized_linears(self) -> Iterator[tuple[str, QuantizedLinear]]:
        """Yield ("mixer.wq"-style qualified name, QuantizedLinear)."""
        for comp_name in ("mixer", "ffn"):
            comp = getattr(self, comp_name)
            if comp is not None:
                for name, ql in comp.linears.items():
                    yield f"{comp_name}.{name}", ql

    # -- dense-family compatibility accessors --------------------------------
    def _site(self, comp: QuantizedComponent | None, *names: str):
        if comp is None:
            return None
        for n in names:
            if n in comp.linears:
                return comp.linears[n]
        return None

    @property
    def wq(self):
        return self._site(self.mixer, "wq")

    @property
    def wk(self):
        return self._site(self.mixer, "wk")

    @property
    def wv(self):
        return self._site(self.mixer, "wv")

    @property
    def wo(self):
        return self._site(self.mixer, "wo")

    @property
    def wg(self):
        # gelu models historically stored wi in the wg slot
        return self._site(self.ffn, "wg", "wi")

    @property
    def wu(self):
        return self._site(self.ffn, "wu")

    @property
    def wd(self):
        return self._site(self.ffn, "wd")


@dataclass
class QuantizedModel:
    cfg: ModelConfig
    ptq: PTQConfig
    embedding: dict
    final_norm: dict
    blocks: list[QuantizedBlock] = field(default_factory=list)

    def quantized_linears(self) -> Iterator[tuple[str, QuantizedLinear]]:
        """Yield ("layer3/ffn.wd", QuantizedLinear) over the whole model."""
        for i, b in enumerate(self.blocks):
            for name, ql in b.quantized_linears():
                yield f"layer{i}/{name}", ql

    def datapath_specs(self) -> dict:
        """{"layer3/ffn.wd": DatapathSpec} — the per-site serving datapaths
        this model was certified for (static act quantizers included).
        This is what the packed artifact embeds; see repro.quant.spec."""
        return {name: ql.spec for name, ql in self.quantized_linears()}

    @property
    def certified(self) -> bool:
        for _, ql in self.quantized_linears():
            if ql.cert is not None and not bool(ql.cert):
                return False
        return True

    def cert_summary(self) -> dict:
        """Aggregate certificate report.

        ``ok`` is explicit no-vacuous-truth semantics: a model with *no*
        certificates (e.g. ``constrain=False``) reports ``ok: False`` and
        ``min_headroom_bits: None`` — absence of a certificate is not a
        guarantee. ``min_headroom_site`` names the arg-min (binding) site,
        so search/debug output can say *where* the budget binds, not just
        by how much.
        """
        worst = None
        worst_site = None
        n = 0
        for name, ql in self.quantized_linears():
            if ql.cert is not None:
                h = ql.cert.headroom_bits
                if worst is None or h < worst:
                    worst, worst_site = h, name
                n += 1
        return {
            "n_certified": n,
            "min_headroom_bits": worst,
            "min_headroom_site": worst_site,
            "ok": n > 0 and self.certified,
        }


def _layer_params(params, cfg: ModelConfig, layer: int):
    slot = layer % cfg.period
    rep = layer // cfg.period
    return jax.tree.map(lambda x: x[rep], params["layers"][slot])


def _flat(x):
    return x.reshape(-1, x.shape[-1])


def _weight_at(p: dict, path: tuple[str, ...]):
    for key in path:
        p = p[key]
    return p


def _strip_quantized(p: dict, specs: dict[str, SiteSpec]) -> dict:
    """Replace quantized weight leaves with None (keys kept so adapters'
    float-leaf access patterns are unchanged)."""
    out = dict(p)
    for spec in specs.values():
        d = out
        for key in spec.path[:-1]:
            d[key] = dict(d[key])
            d = d[key]
        d[spec.path[-1]] = None
    return out


def _apply_quantized(ql: QuantizedLinear, x: jax.Array, use_bias: bool) -> jax.Array:
    """Simulated-integer site evaluation: fake-quant activations, real
    matmul against dequantized weights, optional corrected bias."""
    xq = fake_quantize_act(x, ql.act)
    y = xq @ ql.w_q
    if use_bias and ql.bias is not None:
        y = y + ql.bias
    return y


def _site_ptq(ptq: PTQConfig, site: SiteSpec, override) -> PTQConfig:
    """Per-site PTQConfig: mixed-precision plan entry wins, then the site's
    static ``SiteSpec.datapath`` override, then the model-wide config.

    ``override`` is a :class:`~repro.quant.spec.DatapathSpec` (or None).
    ``constrain`` follows the spec: a >= 32-bit inner register means the
    site runs the unconstrained solver (matching ``to_datapath_spec``'s
    inverse mapping), so plan -> calibrate -> ``datapath_specs()`` round-
    trips on the spec ``key()``.
    """
    dp = override if override is not None else site.datapath
    if dp is None:
        if ptq.sparsity is not None and site.k % 4 != 0:
            # 2:4 groups need K % 4 == 0: this site stays dense under a
            # model-wide sparse recipe (mirrors serve_packed eligibility)
            return sweep_config(ptq, sparsity=None)
        return ptq
    constrained = dp.p_inner is not None and dp.p_inner < 32
    return sweep_config(
        ptq,
        w_bits=dp.w_bits,
        act_bits=dp.act_bits,
        act_signed=dp.act_signed,
        p_bits=dp.p_inner if constrained else ptq.p_bits,
        tile=dp.tile if constrained else ptq.tile,
        constrain=constrained,
        sparsity=dp.sparsity if site.k % 4 == 0 else None,
    )


def _calibrate_component(
    adapter, p, nrm, x_a, x_q, cfg, ptq, positions, equalize,
    plan=None, site_prefix="",
):
    """Norm -> optional SmoothQuant fold -> tapped dual-stream forward.

    ``plan``: optional {"slot0/mixer.wq": DatapathSpec} mixed-precision
    overrides; ``site_prefix`` ("slot0/mixer.") qualifies this component's
    site names against it.

    Returns ((y_a, y_q) component outputs, QuantizedComponent, updated norm).
    """
    h_a = norm(nrm, x_a, cfg.norm)
    h_q = norm(nrm, x_q, cfg.norm)
    if equalize:
        w_absmax = adapter.input_weight_absmax(p, cfg)
        if w_absmax is not None:
            absmax = jnp.max(jnp.abs(_flat(h_q)), axis=0)
            s_eq = smoothquant_scales(absmax, w_absmax)
            nrm["w"] = nrm["w"] / s_eq
            if "b" in nrm:
                nrm["b"] = nrm["b"] / s_eq
            h_a = norm(nrm, x_a, cfg.norm)
            h_q = norm(nrm, x_q, cfg.norm)
            p = adapter.scale_input_weights(p, s_eq, cfg)

    specs = {s.name: s for s in adapter.enumerate_sites(cfg)}
    linears: dict[str, QuantizedLinear] = {}
    # LayerStats shared across sites fed by the same activation pair (e.g.
    # wq/wk/wv): keyed by identity so the O(K^2) accumulation and the
    # eigendecomposition inside the solver run once per distinct input.
    stats_cache: list[tuple[jax.Array, jax.Array, LayerStats]] = []

    def tap(name, xp, stats_from=None):
        spec = specs[name]
        sa, sq = stats_from if stats_from is not None else xp
        stats = None
        for ca, cq, cs in stats_cache:
            if ca is sa and cq is sq and cs.k == spec.k:
                stats = cs
                break
        if stats is None:
            stats = LayerStats(k=spec.k)
            stats.update(_flat(sa), _flat(sq))
            stats_cache.append((sa, sq, stats))
        # the solvers run in f32 whatever the model's param dtype
        w = _weight_at(p, spec.path).astype(jnp.float32)
        override = plan.get(site_prefix + name) if plan else None
        ql = quantize_linear(w, stats, _site_ptq(ptq, spec, override))
        ql.aux["observer"] = stats.observer
        linears[name] = ql
        x_a_in, x_q_in = xp
        return (x_a_in @ w, _apply_quantized(ql, x_q_in, spec.use_bias))

    ctx = TapContext(cfg=cfg, positions=positions)
    y_a, y_q = adapter.forward_with_taps(p, (h_a, h_q), ctx, tap)
    comp = QuantizedComponent(
        adapter=adapter.name,
        kind=adapter.kind,
        params=_strip_quantized(p, specs),
        linears=linears,
        specs=specs,
    )
    return (y_a, y_q), comp, nrm


def calibrate_and_quantize(
    params,
    cfg: ModelConfig,
    batches: list[dict],
    ptq: PTQConfig,
    equalize: bool = True,
    plan=None,
) -> QuantizedModel:
    """Run the full PTQ pipeline. ``batches``: list of {"tokens": (B, S)}.

    ``plan``: optional slot-granular mixed-precision overrides,
    {"slot{s}/{mixer|ffn}.{site}": DatapathSpec} (slot = layer % period —
    repeats of a slot share one packed leaf, so they must share one
    datapath; see :mod:`repro.quant.observe`). Keys naming no quantized
    site raise :class:`~repro.quant.spec.DatapathMismatchError` — a typo'd
    plan must not silently calibrate uniform.
    """
    check_supported(cfg)
    tokens = jnp.concatenate([b["tokens"] for b in batches], axis=0)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    x_a = embed(params["embedding"], tokens, cfg)  # analog activations
    x_q = x_a  # quantized-network activations (lockstep)
    qm = QuantizedModel(
        cfg=cfg, ptq=ptq, embedding=params["embedding"],
        final_norm=params["final_norm"],
    )

    for layer in range(cfg.n_layers):
        p = _layer_params(params, cfg, layer)
        spec = cfg.pattern[layer % cfg.period]
        slot = layer % cfg.period
        block = QuantizedBlock(spec=spec)
        if spec.mixer != "none":
            adapter = get_adapter("mixer", spec.mixer)
            (y_a, y_q), comp, nrm = _calibrate_component(
                adapter, dict(p["mixer"]), dict(p["norm1"]),
                x_a, x_q, cfg, ptq, positions, equalize,
                plan=plan, site_prefix=f"slot{slot}/mixer.",
            )
            x_a = x_a + y_a
            x_q = x_q + y_q
            block.norm1 = nrm
            block.mixer = comp
        if spec.ffn != "none":
            adapter = get_adapter("ffn", spec.ffn)
            (y_a, y_q), comp, nrm = _calibrate_component(
                adapter, dict(p["ffn"]), dict(p["norm2"]),
                x_a, x_q, cfg, ptq, positions, equalize,
                plan=plan, site_prefix=f"slot{slot}/ffn.",
            )
            x_a = x_a + y_a
            x_q = x_q + y_q
            block.norm2 = nrm
            block.ffn = comp
        qm.blocks.append(block)
    if plan:
        known = {
            f"slot{i % cfg.period}/{name}"
            for i, b in enumerate(qm.blocks)
            for name, _ in b.quantized_linears()
        }
        unknown = sorted(k for k in plan if k not in known)
        if unknown:
            raise DatapathMismatchError(
                f"mixed-precision plan names unknown sites {unknown}; "
                f"model enumerates {sorted(known)}"
            )
    return qm


def _quantized_component_forward(comp: QuantizedComponent, h, cfg, positions):
    """Single-stream simulated-integer component forward: the same adapter
    code path as calibration, with taps resolving to stored artifacts and
    the paired streams collapsed (see families.base.both)."""
    adapter = get_adapter(comp.kind, comp.adapter)

    def tap(name, xp, stats_from=None):
        y = _apply_quantized(comp.linears[name], xp[1], comp.specs[name].use_bias)
        return (y, y)

    ctx = TapContext(cfg=cfg, positions=positions)
    return adapter.forward_with_taps(comp.params, (h, h), ctx, tap)[1]



def quantized_forward(qm: QuantizedModel, batch: dict) -> jax.Array:
    """Simulated-integer forward of the quantized model -> logits."""
    cfg = qm.cfg
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = embed(qm.embedding, tokens, cfg)
    for b in qm.blocks:
        if b.mixer is not None:
            h = norm(b.norm1, x, cfg.norm)
            x = x + _quantized_component_forward(b.mixer, h, cfg, positions)
        if b.ffn is not None:
            h = norm(b.norm2, x, cfg.norm)
            x = x + _quantized_component_forward(b.ffn, h, cfg, positions)
    x = norm(qm.final_norm, x, cfg.norm)
    return lm_logits(qm.embedding, x, cfg)


def quantized_ppl(qm: QuantizedModel, batches: list[dict]) -> float:
    """Perplexity of the quantized model over eval batches."""
    tot, n = 0.0, 0
    for b in batches:
        logits = quantized_forward(qm, b).astype(jnp.float32)
        pred = logits[:, :-1]
        labels = b["tokens"][:, 1:]
        logz = jax.nn.logsumexp(pred, axis=-1)
        gold = jnp.take_along_axis(pred, labels[..., None], axis=-1)[..., 0]
        tot += float(jnp.sum(logz - gold))
        n += labels.size
    return math.exp(tot / n)


def float_ppl(params, cfg: ModelConfig, batches: list[dict]) -> float:
    from repro.models.transformer import loss_fn

    tot, n = 0.0, 0
    for b in batches:
        _, m = loss_fn(params, b, cfg)
        tot += float(m["ce"]) * (b["tokens"].shape[0] * (b["tokens"].shape[1] - 1))
        n += b["tokens"].shape[0] * (b["tokens"].shape[1] - 1)
    return math.exp(tot / n)
