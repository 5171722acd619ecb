"""Serving-side saturation counters: the off-hot-path observer.

:class:`SaturationCounters` accumulates, host-side, what the decode path
reports through ``jax.debug.callback`` when an observer is attached to
``repro.models.layers`` (see ``attach_observer`` / ``site_scope`` there):

* **static-quantizer clip counts** — how many activation values the
  calibrated static quantizer clipped (the realized version of the
  ``min_seen``/``max_seen`` vs ``lo``/``hi`` gap the calibration observer
  predicted);
* **activation-code extrema** — the sub-alphabet actually exercised, per
  site.

Everything heavier is computed *at report time* on the host, never in the
serving graph:

* per-site **accumulator watermarks**: the observed code extrema joined
  with the packed leaf's integer weights (unpacked once, host-side) give
  the exact worst partial sum *restricted to the observed code range* —
  an empirical watermark bounded above by the analytic certificate;
* per-KV-head **attention watermarks**: page-pool code extrema against the
  :class:`~repro.quant.spec.AttnDatapathSpec` register bounds.

The counters are pure Python state: when no observer is attached (the
default) the serving jaxpr contains no callback, no counter, no extra op —
asserted structurally by ``PagedEngine.assert_observation_transparent``.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field

import numpy as np


@dataclass
class _SiteCounter:
    n_calls: int = 0
    clip_count: int = 0
    clip_total: int = 0
    code_min: float = math.inf
    code_max: float = -math.inf


@dataclass
class SaturationCounters:
    """Host-side accumulation of per-site serving observations."""

    sites: dict[str, _SiteCounter] = field(default_factory=dict)

    def __post_init__(self):
        self._lock = threading.Lock()

    # -- recording (jax.debug.callback target) ------------------------------
    def record(self, label: str, n_total: int, n_clip, code_min, code_max) -> None:
        """Fold one decode step's observation for ``label``. ``label`` and
        ``n_total`` arrive bound via ``functools.partial`` (static);
        the rest are device scalars delivered by ``jax.debug.callback``."""
        with self._lock:
            c = self.sites.setdefault(label, _SiteCounter())
            c.n_calls += 1
            c.clip_count += int(n_clip)
            c.clip_total += int(n_total)
            c.code_min = min(c.code_min, float(code_min))
            c.code_max = max(c.code_max, float(code_max))

    def reset(self) -> None:
        with self._lock:
            self.sites.clear()

    # -- reporting -----------------------------------------------------------
    def report(self, params=None, pools=None, attn_spec=None) -> dict:
        """ServeMetrics-style summary dict.

        ``params``: optional serving params tree — enables per-site
        accumulator watermarks (unpacks each observed site's integer codes
        host-side, once). ``pools`` + ``attn_spec``: optional paged-cache
        pool list and :class:`AttnDatapathSpec` — enables per-KV-head
        attention watermarks. All optional inputs only add sections; the
        counter core never touches device state.
        """
        out: dict = {"sites": {}}
        with self._lock:
            items = [(k, _SiteCounter(**vars(v))) for k, v in self.sites.items()]
        for label, c in sorted(items):
            sec = {
                "n_calls": c.n_calls,
                "clip_count": c.clip_count,
                "clip_total": c.clip_total,
                "clip_frac": c.clip_count / c.clip_total if c.clip_total else 0.0,
                "code_min": c.code_min if c.n_calls else None,
                "code_max": c.code_max if c.n_calls else None,
            }
            if params is not None and c.n_calls:
                leaf = _find_site_leaf(params, label)
                if leaf is not None:
                    sec.update(_leaf_watermark(leaf, c.code_min, c.code_max))
            out["sites"][label] = sec
        if pools is not None and attn_spec is not None:
            out["kv_heads"] = _kv_watermarks(pools, attn_spec)
        return out


# ---------------------------------------------------------------------------
# Report-time analysis (host only)
# ---------------------------------------------------------------------------
def _find_site_leaf(params, label: str):
    """Resolve "slot0/mixer.wq" against a serving params tree."""
    try:
        slot_part, site = label.split("/", 1)
        kind, name = site.split(".", 1)
        slot = int(slot_part.removeprefix("slot"))
        comp = params["layers"][slot][kind]
    except (KeyError, ValueError, IndexError, TypeError):
        return None
    return _find_named_packed(comp, name)


def _find_named_packed(node, name: str):
    if isinstance(node, dict):
        v = node.get(name)
        if isinstance(v, dict) and "packed" in v:
            return v
        for child in node.values():
            if isinstance(child, dict) and "packed" not in child:
                found = _find_named_packed(child, name)
                if found is not None:
                    return found
    return None


def _leaf_watermark(leaf, code_min: float, code_max: float) -> dict:
    """Exact worst-case accumulator use of a leaf's codes *restricted to
    the observed activation-code range* — the per-site watermark. Bounded
    above by the analytic certificate (which assumes the full alphabet)."""
    import jax

    from repro.core.alphabet import accumulator_range
    from repro.kernels.w4a8_mm import unpack_int4
    from repro.quant.spec import leaf_datapath

    spec = leaf_datapath(leaf)
    if spec is None:
        return {}
    # the padded columns past the scale's logical N hold zero codes
    n = leaf["scale"].shape[-1]
    w = np.asarray(jax.device_get(unpack_int4(leaf["packed"])), np.float64)
    w = w[..., :n]
    k = w.shape[-2]
    w = w.reshape(-1, k, w.shape[-1])  # fold repeat/expert stacking
    t = spec.tile if spec.tile else k
    pad = (-k) % t
    if pad:
        w = np.pad(w, [(0, 0), (0, pad), (0, 0)])
    n_tiles = (k + pad) // t
    # (R, C, n_tiles, T)
    q_ct = w.transpose(0, 2, 1).reshape(w.shape[0], w.shape[2], n_tiles, t)
    pos = np.clip(q_ct, 0, None).sum(-1)
    neg = np.clip(q_ct, None, 0).sum(-1)
    emp_hi = float((code_max * pos + code_min * neg).max())
    emp_lo = float((code_min * pos + code_max * neg).min())
    peak = max(emp_hi, -emp_lo, 1.0)
    _, hi_lim = accumulator_range(spec.p_inner)
    return {
        "watermark_hi": emp_hi,
        "watermark_lo": emp_lo,
        "watermark_bits": math.log2(peak) + 1.0,  # + sign bit
        "p_inner": spec.p_inner,
        "headroom_bits_observed": math.log2(hi_lim) - math.log2(peak),
    }


def _bits_needed(peak: float) -> float:
    return math.log2(max(peak, 1.0)) + 1.0


def _kv_watermarks(pools, attn_spec) -> dict:
    """Per-KV-head attention accumulator watermarks from pool codes."""
    import jax

    out: dict = {}
    for slot, pool in enumerate(pools):
        if not isinstance(pool, dict) or "k_scales" not in pool:
            continue
        k = np.asarray(jax.device_get(pool["k_pages"]), np.float64)
        v = np.asarray(jax.device_get(pool["v_pages"]), np.float64)
        # (..., nb, bs, nkv, hd) -> per-head max |code| (keep the nkv axis)
        k_max = np.abs(k).max(axis=(-1, -3, -4)).reshape(-1, k.shape[-2]).max(0)
        v_max = np.abs(v).max(axis=(-1, -3, -4)).reshape(-1, v.shape[-2]).max(0)
        heads = {}
        for h in range(k_max.shape[0]):
            qk_peak = attn_spec.head_dim * attn_spec.q_qmax * float(k_max[h])
            pv_peak = attn_spec.block_size * attn_spec.prob_qmax * float(v_max[h])
            heads[f"head{h}"] = {
                "k_code_max": float(k_max[h]),
                "v_code_max": float(v_max[h]),
                "qk_watermark_bits": _bits_needed(qk_peak),
                "pv_watermark_bits": _bits_needed(pv_peak),
                "p_qk": attn_spec.p_qk,
                "p_pv": attn_spec.p_pv,
            }
        out[f"slot{slot}"] = heads
    return out
