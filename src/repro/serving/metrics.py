"""Per-request serving latency accounting: TTFT and inter-token latency
percentiles, bucketed by priority class.

The serve loop stamps wall-clock times host-side: one ``submitted`` per
request (its arrival), one ``tokens`` per admit / decode chunk (every
token the chunk produced shares the chunk-end timestamp — intra-chunk
gaps therefore read as zero and the inter-token distribution's tail
measures exactly the stalls an operator feels: head-of-line prefills,
admission waits, preemption restarts). ``ttft`` is the gap from arrival
to the *first* token ever produced — a preempt-and-requeue restart
re-emits tokens but cannot move a request's TTFT.

``summary()`` emits microsecond-suffixed percentile keys
(``ttft_p50_us`` … ``itl_p99_us``), overall and per class under
``class_<p>`` — the shapes ``scripts/bench_compare.py`` classifies as
lower-is-better.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass, field

import numpy as np

# ---------------------------------------------------------------------------
# Host spans of the serve loops (``jax.profiler.TraceAnnotation``): they
# land on the profiler's clock beside the device trace, so every idle gap
# of a traced run can be put down to one phase of the loop. One span per
# phase of a pass, never one per slot or token.
# ---------------------------------------------------------------------------
#: one scheduler pass; carries ``index`` and the counters as they stood
#: when the pass began (:class:`ServeCounters`)
SPAN_PASS = "serve/pass"
#: one page-aligned chunk of a chunked prefill (``uid``)
SPAN_PREFILL = "serve/prefill"
#: one admission, single or batched (``uid`` / ``uids``)
SPAN_ADMIT = "serve/admit"
#: chunk planning: ``plan_chunk``, preemption and cache eviction
SPAN_PLAN = "serve/plan"
#: page growth and page release programs
SPAN_GROW = "serve/grow"
SPAN_RELEASE = "serve/release"
#: the decode chunk's dispatch
SPAN_DECODE = "serve/decode"
#: a blocking read of device results (the chunk's tokens, an admit's
#: first token)
SPAN_READBACK = "serve/readback"
#: the per-slot token bookkeeping after a chunk (``note`` / ``finish``)
SPAN_RECORD = "serve/record"
#: the client hook run at the end of each pass
SPAN_LATE = "serve/late"


@dataclass
class _Rec:
    priority: int
    t_submit: float
    times: list = field(default_factory=list)  # one wall-clock per token
    n_preempt: int = 0


class ServeMetrics:
    def __init__(self):
        self._recs: dict[int, _Rec] = {}

    # ------------------------------------------------------------------
    # Recording (called by the serve loops)
    # ------------------------------------------------------------------
    def submitted(self, uid: int, priority: int, t: float) -> None:
        if uid not in self._recs:  # resubmission after preemption keeps t0
            self._recs[uid] = _Rec(priority=priority, t_submit=t)

    def tokens(self, uid: int, n: int, t: float) -> None:
        self._recs[uid].times.extend([t] * n)

    def preempted(self, uid: int) -> None:
        rec = self._recs[uid]
        rec.n_preempt += 1
        # the produced tokens are discarded and will be re-emitted; keep
        # only the first timestamp so TTFT survives and the restart's
        # re-decode gap lands in the inter-token distribution honestly
        del rec.times[1:]

    # ------------------------------------------------------------------
    # Reporting
    # ------------------------------------------------------------------
    @staticmethod
    def _pcts(vals: list[float]) -> dict:
        if not vals:
            return {}
        a = np.asarray(vals, np.float64) * 1e6  # seconds -> us
        return {"p50_us": float(np.percentile(a, 50)),
                "p99_us": float(np.percentile(a, 99))}

    def _section(self, recs: list[_Rec]) -> dict:
        ttft = [r.times[0] - r.t_submit for r in recs if r.times]
        itl: list[float] = []
        for r in recs:
            itl.extend(float(b - a) for a, b in zip(r.times, r.times[1:]))
        out = {"n_requests": len(recs),
               "n_preemptions": sum(r.n_preempt for r in recs)}
        out.update({f"ttft_{k}": v for k, v in self._pcts(ttft).items()})
        out.update({f"itl_{k}": v for k, v in self._pcts(itl).items()})
        return out

    def summary(self) -> dict:
        recs = list(self._recs.values())
        out = self._section(recs)
        for p in sorted({r.priority for r in recs}):
            out[f"class_{p}"] = self._section(
                [r for r in recs if r.priority == p])
        return out


def pages_filled(lens, k: int, block_size: int) -> int:
    """KV pages the attention of ``k`` decode steps reads, summed over
    rows: a row whose KV holds ``n`` tokens before the chunk attends over
    ``n + j + 1`` of them at step ``j``, in ``ceil((n + j + 1) / bs)``
    pages. Closed form over the rows: ``F(n + k) - F(n)`` with
    ``F(m) = sum_{L=1..m} ceil(L / bs) = bs q (q + 1) / 2 + r (q + 1)``,
    ``m = q bs + r``."""
    n = np.asarray(lens, np.int64)

    def cum(m):
        q, r = np.divmod(m, block_size)
        return block_size * q * (q + 1) // 2 + r * (q + 1)

    return int(np.sum(cum(n + k) - cum(n)))


@dataclass
class ServeCounters:
    """What one :class:`~repro.serving.paged_engine.PagedEngine` has done
    over its lifetime, counted by its serve loops (always on; a few
    integer adds and two clock reads per pass).

    ``attn_pages_live`` / ``attn_pages_grid`` are per decode step (not per
    layer): the pages the decoding rows' live KV fills, against the pages
    the attention kernel's (rows, table width) grid walks; the kernel
    computes the former and skips the rest. ``pass_s`` is
    the host time of the loop's passes, ``readback_s`` the part of it
    spent blocked on device results; what remains is host work in which
    the device waits."""

    passes: int = 0
    decode_steps: int = 0
    tokens_out: int = 0
    prefill_tokens: int = 0
    preemptions: int = 0
    attn_pages_live: int = 0
    attn_pages_grid: int = 0
    pass_s: float = 0.0
    readback_s: float = 0.0

    def snapshot(self) -> dict:
        return asdict(self)

    @staticmethod
    def summary(c: dict) -> str:
        """One operator line from a snapshot, or from the difference of
        two."""
        host = (c["pass_s"] - c["readback_s"]) / c["pass_s"] \
            if c["pass_s"] > 0 else 0.0
        use = c["attn_pages_live"] / c["attn_pages_grid"] \
            if c["attn_pages_grid"] else 0.0
        return (f"passes={c['passes']} steps={c['decode_steps']} "
                f"tokens={c['tokens_out']} "
                f"prefill_tokens={c['prefill_tokens']} "
                f"preemptions={c['preemptions']} "
                f"host_loop_share={100 * host:.1f}% "
                f"attn_page_use={100 * use:.1f}%")
