"""The benchmark's request recorder and the decode rate's arithmetic.

``PagedEngine.serve(metrics=...)`` calls ``submitted`` / ``tokens`` /
``preempted`` (the interface of ``repro.serving.metrics.ServeMetrics``)
with seconds since the serve loop began. Every token of a decode chunk
shares the chunk's host stamp.

The serve loop stamps each row of a chunk in turn, so one chunk's
stamps spread over some milliseconds. The harness therefore marks the
end of every pass of the loop (``mark``: the last stamp so far), and the
measured span runs between pass ends: from the pass at which the window
opens (``opened``, set once the loop is in its steady state) to the end
of the first pass that ends at or after the close. Every chunk is wholly
in the span or out of it, and every token and every second of the
window's work counts, including a stall in its last chunk.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Rec:
    t_due: float
    times: list = field(default_factory=list)  # one stamp per token
    n_preempt: int = 0


class Recorder:
    def __init__(self):
        self.recs: dict[int, Rec] = {}
        self.want: dict[int, int] = {}  # uid -> tokens the request asks for
        self.opened = 0.0  # the window's open, on the serve loop's clock
        self.last = 0.0  # the latest stamp
        self.marks: list[float] = []  # pass ends

    # the serve loop's recording interface
    def submitted(self, uid: int, priority: int, t: float) -> None:
        if uid not in self.recs:  # a preempted request keeps its due time
            self.recs[uid] = Rec(t_due=t)

    def tokens(self, uid: int, n: int, t: float) -> None:
        self.recs[uid].times.extend([t] * n)
        self.last = max(self.last, t)

    def preempted(self, uid: int) -> None:
        rec = self.recs[uid]
        rec.n_preempt += 1
        del rec.times[1:]  # re-emitted tokens; the first one stays

    # ------------------------------------------------------------------
    def mark(self) -> None:
        """A pass of the serve loop has ended: every stamp it made is in."""
        self.marks.append(self.last)

    def open(self) -> None:
        """The window opens at the end of the pass just marked."""
        self.opened = self.last

    def expect(self, uid: int, max_new: int) -> None:
        self.want[uid] = max_new

    def done(self, uid: int) -> bool:
        return uid in self.want and len(self.recs[uid].times) >= self.want[uid]

    def tokens_in(self, t0: float, t1: float) -> int:
        """Tokens stamped in (t0, t1]."""
        return sum(sum(1 for t in r.times if t0 < t <= t1)
                   for r in self.recs.values())

    def span_end(self, seconds: float) -> float | None:
        """The end of the first pass that ends at or after the window's
        close, if any yet."""
        close = self.opened + seconds
        return min((t for t in self.marks if t >= close), default=None)

    def decode_tok_s(self, seconds: float) -> float | None:
        """Tokens stamped from the open to the end of the first pass that
        ends at or after the close, over that time."""
        end = self.span_end(seconds)
        if end is None:
            return None
        return self.tokens_in(self.opened, end) / (end - self.opened)
