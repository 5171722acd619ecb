"""The layer scan's own work inside the decode chunks, in percent of the
traced chunks' device time: what the step spends moving the stacked
weights and KV pools through the scan rather than computing layers.

An op counts when, in a traced ``jit__chunk`` run, either
- its name stack (``scopes.py``) lies under the program's ``layer_scan``
  scope but not under its ``layer`` scope: the scan's per-layer slices of
  the stacked weights and pools and its writes of the stacked outputs; or
- it carries no scope of its own (no name stack, or one that names a loop
  op itself, ``.../while:``) and runs inside the step loop: the carry
  copies and loop bookkeeping the compiler inserts, such as the whole-pool
  copies each step makes of the scan's stacked outputs.

An op that counts adds the time its event covers; a loop op that counts
(the scan's own loop) adds the time inside it that no other op covers.
Times are unions of intervals, not the trace's nesting: an async copy's
event can overlap the start of a loop without containing it. None where
the program names no ``layer_scan`` scope."""

import bisect

from chipbench import scopes
from chipbench import trace as tr


def _is_loop(e) -> bool:
    return tr.op_name(e.name).split(".")[0] == "while"


def _merge(intervals) -> list:
    """Disjoint, sorted intervals covering the same time."""
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def _covered(merged, starts, s: float, t: float) -> float:
    """How much of [s, t] the merged intervals (``starts`` their starts)
    cover."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    got = 0.0
    while i < len(merged) and merged[i][0] < t:
        got += max(0.0, min(t, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return got


def _in_loop(events):
    """A test of whether a time lies inside one of the loop ops among
    ``events`` (strictly after its start, so a loop is not inside
    itself)."""
    spans = _merge((e.start, e.start + e.dur) for e in events if _is_loop(e))
    starts = [s for s, _ in spans]

    def test(t):
        i = bisect.bisect_left(starts, t) - 1
        return i >= 0 and spans[i][0] < t <= spans[i][1]
    return test


def machinery(stack, in_loop: bool) -> bool:
    if stack is not None and scopes.under(stack, "layer_scan"):
        return not scopes.under(stack, "layer")
    return in_loop and (stack is None or stack.rsplit("/", 1)[-1] == "while:")


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    stacks = scopes.stacks_of(ctx)
    if not any(scopes.under(s, "layer_scan") for s in stacks.values()):
        return None
    total = t.program_seconds("chunk")
    ops = t.inside(t.ops, "chunk")
    if total <= 0 or not ops:
        return None
    in_loop = _in_loop(ops)
    counted = [e for e in ops
               if machinery(stacks.get(e.name), in_loop(e.start))]
    leaves = _merge((e.start, e.start + e.dur) for e in ops
                    if not _is_loop(e))
    starts = [a for a, _ in leaves]
    spent = sum(b - a for a, b in _merge(
        (e.start, e.start + e.dur) for e in counted if not _is_loop(e)))
    for a, b in _merge((e.start, e.start + e.dur) for e in counted
                       if _is_loop(e)):
        spent += (b - a) - _covered(leaves, starts, a, b)
    return 100.0 * spent / total
