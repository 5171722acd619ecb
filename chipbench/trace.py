"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time, time per device operation, and the host
activity during the device's idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. The device plane
is ``/device:TPU:0``; its ``XLA Modules`` line holds one event per program
run (``jit__chunk(<hash>)``), its ``XLA Ops`` line one per operation run,
named by the HLO text of the op (``%w4a8_matmul.14 = bf16[32,8192]...
custom-call(...)``). A loop op's event spans the ops of its body, which
have events of their own: an op's self time is its duration less that of
the ops inside it. Host threads are the ``/host:CPU`` plane's lines.
"""

from __future__ import annotations

import glob
import json
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

#: the served kernels' custom calls, by the op names the trace gives them
KERNELS = {"w4a8": ("w4a8_matmul", "w4a8_decode_matmul"),
           "paged_attn": ("paged_decode_attention",)}
#: device programs (``XLA Modules`` events) by the engine's jit names
PROGRAMS = {"chunk": "jit__chunk", "admit": "jit__admit",
            "prefill_chunk": "jit__prefill_chunk"}


def op_name(name: str) -> str:
    """``%w4a8_matmul.14 = bf16[...] custom-call(...)`` -> ``w4a8_matmul.14``"""
    return name.split(" = ", 1)[0].lstrip("%")


def is_kernel(kind: str, name: str) -> bool:
    base = op_name(name).rsplit(".", 1)[0]
    return base in KERNELS[kind]


def is_program(key: str, name: str) -> bool:
    base = name.split("(")[0]
    return base == PROGRAMS[key] or base.startswith(PROGRAMS[key] + ".")


@dataclass
class Event:
    name: str
    start: float  # seconds from the trace's origin
    dur: float
    self_dur: float = 0.0  # dur less the events nested inside it


@dataclass
class Reduced:
    ops: list = field(default_factory=list)  # device operations
    modules: list = field(default_factory=list)  # device programs
    host: list = field(default_factory=list)  # host events, all threads
    window_s: float = 0.0
    busy_s: float = 0.0
    gaps: list = field(default_factory=list)  # (start, dur) device idle

    def program_seconds(self, key: str) -> float:
        """Device time of the runs of one of the engine's programs."""
        return sum(m.dur for m in self.modules if is_program(key, m.name))

    def kernel_seconds(self, kind: str, inside: str) -> float:
        """Device time of a kernel's events within runs of a program."""
        return sum(e.dur for e in self.inside(
            [e for e in self.ops if is_kernel(kind, e.name)], inside))

    def inside(self, events, key: str) -> list:
        """The events that lie within a run of one of the engine's
        programs."""
        runs = sorted((m.start, m.start + m.dur) for m in self.modules
                      if is_program(key, m.name))
        out, i = [], 0
        for e in sorted(events, key=lambda e: e.start):
            while i < len(runs) and runs[i][1] < e.start:
                i += 1
            if i < len(runs) and runs[i][0] <= e.start <= runs[i][1]:
                out.append(e)
        return out

    def breakdown(self, n: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps named by the host event that covered most of each."""
        by_name: dict[str, float] = {}
        for e in self.ops:
            k = op_name(e.name)
            by_name[k] = by_name.get(k, 0.0) + e.self_dur
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[self.host_during(s, d), d] for s, d in gaps]}

    def host_during(self, start: float, dur: float) -> str:
        """The innermost-named host event covering most of an interval."""
        best, best_cover = "idle host", 0.0
        end = start + dur
        for e in self.host:
            cover = min(end, e.start + e.dur) - max(start, e.start)
            # prefer the shorter (more specific) event at equal cover
            if cover > best_cover + 1e-9 or (
                    abs(cover - best_cover) <= 1e-9 and cover > 0
                    and e.dur < self._dur(best)):
                best, best_cover = e.name, cover
        return best

    def _dur(self, name: str) -> float:
        return min((e.dur for e in self.host if e.name == name),
                   default=float("inf"))


def union_seconds(events) -> float:
    total, end = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e.start):
        s, t = e.start, e.start + e.dur
        if t <= end:
            continue
        total += t - max(s, end)
        end = t
    return total


def nest(events) -> None:
    """Set each event's self time: its duration less that of the events
    that start and end inside it (one line's events nest or are apart)."""
    stack: list = []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        e.self_dur = e.dur
        while stack and stack[-1].start + stack[-1].dur <= e.start:
            stack.pop()
        if stack:
            stack[-1].self_dur -= e.dur
        stack.append(e)


def idle_gaps(events, t0: float, t1: float) -> list:
    out, end = [], t0
    for e in sorted(events, key=lambda e: e.start):
        if e.start > end:
            out.append((end, e.start - end))
        end = max(end, e.start + e.dur)
    if t1 > end:
        out.append((end, t1 - end))
    return out


def _events(line) -> list:
    return [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    red = Reduced()
    planes = {p.name: p for p in pd.planes}
    dev = planes.get(DEVICE_PLANE)
    if dev is None:
        raise ValueError(f"no {DEVICE_PLANE} plane in {path}; planes: "
                         f"{sorted(planes)}")
    for line in dev.lines:
        if line.name == OPS_LINE:
            red.ops = _events(line)
        elif line.name == MODULES_LINE:
            red.modules = _events(line)
    if HOST_PLANE in planes:
        for line in planes[HOST_PLANE].lines:
            red.host += [e for e in _events(line) if e.dur > 0]
    stamps = [e for e in red.ops + red.modules + red.host]
    t0 = min(e.start for e in stamps)
    t1 = max(e.start + e.dur for e in stamps)
    red.window_s = t1 - t0
    nest(red.ops)
    red.busy_s = union_seconds(red.ops)
    red.gaps = idle_gaps(red.ops, t0, t1)
    return red


def find(trace_dir) -> str:
    hits = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                / "*" / "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def inventory(path: str, n: int = 25) -> dict:
    """Every plane and line of a trace, with event counts and the names
    that took most time: for reading a trace by hand."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        lines = {}
        for line in plane.lines:
            tot: dict[str, list] = {}
            for e in line.events:
                t = tot.setdefault(e.name, [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns * 1e-9
            lines[line.name] = sorted(
                ([k, c, s] for k, (c, s) in tot.items()),
                key=lambda r: -r[2])[:n]
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import sys

    print(json.dumps(inventory(find(sys.argv[1])), indent=1))
