"""The JAX name stack of every device operation in a profiler trace.

Each event of a trace's device plane names its op by the op's HLO text
(``%copy.166 = s8[...] copy(...)``); the event's metadata carries a
``tf_op`` stat with the name stack JAX gave the op when it was traced
(``jit(_chunk)/while/body/layer_scan/while/body/layer/slot0/mixer/...``).
The program's ``jax.named_scope`` names therefore reach the trace here.
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
this module reads them from the ``.xplane.pb`` itself with a small
protobuf wire-format reader, standard library only.

The fields read (``xplane.proto``): ``XSpace.planes`` = 1;
``XPlane.name`` = 2, ``.event_metadata`` = 4, ``.stat_metadata`` = 5
(maps: key = 1, value = 2); ``XEventMetadata.name`` = 2, ``.stats`` = 5;
``XStatMetadata.name`` = 2; ``XStat.metadata_id`` = 1, ``.str_value`` = 5,
``.ref_value`` = 7 (a string held as the name of a stat metadata entry).

The harness reads both from a traced run's trace once, into the metric
readers' context (``Ctx.scopes``, ``Ctx.counters``): the name stacks, and
the engine counters that ride on the trace's ``serve/pass`` spans.
"""

from __future__ import annotations

from pathlib import Path

#: the device plane whose ops the per-layer metrics read
DEVICE_PLANE = "/device:TPU:0"
#: the name-stack stat of an op's event metadata
TF_OP = "tf_op"
#: the serve loop's pass span; its arguments carry the engine's counters
#: as they stood when the pass began (``repro.serving.metrics``)
PASS_SPAN = "serve/pass"
#: the counters a pass span carries, by the names the engine gives them
COUNTERS = ("passes", "decode_steps", "tokens_out", "prefill_tokens",
            "preemptions", "attn_pages_live", "attn_pages_grid", "pass_s",
            "readback_s")


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, start: int = 0, end: int | None = None):
    """The fields of one protobuf message in ``buf[start:end]``: yields
    (field number, value), the value an int for varint and fixed-width
    fields and a (start, end) slice for length-delimited ones."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} at {i}")
        yield num, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """The value slice of one protobuf map entry."""
    for num, val in fields(buf, *span):
        if num == 2:
            return val
    return None


def _plane(buf, span) -> tuple[str, list, dict]:
    """A plane's name, its event metadata slices and its stat metadata
    names by id."""
    name, events, stat_names = "", [], {}
    for num, val in fields(buf, *span):
        if num == 2:
            name = _text(buf, val)
        elif num == 4:
            v = _map_values(buf, val)
            if v is not None:
                events.append(v)
        elif num == 5:
            v = _map_values(buf, val)
            if v is None:
                continue
            sid, sname = None, ""
            for n, x in fields(buf, *v):
                if n == 1:
                    sid = x
                elif n == 2:
                    sname = _text(buf, x)
            if sid is not None:
                stat_names[sid] = sname
    return name, events, stat_names


def read(path, plane: str = DEVICE_PLANE, stat: str = TF_OP) -> dict:
    """{full event name: ``tf_op`` name stack} for the ops of one plane of
    a ``.xplane.pb`` (events without the stat are left out)."""
    buf = memoryview(Path(path).read_bytes())
    out: dict[str, str] = {}
    for num, val in fields(buf):
        if num != 1:
            continue
        name, events, stat_names = _plane(buf, val)
        if name != plane:
            continue
        want = {i for i, n in stat_names.items() if n == stat}
        for ev in events:
            ev_name, value = "", None
            for n, x in fields(buf, *ev):
                if n == 2:
                    ev_name = _text(buf, x)
                elif n == 5:
                    sid, sval = None, None
                    for m, y in fields(buf, *x):
                        if m == 1:
                            sid = y
                        elif m == 5:
                            sval = _text(buf, y)
                        elif m == 7:
                            sval = stat_names.get(y)
                    if sid in want and sval is not None:
                        value = sval
            if value is not None:
                out[ev_name] = value
    return out


def under(stack: str, scope: str) -> bool:
    """Whether a name stack lies inside a ``jax.named_scope(scope)``: the
    scope is one of its components (``layer`` is not ``layer_scan``)."""
    return scope in stack.split("/")[:-1]


def pass_counters(path) -> dict:
    """The engine counters over the traced passes: the difference between
    the counters carried by the last and the first ``serve/pass`` span of
    the trace's host plane (each span carries them as they stood when its
    pass began, so the difference covers the whole passes in between).
    Empty where the trace holds fewer than two pass spans that carry
    counters."""
    from jax.profiler import ProfileData

    snaps = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != PASS_SPAN:
                    continue
                stats = dict(e.stats)
                if all(k in stats for k in COUNTERS):
                    snaps.append((e.start_ns, stats))
    if len(snaps) < 2:
        return {}
    snaps.sort(key=lambda s: s[0])
    first, last = snaps[0][1], snaps[-1][1]
    return {k: float(last[k]) - float(first[k]) for k in COUNTERS}


def stacks_of(ctx) -> dict:
    """The name stacks a metric reader reads: the traced run's
    (``ctx.scopes``, which the harness fills). Empty for an untraced run
    or where none is found."""
    return ctx.scopes or {}


def counters_of(ctx) -> dict:
    """The engine counters over the traced passes (``ctx.counters``, which
    the harness fills). Empty for an untraced run or where none is
    found."""
    return ctx.counters or {}
