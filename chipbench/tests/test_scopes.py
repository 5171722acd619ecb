"""Name stacks and engine counters read from a trace (``scopes.py``), and
the three readers that use them: on the traces recorded on a TPU v5e
(``fixtures/``), on synthetic contexts, and on a trace of the engine
recorded here on the CPU."""

import glob
import importlib.util
import json
import shutil
from pathlib import Path

import pytest

from chipbench import harness, metrics, scopes, trace
from chipbench.trace import Event, Reduced

FIXTURES = Path(__file__).resolve().parent / "fixtures"
CASES = sorted(p.name for p in FIXTURES.glob("*") if p.is_dir())


@pytest.mark.parametrize("case", CASES)
def test_reader_returns_the_recorded_stacks(case):
    """Every recorded trace names the paged-attention kernel's op by its
    HLO text, and its stack ends in the kernel's jit and pallas call.
    (Copies of a program's arguments carry the argument's name instead.)"""
    path = FIXTURES / case / "trace.xplane.pb"
    stacks = scopes.read(path)
    attn = [k for k in stacks
            if trace.op_name(k).startswith("paged_decode_attention.")]
    assert attn and all(stacks[k].endswith(
        "/jit(paged_decode_attention)/pallas_call:") for k in attn)
    ops = {e.name for e in trace.reduce(str(path)).ops}
    assert set(stacks) <= ops


def _xplane_pb2():
    """The generated ``xplane_pb2`` module, loaded on its own where an
    installed package ships it (importing the package would be slow)."""
    spec = importlib.util.find_spec("tensorflow")
    path = spec and Path(spec.origin).parent / "tsl" / "profiler" \
        / "protobuf" / "xplane_pb2.py"
    if not path or not path.is_file():
        return pytest.importorskip("tsl.profiler.protobuf.xplane_pb2")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("case", CASES)
def test_reader_agrees_with_xplane_pb2(case):
    pb2 = _xplane_pb2()
    path = FIXTURES / case / "trace.xplane.pb"
    space = pb2.XSpace()
    space.ParseFromString(path.read_bytes())
    (plane,) = [p for p in space.planes if p.name == scopes.DEVICE_PLANE]
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    want = {}
    for md in plane.event_metadata.values():
        for st in md.stats:
            if names.get(st.metadata_id) == scopes.TF_OP:
                want[md.name] = st.str_value or names[st.ref_value]
    assert want and scopes.read(path) == want


def test_under_is_a_whole_component():
    s = "jit(_chunk)/while/body/layer_scan/while/body/squeeze:"
    assert scopes.under(s, "layer_scan") and not scopes.under(s, "layer")
    s = "jit(_chunk)/while/body/layer_scan/while/body/closed_call/layer/add:"
    assert scopes.under(s, "layer") and scopes.under(s, "layer_scan")
    assert not scopes.under("jit(_chunk)/layer:", "layer")  # the op itself


def _ctx(red=None, stacks=None, counters=None):
    return metrics.Ctx(cell={"name": "t"}, cfg={}, mix={}, seconds=1.0,
                       rec=None, spans=None, compiles=0, peak_bytes=0,
                       setup_s=0.0, trace=red, scopes=stacks,
                       counters=counters)


SCAN = "jit(_chunk)/while/body/layer_scan/while/body/"


def _chunk_trace():
    """One 10 s chunk run: an entry copy, the step loop (``while.1``)
    holding the layer scan's loop (``while.2``) and, after it, a pool copy
    the compiler inserted and the head."""
    ops = [
        Event("%copy.1 = s8[] copy()", 0.0, 0.5),  # entry: not in a loop
        Event("%while.1 = () while()", 1.0, 8.0),
        Event("%while.2 = () while()", 1.5, 5.0),
        Event("%dynamic-slice.3 = s8[] dynamic-slice()", 2.0, 1.0),
        Event("%w4a8_matmul.4 = bf16[] custom-call()", 3.0, 2.0),
        Event("%bitcast_dynamic-update-slice_fusion.5 = s8[] fusion()",
              5.0, 1.0),
        Event("%copy.6 = s8[] copy()", 7.0, 1.0),
        Event("%fusion.7 = bf16[] fusion()", 8.0, 0.5),
    ]
    trace.nest(ops)
    stacks = {
        ops[3].name: SCAN + "squeeze:",
        ops[4].name: SCAN + "closed_call/layer/slot0/ffn/jit(w4a8_matmul)"
                            "/pallas_call:",
        ops[5].name: SCAN + "dynamic_update_slice:",
        ops[7].name: "jit(_chunk)/while/body/head/bsd,vd->bsv/dot_general:",
    }
    red = Reduced(ops=ops, modules=[Event("jit__chunk(1)", 0.0, 10.0)])
    return red, stacks


def test_layer_scan_overhead_on_a_synthetic_chunk():
    """Counted: the scan's slice (1 s) and write (1 s), the scan loop's own
    time outside its ops (5 - 1 - 2 - 1 = 1 s) and the pool copy in the
    step loop (1 s); not the layer's matmul, the head, the entry copy or
    the step loop's own time: 4 s of the chunk's 10."""
    red, stacks = _chunk_trace()
    read = metrics.reader("layer_scan_overhead.decode")
    assert read(_ctx(red, stacks, {})) == pytest.approx(40.0)
    # a stack naming the loop op itself counts as one with none
    stacks[red.ops[6].name] = "jit(_chunk)/while:"
    assert read(_ctx(red, stacks, {})) == pytest.approx(40.0)


def test_an_async_copy_across_a_loop_start_swallows_nothing():
    """An async copy's event that overlaps the start of the scan's loop
    (as recorded traces hold) is no parent of the loop: it adds its 0.2 s,
    and the loop's own time shrinks by the 0.1 s the copy covers."""
    red, stacks = _chunk_trace()
    red.ops.append(Event("%copy-done.8 = s8[] copy-done()", 1.4, 0.2))
    trace.nest(red.ops)
    read = metrics.reader("layer_scan_overhead.decode")
    assert read(_ctx(red, stacks, {})) == pytest.approx(41.0)


def test_layer_scan_overhead_absent():
    red, stacks = _chunk_trace()
    read = metrics.reader("layer_scan_overhead.decode")
    assert read(_ctx(None, stacks, {})) is None
    # a program that names no layer scan (before the scopes) reads nothing
    bare = {k: v.replace("layer_scan/", "") for k, v in stacks.items()}
    assert read(_ctx(red, bare, {})) is None
    assert read(_ctx(red, {}, {})) is None


COUNTERS = dict(passes=4, decode_steps=10, tokens_out=30, prefill_tokens=0,
                preemptions=0, attn_pages_live=300, attn_pages_grid=1200,
                pass_s=2.0, readback_s=1.5)


@pytest.mark.parametrize("name,want", [("attn_page_use.decode", 25.0),
                                       ("host_loop_share.decode", 25.0)])
def test_counter_readers(name, want):
    read = metrics.reader(name)
    red = Reduced()
    assert read(_ctx(red, {}, dict(COUNTERS))) == pytest.approx(want)
    assert read(_ctx(red, {}, {})) is None  # a program without counters
    assert read(_ctx(None, {}, dict(COUNTERS))) is None  # untraced run
    zero = dict(COUNTERS, attn_pages_grid=0, pass_s=0.0)
    assert read(_ctx(red, {}, zero)) is None


def test_counters_ride_on_the_pass_spans(tmp_path, monkeypatch):
    """The engine serving under the profiler here (CPU): the counters its
    ``serve/pass`` spans carry give, through the trace alone, the counters
    of the passes between the first span and the last; the harness reads
    them from the trace where it leaves it into the readers' context."""
    import jax
    import numpy as np

    from repro.configs import get_smoke
    from repro.models.transformer import init_model
    from repro.serving import PagedConfig, PagedEngine, Request
    from repro.serving import SamplerConfig

    cfg = get_smoke("smollm-360m").scaled(n_layers=2, vocab=128)
    eng = PagedEngine(init_model(jax.random.key(0), cfg), cfg,
                      PagedConfig(block_size=4, num_blocks=32,
                                  max_concurrency=2, max_pages_per_seq=6,
                                  chunk_max=4, attn_impl="ref"),
                      SamplerConfig(temperature=0.0))
    rng = np.random.default_rng(1)

    def reqs(base):
        return [Request(uid=base + i, prompt=rng.integers(0, 128, size=n),
                        max_new=m) for i, (n, m) in enumerate(
                            [(5, 3), (9, 7), (6, 9)])]

    eng.serve(reqs(0))
    snaps = []

    def late(sched, i):
        snaps.append(eng.counters.snapshot())

    raw = tmp_path / "raw"
    with jax.profiler.trace(str(raw)):
        start = eng.counters.snapshot()
        eng.serve(reqs(10), _late=late)
    (path,) = glob.glob(str(raw / "plugins" / "profile" / "*" / "*.xplane.pb"))
    got = scopes.pass_counters(path)
    # first span: the counters at the serve's start; last: those after
    # the next-to-last pass (its _late hook ran just before that pass end)
    assert got["passes"] == len(snaps) - 1
    for k in ("decode_steps", "tokens_out", "attn_pages_live",
              "attn_pages_grid"):
        assert got[k] == pytest.approx(snaps[-2][k] - start[k]), k
    assert 0 < got["readback_s"] < got["pass_s"]

    trace_dir = tmp_path / "trace"
    dest = trace_dir / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    shutil.copy(path, dest / "host.xplane.pb")
    # a CPU trace holds no device plane to reduce
    monkeypatch.setattr(trace, "reduce", lambda p: Reduced())
    ctx = _ctx(None)
    harness.read_trace(ctx, trace_dir)
    assert ctx.scopes == {}  # no device plane here
    assert scopes.counters_of(ctx) == got
    assert metrics.reader("attn_page_use.decode")(ctx) == \
        pytest.approx(100 * got["attn_pages_live"] / got["attn_pages_grid"])
    assert scopes.stacks_of(_ctx(None)) == {}
    assert scopes.counters_of(_ctx(None)) == {}


#: recorded on a TPU v5e with the program's scopes and spans in place:
#: Phi-4-mini's widths at 2 layers, int8 pages, 2 rows; two admits, then
#: two 2-step decode chunks in two passes
SCOPED = "decode-int8-scoped"


def test_recorded_scopes_and_counters():
    """On the recorded trace: the scan's machinery is counted (its slices
    and writes, and the step loop's copies of the stacked int8 pools),
    nothing under ``layer`` is; the pass spans' counters give the first
    pass's decode chunk, whose page use matches the harness's own live
    lengths."""
    from repro.serving.metrics import pages_filled

    path = FIXTURES / SCOPED / "trace.xplane.pb"
    rec = json.loads((FIXTURES / SCOPED / "spans.json").read_text())
    red = trace.reduce(str(path))
    stacks = scopes.read(path)
    counters = scopes.pass_counters(path)
    ctx = _ctx(red, stacks, counters)
    share = metrics.reader("layer_scan_overhead.decode")(ctx)
    assert 0 < share < 100

    lso = importlib.util.spec_from_file_location(
        "lso", Path(metrics.HERE) / "layer_scan_overhead.decode.py")
    mod = importlib.util.module_from_spec(lso)
    lso.loader.exec_module(mod)
    ops = red.inside(red.ops, "chunk")
    in_loop = mod._in_loop(ops)
    counted = [e for e in ops
               if mod.machinery(stacks.get(e.name), in_loop(e.start))]
    layers = rec["cfg"]["num_hidden_layers"]
    pool_copies = [e for e in counted if trace.op_name(e.name).startswith(
        "copy.") and f"s8[{layers}," in e.name.split(" = ", 1)[1][:12]]
    assert pool_copies
    assert not any(scopes.under(stacks.get(e.name, ""), "layer")
                   for e in counted)
    assert any(trace.op_name(e.name).startswith("dynamic-slice")
               for e in counted)

    # the first pass's chunk, from the harness's live lengths
    (_, k, live, _), *_ = rec["chunks"]
    assert counters["decode_steps"] == k
    assert counters["attn_pages_live"] == pages_filled(
        live, k, rec["cfg"]["serving"]["block_size"])
    page_use = metrics.reader("attn_page_use.decode")(ctx)
    host = metrics.reader("host_loop_share.decode")(ctx)
    assert 0 < page_use < 100 and 0 < host < 100
