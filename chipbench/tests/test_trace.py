"""Trace reduction: the arithmetic on synthetic events, and the names on
a small trace recorded on a TPU v5e (``fixtures/``)."""

import json
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.trace import Event, Reduced


def test_union_counts_overlap_once():
    evs = [Event("a", 0.0, 1.0), Event("b", 0.5, 1.0), Event("c", 3.0, 0.5),
           Event("d", 3.1, 0.1)]
    assert trace.union_seconds(evs) == pytest.approx(2.0)
    gaps = trace.idle_gaps(evs, -1.0, 4.0)
    assert gaps == [(-1.0, 1.0), (1.5, 1.5), (3.5, 0.5)]


def test_events_inside_program_runs():
    red = Reduced(
        ops=[Event("%w4a8_matmul.3 = x", 0.1, 0.1),
             Event("%w4a8_matmul.3 = x", 2.1, 0.1),
             Event("other", 0.2, 0.1)],
        modules=[Event("jit__chunk(123)", 0.0, 1.0),
                 Event("jit__admit(456)", 2.0, 1.0)])
    assert red.kernel_seconds("w4a8", inside="chunk") == pytest.approx(0.1)
    assert red.program_seconds("admit") == pytest.approx(1.0)


def test_idle_gap_named_by_covering_host_event():
    red = Reduced(host=[Event("outer", 0.0, 10.0),
                        Event("chipbench_admit", 1.0, 2.0)],
                  gaps=[(1.2, 1.0), (5.0, 1.0)])
    out = red.breakdown()
    assert out["idle_gaps"] == [["chipbench_admit", 1.0], ["outer", 1.0]]


def test_self_time_of_nested_ops():
    loop = Event("%while.3 = (s32[]) while(...)", 0.0, 10.0)
    body = [Event("%w4a8_matmul.1 = bf16[8,8] custom-call()", 1.0, 2.0),
            Event("%paged_decode_attention.2 = bf16[8] custom-call()", 4.0,
                  3.0)]
    trace.nest([loop, *body])
    assert loop.self_dur == pytest.approx(5.0)
    assert [b.self_dur for b in body] == pytest.approx([2.0, 3.0])
    assert trace.is_kernel("w4a8", body[0].name)
    assert trace.is_kernel("paged_attn", body[1].name)
    assert not trace.is_kernel("w4a8", loop.name)
    red = Reduced(ops=[loop, *body])
    assert red.breakdown()["device_ops"][0] == ["while.3", 5.0]


FIXTURES = Path(__file__).resolve().parent / "fixtures"
CASES = sorted(p.name for p in FIXTURES.glob("*") if p.is_dir())


@pytest.mark.parametrize("case", CASES)
def test_recorded_chip_trace(case):
    """A trace recorded on a TPU v5e (2 layers at the cell's widths, two
    admits and one decode chunk): the programs and kernels are found by
    the names the trace gives them, each kernel once per layer and step
    (the W4A8 kernel at each of a layer's 7 sites), and the per-layer
    readers give shares under 100%."""
    from chipbench import metrics

    red = trace.reduce(str(FIXTURES / case / "trace.xplane.pb"))
    spans = json.loads((FIXTURES / case / "spans.json").read_text())
    cfg = spans["cfg"]
    steps = sum(c[1] for c in spans["chunks"])
    layers = cfg["num_hidden_layers"]
    chunk_ops = red.inside(red.ops, "chunk")
    n_attn = sum(trace.is_kernel("paged_attn", e.name) for e in chunk_ops)
    n_w4a8 = sum(trace.is_kernel("w4a8", e.name) for e in chunk_ops)
    assert n_attn == steps * layers
    assert n_w4a8 == steps * layers * 7
    assert red.program_seconds("admit") > 0
    assert 0 < red.busy_s < red.window_s
    assert red.kernel_seconds("w4a8", inside="chunk") > 0
    top = [name for name, _ in red.breakdown()["device_ops"]]
    assert any(n.startswith("paged_decode_attention") for n in top) or any(
        n.startswith("w4a8_matmul") for n in top)

    class Spans:
        rows = spans["rows"]
        chunks = [tuple(c) for c in spans["chunks"]]
        prefills = [tuple(p) for p in spans["prefills"]]

    ctx = metrics.Ctx(cell={"name": case}, cfg=cfg, mix={}, seconds=1.0,
                      rec=None, spans=Spans, compiles=0, peak_bytes=0,
                      setup_s=0.0, trace=red,
                      peaks=metrics.peaks("TPU v5 lite"))
    for name in ("w4a8_roofline.decode", "paged_attn_roofline.decode",
                 "decode_mfu", "device_idle.decode"):
        value = metrics.reader(name)(ctx)
        assert value is not None and 0 < value < 100, (name, value)
