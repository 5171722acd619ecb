"""The serving layer's own instrumentation: the ``serve/*`` host spans of
both serve loops, the device scopes of the decode chunk, the engine's
counters and the launcher's operator line. Tiny configuration, a few
requests, CPU."""

import glob
import math
import re

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models.layers import packed_backend, resolve_paged_attn_impl
from repro.models.transformer import init_model
from repro.serving import (
    PagedConfig,
    PagedEngine,
    Request,
    SamplerConfig,
    SchedulerPolicy,
)
from repro.serving import metrics as sm
from repro.serving.metrics import ServeCounters, pages_filled

FIFO = SchedulerPolicy()
THROUGHPUT = SchedulerPolicy(admit_window=4, batch_max=2, prefill_chunk=8,
                             watermark=(0, 0))


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("smollm-360m").scaled(n_layers=2, vocab=128)
    return cfg, init_model(jax.random.key(0), cfg)


def _engine(cfg, params, **kw):
    pc = dict(block_size=4, num_blocks=32, max_concurrency=2,
              max_pages_per_seq=6, chunk_max=4, attn_impl="ref")
    pc.update(kw)
    return PagedEngine(params, cfg, PagedConfig(**pc),
                       SamplerConfig(temperature=0.0))


def _requests(lens=(5, 11, 7), max_new=6):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(0, 128, size=n)
                    .astype(np.int32), max_new=max_new)
            for i, n in enumerate(lens)]


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(line.name, e.name, e.start_ns, e.end_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serve/")]
    return out


SPANS = {
    "fifo": {sm.SPAN_PASS, sm.SPAN_ADMIT, sm.SPAN_DECODE, sm.SPAN_READBACK,
             sm.SPAN_RECORD, sm.SPAN_RELEASE, sm.SPAN_LATE},
    "throughput": {sm.SPAN_PASS, sm.SPAN_ADMIT, sm.SPAN_PREFILL,
                   sm.SPAN_PLAN, sm.SPAN_GROW, sm.SPAN_DECODE,
                   sm.SPAN_READBACK, sm.SPAN_RECORD, sm.SPAN_RELEASE,
                   sm.SPAN_LATE},
}


@pytest.mark.parametrize("policy", ["fifo", "throughput"])
def test_spans_nest_inside_pass(setup, tmp_path, policy):
    """Under the profiler every phase span of the loop lies inside a
    ``serve/pass`` span on the same thread, and each pass span carries its
    index and the counters as they stood when it began."""
    cfg, params = setup
    eng = _engine(cfg, params,
                  sched=FIFO if policy == "fifo" else THROUGHPUT)
    reqs = _requests((5, 11, 13, 7))
    eng.serve(reqs[:1])  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(reqs[1:], _late=lambda sched, i: None)
    events = _host_events(tmp_path)
    assert {name for _, name, *_ in events} == SPANS[policy]
    passes = [e for e in events if e[1] == sm.SPAN_PASS]
    for thread, name, t0, t1, _ in events:
        if name != sm.SPAN_PASS:
            assert any(p[0] == thread and p[2] <= t0 and t1 <= p[3]
                       for p in passes), name
    stats = [p[4] for p in sorted(passes, key=lambda p: p[2])]
    assert [s["index"] for s in stats] == list(range(len(stats)))
    for a, b in zip(stats, stats[1:]):
        assert b["passes"] == a["passes"] + 1
        assert b["pass_s"] > a["pass_s"]
    assert eng.counters.passes == stats[-1]["passes"] + 1


def test_counters_hand_count(setup):
    """Two requests of 5 and 11 prompt tokens, 6 new tokens each, FIFO,
    pages of 4, chunks of at most 4. Pass 0 admits both (their first
    tokens) and runs a 4-step chunk from KV lengths 5 and 11: row 1 reads
    ceil(6/4)+ceil(7/4)+ceil(8/4)+ceil(9/4) = 2+2+2+3 = 9 pages, row 2
    ceil(12/4)+...+ceil(15/4) = 3+4+4+4 = 15. Pass 1 runs the last step
    from 9 and 15: 3 + 4 = 7. So 31 live pages over 5 steps, against a
    grid of 5 x 2 rows x 6 pages = 60."""
    cfg, params = setup
    eng = _engine(cfg, params)
    out = eng.serve(_requests((5, 11)))
    c = eng.counters.snapshot()
    assert c["tokens_out"] == sum(out[u].size - n for u, n in ((0, 5), (1, 11)))
    assert c["tokens_out"] == 12
    assert (c["passes"], c["decode_steps"], c["prefill_tokens"]) == (2, 5, 16)
    assert c["attn_pages_live"] == 31
    assert c["attn_pages_grid"] == 60
    assert c["preemptions"] == 0
    assert 0 < c["readback_s"] < c["pass_s"]


@pytest.mark.parametrize("policy", ["fifo", "throughput"])
def test_counters_match_what_was_served(setup, policy):
    """Tokens counted equal the tokens served; live pages never exceed
    the grid walked; preemptions is the counters' field, read-only."""
    cfg, params = setup
    eng = _engine(cfg, params, num_blocks=12,
                  sched=FIFO if policy == "fifo" else SchedulerPolicy(
                      admit_window=2, watermark=(1, 3)))
    reqs = _requests((5, 11, 13, 7, 9), max_new=9)
    out = eng.serve(reqs)
    c = eng.counters
    assert c.tokens_out == sum(out[r.uid].size - r.prompt.size for r in reqs)
    assert 0 < c.attn_pages_live <= c.attn_pages_grid
    assert eng.preemptions == c.preemptions
    with pytest.raises(AttributeError):
        eng.preemptions = 0


def test_pages_filled_closed_form():
    rng = np.random.default_rng(3)
    for bs in (1, 3, 4, 64):
        for _ in range(20):
            lens = rng.integers(0, 300, size=rng.integers(0, 6))
            k = int(rng.integers(0, 40))
            want = sum(math.ceil((n + j + 1) / bs)
                       for n in lens for j in range(k))
            assert pages_filled(lens, k, bs) == want


def test_summary_line():
    c = ServeCounters(passes=4, decode_steps=10, tokens_out=30,
                      prefill_tokens=20, attn_pages_live=25,
                      attn_pages_grid=100, pass_s=2.0, readback_s=1.5)
    line = ServeCounters.summary(c.snapshot())
    assert "passes=4 steps=10 tokens=30" in line
    assert "host_loop_share=25.0%" in line and "attn_page_use=25.0%" in line


@pytest.mark.parametrize("kv_dtype", ["act", "int8"])
def test_chunk_names_its_scopes(setup, kv_dtype):
    """The decode chunk's lowered text names the layer scan, the layer,
    the page write, the head, the sampler and the observer's site labels."""
    cfg, params = setup
    eng = _engine(cfg, params, kv_dtype=kv_dtype)
    lowered = eng._chunk.lower(
        eng.params, eng.cache, np.int32(1), packed_backend(),
        resolve_paged_attn_impl("ref"), eng.datapath_fingerprint,
        eng.attn_spec)
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    parts = {p for n in names for p in n.split("/")}
    for scope in ("layer_scan", "layer", "kv_append", "head", "sample",
                  "slot0", "mixer", "ffn"):
        assert scope in parts, scope
    # the page write lies inside the layer, the layer inside the scan
    assert any(n.startswith("layer/slot0/mixer/kv_append/") for n in names)
    assert any("/layer_scan/while/body/" in n for n in names)


def test_launcher_profile_and_counters(tmp_path, capsys):
    from repro.launch.serve import main

    main(["--arch", "tiny-lm-xs", "--batch", "2", "--prompt-len", "8",
          "--max-new", "4", "--paged", "--block-size", "8",
          "--max-concurrency", "2", "--profile", str(tmp_path)])
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if "counters:" in ln)
    assert "passes=" in line and "tokens=8 " in line
    assert "host_loop_share=" in line and "attn_page_use=" in line
    assert glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))
