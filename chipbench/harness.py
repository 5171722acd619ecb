"""One run of one cell: build, warm, the measured window, the check.

``run.py`` is the command; this module is the run itself, so the tests
can drive it on the CPU at a small size (``require_tpu`` lies in
``run.py`` alone).
"""

from __future__ import annotations

import gc
import shutil
import time
from pathlib import Path

import jax
import numpy as np

from chipbench import check, recorder, traffic, weights

#: uids of the warm-up requests, clear of the window's
WARM_UID = 1 << 30
#: a traced run traces from this share of the window, for at most
#: TRACE_SECONDS (a trace of the whole window would take minutes to read)
TRACE_FROM = 0.25
TRACE_SECONDS = 8.0
#: seconds past the window a run may serve for the check's sample
CHECK_WAIT = 60.0


class CompileCounter:
    """Stamps (wall clock) every program lowered, whether the backend then
    compiles it or loads it from the cache."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.stamps: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if event == self.EVENT:
            self.stamps.append(time.time())

    def since(self, t: float | None) -> int:
        return sum(1 for s in self.stamps if t is not None and s >= t)

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._hear)


def engine_for(params, cfg: dict, mix: dict, seed: int):
    from repro.serving import PagedConfig, PagedEngine, SamplerConfig
    from repro.serving.scheduler import SchedulerPolicy

    srv = cfg["serving"]
    bs = srv["block_size"]
    pages = -(-traffic.longest_context(mix) // bs)
    conc = mix["concurrency"]
    pol = {k: (tuple(v) if isinstance(v, list) else v)
           for k, v in mix.get("policy", {}).items()}
    paged = PagedConfig(block_size=bs,
                        num_blocks=mix.get("pool_pages", conc * pages),
                        max_concurrency=conc, max_pages_per_seq=pages,
                        chunk_max=mix.get("chunk_max", 32),
                        kv_dtype=srv["kv_dtype"],
                        sched=SchedulerPolicy(**pol))
    return PagedEngine(params, weights.model_config(cfg), paged,
                       SamplerConfig(temperature=0.0, seed=int(seed) % 2**31))


def reset(engine) -> None:
    """A fresh page pool and allocator for an engine that serves again
    after a window was cut short (its requests in flight still held
    pages). Used where one engine serves many windows."""
    from repro.models.transformer import init_paged_cache
    from repro.serving.scheduler import PoolState

    pc = engine.paged
    engine.cache = None
    gc.collect()
    engine.cache = init_paged_cache(
        engine.cfg, pc.max_concurrency, pc.num_blocks, pc.block_size,
        pc.max_pages_per_seq,
        kv_dtype="int8" if pc.kv_dtype == "int8" else None)
    engine.pool_state = PoolState.fresh(pc.num_blocks)


def warm(engine, mix: dict, seed: int) -> None:
    """Serve one request per prompt length, long enough to grow its page
    row: every admit, prefill-chunk, decode, grow and release program the
    traffic can reach is then compiled or loaded."""
    from repro.serving.scheduler import Request

    bs = engine.paged.block_size
    rng = np.random.default_rng(int(seed) + 2)
    reqs = [Request(uid=WARM_UID + i,
                    prompt=rng.integers(0, engine.cfg.vocab, size=s0),
                    max_new=min(mix["output"]["max"], bs + 2))
            for i, s0 in enumerate(mix["prompt_lens"])]
    engine.serve(reqs)
    jax.block_until_ready(engine.cache)


class Spans:
    """Wraps the engine's device programs: records each decode chunk's
    trip count and live lengths and each prefill's token range, and, while
    tracing, names each call on the host timeline
    (``chipbench_admit``, ``chipbench_chunk``, ...)."""

    PROGRAMS = ("_admit", "_chunk", "_release", "_grow", "_admit_stub",
                "_prefill_chunk", "_admit_batch")

    def __init__(self, engine, t0_ref):
        self.t0 = t0_ref
        self.sched = None
        self.block = engine.paged.block_size
        self.rows = engine.paged.max_concurrency
        #: (t, k, KV length before the chunk of each decoding row, traced)
        self.chunks: list = []
        #: (t, program, first position, end position, with head, traced)
        self.prefills: list = []
        self.tracing = False
        self.engine = engine
        self.real = {n: getattr(engine, n) for n in self.PROGRAMS}
        for name, fn in self.real.items():
            setattr(engine, name, self._wrap(name, fn))

    def unwrap(self) -> None:
        for name, fn in self.real.items():
            setattr(self.engine, name, fn)
        self.engine, self.real = None, {}

    def probe(self, engine, sched) -> None:
        self.sched = sched

    def _note(self, name, a) -> None:
        t = time.perf_counter() - self.t0[0]
        if name == "_chunk" and self.sched is not None:
            live = [st.req.prompt.size + st.produced - 1
                    for st in self.sched.active.values()
                    if not st.prefilling]
            self.chunks.append((t, int(a[2]), live, self.tracing))
        elif name == "_admit":
            self.prefills.append((t, name, 0, int(np.size(a[2])), True,
                                  self.tracing))
        elif name == "_prefill_chunk":
            start = int(a[7]) * self.block
            self.prefills.append((t, name, start, start + int(np.size(a[2])),
                                  bool(a[9]), self.tracing))

    def _wrap(self, name, fn):
        def call(*a, **kw):
            self._note(name, a)
            if not self.tracing:
                return fn(*a, **kw)
            with jax.profiler.TraceAnnotation(f"chipbench{name}"):
                return fn(*a, **kw)
        return call


class WindowClosed(Exception):
    """Raised from the serve loop's pass hook to end a run."""


def serve_window(engine, mix: dict, seed: int, seconds: float, rec, *,
                 trace=None):
    """Drive the mix's closed loop and measure ``seconds`` of it.

    The clients' first requests, cut to the loop's steady state
    (``traffic.first_outputs``), are admitted untimed; the window opens at
    the end of the first pass after which every one of them is decoding
    (``rec.opened``; every pass end is marked, ``rec.mark``).
    From then on each finished request is replaced at once, until the
    close. The run ends at the first pass after the close that ends the
    measured span (``rec.span_end``) or a later one, once it has
    ``check.SAMPLE`` finished requests for the check (at most
    ``CHECK_WAIT`` seconds past the close; nothing is submitted after it).

    Returns (finished, prompts, spans, seconds served past the close,
    wall-clock time of the open): ``finished`` maps each request that
    completed to its prompt and served tokens.

    ``trace`` = (start, stop, begin, end), in seconds from the open:
    ``begin()`` runs at the first pass at or after ``start``, ``end()`` at
    the first at or after ``stop`` (or when the run ends); chunks run in
    between are marked traced."""
    from repro.serving.scheduler import Request

    vocab = engine.cfg.vocab
    t0 = [time.perf_counter()]
    spans = Spans(engine, t0)
    prompts, tokens = {}, {}
    state = {"traced": None, "opened": None}

    def follow(sched):
        """Keep each active request's token list (the scheduler extends
        it in place), so a run stopped mid-flight still has them."""
        for st in sched.active.values():
            tokens.setdefault(st.req.uid, st.tokens)

    def probe(eng, sched):
        spans.probe(eng, sched)
        follow(sched)

    def tracing(since):
        if trace is None:
            return
        start, stop, begin, end = trace
        if state["traced"] is None and since >= start:
            state["traced"] = True
            spans.tracing = True
            begin()
        elif state["traced"] and since >= stop:
            state["traced"] = False
            spans.tracing = False
            end()

    def req(it, max_new=None):
        max_new = it.max_new if max_new is None else max_new
        prompts[it.uid] = it.prompt
        rec.expect(it.uid, max_new)
        return Request(uid=it.uid, prompt=it.prompt, max_new=max_new)

    n = mix["concurrency"]
    stream = traffic.stream(mix, seed, vocab)
    first = [req(next(stream), m) for m in traffic.first_outputs(mix, seed)]

    def late(sched, _):
        now = time.perf_counter() - t0[0]
        follow(sched)
        rec.mark()
        if state["opened"] is None:
            if sched.queue or any(st.prefilling
                                  for st in sched.active.values()):
                return
            state["opened"] = time.time()
            rec.open()
        since = now - rec.opened
        tracing(since)
        if rec.span_end(seconds) is not None:  # the window's last pass ended
            done = sum(1 for u in tokens if rec.done(u))
            if done >= check.SAMPLE or since >= seconds + CHECK_WAIT:
                raise WindowClosed
            return
        for _ in range(n - len(sched.queue) - len(sched.active)):
            r = req(next(stream))
            sched.submit(r)
            rec.submitted(r.uid, 0, now)

    t0[0] = time.perf_counter()
    try:
        engine.serve(first, metrics=rec, _late=late, _probe=probe)
    except WindowClosed:
        pass
    finally:
        spans.unwrap()
    past = time.perf_counter() - t0[0] - rec.opened - seconds
    if state["traced"]:
        spans.tracing = False
        trace[3]()
    jax.block_until_ready(engine.cache)
    finished = {u: np.concatenate([prompts[u], np.asarray(t, np.int32)])
                for u, t in tokens.items() if rec.done(u)}
    return finished, prompts, spans, max(0.0, past), state["opened"]


def sample_gaps(cfg: dict, mix: dict, seed: int, results: dict,
                prompts: dict, **lower):
    """The check's sample and the gap of each of its served tokens (with
    ``lower``, the control's: see ``check.gaps``)."""
    sample = check.pick(results, prompts, seed)
    return sample, check.gaps(cfg, seed, [results[u] for u in sample],
                              [len(prompts[u]) for u in sample],
                              traffic.longest_context(mix), **lower)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path, log=print) -> dict:
    cfg, mix, limits = cell["cfg"], cell["mix"], cell["limits"]
    name = cell["cell"]["name"]
    params = weights.packed_params(cfg, seed)
    jax.block_until_ready(params)
    engine = engine_for(params, cfg, mix, seed)
    del params
    warm(engine, mix, seed)
    rec = recorder.Recorder()
    trace_dir = root / "chiprun_out" / "chipbench" / "trace"
    window = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from TraceAnnotation
        start = TRACE_FROM * seconds
        window = (start, start + min(TRACE_SECONDS, seconds - start),
                  lambda: jax.profiler.start_trace(str(trace_dir),
                                                   profiler_options=opts),
                  jax.profiler.stop_trace)
    compiles = CompileCounter()
    results, prompts, spans, past, opened = serve_window(
        engine, mix, seed, seconds, rec, trace=window)
    compiles.close()
    n_compiles = compiles.since(opened)
    setup_s = (opened or time.time()) - t_start
    peak = peak_bytes()
    log(f"{name}: set-up {setup_s:.3f} s (to the window's open), "
        f"window {seconds} s; served {past:.3f} s past the close; "
        f"{len(results)} requests finished; compiles in the window "
        f"{n_compiles}; peak_bytes_in_use {peak}")
    close = rec.opened + seconds
    attempted = sum(1 for r in rec.recs.values() if r.t_due < close)
    # submitted before the close and never served a token
    failed = sum(1 for r in rec.recs.values()
                 if r.t_due < close and not r.times)
    # the engine's state goes before the reference runs
    engine.cache = engine.params = None
    del engine
    gc.collect()
    t_check = time.perf_counter()
    mean = None
    if results:
        sample, g = sample_gaps(cfg, mix, seed, results, prompts)
        mean = float(g.mean())
        log(f"{name}: check over {len(g)} served tokens of requests "
            f"{sample}: mean logit gap {mean!r}, widest {float(g.max())!r} "
            f"(not compared), {time.perf_counter() - t_check:.1f} s")
    checks = {"mean_logit_gap": {"value": mean,
                                 "limit": limits["mean_logit_gap"]["limit"]}}
    correct = failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    from chipbench import metrics
    from chipbench import trace as tracing

    ctx = metrics.Ctx(cell=cell["cell"], cfg=cfg, mix=mix, seconds=seconds,
                      rec=rec, spans=spans, compiles=n_compiles,
                      peak_bytes=peak, setup_s=setup_s,
                      peaks=metrics.peaks(jax.devices()[0].device_kind)
                      if jax.devices()[0].platform == "tpu" else {})
    dev = {**device_info(), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        ctx.trace = tracing.reduce_dir(trace_dir)
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        found = metrics.read_all(cell["bench"], "per_layer", ctx)
    else:
        found = metrics.read_all(cell["bench"], "end_to_end", ctx)
    for k in [k for k, v in found.items() if not np.isfinite(v["value"])]:
        log(f"{name}: {k} = {found.pop(k)['value']} left out (not finite)")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": found, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = checks
    return result

