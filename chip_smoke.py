#!/usr/bin/env python3
"""Chip smoke test: the main path of this repo, once, on a TPU.

    python chip_smoke.py               # one chip (TPU v5e)
    python chip_smoke.py --four-chips  # the mesh-serving path on a 2x2 host

One process drives everything; it exits non-zero on any failure and prints
nothing ``ok`` unless every phase passed. With no TPU it fails before any
phase runs. The last line of standard output is the result, e.g.

    {"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

Phases on one chip:

(a) device check — JAX sees a TPU, and the served path resolves to the
    Pallas kernels (``packed_backend() == "kernel"``,
    ``resolve_paged_attn_impl("auto") == "kernel"``);
(b) the kernels at SmolLM-360M widths against their references;
(c) the main path at SmolLM-360M's full width, depth cut to
    ``SMOLLM_LAYERS``: ``repro.launch.quantize`` (GPFQ W4A8, P_I=16, T=128,
    random init from ``--seed``) writes a v2 artifact, the serve load path
    (``load_flat_artifact`` -> ``packed_params_from_artifact``) reads it
    back, and ``PagedEngine`` answers 8 requests (128 prompt + 32 new
    tokens) over float and int8 KV pages; teacher-forced logits of the
    served path are compared against the same kernels run by the Pallas
    interpreter and against the dequant backend with reference attention.

``--four-chips`` runs only the mesh path and what it is compared with: a
``phi4-mini-3.8b`` RTN artifact served by ``PagedEngine(mesh=...)`` at
``1,4`` and ``4,1`` against the one-device engine, greedy streams
byte-equal.

The phase functions take the config and backends as arguments, so the CPU
test suite drives phase (c) at ``--smoke`` size in Pallas interpret mode.
Times and rates printed here come from one cold run and are labelled "not
a benchmark".
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
if SRC.is_dir():
    sys.path.insert(0, str(SRC))

#: SmolLM-360M matmul sites (K, N): q/o (960, 960), k/v (960, 320),
#: gate/up (960, 2560), down (2560, 960)
SMOLLM_SITES = ((960, 960), (960, 320), (960, 2560), (2560, 960))

# Tolerances, each with its reason.
#: W4A8 kernel vs ``w4a8_matmul_ref``, relative to max|ref|: both sides sum
#: the same integer products exactly in int32; only the f32 epilogue
#: differs in the order it applies s_x and s_w (a few ulp).
W4A8_RTOL = 1e-5
#: Paged attention over bf16 pages vs the gather reference, absolute: the
#: kernel keeps f32 online-softmax state and rounds once to bf16; the
#: reference rounds its probabilities to bf16 before the PV sum and its
#: output to bf16. Each rounding is <= 2^-9 relative on O(1) outputs.
ATTN_FLOAT_ATOL = 2e-2
#: Paged attention over int8 pages, absolute: the kernel additionally
#: quantizes q per head to int8 codes and the probabilities to 8-bit codes
#: (the AttnDatapathSpec datapath), which the float reference does not.
ATTN_INT8_ATOL = 6e-2
#: Served logits vs the same kernels run by the Pallas interpreter, as
#: ||served - interpreted||_2 / ||interpreted||_2: one datapath, so only
#: float rounding differs (XLA vs Mosaic transcendentals in the softmax,
#: bf16 activations), and it can flip an activation code at a rounding
#: boundary, which the layers then carry forward.
LOGITS_INTERPRET_TOL = 2e-2
#: Served logits vs the dequant backend with reference attention, as
#: ||served - ref||_2 / ||ref||_2 over all compared logits: the served
#: path quantizes every matmul input to the calibrated 8-bit static
#: activation codes (the dequant path keeps them in bf16) and runs the
#: online softmax in another order; int8 pages add the q / probability
#: codes. On random weights the gap compounds with depth: on the CPU at
#: SmolLM-360M width it measured 0.32 at 1 layer and 0.51 at 4 layers, while
#: the same kernels matched a fake-quant simulation to 0.03. Logits that
#: share nothing sit near sqrt(2).
LOGITS_REL_TOL = 0.75
#: Depth of the one-chip run. Width is what the kernels see; past a few
#: layers random-weight logits decorrelate under any perturbation (above),
#: and the logits checks would lose their power.
SMOLLM_LAYERS = 4
#: Depth of the four-chip run: what is compared there is the mesh layout
#: of the pools and weights, which does not depend on depth.
PHI4_LAYERS = 2
#: Where the quantized artifacts are written (git-ignored).
OUT = ROOT / "chiprun_out" / "chip_smoke"


def say(*a) -> None:
    print("[chip_smoke]", *a, flush=True)


def _rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# (b) kernels at real widths
# ---------------------------------------------------------------------------
def kernel_phase(*, interpret: bool = False, seed: int = 0,
                 ms=(8, 128), sites=SMOLLM_SITES) -> dict:
    """W4A8 at every SmolLM site shape and paged attention over bf16 and
    int8 pages (B=8, 15 heads / 5 kv heads, hd=64, 64-token pages), each
    against its reference. Raises AssertionError past a tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import (
        paged_attention_reference,
        paged_decode_attention,
        quantize_kv_pages,
    )
    from repro.kernels.ref import w4a8_matmul_ref
    from repro.kernels.w4a8_mm import pack_int4, pad_packed, w4a8_decode_matmul

    rng = np.random.default_rng(seed)
    errs = {}
    for k, n in sites:
        q = rng.integers(-8, 8, size=(k, n))
        wp = pack_int4(jnp.asarray(q))
        col_sums = jnp.sum(jnp.asarray(q, jnp.int32), axis=0)
        scale = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(n,)), jnp.float32)
        for m in ms:
            x = jnp.asarray(rng.integers(0, 256, size=(m, k)), jnp.uint8)
            y = w4a8_decode_matmul(x, pad_packed(wp), scale, col_sums,
                                   0.02, 131, interpret=interpret)
            ref = w4a8_matmul_ref(x, wp, scale, 0.02, 131)
            err = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
            errs[f"w4a8 M={m} K={k} N={n}"] = err
            say(f"w4a8 M={m} K={k} N={n}: max|err|/max|ref| = {err:.3e} "
                f"(tol {W4A8_RTOL:g})")
            assert y.shape == ref.shape and err <= W4A8_RTOL, (m, k, n, err)

    B, nkv, g, hd, bs, P = 8, 5, 3, 64, 64, 4
    nb = B * P + 4
    qa = jnp.asarray(rng.normal(size=(B, nkv * g, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(nb, bs, nkv, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(nb, bs, nkv, hd)), jnp.bfloat16)
    lens = jnp.asarray(rng.integers(1, P * bs + 1, size=B), jnp.int32)
    tab = np.full((B, P), nb, np.int32)  # sentinel = nb past each length
    perm = rng.permutation(nb)
    for b in range(B):
        npg = -(-int(lens[b]) // bs)
        tab[b, :npg] = perm[b * P:b * P + npg]
    tab = jnp.asarray(tab)
    kc, ks = quantize_kv_pages(kp)
    vc, vs = quantize_kv_pages(vp)
    cases = (
        ("bf16", (kp, vp), {}, ATTN_FLOAT_ATOL),
        ("int8", (kc, vc), {"k_scales": ks, "v_scales": vs}, ATTN_INT8_ATOL),
    )
    for name, (kk, vv), sc, tol in cases:
        out = paged_decode_attention(qa, kk, vv, tab, lens, interpret=interpret,
                                     **sc)
        ref = paged_attention_reference(qa, kk, vv, tab, lens, **sc)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        errs[f"paged_attention {name}"] = err
        say(f"paged attention {name} pages: max|err| = {err:.3e} (tol {tol:g})")
        assert out.shape == ref.shape and err <= tol, (name, err)
    jax.block_until_ready(out)
    return errs


# ---------------------------------------------------------------------------
# (c) the main path
# ---------------------------------------------------------------------------
def quantize_phase(cfg, arch: str, out_dir: str, *, seed: int = 0,
                   seq: int = 128, calib_batches: int = 4,
                   calib_batch_size: int = 4, eval_batches: int = 4,
                   algorithm: str = "gpfq") -> str:
    """``repro.launch.quantize`` at the paper's headline datapath (W4A8,
    P_I=16, T=128) on ``cfg``; returns the artifact directory. GPFQ
    artifacts must be certified; RTN carries no certificate."""
    from repro.launch import quantize as qz

    args = qz.build_parser().parse_args([
        "--arch", arch, "--algorithm", algorithm, "--w-bits", "4",
        "--act-bits", "8", "--p-bits", "16", "--tile", "128",
        "--seq", str(seq), "--calib-batches", str(calib_batches),
        "--calib-batch-size", str(calib_batch_size),
        "--eval-batches", str(eval_batches), "--seed", str(seed),
        "--out", out_dir,
    ])
    t0 = time.perf_counter()
    report = qz.quantize(cfg, args)
    cert = report["cert"]
    say(f"quantize: cert.ok={cert['ok']} n_certified={cert['n_certified']} "
        f"min_headroom_bits={cert['min_headroom_bits']} "
        f"float_ppl={report['float_ppl']} quant_ppl={report['quant_ppl']} "
        f"datapath={report['datapath']} "
        f"({time.perf_counter() - t0:.1f}s, not a benchmark)")
    assert cert["ok"] or algorithm == "rtn", cert
    assert math.isfinite(report["float_ppl"]) and math.isfinite(
        report["quant_ppl"]), report
    return os.path.join(out_dir, "quantized")


def load_phase(cfg, artifact: str, *, seed: int = 0):
    """The serve launcher's load path: the flat artifact over a seeded
    float init (which supplies the embedding)."""
    import jax

    from repro.models.transformer import init_model
    from repro.quant.serve_packed import (
        load_flat_artifact,
        packed_params_from_artifact,
    )
    from repro.quant.spec import tree_datapath_fingerprint

    flat, meta = load_flat_artifact(artifact)
    params = packed_params_from_artifact(
        flat, init_model(jax.random.key(seed), cfg), cfg, meta=meta)
    say(f"loaded artifact v{meta.get('artifact_version')} "
        f"datapath={tree_datapath_fingerprint(params)} "
        f"({meta.get('datapath')})")
    return params


def _prompts(cfg, n: int, prompt_len: int, seed: int):
    import numpy as np

    from repro.data import DataConfig, TokenBatcher

    data = TokenBatcher(DataConfig(vocab=cfg.vocab, seq_len=prompt_len,
                                   global_batch=n, seed=seed))
    return np.asarray(data.batch(0)["tokens"])


def _peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve_phase(params, cfg, prompts, max_new: int, *, kv_dtype: str,
                backend: str, attn_impl: str, block_size: int, seed: int = 0,
                mesh=None):
    """Answer every prompt through ``PagedEngine`` (greedy); returns the
    (n, prompt_len + max_new) token array of the second, warm call."""
    import jax
    import numpy as np

    from repro.models.layers import use_packed_backend
    from repro.serving import PagedConfig, PagedEngine, SamplerConfig

    n, s = prompts.shape
    pages = -(-(s + max_new) // block_size)
    engine = PagedEngine(
        params, cfg,
        PagedConfig(block_size=block_size, num_blocks=n * pages,
                    max_concurrency=n, max_pages_per_seq=pages,
                    kv_dtype=kv_dtype, attn_impl=attn_impl),
        SamplerConfig(temperature=0.0, seed=seed), mesh=mesh)
    with use_packed_backend(backend):
        t0 = time.perf_counter()
        first = engine.generate(prompts, max_new)
        t1 = time.perf_counter()
        out = engine.generate(prompts, max_new)
        t2 = time.perf_counter()
    out = np.asarray(out)
    assert out.shape == (n, s + max_new), out.shape
    assert np.array_equal(out[:, :s], prompts)
    assert ((out >= 0) & (out < cfg.vocab)).all()
    assert np.array_equal(np.asarray(first), out), "repeat run diverged"
    tag = f"kv={kv_dtype} backend={backend} attn={attn_impl}"
    if mesh is not None:
        tag += f" mesh={dict(mesh.shape)}"
    say(f"served {n} requests ({s} prompt + {max_new} new tokens) {tag}: "
        f"first call {t1 - t0:.2f}s incl. compile, warm call {t2 - t1:.3f}s "
        f"= {n * max_new / (t2 - t1):.1f} tok/s (not a benchmark)")
    for i, row in enumerate(out[:, s:]):
        say(f"  stream {i}: {row.tolist()}")
    jax.block_until_ready(engine.cache)
    return out, engine


def teacher_forced_logits(params, cfg, tokens, prompt_len: int, *,
                          kv_dtype: str, paths, block_size: int):
    """Logits of the served programs on fixed tokens, for each
    ``(packed backend, attention impl)`` in ``paths``: the prefill forward
    over the prompt (the admit program's model call), and the paged decode
    step (the decode chunk's model call) fed the prompt and continuation
    one token at a time over a page pool of its own. The prompt's first
    ``prompt_len - 1`` tokens are replayed once on ``paths[0]``; every path
    then decodes the last prompt token and the continuation from that same
    cache. Returns, per path, (prefill logits (n, prompt_len, V), decode
    logits (n, T + 1, V))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.layers import use_packed_backend
    from repro.models.transformer import (
        decode_step_paged,
        init_paged_cache,
        prefill,
    )

    n, total = tokens.shape
    pages = -(-total // block_size)
    cache = init_paged_cache(
        cfg, n, n * pages, block_size, pages,
        kv_dtype="int8" if kv_dtype == "int8" else None)
    cache["block_table"] = jnp.arange(n * pages, dtype=jnp.int32).reshape(
        n, pages)
    cache["active"] = jnp.ones((n,), bool)
    toks = jnp.asarray(tokens)

    # the packed backend is read at trace time: one fresh jit per path
    def prefiller():
        return jax.jit(lambda p, t: prefill(p, {"tokens": t}, cfg, total)[0])

    def stepper(attn_impl):
        return jax.jit(lambda p, t, c: decode_step_paged(
            p, t, c, cfg, attn_impl=attn_impl))

    backend0, attn0 = paths[0]
    with use_packed_backend(backend0):
        step = stepper(attn0)
        for i in range(prompt_len - 1):
            _, cache = step(params, toks[:, i:i + 1], cache)
    out = []
    for backend, attn_impl in paths:
        with use_packed_backend(backend):
            pre_logits = np.asarray(
                prefiller()(params, toks[:, :prompt_len]), np.float32)
            step, c, dec = stepper(attn_impl), cache, []
            for i in range(prompt_len - 1, total):
                logits, c = step(params, toks[:, i:i + 1], c)
                dec.append(np.asarray(logits[:, -1], np.float32))
        out.append((pre_logits, np.stack(dec, axis=1)))
    return out


def _compare_logits(label: str, a, b, tol: float) -> float:
    import numpy as np

    assert np.isfinite(a).all() and a.shape == b.shape, (label, a.shape)
    rel = _rel_l2(a, b)
    agree = float((a.argmax(-1) == b.argmax(-1)).mean())
    say(f"logits {label} {tuple(a.shape)}: rel_l2={rel:.3e} (tol {tol:g}) "
        f"max|diff|={float(np.abs(a - b).max()):.4f} "
        f"argmax agreement {agree:.3f} (not gated)")
    assert rel <= tol, (label, rel)
    return rel


def main_path_phase(cfg, arch: str, out_dir: str, *, backend: str,
                    attn_impl: str, n_requests: int = 8, prompt_len: int = 128,
                    max_new: int = 32, teacher_steps: int = 4,
                    block_size: int = 64, seed: int = 0,
                    quantize_kw: dict | None = None) -> dict:
    """(c): quantize -> artifact -> load -> serve over float and int8 KV
    pages on ``backend``/``attn_impl``, then teacher-forced logits of that
    path against (1) the same kernels run by the Pallas interpreter, which
    executes the kernel bodies as plain XLA ops, and (2) the dequant backend
    with reference attention."""
    artifact = quantize_phase(cfg, arch, out_dir, seed=seed,
                              **(quantize_kw or {}))
    params = load_phase(cfg, artifact, seed=seed)
    prompts = _prompts(cfg, n_requests, prompt_len, seed)
    result = {}
    for kv in ("act", "int8"):
        out, _ = serve_phase(params, cfg, prompts, max_new, kv_dtype=kv,
                             backend=backend, attn_impl=attn_impl,
                             block_size=block_size, seed=seed)
        tokens = out[:, :prompt_len + teacher_steps]
        served, interp, ref = teacher_forced_logits(
            params, cfg, tokens, prompt_len, kv_dtype=kv, block_size=block_size,
            paths=((backend, attn_impl), ("interpret", "interpret"),
                   ("dequant", "ref")))
        for i, phase in enumerate(("prefill", "decode")):
            result[f"{kv}/{phase}/interpret"] = _compare_logits(
                f"kv={kv} {phase} vs interpreted kernels", served[i],
                interp[i], LOGITS_INTERPRET_TOL)
            result[f"{kv}/{phase}/dequant"] = _compare_logits(
                f"kv={kv} {phase} vs dequant+ref", served[i], ref[i],
                LOGITS_REL_TOL)
    return result


# ---------------------------------------------------------------------------
# (d) four chips: mesh serving against the one-device engine
# ---------------------------------------------------------------------------
def four_chip_phase(cfg, arch: str, out_dir: str, *, n_requests: int = 4,
                    prompt_len: int = 64, max_new: int = 16,
                    block_size: int = 64, seed: int = 0) -> dict:
    """Serve one RTN artifact through ``PagedEngine(mesh=...)`` at (1, 4)
    and (4, 1) and through the one-device engine in the same process;
    greedy streams must be byte-equal. The Pallas kernels are one-device
    programs, so every engine here runs the dequant backend with reference
    attention (the mesh engine refuses the attention kernel)."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh

    artifact = quantize_phase(cfg, arch, out_dir, seed=seed, seq=64,
                              calib_batches=1, calib_batch_size=2,
                              eval_batches=1, algorithm="rtn")
    params = load_phase(cfg, artifact, seed=seed)
    prompts = _prompts(cfg, n_requests, prompt_len, seed)
    kw = dict(kv_dtype="act", backend="dequant", attn_impl="ref",
              block_size=block_size, seed=seed)
    base, _ = serve_phase(params, cfg, prompts, max_new, **kw)
    equal = {}
    for shape in ((1, 4), (4, 1)):
        mesh = make_mesh(shape)
        out, engine = serve_phase(params, cfg, prompts, max_new, mesh=mesh,
                                  **kw)
        pool = engine.cache["pools"][0]
        for leaf in ("k_pages", "v_pages"):
            sh = pool[leaf].sharding
            n_dev = len(sh.device_set)
            say(f"mesh {shape}: pools[0].{leaf} spec={sh.spec} over "
                f"{n_dev} devices, shard shape "
                f"{sh.shard_shape(pool[leaf].shape)}")
            assert n_dev == 4, (shape, leaf, n_dev)
        wq = engine.params["layers"][0]["mixer"]["wq"]["packed"].sharding
        say(f"mesh {shape}: layers[0].mixer.wq.packed spec={wq.spec}")
        equal[str(shape)] = bool(np.array_equal(out, base))
        diverged = [i for i in range(len(out))
                    if not np.array_equal(out[i], base[i])]
        say(f"mesh {shape}: greedy streams byte-equal to the one-device "
            f"engine: {equal[str(shape)]} (rows that diverge: {diverged})")
    jax.block_until_ready(engine.cache)
    assert all(equal.values()), equal
    return equal


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh-serving phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the request data")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] FAIL: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"[chip_smoke] FAIL: {want} chips needed, {len(devices)} "
              f"visible", file=sys.stderr)
        return 1

    from repro.compile_cache import setup_compile_cache
    from repro.configs import get_config
    from repro.models.layers import packed_backend, resolve_paged_attn_impl

    say(f"devices: {len(devices)} x {devices[0].device_kind}; jax "
        f"{jax.__version__}; compile cache {setup_compile_cache()}")
    t_start = time.perf_counter()
    if args.four_chips:
        arch = "phi4-mini-3.8b"
        cfg = replace(get_config(arch), n_layers=PHI4_LAYERS)
        say(f"(d) {arch} at full width, {cfg.n_layers} layers")
        four_chip_phase(cfg, arch, os.path.join(OUT, "four_chips"),
                        seed=args.seed)
    else:
        packed, attn = packed_backend(), resolve_paged_attn_impl("auto")
        say(f"(a) resolved backends: packed={packed} attention={attn}")
        assert (packed, attn) == ("kernel", "kernel"), (packed, attn)
        say("(b) kernels at SmolLM-360M widths")
        kernel_phase(seed=args.seed)
        arch = "smollm-360m"
        cfg = replace(get_config(arch), n_layers=SMOLLM_LAYERS)
        say(f"(c) {arch} at full width, {cfg.n_layers} layers")
        main_path_phase(cfg, arch, os.path.join(OUT, "main_path"),
                        backend=packed, attn_impl=attn, seed=args.seed)
        say(f"peak_bytes_in_use={_peak_bytes()} (not a benchmark)")
    say(f"all phases passed in {time.perf_counter() - t_start:.1f}s "
        f"(not a benchmark)")
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
