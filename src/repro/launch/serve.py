"""Serving launcher: batched generation from a (possibly resumed) checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch tiny-lm-s \
        --ckpt-dir /tmp/run1 --batch 8 --prompt-len 32 --max-new 64

``--packed`` packs the weights to the int4 serving artifact first (RTN,
dynamic activation quantization); ``--artifact DIR`` instead loads a
calibrated AXE artifact written by ``repro.launch.quantize --out`` — the
versioned schema carrying per-site DatapathSpecs and *static* activation
quantizers, so the served datapath is exactly what calibration certified.
``--packed-backend`` selects the packed-matmul datapath (auto = fused W4A8
kernel on TPU, in-graph dequant elsewhere; interpret = kernel path in
pallas interpret mode, for validation). ``--host-loop`` uses the per-token
host reference loop instead of the fused on-device generation loop.

``--paged`` serves through the paged-KV continuous-batching engine
(``repro.serving.PagedEngine``) instead of the fixed-slot engine:
``--block-size`` sets the KV page granularity, ``--max-concurrency`` the
engine slot count, ``--num-blocks`` the shared page-pool size (defaults to
enough pages for a full-length batch at ``--max-concurrency``).
``--admit-window`` / ``--admit-batch`` / ``--prefill-chunk`` /
``--watermark LOW HIGH`` switch the engine into the throughput scheduler
(windowed priority admission, batched cold prefill, chunked long-prompt
prefill, watermark reservation with preempt-and-requeue) — token streams
stay bit-identical to the default FIFO loop. See
docs/serving_scheduler.md.

``--profile DIR`` records the timed generation with ``jax.profiler`` into
DIR (the paged engine's ``serve/*`` host spans beside the device ops; see
docs/serving.md, "Tracing and counters"). After the tokens/s line the
paged engine prints what its counters saw over that generation.

``--mesh dp,tp`` serves the paged engine SPMD over a (data, model) mesh —
kv-head-sharded pools, replicated admin leaves, fully-replicated host
reads; token streams are bit-identical to the single-device engine. Under
``jax.distributed`` the same flag spans every process (docs/multihost.md;
``scripts/run_multiprocess.py`` drives the multi-process battery).
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import setup_compile_cache
from repro.configs import get_config, get_smoke
from repro.data import DataConfig, TokenBatcher
from repro.models.layers import use_packed_backend
from repro.models.transformer import init_model
from repro.quant.serve_packed import (
    load_flat_artifact,
    pack_decode_params,
    packed_params_from_artifact,
)
from repro.quant.spec import tree_datapath_fingerprint
from repro.serving import GenerationEngine, PagedConfig, PagedEngine, SamplerConfig
from repro.serving.metrics import ServeCounters


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--packed", action="store_true",
                    help="serve from the packed-int4 W4A8 artifact")
    ap.add_argument("--artifact", type=str, default=None,
                    help="directory of a calibrated AXE artifact "
                         "(repro.launch.quantize --out); loads packed codes "
                         "+ per-site DatapathSpecs + static act quantizers")
    ap.add_argument("--packed-backend", type=str, default="auto",
                    choices=("auto", "dequant", "kernel", "interpret"))
    ap.add_argument("--host-loop", action="store_true",
                    help="per-token host loop instead of the fused device loop")
    ap.add_argument("--paged", action="store_true",
                    help="paged-KV continuous-batching engine")
    ap.add_argument("--block-size", type=int, default=64,
                    help="KV page size in tokens (--paged)")
    ap.add_argument("--max-concurrency", type=int, default=8,
                    help="engine slots for continuous batching (--paged)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV page-pool size (--paged); default fits "
                         "--max-concurrency full-length sequences")
    ap.add_argument("--kv-dtype", type=str, default="act",
                    choices=("act", "int8"),
                    help="KV page element type (--paged): act keeps the "
                         "model act_dtype; int8 stores quantized pages + "
                         "per-page scales (pool HBM ~halves, attention "
                         "serves the AttnDatapathSpec integer datapath)")
    ap.add_argument("--kv-hbm-mb", type=float, default=None,
                    help="size the page pool to an HBM budget (MB) instead "
                         "of --num-blocks — at int8 the same budget holds "
                         "~2x the pages, so admission capacity ~doubles")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share full prompt blocks across requests "
                         "(--paged, attention-only patterns): repeated "
                         "prefixes prefill only their uncached suffix; "
                         "pages are refcounted with LRU eviction")
    ap.add_argument("--admit-window", type=int, default=1,
                    help="queued requests one admission pass may examine "
                         "(--paged; >1 lets urgent classes jump the line)")
    ap.add_argument("--admit-batch", type=int, default=1,
                    help="max cold arrivals co-admitted through one padded "
                         "multi-row prefill program (--paged)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill long prompts in page-aligned chunks of "
                         "at most this many tokens, interleaved with decode "
                         "(--paged; must be a multiple of --block-size)")
    ap.add_argument("--watermark", type=int, nargs=2, default=None,
                    metavar=("LOW", "HIGH"),
                    help="free-page watermarks (--paged): admit against a "
                         "LOW-page reserve instead of each request's worst "
                         "case; decode growth preempts-and-requeues on "
                         "exhaustion, and after a preemption fresh arrivals "
                         "wait for HIGH free pages (hysteresis)")
    ap.add_argument("--plan", type=str, default=None,
                    help="mixed-precision plan.json (repro.launch.search "
                         "--out): validates the artifact's per-site "
                         "datapaths against the plan and, with --paged "
                         "--kv-dtype int8, threads the plan's calibrated "
                         "static KV page scales into the engine")
    ap.add_argument("--mesh", type=str, default=None, metavar="DP,TP",
                    help="serve SPMD over a (data, model) mesh (--paged): "
                         "'dp,tp' whose product equals the global device "
                         "count, or 'auto' for all devices data-parallel. "
                         "Pools shard kv_heads, admin leaves replicate "
                         "(docs/multihost.md)")
    ap.add_argument("--profile", type=str, default=None, metavar="DIR",
                    help="trace the timed generation with jax.profiler "
                         "into DIR (open it with TensorBoard's profile "
                         "plugin or xprof)")
    ap.add_argument("--observe", action="store_true",
                    help="attach serving saturation counters (--paged): "
                         "static-quantizer clip counts + per-site/per-head "
                         "accumulator watermarks, reported after "
                         "generation; the decode jaxpr gains only debug "
                         "callbacks (structurally asserted)")
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    params = init_model(jax.random.key(args.seed), cfg)
    if args.ckpt_dir:
        restored = CheckpointManager(args.ckpt_dir).restore_latest({"params": params})
        if restored is not None:
            _, tree, _ = restored
            params = tree["params"]
            print(f"[serve] restored step {restored[0]}")
    if args.artifact:
        flat, meta = load_flat_artifact(args.artifact)
        params = packed_params_from_artifact(flat, params, cfg, meta=meta)
        print(f"[serve] loaded artifact v{meta.get('artifact_version')} "
              f"datapath={tree_datapath_fingerprint(params)} "
              f"({meta.get('datapath', '?')})")
    elif args.packed:
        params = pack_decode_params(params, cfg)
        print("[serve] packed int4 serving params (RTN fallback, dynamic act)")

    plan = None
    if args.plan:
        from repro.quant.observe import MixedPrecisionPlan
        from repro.quant.serve_packed import plan_expected_specs
        from repro.quant.spec import DatapathSpec, validate_datapath

        if not args.artifact:
            raise SystemExit("--plan validates a calibrated artifact's "
                             "per-site datapaths (add --artifact DIR)")
        plan = MixedPrecisionPlan.load(args.plan)
        base_d = plan.meta.get("base_spec")
        if base_d is None:
            raise SystemExit(f"{args.plan} carries no base_spec meta — "
                             f"re-export with repro.launch.search")
        n = validate_datapath(
            params, plan_expected_specs(cfg, plan, DatapathSpec(**base_d)))
        print(f"[serve] plan validated: {n} per-site datapaths match "
              f"({len(plan.sites)} searched, kv={'static' if plan.kv else 'dynamic'})")

    data = TokenBatcher(
        DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                   global_batch=args.batch, seed=args.seed)
    )
    prompts = np.asarray(data.batch(0)["tokens"])
    sampler = SamplerConfig(temperature=args.temperature, seed=args.seed)
    if not args.paged and (args.kv_dtype != "act" or args.kv_hbm_mb is not None
                           or args.prefix_cache):
        raise SystemExit("--kv-dtype/--kv-hbm-mb/--prefix-cache apply to the "
                         "paged engine only (add --paged)")
    sched_flags = (args.admit_window != 1 or args.admit_batch != 1
                   or args.prefill_chunk is not None
                   or args.watermark is not None)
    if not args.paged and sched_flags:
        raise SystemExit("--admit-window/--admit-batch/--prefill-chunk/"
                         "--watermark apply to the paged engine only "
                         "(add --paged)")
    if args.observe and not args.paged:
        raise SystemExit("--observe applies to the paged engine only "
                         "(add --paged)")
    if args.mesh is not None and not args.paged:
        raise SystemExit("--mesh applies to the paged engine only "
                         "(add --paged)")
    if args.paged:
        if args.host_loop:
            raise SystemExit("--host-loop applies to the fixed-slot engine only")
        from repro.serving.scheduler import blocks_for_budget, kv_pool_bytes

        pages_per_seq = -(-(args.prompt_len + args.max_new - 1) // args.block_size)
        if args.kv_hbm_mb is not None and args.num_blocks is not None:
            raise SystemExit("--num-blocks and --kv-hbm-mb both size the "
                             "page pool — pass one, not both")
        if args.kv_hbm_mb is not None:
            num_blocks = blocks_for_budget(
                int(args.kv_hbm_mb * 2**20), cfg, args.block_size,
                args.kv_dtype)
            if num_blocks < pages_per_seq:
                raise SystemExit(
                    f"--kv-hbm-mb {args.kv_hbm_mb} affords {num_blocks} "
                    f"pages < the {pages_per_seq} one request needs")
        else:
            num_blocks = args.num_blocks or args.max_concurrency * pages_per_seq
        from repro.serving import SchedulerPolicy

        try:
            policy = SchedulerPolicy(
                admit_window=args.admit_window, batch_max=args.admit_batch,
                prefill_chunk=args.prefill_chunk,
                watermark=tuple(args.watermark) if args.watermark else None)
        except ValueError as e:
            raise SystemExit(f"scheduler policy: {e}") from None
        mesh = None
        if args.mesh is not None:
            from repro.launch.mesh import parse_mesh_spec

            mesh = parse_mesh_spec(args.mesh)
            print(f"[serve] mesh: {dict(mesh.shape)} over "
                  f"{len(mesh.devices.flat)} devices "
                  f"({jax.process_count()} process(es))")
        try:
            engine = PagedEngine(
                params, cfg,
                PagedConfig(block_size=args.block_size, num_blocks=num_blocks,
                            max_concurrency=args.max_concurrency,
                            kv_dtype=args.kv_dtype,
                            prefix_cache=args.prefix_cache, sched=policy),
                sampler,
                observe=args.observe,
                kv_scales=plan.kv if plan is not None else None,
                mesh=mesh,
            )
        except ValueError as e:
            raise SystemExit(f"paged engine: {e}") from None
        pool_mb = kv_pool_bytes(cfg, num_blocks, args.block_size,
                                args.kv_dtype) / 2**20
        attn_dp = (f" attn_datapath=[{engine.attn_spec.describe()}]"
                   if engine.attn_spec else "")
        pc = " prefix_cache=on" if args.prefix_cache else ""
        pol = ("" if policy.is_legacy else
               f" policy=(window={policy.admit_window} "
               f"batch={policy.batch_max} chunk={policy.prefill_chunk} "
               f"watermark={policy.watermark})")
        print(f"[serve] paged engine: block_size={args.block_size} "
              f"num_blocks={num_blocks} slots={args.max_concurrency} "
              f"kv_dtype={args.kv_dtype} pool={pool_mb:.2f}MB{pc}{pol}{attn_dp}")
        gen = engine.generate
    else:
        engine = GenerationEngine(params, cfg, sampler)
        gen = engine.generate_host_loop if args.host_loop else engine.generate
    backend_ctx = (
        use_packed_backend(args.packed_backend)
        if args.packed_backend != "auto"
        else contextlib.nullcontext()
    )
    profile = (jax.profiler.trace(args.profile) if args.profile
               else contextlib.nullcontext())
    with backend_ctx:
        gen(prompts, args.max_new)  # warm the jit bucket outside the timed region
        if args.paged:
            before = engine.counters.snapshot()
        with profile:
            t0 = time.time()
            out = gen(prompts, args.max_new)
            dt = time.time() - t0
    n_new = out.shape[1] - prompts.shape[1]
    loop = "paged" if args.paged else ("host-loop" if args.host_loop else "fused")
    print(f"[serve] batch={args.batch} new_tokens={n_new} {loop} "
          f"{dt:.2f}s  {args.batch * n_new / dt:.1f} tok/s")
    if args.paged:
        after = engine.counters.snapshot()
        print("[serve] counters:", ServeCounters.summary(
            {k: after[k] - before[k] for k in after}))
    if args.profile:
        print(f"[serve] trace written under {args.profile}")
    print("[serve] sample:", out[0, -min(16, out.shape[1]):].tolist())
    if args.observe:
        import json as _json

        engine.assert_observation_transparent()
        rep = engine.saturation_report()
        worst = None
        for name, sec in rep["sites"].items():
            h = sec.get("headroom_bits_observed")
            if h is not None and (worst is None or h < worst[1]):
                worst = (name, h)
        print(f"[serve] observed {len(rep['sites'])} sites; "
              f"binding watermark: "
              f"{worst[0] if worst else '-'}"
              f"{f' ({worst[1]:.2f} headroom bits)' if worst else ''}")
        print("[serve] saturation report:",
              _json.dumps(rep, indent=2, default=float))
    return out


if __name__ == "__main__":
    main()
