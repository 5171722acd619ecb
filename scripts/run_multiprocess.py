#!/usr/bin/env python3
"""Multi-process CPU-mesh determinism battery for the paged engine.

    python scripts/run_multiprocess.py --procs 2 --devices-per-proc 2

The parent spawns ``--procs`` worker copies of this script, each a real
OS process with its own jax runtime: workers set
``XLA_FLAGS=--xla_force_host_platform_device_count=K`` *before* importing
jax, select the gloo CPU collectives backend, and rendezvous through
``jax.distributed.initialize`` — so the (procs * K)-device global mesh
runs genuine cross-process psum/all-gather collectives, not fake
single-process sharding.

Each worker then runs the battery:

1. serve a mixed trace (mid-flight admission via the ``_late`` hook and
   a watermark preemption forced by a tight pool) through a local
   1-device reference engine AND through the global-mesh engine;
2. assert every token stream byte-equal between the two;
3. assert the final device ``free_list`` / ``page_refcounts`` byte-equal
   to the reference and to the host ``PoolState`` mirror's replay;
4. allgather a blake2b digest of (streams, free state) across processes
   and assert every process computed the identical bytes — the
   multi-controller contract of docs/multihost.md;
5. repeat (1-4) on the int8-KV + prefix-cache engine.

Exit code 0 only when every worker passes. CI runs this as the second
lane of the ``mesh`` job; locally it needs nothing but a free TCP port.

This is a CPU-only lane, not a chip launcher: the parent never imports
jax, and each worker forces the CPU platform. A chip belongs to one
process, so the chip smoke test (``chip_smoke.py``) drives every chip of
a host from one process instead.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys


def parent(args) -> int:
    env_base = dict(os.environ)
    env_base["JAX_PLATFORMS"] = "cpu"
    procs = []
    for pid in range(args.procs):
        env = dict(env_base)
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices_per_proc}"
        )
        env["REPRO_MP_ROLE"] = "worker"
        env["REPRO_MP_PROC"] = str(pid)
        env["REPRO_MP_NPROCS"] = str(args.procs)
        env["REPRO_MP_COORD"] = f"127.0.0.1:{args.port}"
        procs.append(subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ))
    rc = 0
    for pid, p in enumerate(procs):
        out, _ = p.communicate(timeout=args.timeout)
        status = "ok" if p.returncode == 0 else f"FAILED rc={p.returncode}"
        print(f"[run_multiprocess] worker {pid}: {status}")
        if p.returncode != 0 or args.verbose:
            print("\n".join(f"  [{pid}] {line}"
                            for line in out.splitlines()[-40:]))
        rc = rc or p.returncode
    print(f"[run_multiprocess] {'PASS' if rc == 0 else 'FAIL'}: "
          f"{args.procs} processes x {args.devices_per_proc} devices")
    return rc


def worker() -> int:
    # env (XLA_FLAGS included) was staged by the parent before exec — the
    # device count is locked in before jax ever imports
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=os.environ["REPRO_MP_COORD"],
        num_processes=int(os.environ["REPRO_MP_NPROCS"]),
        process_id=int(os.environ["REPRO_MP_PROC"]),
    )
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

    import hashlib

    import numpy as np
    from jax.experimental import multihost_utils

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_model
    from repro.runtime import sharding as shardlib
    from repro.serving import (
        PagedConfig,
        PagedEngine,
        Request,
        SamplerConfig,
        SchedulerPolicy,
    )

    pid = jax.process_index()
    n_dev = len(jax.devices())
    print(f"worker {pid}: {n_dev} global devices, "
          f"{len(jax.local_devices())} local")

    cfg = get_config("tiny-lm-xs").scaled(n_layers=2, vocab=128)
    params = init_model(jax.random.key(0), cfg)
    mesh = make_mesh((n_dev // 2, 2) if n_dev % 2 == 0 else (n_dev,))

    rng = np.random.default_rng(11)
    lens = [(8, 8, 0), (8, 6, 1), (16, 8, 0), (8, 12, 1), (24, 4, 0)]
    reqs = [Request(uid=u, prompt=rng.integers(0, 128, size=s).astype(np.int32),
                    max_new=m, priority=p)
            for u, (s, m, p) in enumerate(lens)]
    late = Request(uid=99, prompt=rng.integers(0, 128, size=8).astype(np.int32),
                   max_new=6)

    def battery(tag: str, reqs=reqs, ref_pc=None, min_preempt=0,
                **pc_extra):
        pc = dict(block_size=8, num_blocks=18, max_concurrency=3,
                  max_pages_per_seq=4, chunk_max=4, attn_impl="ref")
        pc.update(pc_extra)

        def trace(engine):
            injected = []

            def _late(sched, pass_idx):
                # deterministic mid-flight admission: keyed on the pass
                # index, never the wall clock — identical on every process
                if pass_idx == 1 and not injected:
                    sched.submit(Request(late.uid, late.prompt.copy(),
                                         late.max_new))
                    injected.append(True)

            return engine.serve([Request(r.uid, r.prompt.copy(), r.max_new,
                                         r.priority) for r in reqs],
                                _late=_late)

        ref = PagedEngine(params, cfg, PagedConfig(**(ref_pc or pc)),
                          SamplerConfig(temperature=0.0))
        want = trace(ref)
        eng = PagedEngine(params, cfg, PagedConfig(**pc),
                          SamplerConfig(temperature=0.0), mesh=mesh)
        got = trace(eng)
        if ref_pc is None:
            assert eng.preemptions == ref.preemptions
        assert eng.preemptions >= min_preempt, \
            f"{tag}: wanted >= {min_preempt} preemptions, saw {eng.preemptions}"
        for uid in want:
            np.testing.assert_array_equal(got[uid], want[uid])

        h = hashlib.blake2b(digest_size=16)
        for uid in sorted(got):
            h.update(np.asarray(got[uid], np.int32).tobytes())
        for leaf in ("free_list", "page_refcounts"):
            dev = np.asarray(shardlib.host_read(eng.cache[leaf]), np.int32)
            if ref_pc is None:  # same pool shape -> byte-equal free state
                np.testing.assert_array_equal(
                    dev, np.asarray(jax.device_get(ref.cache[leaf]), np.int32))
            h.update(dev.tobytes())
        # the host allocator mirror must have replayed the identical
        # pops/pushes (PoolState is the lockstep contract)
        np.testing.assert_array_equal(
            np.asarray(shardlib.host_read(eng.cache["free_list"])),
            eng.pool_state.free_list)
        h.update(eng.pool_state.digest().encode())
        eng.assert_sampling_keys_collective_safe()

        # every process must hold the identical bytes: allgather the
        # digest (itself a cross-process collective) and compare
        local = np.frombuffer(h.digest(), np.uint8)
        gathered = np.asarray(multihost_utils.process_allgather(local))
        for other in range(gathered.shape[0]):
            np.testing.assert_array_equal(
                gathered[other], gathered[0],
                err_msg=f"{tag}: process {other} diverged")
        print(f"worker {pid}: {tag} ok "
              f"(digest {h.hexdigest()}, preemptions={eng.preemptions})")

    # cold path + mid-flight admission under the throughput policy
    battery("float+throughput",
            sched=SchedulerPolicy(admit_window=4, batch_max=2,
                                  prefill_chunk=8, watermark=(3, 6)))
    # int8 pages + shared prefixes over the same trace
    battery("int8+prefix", kv_dtype="int8", prefix_cache=True)
    # watermark preemption: short prompts over-admitted against a tight
    # pool, decode growth exhausts it mid-flight -> preempt-and-requeue;
    # the reference runs the roomy FIFO pool (preemption must not change
    # one token)
    grow = [Request(uid=50 + u,
                    prompt=rng.integers(0, 128, size=8).astype(np.int32),
                    max_new=24, priority=p) for u, p in enumerate([0, 1, 1])]
    battery("watermark-preempt", reqs=grow, min_preempt=1,
            ref_pc=dict(block_size=8, num_blocks=16, max_concurrency=3,
                        max_pages_per_seq=4, chunk_max=4, attn_impl="ref"),
            num_blocks=6,
            sched=SchedulerPolicy(admit_window=2, watermark=(1, 4)))
    print(f"worker {pid}: PASS")
    return 0


def main(argv=None) -> int:
    if os.environ.get("REPRO_MP_ROLE") == "worker":
        return worker()
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--devices-per-proc", type=int, default=2)
    ap.add_argument("--port", type=int, default=29512)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.procs < 2:
        raise SystemExit("--procs must be >= 2 (that is the point)")
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
