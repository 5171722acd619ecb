#!/usr/bin/env python3
"""Readings for a cell's check limits, on the chip, in one process.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \
        --seconds 5 [--lower act_bits=4 --lower kv_bits=4]

For each seed: weights from the seed, a short window of the cell's own
traffic through ``PagedEngine.serve`` (as a run drives it),
then the check's sample compared with the plain reference: the served
tokens (the program's reading, the limit's lower end) and the first
choices of the reference computed in a lower precision (the controls'
readings, the upper end: by default 4-bit activation codes and 4-bit KV
pages, each one step below the configuration's 8 bits); each as the mean
gap, the number compared, and the widest. One engine serves every seed; only
its weights change, so nothing compiles after the first seed. Prints one
JSON line per seed. The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE.parent, HERE.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--lower", action="append", default=[],
                    help="a lower precision, e.g. act_bits=4 or kv_bits=4")
    args = ap.parse_args(argv)

    import jax

    from chipbench import harness, recorder, weights
    from chipbench.run import load_cell, say, setup_compile_cache

    root = HERE.parent
    cell = load_cell(root, args.workload)
    cfg, mix = cell["cfg"], cell["mix"]
    if jax.devices()[0].platform != "tpu":
        say("FAIL: no TPU")
        return 2
    setup_compile_cache(root)
    lowers = [dict([kv.split("=")]) for kv in args.lower] or [
        {"act_bits": "4"}, {"kv_bits": "4"}]
    lowers = [{k: int(v) for k, v in lo.items()} for lo in lowers]
    engine = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        params = weights.packed_params(cfg, seed)
        if engine is None:
            engine = harness.engine_for(params, cfg, mix, seed)
            harness.warm(engine, mix, seed)
        else:
            harness.reset(engine)
            engine.params = params
        del params
        rec = recorder.Recorder()
        results, prompts, _, past, _ = harness.serve_window(
            engine, mix, seed, args.seconds, rec)
        row = {"seed": seed, "finished": len(results), "past_s": past}
        sample, g = harness.sample_gaps(cfg, mix, seed, results, prompts)
        row.update(sample=sample, tokens=int(g.size),
                   program_mean=float(g.mean()), program_max=float(g.max()))
        for lo in lowers:
            _, gc = harness.sample_gaps(cfg, mix, seed, results, prompts,
                                        **lo)
            tag = ",".join(f"{k}={v}" for k, v in lo.items())
            row[f"control[{tag}]_mean"] = float(gc.mean())
            row[f"control[{tag}]_max"] = float(gc.max())
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
