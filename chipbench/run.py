#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. ``BENCHMARK.json`` names the cell's
configuration (``chipbench/configs/<config>.json``) and traffic mix
(``chipbench/traffic/<traffic>.json``); ``chipbench/checks/<workload>.json``
holds the limits of its output check. A run:

1. refuses without a TPU, or with fewer chips than the cell asks for;
2. builds the weights on the device from ``--seed`` and the engine;
3. serves one request per prompt length, which compiles (or loads from
   the cache) every program the traffic can reach;
4. admits the closed loop's clients, their first requests cut to the
   loop's steady state, untimed; then measures ``--seconds`` of it
   through ``PagedEngine.serve``, each finished request replaced at once
   through the ``_late`` hook, and stops at the first pass past the close
   once the check has its sample;
5. compares a seeded sample of the finished requests, the longest among
   them, with the plain reference (``check.py``);
6. prints the result as the last line of standard output.

``--trace 1`` runs the same window under the profiler and reports the
per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
for p in (HERE.parent, HERE.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def process_start() -> float:
    """Wall-clock time this process began (Linux), else the import time."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def say(*a) -> None:
    print("[chipbench]", *a, file=sys.stderr, flush=True)


def load_cell(root: Path, workload: str) -> dict:
    """The cell's entry, configuration, mix and check limits, by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    base = root / "chipbench"
    return {
        "bench": bench, "cell": cell, "cfg": cfg,
        "mix": json.loads((base / "traffic" / f"{cell['traffic']}.json")
                          .read_text()),
        "limits": json.loads((base / "checks" / f"{workload}.json")
                             .read_text()),
    }


def setup_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``; every program is cached,
    however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = process_start()
    root = Path.cwd()
    if not (root / "BENCHMARK.json").is_file():
        root = HERE.parent
    cell = load_cell(root, args.workload)

    import jax

    devices = jax.devices()
    want = cell["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < want:
        say(f"FAIL: the cell needs {want} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s). Nothing run.")
        return 2
    say(f"compile cache {setup_compile_cache(root)}")

    from chipbench import harness

    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=t_start, root=root,
                         log=say)
    for name, c in result["check"].items():
        say(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
