"""Benchmark driver — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,table2,...]

Prints ``name,us_per_call,derived`` CSV. Set REPRO_BENCH_FAST=1 for the
reduced grids (CI).
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = ("pareto", "table1", "table2", "table3", "kernels", "roofline",
           "families", "decode", "datapath", "serving", "mesh_serving")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help=f"comma-separated subset of {BENCHES}")
    args = ap.parse_args(argv)
    selected = args.only.split(",") if args.only else list(BENCHES)
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        t0 = time.time()
        try:
            if name == "pareto":
                from . import bench_pareto

                bench_pareto.run()
            elif name == "table1":
                from . import bench_multistage

                bench_multistage.run()
            elif name == "table2":
                from . import bench_ablation

                bench_ablation.run()
            elif name == "table3":
                from . import bench_monolithic

                bench_monolithic.run()
            elif name == "kernels":
                from . import bench_kernels

                bench_kernels.run()
            elif name == "families":
                from . import bench_families

                bench_families.run()
            elif name == "decode":
                from . import bench_decode

                bench_decode.run()
            elif name == "datapath":
                from . import bench_datapath

                bench_datapath.run()
            elif name == "serving":
                from . import bench_serving

                bench_serving.run()
            elif name == "mesh_serving":
                from . import bench_mesh_serving

                bench_mesh_serving.run()
            elif name == "roofline":
                from . import bench_roofline

                bench_roofline.run()
            else:
                raise ValueError(f"unknown bench {name}")
            print(f"bench/{name}/wall,{(time.time() - t0) * 1e6:.0f},ok")
        except Exception as e:  # a failing table must not hide the others
            failures += 1
            traceback.print_exc()
            print(f"bench/{name}/wall,{(time.time() - t0) * 1e6:.0f},"
                  f"FAIL:{type(e).__name__}:{e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
