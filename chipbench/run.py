"""Run one benchmark cell once on the TPU this process starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints informational lines and then each compared number beside its limit
on standard error, and one JSON object as the last line of standard
output.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from chipbench import bench, spec

    cell = spec.cell(args.workload)
    conf = spec.config(cell["config"])
    metric_list = spec.metrics_for(spec.benchmark(), args.workload,
                                   bool(args.trace))
    import repro  # noqa: F401  the system under test must be present

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"error: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    result = bench.run(args.workload, cell, conf, metric_list, seed=args.seed,
                       seconds=args.seconds, traced=bool(args.trace),
                       t_start=T_START)
    bench.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
