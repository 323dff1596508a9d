"""Readings that set a cell's ``logit_gap`` limit, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 3

For each seed: set up, serve the cell's traffic for ``--seconds``, then
compare the served masks with the reference (the lower reading) and, on
the control seeds, the control (the reference in int4) with the reference
on the same micro-batches (the upper reading).  One JSON line per seed,
then a summary line.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from chipbench import bench, spec

    cell = spec.cell(args.workload)
    conf = spec.config(cell["config"])
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 3
    lower, upper = [], []
    for seed in args.seeds:
        sv = bench.setup(conf, seed)
        win = bench.drive(sv.engine, sv.pool, cell["traffic"], args.seconds,
                          seed, sv.win_ref)
        bits = (8, 4) if seed in args.control_seeds else (8,)
        res = bench.verify(cell, conf, sv, win, seed, bits=bits)
        line = {"seed": seed, "images": len(res["picked"]),
                "program_gap": max(res["gaps"])}
        lower.append(line["program_gap"])
        if "control_gaps" in res:
            line["control_gap"] = max(res["control_gaps"])
            line["control_gap_min_image"] = min(res["control_gaps"])
            upper.append(line["control_gap"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(lower),
                      "upper": min(upper) if upper else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
