"""Benchmark harness — one section per paper table/claim.

    PYTHONPATH=src python -m benchmarks.run \
        [--section table1|kernels|roofline|msdf|precision|segserve|autotune|gateway|replay|fabric|capacity|energy|specdecode]

Prints ``name,us_per_call,derived`` CSV rows.  The segserve, autotune,
gateway, fabric and specdecode sections also write machine-readable
``BENCH_segserve.json`` / ``BENCH_autotune.json`` /
``BENCH_gateway.json`` / ``BENCH_fabric.json`` /
``BENCH_specdecode.json`` for the bench tracker
(``scripts/bench_diff.py`` diffs them across revisions).  ``replay`` is
the open-loop trace-replay bench — an alias for the gateway section,
which replays the committed canonical trace ``traces/gateway_burst.json``
through ``repro.workload.replay``.  ``fabric`` replays the scaled
``gateway_burst_x10``/``_x100`` traces through a single modeled gateway
and an N-shard sharded fabric (``repro.serve.Fabric``) and gates
scale-out p99 behavior plus exact fleet-ledger additivity.  ``capacity``
is the SLO-driven fleet capacity planner: it streams a diurnal workload
over a shard x router x policy x plan grid of modeled fabrics and writes
the cost-per-SLO frontier to ``BENCH_capacity.json``.  ``energy`` meters
the same workload with the joule-exact :class:`repro.obs.energy`
telemetry (plan x policy x shard grid) and writes the metered GOPS/W and
energy-per-request frontier to ``BENCH_energy.json``.
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache


def msdf_rows():
    """Cycle-count claims from the MSDF simulator (paper Sec. 3.2)."""
    import numpy as np

    from repro.core.msdf import MMAUnit, kpb_inner_product
    from repro.core import cycle_model as cm

    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, 32).astype(np.uint8)
    w = rng.integers(-128, 128, 32)
    unit = MMAUnit(w, t_n=32)
    t0 = time.perf_counter()
    _, cycles = unit.run(a)
    dt = time.perf_counter() - t0
    rows = [("msdf/mma_unit_sim", dt * 1e6,
             f"cycles={cycles};relation2_inner={cm.mma_tile_cycles()};"
             f"cascaded={cm.cascaded_tile_cycles()}")]
    a9 = rng.integers(0, 256, (9, 32)).astype(np.uint8)
    w9 = rng.integers(-128, 128, (9, 32))
    t0 = time.perf_counter()
    _, kcyc = kpb_inner_product(a9, w9)
    rows.append(("msdf/kpb_sim", (time.perf_counter() - t0) * 1e6,
                 f"cycles={kcyc};taps=9;t_n=32"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all")
    args = ap.parse_args()
    enable_compile_cache()

    rows: list[tuple[str, float, str]] = []
    if args.section in ("all", "msdf"):
        rows += msdf_rows()
    if args.section in ("all", "table1"):
        from benchmarks import table1

        rows += table1.run()
    if args.section in ("all", "kernels"):
        from benchmarks import kernels

        rows += kernels.run()
    if args.section in ("all", "roofline"):
        from benchmarks import roofline

        rows += roofline.run()
    if args.section in ("all", "precision"):
        from benchmarks import precision_sweep

        rows += precision_sweep.run()
    if args.section in ("all", "segserve"):
        from benchmarks import segserve

        rows += segserve.run()
    if args.section in ("all", "autotune"):
        from benchmarks import autotune

        rows += autotune.run()
    if args.section in ("all", "gateway", "replay"):
        from benchmarks import gateway

        rows += gateway.run()
    if args.section in ("all", "fabric"):
        from benchmarks import fabric

        rows += fabric.run()
    if args.section in ("all", "capacity"):
        from benchmarks import capacity

        rows += capacity.run()
    if args.section in ("all", "energy"):
        from benchmarks import energy

        rows += energy.run()
    if args.section in ("all", "specdecode"):
        from benchmarks import specdecode

        rows += specdecode.run()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
