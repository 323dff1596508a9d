"""Shared helpers of the benchmark's tests: the repository root on the
path, and tiny copies of each cell that the CPU can run."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import geometry, spec  # noqa: E402

# each configuration's tiny stand-in: its depth, convs per stage, classes,
# channels, datapath and schedule kind, at widths and sizes a CPU can run
_TINY = {
    "unet48_brats240": {"base": 8, "image": [48, 48], "tile": 32, "pool": 5},
    "unet64_kits512": {"base": 4, "image": [64, 64], "tile": 56, "pool": 4},
}


def tiny_conf(name: str) -> dict:
    conf = spec.config(name)
    t = _TINY[name]
    m = {**conf["model"], "base": t["base"], "depth": 2}
    n_convs = 2 * m["depth"] * m["convs_per_stage"] + m["convs_per_stage"]
    return {**conf, "model": m, "plane_schedule": conf["plane_schedule"][:1] * n_convs,
            "tile": t["tile"], "halo": geometry.halo_for(2, m["convs_per_stage"]),
            "image": t["image"] + conf["image"][2:], "pool": t["pool"]}


def tiny_cell(name: str) -> tuple[dict, dict]:
    cell = spec.cell(name)
    return ({**cell, "check": {**cell["check"], "images": 8}},
            tiny_conf(cell["config"]))


@pytest.fixture(scope="session")
def bench_json():
    return spec.benchmark()
