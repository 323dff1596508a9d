"""Shared helpers of the benchmark's tests: the repository root on the
path, the cells and configurations as ``BENCHMARK.json`` lists them, and
tiny copies of each that the CPU can run."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import spec  # noqa: E402

# each configuration's tiny stand-in, tiny/<config>.json: the sizes its
# architecture's ``tiny`` cuts it to
TINY = Path(__file__).resolve().parent / "tiny"
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
CONFIGS = [c["name"] for c in spec.benchmark()["configs"]]


def tiny_conf(name: str) -> dict:
    conf = spec.config(name)
    return spec.arch(conf).tiny(conf, spec.load_json(TINY / f"{name}.json"))


def tiny_cell(name: str) -> tuple[dict, dict]:
    cell = spec.cell(name)
    return ({**cell, "check": {**cell["check"], "images": 8}},
            tiny_conf(cell["config"]))


@pytest.fixture(scope="session")
def bench_json():
    return spec.benchmark()
