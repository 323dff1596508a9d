"""Architectures, configurations and cells are found by name: a
configuration names its architecture's module, every configuration has a
CPU stand-in of its own, and adding an architecture, a configuration and a
cell adds files and ``BENCHMARK.json`` entries and changes no other file."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from conftest import CONFIGS, ROOT, TINY, tiny_conf

from chipbench import spec


def test_a_configuration_without_an_arch_is_an_error():
    conf = {k: v for k, v in spec.config(CONFIGS[0]).items() if k != "arch"}
    with pytest.raises(KeyError, match="arch"):
        spec.arch(conf)


@pytest.mark.parametrize("name", ["no_such_arch", "../geometry", "unet.py"])
def test_a_configuration_naming_a_missing_arch_is_an_error(name):
    conf = {**spec.config(CONFIGS[0]), "arch": name}
    with pytest.raises(ModuleNotFoundError, match="architecture"):
        spec.arch(conf)


@pytest.mark.parametrize("name", CONFIGS)
def test_each_configuration_has_a_tiny_stand_in(name):
    path = TINY / f"{name}.json"
    assert path.is_file(), f"configuration {name} has no CPU stand-in {path}"
    full, tiny = spec.config(name), tiny_conf(name)
    assert tiny["arch"] == full["arch"] and tiny["model"].keys() == full["model"].keys()
    assert tiny["image"][2:] == full["image"][2:]
    assert tiny["image"][0] * tiny["image"][1] < full["image"][0] * full["image"][1]


# one tiny forward of the reference, the weights, the tile plan and the
# layers of the configuration in argv[1], in a process that cannot import
# the program
_WITHOUT_THE_PROGRAM = """
import json, sys
sys.modules["repro"] = None
import numpy as np
from chipbench import geometry, spec
conf = json.loads(sys.argv[1])
arch = spec.arch(conf)
h, w, c = conf["image"]
params = arch.make_params(conf["model"], 2**33 + 11)
image = np.random.default_rng(3).standard_normal((h, w, c)).astype(np.float32)
cv = arch.canvas(image, conf)
t = arch.plan(h, w, conf)[0]
x = cv[None, t.y0:t.y1, t.x0:t.x1]
planes = np.asarray(geometry.class_planes(conf["plane_schedule"], 0), np.int32)
for bits in (8, 4):
    y = np.asarray(arch.forward(params, x, planes, bits=bits))
    assert y.shape == x.shape[:3] + (conf["model"]["n_classes"],), y.shape
    assert np.isfinite(y).all() and np.abs(y).max() > 0
assert geometry.model_ops(arch, conf["model"], h, w) > 0
assert not [m for m in sys.modules if m.split(".")[0] == "repro" and sys.modules[m]]
print("reference ran without the program")
"""


@pytest.mark.parametrize("name", CONFIGS)
def test_the_reference_runs_without_the_program(name):
    """The architecture's weights, reference, tile plan and layers run in
    a process where ``import repro`` fails: ``correct`` compares the
    program with code that takes nothing from it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    r = subprocess.run([sys.executable, "-c", _WITHOUT_THE_PROGRAM,
                        json.dumps(tiny_conf(name))],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "reference ran without the program" in r.stdout


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_an_arch_a_config_and_a_cell_are_added_as_files_alone(tmp_path):
    """In a copy of the benchmark: a renamed copy of the first cell's
    architecture module, a configuration that names it, a cell of that
    configuration, its tiny stand-in and the ``BENCHMARK.json`` entries.
    The contract test and the new cell's CPU rehearsal pass in the copy,
    and no file that was there before changes but ``BENCHMARK.json``."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec.benchmark()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    b = spec.benchmark()
    first = b["workloads"][0]
    entry = next(c for c in b["configs"] if c["name"] == first["config"])
    conf = spec.config(entry["name"])
    bench_dir = tmp_path / "chipbench"
    shutil.copy(bench_dir / "archs" / f"{conf['arch']}.py",
                bench_dir / "archs" / "rehearsal_arch.py")
    name, cell = "rehearsal_conf", "rehearsal_conf." + first["traffic"]
    (bench_dir / "configs" / f"{name}.json").write_text(json.dumps(
        {**conf, "name": name, "arch": "rehearsal_arch"}))
    (bench_dir / "workloads" / f"{cell}.json").write_text(json.dumps(
        {**spec.cell(first["name"]), "config": name}))
    shutil.copy(TINY / f"{entry['name']}.json",
                tmp_path / "tests" / "chipbench" / "tiny" / f"{name}.json")
    b["configs"].append({**entry, "name": name,
                         "file": f"chipbench/configs/{name}.json"})
    b["workloads"].append({**first, "name": cell, "config": name})
    for m in b["end_to_end"] + b["per_layer"]:
        if first["name"] in m.get("workloads", []):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b, indent=2))

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "tests/chipbench/test_chipbench_run.py",
         "tests/chipbench/test_chipbench_archs.py",
         "-k", f"contract or {name}"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    # the contract, the new cell's rehearsal, the new config's stand-in and
    # its reference without the program
    assert "4 passed" in r.stdout, r.stdout[-2000:]
    after = _digests(tmp_path)
    changed = {p for p, d in before.items() if after.get(p) != d}
    assert changed == {Path("BENCHMARK.json")}
