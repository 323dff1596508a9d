"""Each cell rehearsed end to end on the CPU at a tiny size, the result
line's shape, BENCHMARK.json against the benchmark's contract, and the
command's refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest
from conftest import CELLS, ROOT, tiny_cell

from chipbench import bench, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what every module under chipbench/archs/ gives (chipbench/archs/__init__.py)
ARCH_API = ("make_params", "make_engine", "forward", "canvas_shape", "canvas",
            "plan", "layers", "tiny")


def test_benchmark_json_keeps_to_the_contract(bench_json):
    b = bench_json
    assert list(b) == ["command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"]
    assert b["command"] == ["python3", "chipbench/run.py"]
    assert b["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= b["run_seconds"] <= 51
    assert b["configs"] and b["workloads"]
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and isinstance(c["reduced"], list)
        assert all(isinstance(k, str) and NAME.match(k) for k in c["reduced"])
        conf = spec.config(c["name"])
        assert conf["source"] == c["source"]
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        arch = spec.arch(conf)
        assert all(callable(getattr(arch, f, None)) for f in ARCH_API)
    assert len({c["name"] for c in b["configs"]}) == len(b["configs"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert e2e == {"images_per_s", "setup_s"}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        cell = spec.cell(w["name"])
        assert (cell["config"], cell["chips"], cell["why"]) == (
            w["config"], w["chips"], w["why"])
        assert w["traffic"] == cell["traffic"]["name"] and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        got_e2e = {m["name"] for m in spec.metrics_for(b, w["name"], False)}
        assert "setup_s" in got_e2e and len(got_e2e) >= 2
        assert spec.metrics_for(b, w["name"], True)
    assert len(set(CELLS)) == len(CELLS)
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(CELLS)
    assert {w["config"] for w in b["workloads"]} == {c["name"] for c in b["configs"]}
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert callable(spec.reader(m["name"]))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_rehearses_on_the_cpu(cell_name, bench_json, capsys):
    cell, conf = tiny_cell(cell_name)
    metric_list = spec.metrics_for(bench_json, cell_name, False)
    out = bench.run(cell_name, cell, conf, metric_list, seed=2**40 + 11,
                    seconds=1.0, traced=False, t_start=time.perf_counter())
    bench.emit(out)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {m["name"] for m in metric_list}
    for name, m in line["metrics"].items():
        # a loaded CPU may finish no tiny image inside the one-second window
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["value"] >= 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["checks"]["logit_gap"]["value"] <= line["checks"]["logit_gap"]["limit"]


def test_an_open_loop_mix_rehearses_on_the_cpu(bench_json):
    """A mix that is only data (open-loop arrivals, here in groups of two)
    runs through the same harness with no code of its own."""
    cell_name = CELLS[0]
    cell, conf = tiny_cell(cell_name)
    cell = {**cell, "traffic": {"name": "open_loop", "rate_per_s": 4.0, "group": 2}}
    out = bench.run(cell_name, cell, conf,
                    spec.metrics_for(bench_json, cell_name, False),
                    seed=2**32 + 9, seconds=1.0, traced=False,
                    t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 8
    assert out["checks"]["unfinished"]["value"] == 0


def _run_cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         CELLS[0], "--seed", str(2**33 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_command_refuses_the_cpu():
    r = _run_cli(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec.benchmark()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
