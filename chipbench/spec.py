"""Where the benchmark's data lives, found by name.

A cell is ``workloads/<cell>.json``, a configuration ``configs/<name>.json``
and a metric a reader ``metrics/<name>.py`` with ``read(ctx)``.  Adding one
is adding files: nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    return load_json(HERE / "workloads" / f"{_checked(name)}.json")


def config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{_checked(name)}.json")


def metrics_for(bench: dict, cell_name: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics (untraced run) or per-layer metrics
    (traced run): every entry whose ``workloads`` names the cell, or that
    has no ``workloads`` key."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if cell_name in m.get("workloads", [cell_name])]


def reader(name: str):
    """``read(ctx) -> float | None`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{_checked(name)}.py"
    modname = "chipbench_metric_" + re.sub(r"\W", "_", name)
    mod_spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"chipbench/peaks.json (known: {sorted(table)})"
        )
    return table[device_kind]
