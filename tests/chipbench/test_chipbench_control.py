"""The comparison that decides ``correct`` fails what it has to fail.

At a tiny size on the CPU: the control (the reference computed in int4 in
the program's place) reads above each cell's limit, and a run whose served
path is broken underneath (half of each micro-batch left out; one logit of
each micro-batch altered where it is produced) comes out not correct.
"""
import time

import jax.numpy as jnp
import pytest
from conftest import tiny_cell

from chipbench import bench, model, spec

# each cell's own traffic, and the first cell under an open-loop mix
CASES = {"unet48_brats240.backlog": None, "unet64_kits512.backlog": None,
         "unet48_brats240.open_loop": {"name": "open_loop", "rate_per_s": 32.0}}


@pytest.mark.parametrize("cell_name", ["unet48_brats240.backlog",
                                       "unet64_kits512.backlog"])
def test_the_int4_control_reads_above_the_limit(cell_name):
    cell, conf = tiny_cell(cell_name)
    sv = bench.setup(conf, 2**35 + 3)
    win = bench.drive(sv.engine, sv.pool, cell["traffic"], 1.0, 9, sv.win_ref)
    res = bench.verify(cell, conf, sv, win, 9, bits=(8, 4))
    assert max(res["gaps"]) <= cell["check"]["logit_gap"]
    assert min(res["control_gaps"]) > cell["check"]["logit_gap"]


def _half_batch(fwd):
    def broken(params, x, cfg):
        out = fwd(params, x, cfg)
        return out.at[out.shape[0] // 2:].set(0.0)
    return broken


def _altered(fwd):
    def broken(params, x, cfg):
        out = fwd(params, x, cfg)
        return out.at[0, 0, 0, 0].add(0.5 * jnp.max(jnp.abs(out)))
    return broken


@pytest.mark.parametrize("fault", [_half_batch, _altered],
                         ids=["half_batch_left_out", "answer_altered"])
@pytest.mark.parametrize("case", list(CASES))
def test_a_broken_served_path_is_not_correct(case, fault, monkeypatch,
                                             bench_json):
    make = model.make_engine

    def broken_engine(conf, params):
        engine = make(conf, params)
        engine._fwd = fault(engine._fwd)
        return engine

    monkeypatch.setattr(model, "make_engine", broken_engine)
    cell_name = case.rsplit(".", 1)[0] + ".backlog"
    cell, conf = tiny_cell(cell_name)
    if CASES[case]:
        cell = {**cell, "traffic": CASES[case]}
    out = bench.run(cell_name, cell, conf,
                    spec.metrics_for(bench_json, cell_name, False),
                    seed=2**31 + 17, seconds=0.5, traced=False,
                    t_start=time.perf_counter())
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]
