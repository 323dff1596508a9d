"""Fault tolerance: atomic checkpointing, async save, restart-resume,
elastic re-shard, retention GC, and crash-mid-save recovery."""
import json
import os
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import Checkpointer


def _state(step=0):
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) + step,
                   "b": jnp.ones((4,), jnp.bfloat16) * step},
        "step": jnp.int32(step),
    }


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    st = _state(7)
    ck.save(7, st)
    restored, step = ck.restore(jax.eval_shape(lambda: st))
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(st["params"]["w"]))
    assert restored["params"]["b"].dtype == jnp.bfloat16


def test_async_save_overlaps_and_completes(tmp_path):
    ck = Checkpointer(tmp_path)
    for s in (1, 2, 3):
        ck.save_async(s, _state(s))
    ck.wait()
    assert ck.latest_step() == 3


def test_latest_points_to_committed_only(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(5, _state(5))
    # simulate a crash mid-save: a stale .tmp dir must not be visible
    tmp_dir = tmp_path / "step_000000009.tmp"
    tmp_dir.mkdir()
    (tmp_dir / "leaf_00000.npy").write_bytes(b"garbage")
    assert ck.latest_step() == 5
    restored, step = ck.restore(jax.eval_shape(lambda: _state(0)))
    assert step == 5


def test_retention_gc(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in range(6):
        ck.save(s, _state(s))
    dirs = sorted(p.name for p in tmp_path.glob("step_*") if p.is_dir())
    assert len(dirs) == 2
    assert ck.latest_step() == 5


def test_elastic_restore_reshards(tmp_path):
    """Restore under a different device layout (1 device here, but through
    explicit NamedShardings — the mechanism the multi-pod restart uses)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ck = Checkpointer(tmp_path)
    st = _state(1)
    ck.save(1, st)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), jax.eval_shape(lambda: st))
    restored, _ = ck.restore(jax.eval_shape(lambda: st), shardings=sh)
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())


def test_trainer_restart_is_bit_deterministic(tmp_path):
    """Kill-and-resume must reproduce the uninterrupted run exactly: the data
    pipeline is step-indexed and the checkpoint carries the full state."""
    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataConfig
    from repro.models import build
    from repro.optim import adamw
    from repro.train import train_step as ts
    from repro.train import trainer

    cfg = get_smoke_config("yi_6b")
    mod = build(cfg)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    state0 = {"params": params, "opt": adamw.init(params)}
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4, seed=11)
    step_fn = jax.jit(lambda st, b: ts.train_step(st, b, cfg))
    tc = trainer.TrainerConfig(total_steps=6, ckpt_every=3, log_every=100,
                               ckpt_dir=str(tmp_path / "ck"))

    # uninterrupted run
    final_a, _ = trainer.train(jax.tree.map(jnp.copy, state0), step_fn, dcfg, tc,
                               log=lambda *a: None)

    # interrupted run: stop at 3, resume from checkpoint
    shutil.rmtree(tmp_path / "ck")
    tc_half = trainer.TrainerConfig(total_steps=3, ckpt_every=3, log_every=100,
                                    ckpt_dir=str(tmp_path / "ck"))
    trainer.train(jax.tree.map(jnp.copy, state0), step_fn, dcfg, tc_half,
                  log=lambda *a: None)
    resumed, start = trainer.resume(jax.eval_shape(lambda: state0), tc)
    assert start == 3
    final_b, _ = trainer.train(resumed, step_fn, dcfg, tc, start_step=start,
                               log=lambda *a: None)

    for a, b in zip(jax.tree.leaves(final_a["params"]), jax.tree.leaves(final_b["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_detection():
    from repro.train.trainer import StepTimer

    t = StepTimer()
    for i in range(10):
        t.record(i, 0.1, factor=3.0)
    assert t.record(10, 0.5, factor=3.0) is True
    assert t.record(11, 0.11, factor=3.0) is False
    assert t.flagged == [10]
