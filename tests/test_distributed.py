"""Distribution-layer tests that need >1 device: run in a SUBPROCESS with
forced host devices (conftest keeps the main test process at 1 device).

Covers: logical sharding rules + divisibility fallback, param-spec
derivation, grad-compression collective (error feedback across steps), and
a tiny end-to-end sharded train step on a 4x2 mesh.
"""
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # CI runs these in the non-blocking slow job

SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.parallel import sharding as shd
from repro.parallel import param_specs as pspecs

mesh = make_host_mesh(model=2)  # (4, 2) data x model

# --- rule resolution + divisibility fallback
with shd.use_mesh(mesh):
    spec = shd.spec_for(("batch", None, "heads"), (8, 3, 4))
    assert spec == P("data", None, "model"), spec
    # kv=3 not divisible by model=2 -> dropped
    spec = shd.spec_for(("batch", None, "kv_heads"), (8, 3, 3))
    assert spec == P("data", None, None), spec
    # duplicate axis use prevented
    spec = shd.spec_for(("heads", "ffn"), (4, 4))
    assert spec == P("model", None), spec

# --- param specs on a smoke model
from repro.configs import get_smoke_config
from repro.models import build
cfg = get_smoke_config("yi_6b")
mod = build(cfg)
ab = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
sh = pspecs.named_shardings(ab, cfg, mesh)
wq = sh["blocks"]["attn"]["wq"]["w"]
assert wq.spec == P(None, None, "model"), wq.spec  # (L, d, heads*hd)
wo = sh["blocks"]["attn"]["wo"]["w"]
assert wo.spec == P(None, "model", None), wo.spec  # row-parallel
emb = sh["embed"]["table"]
assert emb.spec == P("model", None), emb.spec      # vocab-sharded

# --- grad compression: compressed mean-allreduce with error feedback
from repro.optim import grad_compress as gc
mesh1 = make_mesh((8,), ("data",))
f = gc.compressed_psum_shardmap(mesh1, ("data",))
rng = np.random.default_rng(0)
g_local = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)  # per-shard
err = jnp.zeros((8, 128), jnp.float32)
exact_mean = jnp.mean(g_local, axis=0)
total_err_first = None
acc = jnp.zeros((128,), jnp.float32)
acc_exact = jnp.zeros((128,), jnp.float32)
for step in range(20):
    synced, err = f(g_local, err)
    acc = acc + synced[0]
    acc_exact = acc_exact + exact_mean
    if step == 0:
        total_err_first = float(jnp.max(jnp.abs(synced[0] - exact_mean)))
# single-step error is bounded by the int8 quant step
assert total_err_first < float(jnp.max(jnp.abs(g_local))) / 127 * 1.01 + 1e-6
# error feedback keeps the ACCUMULATED estimate tight (no drift)
drift = float(jnp.max(jnp.abs(acc - acc_exact)))
assert drift < float(jnp.max(jnp.abs(g_local))) / 127 * 2.5, drift

# --- end-to-end sharded train step on the 4x2 mesh
from repro.train import train_step as ts
ab_state = ts.abstract_state(cfg)
st_sh = ts.state_shardings(ab_state, cfg, mesh)
batch = {"tokens": jax.ShapeDtypeStruct((8, 33), jnp.int32)}
b_sh = ts.batch_shardings(batch, mesh)
params = mod.init_params(jax.random.PRNGKey(0), cfg)
from repro.optim import adamw
state = {"params": params, "opt": adamw.init(params)}
state = jax.device_put(state, st_sh)
tok = jax.device_put(jnp.asarray(rng.integers(0, cfg.vocab, (8, 33)), jnp.int32),
                     b_sh["tokens"])
def step_fn(st, b):
    with shd.use_mesh(mesh):
        return ts.train_step(st, b, cfg)
jitted = jax.jit(step_fn, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))
new_state, metrics = jitted(state, {"tokens": tok})
loss = float(metrics["loss"])
assert np.isfinite(loss), loss

# --- sharded result must equal single-device result
state1 = {"params": params, "opt": adamw.init(params)}
new1, m1 = jax.jit(lambda st, b: ts.train_step(st, b, cfg))(state1, {"tokens": tok})
assert abs(loss - float(m1["loss"])) < 5e-2, (loss, float(m1["loss"]))

# --- elastic restart: save sharded under mesh A, restore under mesh B
import tempfile
from repro.checkpoint.ckpt import Checkpointer
from jax.sharding import NamedSharding
meshA = make_mesh((4, 2), ("data", "model"))
meshB = make_mesh((2, 4), ("data", "model"))
with tempfile.TemporaryDirectory() as td:
    ck = Checkpointer(td)
    w = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    wA = jax.device_put(w, NamedSharding(meshA, P("data", "model")))
    ck.save(1, {"w": wA})
    shB = {"w": NamedSharding(meshB, P("data", "model"))}
    restored, _ = ck.restore(jax.eval_shape(lambda: {"w": w}), shardings=shB)
    assert restored["w"].sharding == shB["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))

# --- EP MoE (shard_map all-to-all) == GSPMD dispatch path, dropless
import dataclasses as dc
from repro.models import moe as moe_lib

mcfg = get_smoke_config("olmoe_1b_7b")
mcfg = mcfg.replace(moe=dc.replace(mcfg.moe, capacity_factor=64.0, ep=True))
mp = moe_lib.init_moe(jax.random.PRNGKey(3), mcfg)
xm = jnp.asarray(rng.standard_normal((4, 16, mcfg.d_model)) * 0.1, jnp.bfloat16)
with shd.use_mesh(mesh):
    y_plain = jax.jit(lambda p_, x_: moe_lib.moe_ffn(p_, x_, mcfg))(mp, xm)
    y_ep = jax.jit(lambda p_, x_: moe_lib.moe_ffn_ep(p_, x_, mcfg))(mp, xm)
diff = float(jnp.max(jnp.abs(y_plain.astype(jnp.float32) - y_ep.astype(jnp.float32))))
scale = float(jnp.max(jnp.abs(y_plain.astype(jnp.float32)))) + 1e-6
assert diff / scale < 0.02, (diff, scale)

print("SUBPROCESS_OK")
"""


def test_distributed_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", SUB],
        capture_output=True, text=True, timeout=900,
        # JAX_PLATFORMS=cpu: these are forced-host-device tests; without it
        # jax probes for a TPU backend in the stripped env and can hang for
        # minutes before falling back.
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": str(Path.home()), "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1],
    )
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + "\n" + r.stderr
