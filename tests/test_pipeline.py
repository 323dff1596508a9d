"""Pipeline parallelism: PP(2) x DP(4) loss must match the single-device
loss; gradients must flow (subprocess with 8 forced host devices)."""
import subprocess
import sys
from pathlib import Path

SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import build
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as shd
from repro.parallel.pipeline import pipelined_loss_fn, bubble_fraction

cfg = get_smoke_config("yi_6b").replace(seq_shard=False)
mod = build(cfg)
params = mod.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 33)), jnp.int32)}

# single-device reference
ref_loss, _ = mod.loss_fn(params, batch, cfg)
ref_loss = float(ref_loss)

mesh = make_mesh((4, 2), ("data", "model"))  # PP=2, DP=4
with shd.use_mesh(mesh):
    loss_fn = lambda p, b: pipelined_loss_fn(p, b, cfg, n_micro=2)[0]
    pp_loss = float(jax.jit(loss_fn)(params, batch))
    # gradients flow through the pipeline (ppermute transpose)
    g = jax.jit(jax.grad(loss_fn))(params, batch)
gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(g))))
assert abs(pp_loss - ref_loss) / abs(ref_loss) < 2e-2, (pp_loss, ref_loss)
assert np.isfinite(gn) and gn > 0, gn
# first-layer and last-layer block grads must both be nonzero (both stages
# participated in backward)
gb = g["blocks"]["attn"]["wq"]["w"].astype(jnp.float32)
assert float(jnp.abs(gb[0]).max()) > 0 and float(jnp.abs(gb[-1]).max()) > 0
assert abs(bubble_fraction(2, 2) - 1/3) < 1e-9
print("PIPELINE_OK", pp_loss, ref_loss)
"""


def test_pipeline_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", SUB], capture_output=True, text=True, timeout=900,
        # JAX_PLATFORMS=cpu: a forced-host-device test; without it jax probes
        # for a TPU backend in the stripped env before falling back.
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": str(Path.home()), "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1],
    )
    assert "PIPELINE_OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
