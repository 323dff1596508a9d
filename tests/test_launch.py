"""Launch-layer units that don't need 512 devices: HLO collective parsing,
analytic HBM model, cell bookkeeping, cycle-model calibration artifacts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as ha


SAMPLE_HLO = """
HloModule test
ENTRY main {
  %p0 = bf16[128,256]{1,0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %ag = bf16[128,4096]{1,0} all-gather(%p0), replica_groups={}, dimensions={1}
  %ar = f32[64]{0} all-reduce(%p1), to_apply=%add
  %rs = bf16[8,256]{1,0} reduce-scatter(%p0), to_apply=%add
  %a2a = bf16[128,256]{1,0} all-to-all(%p0), dimensions={0}
  %cp.1 = bf16[128,256]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  ROOT %dot.5 = f32[128,128]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}
"""


def test_collective_parser():
    st = ha.collective_stats(SAMPLE_HLO)
    p0 = 128 * 256 * 2
    p1 = 64 * 4
    assert st["bytes_by_kind"]["all-gather"] == p0
    assert st["bytes_by_kind"]["all-reduce"] == p1
    assert st["bytes_by_kind"]["reduce-scatter"] == p0
    assert st["bytes_by_kind"]["all-to-all"] == p0
    assert st["bytes_by_kind"]["collective-permute"] == p0
    assert st["total_count"] == 5


def test_collective_parser_on_real_lowering():
    """Parse a real jitted psum lowering (1 device, degenerate but present
    or absent cleanly)."""
    def f(x):
        return x @ x.T

    text = jax.jit(f).lower(jnp.ones((32, 32))).compile().as_text()
    st = ha.collective_stats(text)
    assert st["total_bytes"] == 0  # no collectives on one device


def test_roofline_terms():
    r = ha.roofline(flops=197e12, bytes_accessed=819e9, coll_bytes=0.0)
    assert r["compute_s"] == pytest.approx(1.0)
    assert r["memory_s"] == pytest.approx(1.0)
    assert r["dominant"] in ("compute", "memory")
    r2 = ha.roofline(1e12, 1e9, 500e9)
    assert r2["dominant"] == "collective"
    assert r2["step_time_lower_bound_s"] == pytest.approx(10.0)


def test_analytic_hbm_decode_is_weights_plus_cache():
    m = ha.analytic_hbm_bytes("decode", w_bytes=4e8, cache_bytes=1e9,
                              logits_bytes=1e6)
    assert m["total"] == pytest.approx(4e8 + 1e9 + 1e6)


def test_analytic_hbm_train_scales_with_microbatches():
    kw = dict(w_bytes=1e9, opt_bytes=6e9, resid_bytes=1e8, n_layers=32,
              logits_bytes=1e9)
    m1 = ha.analytic_hbm_bytes("train", microbatches=1, **kw)
    m4 = ha.analytic_hbm_bytes("train", microbatches=4, **kw)
    assert m4["parts"]["weights"] == 4 * m1["parts"]["weights"]
    assert m4["parts"]["opt"] == m1["parts"]["opt"]  # update happens once


def test_param_specs_shapes():
    from repro.configs import get_smoke_config
    from repro.parallel import param_specs as ps
    from repro.models import build

    cfg = get_smoke_config("rwkv6_3b")
    mod = build(cfg)
    ab = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
    logical = ps.param_logical(ab, cfg)
    # head (d, vocab) -> vocab-sharded on last dim
    assert logical["head"]["w"] == (None, "vocab")
    # channel-mix wv is row-parallel
    assert logical["blocks"]["channel_mix"]["wv"]["w"][1] == "ffn"
    # norms replicated
    assert all(n is None for n in logical["ln_f"]["scale"])


def test_dryrun_results_complete():
    """All 33 runnable cells x 2 meshes exist with sane content."""
    import json
    from pathlib import Path

    from repro.configs import ARCH_IDS
    from repro.configs.base import cells

    res = Path(__file__).resolve().parents[1] / "results" / "dryrun"
    if not res.exists():
        pytest.skip("dry-run results not generated")
    missing = []
    for arch in ARCH_IDS:
        for shape in cells(arch):
            for mesh in ("16_16", "2_16_16"):
                p = res / f"{arch}__{shape}__{mesh}.json"
                if not p.exists():
                    missing.append(p.name)
                    continue
                r = json.loads(p.read_text())
                assert r["cost"]["flops"] > 0, p.name
                assert r["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert not missing, missing


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after a test changes it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_placement(monkeypatch, tmp_path, cache_dir_config,
                                 from_env):
    """The variable names the cache and nothing is set in code; without it
    the cache sits at the fixed ``<repo root>/.jax_cache``."""
    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
