"""``chip_smoke.py`` rehearsed on the CPU at a tiny size: the same phase
code the chip runs, with Pallas kernels in interpret mode.  The script
itself refuses to run anywhere but on a TPU, and fails outright when it is
copied out of the repository."""
import glob
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_unet_phase_tiny(chip_smoke):
    out = chip_smoke.unet_phase(image_hw=32, n_images=2, tile=16, base=4,
                                require_kernel=False)
    assert out["platform"] == "cpu" and out["device_count"] == 1
    assert out["max_conv_diff_vs_oracle"] == 0
    assert out["max_logit_diff_vs_xla"] <= (
        chip_smoke.LOGIT_RTOL * out["max_abs_logit"])
    json.dumps(out)  # every phase line is printable JSON


def test_lm_phase_tiny(chip_smoke):
    out = chip_smoke.lm_phase(n_requests=2, max_new=3)
    assert out["tokens_identical_to_xla"]
    assert out["requests"] == 2
    json.dumps(out)


SHARDED = """
import importlib.util, json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
print(json.dumps(mod.sharded_train_phase(steps=2)))
"""


def test_sharded_train_phase_on_four_virtual_devices(chip_smoke):
    """The ``--chips 4`` phase on four forced host devices (a subprocess:
    this process keeps its one CPU device)."""
    r = subprocess.run(
        [sys.executable, "-c", SHARDED], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
             "HOME": str(Path.home()), "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["mesh"] == {"data": 2, "model": 2}
    assert out["max_loss_diff"] < chip_smoke.LOSS_ATOL


def _run_alone(directory: Path) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, cwd=directory,
        env={"PATH": os.environ.get("PATH", ""), "HOME": str(Path.home()),
             "TPU_LOG_DIR": "disabled"},
    )


def test_chip_smoke_fails_outside_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_alone(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.skipif(bool(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")),
                    reason="a TPU is attached")
def test_chip_smoke_fails_without_a_chip():
    r = _run_alone(ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
