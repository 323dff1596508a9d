"""The table of published peaks."""
import pytest

from chipbench import spec


def test_v5e_peaks_are_the_published_ones():
    p = spec.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)
    assert "TPU v5e" in spec.load_json(spec.HERE / "peaks.json")["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_an_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        spec.peaks(kind)
