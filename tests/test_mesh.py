"""Published chip peaks, keyed by device kind (``launch/mesh.py``)."""
import pytest


def test_chip_peaks_by_device_kind():
    """Roofline denominators come from a table keyed by device kind; an
    unknown kind is refused, never given another chip's peaks."""
    from repro.launch.mesh import chip_peaks
    assert chip_peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="TPU v4"):
        chip_peaks("TPU v4")
