"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — the dry-run driver
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax initialization; everything else (smoke tests, benches) sees 1 device.

Topology model: TPU v5e pods — a pod is a 16×16 slice (256 chips); the
multi-pod mesh stacks 2 pods on a leading ``pod`` axis (data-parallel
across pods, as inter-pod DCI bandwidth ≪ intra-pod ICI).

Every mesh uses ``Auto`` axes: the sharding rules place arrays with
``with_sharding_constraint``, which ``Explicit`` axes (the
``jax.make_mesh`` default) refuse.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2,
                    pods: Optional[int] = None) -> Mesh:
    """Small mesh for CI-scale sharding tests (requires host device count
    >= product, set via XLA_FLAGS in the spawning process)."""
    if pods:
        return _mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def fleet_mesh(n_sims: Optional[int] = None) -> Mesh:
    """1-D mesh over local devices for fleet sharding (axis ``"sims"``).

    The fleet runner shards the leading sim axis of a stacked
    :class:`~repro.fleet.state.SimState` across devices with
    ``shard_map`` — each device advances its slice of the grid
    independently (no cross-sim collectives).  ``n_sims`` limits the
    mesh to the first ``n_sims`` devices (must divide the batch).
    """
    n = n_sims or len(jax.devices())
    return _mesh((n,), ("sims",))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect = 4 links x 50 GB/s).
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "hbm_bw": 819e9,
        "ici_bw_per_link": 50e9,
        "hbm_bytes": 16 * 1024 ** 3,
    },
}


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Roofline denominators for ``device_kind``; an unknown kind is an
    error, never a silent default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None
