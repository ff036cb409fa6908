"""The fleet's device mesh, and the published peaks of the chips it runs on.

The mesh is built by a FUNCTION (not a module constant), so importing
this module never touches jax device state.  It uses ``Auto`` axes, not
the ``Explicit`` axes that ``jax.make_mesh`` defaults to.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import AxisType, Mesh


def fleet_mesh(n_sims: Optional[int] = None) -> Mesh:
    """1-D mesh over local devices for fleet sharding (axis ``"sims"``).

    The fleet runner shards the leading sim axis of a stacked
    :class:`~repro.fleet.state.SimState` across devices with
    ``shard_map`` — each device advances its slice of the grid
    independently (no cross-sim collectives).  ``n_sims`` limits the
    mesh to the first ``n_sims`` devices (must divide the batch).
    """
    n = n_sims or len(jax.devices())
    return jax.make_mesh((n,), ("sims",), axis_types=(AxisType.Auto,))


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
