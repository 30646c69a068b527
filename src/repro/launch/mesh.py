"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device; only
``launch/dryrun.py`` forces the 512-device host platform).

Target: TPU v5e.  Single pod = (data=16, model=16) = 256 chips; multi-pod
= (pod=2, data=16, model=16) = 512 chips, with the slow inter-pod (DCI)
axis outermost so XLA keeps pod-crossing collectives to the gradient
reduction only.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks for the roofline model."""
    flops_bf16: float          # FLOP/s
    hbm_bw: float              # B/s
    ici_bw: float              # B/s per link
    ici_links: int


# Keyed by ``jax.devices()[0].device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# of inter-chip interconnect = 4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_bw=50e9, ici_links=4),
}

# The chip the production meshes below (and the dry-run) are built for.
TARGET_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def _mesh(shape, axes):
    # Auto axes: the model's logical-axis ``shard()`` constraints assume
    # them (newer JAX defaults ``make_mesh`` to Explicit axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever-fits mesh for CPU tests/examples (1 device -> (1, 1))."""
    n = len(jax.devices())
    dp = max(n // model_parallel, 1)
    return _mesh((dp, model_parallel), ("data", "model"))
