"""Production meshes.

Target hardware: TPU v5e pods — 256 chips/pod as a (16, 16) (data, model)
mesh; the multi-pod configuration stacks 2 pods into (pod, data, model) =
(2, 16, 16) = 512 chips. Functions (not module-level constants) so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e per-chip constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link (~4 links usable per chip)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist locally (tests / CPU examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
