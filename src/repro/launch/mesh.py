"""Mesh construction: every mesh in the repo comes from ``make_mesh``.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str] = ("data", "model")
              ) -> Mesh:
    """A mesh whose axes are all ``AxisType.Auto``.

    The model code places arrays with ``with_sharding_constraint`` hints
    and lets GSPMD propagate the rest (``repro.dist``).  ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which ops such as the embedding
    gather raise ``ShardingTypeError`` instead of propagating.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def parse_mesh(spec: Optional[str], n_devices: int) -> tuple:
    """``"D,M"`` -> ``(D, M)`` data x model sizes; ``None`` -> pure data
    parallelism ``(n_devices, 1)``.  The product must be ``n_devices``."""
    if spec is None:
        return (n_devices, 1)
    shape = tuple(int(s) for s in spec.split(","))
    if len(shape) != 2 or shape[0] * shape[1] != n_devices:
        raise ValueError(f"--mesh {spec!r} must be 'data,model' sizes whose "
                         f"product is the device count {n_devices}")
    return shape


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e); multi-pod adds a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


HW = {
    # TPU v5e per-chip hardware constants used by the roofline model
    "peak_bf16_flops": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link
}
