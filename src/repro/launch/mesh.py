"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — critical because the dry-run needs
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` set *before* the
first jax device query, while smoke tests/benches must see 1 CPU device.

Mesh shapes (TPU v5e pods, 256 chips each):
  single-pod:  (16, 16)      axes ("data", "model")
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model")

The "model" axis carries TP / EP / (serving) 2D weight sharding; "data"
carries DP / FSDP / sequence-sharded KV; "pod" is pure data parallelism over
pods (DCN-connected), matching the paper's centralized-scheduler +
SPMD-worker deployment scaled to multi-pod.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules here annotate
    with ``with_sharding_constraint`` and leave propagation to the compiler,
    which Explicit axes (the default since JAX 0.7) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (hillclimb sweeps over layouts)."""
    return _auto_mesh(shape, axes)


def make_host_mesh(n_model: Optional[int] = None) -> Mesh:
    """Tiny mesh over whatever devices exist (CPU tests: 1 device)."""
    n = len(jax.devices())
    nm = n_model or 1
    return _auto_mesh((n // nm, nm), ("data", "model"))


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s per link
