"""Production meshes.

Single pod: (data=16, model=16) — 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the
'pod' axis crosses the DCN, which is exactly the expensive inter-node link
the paper's decentralized setting targets (pods-as-nodes diffusion).

Everything here is a FUNCTION — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax


# TPU v5e hardware constants (roofline denominators; EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def node_axes(mesh) -> tuple:
    """Mesh axes that carry the decentralized node dimension (the leading
    param/batch axis of the diffusion trainer)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def n_nodes(mesh) -> int:
    out = 1
    for a in node_axes(mesh):
        out *= mesh.shape[a]
    return out


def n_chips(mesh) -> int:
    out = 1
    for a in mesh.shape:
        out *= mesh.shape[a]
    return out


def make_host_mesh():
    """Whatever devices exist locally (tests / examples): 1-D data mesh."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
