"""Hardware gossip — the AGREE protocol on a TPU mesh.

Two numerically-identical implementations of one circulant gossip round
    Z_g ← w_self · Z_g + Σ_k w_k · Z_{g+s_k  (mod L)}
(= ``Z ← W Z`` for the circulant W of repro.distributed.mixing):

  * :func:`shard_map_gossip` — nodes are devices along a mesh axis; each
    shift is ONE ``lax.ppermute`` (nearest-neighbour collective-permute on
    the ICI torus).  This is the paper's communication pattern lowered to
    TPU-native collectives; used by the linear-MTRL distributed runtime.
  * :func:`roll_gossip` — nodes are the leading array axis; each shift is
    a ``jnp.roll``.  Under pjit with that axis sharded over the mesh, XLA
    lowers the roll to the same collective-permute — but the function
    composes freely with vmap/grad/scan, so the deep-learning trainer
    (repro.distributed.aggregation) uses this form.

Both bottom out in the unified consensus layer's K+1-way combine
(:func:`repro.distributed.consensus.combine_blocks`) — the same primitive
the AltGDmin mesh runtime fuses into one ``gossip_combine`` dispatch per
round on the pallas backends.

DESIGN.md §3 hardware adaptation: production topologies are rings/tori
(fabric-native); arbitrary Erdős–Rényi graphs stay in the simulator.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import numpy as np

from repro.distributed.consensus import GossipCombine, get_rule


def ring_weights(shifts: Sequence[int] = (-1, 1),
                 self_weight: float | None = None):
    """(self_weight, per-shift weight) for a symmetric circulant mixer.
    Defaults to equal weights 1/(k+1) — the paper's equal-neighbour rule on
    a regular ring."""
    return GossipCombine._ring_weights(shifts, self_weight)


def torus_shifts(rows: int, cols: int):
    """Neighbour shifts of a rows×cols torus flattened row-major: ±1 (same
    row, wrap handled by flat modular shift) and ±cols."""
    return (-1, 1, -cols, cols)


# ---------------------------------------------------------------- pjit form

def roll_gossip(tree, T_con: int, shifts: Sequence[int] = (-1, 1),
                self_weight: float | None = None, *, W=None,
                backend: str = "xla-ref"):
    """T_con gossip rounds over the leading (node) axis of every leaf.

    Without ``W`` this is the uniform circulant mixer of ``shifts`` /
    ``self_weight`` (the historical trainer form).  Pass ``W=`` — ANY
    concrete (L, L) mixing matrix — to gossip with the matrix's actual
    weights: the consensus layer decomposes it into cyclic shifts plus
    per-node weight rows (circulant matrices collapse to the shared
    scalar fast path, bit-compatible with the legacy form; irregular
    Metropolis/ER matrices roll with an (L, K+1) table each node indexes
    by its row).  Leaves whose leading axis disagrees with W's size
    raise a ``ValueError`` instead of silently mixing with wrong
    weights."""
    if T_con == 0:
        return tree
    rule = get_rule("gossip")
    if W is not None:
        # one source of truth with the shard_map mesh lowering:
        # _mesh_weights collapses a circulant W to shared scalars and
        # keeps an (L, K+1) per-node table otherwise
        L = np.asarray(W).shape[0]
        shifts, weights = GossipCombine._mesh_weights(L, (), None, W)
        bad = [x.shape for x in jax.tree.leaves(tree)
               if x.shape[:1] != (L,)]
        if bad:
            raise ValueError(
                f"roll_gossip W= is {L}×{L} but leaves have leading "
                f"(node) axes {sorted({s[0] for s in bad})} — every leaf "
                f"must carry one row per node")
    else:
        sw, wn = ring_weights(shifts, self_weight)
        weights = (sw,) + (wn,) * len(shifts)

    def one_round(t):
        return jax.tree.map(
            lambda x: rule.roll_round(x, shifts, weights, backend=backend),
            t)

    for _ in range(T_con):
        tree = one_round(tree)
    return tree


# ---------------------------------------------------------- shard_map form

def shard_map_gossip(Z, mesh, axis_name: str, T_con: int,
                     shifts: Sequence[int] = (-1, 1),
                     self_weight: float | None = None, *, W=None,
                     backend: str = "xla-ref"):
    """AGREE on hardware: Z's leading axis (length = mesh axis size) is
    sharded over ``axis_name``; every round each device exchanges its block
    with its graph neighbours via collective-permute, then combines them
    (one fused K+1-way dispatch per round on the pallas backends).
    Pass ``W=`` (a concrete mixing matrix) to gossip over an arbitrary
    weighted topology instead of the uniform circulant of ``shifts``."""
    L = mesh.shape[axis_name]
    if Z.shape[0] != L:
        raise ValueError(f"leading axis {Z.shape[0]} != mesh axis {L}")
    mixer = get_rule("gossip").make_mesh_mixer(
        axis_name, L, T_con, shifts, self_weight, W=W, backend=backend)
    spec = jax.sharding.PartitionSpec(axis_name)

    # a pallas_call's outputs carry no varying-axes type: check only
    # the kernel-free xla-ref lowering
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=spec,
                       out_specs=spec, axis_names={axis_name},
                       check_vma=backend == "xla-ref")
    def run(z):
        return mixer(z)

    return run(Z)


def axis_mean(tree, axis_name: str):
    """Fusion-center baseline inside shard_map: exact pmean."""
    mix = get_rule("central").make_mesh_mixer(axis_name, 0)
    return jax.tree.map(mix, tree)
