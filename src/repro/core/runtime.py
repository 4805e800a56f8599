"""Substrate skeletons for AltGDmin on the production mesh.

This module holds the two shard_map iteration skeletons the program
lowerings in :mod:`repro.core.program` execute on:

  * :func:`_altgdmin_mesh`         — one node per device; per iteration
    each device solves its local LS, applies the program's update (the
    combine crossing the wire by ``lax.ppermute``), and retracts with a
    local QR.  Numerically identical to the simulator run with the same
    W (tests/test_runtime_mesh.py, tests/test_programs.py), so every
    Theorem-1 guarantee transfers with γ(W) of the actual topology.
  * :func:`_altgdmin_virtual_mesh` — the virtual-node block tier
    (L = devices × block): each device is a small simulator over a
    contiguous (block, d, r) slab; co-located gossip edges run as
    on-device segment-sums and one collective-permute crosses the wire
    per cross-device shift class
    (:class:`~repro.distributed.consensus.VirtualTopology`).

Neither skeleton knows any solver: the per-iteration update arrives as
``make_update(eng) -> update(U, aux, min_grad[, xt])`` built by
:func:`repro.core.program.lower_mesh` /
:func:`~repro.core.program.lower_virtual_mesh` from a
:class:`~repro.core.program.SolverProgram`.  The historical per-solver
``*_mesh`` closures this module used to carry are gone — the program
registry derives every solver's mesh and virtual-mesh entry points, and
``tools/check_runtime_clean.py`` guards against them growing back.

Topologies: the consensus layer lowers ANY concrete mixing matrix to
collective-permutes (``W=`` kwarg — one permute per distinct cyclic
shift of W's sparsity pattern, each device combining with its own W
row; see :func:`repro.distributed.consensus.mesh_weights_from_matrix`).
Without ``W`` the historical uniform circulant of ``shifts`` /
``self_weight`` runs (nearest-neighbour on the ICI torus).

The min-B and gradient phases route through the same
:class:`repro.core.engine.AltgdminEngine` as the simulator (``engine=``/
``backend=`` kwargs).  The federated property is structural: only the
iterate (or the rule's compact payload) crosses the wire; X_g, y_g, B_g
never leave the device.

Pass ``U_star`` to additionally record the simulator's per-iteration
metrics (sd_max / sd_mean / consensus spread, via one all-gather of the
iterate per iteration) and get a full :class:`RunResult` back; without
it the return is the legacy ``(U_nodes, B_nodes)`` pair and no extra
collective runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine import AltgdminEngine, resolve_engine
from repro.core.metrics import consensus_spread, subspace_distance
from repro.utils.spans import span


def _altgdmin_mesh(U0, Xg, yg, mesh, axis_name: str, *, eta: float,
                   T_GD: int, make_update,
                   engine: AltgdminEngine | None,
                   backend: str | None, U_star, init_aux=None, xs=None):
    """Shared shard_map skeleton for the decentralized mesh solvers.

    ``make_update(eng) -> update(U, aux, min_grad)`` builds the
    per-iteration update from the resolved engine: it receives this
    device's iterate, the solver's auxiliary scan state (``None`` unless
    ``init_aux`` is given — e.g. exact diffusion's ψ correction), and a
    ``min_grad(U) -> (B, G)`` closure over the device's local data (ONE
    fused kernel dispatch per call on the pallas backends), and returns
    ``(U_new, aux_new)``.  Everything else — the scan, the optional
    metrics all-gather, the final min-B — is solver-independent.
    ``init_aux(U_local)`` seeds the auxiliary state from the device's
    starting iterate.

    ``xs`` (optional) is a pytree of per-iteration scan inputs with a
    leading T_GD axis, replicated to every device (the dropout solvers'
    availability masks); when given, the update is called as
    ``update(U, aux, min_grad, xt)`` with iteration τ's slice.
    """
    from repro.core.altgdmin import RunResult

    L = mesh.shape[axis_name]
    if U0.shape[0] != L:
        raise ValueError(f"need one node per device: L={U0.shape[0]} vs "
                         f"mesh axis {L}")
    with span("solve.build"):
        eng = resolve_engine(engine, backend)
        update = make_update(eng)
    with_metrics = U_star is not None
    has_xs = xs is not None

    def local_min_B(U, X, y):
        """b_t = (X_t U)† y_t for the device's tasks, through the engine
        (node-batch of one). X: (tpn, n, d)."""
        return eng.minimize_B(U[None], X[None], y[None])[0]

    def local_min_grad(U, X, y):
        """Fused min-B + gradient — ONE kernel dispatch per device per
        call on the pallas backends."""
        B, G = eng.min_grad(U[None], X[None], y[None], X[None], y[None],
                            same_data=True)
        return B[0], G[0]

    def body(U0, Xg, yg, U_star, *rest):
        U = U0[0]                       # this device's node
        X, y = Xg[0], yg[0]

        def mg(U_):
            return local_min_grad(U_, X, y)

        def step(carry, xt):
            U, aux = carry
            if has_xs:
                U_new, aux_new = update(U, aux, mg, xt)
            else:
                U_new, aux_new = update(U, aux, mg)
            if not with_metrics:
                return (U_new, aux_new), None
            U_all = jax.lax.all_gather(U_new, axis_name)     # (L, d, r)
            return (U_new, aux_new), (subspace_distance(U_new, U_star),
                                      consensus_spread(U_all))

        aux0 = init_aux(U) if init_aux is not None else None
        xseq = rest[0] if has_xs else None
        (U_fin, _), metrics = jax.lax.scan(
            step, (U, aux0), xseq, length=None if has_xs else T_GD)
        B_fin = local_min_B(U_fin, X, y)
        if not with_metrics:
            return U_fin[None], B_fin[None]
        sd, spread = metrics
        return U_fin[None], B_fin[None], sd[None], spread[None]

    sharded = P(axis_name)
    out_specs = ((sharded,) * 4) if with_metrics else (sharded, sharded)
    # one jitted program; called eagerly, a shard_map runs its body
    # primitive by primitive, each a program of its own.  A pallas_call's
    # outputs carry no varying-axes type, so the check is off exactly
    # when the engine dispatches Pallas kernels
    run = jax.jit(jax.shard_map(body, mesh=mesh,
                                in_specs=(sharded, sharded, sharded, P())
                                + ((P(),) if has_xs else ()),
                                out_specs=out_specs,
                                axis_names={axis_name},
                                check_vma=not eng.fused))

    U_dummy = U0[0] if U_star is None else U_star
    # one program: the scan and the final min-B
    with span("solve.scan"):
        out = run(U0, Xg, yg, U_dummy, *((xs,) if has_xs else ()))
    if not with_metrics:
        return out
    U_fin, B_fin, sd, spread = out          # sd/spread: (L, T_GD)
    return RunResult(U_nodes=U_fin, B_nodes=B_fin,
                     sd_max=jnp.max(sd, axis=0),
                     sd_mean=jnp.mean(sd, axis=0),
                     spread=spread[0], eta=eta)


def _altgdmin_virtual_mesh(U0, Xg, yg, mesh, axis_name: str, *, vt,
                           eta: float, T_GD: int, make_update,
                           engine: AltgdminEngine | None,
                           backend: str | None, U_star, init_aux=None,
                           xs=None, wire: dict | None = None):
    """Shared shard_map skeleton for the VIRTUAL-NODE mesh tier:
    L = devices × block nodes, each device holding a contiguous
    (block, d, r) slab of iterates and the matching data shard.  The
    local min-B/gradient phases run node-batched through the engine
    exactly like the simulator (a device IS a small simulator over its
    block); the combine inside the program's update is the
    :class:`~repro.distributed.consensus.VirtualTopology` lowering —
    co-located gossip as an on-device segment-sum shuffle, one
    collective-permute per cross-device edge class.  ``vt`` carries the
    decomposed mixing matrix (``VirtualTopology.from_weights``).

    Same ``make_update``/``init_aux``/``xs`` contract as
    :func:`_altgdmin_mesh`, except the per-device iterate is the
    (block, d, r) slab and ``min_grad`` is node-batched over it.
    Federated structure is preserved: only the (block, d, r) iterate
    slab (or the rule's compact payload) crosses the wire, never data.

    The node-axis operands are placed on the mesh first (span
    ``repro.virtual.shard``), so that resharding is a phase of its own;
    ``repro.solve.scan`` counts ``devices``, ``gathers_per_iter`` (the
    metrics' all-gather) and what ``wire`` holds, the lowering's
    ``ppermutes_per_iter`` and ``wire_bytes_per_iter``."""
    from repro.core.altgdmin import RunResult

    D = mesh.shape[axis_name]
    L = U0.shape[0]
    if vt.n_dev != D or vt.n_nodes != L:
        raise ValueError(f"VirtualTopology is {vt.n_dev} dev × {vt.block} "
                         f"block but the run has {D} devices and L={L}")
    with span("solve.build"):
        eng = resolve_engine(engine, backend)
        update = make_update(eng)
    with_metrics = U_star is not None
    has_xs = xs is not None

    def body(U0b, Xb, yb, U_star_, *rest):
        # U0b: (V, d, r) — this device's block of virtual nodes
        def mg(U_):
            return eng.min_grad(U_, Xb, yb, Xb, yb, same_data=True)

        def step(carry, xt):
            U, aux = carry
            if has_xs:
                U_new, aux_new = update(U, aux, mg, xt)
            else:
                U_new, aux_new = update(U, aux, mg)
            if not with_metrics:
                return (U_new, aux_new), None
            sd = jax.vmap(lambda u: subspace_distance(u, U_star_))(U_new)
            U_all = jax.lax.all_gather(U_new, axis_name)   # (D, V, d, r)
            spread = consensus_spread(
                U_all.reshape(L, *U_all.shape[2:]))
            return (U_new, aux_new), (sd, spread)

        aux0 = init_aux(U0b) if init_aux is not None else None
        xseq = rest[0] if has_xs else None
        (U_fin, _), metrics = jax.lax.scan(
            step, (U0b, aux0), xseq, length=None if has_xs else T_GD)
        B_fin = eng.minimize_B(U_fin, Xb, yb)
        if not with_metrics:
            return U_fin, B_fin
        sd, spread = metrics                         # (T, V), (T,)
        return U_fin, B_fin, sd[None], spread[None]

    sharded = P(axis_name)
    out_specs = ((sharded,) * 4) if with_metrics else (sharded, sharded)
    # one jitted program; called eagerly, a shard_map runs its body
    # primitive by primitive, each a program of its own.  A pallas_call's
    # outputs carry no varying-axes type, so the check is off exactly
    # when the engine dispatches Pallas kernels
    run = jax.jit(jax.shard_map(body, mesh=mesh,
                                in_specs=(sharded, sharded, sharded, P())
                                + ((P(),) if has_xs else ()),
                                out_specs=out_specs,
                                axis_names={axis_name},
                                check_vma=not eng.fused))

    U_dummy = U0[0] if U_star is None else U_star
    with span("virtual.shard"):
        U0, Xg, yg = jax.device_put((U0, Xg, yg),
                                    NamedSharding(mesh, sharded))
    # one program: the scan and the final min-B
    with span("solve.scan", devices=D, gathers_per_iter=int(with_metrics),
              **(wire or {})):
        out = run(U0, Xg, yg, U_dummy, *((xs,) if has_xs else ()))
    if not with_metrics:
        return out
    U_fin, B_fin, sd, spread = out       # sd: (D, T_GD, V), spread: (D, T)
    return RunResult(U_nodes=U_fin, B_nodes=B_fin,
                     sd_max=jnp.max(sd, axis=(0, 2)),
                     sd_mean=jnp.mean(sd, axis=(0, 2)),
                     spread=spread[0], eta=eta)
