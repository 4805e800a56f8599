"""``run_experiment(spec, key) -> Trace`` — the one entry point.

Materializes the spec (problem → node view → graph/weights → spectral
init → η), dispatches to the registered solver on the chosen substrate,
and returns a :class:`Trace` carrying the per-iteration metrics, the
final iterates, the resolved η, and the comm-model wall-clock axis so
figure code stops recomputing it.

Substrates:

  * ``"simulator"`` — the single-host node-batched simulator
    (:mod:`repro.core.altgdmin`), any topology/solver;
  * ``"mesh"``      — the shard_map runtime (one node per device,
    AGREE = collective-permute gossip).  Requires a mesh-capable solver
    and L = the mesh's devices (``spec.n_devices``, else all of them),
    or a multiple of them on the virtual-node tier; ANY weight scheme
    runs — circulant weights lower to the native uniform ring form, and
    every other scheme (metropolis/equal_neighbor/lazy on arbitrary
    graphs) is decomposed into per-shift, per-device weights by the
    consensus layer.  The min-B and gradient phases route through the same
    :class:`AltgdminEngine` backend as the simulator, so
    ``pallas``/``pallas-interpret`` reach hardware nodes.

Determinism: the problem and init keys are derived from the caller's
``key`` by ``fold_in``, so two specs that share problem/topology/init
sub-specs (e.g. the four solvers of one figure cell) see identical data,
graphs, and starting bases.
"""
from __future__ import annotations

import dataclasses
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import SolverDef, get_solver
from repro.api.spec import ExperimentSpec, SystemSpec
from repro.core import comm_model as _cm
from repro.core import system_clock as _sysclock
from repro.core.altgdmin import RunResult, resolve_eta
from repro.core.problem import (MTRLProblem, generate_problem, node_view,
                                split_samples)
from repro.core.spectral import SpectralInit, decentralized_spectral_init
from repro.distributed import consensus as _consensus
from repro.distributed.graphs import Graph, SparseGraph
from repro.utils.spans import span


_COMM_MODELS = {"ethernet-1gbps": _cm.ETHERNET_1GBPS,
                "tpu-ici": _cm.TPU_ICI}


@dataclasses.dataclass(frozen=True)
class Materialized:
    """The spec's liturgy, executed: everything a solver call needs.

    On the sparse representation (``TopologySpec.use_sparse``) ``W`` is
    a :class:`~repro.distributed.mixing.SparseWeights` and ``adj`` the
    :class:`~repro.distributed.graphs.SparseGraph` itself — nothing
    (L, L)-shaped is ever materialized; the consensus layer lowers both
    to padded segment-sum rounds."""
    problem: MTRLProblem
    Xg: jax.Array
    yg: jax.Array
    graph: Graph | SparseGraph
    W: jax.Array                 # or SparseWeights (sparse representation)
    adj: jax.Array               # or SparseGraph  (sparse representation)
    init: SpectralInit
    eta: float


@dataclasses.dataclass(frozen=True)
class Trace:
    """Result of one experiment run.

    ``sd_max``/``sd_mean``/``spread`` are per-iteration (length T_GD);
    ``time_axis`` is the cumulative emulated wall-clock under the spec's
    comm model, priced by the solver's communication pattern (gossip /
    neighbor / central) — the x-axis of the paper's Fig. 1 right panes.
    ``time_axis_source`` records how it was priced: ``"closed_form"``
    (the comm-model formula) or ``"simulated"`` (the event-driven
    system clock, whenever the spec carries a SystemSpec).
    """
    spec: ExperimentSpec
    U_nodes: jax.Array
    B_nodes: jax.Array
    sd_max: np.ndarray
    sd_mean: np.ndarray
    spread: np.ndarray
    eta: float
    time_axis: np.ndarray
    materialized: Materialized
    time_axis_source: str = "closed_form"

    @property
    def final_sd_max(self) -> float:
        return float(self.sd_max[-1])


def _as_key(key: Union[jax.Array, int, None]) -> jax.Array:
    if key is None:
        return jax.random.PRNGKey(0)
    if isinstance(key, int):
        return jax.random.PRNGKey(key)
    return key


def materialize(spec: ExperimentSpec, key=None) -> Materialized:
    """Run the setup liturgy for a spec: generate the problem, build the
    topology, run the spectral init, resolve η."""
    with span("materialize"):
        key = _as_key(key)
        p = spec.problem
        dtype = jnp.dtype(p.dtype)
        with span("materialize.problem"):
            prob = generate_problem(jax.random.fold_in(key, 0), d=p.d, T=p.T,
                                    r=p.r, n=p.n, L=p.L, kappa=p.kappa,
                                    noise_std=p.noise_std, dtype=dtype)
            # the init sees the full unsplit data (Algorithm 2 precedes
            # the fold partition of Algorithm 3 line 4)
            Xg_init, yg_init = node_view(prob)
            if p.n_folds > 1:
                prob = split_samples(prob, p.n_folds)
            Xg, yg = node_view(prob)
        with span("materialize.topology"):
            graph = spec.topology.build_graph(p.L)
            if spec.topology.use_sparse(p.L, graph):
                sg = (graph if isinstance(graph, SparseGraph)
                      else graph.to_sparse())
                graph = sg
                W = spec.topology.build_sparse_weights(p.L, sg)
                adj = sg
            else:
                W = jnp.asarray(spec.topology.build_weights(p.L, graph), dtype)
                adj = jnp.asarray(graph.adj, dtype)  # reprolint: allow=RL002 — dense branch: use_sparse() declined, L below the sparse tier
        with span("materialize.spectral_init"):
            init = decentralized_spectral_init(
                jax.random.fold_in(key, 1), Xg_init, yg_init, W,
                kappa=prob.kappa, mu=prob.mu, r=p.r, T_pm=spec.init.T_pm,
                T_con=spec.init.T_con, broadcast=spec.init.broadcast)
        with span("materialize.eta"):
            eta = _resolve_spec_eta(spec, init)
        return Materialized(problem=prob, Xg=Xg, yg=yg, graph=graph, W=W,
                            adj=adj, init=init, eta=eta)


def _resolve_spec_eta(spec: ExperimentSpec, init) -> float:
    return resolve_eta(spec.solver.eta, spec.problem.n, R_diag=init.R_diag,
                       L=spec.problem.L, c_eta=spec.solver.c_eta)


def comm_time_axis(spec: ExperimentSpec, solver: SolverDef,
                   graph: Graph) -> np.ndarray:
    """Cumulative emulated wall-clock per outer iteration, priced from
    the solver's CombineRule comm signature under the spec's network
    model (one d×r exchange per neighbour per round).  Solvers that
    consume ``local_steps`` (beyond_central) pay that many compute
    units per outer iteration — the comm savings are not free local
    work."""
    p, c = spec.problem, spec.comm
    compute = c.compute_s_per_iter
    if "local_steps" in solver.spec_kwargs:
        compute *= spec.solver.local_steps
    # payload context: compressed rules fill entries_per_round /
    # bytes_per_entry from these, base rules ignore them
    sig = solver.signature(spec.solver.T_con, d=p.d, r=p.r,
                           compression=spec.solver.compression,
                           compression_k=spec.solver.compression_k,
                           event_threshold=spec.solver.event_threshold)
    return _cm.time_axis_from_signature(
        sig, spec.solver.T_GD, p.d, p.r,
        p.L, graph.max_degree, compute,
        model=_COMM_MODELS[c.model], rng=c.rng())


def _system_model(spec: ExperimentSpec) -> _cm.NetworkModel:
    """The comm model with the SystemSpec's link overrides applied."""
    model = _COMM_MODELS[spec.comm.model]
    s = spec.system
    if s is not None and (s.latency_s is not None
                         or s.jitter_std_s is not None):
        model = dataclasses.replace(
            model,
            latency_s=(model.latency_s if s.latency_s is None
                       else s.latency_s),
            jitter_std_s=(model.jitter_std_s if s.jitter_std_s is None
                          else s.jitter_std_s))
    return model


def system_time_axis(spec: ExperimentSpec, solver: SolverDef, graph: Graph,
                     avail: np.ndarray | None = None,
                     send_frac: np.ndarray | None = None) -> np.ndarray:
    """Simulated wall-clock axis under the spec's :class:`SystemSpec` —
    the event-driven clock of :mod:`repro.core.system_clock` replacing
    the closed-form pricing.  ``avail`` reuses a mask the solver run
    already materialized (one fault schedule for trajectory AND time);
    ``send_frac`` feeds the event rule's measured per-iteration trigger
    rate into the wire pricing.  Non-gossip patterns (central / no
    communication) keep the closed-form axis under the overridden link
    model: the clock simulates neighbour gossip only."""
    p, c, s = spec.problem, spec.comm, spec.system
    T_GD = spec.solver.T_GD
    compute = c.compute_s_per_iter
    if "local_steps" in solver.spec_kwargs:
        compute *= spec.solver.local_steps
    sig = solver.signature(spec.solver.T_con, d=p.d, r=p.r,
                           compression=spec.solver.compression,
                           compression_k=spec.solver.compression_k,
                           event_threshold=spec.solver.event_threshold)
    model = _system_model(spec)
    if sig.pattern in ("central", "none") or sig.rounds_per_iter == 0:
        return _cm.time_axis_from_signature(
            sig, T_GD, p.d, p.r, p.L, graph.max_degree, compute,
            model=model, rng=c.rng())
    if avail is None:
        avail = (s.availability_mask(T_GD, p.L) if solver.takes_avail
                 else np.ones((T_GD, p.L), bool))
    entries = sig.entries_per_round
    return _sysclock.simulated_time_axis(
        avail=avail, rounds_per_iter=sig.rounds_per_iter,
        neighbors=graph.neighbor_lists(), model=model,
        compute_s_per_iter=compute, speeds=s.node_speeds(p.L),
        straggler_prob=s.straggler_prob,
        straggler_factor=s.straggler_factor,
        n_entries=p.d * p.r if entries is None else entries,
        bytes_per_entry=sig.bytes_per_entry,
        rng=np.random.default_rng([c.seed, s.seed]),
        send_fraction=send_frac)


def run_experiment(spec: ExperimentSpec, key=None, *, engine=None,
                   materialized: Materialized | None = None,
                   checkpoint_every: int | None = None,
                   checkpoint_dir: str | None = None) -> Trace:
    """Materialize ``spec`` and run it end to end.

    ``engine`` optionally injects a pre-built :class:`AltgdminEngine`
    (must agree with ``spec.engine.backend`` if both are given);
    otherwise one is constructed from the spec.

    ``materialized`` optionally reuses an earlier :func:`materialize`
    result — the sweep-driver path, where the four solvers of one figure
    cell share problem/topology/init and should not pay the setup (data
    generation + T_pm power iterations) four times.  The caller must
    pass a materialization of a spec sharing this spec's problem /
    topology / init sub-specs and key; η is re-resolved from this spec's
    SolverSpec either way.

    ``checkpoint_every`` (with ``checkpoint_dir``) publishes U snapshots
    for the serving subsystem: the spectral init at step 0, then the
    node bases every that-many outer iterations (and at T_GD), each a
    crash-safe checkpoint via
    :func:`repro.serving.publisher.publish_representation`.  The run is
    executed in segments of that length with the U iterate chained
    through, so a server can hot-swap to fresher U's while the solver
    keeps refining (the drifting-U continual mode).  Solvers whose scan
    carry is just U (dif/dec/dgd/centralized, partial/pushsum) produce
    BIT-IDENTICAL trajectories to the unsegmented run (pinned in
    tests/test_serving.py); solvers carrying auxiliary state
    (exact_diffusion's ψ, the compressed rules' public copies,
    stale_gossip's queue) re-anchor that state at segment boundaries.
    Simulator substrate only; incompatible with ``n_folds > 1`` (the
    fold schedule restarts per segment).
    """
    with span("run_experiment"):
        from repro.core.engine import resolve_engine
        solver = get_solver(spec.solver.name)
        # spec-only validation runs BEFORE the expensive materialization so
        # an invalid sweep cell fails without paying the setup liturgy: a
        # non-default solver knob on a solver that ignores it must raise
        # instead of silently running without it
        for field, default in (("local_steps", 1), ("compression", None),
                               ("compression_k", 0), ("event_threshold", 0.0),
                               ("consensus_gamma", 1.0)):
            value = getattr(spec.solver, field)
            if value != default and field not in solver.spec_kwargs:
                raise ValueError(
                    f"solver {solver.name!r} does not consume {field} "
                    f"(got {field}={value}); only solvers declaring it in "
                    f"spec_kwargs honor the field")
        # availability: the SystemSpec's fault schedule feeds the
        # dropout-tolerant solvers; a faulty schedule on a solver with no
        # notion of dropped nodes must raise, not silently run fault-free
        if (spec.system is not None and not spec.system.is_always_on
                and not solver.takes_avail):
            raise ValueError(
                f"spec.system schedules node dropout but solver "
                f"{solver.name!r} cannot consume an availability mask; use "
                f"one of the dropout-tolerant solvers (dif_partial / "
                f"dif_stale / dif_pushsum)")
        avail_np = None
        if solver.takes_avail:
            sys_spec = spec.system if spec.system is not None else SystemSpec()
            avail_np = sys_spec.availability_mask(spec.solver.T_GD,
                                                  spec.problem.L)
        if checkpoint_every is not None:
            if checkpoint_dir is None:
                raise ValueError("checkpoint_every needs checkpoint_dir")
            if checkpoint_every < 1:
                raise ValueError(f"checkpoint_every must be >= 1, got "
                                 f"{checkpoint_every}")
            if spec.substrate == "mesh":
                raise ValueError("checkpoint publishing runs on "
                                 "substrate='simulator' only")
            if spec.problem.n_folds > 1:
                raise ValueError("checkpoint_every segments the run, which "
                                 "would restart the n_folds sample-split "
                                 "schedule; use n_folds <= 1")
        mat = materialize(spec, key) if materialized is None else materialized
        eta = _resolve_spec_eta(spec, mat.init)
        eng = resolve_engine(engine, spec.engine.backend,
                             blk_d=spec.engine.blk_d)
        with span("run_experiment.solve"):
            if spec.substrate == "mesh":
                result = _run_mesh(spec, solver, mat, eng, eta, avail=avail_np)
            elif checkpoint_every is not None:
                result = _run_segmented(spec, mat, eng, eta, avail=avail_np,
                                        every=checkpoint_every,
                                        directory=checkpoint_dir)
            else:
                result = simulate(spec, mat, engine=eng, avail=avail_np)
        with span("run_experiment.time_axis"):
            if spec.system is not None:
                sf = getattr(result, "send_frac", None)
                time_axis = system_time_axis(
                    spec, solver, mat.graph, avail=avail_np,
                    send_frac=None if sf is None else np.asarray(sf))
                source = "simulated"
            else:
                time_axis = comm_time_axis(spec, solver, mat.graph)
                source = "closed_form"
        return Trace(spec=spec, U_nodes=result.U_nodes, B_nodes=result.B_nodes,
                     sd_max=np.asarray(result.sd_max),
                     sd_mean=np.asarray(result.sd_mean),
                     spread=np.asarray(result.spread), eta=result.eta,
                     time_axis=time_axis, materialized=mat,
                     time_axis_source=source)


def simulate(spec: ExperimentSpec, mat: Materialized, *, engine=None,
             U0: jax.Array | None = None, T_GD: int | None = None,
             avail: np.ndarray | None = None) -> RunResult:
    """The simulator substrate's solver call, as :func:`run_experiment`
    makes it: the engine from ``spec.engine`` (or ``engine``), η from the
    spec and ``mat.init``, the solver's spec kwargs.  ``U0`` and ``T_GD``
    default to the init and the spec's, for callers that run a segment;
    ``avail`` is the availability mask of the dropout-tolerant solvers."""
    from repro.core.engine import resolve_engine
    solver = get_solver(spec.solver.name)
    eng = resolve_engine(engine, spec.engine.backend,
                         blk_d=spec.engine.blk_d)
    extra = {k: getattr(spec.solver, k) for k in solver.spec_kwargs}
    if avail is not None:
        extra["avail"] = jnp.asarray(avail)
    U0 = mat.init.U0 if U0 is None else U0
    # reprolint: allow=RL002 — Materialized.adj field: SparseGraph on the sparse path, dense only below the use_sparse gate
    return solver.call(U0, mat.Xg, mat.yg, mat.W, mat.adj,
                       eta=_resolve_spec_eta(spec, mat.init),
                       T_GD=spec.solver.T_GD if T_GD is None else T_GD,
                       T_con=spec.solver.T_con, U_star=mat.problem.U_star,
                       engine=eng, **extra)


def _run_segmented(spec: ExperimentSpec, mat: Materialized, eng,
                   eta: float, *,
                   avail: np.ndarray | None, every: int,
                   directory: str) -> RunResult:
    """The checkpoint-publishing driver: run the solver in segments of
    ``every`` iterations, chaining the U iterate and publishing a
    serving checkpoint after each segment (plus the step-0 init).  The
    availability schedule is sliced per segment so the fault sequence
    matches the unsegmented run row for row."""
    from repro.serving.publisher import publish_representation
    T_GD = spec.solver.T_GD
    publish_representation(directory, 0, mat.init.U0)
    U_cur = mat.init.U0
    chunks = []
    done = 0
    while done < T_GD:
        seg = min(every, T_GD - done)
        res = simulate(spec, mat, engine=eng, U0=U_cur, T_GD=seg,
                       avail=None if avail is None
                       else avail[done:done + seg])
        done += seg
        publish_representation(directory, done, res.U_nodes)
        chunks.append(res)
        U_cur = res.U_nodes
    def cat(name):
        return jnp.concatenate([getattr(c, name) for c in chunks])

    sfs = [c.send_frac for c in chunks]
    return RunResult(chunks[-1].U_nodes, chunks[-1].B_nodes,
                     cat("sd_max"), cat("sd_mean"), cat("spread"), eta,
                     send_frac=(jnp.concatenate(sfs)
                                if all(s is not None for s in sfs)
                                else None))


def _run_mesh(spec: ExperimentSpec, solver: SolverDef, mat: Materialized,
              eng, eta: float, avail: np.ndarray | None = None) -> RunResult:
    topo, p = spec.topology, spec.problem
    if not solver.mesh_capable:
        raise ValueError(f"solver {solver.name!r} has no mesh runtime; "
                         f"use substrate='simulator'")
    if p.n_folds > 1:
        raise ValueError("substrate='mesh' does not support sample "
                         "splitting (n_folds > 1)")
    devices = _mesh_devices(spec)
    n_dev = len(devices)
    if p.L != n_dev:
        if solver.virtual_mesh_fn is not None and p.L % n_dev == 0:
            return _run_virtual_mesh(spec, solver, mat, eng, eta, devices,
                                     avail=avail)
        raise ValueError(f"substrate='mesh' needs one device per node: "
                         f"L={p.L} but the mesh has {n_dev} devices "
                         f"(the virtual-node tier needs a solver with a "
                         f"virtual mesh runtime and n_dev | L)")
    mesh = jax.make_mesh((p.L,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices)
    kw = {k: getattr(spec.solver, k) for k in solver.spec_kwargs}
    if avail is not None:
        kw.update(avail=jnp.asarray(avail))
    if topo.weights == "circulant":
        # mesh-native uniform weights: each shift one collective-permute
        kw.update(shifts=topo.shifts, self_weight=topo.self_weight)
    elif solver.topology == "adj":
        # the solver averages neighbours (excl. self): lower the same
        # row-stochastic adj/deg matrix the simulator driver builds
        # reprolint: allow=RL002 — one-node-per-device mesh tier: L == device count, far below the sparse tier
        kw.update(W=np.asarray(_consensus.neighbor_average_matrix(mat.adj)))
    else:
        # arbitrary weighted topology: the consensus layer decomposes W
        # into per-shift, per-device weights (metropolis/lazy/... rows)
        kw.update(W=np.asarray(mat.W))
    return solver.mesh_fn(
        mat.init.U0, mat.Xg, mat.yg, mesh, "nodes", eta=eta,
        T_GD=spec.solver.T_GD, T_con=spec.solver.T_con,
        engine=eng, U_star=mat.problem.U_star, **kw)


def _mesh_devices(spec: ExperimentSpec) -> list:
    """The mesh substrate's devices: the first ``spec.n_devices`` of
    ``jax.devices()``, or all of them where the spec leaves it None."""
    devices = jax.devices()
    if spec.n_devices is None:
        return devices
    if len(devices) < spec.n_devices:
        raise ValueError(f"the spec deploys over n_devices="
                         f"{spec.n_devices} but JAX sees {len(devices)} "
                         f"devices")
    return devices[:spec.n_devices]


def _run_virtual_mesh(spec: ExperimentSpec, solver: SolverDef,
                      mat: Materialized, eng, eta: float, devices: list,
                      avail: np.ndarray | None = None) -> RunResult:
    """The virtual-node mesh tier: L = n_dev × block, contiguous blocks
    of virtual nodes per device — co-located gossip is an on-device
    segment-sum, only cross-device edge classes pay collective-permutes.
    Any mixing matrix (dense or SparseWeights) decomposes; the W is the
    SAME one the simulator mixes with (for ``"adj"`` solvers, the same
    row-stochastic neighbour average the simulator builds), so
    trajectories agree to the consensus layer's parity tolerance."""
    from repro.distributed.mixing import SparseWeights
    if solver.topology == "adj":
        # reprolint: allow=RL002 — Materialized.adj field: SparseGraph on the sparse path, dense only below the use_sparse gate
        W = np.asarray(_consensus.neighbor_average_matrix(mat.adj))
    else:
        W = mat.W
    if not isinstance(W, SparseWeights):
        W = SparseWeights.from_dense(np.asarray(W))
    with span("virtual.topology") as sp:
        vt = _consensus.VirtualTopology.from_weights(W, len(devices))
        sp.count(devices=vt.n_dev, block=vt.block,
                 shift_classes=len(vt.dev_shifts),
                 cross_edges=vt.n_cross_entries)
    mesh = jax.make_mesh((vt.n_dev,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices)
    kw = {k: getattr(spec.solver, k) for k in solver.spec_kwargs}
    if avail is not None:
        kw.update(avail=jnp.asarray(avail))
    return solver.virtual_mesh_fn(
        mat.init.U0, mat.Xg, mat.yg, mesh, "nodes", vt=vt, eta=eta,
        T_GD=spec.solver.T_GD, T_con=spec.solver.T_con,
        engine=eng, U_star=mat.problem.U_star, **kw)
