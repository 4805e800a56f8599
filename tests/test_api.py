"""Declarative experiment API: spec JSON round-trip, solver-registry
completeness, legacy-wrapper parity (bit-identical on xla-ref), substrate
validation, and the attached comm-model wall-clock axis."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.api import (CommSpec, EngineSpec, ExperimentSpec, InitSpec,
                       ProblemSpec, SolverSpec, SOLVERS, SolverDef,
                       TopologySpec, get_solver, materialize,
                       register_solver, run_experiment, simulate,
                       solver_names)
from repro.core import (centralized_altgdmin, dec_altgdmin, dgd_altgdmin,
                        dif_altgdmin)
from repro.core.engine import AltgdminEngine

TINY = ExperimentSpec(
    problem=ProblemSpec(d=40, T=12, r=3, n=20, L=4, kappa=1.5),
    topology=TopologySpec(family="erdos_renyi", p=0.6, seed=1,
                          weights="metropolis"),
    init=InitSpec(T_pm=10, T_con=5),
    solver=SolverSpec(name="dif_altgdmin", T_GD=15, T_con=2),
    engine=EngineSpec(backend="xla-ref"))


def _with_solver(spec, name):
    return dataclasses.replace(
        spec, solver=dataclasses.replace(spec.solver, name=name))


# ------------------------------------------------------- JSON round-trip

def test_spec_json_round_trip():
    spec = dataclasses.replace(
        TINY,
        topology=TopologySpec(family="ring", weights="circulant",
                              shifts=(-1, 1), self_weight=0.5),
        comm=CommSpec(model="tpu-ici", compute_s_per_iter=1e-4),
        substrate="simulator", name="rt")
    text = spec.to_json()
    back = ExperimentSpec.from_json(text)
    assert back == spec
    # through a generic JSON dump/load too (tuples become lists and are
    # normalized back)
    back2 = ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert back2 == spec
    assert isinstance(back2.topology.shifts, tuple)
    # a mesh deployment that names its chip count
    mesh4 = dataclasses.replace(spec, substrate="mesh", n_devices=4)
    assert ExperimentSpec.from_json(mesh4.to_json()) == mesh4
    assert mesh4.to_dict()["n_devices"] == 4 and spec.n_devices is None


def test_spec_from_dict_rejects_unknown_fields():
    d = TINY.to_dict()
    d["problem"]["bogus"] = 1
    with pytest.raises(ValueError, match="bogus"):
        ExperimentSpec.from_dict(d)


def test_spec_validation():
    with pytest.raises(ValueError):
        ProblemSpec(T=10, L=4)                       # L does not divide T
    with pytest.raises(ValueError):
        TopologySpec(family="smallworld")
    with pytest.raises(ValueError):
        TopologySpec(weights="chebyshev")
    with pytest.raises(ValueError):
        CommSpec(model="carrier-pigeon")
    with pytest.raises(ValueError):
        ExperimentSpec(substrate="abacus")
    # circulant weights must gossip over a matching circulant graph
    with pytest.raises(ValueError, match="circulant"):
        TopologySpec(family="erdos_renyi", weights="circulant")
    with pytest.raises(ValueError, match="circulant"):
        TopologySpec(family="ring", weights="circulant", shifts=(-2, 2))
    t = TopologySpec(family="circulant", weights="circulant",
                     shifts=(-2, 2))
    assert t.build_graph(8).degrees.tolist() == [2] * 8


# ------------------------------------------------------------- registry

def test_registry_covers_all_four_algorithms():
    assert set(solver_names()) >= {"dif_altgdmin", "dec_altgdmin",
                                   "centralized_altgdmin", "dgd_altgdmin"}


@pytest.mark.parametrize("name", sorted(SOLVERS))
def test_every_registered_solver_runs(name):
    trace = run_experiment(_with_solver(TINY, name), key=0)
    T_GD = TINY.solver.T_GD
    assert trace.sd_max.shape == (T_GD,)
    assert trace.sd_mean.shape == (T_GD,)
    assert trace.spread.shape == (T_GD,)
    assert np.all(np.isfinite(trace.sd_max))
    assert trace.time_axis.shape == (T_GD,)
    assert np.all(np.diff(trace.time_axis) > 0)      # cumulative clock
    assert trace.eta > 0
    L = TINY.problem.L if SOLVERS[name].decentralized else 1
    assert trace.U_nodes.shape[0] == L


def test_get_solver_unknown():
    with pytest.raises(ValueError, match="unknown solver"):
        get_solver("admm")


def test_register_solver_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        register_solver(SolverDef(name="dif_altgdmin",
                                  fn=dif_altgdmin))


# ----------------------------------------------- legacy-wrapper parity

_LEGACY = {
    "dif_altgdmin": lambda m, kw: dif_altgdmin(
        m.init.U0, m.Xg, m.yg, m.W, T_con=TINY.solver.T_con, **kw),
    "dec_altgdmin": lambda m, kw: dec_altgdmin(
        m.init.U0, m.Xg, m.yg, m.W, T_con=TINY.solver.T_con, **kw),
    "centralized_altgdmin": lambda m, kw: centralized_altgdmin(
        m.init.U0[0], m.Xg, m.yg, **kw),
    "dgd_altgdmin": lambda m, kw: dgd_altgdmin(
        m.init.U0, m.Xg, m.yg, m.adj, **kw),
}


@pytest.mark.parametrize("name", sorted(_LEGACY))
def test_run_experiment_matches_legacy_bit_identical(name):
    """Acceptance: run_experiment reproduces the legacy driver's
    trajectory bit-identically on xla-ref — no tolerance."""
    trace = run_experiment(_with_solver(TINY, name), key=7)
    m = trace.materialized
    legacy = _LEGACY[name](m, dict(eta=m.eta, T_GD=TINY.solver.T_GD,
                                   U_star=m.problem.U_star,
                                   backend="xla-ref"))
    np.testing.assert_array_equal(np.asarray(trace.U_nodes),
                                  np.asarray(legacy.U_nodes))
    np.testing.assert_array_equal(np.asarray(trace.B_nodes),
                                  np.asarray(legacy.B_nodes))
    np.testing.assert_array_equal(trace.sd_max,
                                  np.asarray(legacy.sd_max))
    np.testing.assert_array_equal(trace.spread,
                                  np.asarray(legacy.spread))
    assert trace.eta == legacy.eta


def test_shared_materialization_across_solvers():
    """Solvers differing only in SolverSpec.name see the same problem,
    graph, init, and η (the paper's figure-cell contract)."""
    a = materialize(_with_solver(TINY, "dif_altgdmin"), key=3)
    b = materialize(_with_solver(TINY, "dgd_altgdmin"), key=3)
    np.testing.assert_array_equal(np.asarray(a.Xg), np.asarray(b.Xg))
    np.testing.assert_array_equal(np.asarray(a.init.U0),
                                  np.asarray(b.init.U0))
    np.testing.assert_array_equal(a.graph.adj, b.graph.adj)
    assert a.eta == b.eta


def test_run_experiment_deterministic():
    t1 = run_experiment(TINY, key=5)
    t2 = run_experiment(TINY, key=5)
    np.testing.assert_array_equal(np.asarray(t1.U_nodes),
                                  np.asarray(t2.U_nodes))
    np.testing.assert_array_equal(t1.time_axis, t2.time_axis)


def test_sample_split_spec_runs():
    spec = dataclasses.replace(
        TINY, problem=dataclasses.replace(TINY.problem, n_folds=2))
    trace = run_experiment(spec, key=0)
    assert np.all(np.isfinite(trace.sd_max))
    # Algorithm 2 precedes the fold partition: the spectral init sees
    # the full unsplit data, so it matches the unsplit spec's init
    unsplit = materialize(TINY, key=0)
    split = materialize(spec, key=0)
    np.testing.assert_array_equal(np.asarray(split.init.U0),
                                  np.asarray(unsplit.init.U0))
    assert split.Xg.ndim == 5                    # solver data is folded


def test_materialized_reuse_matches_fresh_run():
    """The sweep-driver path: passing a shared Materialized must give
    the same Trace as materializing inside run_experiment."""
    mat = materialize(TINY, key=4)
    for name in sorted(SOLVERS):
        spec = _with_solver(TINY, name)
        fresh = run_experiment(spec, key=4)
        shared = run_experiment(spec, key=4, materialized=mat)
        np.testing.assert_array_equal(np.asarray(fresh.U_nodes),
                                      np.asarray(shared.U_nodes))
        assert fresh.eta == shared.eta


def test_simulate_under_jit_matches_run_experiment():
    """``simulate`` is the runner's simulator call; traced with the data
    as arguments it still resolves η on the concrete init and gives the
    run's iterate."""
    mat = materialize(TINY, key=2)
    run = run_experiment(TINY, key=2, materialized=mat)

    def sim(Xg, yg):
        res = simulate(TINY, dataclasses.replace(mat, Xg=Xg, yg=yg))
        return res.U_nodes, res.eta

    U, eta = jax.jit(sim)(mat.Xg, mat.yg)
    assert float(eta) == run.eta
    np.testing.assert_allclose(np.asarray(U), np.asarray(run.U_nodes),
                               rtol=0, atol=1e-10)


# --------------------------------------------------- engine & substrate

def test_engine_injection_conflict():
    spec = dataclasses.replace(TINY,
                               engine=EngineSpec(backend="pallas-interpret"))
    with pytest.raises(ValueError, match="conflicting"):
        run_experiment(spec, key=0, engine=AltgdminEngine("xla-ref"))


def test_mesh_substrate_validation():
    # every registered solver now carries a mesh runtime (PR 4)
    assert all(SOLVERS[n].mesh_fn is not None for n in solver_names()), [
        n for n in solver_names() if SOLVERS[n].mesh_fn is None]
    # ... but user-registered solvers without one still fail loudly
    if "sim_only_solver" not in SOLVERS:
        register_solver(SolverDef(name="sim_only_solver",
                                  fn=dif_altgdmin, topology="W"))
    mesh_spec = dataclasses.replace(TINY, substrate="mesh")
    with pytest.raises(ValueError, match="no mesh runtime"):
        run_experiment(_with_solver(mesh_spec, "sim_only_solver"), key=0)
    # weights are no longer restricted to circulant — with the right
    # device count a metropolis ER spec dispatches (subprocess tests
    # assert the parity).  When L != device_count, every program-derived
    # solver dispatches on the virtual-node tier as long as the node
    # count divides evenly over devices (since PR 9 that is ALL
    # registered solvers); only a hand-registered def without a virtual
    # runtime fails loudly on the node/device check.
    if "mesh_only_solver" not in SOLVERS:
        register_solver(SolverDef(name="mesh_only_solver",
                                  fn=dif_altgdmin,
                                  mesh_fn=SOLVERS["dif_altgdmin"].mesh_fn,
                                  topology="W"))
    if jax.device_count() != TINY.problem.L:
        with pytest.raises(ValueError, match="device"):
            run_experiment(_with_solver(mesh_spec, "mesh_only_solver"),
                           key=0)
        if TINY.problem.L % jax.device_count() == 0:
            trace = run_experiment(mesh_spec, key=0)   # virtual tier
            assert trace.U_nodes.shape[0] == TINY.problem.L
            dgd = run_experiment(_with_solver(mesh_spec, "dgd_altgdmin"),
                                 key=0)                # newly virtual-capable
            assert dgd.U_nodes.shape[0] == TINY.problem.L


# --------------------------------------------------------- wall clock

def test_comm_axis_prices_patterns_differently():
    """dgd gossips once per iteration, dif T_con times, centralized pays
    gather+broadcast — the attached wall-clock axes must reflect that."""
    dif = run_experiment(_with_solver(TINY, "dif_altgdmin"), key=0)
    dgd = run_experiment(_with_solver(TINY, "dgd_altgdmin"), key=0)
    assert dgd.time_axis[-1] < dif.time_axis[-1]    # T_con=2 vs 1 round
    ici = dataclasses.replace(TINY, comm=CommSpec(model="tpu-ici"))
    fast = run_experiment(ici, key=0)
    assert fast.time_axis[-1] < dif.time_axis[-1]   # 50 GB/s vs 1 Gbps
