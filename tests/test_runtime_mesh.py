"""Mesh runtime ≡ simulator: Dif-AltGDmin with shard_map/ppermute gossip
must match the simulator run with the circulant ring W bit-for-bit-ish
(subprocess: 8 fake devices, one node per device), on every engine
backend — the mesh runtime routes its min-B/gradient phases through the
same AltgdminEngine as the simulator."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, "src")
    import jax.numpy as jnp, numpy as np
    from repro.core import (generate_problem, node_view,
                            decentralized_spectral_init, dif_altgdmin,
                            subspace_distance)
    from repro.core import dif_altgdmin_mesh
    from repro.core.altgdmin import resolve_eta
    from repro.distributed import circulant_weights

    L = 8
    prob = generate_problem(jax.random.PRNGKey(0), d=60, T=32, r=3, n=25,
                            L=L, kappa=1.5)
    Xg, yg = node_view(prob)
    W = jnp.asarray(circulant_weights(L, (-1, 1)))
    init = decentralized_spectral_init(
        jax.random.PRNGKey(1), Xg, yg, W, kappa=prob.kappa, mu=prob.mu,
        r=prob.r, T_pm=20, T_con=8)
    eta = resolve_eta(None, prob.n, R_diag=init.R_diag, L=L)

    sim = dif_altgdmin(init.U0, Xg, yg, W, eta=eta, T_GD=150, T_con=2,
                       U_star=prob.U_star)

    mesh = jax.make_mesh((L,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    U_hw, B_hw = dif_altgdmin_mesh(init.U0, Xg, yg, mesh, "nodes",
                                   eta=eta, T_GD=150, T_con=2)

    # identical trajectories (same arithmetic, different lowering)
    np.testing.assert_allclose(np.asarray(U_hw), np.asarray(sim.U_nodes),
                               rtol=1e-8, atol=1e-9)
    np.testing.assert_allclose(np.asarray(B_hw), np.asarray(sim.B_nodes),
                               rtol=1e-7, atol=1e-8)
    # and it actually converged
    sd = max(float(subspace_distance(U, prob.U_star)) for U in U_hw)
    assert sd < 5e-2, sd  # 150 iters suffice here
    # the lowering uses collective-permutes (the ICI gossip)
    spec = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("nodes"))
    lowered = jax.jit(
        lambda u, x, y: dif_altgdmin_mesh(u, x, y, mesh, "nodes", eta=eta,
                                          T_GD=2, T_con=2),
        in_shardings=(spec, spec, spec)).lower(init.U0, Xg, yg)
    assert "collective-permute" in lowered.compile().as_text()
    print("OK", sd)
""")


def test_mesh_runtime_matches_simulator():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "OK" in r.stdout


# ------------------------------------------------- mesh through engine

ENGINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, "src")
    import dataclasses
    import numpy as np
    from repro.api import (ExperimentSpec, ProblemSpec, TopologySpec,
                           InitSpec, SolverSpec, EngineSpec,
                           run_experiment)
    import repro.core.engine as engine_mod

    backend = sys.argv[1]

    # count engine phase calls so "routes through AltgdminEngine" is
    # asserted structurally, not just numerically
    calls = {"min_grad": 0}
    orig = engine_mod.AltgdminEngine.min_grad
    def counting(self, *a, **kw):
        calls["min_grad"] += 1
        return orig(self, *a, **kw)
    engine_mod.AltgdminEngine.min_grad = counting

    spec = ExperimentSpec(
        problem=ProblemSpec(d=48, T=32, r=3, n=25, L=8, kappa=1.5),
        topology=TopologySpec(family="ring", weights="circulant"),
        init=InitSpec(T_pm=15, T_con=6),
        solver=SolverSpec(name="dif_altgdmin", T_GD=60, T_con=2),
        engine=EngineSpec(backend=backend))

    sim = run_experiment(spec, key=0)
    calls_sim = calls["min_grad"]
    hw = run_experiment(dataclasses.replace(spec, substrate="mesh"),
                        key=0)
    assert calls["min_grad"] > calls_sim, "mesh run bypassed the engine"

    # acceptance: mesh matches the simulator to <= 1e-7 on this backend
    drift = float(np.max(np.abs(np.asarray(hw.U_nodes)
                                - np.asarray(sim.U_nodes))))
    assert drift <= 1e-7, f"U drift {drift} on {backend}"
    np.testing.assert_allclose(hw.sd_max, sim.sd_max,
                               rtol=1e-7, atol=1e-9)
    np.testing.assert_allclose(hw.spread, sim.spread,
                               rtol=1e-6, atol=1e-9)
    # B is emitted by the engine in f32 on fused backends, so allow one
    # f32 ULP there; xla-ref keeps the f64 tolerance
    b_tol = (dict(rtol=1e-7, atol=1e-8) if backend == "xla-ref"
             else dict(rtol=1e-5, atol=1e-6))
    np.testing.assert_allclose(np.asarray(hw.B_nodes),
                               np.asarray(sim.B_nodes), **b_tol)
    # the mesh Trace carries the full metric set, same shapes
    assert hw.sd_max.shape == sim.sd_max.shape
    assert hw.time_axis.shape == sim.time_axis.shape
    print("OK", backend, drift)
""")


@pytest.mark.parametrize("backend", ["xla-ref", "pallas-interpret"])
def test_mesh_through_engine_matches_simulator(backend):
    """The same ExperimentSpec run on substrate='mesh' must match the
    simulator to <= 1e-7 while routing min-B/grad through the engine —
    on the seed-numerics backend AND the fused kernel backend."""
    r = subprocess.run([sys.executable, "-c", ENGINE_SCRIPT, backend],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert f"OK {backend}" in r.stdout


# ------------------------------------------- dec/dgd mesh runtimes

DEC_DGD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, "src")
    import dataclasses
    import numpy as np
    from repro.api import (ExperimentSpec, ProblemSpec, TopologySpec,
                           InitSpec, SolverSpec, EngineSpec,
                           run_experiment)

    solver, backend = sys.argv[1], sys.argv[2]
    spec = ExperimentSpec(
        problem=ProblemSpec(d=48, T=32, r=3, n=25, L=8, kappa=1.5),
        topology=TopologySpec(family="ring", weights="circulant"),
        init=InitSpec(T_pm=15, T_con=6),
        solver=SolverSpec(name=solver, T_GD=60, T_con=2),
        engine=EngineSpec(backend=backend))

    sim = run_experiment(spec, key=0)
    hw = run_experiment(dataclasses.replace(spec, substrate="mesh"),
                        key=0)
    drift = float(np.max(np.abs(np.asarray(hw.U_nodes)
                                - np.asarray(sim.U_nodes))))
    assert drift <= 1e-7, f"U drift {drift} for {solver} on {backend}"
    np.testing.assert_allclose(hw.sd_max, sim.sd_max,
                               rtol=1e-7, atol=1e-9)
    print("OK", solver, backend, drift)
""")


@pytest.mark.parametrize("backend", ["xla-ref", "pallas-interpret"])
@pytest.mark.parametrize("solver", ["dec_altgdmin", "dgd_altgdmin"])
def test_dec_dgd_mesh_matches_simulator(solver, backend):
    """Acceptance: the newly mesh-capable solvers (combine-then-adjust
    and the DGD variation) match their simulator trajectories to <= 1e-7
    on both the seed-numerics and the fused kernel backend."""
    r = subprocess.run([sys.executable, "-c", DEC_DGD_SCRIPT, solver,
                        backend],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert f"OK {solver} {backend}" in r.stdout


# ------------------------------- fused combine dispatch per gossip round

FUSED_COMBINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import sys
    sys.path.insert(0, "src")
    import jax.numpy as jnp, numpy as np
    from repro.core import generate_problem, node_view, \\
        decentralized_spectral_init
    from repro.core import dif_altgdmin_mesh
    from repro.distributed import circulant_weights
    from repro.kernels import ops

    # count trace-time gossip_combine dispatches: the round body of the
    # mesh mixer must contain exactly ONE fused K+1-way combine (not K
    # separate weighted-sum sweeps); lax.scan then runs it T_con times.
    calls = {"n": 0}
    orig = ops.gossip_combine
    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)
    ops.gossip_combine = counting

    L, T_con = 8, 3
    prob = generate_problem(jax.random.PRNGKey(0), d=32, T=16, r=3, n=20,
                            L=L, kappa=1.5, dtype=jnp.float32)
    Xg, yg = node_view(prob)
    W = jnp.asarray(circulant_weights(L, (-1, 1)), jnp.float32)
    init = decentralized_spectral_init(
        jax.random.PRNGKey(1), Xg, yg, W, kappa=prob.kappa, mu=prob.mu,
        r=prob.r, T_pm=10, T_con=4)
    mesh = jax.make_mesh((L,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    U, B = dif_altgdmin_mesh(init.U0, Xg, yg, mesh, "nodes", eta=1e-4,
                             T_GD=4, T_con=T_con,
                             backend="pallas-interpret")
    jax.block_until_ready(U)
    assert calls["n"] == 1, \\
        f"expected ONE fused combine in the gossip round body, " \\
        f"got {calls['n']}"
    assert np.all(np.isfinite(np.asarray(U)))

    # xla-ref keeps the exact unfused chain: no fused dispatch at all
    calls["n"] = 0
    U2, _ = dif_altgdmin_mesh(init.U0, Xg, yg, mesh, "nodes", eta=1e-4,
                              T_GD=4, T_con=T_con, backend="xla-ref")
    jax.block_until_ready(U2)
    assert calls["n"] == 0, calls["n"]
    # and the fused rounds agree with the exact chain (f32 tolerance)
    np.testing.assert_allclose(np.asarray(U), np.asarray(U2),
                               rtol=2e-4, atol=2e-5)
    print("OK fused-combine")
""")


def test_runtime_single_fused_combine_dispatch_per_round():
    """Acceptance: on pallas backends the mesh runtime issues ONE fused
    gossip_combine per gossip round (the K+1-way kernel) instead of the
    T_con x K weighted-sum chain; xla-ref keeps the exact chain."""
    r = subprocess.run([sys.executable, "-c", FUSED_COMBINE_SCRIPT],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "OK fused-combine" in r.stdout


# ------------------------------- arbitrary weighted topologies (PR 4)

WEIGHTED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, "src")
    import dataclasses
    import numpy as np
    from repro.api import (ExperimentSpec, ProblemSpec, TopologySpec,
                           InitSpec, SolverSpec, EngineSpec,
                           run_experiment)
    from repro.distributed import erdos_renyi

    solver, backend = sys.argv[1], sys.argv[2]
    # the graph must be genuinely irregular so the per-device weight
    # table (not the uniform scalar fast path) is what runs
    g = erdos_renyi(8, 0.45, seed=2)
    assert len({int(d) for d in g.degrees}) > 1, list(g.degrees)

    kw = {"local_steps": 2} if solver == "beyond_central" else {}
    spec = ExperimentSpec(
        problem=ProblemSpec(d=48, T=32, r=3, n=25, L=8, kappa=1.5),
        topology=TopologySpec(family="erdos_renyi", p=0.45, seed=2,
                              weights="metropolis"),
        init=InitSpec(T_pm=15, T_con=6),
        solver=SolverSpec(name=solver, T_GD=40, T_con=2, **kw),
        engine=EngineSpec(backend=backend))

    sim = run_experiment(spec, key=0)
    hw = run_experiment(dataclasses.replace(spec, substrate="mesh"),
                        key=0)
    U_sim = np.asarray(sim.U_nodes)
    U_hw = np.asarray(hw.U_nodes)
    if U_sim.shape[0] == 1:     # centralized: one U vs L identical rows
        U_sim = np.broadcast_to(U_sim, U_hw.shape)
    drift = float(np.max(np.abs(U_hw - U_sim)))
    assert drift <= 1e-7, f"U drift {drift} for {solver} on {backend}"
    np.testing.assert_allclose(hw.sd_max, sim.sd_max,
                               rtol=1e-7, atol=1e-9)
    print("OK", solver, backend, drift)
""")

ALL_SOLVERS = ["dif_altgdmin", "dec_altgdmin", "dgd_altgdmin",
               "centralized_altgdmin", "exact_diffusion", "beyond_central"]


@pytest.mark.parametrize("backend", ["xla-ref", "pallas-interpret"])
@pytest.mark.parametrize("solver", ALL_SOLVERS)
def test_weighted_topology_mesh_matches_simulator(solver, backend):
    """Acceptance (PR 4): every registered solver runs a
    Metropolis-weighted irregular-ER spec on the mesh substrate with
    <= 1e-7 parity to the simulator, on the seed-numerics backend AND
    the fused kernel backend — the consensus layer decomposes the
    arbitrary W into per-shift, per-device weights."""
    r = subprocess.run([sys.executable, "-c", WEIGHTED_SCRIPT, solver,
                        backend],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert f"OK {solver} {backend}" in r.stdout


WEIGHTED_COMBINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import sys
    sys.path.insert(0, "src")
    import jax.numpy as jnp, numpy as np
    from repro.core import generate_problem, node_view, \\
        decentralized_spectral_init
    from repro.core import dif_altgdmin_mesh
    from repro.distributed import erdos_renyi, metropolis_weights
    from repro.kernels import ops

    # weighted combines must stay ONE fused dispatch per gossip round:
    # the per-shift weight vector rides the kernel as an operand, not as
    # K separate axpy sweeps
    calls = {"n": 0}
    orig = ops.gossip_combine
    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)
    ops.gossip_combine = counting

    L, T_con = 8, 3
    g = erdos_renyi(L, 0.45, seed=2)
    assert len({int(d) for d in g.degrees}) > 1      # irregular
    W = jnp.asarray(metropolis_weights(g), jnp.float32)
    prob = generate_problem(jax.random.PRNGKey(0), d=32, T=16, r=3, n=20,
                            L=L, kappa=1.5, dtype=jnp.float32)
    Xg, yg = node_view(prob)
    init = decentralized_spectral_init(
        jax.random.PRNGKey(1), Xg, yg, W, kappa=prob.kappa, mu=prob.mu,
        r=prob.r, T_pm=10, T_con=4)
    mesh = jax.make_mesh((L,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    U, B = dif_altgdmin_mesh(init.U0, Xg, yg, mesh, "nodes", eta=1e-4,
                             T_GD=4, T_con=T_con, W=np.asarray(W),
                             backend="pallas-interpret")
    jax.block_until_ready(U)
    assert calls["n"] == 1, \\
        f"expected ONE fused weighted combine per gossip round, " \\
        f"got {calls['n']}"
    assert np.all(np.isfinite(np.asarray(U)))

    # xla-ref keeps the exact unfused chain: no fused dispatch at all
    calls["n"] = 0
    U2, _ = dif_altgdmin_mesh(init.U0, Xg, yg, mesh, "nodes", eta=1e-4,
                              T_GD=4, T_con=T_con, W=np.asarray(W),
                              backend="xla-ref")
    jax.block_until_ready(U2)
    assert calls["n"] == 0, calls["n"]
    # and the fused weighted rounds agree with the exact chain
    np.testing.assert_allclose(np.asarray(U), np.asarray(U2),
                               rtol=2e-4, atol=2e-5)
    print("OK weighted-combine")
""")


def test_weighted_combine_single_dispatch_per_round():
    """Acceptance (PR 4): the generalized per-shift-weight combine on an
    irregular Metropolis graph still lowers to ONE fused gossip_combine
    dispatch per gossip round on the pallas backends."""
    r = subprocess.run([sys.executable, "-c", WEIGHTED_COMBINE_SCRIPT],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "OK weighted-combine" in r.stdout


# --------------------------- compressed consensus rules (PR 5)

COMPRESSED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, "src")
    import dataclasses
    import numpy as np
    from repro.api import (ExperimentSpec, ProblemSpec, TopologySpec,
                           InitSpec, SolverSpec, EngineSpec,
                           run_experiment)

    solver, backend = sys.argv[1], sys.argv[2]
    kw = {"dif_topk": {"compression_k": 12},
          "dif_quantized": {"compression": "int8_stochastic"},
          "dif_event": {"event_threshold": 0.05}}[solver]
    # irregular weighted graph: the per-device weight table path AND the
    # compact-payload ppermute path run together
    spec = ExperimentSpec(
        problem=ProblemSpec(d=48, T=32, r=3, n=25, L=8, kappa=1.5),
        topology=TopologySpec(family="erdos_renyi", p=0.45, seed=2,
                              weights="metropolis"),
        init=InitSpec(T_pm=15, T_con=6),
        solver=SolverSpec(name=solver, T_GD=40, T_con=2, **kw),
        engine=EngineSpec(backend=backend))

    sim = run_experiment(spec, key=0)
    hw = run_experiment(dataclasses.replace(spec, substrate="mesh"),
                        key=0)
    drift = float(np.max(np.abs(np.asarray(hw.U_nodes)
                                - np.asarray(sim.U_nodes))))
    assert drift <= 1e-7, f"U drift {drift} for {solver} on {backend}"
    np.testing.assert_allclose(hw.sd_max, sim.sd_max,
                               rtol=1e-7, atol=1e-9)
    print("OK", solver, backend, drift)
""")

COMPRESSED_SOLVERS = ["dif_topk", "dif_quantized", "dif_event"]


@pytest.mark.parametrize("backend", ["xla-ref", "pallas-interpret"])
@pytest.mark.parametrize("solver", COMPRESSED_SOLVERS)
def test_compressed_mesh_matches_simulator(solver, backend):
    """Acceptance (PR 5): the compressed solvers — whose reference-copy
    error-feedback state rides the aux scan carry and whose COMPACT
    payloads (top-k rows + indices / int8 + scale / triggered resends)
    are what crosses the collective-permutes — match their simulator
    trajectories to <= 1e-7 on a Metropolis-weighted irregular-ER spec,
    on the seed-numerics backend AND the fused kernel backend."""
    r = subprocess.run([sys.executable, "-c", COMPRESSED_SCRIPT, solver,
                        backend],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert f"OK {solver} {backend}" in r.stdout


COMPRESSED_COMBINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import sys
    sys.path.insert(0, "src")
    import jax.numpy as jnp, numpy as np
    from repro.core import generate_problem, node_view, \\
        decentralized_spectral_init
    from repro.core import dif_topk_mesh
    from repro.distributed import circulant_weights
    from repro.kernels import ops
    from repro.kernels import compress as cpk

    # the compressed round must stay ONE fused gossip_combine dispatch
    # per round (after the compact-payload permutes + copy refresh), and
    # the compress_topk kernel is what encodes the payload
    calls = {"combine": 0, "topk": 0}
    orig_combine = ops.gossip_combine
    def counting_combine(*a, **kw):
        calls["combine"] += 1
        return orig_combine(*a, **kw)
    ops.gossip_combine = counting_combine
    orig_topk = cpk.compress_topk
    def counting_topk(*a, **kw):
        calls["topk"] += 1
        return orig_topk(*a, **kw)
    cpk.compress_topk = counting_topk

    L, T_con = 8, 3
    prob = generate_problem(jax.random.PRNGKey(0), d=32, T=16, r=3, n=20,
                            L=L, kappa=1.5, dtype=jnp.float32)
    Xg, yg = node_view(prob)
    W = jnp.asarray(circulant_weights(L, (-1, 1)), jnp.float32)
    init = decentralized_spectral_init(
        jax.random.PRNGKey(1), Xg, yg, W, kappa=prob.kappa, mu=prob.mu,
        r=prob.r, T_pm=10, T_con=4)
    mesh = jax.make_mesh((L,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    U, B = dif_topk_mesh(init.U0, Xg, yg, mesh, "nodes", eta=1e-4,
                         T_GD=4, T_con=T_con, compression_k=8,
                         backend="pallas-interpret")
    jax.block_until_ready(U)
    assert calls["combine"] == 1, \\
        f"expected ONE fused combine per compressed round, " \\
        f"got {calls['combine']}"
    assert calls["topk"] == 1, calls["topk"]
    assert np.all(np.isfinite(np.asarray(U)))

    # xla-ref keeps the exact unfused chain + reference encoder: no
    # fused kernel dispatches at all
    calls["combine"] = calls["topk"] = 0
    U2, _ = dif_topk_mesh(init.U0, Xg, yg, mesh, "nodes", eta=1e-4,
                          T_GD=4, T_con=T_con, compression_k=8,
                          backend="xla-ref")
    jax.block_until_ready(U2)
    assert calls["combine"] == 0 and calls["topk"] == 0, calls
    np.testing.assert_allclose(np.asarray(U), np.asarray(U2),
                               rtol=2e-4, atol=2e-5)
    print("OK compressed-combine")
""")


def test_compressed_combine_single_dispatch_per_round():
    """Acceptance (PR 5): compression does not unfuse the combine — on
    pallas backends each compressed gossip round is still ONE fused
    gossip_combine dispatch (plus the compress_topk payload encode);
    xla-ref keeps the exact chain with zero fused dispatches."""
    r = subprocess.run([sys.executable, "-c", COMPRESSED_COMBINE_SCRIPT],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "OK compressed-combine" in r.stdout


# ------------------------- dropout-tolerant consensus rules (PR 6)

SYSTEM_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, "src")
    import dataclasses
    import numpy as np
    from repro.api import (ExperimentSpec, ProblemSpec, TopologySpec,
                           InitSpec, SolverSpec, SystemSpec,
                           run_experiment)

    solver = sys.argv[1]
    spec = ExperimentSpec(
        problem=ProblemSpec(d=48, T=32, r=3, n=25, L=8, kappa=1.5),
        topology=TopologySpec(family="erdos_renyi", p=0.45, seed=2,
                              weights="metropolis"),
        init=InitSpec(T_pm=15, T_con=6),
        solver=SolverSpec(name=solver, T_GD=25, T_con=2),
        system=SystemSpec(availability="bernoulli", p_on=0.7, seed=7))

    # degenerate anchor: an always-on SystemSpec on the MESH substrate
    # reproduces the dense mesh run bit-for-bit (partial/stale)
    dense = run_experiment(dataclasses.replace(
        spec, solver=dataclasses.replace(spec.solver,
                                         name="dif_altgdmin"),
        system=None, substrate="mesh"), key=0)
    anchor = run_experiment(dataclasses.replace(
        spec, system=SystemSpec(), substrate="mesh"), key=0,
        materialized=dense.materialized)
    if solver in ("dif_partial", "dif_stale"):
        assert np.array_equal(np.asarray(anchor.U_nodes),
                              np.asarray(dense.U_nodes)), "anchor drift"
        np.testing.assert_array_equal(anchor.sd_max, dense.sd_max)
    else:
        np.testing.assert_allclose(anchor.sd_max, dense.sd_max,
                                   rtol=1e-8, atol=1e-10)

    # faulted run: one seeded 30%-dropout schedule, both substrates
    sim = run_experiment(spec, key=0, materialized=dense.materialized)
    hw = run_experiment(dataclasses.replace(spec, substrate="mesh"),
                        key=0, materialized=dense.materialized)
    drift = float(np.max(np.abs(np.asarray(hw.U_nodes)
                                - np.asarray(sim.U_nodes))))
    assert drift <= 2e-6, f"U drift {drift} for {solver}"
    np.testing.assert_allclose(hw.sd_max, sim.sd_max, atol=2e-6)
    for t in (sim, hw):
        assert np.all(np.isfinite(t.sd_max))
        assert np.all(np.diff(t.time_axis) > 0)
        assert t.time_axis_source == "simulated"
    np.testing.assert_array_equal(sim.time_axis, hw.time_axis)
    print("OK", solver, drift)
""")

SYSTEM_SOLVERS = ["dif_partial", "dif_stale", "dif_pushsum"]


@pytest.mark.parametrize("solver", SYSTEM_SOLVERS)
def test_dropout_mesh_matches_simulator(solver):
    """Acceptance (PR 6): the dropout-tolerant solvers — whose seeded
    availability mask rides the scan's xs on both substrates — (a)
    reduce to the dense mesh run bit-for-bit under an always-on
    SystemSpec (push-sum to float round-off: its ratio correction is
    different arithmetic), and (b) under seeded 30% Bernoulli dropout
    match the simulator trajectory to <= 2e-6 with a finite, strictly
    monotone, substrate-independent simulated time axis."""
    r = subprocess.run([sys.executable, "-c", SYSTEM_SCRIPT, solver],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1200)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert f"OK {solver}" in r.stdout
