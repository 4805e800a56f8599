#!/usr/bin/env python3
"""Smoke run of Dif-AltGDmin and the serving solve on a TPU.

One chip (no arguments), at the paper's Experiment-1 widths
(``configs/paper.EXPERIMENT1``: L=20, d=T=600, r=4, n=30, Erdős–Rényi
p=0.5, Metropolis weights), in float32 with x64 off and
``jax.default_matmul_precision("highest")``:

  * kernels  — the wire-compression and gossip kernels against their
    ``xla-ref`` oracles at those widths;
  * training — ``run_experiment`` with the compiled ``pallas`` engine,
    then with ``xla-ref`` on the same materialized problem.  sd_max must
    fall, the final iterates must agree within ``TOL_U``, and the
    compiled iteration must hold at least the program's dispatch budget
    of Pallas kernels (``tpu_custom_call``);
  * serving  — a ``ServingEngine`` on the trained basis answers ragged
    requests through ``pallas``, checked against the ``xla-ref`` solve.

Four chips (``--chips 4``) runs only Dif-AltGDmin on the mesh substrate
(L=4, one node per chip) and on the virtual-node tier (L=20, five per
chip), each against the simulator run of the same spec (U and the final
per-node B), and checks that the result is sharded over four distinct
devices.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check, a platform other than TPU, or an exception exits
non-zero without it.

    python3 chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# f32 tolerances, fixed before any chip run.  Both sides compute in f32
# at "highest" matmul precision and differ only in summation order
# (~sqrt(600)·eps ≈ 3e-6 relative per Gram/gradient sum); Dif-AltGDmin
# contracts, so the gap stays near that level instead of compounding.
TOL_U = 1e-4          # max |U_a − U_b| over entries of orthonormal bases
TOL_B = 1e-4          # max |B_a − B_b| / max |B_b| over the final refits
TOL_SERVE = 1e-4      # max |θ_a − θ_b| / max |θ_b| over served requests
TOL_KERNEL = 1e-5     # max |kernel − oracle| / max |oracle|
T_GD = 40             # outer iterations per training run
SEED = 0              # problem, init and request seed


class Checks:
    """Collects named pass/fail checks; prints each as it is made."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)


def experiment1_spec(*, backend: str, L: int | None = None,
                     substrate: str = "simulator"):
    from repro.api import (EngineSpec, ExperimentSpec, InitSpec, ProblemSpec,
                           SolverSpec, TopologySpec)
    from repro.configs.paper import EXPERIMENT1
    cfg = EXPERIMENT1[0]
    return ExperimentSpec(
        name=f"{cfg.name}_smoke",
        problem=ProblemSpec(d=cfg.d, T=cfg.T, r=cfg.r, n=cfg.n,
                            L=cfg.L if L is None else L, kappa=cfg.kappa,
                            dtype="float32"),
        topology=TopologySpec(family="erdos_renyi", p=cfg.p, seed=cfg.seed,
                              weights="metropolis"),
        init=InitSpec(T_pm=cfg.T_pm, T_con=cfg.T_con),
        solver=SolverSpec(name="dif_altgdmin", T_GD=T_GD, T_con=cfg.T_con),
        engine=EngineSpec(backend=backend),
        substrate=substrate)


def _max_abs(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _timed_run(spec, mat):
    import jax
    from repro.api import run_experiment
    t0 = time.perf_counter()
    trace = run_experiment(spec, key=SEED, materialized=mat)
    jax.block_until_ready(trace.U_nodes)
    return trace, time.perf_counter() - t0


def compiled_kernel_count(spec, mat) -> tuple[int, int]:
    """(Pallas kernels in the compiled run of ONE outer iteration plus
    the final B refit, the program's per-iteration dispatch budget).
    The iteration is the runner's own simulator call (``simulate``), so
    its engine and η are resolved as in the counted run."""
    import jax
    from repro.api import get_solver, simulate

    def one_iter(Xg, yg):
        res = simulate(spec, dataclasses.replace(mat, Xg=Xg, yg=yg),
                       T_GD=1)
        return res.U_nodes, res.B_nodes

    hlo = jax.jit(one_iter).lower(mat.Xg, mat.yg).compile().as_text()
    n = hlo.count('custom_call_target="tpu_custom_call"')
    solver = get_solver(spec.solver.name)
    budget = solver.dispatch_budget.per_iter(
        "simulator", solver.signature(spec.solver.T_con).rounds_per_iter,
        0, 1)
    return n, budget


def kernel_phase(check: Checks, *, backend: str, N: int = 20, d: int = 600,
                 r: int = 4, k: int = 150, K: int = 3) -> None:
    """The compressed-wire and gossip kernels against their oracles."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    key = jax.random.PRNGKey(SEED)
    M = jax.random.normal(key, (N, d, r), jnp.float32)
    v, i = ops.compress_topk(M, k, backend=backend)
    v_ref, i_ref = ops.compress_topk(M, k, backend="xla-ref")
    check("compress_topk", bool(jnp.all(i == i_ref))
          and _max_abs(v, v_ref) == 0.0,
          f"(N, d, r, k)=({N}, {d}, {r}, {k}) indices equal, "
          f"max |vals − ref| = {_max_abs(v, v_ref):.3e}")
    q = jax.random.randint(jax.random.fold_in(key, 1), (N, d, r), -127, 128,
                           jnp.int32).astype(jnp.int8)
    s = jax.random.uniform(jax.random.fold_in(key, 2), (N, 1, 1),
                           jnp.float32)
    err = _max_abs(ops.dequant(q, s, backend=backend),
                   ops.dequant(q, s, backend="xla-ref"))
    check("dequant", err == 0.0, f"max |kernel − ref| = {err:.3e}")
    z = M.reshape(-1)
    nbrs = jax.random.normal(jax.random.fold_in(key, 3), (K, z.size),
                             jnp.float32)
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 4),
                                         (K + 1,)))
    got = ops.gossip_combine(z, nbrs, w, backend=backend)
    want = ops.gossip_combine(z, nbrs, w, backend="xla-ref")
    rel = _max_abs(got, want) / float(jnp.max(jnp.abs(want)))
    check("gossip_combine", rel <= TOL_KERNEL,
          f"K={K}, {z.size} entries, rel err {rel:.3e} "
          f"(tol {TOL_KERNEL:g})")


def train_phase(check: Checks, *, backend: str, ref_backend: str,
                count_kernels: bool):
    """Dif-AltGDmin through run_experiment on ``backend`` and
    ``ref_backend``; returns the fused run's Trace."""
    import jax
    import numpy as np
    from repro.api import materialize
    from repro.core.metrics import subspace_distance
    spec = experiment1_spec(backend=backend)
    p = spec.problem
    print(f"training: dif_altgdmin L={p.L} d={p.d} T={p.T} r={p.r} "
          f"n={p.n} T_GD={spec.solver.T_GD} T_con={spec.solver.T_con} "
          f"{p.dtype}", flush=True)
    mat = materialize(spec, key=SEED)
    sd_init = float(jax.vmap(lambda u: subspace_distance(
        u, mat.problem.U_star))(mat.init.U0).max())
    fused, t_fused = _timed_run(spec, mat)
    ref, t_ref = _timed_run(dataclasses.replace(
        spec, engine=dataclasses.replace(spec.engine, backend=ref_backend)),
        mat)
    print(f"  {backend}: {t_fused:.2f} s wall incl. compile; "
          f"{ref_backend}: {t_ref:.2f} s wall incl. compile", flush=True)
    sd = np.asarray(fused.sd_max)
    check("sd_max falls", bool(np.all(np.isfinite(sd))) and sd[-1] < sd_init,
          f"init {sd_init:.6e} → iter 1 {sd[0]:.6e} → iter {sd.size} "
          f"{sd[-1]:.6e} ({backend})")
    du = _max_abs(fused.U_nodes, ref.U_nodes)
    check(f"U {backend} vs {ref_backend}", du <= TOL_U,
          f"max |ΔU| = {du:.3e} (tol {TOL_U:g}); final sd_max "
          f"{float(fused.sd_max[-1]):.6e} vs {float(ref.sd_max[-1]):.6e}")
    if count_kernels:
        n, budget = compiled_kernel_count(spec, mat)
        check("compiled kernels", n >= budget + 1,
              f"{n} tpu_custom_call in the compiled iteration + final "
              f"refit; budget {budget}/iteration + 1 refit")
    return fused, mat


def serve_phase(check: Checks, trace, U_star, *, backend: str,
                ref_backend: str, n_requests: int = 24,
                max_batch: int = 32, t_new=(6, 10, 16, 30)) -> None:
    """Ragged personalization requests on the trained basis."""
    import numpy as np
    from repro.serving import ServingEngine
    from repro.serving.publisher import deployable_basis
    from repro.serving.queue import RequestGenerator
    U = deployable_basis(trace.U_nodes)
    reqs = RequestGenerator(np.asarray(U_star), t_new=t_new,
                            seed=SEED).generate(n_requests)
    X = [q.X for q in reqs]
    y = [q.y for q in reqs]
    theta_star = np.stack([q.theta_star for q in reqs])
    print(f"serving: {n_requests} requests, T_new ∈ {tuple(t_new)}, "
          f"max_batch={max_batch}, d={U.shape[0]}, r={U.shape[1]}",
          flush=True)
    out = {}
    for b in (backend, ref_backend):
        eng = ServingEngine(U, max_batch=max_batch, backend=b)
        _, theta, _ = eng.solve(X, y)
        theta = np.asarray(theta, np.float64)
        err = (np.linalg.norm(theta - theta_star, axis=1)
               / np.linalg.norm(theta_star, axis=1))
        out[b] = theta
        print(f"  {b}: recovery err ||θ−θ*||/||θ*|| mean {err.mean():.6e} "
              f"max {err.max():.6e}", flush=True)
    rel = (_max_abs(out[backend], out[ref_backend])
           / float(np.max(np.abs(out[ref_backend]))))
    check(f"serve {backend} vs {ref_backend}", rel <= TOL_SERVE,
          f"max |Δθ| / max |θ| = {rel:.3e} (tol {TOL_SERVE:g})")


def mesh_phase(check: Checks, *, backend: str, n_dev: int) -> None:
    """Mesh substrate (one node per device) and virtual tier (five
    nodes per device), each against the simulator run of the spec."""
    import numpy as np
    from repro.api import materialize
    for L, tier in ((n_dev, "mesh"), (5 * n_dev, "virtual")):
        spec = experiment1_spec(backend=backend, L=L, substrate="mesh")
        print(f"{tier}: dif_altgdmin L={L} on {n_dev} devices, "
              f"T_GD={T_GD}, {backend}", flush=True)
        mat = materialize(spec, key=SEED)
        hw, t_hw = _timed_run(spec, mat)
        sim, t_sim = _timed_run(dataclasses.replace(spec,
                                                    substrate="simulator"),
                                mat)
        devs = hw.U_nodes.sharding.device_set
        print(f"  {tier}: {t_hw:.2f} s, simulator: {t_sim:.2f} s "
              f"(wall incl. compile)", flush=True)
        check(f"{tier} spans {n_dev} devices", len(devs) == n_dev,
              f"U_nodes on devices {sorted(d.id for d in devs)}")
        du = _max_abs(hw.U_nodes, sim.U_nodes)
        check(f"{tier} vs simulator", du <= TOL_U,
              f"max |ΔU| = {du:.3e} (tol {TOL_U:g}); final sd_max "
              f"{float(hw.sd_max[-1]):.6e} vs {float(sim.sd_max[-1]):.6e}")
        # per-node refits: a node's B placed on another node's chip
        # leaves U (the nodes agree on it) right and B wrong
        db = (_max_abs(hw.B_nodes, sim.B_nodes)
              / float(np.max(np.abs(np.asarray(sim.B_nodes)))))
        check(f"{tier} B vs simulator", db <= TOL_B,
              f"max |ΔB| / max |B| = {db:.3e} (tol {TOL_B:g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # imported before JAX reaches for a device: without the repo's src/
    # beside this script the smoke fails here, holding no chip
    from repro.utils.compile_cache import use_persistent_cache
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend is "
              f"{platform!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", False)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; jax {jax.__version__}; compile cache "
          f"{use_persistent_cache()}", flush=True)

    check = Checks()
    with jax.default_matmul_precision("highest"):
        if args.chips == 4:
            mesh_phase(check, backend="pallas", n_dev=4)
        else:
            kernel_phase(check, backend="pallas")
            trace, mat = train_phase(check, backend="pallas",
                                     ref_backend="xla-ref",
                                     count_kernels=True)
            serve_phase(check, trace, mat.problem.U_star, backend="pallas",
                        ref_backend="xla-ref")
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
