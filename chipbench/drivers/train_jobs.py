"""Training jobs back to back (traffic driver ``train_jobs``).

Job j of a run has the key ``fold_in(seed_key(seed), j)``.  A job is
what a user of the system runs: ``repro.api.materialize`` (problem
generation, graph and weights, spectral init, η), then
``run_experiment`` with ``T_GD`` outer iterations, ending when the final
``U_nodes`` are on the device.  Job 0 (and any further ``warmup_jobs``)
runs in set-up and compiles everything; the window starts jobs while
less than ``--seconds`` has passed and ends with the last one.

A job reaches the target when the harness's own max-over-nodes
subspace distance to U* (drawn again from the job's key) is at most
``sd_target``; a job that misses counts as failed.  After the window a
sample of ``check_jobs`` jobs, drawn from the seed, is solved again by
the plain reference and compared basis by basis and task by task.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import gaps, harness, work
from chipbench.reference import mtrl


def reference_job(spec, key, *, precision: str):
    """(U_nodes, B_nodes) of the plain reference for one job's key, on
    the host.  It reads the sizes from the spec and draws the graph and
    the problem again itself."""
    import jax
    import jax.numpy as jnp
    p, t = spec.problem, spec.topology
    W = jnp.asarray(mtrl.metropolis(mtrl.er_adjacency(p.L, t.p, t.seed)),
                    jnp.float32)
    U, B, _ = mtrl.solve_job(
        key, W, d=p.d, T=p.T, r=p.r, n=p.n, L=p.L, kappa=float(p.kappa),
        T_pm=spec.init.T_pm, T_con_init=spec.init.T_con,
        T_GD=spec.solver.T_GD, T_con=spec.solver.T_con,
        eta=float(spec.solver.eta), precision=precision)
    return jax.device_get(U), jax.device_get(B)


def sd_max(U_nodes, U_star) -> float:
    """max_g ||U* − U_g U_gᵀ U*||₂ (the program's SD₂, taken here)."""
    U = np.asarray(U_nodes, np.float64)
    Us = np.asarray(U_star, np.float64)
    return float(max(np.linalg.norm(Us - Ug @ (Ug.T @ Us), ord=2)
                     for Ug in U))


def make_job(spec):
    """The timed path of one job, as a user calls it."""
    import jax
    from repro.api import materialize, run_experiment

    def job(key, spans):
        with spans("materialize"):
            mat = materialize(spec, key)
            jax.block_until_ready(mat.init.U0)
        with spans("run_experiment"):
            out = run_experiment(spec, key, materialized=mat)
            jax.block_until_ready(out.U_nodes)
        return out.U_nodes, out.B_nodes
    return job


def run(ctx: harness.Context, *, t_begin: float) -> harness.DriverResult:
    import jax
    tr = ctx.traffic
    spec = harness.spec_from_config(ctx.config, T_GD=int(tr["T_GD"]))
    p = spec.problem
    job = make_job(spec)
    n_warm = int(tr["warmup_jobs"])
    for j in range(n_warm):
        job(mtrl.job_key(ctx.seed, j), harness.Spans())
    setup_s = time.perf_counter() - t_begin

    results = []
    compiles = harness.CompileEvents.get()
    with compiles.counting() as window_compiles, ctx.tracer.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            j = n_warm + len(results)
            results.append((j, job(mtrl.job_key(ctx.seed, j), ctx.spans)))
        window_s = time.perf_counter() - t0
    memory_peak = harness.memory_peak(ctx.devices)

    # the program's answers to host memory; its device state is freed
    answers = [(j, jax.device_get(U), jax.device_get(B))
               for j, (U, B) in results]
    del results
    sds = [sd_max(U, mtrl.u_star(mtrl.job_key(ctx.seed, j), d=p.d, r=p.r))
           for j, U, _ in answers]
    on_target = sum(sd <= float(tr["sd_target"]) for sd in sds)

    rng = np.random.default_rng([ctx.seed, 1])
    picks = sorted(rng.choice(len(answers),
                              size=min(int(tr["check_jobs"]), len(answers)),
                              replace=False).tolist())
    u_gaps, theta_gaps = [], []
    for i in picks:
        j, U, B = answers[i]
        U_ref, B_ref = reference_job(spec, mtrl.job_key(ctx.seed, j),
                                     precision=ctx.config["precision"])
        u_gaps.append(gaps.u_gap(U, U_ref))
        theta_gaps.append(gaps.theta_gap(gaps.theta_nodes(U, B),
                                         gaps.theta_nodes(U_ref, B_ref)))

    shapes = dict(L=p.L, tpn=p.T // p.L, n=p.n, d=p.d, r=p.r)
    per_job = work.training_job(**shapes, T_pm=spec.init.T_pm,
                                T_con_init=spec.init.T_con,
                                T_GD=spec.solver.T_GD,
                                T_con=spec.solver.T_con)
    n_jobs = len(answers)
    return harness.DriverResult(
        attempted=n_jobs, failed=n_jobs - on_target,
        end_to_end={"train_time_to_target_s": window_s / max(on_target, 1),
                    "setup_s": setup_s},
        checks={"u_gap": max(u_gaps), "theta_gap": max(theta_gaps)},
        window_s=window_s, memory_peak_bytes=memory_peak,
        work={"jobs": n_jobs, "T_GD": spec.solver.T_GD, "shapes": shapes,
              "job": per_job, "fused_iter": work.fused_iter(**shapes)},
        counters={"jobs": n_jobs, "jobs_on_target": on_target,
                  "sd_max_worst": max(sds), "checked_jobs": [answers[i][0]
                                                             for i in picks],
                  "window_compiles": window_compiles["cache_misses"],
                  "window_cache_hits": window_compiles["cache_hits"]},
        check_ok=on_target > 0)
