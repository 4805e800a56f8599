"""Open-loop personalization serving (traffic driver ``serve_open_loop``).

Set-up trains one job (job 0 of the run, ``train_T_GD`` iterations,
through ``materialize`` and ``run_experiment``), publishes its
``deployable_basis`` as the served U, and builds a pool of ``pool``
requests: new users drawn from the paper's model, θ* = U* b* with
b* ~ N(0, I_r), a Gaussian design of T_new rows and y = X θ*.  The
T_new are the same ``pool`` sizes for every seed (quantiles of a
log-uniform law on [t_new_min, t_new_max]) in a seeded order.  Every
padding bucket and every batch size is compiled in set-up.

The window offers N = rate_hz × seconds requests whose due times are N
sorted uniform draws over the window (a Poisson process given its
count), cycling through the pool.  One loop serves them greedily: when
the engine is free, every due request up to ``max_batch`` goes into one
``ServingEngine.solve``.  A request's latency runs from its due time
until its θ is on the host.  Requests not served within ``grace_s``
after the window count as failed, at the latency they had then.

After the window ``check_requests`` requests, drawn from the seed, are
solved again by the plain reference on the reference's own U (the
reference training of job 0) and compared by the values each θ fits
to its request's samples (``gaps.fit_gap``).
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import gaps, harness, work
from chipbench.drivers import train_jobs
from chipbench.reference import mtrl


def request_sizes(traffic: dict) -> np.ndarray:
    """The pool's T_new, ascending: quantiles of log-uniform."""
    P, lo, hi = (int(traffic["pool"]), int(traffic["t_new_min"]),
                 int(traffic["t_new_max"]))
    u = (np.arange(P) + 0.5) / P
    return np.floor(np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
                    ).astype(int)


def request_pool(U_star, traffic: dict, rng: np.random.Generator):
    """Seeded new users, as ``serving.queue.RequestGenerator`` draws
    them: lists of X (T_new, d) and y (T_new,) in float32."""
    U_star = np.asarray(U_star, np.float64)
    d, r = U_star.shape
    sizes = rng.permutation(request_sizes(traffic))
    X_list, y_list = [], []
    for t in sizes:
        theta = U_star @ rng.standard_normal(r)
        X = rng.standard_normal((int(t), d))
        X_list.append(X.astype(np.float32))
        y_list.append((X @ theta).astype(np.float32))
    return X_list, y_list


def arrivals(traffic: dict, seconds: float, rng: np.random.Generator):
    n = int(round(float(traffic["rate_hz"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def buckets(traffic: dict) -> list[int]:
    step = int(traffic["pad_n_to"])
    lo = -(-int(traffic["t_new_min"]) // step) * step
    hi = -(-int(traffic["t_new_max"]) // step) * step
    return list(range(lo, hi + 1, step))


def warm_up(engine, X_list, y_list, traffic: dict) -> int:
    """Solve one batch per padding bucket and one per batch size, so the
    window compiles nothing.  Returns the number of solves."""
    step = int(traffic["pad_n_to"])
    sizes = np.array([x.shape[0] for x in X_list])
    pad = -(-sizes // step) * step
    calls = 0
    for b in buckets(traffic):
        i = int(np.flatnonzero(pad == b)[0])
        np.asarray(engine.solve([X_list[i]], [y_list[i]])[1])
        calls += 1
    for R in range(1, int(traffic["max_batch"]) + 1):
        np.asarray(engine.solve(X_list[:R], y_list[:R])[1])
        calls += 1
    return calls


def run(ctx: harness.Context, *, t_begin: float) -> harness.DriverResult:
    import jax
    from repro.serving import ServingEngine
    from repro.serving.publisher import deployable_basis
    tr = ctx.traffic
    spec = harness.spec_from_config(ctx.config, T_GD=int(tr["train_T_GD"]))
    p = spec.problem
    key0 = mtrl.job_key(ctx.seed, 0)
    U_nodes, _ = train_jobs.make_job(spec)(key0, harness.Spans())
    engine = ServingEngine(deployable_basis(U_nodes),
                           max_batch=int(tr["max_batch"]),
                           backend=spec.engine.backend,
                           blk_d=spec.engine.blk_d,
                           pad_n_to=int(tr["pad_n_to"]))
    del U_nodes
    rng = np.random.default_rng([ctx.seed, 2])
    X_pool, y_pool = request_pool(mtrl.u_star(key0, d=p.d, r=p.r), tr, rng)
    due = arrivals(tr, ctx.seconds, rng)
    N, P = due.size, len(X_pool)
    picks = np.sort(rng.choice(N, size=min(int(tr["check_requests"]), N),
                               replace=False))
    warm_calls = warm_up(engine, X_pool, y_pool, tr)
    setup_s = time.perf_counter() - t_begin

    max_batch = int(tr["max_batch"])
    grace = float(tr["grace_s"])
    done_at = np.full(N, np.nan)
    served = {}
    pick_set = set(picks.tolist())
    late, batch_sizes, batch_rows = [], [], []
    compiles = harness.CompileEvents.get()
    with compiles.counting() as window_compiles, ctx.tracer.window():
        t0 = time.perf_counter()
        i = 0
        while i < N:
            now = time.perf_counter() - t0
            if now > ctx.seconds + grace:
                break
            if due[i] > now:
                if due[i] - now > 3e-3:
                    time.sleep(due[i] - now - 2e-3)
                while time.perf_counter() - t0 < due[i]:
                    pass
                now = time.perf_counter() - t0
                late.append(now - due[i])
            k = min(int(np.searchsorted(due, now, side="right")),
                    i + max_batch) - i
            ids = range(i, i + k)
            Xs = [X_pool[q % P] for q in ids]
            ys = [y_pool[q % P] for q in ids]
            with ctx.spans("solve"):
                _, theta, _ = engine.solve(Xs, ys)
                theta = np.asarray(theta)
            done_at[i:i + k] = time.perf_counter() - t0
            for q in ids:
                if q in pick_set:
                    served[q] = theta[q - i].copy()
            batch_sizes.append(k)
            batch_rows.append(sum(x.shape[0] for x in Xs))
            i += k
        t_end = time.perf_counter() - t0
    memory_peak = harness.memory_peak(ctx.devices)
    del engine

    completed = int(np.sum(np.isfinite(done_at)))
    latency = np.where(np.isfinite(done_at), done_at, t_end) - due
    window_s = max(float(ctx.seconds), float(np.nanmax(done_at)))

    # the reference's own U: its training of job 0, then the θ solves
    U_ref_nodes, _ = train_jobs.reference_job(
        spec, key0, precision=ctx.config["precision"])
    U_ref = mtrl.deployable_basis(jax.numpy.asarray(U_ref_nodes))
    checked = [q for q in picks.tolist() if q in served]
    n_max = max(int(X_pool[q % P].shape[0]) for q in checked)
    Xc = np.zeros((len(checked), n_max, p.d), np.float32)
    yc = np.zeros((len(checked), n_max), np.float32)
    for row, q in enumerate(checked):
        t = X_pool[q % P].shape[0]
        Xc[row, :t], yc[row, :t] = X_pool[q % P], y_pool[q % P]
    theta_ref = mtrl.serve_theta(U_ref, Xc, yc,
                                 precision=ctx.config["precision"])
    fit_gap = gaps.fit_gap(Xc, np.stack([served[q] for q in checked]),
                           jax.device_get(theta_ref))

    rows = int(np.sum(batch_rows))
    p50, p95, p99 = (float(np.percentile(latency, q)) * 1e3
                     for q in (50, 95, 99))
    return harness.DriverResult(
        attempted=N, failed=N - completed,
        end_to_end={"serve_req_per_s": completed / window_s,
                    "setup_s": setup_s},
        checks={"fit_gap": fit_gap},
        window_s=window_s, memory_peak_bytes=memory_peak,
        work={"batches": len(batch_sizes), "requests": completed,
              "p95_ms": p95,
              "rows": rows, "d": p.d, "r": p.r,
              **{name: {k: sum(f(rows=n, requests=b, d=p.d, r=p.r)[k]
                               for n, b in zip(batch_rows, batch_sizes))
                        for k in ("flops", "bytes")}
                 for name, f in (("gram", work.task_gram),
                                 ("served", work.served))}},
        counters={"offered": N, "completed": completed,
                  "rate_hz": float(tr["rate_hz"]),
                  "batches": len(batch_sizes),
                  "mean_batch": float(np.mean(batch_sizes)),
                  "p50_ms": p50, "p95_ms": p95, "p99_ms": p99,
                  "generator_late_p99_ms": (float(np.percentile(late, 99))
                                            * 1e3 if late else 0.0),
                  "warmup_solves": warm_calls,
                  "checked_requests": len(checked),
                  "window_compiles": window_compiles["cache_misses"],
                  "window_cache_hits": window_compiles["cache_hits"]},
        check_ok=len(checked) > 0)
