"""Scale benchmark — the sparse consensus path at large L
(``BENCH_altgdmin.json["scale"]``):

  * section="large_L": a full dif_altgdmin run through the runner on the
    sparse simulator substrate at L=100k (quick: L=10k) — Barabási–Albert
    relatedness graph, O(E) SparseWeights mixing, no (L, L) allocation
    anywhere.  Reports µs per outer GD iteration, peak RSS, and the edge
    count the comm model prices.
  * section="sparse_vs_dense": µs per T_con-round AGREE mix of the
    sparse segment-sum lowering vs the dense stacked ``W @ Z`` at
    moderate L — the crossover behind the auto-sparsify density/size
    cutoff.
  * section="rcm": shift-count pruning of the mesh cyclic-shift
    decomposition under RCM relabeling — irregular ER (an expander:
    bandwidth, hence shift count, is irreducible) vs a
    scrambled-labeling cluster-of-cliques graph where RCM recovers the
    banded structure.
  * section="virtual_mesh": the virtual-node mesh tier at the same
    L=100k (quick: 10k) — three NON-gossip programs (exact_diffusion's
    ψ-corrected combine, dif_topk's compressed wire, dif_partial's
    masked dropout combine) through the one program lowering, via the
    runner's mesh dispatch.  On a TPU host it runs in-process over the
    chips; on CPU in a subprocess over 8 fake host devices, because the
    fake device count is fixed at process start.
"""
from __future__ import annotations

import json
import resource
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def bench_large_L(quick: bool = False):
    from repro.api.runner import materialize, run_experiment
    from repro.api.spec import (ExperimentSpec, InitSpec, ProblemSpec,
                                SolverSpec, TopologySpec)

    L = 10_000 if quick else 100_000
    spec = ExperimentSpec(
        problem=ProblemSpec(d=16, T=L, r=2, n=8, L=L, kappa=1.2),
        topology=TopologySpec(family="barabasi_albert", ba_m=3, seed=0,
                              weights="metropolis",
                              representation="sparse"),
        init=InitSpec(T_pm=3, T_con=2),
        solver=SolverSpec(name="dif_altgdmin", T_GD=3, T_con=3, eta=1e-4),
        substrate="simulator",
    )
    rss0 = _peak_rss_mb()
    mat = materialize(spec)
    graph = mat.graph
    t0 = time.perf_counter()
    trace = run_experiment(spec, materialized=mat)
    jax.block_until_ready(trace.U_nodes)
    total_s = time.perf_counter() - t0
    # separate the steady-state iteration cost from jit compilation:
    # second run on the SAME materialization reuses every compiled fn
    t1 = time.perf_counter()
    trace = run_experiment(spec, materialized=mat)
    jax.block_until_ready(trace.U_nodes)
    warm_s = time.perf_counter() - t1
    return [{
        "section": "large_L",
        "L": L,
        "family": "barabasi_albert",
        "n_edges": int(graph.n_edges),
        "density": float(graph.density),
        "us_per_iter": warm_s / spec.solver.T_GD * 1e6,
        "first_run_s": round(total_s, 3),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "rss_before_mb": round(rss0, 1),
        "sd_max_final": float(trace.sd_max[-1]),
    }]


def bench_sparse_vs_dense(quick: bool = False):
    from repro.distributed import graphs, mixing
    from repro.distributed.consensus import stacked_product

    rows = []
    t_con = 3
    Ls = (512, 1024) if quick else (512, 1024, 4096)
    for L in Ls:
        g = graphs.erdos_renyi(L, p=min(10.0 / L, 1.0), seed=0)
        sw = mixing.metropolis_weights_sparse(g)
        Wd = jnp.asarray(sw.to_dense())
        Z = jax.random.normal(jax.random.PRNGKey(1), (L, 64))

        def dense_mix(z):
            return stacked_product(z, Wd, t_con)

        def sparse_mix(z):
            return stacked_product(z, sw, t_con)

        for name, fn in (("dense", jax.jit(dense_mix)),
                         ("sparse", jax.jit(sparse_mix))):
            fn(Z).block_until_ready()
            t0 = time.perf_counter()
            reps = 10
            for _ in range(reps):
                out = fn(Z)
            jax.block_until_ready(out)
            rows.append({
                "section": "sparse_vs_dense",
                "L": L,
                "path": name,
                "n_edges": int(sw.n_edges),
                "T_con": t_con,
                "us_per_mix": (time.perf_counter() - t0) / reps * 1e6,
            })
    return rows


def bench_rcm(quick: bool = False):
    from repro.distributed import graphs, mixing
    from repro.distributed.consensus import mesh_weights_relabeled

    rows = []
    L = 128 if quick else 256
    cases = {
        "erdos_renyi": np.asarray(mixing.metropolis_weights(
            graphs.erdos_renyi(L, p=4.0 / L, seed=5).to_dense())),
    }
    rng = np.random.default_rng(0)
    Wc = np.asarray(mixing.metropolis_weights(
        graphs.cluster_of_cliques(L, clique=8, seed=2).to_dense()))
    p = rng.permutation(L)
    cases["cluster_cliques_scrambled"] = Wc[np.ix_(p, p)]
    for name, W in cases.items():
        t0 = time.perf_counter()
        rw = mesh_weights_relabeled(W)     # includes round-trip verify
        rows.append({
            "section": "rcm",
            "L": L,
            "graph": name,
            "shifts_before": rw.shifts_before,
            "shifts_after": rw.shifts_after,
            "prune_factor": round(rw.shifts_before
                                  / max(rw.shifts_after, 1), 2),
            "ms": (time.perf_counter() - t0) * 1e3,
        })
    return rows


def virtual_mesh_rows(L: int, dtype: str = "float64"):
    """The virtual_mesh rows on whatever devices this process sees."""
    import dataclasses
    from repro.api.runner import materialize, run_experiment
    from repro.api.spec import (ExperimentSpec, InitSpec, ProblemSpec,
                                SolverSpec, TopologySpec)

    base = ExperimentSpec(
        problem=ProblemSpec(d=16, T=L, r=2, n=8, L=L, kappa=1.2,
                            dtype=dtype),
        topology=TopologySpec(family="barabasi_albert", ba_m=3, seed=0,
                              weights="metropolis",
                              representation="sparse"),
        init=InitSpec(T_pm=3, T_con=2),
        solver=SolverSpec(name="dif_altgdmin", T_GD=3, T_con=3, eta=1e-4),
        substrate="mesh",
    )
    mat = materialize(base)          # one graph/init for all solvers
    n_dev = jax.device_count()
    rows = []
    for name, kw in (("exact_diffusion", {}),
                     ("dif_topk", {"compression_k": 4}),
                     ("dif_partial", {})):
        spec = dataclasses.replace(
            base, solver=dataclasses.replace(base.solver, name=name, **kw))
        t0 = time.perf_counter()
        trace = run_experiment(spec, materialized=mat)
        jax.block_until_ready(trace.U_nodes)
        total_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        trace = run_experiment(spec, materialized=mat)
        jax.block_until_ready(trace.U_nodes)
        warm_s = time.perf_counter() - t1
        rows.append({
            "section": "virtual_mesh",
            "solver": name,
            "L": L,
            "n_dev": n_dev,
            "block": L // n_dev,
            "n_edges": int(mat.graph.n_edges),
            "us_per_iter": warm_s / spec.solver.T_GD * 1e6,
            "first_run_s": round(total_s, 3),
            "peak_rss_mb": round(_peak_rss_mb(), 1),
            "sd_max_final": float(trace.sd_max[-1]),
        })
    return rows


# CPU only: the 8 fake host devices must be fixed before jax starts, so
# the rows run in a child that imports nothing before setting them
_VIRTUAL_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    jax.config.update("jax_enable_x64", True)
    from benchmarks.scale_bench import virtual_mesh_rows
    print("ROWS=" + json.dumps(virtual_mesh_rows(int(sys.argv[1]))))
""")


def bench_virtual_mesh(quick: bool = False):
    """Virtual-node mesh tier rows — non-gossip programs at large L.  On
    a TPU host they run in this process over its chips (a child could
    not reach a chip this process holds), in float32 with x64 off as the
    compiled kernels need; elsewhere in float64 on 8 fake host devices in
    a child process."""
    import os

    L = 10_000 if quick else 100_000
    if jax.default_backend() == "tpu":
        with jax.enable_x64(False):
            return virtual_mesh_rows(L, dtype="float32")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, "-c", _VIRTUAL_SCRIPT, str(L)],
                       capture_output=True, text=True, cwd=repo_root,
                       env={**env, "PYTHONPATH": "src"}, timeout=5400)
    if r.returncode != 0:
        raise RuntimeError(f"virtual-mesh bench failed:\n{r.stderr[-4000:]}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("ROWS=")][-1]
    return json.loads(line[len("ROWS="):])


def bench_scale(quick: bool = False):
    return (bench_large_L(quick=quick)
            + bench_sparse_vs_dense(quick=quick)
            + bench_rcm(quick=quick)
            + bench_virtual_mesh(quick=quick))
