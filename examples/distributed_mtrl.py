"""The paper's algorithm on the production mesh: Dif-AltGDmin with nodes
= devices and AGREE = collective-permute ring gossip (shard_map), checked
against the single-host simulator.

With the declarative API this is ONE spec run on TWO substrates — the
``substrate`` field is the only difference between the simulator call and
the mesh call; min-B/gradient route through the same AltgdminEngine on
both, so the comparison isolates the gossip lowering (dense W product vs
collective-permute).

Needs several devices.  Where JAX would see a single CPU device it
re-executes itself with 8 fake CPU devices; it asks a short-lived child,
so this process never holds a chip that the run needs.  On CPU it runs
in float64 (the exact oracle); on a TPU in float32.

  PYTHONPATH=src python examples/distributed_mtrl.py
"""
import os
import subprocess
import sys

if "XLA_FLAGS" not in os.environ:
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.default_backend(), jax.device_count())"],
        capture_output=True, text=True, check=True)
    if probe.stdout.split()[-2:] == ["cpu", "1"]:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        raise SystemExit(
            subprocess.run([sys.executable] + sys.argv).returncode)

import dataclasses

import jax
ON_CPU = jax.default_backend() == "cpu"
jax.config.update("jax_enable_x64", ON_CPU)

import jax.numpy as jnp                                       # noqa: E402
from repro.api import (                                       # noqa: E402
    ExperimentSpec, ProblemSpec, TopologySpec, InitSpec, SolverSpec,
    run_experiment,
)


def main():
    L = 8
    print(f"devices: {len(jax.devices())} (one Dec-MTRL node per device)")
    spec = ExperimentSpec(
        name="mesh_vs_simulator",
        problem=ProblemSpec(d=100, T=64, r=4, n=30, L=L, kappa=2.0,
                            dtype="float64" if ON_CPU else "float32"),
        topology=TopologySpec(family="ring", weights="circulant",
                              shifts=(-1, 1)),     # ring = ICI-native
        init=InitSpec(T_pm=25, T_con=8),
        solver=SolverSpec(name="dif_altgdmin", T_GD=200, T_con=2),
    )

    sim = run_experiment(spec, key=0)
    hw = run_experiment(dataclasses.replace(spec, substrate="mesh"), key=0)

    drift = float(jnp.max(jnp.abs(hw.U_nodes - sim.U_nodes)))
    print(f"mesh runtime   : SD₂ = {hw.final_sd_max:.2e}  (ring gossip, "
          f"T_con=2, 200 iters)")
    print(f"simulator (W)  : SD₂ = {sim.final_sd_max:.2e}")
    print(f"max |U_hw − U_sim| = {drift:.2e}  (identical algorithm, "
          f"collective-permute vs matmul gossip)")
    assert drift < (1e-7 if ON_CPU else 1e-4)
    print("\nOnly the d×r iterate crossed the wire — X, y, B stayed "
          "node-local (federated).")


if __name__ == "__main__":
    main()
