"""SPMD lowering semantics on a small fake mesh (subprocess, 8 devices):
the paper's diffusion aggregation must lower to collective-permute
(neighbour gossip), the fusion-center baseline to all-reduce — the
communication patterns of Alg. 3 vs AltGDmin, visible in the HLO."""
import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import sys
    sys.path.insert(0, "src")
    from repro.configs import get_config
    from repro.launch.specs import input_specs
    from repro.utils.hlo import collective_stats

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("mamba2-130m")

    def lower(agg):
        spec = input_specs(cfg, "train_4k", mesh, aggregation=agg)
        with mesh:
            c = jax.jit(spec.step_fn,
                        in_shardings=spec.in_shardings).lower(
                            *spec.args).compile()
        return collective_stats(c.as_text())

    dif = lower("diffusion")
    ar = lower("allreduce")
    cp_dif = dif["per_op"].get("collective-permute", {}).get("count", 0)
    cp_ar = ar["per_op"].get("collective-permute", {}).get("count", 0)
    ar_count = ar["per_op"].get("all-reduce", {}).get("count", 0)
    assert cp_dif > cp_ar, (dif["per_op"], ar["per_op"])
    assert ar_count > 0, ar["per_op"]
    print("OK", cp_dif, cp_ar, ar_count)
""")


def test_diffusion_lowers_to_permutes_allreduce_to_allreduce():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1800)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "OK" in r.stdout
