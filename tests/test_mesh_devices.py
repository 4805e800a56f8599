"""The mesh substrate's chip count comes from the spec: ``n_devices``
picks the first devices of the host (8 fake CPU devices, in a
subprocess), a count that does not divide L raises the tier's error,
one beyond the host raises, and ``None`` keeps every device.  The
virtual-node tier's spans and counts are recorded, and its
``ppermutes_per_iter`` is the lowered jaxpr's own count."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys, tempfile
    import jax
    jax.config.update("jax_enable_x64", False)
    sys.path.insert(0, "src")
    from repro.api import ExperimentSpec, run_experiment
    from repro.utils import spans

    def spec(L, n_devices, T_GD=4):
        return ExperimentSpec.from_dict({
            "problem": {"d": 24, "T": 2 * L, "r": 2, "n": 10, "L": L,
                        "dtype": "float32"},
            "topology": {"family": "erdos_renyi", "p": 0.5, "seed": 0},
            "init": {"T_pm": 5, "T_con": 3},
            "solver": {"name": "dif_altgdmin", "T_GD": T_GD, "T_con": 3,
                       "eta": 0.02},
            "engine": {"backend": "xla-ref"},
            "substrate": "mesh", "n_devices": n_devices})

    def devices_of(x):
        return sorted(d.id for d in x.sharding.device_set)

    out = {}
    with tempfile.TemporaryDirectory() as tmp, jax.profiler.trace(tmp):
        t = run_experiment(spec(8, 4), 0)
        jax.block_until_ready(t.U_nodes)
    out["n4"] = devices_of(t.U_nodes)
    out["spans"] = [[n, st] for n, _, _, st in spans.recorded()
                    if n.startswith(("repro.virtual.", "repro.solve."))]
    out["none"] = devices_of(run_experiment(spec(8, None), 0).U_nodes)
    out["none_virtual"] = devices_of(run_experiment(spec(16, None),
                                                    0).U_nodes)
    for key, L, n in (("not_dividing", 20, 3), ("beyond", 8, 9)):
        try:
            run_experiment(spec(L, n), 0)
            out[key] = "ran"
        except ValueError as e:
            out[key] = str(e)

    # ppermutes per outer iteration in the lowered jaxpr, as the
    # reprolint harness walks it (JX004)
    from repro.analysis.harness import iter_eqns
    from repro.api.runner import materialize
    from repro.core.program import get_program, lower_virtual_mesh
    from repro.distributed.consensus import VirtualTopology
    from repro.distributed.mixing import SparseWeights
    s = spec(8, 4, T_GD=5)
    mat = materialize(s, 0)
    vt = VirtualTopology.from_weights(SparseWeights.from_dense(mat.W), 4)
    mesh = jax.make_mesh((4,), ("nodes",), devices=jax.devices()[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    run = lower_virtual_mesh(get_program("dif_altgdmin"))
    jaxpr = jax.make_jaxpr(lambda U0, X, y: run(
        U0, X, y, mesh, "nodes", vt=vt, eta=0.02, T_GD=5, T_con=3,
        backend="xla-ref", U_star=mat.problem.U_star))(
            mat.init.U0, mat.Xg, mat.yg)
    out["jaxpr_ppermutes_per_iter"] = sum(
        m for e, m, inner in iter_eqns(jaxpr, outer_len=5)
        if inner and e.primitive.name == "ppermute")
    out["jaxpr_gathers_per_iter"] = sum(
        m for e, m, inner in iter_eqns(jaxpr, outer_len=5)
        if inner and e.primitive.name == "all_gather")
    out["vt"] = [vt.n_dev, vt.block, len(vt.dev_shifts),
                 vt.n_cross_entries]
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def result():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, cwd=REPO_ROOT, timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    line, = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_n_devices_runs_on_the_first_devices(result):
    assert result["n4"] == [0, 1, 2, 3]


def test_n_devices_none_keeps_every_device(result):
    assert result["none"] == list(range(8))           # one node a device
    assert result["none_virtual"] == list(range(8))   # two nodes a device


def test_n_devices_that_does_not_divide_L_raises_the_tier_error(result):
    assert "needs one device per node" in result["not_dividing"]
    assert "L=20" in result["not_dividing"]


def test_n_devices_beyond_the_host_raises(result):
    assert "n_devices=9" in result["beyond"]
    assert "8 devices" in result["beyond"]


def test_virtual_tier_records_its_spans_and_counts(result):
    by_name = dict(result["spans"])
    n_dev, block, shifts, cross = result["vt"]
    assert (n_dev, block) == (4, 2)
    assert {k: by_name["repro.virtual.topology"][k]
            for k in ("devices", "block", "shift_classes",
                      "cross_edges")} == {
        "devices": 4, "block": 2, "shift_classes": shifts,
        "cross_edges": cross}
    assert "repro.virtual.shard" in by_name
    scan = by_name["repro.solve.scan"]
    assert scan["devices"] == 4 and scan["gathers_per_iter"] == 1
    assert scan["ppermutes_per_iter"] == 3 * shifts       # T_con rounds
    # each permute carries the chip's (block, d, r) float32 slab
    assert scan["wire_bytes_per_iter"] == (
        scan["ppermutes_per_iter"] * block * 24 * 2 * 4)


def test_ppermutes_per_iter_is_the_lowered_jaxpr_count(result):
    scan = dict(result["spans"])["repro.solve.scan"]
    assert scan["ppermutes_per_iter"] == result["jaxpr_ppermutes_per_iter"]
    assert scan["gathers_per_iter"] == result["jaxpr_gathers_per_iter"]
