"""Compressed & event-triggered consensus rules (PR 5): the compress
kernels vs their oracles, the reference-copy error-feedback state, the
lossless-recovery bit-identities, the shared f64 precision gate, the
payload-aware comm pricing (dense vs compressed axes), and the paper-shape
acceptance (top-k at k = d/4 within 2x of the dense floor while the wire
carries >= 4x fewer bytes)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.api import (ExperimentSpec, InitSpec, ProblemSpec, SolverSpec,
                       TopologySpec, get_solver, run_experiment)
from repro.api.runner import materialize
from repro.core import comm_model as cm
from repro.distributed import CommSignature, get_rule
from repro.distributed.mixing import metropolis_weights
from repro.distributed.graphs import ring
from repro.kernels import compress as cpk
from repro.kernels import gossip_axpy as ga
from repro.kernels import ops, ref


TINY = ExperimentSpec(
    problem=ProblemSpec(d=36, T=24, r=3, n=22, L=8, kappa=1.5),
    topology=TopologySpec(family="ring", weights="metropolis"),
    init=InitSpec(T_pm=12, T_con=5),
    solver=SolverSpec(name="dif_altgdmin", T_GD=30, T_con=2))


def _tiny_with(solver: SolverSpec) -> ExperimentSpec:
    return dataclasses.replace(TINY, solver=solver)


# ------------------------------------------------------------- kernels

def test_compress_topk_kernel_matches_ref():
    """Selection AND gathered rows of the pallas kernel equal the
    lax.top_k oracle bit-for-bit on f32 blocks."""
    M = jax.random.normal(jax.random.PRNGKey(3), (5, 32, 3), jnp.float32)
    v_k, i_k = ops.compress_topk(M, 8, backend="pallas-interpret")
    v_r, i_r = ref.ref_compress_topk(M, 8)
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    assert i_k.dtype == jnp.int32 and v_k.dtype == M.dtype


def test_compress_topk_kernel_selects_nan_rows_like_ref():
    """A diverged (NaN) row ranks first, as in lax.top_k, in index order."""
    M = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 3), jnp.float32)
    M = M.at[0, 5, 1].set(jnp.nan).at[1, 9, 0].set(jnp.nan)
    M = M.at[1, 2, 2].set(jnp.nan)
    v_k, i_k = ops.compress_topk(M, 4, backend="pallas-interpret")
    v_r, i_r = ref.ref_compress_topk(M, 4)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    assert int(i_k[0, 0]) == 5 and list(np.asarray(i_k[1, :2])) == [2, 9]


def test_compress_topk_full_k_covers_all_rows():
    M = jax.random.normal(jax.random.PRNGKey(4), (3, 12, 2), jnp.float32)
    vals, idx = ops.compress_topk(M, 12, backend="pallas-interpret")
    for n in range(3):
        assert sorted(np.asarray(idx[n])) == list(range(12))
    # scatter-replace over the full index set reproduces M exactly
    out = jax.vmap(lambda x, v, i: x.at[i].set(v))(
        jnp.zeros_like(M), vals, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(M))


def test_compress_topk_validates_k():
    M = jnp.ones((2, 8, 2), jnp.float32)
    with pytest.raises(ValueError, match="1 <= k <= d"):
        ops.compress_topk(M, 0, backend="xla-ref")
    with pytest.raises(ValueError, match="1 <= k <= d"):
        ops.compress_topk(M, 9, backend="pallas-interpret")


def test_dequant_kernel_matches_ref():
    q = jax.random.randint(jax.random.PRNGKey(5), (4, 20, 3), -127,
                           128).astype(jnp.int8)
    scale = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (4, 1, 1),
                                      jnp.float32)) + 1e-3
    got = ops.dequant(q, scale, backend="pallas-interpret")
    want = ref.ref_dequant(q, scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == scale.dtype


# ------------------------------------------- lossless-recovery anchors

def test_topk_full_k_recovers_dense_gossip_bit_identically():
    """k = d refreshes every row of the public copy with the exact
    iterate, so compressed Dif-AltGDmin IS Dif-AltGDmin bit-for-bit."""
    mat = materialize(TINY, key=0)
    dense = run_experiment(TINY, key=0, materialized=mat)
    full = run_experiment(_tiny_with(SolverSpec(
        name="dif_topk", T_GD=30, T_con=2,
        compression_k=TINY.problem.d)), key=0, materialized=mat)
    np.testing.assert_array_equal(np.asarray(full.U_nodes),
                                  np.asarray(dense.U_nodes))
    np.testing.assert_array_equal(full.sd_max, dense.sd_max)
    np.testing.assert_array_equal(np.asarray(full.B_nodes),
                                  np.asarray(dense.B_nodes))


def test_event_zero_threshold_recovers_dense_gossip_bit_identically():
    """theta = 0 always triggers the re-broadcast, so every public copy
    equals the iterate and the round is the dense product."""
    mat = materialize(TINY, key=0)
    dense = run_experiment(TINY, key=0, materialized=mat)
    ev = run_experiment(_tiny_with(SolverSpec(
        name="dif_event", T_GD=30, T_con=2)), key=0, materialized=mat)
    np.testing.assert_array_equal(np.asarray(ev.U_nodes),
                                  np.asarray(dense.U_nodes))
    np.testing.assert_array_equal(ev.sd_max, dense.sd_max)


# --------------------------------------- error-feedback state plumbing

def test_error_feedback_state_round_trips_through_scan():
    """The driver's lax.scan carry must thread the reference-copy state
    across rounds AND outer iterations: a hand-rolled python loop over
    the same stateful mixer reproduces the scanned run exactly."""
    from repro.core.engine import AltgdminEngine
    from repro.core.spectral import _qr_pos
    mat = materialize(TINY, key=0)
    spec = _tiny_with(SolverSpec(name="dif_topk", T_GD=6, T_con=2,
                                 compression_k=9))
    got = run_experiment(spec, key=0, materialized=mat)

    rule = get_rule("topk_gossip")
    eng = AltgdminEngine("xla-ref")
    mix = rule.make_sim_state_mixer(mat.W, 2, backend="xla-ref",
                                    compression_k=9)
    L = TINY.problem.L
    U = mat.init.U0
    state = rule.init_state(U, compression_k=9)
    for _ in range(6):
        B, G = eng.min_grad(U, mat.Xg, mat.yg, mat.Xg, mat.yg,
                            same_data=True)
        U_tilde, state = mix(U - mat.eta * L * G, state)
        U = _qr_pos(U_tilde)[0]
    # scan-traced vs eager arithmetic: machine-eps only
    np.testing.assert_allclose(np.asarray(got.U_nodes), np.asarray(U),
                               rtol=0, atol=1e-12)
    # the state genuinely evolved (it is not a dead carry slot): a run
    # whose copies are frozen at init diverges at O(1)
    U_frozen = mat.init.U0
    state0 = rule.init_state(U_frozen, compression_k=9)
    for _ in range(6):
        B, G = eng.min_grad(U_frozen, mat.Xg, mat.yg, mat.Xg, mat.yg,
                            same_data=True)
        U_t, _ = mix(U_frozen - mat.eta * L * G, state0)
        U_frozen = _qr_pos(U_t)[0]
    assert float(jnp.max(jnp.abs(np.asarray(got.U_nodes)
                                 - np.asarray(U_frozen)))) > 1e-3


def test_compressed_state_not_shared_across_runs():
    """Two runs from the same spec start from fresh zero copies: results
    are reproducible (no hidden module-level state)."""
    spec = _tiny_with(SolverSpec(name="dif_quantized", T_GD=8, T_con=2,
                                 compression="int8_stochastic"))
    mat = materialize(spec, key=0)
    a = run_experiment(spec, key=0, materialized=mat)
    b = run_experiment(spec, key=0, materialized=mat)
    np.testing.assert_array_equal(np.asarray(a.U_nodes),
                                  np.asarray(b.U_nodes))


# ------------------------------------------------- f64 precision gate

def test_f64_operands_take_exact_unfused_path(monkeypatch):
    """x64 policy (the shared _fused_wanted gate): on the pallas
    backends float64 operands never reach the f32-accumulating kernels —
    neither the combine/mix kernels nor the new compress/dequant pair;
    the exact reference encoder + unfused chain run instead."""
    calls = {"n": 0}

    def count(orig):
        def wrapped(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)
        return wrapped

    monkeypatch.setattr(cpk, "compress_topk", count(cpk.compress_topk))
    monkeypatch.setattr(cpk, "dequant", count(cpk.dequant))
    monkeypatch.setattr(ga, "gossip_combine", count(ga.gossip_combine))
    monkeypatch.setattr(ga, "mix_rows", count(ga.mix_rows))

    for name, kw in (("dif_topk", {"compression_k": 9}),
                     ("dif_quantized", {"compression": "int8"})):
        spec = dataclasses.replace(
            _tiny_with(SolverSpec(name=name, T_GD=4, T_con=2, **kw)),
            engine=dataclasses.replace(TINY.engine,
                                       backend="pallas-interpret"))
        trace = run_experiment(spec, key=0)   # f64 problem dtype
        assert np.all(np.isfinite(trace.sd_max))
    assert calls["n"] == 0, f"{calls['n']} fused kernel dispatches on f64"


# ------------------------------------------------- convergence checks

@pytest.mark.parametrize("name,kw,shrink", [
    # top-k and event-triggered trade convergence speed for wire volume,
    # so their short-horizon bounds are looser than the quantized wire's
    ("dif_topk", {"compression_k": 9}, 0.65),
    ("dif_quantized", {}, 0.5),
    ("dif_quantized", {"compression": "int8"}, 0.5),
    ("dif_quantized", {"compression": "int8_stochastic"}, 0.5),
    ("dif_event", {"event_threshold": 0.02}, 0.6),
])
def test_compressed_solvers_converge(name, kw, shrink):
    """Every compressed solver is registered, runnable via
    run_experiment, and decreases sd_max."""
    spec = _tiny_with(SolverSpec(name=name, T_GD=60, T_con=3, **kw))
    trace = run_experiment(spec, key=0)
    assert np.all(np.isfinite(trace.sd_max))
    assert trace.sd_max[-1] < shrink * trace.sd_max[0], (
        name, kw, trace.sd_max[0], trace.sd_max[-1])


def test_quantized_bf16_tracks_dense_floor():
    """Difference quantization contracts with consensus: the bf16 wire
    reaches the dense trajectory's neighbourhood (not a bf16-resolution
    floor on the iterate)."""
    mat = materialize(TINY, key=0)
    dense = run_experiment(_tiny_with(SolverSpec(
        name="dif_altgdmin", T_GD=80, T_con=3)), key=0, materialized=mat)
    q = run_experiment(_tiny_with(SolverSpec(
        name="dif_quantized", T_GD=80, T_con=3)), key=0, materialized=mat)
    assert q.sd_max[-1] <= 3 * dense.sd_max[-1] + 1e-6, (
        q.sd_max[-1], dense.sd_max[-1])


def test_unconsumed_compression_knobs_rejected():
    """Non-default compression knobs on solvers that ignore them raise
    before materialization (same policy as local_steps)."""
    for field, kw in (("compression", {"compression": "bf16"}),
                      ("compression_k", {"compression_k": 5}),
                      ("event_threshold", {"event_threshold": 0.1})):
        spec = _tiny_with(SolverSpec(name="dif_altgdmin", T_GD=5, **kw))
        with pytest.raises(ValueError, match=f"does not consume {field}"):
            run_experiment(spec, key=0)
    # and the knobs ARE consumed by their own solvers
    with pytest.raises(ValueError, match="does not consume compression_k"):
        run_experiment(_tiny_with(SolverSpec(
            name="dif_quantized", T_GD=5, compression_k=3)), key=0)


def test_bad_quantized_wire_format_rejected():
    spec = _tiny_with(SolverSpec(name="dif_quantized", T_GD=5,
                                 compression="fp4"))
    with pytest.raises(ValueError, match="wire format"):
        run_experiment(spec, key=0)


def test_event_send_fraction_drops_as_consensus_tightens():
    """The event trigger actually suppresses re-broadcasts once nodes
    agree: with a converged iterate and warm copies the measured send
    fraction is far below the theta=0 worst case the signature prices."""
    rule = get_rule("event_gossip")
    Z = jax.random.normal(jax.random.PRNGKey(0), (8, 12, 3))
    frac_cold = float(rule.send_fraction(Z, jnp.zeros_like(Z), 0.05))
    frac_warm = float(rule.send_fraction(Z, Z * (1 + 1e-4), 0.05))
    assert frac_cold == 1.0 and frac_warm == 0.0


# ------------------------------------------------- comm pricing (bugfix)

def test_signature_payload_fields_route_into_pricing():
    """Regression (PR-5 satellite): time_axis_from_signature used to
    hardwire a dense d x r exchange at the model's bytes_per_entry, so a
    CommSignature could not express a smaller payload.  The signature's
    entries/bytes now reach the per-message cost."""
    d, r, L, deg, T = 100, 4, 16, 2, 20
    flat = cm.NetworkModel(bandwidth_bytes=1e9 / 8, latency_s=0.0,
                           jitter_std_s=0.0, bytes_per_entry=8)
    dense_sig = CommSignature("gossip", 3)
    topk_sig = get_rule("topk_gossip").signature(3, d=d, r=r)
    dense_axis = cm.time_axis_from_signature(dense_sig, T, d, r, L, deg,
                                             0.0, model=flat)
    topk_axis = cm.time_axis_from_signature(topk_sig, T, d, r, L, deg,
                                            0.0, model=flat)
    # defaults reproduce the historical dense pricing exactly
    np.testing.assert_array_equal(
        dense_axis, cm.decentralized_time_axis(T, 3, d, r, deg, 0.0,
                                               model=flat))
    # f32 values + int32 indices for d/4 rows: 500 B vs 3200 B per
    # message.  The 6.4x wire factor decomposes as 3.2x fewer entries
    # x 2x f32-instead-of-f64 wire (see TopkGossipCombine docstring).
    assert topk_sig.entries_per_round == (d // 4) * (r + 1)
    assert topk_sig.bytes_per_entry == 4
    ratio = dense_axis[-1] / topk_axis[-1]
    assert ratio >= 4.0, ratio
    # the entry-count factor alone (model-native precision both sides)
    assert (d * r) / topk_sig.entries_per_round == pytest.approx(3.2)


def test_bytes_per_iter_honors_signature_payload():
    d, r = 100, 4
    dense = CommSignature("gossip", 3).bytes_per_iter(d * r, 8, 16, 2)
    topk = get_rule("topk_gossip").signature(3, d=d, r=r).bytes_per_iter(
        d * r, 8, 16, 2)
    quant = get_rule("quantized_gossip").signature(
        3, d=d, r=r).bytes_per_iter(d * r, 8, 16, 2)
    assert dense / topk >= 4.0
    assert dense / quant == 4.0              # bf16 wire: 2 B vs 8 B
    int8 = get_rule("quantized_gossip").signature(
        3, d=d, r=r, compression="int8").bytes_per_iter(d * r, 8, 16, 2)
    assert dense / int8 > 7.5                # 1 B + scale vs 8 B


def test_signature_without_dims_falls_back_dense():
    sig = get_rule("topk_gossip").signature(4)
    assert sig == CommSignature("gossip", 4)
    assert get_rule("event_gossip").signature(4).entries_per_round is None


def test_trace_time_axis_prices_compression():
    """End to end through run_experiment: the tpu-ici model's axis is
    cheaper for the compressed solver than the dense one (same spec
    otherwise)."""
    base = dataclasses.replace(
        TINY, comm=dataclasses.replace(TINY.comm, model="tpu-ici",
                                       compute_s_per_iter=0.0))
    mat = materialize(base, key=0)
    dense = run_experiment(base, key=0, materialized=mat)
    tk = run_experiment(dataclasses.replace(base, solver=SolverSpec(
        name="dif_topk", T_GD=30, T_con=2)), key=0, materialized=mat)
    assert tk.time_axis[-1] < dense.time_axis[-1]


# --------------------------------------------- paper-shape acceptance

def test_acceptance_topk_quarter_d_paper_shape():
    """PR-5 acceptance: dif_altgdmin with topk_gossip at k = d/4 on the
    paper's (d=100, r=4, L=16) shape reaches sd_max within 2x of the
    dense-gossip floor at equal T_GD, while the priced time axis and the
    CommSignature bytes/iter both show >= 4x reduction."""
    spec = ExperimentSpec(
        problem=ProblemSpec(d=100, T=64, r=4, n=60, L=16, kappa=1.5,
                            noise_std=3e-2),
        topology=TopologySpec(family="ring", weights="metropolis"),
        init=InitSpec(T_pm=30, T_con=10),
        solver=SolverSpec(name="dif_altgdmin", T_GD=400, T_con=3))
    mat = materialize(spec, key=0)
    dense = run_experiment(spec, key=0, materialized=mat)
    tk = run_experiment(dataclasses.replace(spec, solver=SolverSpec(
        name="dif_topk", T_GD=400, T_con=3, compression_k=25)), key=0,
        materialized=mat)
    assert tk.sd_max[-1] <= 2.0 * dense.sd_max[-1], (
        float(tk.sd_max[-1]), float(dense.sd_max[-1]))

    # >= 4x wire reduction, priced and declared
    d, r = 100, 4
    solver = get_solver("dif_topk")
    sig = solver.signature(3, d=d, r=r, compression_k=25)
    dense_bytes = CommSignature("gossip", 3).bytes_per_iter(d * r, 8, 16, 2)
    assert dense_bytes / sig.bytes_per_iter(d * r, 8, 16, 2) >= 4.0
    flat = cm.NetworkModel(bandwidth_bytes=1e9 / 8, latency_s=0.0,
                           jitter_std_s=0.0, bytes_per_entry=8)
    dense_axis = cm.time_axis_from_signature(CommSignature("gossip", 3),
                                             400, d, r, 16, 2, 0.0,
                                             model=flat)
    topk_axis = cm.time_axis_from_signature(sig, 400, d, r, 16, 2, 0.0,
                                            model=flat)
    assert dense_axis[-1] / topk_axis[-1] >= 4.0


# ------------------------------------------ fold-schedule pin (bugfix)

def _folded_setup(T_GD):
    from repro.core import generate_problem, node_view, split_samples
    prob = generate_problem(jax.random.PRNGKey(9), d=24, T=16, r=3, n=40,
                            L=8, kappa=1.5)
    folded = split_samples(prob, 4)
    Xg, yg = node_view(folded)
    W = jnp.asarray(metropolis_weights(ring(8)))
    U0 = jnp.stack([jnp.linalg.qr(jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(10), g), (24, 3)))[0]
        for g in range(8)])
    return prob, Xg, yg, W, U0


def test_fold_schedule_is_2tau_2tau_plus_1():
    """Pin the sample-split schedule: 0-based iteration tau consumes
    fold (2*tau mod F) for the min step and (2*tau + 1 mod F) for the
    gradient step — exactly what a hand-rolled loop with that selection
    produces."""
    from repro.core import dif_altgdmin
    from repro.core.engine import (AltgdminEngine, ref_grad_U,
                                   ref_minimize_B)
    from repro.core.spectral import _qr_pos
    from repro.core.agree import agree
    T_GD, T_con, F = 5, 2, 4
    prob, Xg, yg, W, U0 = _folded_setup(T_GD)
    eng = AltgdminEngine("xla-ref")
    got = dif_altgdmin(U0, Xg, yg, W, eta=1e-3, T_GD=T_GD, T_con=T_con,
                       engine=eng)

    U = U0
    for tau in range(T_GD):
        Xb, yb = Xg[(2 * tau) % F], yg[(2 * tau) % F]
        Xc, yc = Xg[(2 * tau + 1) % F], yg[(2 * tau + 1) % F]
        B = ref_minimize_B(U, Xb, yb)
        G = ref_grad_U(U, B, Xc, yc)
        U = _qr_pos(agree(U - (1e-3 * 8) * G, W, T_con))[0]
    # machine-eps only (scan-traced vs eager loop); the off-by-one
    # schedule of the old docstring, (2*tau - 1, 2*tau), diverges at
    # O(0.1) on this instance
    np.testing.assert_allclose(np.asarray(got.U_nodes), np.asarray(U),
                               rtol=0, atol=1e-12)


def test_final_B_refits_on_last_min_fold():
    """Regression (PR-5 satellite): B_fin used to refit on fold 0
    regardless of where the trajectory ended; it must use the LAST min
    fold, 2*(T_GD - 1) mod F — the data that produced the final U."""
    from repro.core import dif_altgdmin, beyond_central_altgdmin
    from repro.core.engine import AltgdminEngine
    T_GD, F = 5, 4
    prob, Xg, yg, W, U0 = _folded_setup(T_GD)
    eng = AltgdminEngine("xla-ref")
    res = dif_altgdmin(U0, Xg, yg, W, eta=1e-3, T_GD=T_GD, T_con=2,
                       engine=eng)
    last_min = (2 * (T_GD - 1)) % F
    want = eng.minimize_B(res.U_nodes, Xg[last_min], yg[last_min])
    np.testing.assert_array_equal(np.asarray(res.B_nodes),
                                  np.asarray(want))
    # beyond_central interleaves local_steps folds: its last min fold is
    # 2*(T_GD*local_steps - 1) mod F
    res_bc = beyond_central_altgdmin(U0, Xg, yg, W, eta=1e-3, T_GD=T_GD,
                                     T_con=1, local_steps=2, engine=eng)
    last_min_bc = (2 * (T_GD * 2 - 1)) % F
    want_bc = eng.minimize_B(res_bc.U_nodes, Xg[last_min_bc],
                             yg[last_min_bc])
    np.testing.assert_array_equal(np.asarray(res_bc.B_nodes),
                                  np.asarray(want_bc))
