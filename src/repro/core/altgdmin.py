"""AltGDmin family — Algorithm 3 (Dif-AltGDmin) and the three baselines
compared in the paper's Experiment 1:

  * ``dif_altgdmin``        — the paper's contribution (adapt-then-combine);
  * ``dec_altgdmin``        — [9]'s combine-then-adjust (consensus on
                              gradients before the projected-GD step);
  * ``centralized_altgdmin``— AltGDmin [10] with a fusion center (exact
                              gradient aggregation);
  * ``dgd_altgdmin``        — the DGD-variation defined in Experiment 1:
                              Ũ_g ← QR((1/deg_g) Σ_{g'∈N_g} U_g' − η ∇f_g);

plus the related-work combine-rule variants enabled by the unified
consensus layer (:mod:`repro.distributed.consensus`):

  * ``exact_diffusion_altgdmin`` — the projection-corrected combine of
    Exact Subspace Diffusion (arXiv:2304.07358): the adapt iterate is
    bias-corrected with the previous adapt state before the AGREE
    product, so the combine tracks the exact fixed point;
  * ``beyond_central_altgdmin``  — the communication-efficient variant of
    Beyond Centralization (arXiv:2512.22675): several local adapt steps
    per outer iteration, then ONE gossip round (a single d×r exchange
    per iteration instead of the T_con-round chain);

and the compressed-communication variants on the consensus layer's
stateful wire rules (top-k sparsified / quantized / event-triggered
gossip with error feedback riding the scan carry):

  * ``dif_topk_altgdmin``      — ``topk_gossip`` (k rows per round);
  * ``dif_quantized_altgdmin`` — ``quantized_gossip`` (bf16/int8 wire);
  * ``dif_event_altgdmin``     — ``event_gossip`` (threshold-triggered);

and the dropout-tolerant variants consuming a (T_GD, L) availability
mask (system-realism layer; down nodes are frozen for the iteration):

  * ``dif_partial_altgdmin`` — ``partial_gossip`` (masked weights);
  * ``dif_stale_altgdmin``   — ``stale_gossip`` (last-delivered copies);
  * ``dif_pushsum_altgdmin`` — ``push_sum_gossip`` (bias-corrected
    ratio consensus for the directed masked topology).

Simulator layout: node axis leading. U_nodes: (L, d, r); per-node data
Xg: (L, tpn, n, d), yg: (L, tpn, n).  All loops are lax.scan so tracing
stays cheap for T_GD in the hundreds.

Sample splitting: if Xg/yg carry a leading fold axis (F, L, ...), the
0-based iteration τ = 0, 1, … uses fold (2τ mod F) for the min step and
fold (2τ+1 mod F) for the gradient step, mirroring Algorithm 3's
disjoint-set schedule (consecutive fresh folds per iteration, wrapping
modulo F); the final B refit reuses the LAST min fold, 2·(T_GD−1) mod F,
so B is fit on the same data that produced the final U.  Without a fold
axis the same data is reused every iteration (as in the paper's
simulations) and the refit fold index is irrelevant.

Execution: every driver routes its min-B/gradient/combine phases through
an :class:`repro.core.engine.AltgdminEngine` (``engine=`` or ``backend=``
kwargs).  The default backend off-TPU is ``xla-ref`` — the seed's unfused
einsum paths, bit-identical to the pre-engine code; ``pallas`` /
``pallas-interpret`` select the fused node-batched kernel where one outer
iteration is a single dispatch and AGREE runs as one precomputed
W^{T_con} combine.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.engine import (AltgdminEngine, ref_grad_U, ref_minimize_B,
                               resolve_engine)
from repro.core.metrics import subspace_distance, consensus_spread
from repro.core.spectral import _qr_pos
from repro.distributed.consensus import (ExactDiffusionCombine, get_rule,
                                         neighbor_average_matrix)


class RunResult(NamedTuple):
    U_nodes: jax.Array       # (L, d, r) final bases ((1,d,r) for centralized)
    B_nodes: jax.Array       # (L, tpn, r) final coefficients
    sd_max: jax.Array        # (T_GD,) max_g SD₂(U_g, U*) per iteration
    sd_mean: jax.Array       # (T_GD,)
    spread: jax.Array        # (T_GD,) max_{g,g'} ||U_g − U_g'||_F
    eta: float
    # (T_GD,) measured per-iteration send rate (event-triggered rule
    # only; feeds the system clock's wire pricing).  Trailing default
    # keeps the historical 6-positional constructors working.
    send_frac: Optional[jax.Array] = None


# ----------------------------------------------------------------------
# shared pieces
# ----------------------------------------------------------------------

# The unfused reference implementations live in repro.core.engine (they
# are the engine's xla-ref backend); re-exported here under their
# historical names.
minimize_B = ref_minimize_B
grad_U = ref_grad_U


def theta_nodes(U_nodes, B_nodes):
    """θ_t = U_g b_t for local tasks: (L, tpn, d)."""
    return jnp.einsum("gdr,gtr->gtd", U_nodes, B_nodes)


def _select(Xg, yg, fold):
    if Xg.ndim == 5:     # (F, L, tpn, n, d)
        F = Xg.shape[0]
        i = fold % F
        return Xg[i], yg[i]
    return Xg, yg


def _metrics(U_nodes, U_star):
    sd = jax.vmap(lambda U: subspace_distance(U, U_star))(U_nodes)
    return jnp.max(sd), jnp.mean(sd), consensus_spread(U_nodes)


def resolve_eta(eta, n, sigma_max=None, R_diag=None, L=None,
                c_eta: float = 0.4):
    """η = c_η / (n σ*max²) (Theorem 1).  When σ*max is unknown, estimate
    σ̂max² = L · max diag(R^(T_pm)) from the spectral init (the power method
    converges to the top eigenvalue of (1/L) Θ*Θ*ᵀ = σ*max²/L), matching the
    paper's simulation recipe."""
    if eta is not None:
        return float(eta)
    if sigma_max is not None:
        return c_eta / (n * sigma_max**2)
    # η is a static float: evaluate on the concrete init even when the
    # caller is being traced (a jitted solver call)
    with jax.ensure_compile_time_eval():
        sig2 = float(L * jnp.max(R_diag))
    return c_eta / (n * sig2)


# ----------------------------------------------------------------------
# algorithms
# ----------------------------------------------------------------------

def dif_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int, T_con: int,
                 U_star=None, engine: Optional[AltgdminEngine] = None,
                 backend: Optional[str] = None) -> RunResult:
    """Algorithm 3: adapt (min-B + local projected-GD pre-image) THEN
    combine (AGREE on the updated iterate), then QR retraction."""
    L = U0_nodes.shape[0]
    U_star_ = U_star if U_star is not None else U0_nodes[0]
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4                  # no sample-split fold axis
    mix = eng.make_mixer(W, T_con)

    def step(U, tau):
        Xb, yb = _select(Xg, yg, 2 * tau)
        Xc, yc = _select(Xg, yg, 2 * tau + 1)
        B, G = eng.min_grad(U, Xb, yb, Xc, yc,
                            same_data=same_data)   # lines 8 & 11, fused
        U_breve = U - (eta * L) * G           # local update (line 12)
        U_tilde = mix(U_breve)                # diffusion     (line 13)
        U_new, _ = _qr_pos(U_tilde)           # projection    (line 14)
        return U_new, _metrics(U_new, U_star_)

    U_fin, (sd_max, sd_mean, spread) = jax.lax.scan(
        step, U0_nodes, jnp.arange(T_GD))
    B_fin = eng.minimize_B(U_fin, *_select(Xg, yg, 2 * (T_GD - 1)))
    return RunResult(U_fin, B_fin, sd_max, sd_mean, spread, eta)


def dec_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int, T_con: int,
                 U_star=None, engine: Optional[AltgdminEngine] = None,
                 backend: Optional[str] = None) -> RunResult:
    """Dec-AltGDmin [9]: combine-then-adjust — consensus on the *gradients*
    first, then each node takes the projected-GD step with the gossiped
    gradient estimate."""
    L = U0_nodes.shape[0]
    U_star_ = U_star if U_star is not None else U0_nodes[0]
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4
    mix = eng.make_mixer(W, T_con)

    def step(U, tau):
        Xb, yb = _select(Xg, yg, 2 * tau)
        Xc, yc = _select(Xg, yg, 2 * tau + 1)
        B, G = eng.min_grad(U, Xb, yb, Xc, yc, same_data=same_data)
        G_hat = mix(G)                        # consensus on gradients
        U_new, _ = _qr_pos(U - (eta * L) * G_hat)
        return U_new, _metrics(U_new, U_star_)

    U_fin, (sd_max, sd_mean, spread) = jax.lax.scan(
        step, U0_nodes, jnp.arange(T_GD))
    B_fin = eng.minimize_B(U_fin, *_select(Xg, yg, 2 * (T_GD - 1)))
    return RunResult(U_fin, B_fin, sd_max, sd_mean, spread, eta)


def centralized_altgdmin(U0, Xg, yg, *, eta: float, T_GD: int,
                         U_star=None, engine: Optional[AltgdminEngine] = None,
                         backend: Optional[str] = None) -> RunResult:
    """AltGDmin [10] with a fusion center: exact gradient sum, single U.
    U0: (d, r).  Data still node-major for API symmetry."""
    U_star_ = U_star if U_star is not None else U0
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4

    def step(U, tau):
        Xb, yb = _select(Xg, yg, 2 * tau)
        Xc, yc = _select(Xg, yg, 2 * tau + 1)
        Ub = jnp.broadcast_to(U[None], (Xb.shape[0],) + U.shape)
        B, G = eng.min_grad(Ub, Xb, yb, Xc, yc, same_data=same_data)
        grad = jnp.sum(G, axis=0)             # fusion-center aggregation
        U_new, _ = _qr_pos(U - eta * grad)
        sd = subspace_distance(U_new, U_star_)
        return U_new, (sd, sd, jnp.zeros((), U.dtype))

    U_fin, (sd_max, sd_mean, spread) = jax.lax.scan(
        step, U0, jnp.arange(T_GD))
    Xb, yb = _select(Xg, yg, 0)
    B_fin = eng.minimize_B(jnp.broadcast_to(U_fin[None],
                                            (Xb.shape[0],) + U_fin.shape),
                           Xb, yb)
    return RunResult(U_fin[None], B_fin, sd_max, sd_mean, spread, eta)


def exact_diffusion_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                             T_con: int, U_star=None,
                             engine: Optional[AltgdminEngine] = None,
                             backend: Optional[str] = None) -> RunResult:
    """Exact Subspace Diffusion (arXiv:2304.07358): adapt-correct-combine.

    Per iteration: ψ_g = U_g − ηL ∇f_g (adapt), then the bias correction
    φ_g = ψ_g + U_g^{prev-combined} − ψ_g^{prev} (the exact-diffusion
    recursion — at τ=0 the correction vanishes), then T_con AGREE rounds
    on φ and the QR retraction back onto the Grassmannian (the subspace
    "projection" step).  Removes the diffusion bias floor when the nodes'
    local minimizers disagree (heterogeneous tasks)."""
    L = U0_nodes.shape[0]
    U_star_ = U_star if U_star is not None else U0_nodes[0]
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4
    mix = eng.make_mixer(W, T_con, rule="exact_diffusion")

    def step(carry, tau):
        U, psi_prev = carry
        Xb, yb = _select(Xg, yg, 2 * tau)
        Xc, yc = _select(Xg, yg, 2 * tau + 1)
        B, G = eng.min_grad(U, Xb, yb, Xc, yc, same_data=same_data)
        psi = U - (eta * L) * G                        # adapt
        phi = ExactDiffusionCombine.correct(psi, psi_prev, U)
        U_tilde = mix(phi)                             # combine
        U_new, _ = _qr_pos(U_tilde)                    # projection
        return (U_new, psi), _metrics(U_new, U_star_)

    (U_fin, _), (sd_max, sd_mean, spread) = jax.lax.scan(
        step, (U0_nodes, U0_nodes), jnp.arange(T_GD))
    B_fin = eng.minimize_B(U_fin, *_select(Xg, yg, 2 * (T_GD - 1)))
    return RunResult(U_fin, B_fin, sd_max, sd_mean, spread, eta)


def beyond_central_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                            T_con: int = 1, local_steps: int = 1,
                            U_star=None,
                            engine: Optional[AltgdminEngine] = None,
                            backend: Optional[str] = None) -> RunResult:
    """Beyond Centralization (arXiv:2512.22675): communication-efficient
    AltGDmin — ``local_steps`` full local adapt steps (min-B + projected
    GD + retraction, no communication) per outer iteration, then ONE
    gossip round.  The wire cost per outer iteration is a single d×r
    neighbour exchange, independent of ``T_con`` (which the combine rule
    ignores by construction)."""
    L = U0_nodes.shape[0]
    U_star_ = U_star if U_star is not None else U0_nodes[0]
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4
    mix1 = eng.make_mixer(W, T_con, rule="beyond_central")

    def step(U, tau):
        for j in range(local_steps):                   # local adapt epoch
            fold = tau * local_steps + j
            Xb, yb = _select(Xg, yg, 2 * fold)
            Xc, yc = _select(Xg, yg, 2 * fold + 1)
            B, G = eng.min_grad(U, Xb, yb, Xc, yc, same_data=same_data)
            U, _ = _qr_pos(U - (eta * L) * G)
        U_new, _ = _qr_pos(mix1(U))                    # one combine round
        return U_new, _metrics(U_new, U_star_)

    U_fin, (sd_max, sd_mean, spread) = jax.lax.scan(
        step, U0_nodes, jnp.arange(T_GD))
    # the last LOCAL min fold: iteration T_GD−1's final adapt step
    B_fin = eng.minimize_B(U_fin, *_select(Xg, yg,
                                           2 * (T_GD * local_steps - 1)))
    return RunResult(U_fin, B_fin, sd_max, sd_mean, spread, eta)


def dgd_altgdmin(U0_nodes, Xg, yg, adj, *, eta: float, T_GD: int,
                 U_star=None, engine: Optional[AltgdminEngine] = None,
                 backend: Optional[str] = None) -> RunResult:
    """DGD-variation of AltGDmin (Experiment 1 (iii)):
    Ũ_g ← QR( (1/deg_g) Σ_{g'∈N_g} U_g'^{(τ-1)} − η ∇f_g ).
    ``adj``: (L, L) adjacency (no self loops), per the paper's formula the
    neighbour average EXCLUDES the node itself."""
    M = neighbor_average_matrix(adj)          # row-stochastic neighbour avg
    U_star_ = U_star if U_star is not None else U0_nodes[0]
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4
    nbr_mix = eng.make_neighbor_mixer(M)

    def step(U, tau):
        Xb, yb = _select(Xg, yg, 2 * tau)
        Xc, yc = _select(Xg, yg, 2 * tau + 1)
        B, G = eng.min_grad(U, Xb, yb, Xc, yc, same_data=same_data)
        nbr = nbr_mix(U)
        U_new, _ = _qr_pos(nbr - eta * G)
        return U_new, _metrics(U_new, U_star_)

    U_fin, (sd_max, sd_mean, spread) = jax.lax.scan(
        step, U0_nodes, jnp.arange(T_GD))
    B_fin = eng.minimize_B(U_fin, *_select(Xg, yg, 2 * (T_GD - 1)))
    return RunResult(U_fin, B_fin, sd_max, sd_mean, spread, eta)


# ----------------------------------------------------------------------
# compressed-communication variants (stateful consensus rules)
# ----------------------------------------------------------------------

def _compressed_dif(U0_nodes, Xg, yg, W, *, rule_name: str, eta: float,
                    T_GD: int, T_con: int, U_star, engine, backend,
                    **rule_kw) -> RunResult:
    """Dif-AltGDmin (adapt-then-combine) with a STATEFUL compressed
    combine rule: the rule's per-node compression state (error-feedback
    residual / last-sent iterate) rides the lax.scan carry next to U and
    is updated by every gossip round, so compression error is fed back
    instead of discarded."""
    L = U0_nodes.shape[0]
    U_star_ = U_star if U_star is not None else U0_nodes[0]
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4
    rule = get_rule(rule_name)
    mix = eng.make_state_mixer(W, T_con, rule=rule_name, **rule_kw)
    state0 = rule.init_state(U0_nodes, **rule_kw)
    # Event rule: also record the measured trigger rate per iteration
    # (first-round decision against the carried public copies — the
    # same condition the rule's encode uses), the telemetry the system
    # clock prices actual wire traffic with.
    is_event = rule_name == "event_gossip"
    threshold = float(rule_kw.get("event_threshold", 0.0))

    def step(carry, tau):
        U, cstate = carry
        Xb, yb = _select(Xg, yg, 2 * tau)
        Xc, yc = _select(Xg, yg, 2 * tau + 1)
        B, G = eng.min_grad(U, Xb, yb, Xc, yc, same_data=same_data)
        U_breve = U - (eta * L) * G              # local adapt
        if is_event:
            sf = rule.send_fraction(U_breve, cstate, threshold)
        U_tilde, cstate = mix(U_breve, cstate)   # compressed diffusion
        U_new, _ = _qr_pos(U_tilde)              # projection
        out = _metrics(U_new, U_star_)
        if is_event:
            out = out + (sf,)
        return (U_new, cstate), out

    (U_fin, _), outs = jax.lax.scan(
        step, (U0_nodes, state0), jnp.arange(T_GD))
    sfrac = None
    if is_event:
        sd_max, sd_mean, spread, sfrac = outs
    else:
        sd_max, sd_mean, spread = outs
    B_fin = eng.minimize_B(U_fin, *_select(Xg, yg, 2 * (T_GD - 1)))
    return RunResult(U_fin, B_fin, sd_max, sd_mean, spread, eta,
                     send_frac=sfrac)


def dif_topk_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                      T_con: int, compression_k: int = 0,
                      consensus_gamma: float = 1.0, U_star=None,
                      engine: Optional[AltgdminEngine] = None,
                      backend: Optional[str] = None) -> RunResult:
    """Dif-AltGDmin over the ``topk_gossip`` rule: each gossip round
    exchanges only the ``compression_k`` largest-norm rows of the
    error-compensated iterate (0 → d/4), with the compression error fed
    back next round.  ``compression_k = d`` recovers ``dif_altgdmin``
    bit-identically on the exact (xla-ref / f64) path; fused backends
    agree to f32 round-off only, since dense gossip hoists the whole
    AGREE phase into one precomputed W^{T_con} combine while the
    compressed rule must mix round by round.  ``consensus_gamma`` is
    the CHOCO consensus step size: ``Z ← Z + γ(W x̂ − Z)`` relaxes the
    gossip move toward the compressed average, stabilizing aggressive
    sparsification (k ≪ d/4); γ = 1 is the plain combine, preserved
    bit-for-bit."""
    return _compressed_dif(U0_nodes, Xg, yg, W, rule_name="topk_gossip",
                           eta=eta, T_GD=T_GD, T_con=T_con, U_star=U_star,
                           engine=engine, backend=backend,
                           compression_k=compression_k,
                           consensus_gamma=consensus_gamma)


def dif_quantized_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                           T_con: int, compression: Optional[str] = None,
                           consensus_gamma: float = 1.0, U_star=None,
                           engine: Optional[AltgdminEngine] = None,
                           backend: Optional[str] = None) -> RunResult:
    """Dif-AltGDmin over the ``quantized_gossip`` rule: the wire carries
    a low-precision cast of the error-compensated iterate —
    ``compression`` picks ``"bf16"`` (default), ``"int8"``, or
    ``"int8_stochastic"`` — while the combine accumulates in f32 (f64 on
    the exact x64 path)."""
    return _compressed_dif(U0_nodes, Xg, yg, W,
                           rule_name="quantized_gossip", eta=eta,
                           T_GD=T_GD, T_con=T_con, U_star=U_star,
                           engine=engine, backend=backend,
                           compression=compression,
                           consensus_gamma=consensus_gamma)


def dif_event_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                       T_con: int, event_threshold: float = 0.0,
                       consensus_gamma: float = 1.0, U_star=None,
                       engine: Optional[AltgdminEngine] = None,
                       backend: Optional[str] = None) -> RunResult:
    """Dif-AltGDmin over the ``event_gossip`` rule: a node re-broadcasts
    its iterate only when ‖U_g − U_g^last-sent‖_F > θ·‖U_g‖_F
    (θ = ``event_threshold``); neighbours combine with the stale
    last-sent value otherwise.  θ = 0 recovers ``dif_altgdmin``
    bit-identically on the exact (xla-ref / f64) path (fused backends:
    f32 round-off vs the hoisted W^{T_con} dense combine)."""
    return _compressed_dif(U0_nodes, Xg, yg, W, rule_name="event_gossip",
                           eta=eta, T_GD=T_GD, T_con=T_con, U_star=U_star,
                           engine=engine, backend=backend,
                           event_threshold=event_threshold,
                           consensus_gamma=consensus_gamma)


# ----------------------------------------------------------------------
# dropout-tolerant variants (availability-masked consensus rules)
# ----------------------------------------------------------------------

def _masked_dif(U0_nodes, Xg, yg, W, *, rule_name: str, eta: float,
                T_GD: int, T_con: int, avail, U_star, engine,
                backend) -> RunResult:
    """Dif-AltGDmin (adapt-then-combine) under a per-iteration node
    availability mask ``avail: (T_GD, L)`` (truthy = live).  Down nodes
    are FULLY frozen for the iteration — no adapt, no combine, no
    retraction — and the masked combine rule decides how the live nodes
    mix around the hole (weight folding / stale copies / push-sum).
    All T_con AGREE rounds of one iteration share its mask: node churn
    is an outer-iteration phenomenon here.  ``avail=None`` (or all
    ones) reproduces the dense drivers — bit-for-bit for
    ``partial_gossip`` and ``stale_gossip``, to float round-off for
    ``push_sum_gossip`` (its ratio correction is different arithmetic).
    """
    L = U0_nodes.shape[0]
    U_star_ = U_star if U_star is not None else U0_nodes[0]
    eng = resolve_engine(engine, backend)
    same_data = Xg.ndim == 4
    rule = get_rule(rule_name)
    stateful = rule_name == "stale_gossip"
    if avail is None:
        avail = jnp.ones((T_GD, L), bool)
    avail = jnp.asarray(avail).astype(bool)
    if avail.shape != (T_GD, L):
        raise ValueError(f"availability mask {avail.shape} does not "
                         f"match (T_GD, L) = ({T_GD}, {L})")
    if stateful:
        mix = eng.make_masked_state_mixer(W, T_con, rule=rule_name)
        state0 = rule.init_state(U0_nodes)
    else:
        mix = eng.make_masked_mixer(W, T_con, rule=rule_name)

    def step(carry, xt):
        tau, m = xt
        U = carry[0] if stateful else carry
        Xb, yb = _select(Xg, yg, 2 * tau)
        Xc, yc = _select(Xg, yg, 2 * tau + 1)
        B, G = eng.min_grad(U, Xb, yb, Xc, yc, same_data=same_data)
        U_breve = U - (eta * L) * G              # local adapt
        if stateful:
            U_tilde, cstate = mix(U_breve, carry[1], m)
        else:
            U_tilde = mix(U_breve, m)
        # down nodes are frozen for the whole iteration (a masked rule
        # already returns their iterate unchanged through the combine,
        # but the adapt/retraction must be undone too)
        U_new = jnp.where(m[:, None, None], _qr_pos(U_tilde)[0], U)
        out = _metrics(U_new, U_star_)
        return ((U_new, cstate) if stateful else U_new), out

    carry0 = (U0_nodes, state0) if stateful else U0_nodes
    carry_fin, (sd_max, sd_mean, spread) = jax.lax.scan(
        step, carry0, (jnp.arange(T_GD), avail))
    U_fin = carry_fin[0] if stateful else carry_fin
    B_fin = eng.minimize_B(U_fin, *_select(Xg, yg, 2 * (T_GD - 1)))
    return RunResult(U_fin, B_fin, sd_max, sd_mean, spread, eta)


def dif_partial_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                         T_con: int, avail=None, U_star=None,
                         engine: Optional[AltgdminEngine] = None,
                         backend: Optional[str] = None) -> RunResult:
    """Dif-AltGDmin over ``partial_gossip``: per iteration, links with a
    down endpoint carry no weight and the lost mass folds into the self
    weight (the effective matrix stays doubly stochastic for symmetric
    W).  ``avail`` all-ones reproduces ``dif_altgdmin`` bit-for-bit."""
    return _masked_dif(U0_nodes, Xg, yg, W, rule_name="partial_gossip",
                       eta=eta, T_GD=T_GD, T_con=T_con, avail=avail,
                       U_star=U_star, engine=engine, backend=backend)


def dif_stale_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                       T_con: int, avail=None, U_star=None,
                       engine: Optional[AltgdminEngine] = None,
                       backend: Optional[str] = None) -> RunResult:
    """Dif-AltGDmin over ``stale_gossip``: every node's last-published
    copy persists in the scan carry; live nodes combine dense weights
    with a down neighbour's STALE copy instead of reweighting around
    it.  ``avail`` all-ones reproduces ``dif_altgdmin`` bit-for-bit."""
    return _masked_dif(U0_nodes, Xg, yg, W, rule_name="stale_gossip",
                       eta=eta, T_GD=T_GD, T_con=T_con, avail=avail,
                       U_star=U_star, engine=engine, backend=backend)


def dif_pushsum_altgdmin(U0_nodes, Xg, yg, W, *, eta: float, T_GD: int,
                         T_con: int, avail=None, U_star=None,
                         engine: Optional[AltgdminEngine] = None,
                         backend: Optional[str] = None) -> RunResult:
    """Dif-AltGDmin over ``push_sum_gossip``: the masked mixing matrix
    is column-stochastic (each live sender renormalizes its own column)
    and a companion weight scalar carried through the same matrix
    bias-corrects the readout z/w — exact averaging under the DIRECTED
    effective topologies dropout induces.  ``avail`` all-ones matches
    ``dif_altgdmin`` to float round-off (the ratio correction is
    genuinely different arithmetic)."""
    return _masked_dif(U0_nodes, Xg, yg, W, rule_name="push_sum_gossip",
                       eta=eta, T_GD=T_GD, T_con=T_con, avail=avail,
                       U_star=U_star, engine=engine, backend=backend)
