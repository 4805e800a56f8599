"""Solver programs — one declarative IR, three lowerings.

The AltGDmin family is one alternating loop: a local min-B/gradient
step through the :class:`~repro.core.engine.AltgdminEngine`, a
per-solver combination of the iterate with a
:class:`~repro.distributed.consensus.CombineRule`, and the QR
retraction.  Historically the repo encoded that loop 2–3 times per
solver — a simulator scan driver in :mod:`repro.core.altgdmin`, a
hand-written ``*_mesh`` closure on :func:`repro.core.runtime.
_altgdmin_mesh`, and (for ``dif_altgdmin`` only) a separate
virtual-node runtime.  A :class:`SolverProgram` captures the loop ONCE
as data:

  * ``update`` — the per-iteration body, written against a substrate-
    independent :class:`ProgramCtx` (``min_grad``/``mix``/``qr``/
    ``all_sum``/``where_live`` plus the step sizes);
  * ``mixer`` — which CombineRule lowering family carries the combine
    (``plain``/``neighbor``/``central``/``state``/``masked``/
    ``masked_state``);
  * ``aux`` — what rides the scan carry next to U (nothing, the
    previous adapt iterate, or the rule's consensus state);
  * call-convention metadata (``topology``/``stacked``/``spec_kwargs``/
    ``defaults``/``refit``) that the registry previously special-cased
    per solver.

Three *lowerings* execute any program:

  * :func:`lower_simulator`   — stacked ``lax.scan`` over the node axis
    (dense or sparse segment-sum combine, both engine backends),
    bit-identical to the legacy drivers (which remain in
    :mod:`repro.core.altgdmin` as the pinned oracles);
  * :func:`lower_mesh`        — shard_map with one node per device,
    per-shift ``ppermute`` gossip rounds, on the shared
    :func:`~repro.core.runtime._altgdmin_mesh` skeleton;
  * :func:`lower_virtual_mesh`— the virtual-node block tier
    (L = devices × block): co-located edges as on-device segment-sums,
    one collective-permute per cross-device shift class, on
    :func:`~repro.core.runtime._altgdmin_virtual_mesh`.

Registering a new solver is ~20 lines: write an ``update`` body against
the ctx, ``register_program`` it with its combine rule, and all three
substrates (plus the runner's substrate dispatch) come for free.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.altgdmin import RunResult, _metrics, _select
from repro.core.engine import resolve_engine
from repro.core.metrics import subspace_distance
from repro.core.runtime import _altgdmin_mesh, _altgdmin_virtual_mesh
from repro.core.spectral import _qr_pos
from repro.distributed.consensus import (ExactDiffusionCombine, get_rule,
                                         neighbor_average_matrix)
from repro.utils.spans import span


class ProgramCtx(NamedTuple):
    """What a solver's per-iteration ``update`` may touch — each lowering
    binds these to its substrate.

    ``min_grad(U, fold)`` — fused min-B + gradient on iteration
    ``fold``'s sample-split folds (simulator; the mesh substrates have
    no fold axis and ignore ``fold``); ``mix`` — the combine closure of
    the program's mixer family; ``qr`` — the positive-diagonal QR
    retraction (vmapped over the block on the virtual tier);
    ``all_sum`` — the fusion-center exact gradient sum (``central``
    programs only); ``where_live(m, a, b)`` — per-node freeze under an
    availability mask (masked programs only); ``send_fraction(Z, st)``
    — the event rule's measured trigger rate (simulator only; None
    elsewhere, so the extra output is skipped)."""
    min_grad: Callable
    mix: Optional[Callable]
    qr: Callable
    eta: float
    eta_L: float
    local_steps: int
    all_sum: Optional[Callable]
    where_live: Optional[Callable]
    send_fraction: Optional[Callable]


# ----------------------------------------------------------------------
# refit-fold schedules (the _select index of the final B refit)
# ----------------------------------------------------------------------

def _refit_last_min(T_GD: int, local_steps: int) -> int:
    """The last min fold, 2·(T_GD−1): B is fit on the same data that
    produced the final U."""
    return 2 * (T_GD - 1)


def _refit_last_local(T_GD: int, local_steps: int) -> int:
    """Beyond-central: iteration T_GD−1's final LOCAL adapt step."""
    return 2 * (T_GD * local_steps - 1)


def _refit_first(T_GD: int, local_steps: int) -> int:
    """Centralized: the historical fold-0 refit."""
    return 0


# ----------------------------------------------------------------------
# the program IR
# ----------------------------------------------------------------------

MIXERS = ("plain", "neighbor", "central", "state", "masked",
          "masked_state")


class DispatchBudget(NamedTuple):
    """Statically-enforced kernel-dispatch pricing of a program's
    lowerings (checked by ``python -m tools.reprolint``, rule JX001).

    Each substrate entry is coefficients ``(a, b, c, d)`` of the
    per-outer-iteration ``pallas_call`` count on fused backends::

        count = a + R·(b + c·K) + d·local_steps

    where R is the combine rule's ``CommSignature.rounds_per_iter`` and
    K the number of cyclic shift classes of the decomposed mixing
    matrix (0 on the simulator — its AGREE chain is the hoisted
    W^{T_con} combine).  ``a`` counts the round-independent dispatches
    (the fused min-B+gradient; the hoisted combine), ``b``/``c`` the
    per-round and per-round-per-shift ones (stateful encode/decode),
    ``d`` the local adapt epoch.  One extra dispatch — the final B
    refit — always sits outside the outer scan and is budgeted
    separately by the analyzer.

    ``wire_mesh`` / ``wire_virtual`` price the gossip structure (rule
    JX004): ppermutes per outer iteration must equal R·K·wire — wire is
    1 for value-shipping rules, 2 where a payload rides with each
    message (top-k indices, quantization scales, push-sum weights), 0
    for the fusion-center psum."""
    simulator: tuple
    mesh: tuple
    virtual: tuple
    wire_mesh: int = 1
    wire_virtual: int = 1

    def per_iter(self, substrate: str, rounds: int, n_shifts: int,
                 local_steps: int) -> int:
        a, b, c, d = getattr(self, "virtual" if substrate == "virtual"
                             else substrate)
        return a + rounds * (b + c * n_shifts) + d * local_steps


@dataclasses.dataclass(frozen=True)
class SolverProgram:
    """One AltGDmin-family solver as data.

    ``update(ctx, U, aux, tau[, m]) -> (U_new, aux_new, extra)`` is the
    per-iteration body; ``aux`` is the scan-carry slot declared by the
    ``aux`` field (``None`` | ``"iterate"`` — the previous adapt state,
    seeded with U0 | ``"state"`` — the combine rule's ``init_state``);
    ``extra`` is an optional per-iteration scalar recorded next to the
    metrics (the event rule's send fraction; None elsewhere).
    ``mixer`` picks the CombineRule lowering family, ``rule_kwargs``
    names the spec knobs forwarded into stateful mixers and their
    ``init_state``, and ``defaults`` carries the knobs' default values
    as ``((name, value), ...)``.  ``stacked=False`` marks the one
    fusion-center program whose simulator carries a single (d, r)
    iterate.  ``refit(T_GD, local_steps)`` is the ``_select`` index of
    the final B refit."""
    name: str
    combine: str
    update: Callable
    mixer: str = "plain"
    stacked: bool = True
    topology: str = "W"              # "W" | "adj" | "none"
    decentralized: bool = True
    takes_avail: bool = False
    records_send_frac: bool = False
    aux: Optional[str] = None        # None | "iterate" | "state"
    spec_kwargs: tuple = ()
    rule_kwargs: tuple = ()
    defaults: tuple = ()             # ((name, value), ...)
    refit: Callable = _refit_last_min
    dispatch_budget: Optional[DispatchBudget] = None

    def __post_init__(self):
        if self.mixer not in MIXERS:
            raise ValueError(f"bad mixer kind {self.mixer!r}; expected "
                             f"one of {MIXERS}")
        if self.aux not in (None, "iterate", "state"):
            raise ValueError(f"bad aux kind {self.aux!r}")


def _resolve_spec(program: SolverProgram, spec_kw: dict) -> dict:
    unknown = set(spec_kw) - set(program.spec_kwargs)
    if unknown:
        raise TypeError(f"solver {program.name!r} got unexpected spec "
                        f"kwargs {sorted(unknown)}; takes "
                        f"{sorted(program.spec_kwargs)}")
    kw = dict(program.defaults)
    kw.update(spec_kw)
    return kw


def _check_avail(program: SolverProgram, avail, T_GD: int, L: int):
    """The masked drivers' legacy validation, shared by all lowerings."""
    if not program.takes_avail:
        if avail is not None:
            raise TypeError(f"solver {program.name!r} does not take an "
                            f"availability mask")
        return None
    if avail is None:
        avail = jnp.ones((T_GD, L), bool)
    avail = jnp.asarray(avail).astype(bool)
    if avail.shape != (T_GD, L):
        raise ValueError(f"availability mask {avail.shape} does not "
                         f"match (T_GD, L) = ({T_GD}, {L})")
    return avail


# ----------------------------------------------------------------------
# per-iteration update bodies (substrate-independent)
# ----------------------------------------------------------------------

def _upd_dif(ctx, U, aux, tau):
    """Algorithm 3: adapt-then-combine."""
    _, G = ctx.min_grad(U, tau)
    U_breve = U - ctx.eta_L * G           # local adapt (line 12)
    U_tilde = ctx.mix(U_breve)            # diffusion   (line 13)
    return ctx.qr(U_tilde), aux, None     # projection  (line 14)


def _upd_dec(ctx, U, aux, tau):
    """Dec-AltGDmin [9]: combine-then-adjust (consensus on gradients)."""
    _, G = ctx.min_grad(U, tau)
    G_hat = ctx.mix(G)
    return ctx.qr(U - ctx.eta_L * G_hat), aux, None


def _upd_central(ctx, U, aux, tau):
    """AltGDmin [10] with a fusion center: exact gradient sum."""
    _, G = ctx.min_grad(U, tau)
    grad = ctx.all_sum(G)
    return ctx.qr(U - ctx.eta * grad), aux, None


def _upd_dgd(ctx, U, aux, tau):
    """DGD-variation (Experiment 1 iii): self-excluding neighbour
    average of the PREVIOUS iterate minus the plain-η local gradient."""
    _, G = ctx.min_grad(U, tau)
    nbr = ctx.mix(U)
    return ctx.qr(nbr - ctx.eta * G), aux, None


def _upd_exact_diffusion(ctx, U, psi_prev, tau):
    """Exact Subspace Diffusion (arXiv:2304.07358):
    adapt-correct-combine; aux carries the previous adapt state ψ."""
    _, G = ctx.min_grad(U, tau)
    psi = U - ctx.eta_L * G                        # adapt
    phi = ExactDiffusionCombine.correct(psi, psi_prev, U)
    return ctx.qr(ctx.mix(phi)), psi, None         # combine + project


def _upd_beyond_central(ctx, U, aux, tau):
    """Beyond Centralization (arXiv:2512.22675): ``local_steps`` full
    local adapt steps, then ONE combine round."""
    for j in range(ctx.local_steps):               # local adapt epoch
        fold = tau * ctx.local_steps + j
        _, G = ctx.min_grad(U, fold)
        U = ctx.qr(U - ctx.eta_L * G)
    return ctx.qr(ctx.mix(U)), aux, None           # one combine round


def _upd_compressed(ctx, U, cstate, tau):
    """Adapt-then-combine over a STATEFUL compressed rule; the error-
    feedback state rides the aux carry.  The measured send fraction
    (event rule, simulator lowering only) is recorded BEFORE the mix —
    the same first-round trigger decision the encode uses."""
    _, G = ctx.min_grad(U, tau)
    U_breve = U - ctx.eta_L * G                    # local adapt
    sf = (ctx.send_fraction(U_breve, cstate)
          if ctx.send_fraction is not None else None)
    U_tilde, cstate = ctx.mix(U_breve, cstate)     # compressed diffusion
    return ctx.qr(U_tilde), cstate, sf             # projection


def _upd_masked(ctx, U, aux, tau, m):
    """Adapt-then-combine under an availability mask; down nodes are
    FULLY frozen for the iteration (no adapt/combine/retraction)."""
    _, G = ctx.min_grad(U, tau)
    U_breve = U - ctx.eta_L * G                    # local adapt
    U_tilde = ctx.mix(U_breve, m)
    return ctx.where_live(m, ctx.qr(U_tilde), U), aux, None


def _upd_masked_state(ctx, U, cstate, tau, m):
    """The stale-copy variant: the last-published copies ride the aux
    carry through the masked state mixer."""
    _, G = ctx.min_grad(U, tau)
    U_breve = U - ctx.eta_L * G                    # local adapt
    U_tilde, cstate = ctx.mix(U_breve, cstate, m)
    return ctx.where_live(m, ctx.qr(U_tilde), U), cstate, None


# ----------------------------------------------------------------------
# lowerings
# ----------------------------------------------------------------------

# jitted scans the simulator keeps, newest last; a constant, so a sweep
# over many graphs holds at most this many compiled loops
SCAN_CACHE_SIZE = 16
_SCANS: collections.OrderedDict = collections.OrderedDict()


def clear_scan_cache() -> None:
    """Drop every kept simulator loop.  The key reads a call's statics,
    not the code its loop calls: after replacing a function that the
    loop traces (an engine method, a kernel, a mixer), clear the cache
    so that the next call traces the replacement."""
    _SCANS.clear()


def _content_key(x):
    """A hashable key of a concrete topology operand's content: a dense
    matrix by its bytes, shape and dtype, a dataclass (``SparseWeights``,
    ``SparseGraph``) by its fields.  None where there is no content to
    key on (a traced operand, an object that is no array)."""
    if x is None or isinstance(x, (bool, int, float, str)):
        return (type(x).__name__, x)
    if isinstance(x, jax.core.Tracer):
        return None
    if dataclasses.is_dataclass(x):
        parts = [type(x).__name__]
        for f in dataclasses.fields(x):
            k = _content_key(getattr(x, f.name))
            if k is None:
                return None
            parts.append((f.name, k))
        return tuple(parts)
    a = np.ascontiguousarray(np.asarray(x))
    if a.dtype == object:
        return None
    return (a.shape, a.dtype.str,
            hashlib.blake2b(a.tobytes(), digest_size=16).digest())


def _scan_key(program, eng, topo, kw, *, T_con):
    """The static part of a simulator call: everything its jitted scan
    builds on the host (the mixer from the topology's content, the
    spec's knobs).  η, the data and the iteration axis are arguments,
    whose shapes, dtypes and tree structure (``same_data``, the mask)
    are ``jax.jit``'s own key.  None where the call has no hashable key
    (a traced topology)."""
    topo_key = _content_key(topo)
    if topo_key is None:
        return None
    key = (program, eng.backend, eng.blk_d, T_con,
           tuple((k, type(v), v) for k, v in sorted(kw.items())),
           jax.config.jax_enable_x64, jax.config.jax_default_matmul_precision,
           topo_key)
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _simulator_scan(program, eng, topo, *, T_con, rule_kw, local_steps,
                    threshold):
    """The iteration loop of one static configuration,
    ``scan_fn((U0, aux0), xs, Xg, yg, U_star, eta, eta_L) -> ((U, aux),
    outs)``.  The mixer is built here from the concrete topology; the
    job's data and step size come in as arguments, so one jitted loop
    serves every job of the same shapes."""
    rule = get_rule(program.combine)
    mix = all_sum = None
    if program.mixer == "plain":
        mix = eng.make_mixer(topo, T_con, rule=program.combine)
    elif program.mixer == "neighbor":
        mix = eng.make_neighbor_mixer(neighbor_average_matrix(topo))
    elif program.mixer == "central":
        def all_sum(G):
            return jnp.sum(G, axis=0)             # fusion-center aggregation
    elif program.mixer == "state":
        mix = eng.make_state_mixer(topo, T_con, rule=program.combine,
                                   **rule_kw)
    elif program.mixer == "masked":
        mix = eng.make_masked_mixer(topo, T_con, rule=program.combine)
    elif program.mixer == "masked_state":
        mix = eng.make_masked_state_mixer(topo, T_con,
                                          rule=program.combine)

    send_fraction = None
    if program.records_send_frac:
        def send_fraction(Z, st):
            return rule.send_fraction(Z, st, threshold)

    def where_live(m, a, b):
        return jnp.where(m[:, None, None], a, b)

    def scan_fn(carry0, xs, Xg, yg, U_star_, eta, eta_L):
        same_data = Xg.ndim == 4              # no sample-split fold axis

        def min_grad(U, fold):
            Xb, yb = _select(Xg, yg, 2 * fold)
            Xc, yc = _select(Xg, yg, 2 * fold + 1)
            if program.stacked:
                return eng.min_grad(U, Xb, yb, Xc, yc, same_data=same_data)
            Ub = jnp.broadcast_to(U[None], (Xb.shape[0],) + U.shape)
            return eng.min_grad(Ub, Xb, yb, Xc, yc, same_data=same_data)

        ctx = ProgramCtx(min_grad=min_grad, mix=mix,
                         qr=lambda M: _qr_pos(M)[0], eta=eta, eta_L=eta_L,
                         local_steps=local_steps, all_sum=all_sum,
                         where_live=where_live, send_fraction=send_fraction)

        if program.stacked:
            def metrics(U_new):
                return _metrics(U_new, U_star_)
        else:
            def metrics(U_new):
                sd = subspace_distance(U_new, U_star_)
                return (sd, sd, jnp.zeros((), U_new.dtype))

        def step(carry, xt):
            U, aux = carry
            if program.takes_avail:
                tau, m = xt
                U_new, aux_new, extra = program.update(ctx, U, aux, tau, m)
            else:
                U_new, aux_new, extra = program.update(ctx, U, aux, xt)
            out = metrics(U_new)
            if extra is not None:
                out = out + (extra,)
            return (U_new, aux_new), out

        return jax.lax.scan(step, carry0, xs)

    return scan_fn


def lower_simulator(program: SolverProgram) -> Callable:
    """Stacked single-host simulator: ``run(U0, Xg, yg, topo, *, eta,
    T_GD, T_con, ...) -> RunResult``, trajectory-bit-identical to the
    legacy :mod:`repro.core.altgdmin` driver on both engine backends.
    ``topo`` is the mixing matrix (``"W"`` programs), the adjacency
    (``"adj"``), or absent (``"none"``) — the registry's per-topology
    call convention, preserved.

    The iteration loop is jitted once per static key (:func:`_scan_key`)
    and kept in a small LRU shared by every program, so jobs that
    differ only in their data and step size trace and lower it once;
    the ``solve.scan`` span counts ``cached=1`` when the loop came from
    the cache.  The final B refit stays outside the jitted loop, as the
    legacy drivers run it."""

    def run(U0, Xg, yg, topo=None, *, eta, T_GD, T_con=1, U_star=None,
            engine=None, backend=None, avail=None, **spec_kw):
        with span("solve.build"):
            kw = _resolve_spec(program, spec_kw)
            local_steps = int(kw.get("local_steps", 1))
            eng = resolve_engine(engine, backend)
            if program.stacked:
                L = U0.shape[0]
                U_star_ = U_star if U_star is not None else U0[0]
            else:
                L = Xg.shape[0] if Xg.ndim == 4 else Xg.shape[1]
                U_star_ = U_star if U_star is not None else U0
            eta_L = eta * L
            avail_ = _check_avail(program, avail, T_GD, L)
            rule_kw = {k: kw[k] for k in program.rule_kwargs}
            if program.aux == "iterate":
                aux0 = U0
            elif program.aux == "state":
                aux0 = get_rule(program.combine).init_state(U0, **rule_kw)
            else:
                aux0 = None
            xs = ((jnp.arange(T_GD), avail_) if program.takes_avail
                  else jnp.arange(T_GD))

            key = _scan_key(program, eng, topo, kw, T_con=T_con)
            scan_fn = _SCANS.pop(key, None) if key is not None else None
            cached = scan_fn is not None
            if not cached:
                build = functools.partial(
                    _simulator_scan, program, eng, topo, T_con=T_con,
                    rule_kw=rule_kw, local_steps=local_steps,
                    threshold=float(kw.get("event_threshold", 0.0)))
                if key is None:
                    scan_fn = build()
                else:
                    # the mixer's set-up runs on the concrete topology
                    # even under an outer trace: a kept loop holds no
                    # tracer
                    with jax.ensure_compile_time_eval():
                        scan_fn = jax.jit(build())
            if key is not None:
                _SCANS[key] = scan_fn                 # the newest last
                while len(_SCANS) > SCAN_CACHE_SIZE:
                    _SCANS.popitem(last=False)
        with span("solve.scan", cached=int(cached)):
            (U_fin, _), outs = scan_fn((U0, aux0), xs, Xg, yg, U_star_, eta,
                                       eta_L)
        sfrac = None
        if program.records_send_frac:
            sd_max, sd_mean, spread, sfrac = outs
        else:
            sd_max, sd_mean, spread = outs

        with span("solve.refit"):
            Xb, yb = _select(Xg, yg, program.refit(T_GD, local_steps))
            if program.stacked:
                U_out, B_fin = U_fin, eng.minimize_B(U_fin, Xb, yb)
            else:
                B_fin = eng.minimize_B(
                    jnp.broadcast_to(U_fin[None],
                                     (Xb.shape[0],) + U_fin.shape), Xb, yb)
                U_out = U_fin[None]
        return RunResult(U_out, B_fin, sd_max, sd_mean, spread, eta,
                         send_frac=sfrac)

    run.__name__ = run.__qualname__ = f"{program.name}__simulator"
    run.__doc__ = (f"Simulator lowering of the {program.name!r} solver "
                   f"program (combine rule {program.combine!r}).")
    return run


def lower_mesh(program: SolverProgram) -> Callable:
    """One-node-per-device shard_map lowering on the shared
    :func:`~repro.core.runtime._altgdmin_mesh` skeleton: ``run(U0, Xg,
    yg, mesh, axis_name, *, eta, T_GD, T_con, shifts, self_weight, W,
    ...)`` — the historical ``*_mesh`` signature, for every program."""

    def run(U0, Xg, yg, mesh, axis_name, *, eta, T_GD, T_con=1,
            shifts=(-1, 1), self_weight=None, W=None, engine=None,
            backend=None, U_star=None, avail=None, **spec_kw):
        kw = _resolve_spec(program, spec_kw)
        rule_kw = {k: kw[k] for k in program.rule_kwargs}
        local_steps = int(kw.get("local_steps", 1))
        L = mesh.shape[axis_name]
        eta_L = eta * L
        rule = get_rule(program.combine)
        if not program.stacked:
            # fusion center: every device starts (and stays) on node
            # 0's iterate — the psum keeps the rows identical
            U0 = jnp.broadcast_to(U0[:1], U0.shape)
        xs = _check_avail(program, avail, T_GD, L)

        def make_update(eng):
            mix = all_sum = None
            if program.mixer == "plain":
                mix = rule.make_mesh_mixer(axis_name, L, T_con, shifts,
                                           self_weight, W=W,
                                           backend=eng.backend)
            elif program.mixer == "neighbor":
                # single self-excluding round; T_con / self_weight are
                # structurally ignored by the rule
                mix = rule.make_mesh_mixer(axis_name, L, 1, shifts, W=W,
                                           backend=eng.backend)
            elif program.mixer == "central":
                def all_sum(G):
                    return jax.lax.psum(G, axis_name)
            elif program.mixer == "state":
                mix = rule.make_mesh_state_mixer(
                    axis_name, L, T_con, shifts, self_weight, W=W,
                    backend=eng.backend, **rule_kw)
            elif program.mixer == "masked":
                mix = rule.make_mesh_masked_mixer(
                    axis_name, L, T_con, shifts, self_weight, W=W,
                    backend=eng.backend)
            elif program.mixer == "masked_state":
                mix = rule.make_mesh_masked_state_mixer(
                    axis_name, L, T_con, shifts, self_weight, W=W,
                    backend=eng.backend)

            def where_live(m, a, b):
                return jnp.where(m[jax.lax.axis_index(axis_name)], a, b)

            def update(U, aux, mg, xt=None):
                ctx = ProgramCtx(min_grad=lambda U_, fold: mg(U_),
                                 mix=mix, qr=lambda M: _qr_pos(M)[0],
                                 eta=eta, eta_L=eta_L,
                                 local_steps=local_steps, all_sum=all_sum,
                                 where_live=where_live, send_fraction=None)
                if program.takes_avail:
                    U_new, aux_new, _ = program.update(ctx, U, aux, 0, xt)
                else:
                    U_new, aux_new, _ = program.update(ctx, U, aux, 0)
                return U_new, aux_new
            return update

        if program.aux == "iterate":
            def init_aux(U):
                return U
        elif program.aux == "state":
            if program.mixer == "state":
                # one neighbour-copy buffer per distinct cyclic shift
                n_shifts = len(rule._mesh_weights(L, shifts, self_weight,
                                                  W)[0])

                def init_aux(U):
                    return rule.init_mesh_state(U, n_shifts, **rule_kw)
            else:
                def init_aux(U):
                    return rule.init_mesh_state(U)
        else:
            init_aux = None

        return _altgdmin_mesh(U0, Xg, yg, mesh, axis_name, eta=eta,
                              T_GD=T_GD, make_update=make_update,
                              engine=engine, backend=backend,
                              U_star=U_star, init_aux=init_aux, xs=xs)

    run.__name__ = run.__qualname__ = f"{program.name}__mesh"
    run.__doc__ = (f"Mesh lowering of the {program.name!r} solver "
                   f"program (combine rule {program.combine!r}).")
    return run


def _virtual_wire(program: SolverProgram, rule, vt, U0, T_con: int,
                  rule_kw: dict) -> dict:
    """What one device sends per outer iteration on the virtual tier, as
    the rule's ``CommSignature`` prices it (JX004 holds the lowering to
    the count): ``ppermutes_per_iter``, rounds × cross-device shift
    classes × the program's ``wire_virtual``, and
    ``wire_bytes_per_iter``, the block's payload over them (the
    fusion center's all-reduce of one block sum instead)."""
    d, r = U0.shape[1], U0.shape[2]
    itemsize = jnp.dtype(U0.dtype).itemsize
    sig = rule.signature(T_con, d=d, r=r, **rule_kw)
    if program.mixer == "central":
        return dict(ppermutes_per_iter=0,
                    wire_bytes_per_iter=sig.bytes_per_iter(
                        d * r, itemsize, vt.n_dev, 0))
    S = vt.block_sends_per_round
    budget = program.dispatch_budget
    wire = 1 if budget is None else budget.wire_virtual
    return dict(ppermutes_per_iter=sig.rounds_per_iter * S * wire,
                wire_bytes_per_iter=vt.block * sig.bytes_per_iter(
                    d * r, itemsize, vt.n_nodes, S))


def lower_virtual_mesh(program: SolverProgram) -> Callable:
    """Virtual-node block-tier lowering (L = devices × block) on
    :func:`~repro.core.runtime._altgdmin_virtual_mesh`: each device is a
    small simulator over its (block, d, r) slab; the combine is the
    rule's ``make_virtual_mesh_*`` sparse-round lowering.  ``run(U0, Xg,
    yg, mesh, axis_name, *, vt, eta, T_GD, T_con, ...)``."""

    def run(U0, Xg, yg, mesh, axis_name, *, vt, eta, T_GD, T_con=1,
            engine=None, backend=None, U_star=None, avail=None,
            **spec_kw):
        kw = _resolve_spec(program, spec_kw)
        rule_kw = {k: kw[k] for k in program.rule_kwargs}
        local_steps = int(kw.get("local_steps", 1))
        L = U0.shape[0]
        eta_L = eta * L                       # L is the GLOBAL node count
        rule = get_rule(program.combine)
        if not program.stacked:
            U0 = jnp.broadcast_to(U0[:1], U0.shape)
        xs = _check_avail(program, avail, T_GD, L)
        D, V = vt.n_dev, vt.block

        def make_update(eng):
            mix = all_sum = None
            if program.mixer in ("plain", "neighbor"):
                # the neighbor rule's virtual lowering is structurally a
                # single round, matching its mesh/simulator forms
                mix = eng.make_virtual_mixer(vt, axis_name, T_con,
                                             rule=program.combine)
            elif program.mixer == "central":
                def all_sum(G):
                    # block-local sum, then the cross-device psum — the
                    # exact global gradient on every device
                    return jax.lax.psum(jnp.sum(G, axis=0), axis_name)
            elif program.mixer == "state":
                mix = eng.make_virtual_state_mixer(vt, axis_name, T_con,
                                                   rule=program.combine,
                                                   **rule_kw)
            elif program.mixer == "masked":
                mix = eng.make_virtual_masked_mixer(vt, axis_name, T_con,
                                                    rule=program.combine)
            elif program.mixer == "masked_state":
                mix = eng.make_virtual_masked_state_mixer(
                    vt, axis_name, T_con, rule=program.combine)

            def where_live(m, a, b):
                rows = m.reshape(D, V)[jax.lax.axis_index(axis_name)]
                return jnp.where(rows[:, None, None], a, b)

            qr = jax.vmap(lambda u: _qr_pos(u)[0])

            def update(U, aux, mg, xt=None):
                ctx = ProgramCtx(min_grad=lambda U_, fold: mg(U_),
                                 mix=mix, qr=qr, eta=eta, eta_L=eta_L,
                                 local_steps=local_steps, all_sum=all_sum,
                                 where_live=where_live, send_fraction=None)
                if program.takes_avail:
                    U_new, aux_new, _ = program.update(ctx, U, aux, 0, xt)
                else:
                    U_new, aux_new, _ = program.update(ctx, U, aux, 0)
                return U_new, aux_new
            return update

        if program.aux == "iterate":
            def init_aux(Ub):
                return Ub
        elif program.aux == "state":
            # the simulator's stacked state, per block slab (zero
            # public copies; the stochastic round counter stays a
            # per-device scalar with identical per-round values)
            def init_aux(Ub):
                return rule.init_state(Ub, **rule_kw)
        else:
            init_aux = None

        return _altgdmin_virtual_mesh(U0, Xg, yg, mesh, axis_name, vt=vt,
                                      eta=eta, T_GD=T_GD,
                                      make_update=make_update,
                                      engine=engine, backend=backend,
                                      U_star=U_star, init_aux=init_aux,
                                      xs=xs,
                                      wire=_virtual_wire(program, rule, vt,
                                                         U0, T_con, rule_kw))

    run.__name__ = run.__qualname__ = f"{program.name}__virtual_mesh"
    run.__doc__ = (f"Virtual-mesh lowering of the {program.name!r} solver "
                   f"program (combine rule {program.combine!r}).")
    return run


# ----------------------------------------------------------------------
# program registry — the 12 solvers as data
# ----------------------------------------------------------------------

PROGRAMS: dict[str, SolverProgram] = {}


def register_program(program: SolverProgram) -> SolverProgram:
    if program.name in PROGRAMS:
        raise ValueError(f"solver program {program.name!r} already "
                         f"registered")
    PROGRAMS[program.name] = program
    return program


def get_program(name: str) -> SolverProgram:
    try:
        return PROGRAMS[name]
    except KeyError:
        raise ValueError(f"unknown solver program {name!r}; registered: "
                         f"{sorted(PROGRAMS)}") from None


def program_names() -> tuple[str, ...]:
    return tuple(sorted(PROGRAMS))


# Budget shorthand: the adapt-then-combine family shares one shape —
# simulator fuses min-grad + the hoisted W^{T_con} combine (2 dispatches,
# round-independent); mesh keeps the combine per round (1 + R); the
# virtual tier's combine is segment-sum/ppermute only (1).
_BUDGET_DIFFUSION = DispatchBudget(
    simulator=(2, 0, 0, 0), mesh=(1, 1, 0, 0), virtual=(1, 0, 0, 0))

# Masked / event rules: one masked-combine dispatch per round on both
# stacked tiers, none on the virtual tier.
_BUDGET_MASKED = DispatchBudget(
    simulator=(1, 1, 0, 0), mesh=(1, 1, 0, 0), virtual=(1, 0, 0, 0))

register_program(SolverProgram(
    name="dif_altgdmin", combine="gossip", update=_upd_dif,
    dispatch_budget=_BUDGET_DIFFUSION))

register_program(SolverProgram(
    name="dec_altgdmin", combine="gossip", update=_upd_dec,
    dispatch_budget=_BUDGET_DIFFUSION))

register_program(SolverProgram(
    name="centralized_altgdmin", combine="central", update=_upd_central,
    mixer="central", stacked=False, topology="none", decentralized=False,
    refit=_refit_first,
    dispatch_budget=DispatchBudget(
        simulator=(1, 0, 0, 0), mesh=(1, 0, 0, 0), virtual=(1, 0, 0, 0),
        wire_mesh=0, wire_virtual=0)))   # fusion center: psum, no gossip

register_program(SolverProgram(
    name="dgd_altgdmin", combine="neighbor", update=_upd_dgd,
    mixer="neighbor", topology="adj",
    dispatch_budget=DispatchBudget(      # single self-excluding round
        simulator=(1, 1, 0, 0), mesh=(1, 1, 0, 0), virtual=(1, 0, 0, 0))))

register_program(SolverProgram(
    name="exact_diffusion", combine="exact_diffusion",
    update=_upd_exact_diffusion, aux="iterate",
    dispatch_budget=_BUDGET_DIFFUSION))

register_program(SolverProgram(
    name="beyond_central", combine="beyond_central",
    update=_upd_beyond_central, spec_kwargs=("local_steps",),
    defaults=(("local_steps", 1),), refit=_refit_last_local,
    dispatch_budget=DispatchBudget(      # one min-grad per LOCAL step,
        simulator=(0, 1, 0, 1),          # one combine round per iter
        mesh=(0, 1, 0, 1), virtual=(0, 0, 0, 1))))

register_program(SolverProgram(
    name="dif_topk", combine="topk_gossip", update=_upd_compressed,
    mixer="state", aux="state",
    spec_kwargs=("compression_k", "consensus_gamma"),
    rule_kwargs=("compression_k", "consensus_gamma"),
    defaults=(("compression_k", 0), ("consensus_gamma", 1.0)),
    dispatch_budget=DispatchBudget(      # encode + combine per round;
        simulator=(1, 2, 0, 0),          # indices ride the wire (w=2)
        mesh=(1, 2, 0, 0), virtual=(1, 1, 0, 0), wire_mesh=2)))

register_program(SolverProgram(
    name="dif_quantized", combine="quantized_gossip",
    update=_upd_compressed, mixer="state", aux="state",
    spec_kwargs=("compression", "consensus_gamma"),
    rule_kwargs=("compression", "consensus_gamma"),
    defaults=(("compression", None), ("consensus_gamma", 1.0)),
    dispatch_budget=DispatchBudget(      # per-shift dequant on mesh;
        simulator=(1, 2, 0, 0),          # scales ride the wire (w=2)
        mesh=(1, 2, 1, 0), virtual=(1, 1, 0, 0), wire_mesh=2)))

register_program(SolverProgram(
    name="dif_event", combine="event_gossip", update=_upd_compressed,
    mixer="state", aux="state", records_send_frac=True,
    spec_kwargs=("event_threshold", "consensus_gamma"),
    rule_kwargs=("event_threshold", "consensus_gamma"),
    defaults=(("event_threshold", 0.0), ("consensus_gamma", 1.0)),
    dispatch_budget=_BUDGET_MASKED))

register_program(SolverProgram(
    name="dif_partial", combine="partial_gossip", update=_upd_masked,
    mixer="masked", takes_avail=True,
    dispatch_budget=_BUDGET_MASKED))

register_program(SolverProgram(
    name="dif_stale", combine="stale_gossip", update=_upd_masked_state,
    mixer="masked_state", aux="state", takes_avail=True,
    dispatch_budget=_BUDGET_MASKED))

register_program(SolverProgram(
    name="dif_pushsum", combine="push_sum_gossip", update=_upd_masked,
    mixer="masked", takes_avail=True,
    dispatch_budget=DispatchBudget(      # ratio consensus: weight row
        simulator=(1, 2, 0, 0),          # rides with every message
        mesh=(1, 1, 0, 0), virtual=(1, 0, 0, 0),
        wire_mesh=2, wire_virtual=2)))
