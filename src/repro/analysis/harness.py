"""Trace harness: every registered program × every lowering, as jaxprs.

The jaxpr analyzers never EXECUTE a solver — they ``jax.make_jaxpr`` the
lowering at a tiny shape and walk the closed jaxpr.  Tracing is enough:
dispatch counts, aval shapes, dtype narrowings, and ppermute structure
are all properties of the trace, and random normal data is as good as a
real problem instance.

Shape choices (why these numbers):

  * sim/mesh use L = 8 — one node per fake host device, matching the
    parity tests in tests/test_programs.py; virtual uses L = 24 on 8
    devices (block 3) so L, the device count, and the block size are
    three DISTINCT numbers and a dim equal to L is unambiguous.
  * d = 16, r = 2, tpn = 3, n = 12 — no dim collides with L on either
    tier, so the no-dense-node-axis rule (JX002) cannot false-positive
    on a data axis.
  * T_GD = 3, T_con = 2, local_steps = 2 — all distinct, so the outer
    scan is identified by ``length == T_GD`` alone.

The walker (:func:`iter_eqns`) recurses into scan / pjit / shard_map /
custom-call sub-jaxprs and yields ``(eqn, mult, in_outer)`` where
``mult`` is the number of times the eqn runs per outer iteration
(inner-scan lengths multiply — a statically-single ppermute inside a
``length=T_con`` round scan runs T_con times) and ``in_outer`` says
whether the eqn is under the outer T_GD scan at all.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Iterator

import numpy as np

import jax
import jax.numpy as jnp

# Trace-time constants — see module docstring for why each value.
D, R, TPN, N = 16, 2, 3, 12
T_GD, T_CON, LOCAL_STEPS = 3, 2, 2
L_SIM = 8            # simulator + mesh node count (== device count)
L_VIRT = 24          # virtual tier: 8 devices × block 3
N_DEV = 8

SUBSTRATES = ("simulator", "mesh", "virtual")

# The non-default spec knobs per program (mirrors the parity tests —
# exercises the compressed / local-epoch paths the defaults skip).
SPEC_KW = {
    "beyond_central": dict(local_steps=LOCAL_STEPS),
    "dif_topk": dict(compression_k=3),
    "dif_quantized": dict(compression="int8_stochastic"),
    "dif_event": dict(event_threshold=0.05),
}


@dataclasses.dataclass(frozen=True)
class Trace:
    """One traced (program, substrate) pair plus the structural facts
    the analyzers price against."""
    program: Any              # the SolverProgram
    substrate: str            # "simulator" | "mesh" | "virtual"
    dtype: Any                # trace input dtype (jnp.float32/float64)
    jaxpr: Any                # ClosedJaxpr
    L: int                    # global node count of this trace
    rounds: int               # R — CommSignature.rounds_per_iter at T_con
    n_shifts: int             # K — shift classes (0 on the simulator)
    local_steps: int


def _orthonormal(rng, shape, dtype):
    *lead, d, r = shape
    q = np.linalg.qr(rng.standard_normal(shape))[0]
    return jnp.asarray(q.astype(dtype))


@functools.lru_cache(maxsize=4)
def _setup(L: int, dtype_name: str):
    """Concrete trace inputs for node count L.  Cached: the two node
    counts × two dtypes cover every trace."""
    from repro.distributed import graphs, mixing
    from repro.distributed.consensus import neighbor_average_matrix

    dtype = np.dtype(dtype_name)
    rng = np.random.default_rng(7)
    g = (graphs.erdos_renyi(L, 0.6, seed=2) if L == L_SIM
         else graphs.erdos_renyi(L, 0.4, seed=3))
    adj = jnp.asarray(np.asarray(  # reprolint: allow=RL002 — trace-time toy graph, L <= 24
        g.adj, dtype=dtype))
    W = jnp.asarray(np.asarray(mixing.metropolis_weights(g), dtype=dtype))
    Madj = jnp.asarray(np.asarray(neighbor_average_matrix(adj),
                                  dtype=dtype))
    U0 = _orthonormal(rng, (L, D, R), dtype)
    Xg = jnp.asarray(rng.standard_normal((L, TPN, N, D)).astype(dtype))
    yg = jnp.asarray(rng.standard_normal((L, TPN, N)).astype(dtype))
    avail = jnp.asarray(rng.random((T_GD, L)) > 0.3)
    return dict(adj=adj, W=W, Madj=Madj, U0=U0, Xg=Xg, yg=yg, avail=avail)


def _mesh8():
    if len(jax.devices()) < N_DEV:
        raise RuntimeError(
            f"the mesh/virtual traces need {N_DEV} devices (have "
            f"{len(jax.devices())}); run via `python -m tools.reprolint`, "
            f"which sets XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{N_DEV} before importing jax")
    return jax.make_mesh((N_DEV,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def trace_program(name: str, substrate: str, dtype=jnp.float32) -> Trace:
    """Trace one program through one lowering; returns the closed jaxpr
    plus the R/K context its budgets are priced against."""
    from repro.core.program import (get_program, lower_mesh,
                                    lower_simulator, lower_virtual_mesh)
    from repro.distributed.consensus import (VirtualTopology, get_rule,
                                             mesh_weights_from_matrix)
    from repro.distributed.mixing import SparseWeights

    program = get_program(name)
    rule = get_rule(program.combine)
    spec_kw = SPEC_KW.get(name, {})
    local_steps = int(spec_kw.get("local_steps", 1))
    L = L_VIRT if substrate == "virtual" else L_SIM
    pb = _setup(L, np.dtype(dtype).name)
    kw = dict(eta=0.01, T_GD=T_GD, U_star=pb["U0"][0],
              backend="pallas-interpret", **spec_kw)
    if program.takes_avail:
        kw["avail"] = pb["avail"]
    rounds = int(rule.signature(T_CON).rounds_per_iter)

    if substrate == "simulator":
        run = lower_simulator(program)
        if program.topology == "none":
            fn = lambda U0, Xg, yg: run(U0[0], Xg, yg, **kw)
        elif program.topology == "adj":
            fn = lambda U0, Xg, yg: run(U0, Xg, yg, pb["adj"], **kw)
        else:
            fn = lambda U0, Xg, yg: run(U0, Xg, yg, pb["W"], T_con=T_CON,
                                        **kw)
        n_shifts = 0
    elif substrate == "mesh":
        run = lower_mesh(program)
        mesh = _mesh8()
        W = pb["Madj"] if program.topology == "adj" else pb["W"]
        shifts, _ = mesh_weights_from_matrix(np.asarray(W))
        n_shifts = len(shifts)
        fn = lambda U0, Xg, yg: run(U0, Xg, yg, mesh, "nodes",
                                    T_con=T_CON, W=np.asarray(W), **kw)
    elif substrate == "virtual":
        run = lower_virtual_mesh(program)
        mesh = _mesh8()
        W = pb["Madj"] if program.topology == "adj" else pb["W"]
        vt = VirtualTopology.from_weights(
            SparseWeights.from_dense(np.asarray(W)), N_DEV)
        n_shifts = len(vt.dev_shifts)
        fn = lambda U0, Xg, yg: run(U0, Xg, yg, mesh, "nodes", vt=vt,
                                    T_con=T_CON, **kw)
    else:
        raise ValueError(f"unknown substrate {substrate!r}; expected one "
                         f"of {SUBSTRATES}")

    jaxpr = jax.make_jaxpr(fn)(pb["U0"], pb["Xg"], pb["yg"])
    return Trace(program=program, substrate=substrate, dtype=dtype,
                 jaxpr=jaxpr, L=L, rounds=rounds, n_shifts=n_shifts,
                 local_steps=local_steps)


# ----------------------------------------------------------------------
# jaxpr walking
# ----------------------------------------------------------------------

def _sub_jaxprs(eqn):
    """Every sub-jaxpr reachable from an eqn's params, as bare Jaxprs.
    Covers scan/while (jaxpr), pjit/shard_map/custom_* (jaxpr /
    call_jaxpr / branches) without enumerating primitive names."""
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                # bare Jaxpr


def iter_eqns(closed_jaxpr, outer_len: int = T_GD
              ) -> Iterator[tuple[Any, int, bool]]:
    """Yield ``(eqn, mult, in_outer)`` over the whole jaxpr tree.

    ``mult`` is how many times the eqn executes per outer iteration
    (once ``in_outer``) or per run (outside it): scans that are not the
    outer T_GD loop multiply by their ``length``; the outer scan itself
    flips ``in_outer`` without multiplying, which is exactly the
    "per outer iteration" accounting the dispatch budget is written in.
    """
    def walk(jaxpr, mult, in_outer):
        for eqn in jaxpr.eqns:
            yield eqn, mult, in_outer
            sub_mult, sub_outer = mult, in_outer
            if eqn.primitive.name == "scan":
                length = eqn.params.get("length")
                if length == outer_len and not in_outer:
                    sub_outer = True
                elif length is not None:
                    sub_mult = mult * int(length)
            for sub in _sub_jaxprs(eqn):
                yield from walk(sub, sub_mult, sub_outer)

    yield from walk(closed_jaxpr.jaxpr, 1, False)


def eqn_location(eqn):
    """(repo-relative path, function name, line) of the user frame that
    traced this eqn, or ('', '', 0) when jax has no source info."""
    try:
        from jax._src import source_info_util
        fr = source_info_util.user_frame(eqn.source_info.traceback)
        if fr is None:
            return "", "", 0
        path = fr.file_name
        marker = "/src/repro/"
        if marker in path:
            path = "src/repro/" + path.split(marker, 1)[1]
        return path, fr.function_name, fr.start_line
    except Exception:
        return "", "", 0


def count_primitive(trace: Trace, prim: str) -> tuple[int, int]:
    """(per-outer-iteration count, outside-outer count) of a primitive,
    dynamic — inner-scan lengths included."""
    inner = outer = 0
    for eqn, mult, in_outer in iter_eqns(trace.jaxpr):
        if eqn.primitive.name == prim:
            if in_outer:
                inner += mult
            else:
                outer += mult
    return inner, outer
