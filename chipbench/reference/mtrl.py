"""Plain float32 reference of the Experiment-1 deployment.

A straightforward ``jax.numpy`` implementation of the paper's setting
(Sec. II), its decentralized truncated spectral initialization
(Algorithm 2) and Dif-AltGDmin (Algorithm 3), plus the serving-side
least-squares solve.  It imports nothing of the system under test and
takes nothing it made: the problem, the graph and the mixing weights are
drawn again here from the same key and seed, by the same recipe
(Gaussian designs, y = X U* b*, Erdős–Rényi with connectivity
resampling, Metropolis weights).

Every matrix product goes through :func:`ein`, whose ``precision`` is
``"highest"`` (float32 products, what the configurations state) or
``"high"`` (three bfloat16 passes, the control: the step below the
stated precision).  The control is spelled out as the bf16x3 split
rather than left to the backend, so it reads the same on any device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


def ein(subscripts: str, a, b, *, precision: str):
    """Two-operand einsum in float32 at the named matmul precision."""
    if precision == "highest":
        return jnp.einsum(subscripts, a, b,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    a_hi, a_lo = _bf16_split(a)
    b_hi, b_lo = _bf16_split(b)

    def one(x, y):
        return jnp.einsum(subscripts, x.astype(jnp.bfloat16),
                          y.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return one(a_lo, b_hi) + one(a_hi, b_lo) + one(a_hi, b_hi)


def _bf16_split(a):
    """a ≈ hi + lo, each rounded to bfloat16 but held in float32.
    ``reduce_precision`` rounds where a float32 → bfloat16 → float32
    round trip may be dropped by a compiler allowed excess precision
    (the TPU's is): with it dropped, lo came out 0 and the control ran
    one bfloat16 pass instead of three."""
    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    hi = bf16(a)
    return hi, bf16(a - hi)


def seed_key(seed: int) -> jax.Array:
    """The run's root key from a seed of up to 64 bits (x64 off)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def job_key(seed: int, j: int) -> jax.Array:
    """Job ``j`` of a run: ``fold_in(seed_key(seed), j)``."""
    return jax.random.fold_in(seed_key(seed), j)


# ------------------------------------------------------------- problem

def u_star(key, *, d: int, r: int) -> jax.Array:
    """The ground-truth basis U* = QR(Gaussian d×r) of the problem drawn
    from ``key`` (the first of the problem key's four splits)."""
    k_u = jax.random.split(jax.random.fold_in(key, 0), 4)[0]
    return jnp.linalg.qr(jax.random.normal(k_u, (d, r), jnp.float32))[0]


def problem(key, *, d: int, T: int, r: int, n: int, L: int, kappa: float,
            precision: str):
    """The paper's synthetic instance, node-major: X (L, T/L, n, d),
    y (L, T/L, n), U* (d, r), B* (r, T) and the incoherence μ (traced).
    Tasks are split over nodes in contiguous blocks."""
    k_u, k_v, k_x, _ = jax.random.split(jax.random.fold_in(key, 0), 4)
    U, _ = jnp.linalg.qr(jax.random.normal(k_u, (d, r), jnp.float32))
    V, _ = jnp.linalg.qr(jax.random.normal(k_v, (T, r), jnp.float32))
    sig = jnp.geomspace(kappa, 1.0, r).astype(jnp.float32)
    B = sig[:, None] * V.T
    X = jax.random.normal(k_x, (T, n, d), jnp.float32)
    y = ein("tnd,dt->tn", X, ein("dr,rt->dt", U, B, precision=precision),
            precision=precision)
    mu = jnp.sqrt(jnp.max(jnp.sum(B ** 2, axis=0)) * T / (r * sig[0] ** 2))
    tpn = T // L
    return X.reshape(L, tpn, n, d), y.reshape(L, tpn, n), U, B, mu


def er_adjacency(L: int, p: float, seed: int) -> np.ndarray:
    """G(L, p), redrawn until connected, as (L, L) 0/1 ints."""
    rng = np.random.default_rng(seed)
    for _ in range(1000):
        u = rng.random((L, L))
        a = ((u < p) & np.triu(np.ones((L, L), bool), 1)).astype(np.int64)
        a = a + a.T
        if _connected(a):
            return a
    raise ValueError(f"G({L}, {p}) stayed disconnected after 1000 draws")


def _connected(a: np.ndarray) -> bool:
    seen = {0}
    stack = [0]
    while stack:
        for v in np.nonzero(a[stack.pop()])[0]:
            if int(v) not in seen:
                seen.add(int(v))
                stack.append(int(v))
    return len(seen) == a.shape[0]


def metropolis(a: np.ndarray) -> np.ndarray:
    """W_ij = 1 / (1 + max(deg_i, deg_j)) on edges, rows summing to 1."""
    deg = a.sum(axis=1)
    W = np.where(a > 0, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    np.fill_diagonal(W, 1.0 - W.sum(axis=1))
    return W


# ------------------------------------------------------------ algorithm

def agree(Z, W, T_con: int, *, precision: str):
    """T_con gossip rounds Z ← W Z over the leading node axis."""
    flat = jax.lax.fori_loop(
        0, T_con, lambda _, f: ein("gh,hk->gk", W, f, precision=precision),
        Z.reshape(Z.shape[0], -1))
    return flat.reshape(Z.shape)


def qr_pos(M):
    """Q of the QR factorization with R's diagonal made positive."""
    Q, R = jnp.linalg.qr(M)
    s = jnp.sign(jnp.diagonal(R, axis1=-2, axis2=-1))
    s = jnp.where(s == 0, 1.0, s)
    return Q * s[..., None, :]


def spectral_init(key, X, y, W, *, kappa, mu, r: int, T_pm: int,
                  T_con: int, precision: str):
    """Algorithm 2: gossiped truncation level, truncated covariance
    columns, T_pm decentralized power iterations from a common Gaussian
    start, then node 0's basis broadcast and re-orthonormalized."""
    L, tpn, n, d = X.shape
    T = L * tpn
    alpha = 9.0 * kappa ** 2 * mu ** 2 * (L / (n * T)) * jnp.sum(
        y ** 2, axis=(1, 2))
    alpha = agree(alpha, W, T_con, precision=precision)
    y_trnc = y * (y ** 2 <= alpha[:, None, None]).astype(jnp.float32)
    Theta0 = ein("gtnd,gtn->gdt", X, y_trnc, precision=precision) / n
    U = qr_pos(jax.random.normal(jax.random.fold_in(key, 1), (d, r),
                                 jnp.float32))
    def power_step(_, U):
        V = ein("gdt,gtr->gdr", Theta0,
                ein("gdt,gdr->gtr", Theta0, U, precision=precision),
                precision=precision)
        return qr_pos(agree(V, W, T_con, precision=precision))

    U = jax.lax.fori_loop(0, T_pm, power_step,
                          jnp.broadcast_to(U, (L, d, r)))
    U_bc = jnp.zeros_like(U).at[0].set(U[0])
    return qr_pos(agree(U_bc, W, T_con, precision=precision))


def min_B(U, X, y, *, precision: str):
    """b_t = argmin ||X_t U_g b − y_t|| per task: (L, tpn, r)."""
    A = ein("gtnd,gdr->gtnr", X, U, precision=precision)
    G = ein("gtnr,gtns->gtrs", A, A, precision=precision)
    c = ein("gtnr,gtn->gtr", A, y, precision=precision)
    return jax.scipy.linalg.solve(G, c[..., None], assume_a="pos")[..., 0]


def dif_altgdmin(U, X, y, W, *, eta: float, T_GD: int, T_con: int,
                 precision: str):
    """Algorithm 3 on the stacked nodes: min-B, local gradient step of
    size η·L, T_con gossip rounds, QR retraction; then the final B."""
    L = U.shape[0]

    def step(U, _):
        B = min_B(U, X, y, precision=precision)
        A = ein("gtnd,gdr->gtnr", X, U, precision=precision)
        resid = ein("gtnr,gtr->gtn", A, B, precision=precision) - y
        G = ein("gtnd,gtnr->gdr", X,
                resid[..., None] * B[:, :, None, :], precision=precision)
        U_new = qr_pos(agree(U - (eta * L) * G, W, T_con,
                             precision=precision))
        return U_new, None

    U, _ = jax.lax.scan(step, U, None, length=T_GD)
    return U, min_B(U, X, y, precision=precision)


@functools.partial(jax.jit, static_argnames=(
    "d", "T", "r", "n", "L", "kappa", "T_pm", "T_con_init", "T_GD",
    "T_con", "eta", "precision"))
def solve_job(key, W, *, d, T, r, n, L, kappa, T_pm, T_con_init, T_GD,
              T_con, eta, precision):
    """One training job from its key: (U_nodes, B_nodes, U*)."""
    X, y, U_star, _, mu = problem(key, d=d, T=T, r=r, n=n, L=L,
                                  kappa=kappa, precision=precision)
    U0 = spectral_init(key, X, y, W, kappa=kappa, mu=mu, r=r, T_pm=T_pm,
                       T_con=T_con_init, precision=precision)
    U, B = dif_altgdmin(U0, X, y, W, eta=eta, T_GD=T_GD, T_con=T_con,
                        precision=precision)
    return U, B, U_star


def deployable_basis(U_nodes):
    """The one served basis: the node mean, re-orthonormalized."""
    return qr_pos(jnp.mean(U_nodes, axis=0))


@functools.partial(jax.jit, static_argnames=("precision",))
def serve_theta(U, X, y, *, precision: str):
    """θ = U (XU)† y per request.  X (R, n, d) and y (R, n) carry zero
    rows past each request's own sample count, which add nothing."""
    A = ein("knd,dr->knr", X, U, precision=precision)
    G = ein("knr,kns->krs", A, A, precision=precision)
    c = ein("knr,kn->kr", A, y, precision=precision)
    b = jax.scipy.linalg.solve(G, c[..., None], assume_a="pos")[..., 0]
    return ein("kr,dr->kd", b, U, precision=precision)
