"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference gives for the same inputs."""
from __future__ import annotations

import numpy as np


def u_gap(U, U_ref) -> float:
    """Largest entry of |U − U_ref| over every node's (d, r) basis.
    Both are orthonormal, so this is relative to entries of norm ≤ 1.
    It sees the basis itself, not only its span: a run that lands on the
    same subspace by another path (a combine left out, a gradient on half
    the tasks) ends on another basis."""
    U = np.asarray(U, np.float64)
    U_ref = np.asarray(U_ref, np.float64)
    if U.shape != U_ref.shape:
        raise ValueError(f"shapes differ: {U.shape} vs {U_ref.shape}")
    return float(np.max(np.abs(U - U_ref)))


def theta_nodes(U_nodes, B_nodes) -> np.ndarray:
    """θ_t = U_g b_t for every task of every node: (L, tpn, d)."""
    return np.einsum("gdr,gtr->gtd", np.asarray(U_nodes, np.float64),
                     np.asarray(B_nodes, np.float64))


def theta_gap(theta, theta_ref) -> float:
    """Largest ||θ − θ_ref|| / ||θ_ref|| over the rows of (..., d)
    arrays of per-task (or per-request) regressors."""
    th = np.asarray(theta, np.float64)
    ref = np.asarray(theta_ref, np.float64)
    if th.shape != ref.shape:
        raise ValueError(f"shapes differ: {th.shape} vs {ref.shape}")
    num = np.linalg.norm(th - ref, axis=-1)
    den = np.linalg.norm(ref, axis=-1)
    return float(np.max(num / den))


def fit_gap(X, theta, theta_ref) -> float:
    """Largest ||X (θ − θ_ref)|| / ||X θ_ref|| over requests: the gap in
    the values each served θ fits to its own samples.  X (R, n, d) holds
    each request's rows (zero rows past its own count add nothing); θ
    and θ_ref are (R, d).  A few-shot design (6 rows for r = 4) can be
    ill-conditioned, and float32 rounding then moves θ along its weak
    directions by up to cond² times the rounding; the fitted values are
    what the solve determines well, so this gap stays at rounding on
    every request while a solve at lower precision, or a wrong one,
    moves it."""
    X = np.asarray(X, np.float64)
    th = np.asarray(theta, np.float64)
    ref = np.asarray(theta_ref, np.float64)
    if th.shape != ref.shape or X.shape[::2] != th.shape:
        raise ValueError(f"shapes differ: X {X.shape}, θ {th.shape}, "
                         f"θ_ref {ref.shape}")
    num = np.linalg.norm(np.einsum("rnd,rd->rn", X, th - ref), axis=-1)
    den = np.linalg.norm(np.einsum("rnd,rd->rn", X, ref), axis=-1)
    return float(np.max(num / den))
