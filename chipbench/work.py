"""Required work of the hot paths, from their shapes.

What the algorithm needs, not what the program happens to do: float32
operands (4 bytes), each input read once and each output written once,
unpadded widths, and the per-node gradient as output (the per-task
tiles the fused kernel writes are the program's choice).  A FLOP is a
multiply or an add; a multiply-add counts two.
"""
from __future__ import annotations

F32 = 4


def fused_iter(*, L: int, tpn: int, n: int, d: int, r: int) -> dict:
    """One min-B + gradient pass over every task (``node_fused_iter``):
    A = X U, the r×r normal equations and their solve, the residual
    A b − y, Xᵀ·residual, its outer product with b, and the sum over a
    node's tasks into its (d, r) gradient."""
    N = L * tpn
    per_task = (2 * n * d * r            # A = X_t U_g
                + 2 * n * r * r          # Aᵀ A
                + 2 * n * r              # Aᵀ y
                + r ** 3                 # Cholesky solve of the r×r system
                + 2 * n * r              # A b − y
                + 2 * n * d              # Xᵀ (A b − y)
                + d * r                  # outer product with b
                + d * r)                 # sum over the node's tasks
    flops = N * per_task
    nbytes = F32 * (N * n * d            # X
                    + N * n              # y
                    + L * d * r          # U
                    + N * r              # B out
                    + L * d * r)         # per-node gradient out
    return {"flops": float(flops), "bytes": float(nbytes)}


def task_gram(*, rows: int, requests: int, d: int, r: int) -> dict:
    """The serving solve's Gram pass (``node_task_gram``) over the real
    rows of a batch: A = X U, Aᵀ A and Aᵀ y for ``requests`` requests
    holding ``rows`` samples between them."""
    flops = rows * (2 * d * r + 2 * r * r + 2 * r)
    nbytes = F32 * (rows * d + rows + d * r
                    + requests * (r * r + r))
    return {"flops": float(flops), "bytes": float(nbytes)}


def served(*, rows: int, requests: int, d: int, r: int) -> dict:
    """A whole serving solve over a batch's real rows: the Gram pass
    (:func:`task_gram`), each request's r×r Cholesky solve, and its
    θ = U b written out."""
    g = task_gram(rows=rows, requests=requests, d=d, r=r)
    return {"flops": g["flops"] + float(requests * (r ** 3 + 2 * d * r)),
            "bytes": g["bytes"] + float(F32 * requests * d)}


def mix(*, L: int, d: int, r: int) -> dict:
    """One combine with a precomputed (L, L) mixing matrix."""
    return {"flops": float(2 * L * L * d * r),
            "bytes": float(F32 * (L * L + 2 * L * d * r))}


def qr(*, L: int, d: int, r: int) -> dict:
    """Householder QR of L (d, r) blocks (≈ 2 d r² FLOPs each)."""
    return {"flops": float(L * 2 * d * r * r),
            "bytes": float(F32 * 2 * L * d * r)}


def spectral_init(*, L: int, tpn: int, n: int, d: int, r: int, T_pm: int,
                  T_con: int) -> dict:
    """Algorithm 2: the truncated covariance columns Xᵀ y (one read of
    X), T_pm power iterations Θ_g Θ_gᵀ U_g with T_con gossip rounds and
    a QR each, and node 0's broadcast."""
    T = L * tpn
    flops = (2 * T * n * d
             + T_pm * (4 * d * T * r)
             + (T_pm + 1) * T_con * mix(L=L, d=d, r=r)["flops"]
             + (T_pm + 1) * qr(L=L, d=d, r=r)["flops"])
    nbytes = (F32 * (T * n * d + T * n)
              + T_pm * F32 * 2 * (d * T + L * d * r)
              + (T_pm + 1) * (T_con * mix(L=L, d=d, r=r)["bytes"]
                              + qr(L=L, d=d, r=r)["bytes"]))
    return {"flops": float(flops), "bytes": float(nbytes)}


def training_job(*, L: int, tpn: int, n: int, d: int, r: int, T_pm: int,
                 T_con_init: int, T_GD: int, T_con: int) -> dict:
    """A whole job: the spectral init, T_GD iterations (fused pass, one
    combine with W^{T_con}, QR) and the final min-B refit."""
    it = fused_iter(L=L, tpn=tpn, n=n, d=d, r=r)
    mx, q = mix(L=L, d=d, r=r), qr(L=L, d=d, r=r)
    init = spectral_init(L=L, tpn=tpn, n=n, d=d, r=r, T_pm=T_pm,
                         T_con=T_con_init)
    refit = task_gram(rows=L * tpn * n, requests=L * tpn, d=d, r=r)
    return {k: init[k] + T_GD * (it[k] + mx[k] + q[k]) + refit[k]
            for k in ("flops", "bytes")}


def roofline_s(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and which bound
    sets it (``"flops"`` or ``"bytes"``)."""
    t_flops = work["flops"] / peaks["flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
