"""Batched min-B inference: the serving-side solve.

The product story of the paper is that the learned low-rank basis U
turns a brand-new user's d-dimensional regression into a cheap
r-dimensional one: given the user's few-shot data (X_new, y_new), the
personalized head is b_new = (X_new U)† y_new — exactly the min-B step
of Algorithm 3, with one shared U instead of per-node bases.

:class:`ServingEngine` treats that solve as a request workload.  R
in-flight requests are padded/packed into ONE dispatch of the training
engine's min-B path (:meth:`repro.core.engine.AltgdminEngine.minimize_B`
— the streamed-A ``node_task_gram`` kernel with in-batch Cholesky on the
pallas backends, the ``ref_minimize_B`` oracle on xla-ref), so serving
is bit-consistent with the training-side fold solve by construction.

Packing is exact, not approximate:

  * ragged sample counts (heterogeneous T_new) are right-padded with
    ZERO rows of X and y — a zero row contributes nothing to the Gram
    AᵀA or to Aᵀy, so the padded solve is bit-identical to the unpadded
    one (pinned in tests/test_serving.py);
  * a short batch (R < max_batch) is padded with dummy slots that
    replicate request 0's design and carry y = 0 — their solution is
    exactly 0 and the Gram stays SPD (no NaN lanes), while the real
    slots are untouched bit-for-bit.

Fixed padded shapes (``max_batch`` slots × bucketed T_new) mean the jit
cache holds one executable per (batch-capacity, sample-bucket) pair, not
one per ragged request mix.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import AltgdminEngine
from repro.utils.spans import span


def pack_requests(X_list, y_list, *, max_batch: int, pad_n_to: int = 8,
                  dtype=None):
    """Pad/pack R ragged requests into fixed-shape arrays.

    X_list[i]: (T_i, d); y_list[i]: (T_i,).  Returns
    (X (max_batch, n_pad, d), y (max_batch, n_pad), R) where n_pad is
    the max T_i rounded up to a multiple of ``pad_n_to``.  Slots ≥ R
    replicate request 0's design with zero responses (solution exactly
    0, Gram SPD)."""
    R = len(X_list)
    if R == 0:
        raise ValueError("pack_requests needs at least one request")
    if R > max_batch:
        raise ValueError(f"got {R} requests but max_batch={max_batch}; "
                         f"the admission queue must cap batches")
    with span("serve.pack"):
        d = np.shape(X_list[0])[-1]
        n_pad = (-(-max(np.shape(x)[0] for x in X_list) // pad_n_to)
                 * pad_n_to)
        dt = dtype or jnp.asarray(X_list[0]).dtype
        X = np.zeros((max_batch, n_pad, d), dt)
        y = np.zeros((max_batch, n_pad), dt)
        for i, (Xi, yi) in enumerate(zip(X_list, y_list)):
            t = np.shape(Xi)[0]
            if np.shape(yi)[0] != t:
                raise ValueError(f"request {i}: X has {t} rows but y has "
                                 f"{np.shape(yi)[0]}")
            X[i, :t] = np.asarray(Xi, dt)
            y[i, :t] = np.asarray(yi, dt)
        for i in range(R, max_batch):      # dummy slots: SPD Gram, b = 0
            X[i] = X[0]
    with span("serve.put"):
        return jnp.asarray(X), jnp.asarray(y), R


class ServingEngine:
    """The frozen-or-drifting-U request solver.

    One instance holds the current representation U (d, r) plus a
    :class:`AltgdminEngine` backend binding; :meth:`solve` is the
    request-facing entry (ragged list in, per-request b_new out) and
    :meth:`solve_packed` the fixed-shape hot path the benchmark drives
    directly.  ``update_representation`` hot-swaps U between batches
    (the drifting-U continual mode); the swap is lock-guarded so a
    publisher thread can push while the serving loop drains.
    """

    def __init__(self, U, *, max_batch: int = 32, backend: str | None = None,
                 blk_d: int = 256, pad_n_to: int = 8, version: int = 0):
        self.engine = AltgdminEngine(backend, blk_d=blk_d)
        self.max_batch = int(max_batch)
        self.pad_n_to = int(pad_n_to)
        self._lock = threading.Lock()
        self.update_representation(U, version=version)
        # one jitted closure; U rides as an argument so hot swaps hit
        # the same executable (shapes/dtype unchanged)
        self._solve = jax.jit(self._solve_impl)

    # ------------------------------------------------------------ U life

    def update_representation(self, U, *, version: int | None = None):
        """Hot-swap the representation (e.g. a fresher checkpoint)."""
        U = jnp.asarray(U)
        if U.ndim != 2:
            raise ValueError(f"serving wants a single (d, r) basis, got "
                             f"shape {U.shape}")
        with self._lock:
            self.U = U
            if version is not None:
                self.version = int(version)

    @property
    def d(self) -> int:
        return self.U.shape[0]

    @property
    def r(self) -> int:
        return self.U.shape[1]

    # ------------------------------------------------------------ solve

    def _solve_impl(self, U, X, y):
        # the training-side min-B path verbatim: one node, R tasks
        return self.engine.minimize_B(U[None], X[None], y[None])[0]

    def solve_packed(self, X, y):
        """Fixed-shape hot path.  X: (R, n, d); y: (R, n) → b (R, r).
        Rows beyond a request's true T_new must be zero (exact padding);
        bit-consistent with the training engine's fold solve."""
        with self._lock:
            U, version = self.U, self.version
        return self._solve(U, X, y), version

    def solve(self, X_list, y_list):
        """Ragged request list in, per-request solutions out.

        Returns (B (R, r), theta (R, d), version): b_new per request and
        the personalized regressors θ̂ = U b_new (the basis-invariant
        quantity a drifting U is scored on)."""
        with span("serve.solve"):
            rows = 0
            for i, Xi in enumerate(X_list):
                t = np.shape(Xi)[0]
                if t < self.r:
                    raise ValueError(
                        f"request {i} has T_new={t} < r={self.r} samples; "
                        f"the r-dimensional system is underdetermined")
                rows += t
            X, y, R = pack_requests(X_list, y_list, max_batch=self.max_batch,
                                    pad_n_to=self.pad_n_to, dtype=self.U.dtype)
            slots, n_pad = X.shape[:2]
            with span("serve.dispatch", requests=R, slots=slots, rows=rows,
                      rows_padded=slots * n_pad):
                B_full, version = self.solve_packed(X, y)
            with span("serve.theta"):
                B = B_full[:R]
                theta = B @ self.U.T
            return B, theta, version
