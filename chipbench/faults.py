"""Breakages planted under a run, for the limits' upper readings.

Each planter takes a ``pytest.MonkeyPatch`` and patches the system
under test (or the driver's entry into it) for one run that otherwise
goes through the harness as a measured run does.  :func:`control` puts
the plain reference in the program's place at the precision below the
configuration's; the others are the faults a cell can have.  Run them
with ``python3 -m chipbench.calibrate`` on the chip, and at a small
size in ``chipbench/tests``.
"""
from __future__ import annotations

import functools

import numpy as np

from chipbench.drivers import train_jobs
from chipbench.reference import mtrl

LOWER = {"highest": "high"}


# ---------------------------------------------------------------- control

class ReferenceEngine:
    """``ServingEngine``'s place taken by the plain reference: each batch
    padded to ``max_batch`` requests of the bucket's row count, solved
    by ``mtrl.serve_theta`` at ``precision``."""

    def __init__(self, U, *, max_batch: int, pad_n_to: int, precision: str,
                 **_):
        self.U = U
        self.max_batch = max_batch
        self.pad_n_to = pad_n_to
        self.precision = precision

    def solve(self, X_list, y_list):
        R, d = len(X_list), X_list[0].shape[1]
        n = -(-max(x.shape[0] for x in X_list) // self.pad_n_to)
        n *= self.pad_n_to
        X = np.zeros((self.max_batch, n, d), np.float32)
        y = np.zeros((self.max_batch, n), np.float32)
        for k in range(self.max_batch):
            src = k if k < R else 0
            t = X_list[src].shape[0]
            X[k, :t], y[k, :t] = X_list[src], y_list[src]
        theta = mtrl.serve_theta(self.U, X, y, precision=self.precision)
        return None, theta[:R], None


def control(mp, precision: str = "high"):
    """Every job trained, and every request answered, by the plain
    reference at ``precision`` in the program's place: the jobs of a
    training cell, and the served basis and the solves of a serving
    cell."""
    import jax.numpy as jnp
    import repro.serving
    import repro.serving.publisher

    def make_job(spec):
        def job(key, spans):
            with spans("run_experiment"):
                return train_jobs.reference_job(spec, key,
                                                precision=precision)
        return job
    mp.setattr(train_jobs, "make_job", make_job)
    mp.setattr(repro.serving.publisher, "deployable_basis",
               lambda U_nodes: mtrl.deployable_basis(jnp.asarray(U_nodes)))
    mp.setattr(repro.serving, "ServingEngine",
               functools.partial(ReferenceEngine, precision=precision))


# ----------------------------------------------------------------- faults

def _engine():
    from repro.core import engine
    return engine.AltgdminEngine


def unchanged_state(mp):
    """Every step returns its U: no gradient, no combine (U stays
    orthonormal, so the QR returns it as it was)."""
    import jax.numpy as jnp
    Engine = _engine()
    real = Engine.min_grad

    def no_grad(self, U, *a, **kw):
        B, G = real(self, U, *a, **kw)
        return B, jnp.zeros_like(G)
    mp.setattr(Engine, "min_grad", no_grad)
    mp.setattr(Engine, "make_mixer",
               lambda self, W, T_con, **kw: (lambda z: z))


def half_batch(mp):
    """The gradient from the first half of each node's tasks, scaled
    as the mean over the rest stood for all of them."""
    Engine = _engine()
    real = Engine.min_grad

    def half(self, U, Xb, yb, Xc, yc, **kw):
        B, _ = real(self, U, Xb, yb, Xc, yc, **kw)
        h = Xc.shape[1] // 2
        _, G = real(self, U, Xb[:, :h], yb[:, :h], Xc[:, :h], yc[:, :h],
                    **kw)
        return B, 2.0 * G
    mp.setattr(Engine, "min_grad", half)


def no_exchange(mp):
    """Each node keeps its own iterate: the combine is left out."""
    mp.setattr(_engine(), "make_mixer",
               lambda self, W, T_con, **kw: (lambda z: z))


def altered_answer(mp):
    """One coefficient of the final refit changed by 1%."""
    Engine = _engine()
    real = Engine.minimize_B

    def altered(self, *a):
        return real(self, *a).at[0, 0, 0].multiply(1.01)
    mp.setattr(Engine, "minimize_B", altered)


def altered_served_answer(mp):
    """The first coefficient of every served head changed by 1% where
    the solve produces it."""
    from repro.serving import engine as serving
    real = serving.ServingEngine._solve_impl

    def altered(self, U, X, y):
        return real(self, U, X, y).at[:, 0].multiply(1.01)
    mp.setattr(serving.ServingEngine, "_solve_impl", altered)


def served_half_batch(mp):
    """Only the first half of each batch's requests is solved; the rest
    get the mean of those heads and its θ."""
    import jax.numpy as jnp
    from repro.serving import engine as serving
    real = serving.ServingEngine.solve

    def half(self, X_list, y_list):
        h = max(1, len(X_list) // 2)
        B, theta, version = real(self, X_list[:h], y_list[:h])
        n = len(X_list) - h
        B = jnp.concatenate([B, jnp.broadcast_to(B.mean(0),
                                                 (n, B.shape[1]))])
        theta = jnp.concatenate(
            [theta, jnp.broadcast_to(theta.mean(0), (n, theta.shape[1]))])
        return B, theta, version
    mp.setattr(serving.ServingEngine, "solve", half)


FAULTS = {
    "train_jobs": [unchanged_state, half_batch, no_exchange, altered_answer],
    "serve_open_loop": [unchanged_state, served_half_batch,
                        altered_served_answer],
}
