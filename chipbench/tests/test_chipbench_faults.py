"""A run with the timed path broken underneath comes out not correct.

Each fault of ``chipbench.faults`` is planted in the program
(``repro``) for one tiny run that otherwise goes through the harness as
on the chip; ``test_chipbench_drivers`` runs the same cells sound."""
import pytest

from chipbench import faults
from chipbench.tests import tiny

TRAIN_FAULTS = faults.FAULTS["train_jobs"]
SERVE_FAULTS = faults.FAULTS["serve_open_loop"]


@pytest.mark.parametrize("fault", TRAIN_FAULTS, ids=lambda f: f.__name__)
def test_training_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = tiny.execute("exp1.train", seed=41, seconds=0.3)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", SERVE_FAULTS, ids=lambda f: f.__name__)
def test_serving_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    # offered faster than the CPU serves, so batches hold many requests
    out = tiny.execute("exp1.serve", seed=43, seconds=0.3, rate_hz=5000)
    assert out["correct"] is False, out["checks"]
