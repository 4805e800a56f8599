"""Median host time of ``run_experiment`` per job, over its T_GD outer
iterations (the final B refit and the call's own host work included);
from the harness's spans."""
import numpy as np


def read(rec):
    d = rec.spans.durations("run_experiment")
    if not d.size:
        return None
    return float(np.median(d)) / rec.work["T_GD"] * 1e3
