"""Median host time of ``repro.api.materialize`` per job (problem
generation, graph and weights, spectral init, η), ended by
``block_until_ready`` on the init; from the harness's spans."""
import numpy as np


def read(rec):
    d = rec.spans.durations("materialize")
    return float(np.median(d)) * 1e3 if d.size else None
