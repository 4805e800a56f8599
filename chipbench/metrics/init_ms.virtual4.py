"""Median host time of ``repro.api.materialize`` per job on the
virtual-node tier, read as ``init_ms.train`` reads it: the spectral init
runs on one chip before the node data is spread over the cell's four."""
from chipbench import harness

read = harness.load_metric_reader("init_ms.train")
