"""Median host time of ``run_experiment`` per job, over its T_GD outer
iterations, read as ``gd_iter_ms.train`` reads it; on the virtual-node
tier the span also holds the node data's placement on the chips and the
shard_map's compile path."""
from chipbench import harness

read = harness.load_metric_reader("gd_iter_ms.train")
