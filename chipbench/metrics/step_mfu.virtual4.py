"""The whole job's share of the chips' peak, by ``step_mfu.train``'s
formula: the window's required work over window × chips × peak, with
the cell's four chips."""
from chipbench import harness

read = harness.load_metric_reader("step_mfu.train")
