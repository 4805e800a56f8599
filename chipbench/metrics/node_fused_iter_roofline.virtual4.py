"""Share of its roofline that the fused min-B + gradient kernel reaches
on the virtual-node tier, read as ``node_fused_iter_roofline`` reads it:
each chip runs the kernel on its own block of L / chips nodes, and the
share is the mean over the cell's chips."""
from chipbench import harness

read = harness.load_metric_reader("node_fused_iter_roofline")
