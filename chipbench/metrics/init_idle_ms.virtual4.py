"""Device-idle time per job under the program's ``repro.materialize``
spans on the virtual-node tier, read as ``init_idle_ms.train`` reads it
(idle mean over the cell's chips), with the same per-phase fields."""
from chipbench import harness

read = harness.load_metric_reader("init_idle_ms.train")
