"""Host time on JAX's compile path per job, read as
``compile_path_ms.train`` reads it (with its ``lowerings_per_job`` and
``traces_per_job``); on the virtual-node tier it holds the shard_map
that every job traces and lowers.  Extra fields: the mean host time of
the tier's own phases, ``topology_ms`` (``repro.virtual.topology``, the
mixing matrix cut into chips) and ``shard_ms`` (``repro.virtual.shard``,
the node data placed on the chips)."""
import numpy as np

from chipbench import harness, program_spans

_compile_path = harness.load_metric_reader("compile_path_ms.train")


def read(rec):
    got = _compile_path(rec)
    if got is None:
        return None
    for phase in ("topology", "shard"):
        d = [sp[2] for sp in program_spans.in_window(
            rec, f"repro.virtual.{phase}")]
        if d:
            got[f"{phase}_ms"] = float(np.mean(d)) * 1e3
    return got
