"""Share of the traced window in which no op runs on a chip, mean over
the cell's chips; extra fields the least and the most idle chip."""
import numpy as np

from chipbench import trace


def read(rec):
    if rec.trace is None or not rec.trace.devices:
        return None
    win = trace.window_len_s(rec.trace) * 1e9
    idle = [100.0 * (1.0 - trace.length(trace.busy(rec.trace, dev)) / win)
            for dev in sorted(rec.trace.devices)]
    return {"value": float(np.mean(idle)), "min": float(min(idle)),
            "max": float(max(idle))}
