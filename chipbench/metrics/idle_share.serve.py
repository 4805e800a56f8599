"""Share of the traced window in which no op runs on the device, mean
over the cell's chips."""
from chipbench import trace


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(rec.trace)
                    / trace.window_len_s(rec.trace))
