"""The serving path's share of the chip's peak: the required work of
every batch the window served (the Gram pass, the r×r solves and θ out,
over real rows; see ``work.served``) over window × chips × peak.  The
larger of the FLOP share and the HBM-byte share, labelled by
``bound``."""


def read(rec):
    w = rec.work["served"]
    span = rec.window_s * rec.n_chips
    flops = w["flops"] / (span * rec.peaks["flops_per_s"])
    nbytes = w["bytes"] / (span * rec.peaks["hbm_bytes_per_s"])
    bound = "flops" if flops >= nbytes else "bytes"
    return {"value": 100.0 * max(flops, nbytes), "bound": bound}
