"""The whole job's share of the chip's peak: the required work of every
job in the window (spectral init, T_GD iterations, final refit; see
``work.training_job``, which the driver puts in ``rec.work["job"]``)
over window × chips × peak.  The larger of the FLOP share and the
HBM-byte share, labelled by ``bound``."""


def read(rec):
    w = rec.work["job"]
    span = rec.window_s * rec.n_chips
    flops = rec.work["jobs"] * w["flops"] / (span * rec.peaks["flops_per_s"])
    nbytes = rec.work["jobs"] * w["bytes"] / (span
                                              * rec.peaks["hbm_bytes_per_s"])
    bound = "flops" if flops >= nbytes else "bytes"
    return {"value": 100.0 * max(flops, nbytes), "bound": bound}
