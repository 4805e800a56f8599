"""The 95th percentile of the serving latency, over every request due
in the window, from its due time until its θ is on the host (an
unserved request counts at the wait it had when the run gave up).  The
tail is set by the host's batch time and by how the host stalls, and
swings from run to run by far more than an end-to-end bound can hold
(PERF.md), so it stands here beside ``serve_req_per_s``."""


def read(rec):
    return rec.work.get("p95_ms")
