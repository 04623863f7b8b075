"""Share of the roofline of the DP step: the least time the chip could
take for the algorithm's work (8 bytes per parameter of each selected
client's update, its FLOPs besides), over the device time of every op
under the ``dp_privatize`` scope (the Pallas kernels and the noise draw)."""
from bench.work import flops as work


def read(r):
    if r.peaks is None:
        return None
    t = r.trace.scope_s("dp_privatize") if r.trace is not None else None
    cohort = r.traced.get("cohort_rounds", 0)
    if not t or not cohort:
        return None
    n = work.params(r.traced["model"], r.traced)
    least = max(work.dp_bytes(cohort, n) / r.peaks.hbm_bw,
                work.dp_flops(cohort, n) / r.peaks.flops)
    return least / t * 100.0
