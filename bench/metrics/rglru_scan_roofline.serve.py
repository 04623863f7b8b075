"""Share of the roofline of the ``rglru_scan`` kernel in the scorer: the
least time for the algorithm's bytes and FLOPs of the scan over the chunk
states of the windows scored in the traced segment (two views a window),
over the device time of the kernel's events."""
from bench.work import flops as work


def read(r):
    if r.peaks is None:
        return None
    t = r.trace.kernel_s("_rglru_scan") if r.trace is not None else None
    n = r.traced.get("windows")
    if not t or not n:
        return None
    g = work.ssm_dims(r.traced["hidden"], *r.traced["feature_shape"])
    width = g["heads"] * g["p"] * g["n"]
    fl, by = work.rglru_scan_work(n * r.traced["views"], g["nc"], width)
    least = max(fl / r.peaks.flops, by / r.peaks.hbm_bw)
    return least / t * 100.0
