"""Model FLOP utilisation of scoring: the model FLOPs of the windows scored
in the traced segment (every view), over the device busy time of the
segment times the bf16 peak.  The detectors compute in float32."""
from bench.work import flops as work


def read(r):
    if r.peaks is None:
        return None
    n = r.traced.get("windows")
    if r.trace is None or not n or r.trace.busy_s <= 0:
        return None
    w, s = r.traced["feature_shape"]
    f = n * r.traced["views"] * work.ssm_view_flops(r.traced["hidden"], w, s)
    return f / (r.trace.busy_s * r.chips * r.peaks.flops) * 100.0
