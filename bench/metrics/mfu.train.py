"""Model FLOP utilisation of training: the model FLOPs per lane-round of
the traced calls (selected clients' local steps, forward and backward,
plus the eval forward once per block) times the run's lane-rounds per
second, over the chips' bf16 peak.  The detectors compute in float32."""
from bench.work import flops as work


def read(r):
    if r.peaks is None:
        return None
    t = r.traced
    rate = r.e2e.get("lane_rounds_per_s")
    if not t.get("lane_rounds") or not rate:
        return None
    total = work.train_flops(t["model"], t, t["cohort_rounds"],
                             t["eval_blocks"])
    return total / t["lane_rounds"] * rate / (r.chips * r.peaks.flops) * 100.0
