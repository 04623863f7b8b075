"""Device microseconds per lane-round under the ``cohort_topk`` named scope."""


def read(r):
    t = r.trace.scope_s("cohort_topk") if r.trace is not None else None
    n = r.traced.get("lane_rounds", 0)
    if t is None or not n:
        return None
    return t / n * 1e6
