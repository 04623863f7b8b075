"""Device microseconds per lane-round under the ``carry_update`` named
scope: the scatter of the cohort's results back into the per-client
carries, the utility update and the adaptive-K step."""


def read(r):
    t = r.trace.scope_s("carry_update") if r.trace is not None else None
    n = r.traced.get("lane_rounds", 0)
    if t is None or not n:
        return None
    return t / n * 1e6
