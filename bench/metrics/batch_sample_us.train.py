"""Device microseconds per lane-round under the ``batch_sample`` named scope
(the round's batch gather: dense clients' shards, or the cohort's rows)."""


def read(r):
    t = r.trace.scope_s("batch_sample") if r.trace is not None else None
    n = r.traced.get("lane_rounds", 0)
    if t is None or not n:
        return None
    return t / n * 1e6
