"""Device microseconds per lane-round under the ``dp_layout`` named scope:
the DP step's pad of each update leaf to [rows, 128] tiles and back."""


def read(r):
    t = r.trace.scope_s("dp_layout") if r.trace is not None else None
    n = r.traced.get("lane_rounds", 0)
    if t is None or not n:
        return None
    return t / n * 1e6
