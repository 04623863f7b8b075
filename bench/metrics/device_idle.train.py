"""Share of the traced segment in which no operation ran on the device."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return (1.0 - r.trace.busy_s / r.trace.window_s) * 100.0
