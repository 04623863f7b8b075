"""Host milliseconds per sweep call in the program's front door: the wall
of its ``sweep.prepare`` and ``sweep.readback`` spans, per traced call."""


def read(r):
    calls = r.traced.get("calls", 0)
    walls = [s.wall_s for s in r.spans
             if s.name in ("sweep.prepare", "sweep.readback")]
    if not calls or not walls:
        return None
    return sum(walls) / calls * 1e3
