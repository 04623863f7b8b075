"""Device-idle milliseconds per traced call that fall inside the front
doors' host phases: the ``sweep.*`` and ``population.*`` prepare, readback
and results spans, placed on the trace's clock (``bench/trace/clock.py``),
intersected with the idle gaps of the traced segment on device 0."""
from bench.trace import clock

DOORS = ("sweep.", "population.")
PHASES = (".prepare", ".readback", ".results")


def read(r):
    calls = r.traced.get("calls", 0)
    if r.trace is None or not calls:
        return None
    off = clock.offset_ns(r.trace, r.spans)
    spans = [s for s in r.spans
             if s.name.startswith(DOORS) and s.name.endswith(PHASES)]
    if off is None or not spans:
        return None
    idle = clock.shared_ns(r.trace.gaps(0),
                           [clock.interval(s, off) for s in spans])
    return idle / calls * 1e-6
