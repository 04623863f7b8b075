"""The program's spans and events on the device trace's timeline.

The program's tracer (``repro/obs/trace.py``) stamps each span and event
with ``t0_ns`` and ``t1_ns`` from ``time.time_ns()``; the trace counts ns
from the profile's start.  While profiling, each span also enters the trace
as a host annotation of the same name, so the n-th span of a name is the
n-th annotation of that name.  The offset between the two clocks is the
median, over those pairs, of the span's start less its annotation's start:
the trace's ``profile_start_time`` (its ``Task Environment`` plane), up to
the few microseconds between entering an annotation and reading the clock.
A span or an event minus the offset lies on the device timeline.

Spans without ``t0_ns`` (a program older than that field) are not placed:
:func:`offset_ns` is then None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.trace.xplane import _union


def _stamp(item, key: str) -> Optional[int]:
    """``t0_ns``/``t1_ns`` of a ``Span`` or of an event dict."""
    if isinstance(item, dict):
        return item.get(key)
    return getattr(item, key, None)


def offset_ns(red, spans: Sequence) -> Optional[float]:
    """Program clock (epoch ns) minus trace clock (ns from the profile's
    start), from the annotations in ``red.host`` (a
    ``bench.trace.xplane.Reduction``) matched to ``spans`` by name and
    order; None when no span matches."""
    starts: Dict[str, List[float]] = {}
    for s, _, name in sorted(red.host):
        starts.setdefault(name, []).append(s)
    seen: Dict[str, int] = {}
    diffs = []
    for sp in sorted(spans, key=lambda sp: sp.index):
        t0 = _stamp(sp, "t0_ns")
        if t0 is None:
            continue
        i = seen.get(sp.name, 0)
        seen[sp.name] = i + 1
        ann = starts.get(sp.name, [])
        if i < len(ann):
            diffs.append(t0 - ann[i])
    return float(np.median(diffs)) if diffs else None


def interval(item, offset: float) -> Tuple[float, float]:
    """A span's or an event's (start, end) in trace ns; an event's two
    ends are the same instant."""
    return _stamp(item, "t0_ns") - offset, _stamp(item, "t1_ns") - offset


def shared_ns(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> float:
    """The ns that the union of intervals ``a`` and the union of ``b``
    have in common."""
    tot, ua, ub = 0.0, _union(list(a)), _union(list(b))
    i = j = 0
    while i < len(ua) and j < len(ub):
        lo, hi = max(ua[i][0], ub[j][0]), min(ua[i][1], ub[j][1])
        tot += max(0.0, hi - lo)
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return tot
