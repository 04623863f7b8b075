"""Median over the windows due in the traced segment of the wait from a
window's due time to the start of its batch's ``serve.dispatch`` span."""
import numpy as np


def read(r):
    due = r.traced.get("due_epoch")
    batch_of = r.traced.get("batch_of")
    if due is None or batch_of is None:
        return None
    starts = [s.t0 for s in r.spans if s.name == "serve.dispatch"]
    if not starts:
        return None
    first = r.traced["first_traced_batch"]
    waits = []
    for t_due, b in zip(due, batch_of):
        j = b - first
        if 0 <= j < len(starts):
            waits.append(starts[j] - t_due)
    if not waits:
        return None
    return float(np.median(waits)) * 1e3
