"""Median wait of a query in the serving front end: from when it was due to
the start of the executor call that served it (host clock, ms)."""
import numpy as np


def read(run):
    w = run.window
    start = {q: c.start for c in w.calls for q in c.qids}
    waits = [start[i] - w.due[i] for i in range(len(w.due)) if i in start]
    return float(np.median(waits)) * 1e3 if waits else None
