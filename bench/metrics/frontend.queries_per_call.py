"""Mean number of real queries per executor call that the front end makes
(the program's ``BatchStats.n_requests``, one call per flush group)."""
import numpy as np


def read(run):
    calls = run.window.calls
    return float(np.mean([len(c.qids) for c in calls])) if calls else None
