"""Share of answered queries whose plan the exact planner counted by
popcount over the store's key bitmaps (the program's
``ServedResult.planned_by_bitmap``); None where the results lack the
field."""
import numpy as np


def read(run):
    rs = [r for r in run.window.results if r is not None]
    if not rs or not all(hasattr(r, "planned_by_bitmap") for r in rs):
        return None
    return float(np.mean([r.planned_by_bitmap for r in rs]))
