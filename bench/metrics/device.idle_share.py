"""Share of the time with at least one query outstanding in which no
operation ran on the device (profiler trace, %)."""


def read(run):
    t = run.trace
    if t is None or t.outstanding_s <= 0:
        return None
    return (1.0 - t.busy_outstanding_s / t.outstanding_s) * 100.0
