"""Device busy time in the traced window per query answered in it
(profiler trace, ms)."""


def read(run):
    t, n = run.trace, run.window.answered_in_window
    return t.busy_s / n * 1e3 if t is not None and n else None
