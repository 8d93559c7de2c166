"""Executor device time per query: the time the profiler trace gives the
``run_query_stream_with_masks`` program (the refill executor loop) inside
the window, over the queries answered in it (ms)."""


def read(run):
    t = run.trace
    n = run.window.answered_in_window
    name = "jit_run_query_stream_with_masks"
    if t is None or not n or name not in t.program_s:
        return None
    return t.program_s[name] / n * 1e3
