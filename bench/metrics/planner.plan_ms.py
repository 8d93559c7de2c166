"""Planner device time per query: the time the profiler trace gives the
``plan_query_batch`` program inside the window, over the queries answered
in it (ms). The host's ``BatchStats.plan_s`` is not used: on a TPU it
times the asynchronous dispatch only, and the planner's device time lands
in the execute call's ``exec_s``."""


def read(run):
    t = run.trace
    n = run.window.answered_in_window
    if t is None or not n or "jit_plan_query_batch" not in t.program_s:
        return None
    return t.program_s["jit_plan_query_batch"] / n * 1e3
