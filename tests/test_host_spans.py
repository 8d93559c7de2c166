"""The serving layer's host spans, recorded by JAX's profiler on the CPU:
each request's path from the front end through the executor call, nested
as the trace reduction reads it and tagged with request numbers."""
import glob
import time

import jax
import numpy as np
import pytest

from conftest import small_workload, TEST_GRID_BINS
from repro.core.types import EngineConfig
from repro.launch import batching

CFG = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS)
CALL_PARTS = ("executor.pad", "planner.dispatch", "executor.dispatch",
              "executor.wait", "executor.unpad")


def _spans(log_dir):
    """(name, start, end, stats) of every host span the serving layer
    opened, per host thread."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(
        glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0])
    threads = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith(("frontend.", "executor.",
                                         "planner."))]
            if evs:
                threads.append(evs)
    return threads


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _numbers(label):
    return {int(w[1:]) for w in label.split()}


@pytest.mark.parametrize("refill", [True, False])
def test_microbatcher_spans_nest_and_carry_request_numbers(tmp_path,
                                                           refill):
    wl = small_workload(seed=0, n_queries=6)
    bcfg = batching.BatchingConfig(max_batch=4, q_buckets=(1, 4),
                                   t_buckets=(2, 3), refill=refill,
                                   refill_depth=4)
    ex = batching.BatchExecutor(wl.store, wl.relax, CFG, "specqp", bcfg)
    ex.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with batching.MicroBatcher(ex) as mb:
            futs = []
            for q in wl.queries:
                futs.append(mb.submit(np.asarray(q)))
                time.sleep(0.02)
            for f in futs:
                f.result(timeout=120)
    finally:
        jax.profiler.stop_trace()

    threads = _spans(tmp_path)
    assert len(threads) == 1                 # the MicroBatcher's worker
    spans = threads[0]
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert set(by) == {"frontend.collect", "frontend.flush",
                       "executor.call", *CALL_PARTS}

    # Every request is collected, flushed and served exactly once.
    for name in ("frontend.collect", "frontend.flush", "executor.call"):
        got = [n for s in by[name] for n in _numbers(s[3]["requests"])]
        assert sorted(got) == list(range(1, len(wl.queries) + 1)), name
    # A group is collected, then flushed; its calls run inside the flush.
    for col in by["frontend.collect"]:
        flush = next(f for f in by["frontend.flush"]
                     if f[3]["requests"] == col[3]["requests"])
        assert col[2] <= flush[1]
    for call in by["executor.call"]:
        flush = [f for f in by["frontend.flush"] if _inside(call, f)]
        assert len(flush) == 1
        assert _numbers(call[3]["requests"]) <= _numbers(
            flush[0][3]["requests"])
        assert call[3]["n"] == len(_numbers(call[3]["requests"]))
        assert call[3]["t_bucket"] in (2, 3)
        assert call[3]["depth"] in (1, 4)
        # One of each part per call, inside it, under its requests.
        for name in CALL_PARTS:
            mine = [s for s in by[name] if _inside(s, call)]
            assert len(mine) == 1, name
            assert mine[0][3]["requests"] == call[3]["requests"]
    assert len(by["executor.call"]) == len(by["executor.wait"])


def test_served_path_plan_s_is_zero_and_plan_group_is_timed():
    """A call that plans for itself waits for no plan (its planner time is
    in exec_s); plan_group waits for its masks and is timed."""
    wl = small_workload(seed=0, n_queries=4)
    bcfg = batching.BatchingConfig(max_batch=4, q_buckets=(1, 4),
                                   t_buckets=(2, 3), refill=True,
                                   refill_depth=4)
    ex = batching.BatchExecutor(wl.store, wl.relax, CFG, "specqp", bcfg)
    ex.run_batch([np.asarray(q) for q in wl.queries[:2]])
    assert ex.stats[-1].plan_s == 0.0 and ex.stats[-1].exec_s > 0.0
    assert ex.plan_total_s == 0.0
    ex.run([np.asarray(q) for q in wl.queries])
    assert ex.plan_total_s > 0.0
    assert all(s.plan_s == 0.0 for s in ex.stats)
