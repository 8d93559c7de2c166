"""The trace reduction on small synthetic traces, against brute force."""
import sys
from pathlib import Path

# The benchmark is the package ``bench`` at the root of the checkout.
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np
import pytest

from bench import trace as tr


def _grid(intervals, lo, hi):
    """Boolean occupancy of integer instants [lo, hi) (brute force)."""
    x = np.zeros(hi - lo, bool)
    for s, e in intervals:
        x[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    return x


@pytest.mark.parametrize("seed", range(5))
def test_interval_algebra_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    a = [(int(s), int(s + d)) for s, d in zip(rng.integers(0, 900, 40),
                                               rng.integers(1, 60, 40))]
    b = [(int(s), int(s + d)) for s, d in zip(rng.integers(0, 900, 15),
                                               rng.integers(1, 120, 15))]
    ua = tr.union(np.array([x[0] for x in a], float),
                  np.array([x[1] for x in a], float))
    ub = tr.union(np.array([x[0] for x in b], float),
                  np.array([x[1] for x in b], float))
    ga, gb = _grid(a, 0, 1100), _grid(b, 0, 1100)
    assert np.sum(ua[1] - ua[0]) == ga.sum()
    both = tr.intersect(ua, ub)
    assert np.sum(both[1] - both[0]) == (ga & gb).sum()
    gaps = tr.gaps_within(ua, ub)
    assert np.sum(gaps[1] - gaps[0]) == (~ga & gb).sum()
    assert np.all(np.diff(ua[0]) > 0) and np.all(ua[1][:-1] < ua[0][1:])


def _trace():
    dev = tr.Events.of([("%fusion.1 = f32[8] fusion()", 100, 50),
                        ("%while.2 = (s32[]) while()", 150, 200),
                        ("%fusion.4 = f32[8] fusion()", 160, 40),
                        ("%fusion.1 = f32[8] fusion()", 600, 100),
                        ("%copy.3 = f32[8] copy()", 900, 50)], tr.short_name)
    mods = tr.Events.of([("jit_plan(123)", 100, 250), ("jit_run(9)", 600, 350)],
                        tr.short_name)
    host = {
        "/host:CPU/python#0": tr.Events.of([("bench.anchor", 10, 1)]),
        "/host:CPU/python#1": tr.Events.of([
            ("bench.call", 90, 400), ("PjitFunction(plan)", 360, 100),
            ("bench.call", 580, 400)]),
    }
    return tr.Trace(devices={"/device:TPU:0": dev},
                    programs={"/device:TPU:0": mods}, host=host, anchor_ns=10)


def test_reduce_busy_idle_ops_and_gaps():
    red = tr.reduce(_trace(), 0, 1000,
                    (np.array([80.0, 500.0]), np.array([400.0, 1000.0])))
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(400e-9)
    # outstanding: [80, 400) and [500, 1000) = 820 ns, busy inside 400 ns
    assert red.outstanding_s == pytest.approx(820e-9)
    assert red.busy_outstanding_s == pytest.approx(400e-9)
    ops = dict(red.top_ops)
    assert ops["while.2"] == pytest.approx(160e-9)     # self time: body apart
    assert ops["fusion.4"] == pytest.approx(40e-9)
    assert ops["fusion.1"] == pytest.approx(150e-9)
    assert red.program_s == {"jit_plan": pytest.approx(250e-9),
                             "jit_run": pytest.approx(350e-9)}
    # idle while outstanding: [80,100) [350,400) [500,600) [700,900) [950,1000)
    labels = {round(s * 1e9): lab for lab, s in red.idle_gaps}
    assert sorted(labels) == [20, 50, 100, 200]
    assert red.idle_gaps[0] == ("bench.call", pytest.approx(200e-9))
    assert labels[100] == "bench.none"                  # between calls
    assert "bench.call>PjitFunction(plan)" in [lab for lab, _ in red.idle_gaps]


def test_reduce_averages_devices_and_refuses_no_device():
    t = _trace()
    t.devices["/device:TPU:1"] = tr.Events.of([("fusion.9", 0, 1000)])
    t.programs["/device:TPU:1"] = tr.Events.of([("jit_plan(1)", 0, 1000)],
                                               tr.short_name)
    red = tr.reduce(t, 0, 1000, (np.array([0.0]), np.array([1000.0])))
    assert red.busy_s == pytest.approx(700e-9)
    assert red.program_s["jit_plan"] == pytest.approx(625e-9)
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace({}, {}, {}, None), 0, 1,
                  (np.zeros(1), np.ones(1)))


def test_self_time_and_short_names():
    ev = tr.Events.of([("a", 0, 100), ("b", 10, 30), ("c", 15, 5),
                       ("d", 50, 20), ("e", 200, 10)])
    assert list(tr.self_time(ev)) == [50, 25, 5, 20, 10]
    assert tr.short_name("%fusion.12 = f32[8]{0} fusion(%p)") == "fusion.12"
    assert tr.short_name("jit_run_query(8841)") == "jit_run_query"
