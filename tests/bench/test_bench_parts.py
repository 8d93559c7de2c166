"""The per-part and per-layer reductions on small synthetic traces, against
brute force over integer instants."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np
import pytest

from bench import parts
from bench import trace as tr

PART_NAMES = ("planner.cardinality", "executor.probe", "")


@pytest.mark.parametrize("op_name, part", [
    ("jit(plan_query_batch)/vmap(planner.cardinality)/while/body/closed_call"
     "/jit(searchsorted)/vmap()/while/body/add", "planner.cardinality"),
    ("jit(f)/executor.pull/executor.probe/dot_general", "executor.probe"),
    ("jit(f)/vmap(vmap(executor.admit))/gather", "executor.admit"),
    ("jit(run_query_stream_with_masks)/while/body/vmap()/select_n", ""),
    ("jit(plan_query_batch)/vmap()/ne", ""),
    ("while", ""),
])
def test_part_of(op_name, part):
    assert parts.part_of(op_name) == part


def _device(rng, n_top=25):
    """Disjoint top-level operations, some holding nested ones (a loop and
    its body), each named by its part."""
    rows, t = [], 0
    for _ in range(n_top):
        t += int(rng.integers(0, 40))
        dur = int(rng.integers(5, 60))
        rows.append((PART_NAMES[rng.integers(3)], t, dur))
        if dur > 20 and rng.random() < 0.5:
            inner = t + int(rng.integers(1, 5))
            rows.append((PART_NAMES[rng.integers(3)], inner, 10))
        t += dur
    return tr.Events.of(rows)


def _host(rng, hi):
    """Two host threads: front-end spans, and executor spans that nest
    inside some of them or stand alone."""
    t0 = []
    for _ in range(12):
        s = int(rng.integers(0, hi))
        t0.append(("frontend.flush", s, int(rng.integers(5, 80))))
        if rng.random() < 0.6:
            t0.append(("executor.call", s + 2, int(rng.integers(1, 60))))
    t1 = [("executor.wait" if rng.random() < 0.5 else "planner.dispatch",
           int(rng.integers(0, hi)), int(rng.integers(1, 40)))
          for _ in range(6)]
    t1 += [("bench.call", int(rng.integers(0, hi)), 50)]
    return {"/host:CPU/python#0": tr.Events.of(t0),
            "/host:CPU/python#1": tr.Events.of(t1)}


def _occupied(ev, lo, hi, names=None):
    x = np.zeros(hi - lo, bool)
    for i in range(len(ev.ids)):
        if names is None or ev.name(i).startswith(names):
            x[max(int(ev.start[i]), lo) - lo:
              max(min(int(ev.end[i]), hi) - lo, 0)] = True
    return x


def _brute(devices, host, lo, hi, out_iv):
    """Per instant: the innermost (shortest) operation's part; for idle
    instants while outstanding, the host layer."""
    n = hi - lo
    out = np.zeros(n, bool)
    for s, e in out_iv:
        out[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    exe = np.zeros(n, bool)
    front = np.zeros(n, bool)
    for ev in host.values():
        exe |= _occupied(ev, lo, hi, ("executor.", "planner."))
        front |= _occupied(ev, lo, hi, ("frontend.",))
    scope, idle = {}, dict.fromkeys(parts.LAYERS, 0.0)
    for ev in devices.values():
        best = np.full(n, np.inf)
        who = np.full(n, -1)
        for i in range(len(ev.ids)):
            a = max(int(ev.start[i]), lo) - lo
            b = min(int(ev.end[i]), hi) - lo
            if b <= a:
                continue
            d = ev.end[i] - ev.start[i]
            sel = np.zeros(n, bool)
            sel[a:b] = True
            sel &= d < best
            best[sel] = d
            who[sel] = i
        for i in np.unique(who[who >= 0]):
            nm = ev.name(i)
            scope[nm] = scope.get(nm, 0) + int(np.sum(who == i))
        gap = out & (who < 0)
        idle["executor"] += int(np.sum(gap & exe))
        idle["frontend"] += int(np.sum(gap & front & ~exe))
        idle["none"] += int(np.sum(gap & ~front & ~exe))
    k = len(devices)
    return ({p: v / k * 1e-9 for p, v in scope.items()},
            {p: v / k * 1e-9 for p, v in idle.items()})


@pytest.mark.parametrize("seed", range(6))
def test_parts_match_brute_force(seed):
    rng = np.random.default_rng(seed)
    devices = {f"/device:TPU:{d}": _device(rng) for d in range(1 + seed % 2)}
    hi = int(max(ev.end.max() for ev in devices.values())) + 20
    lo = int(rng.integers(0, 30))
    host = _host(rng, hi)
    out_iv = [(int(s), int(s + d)) for s, d in
              zip(rng.integers(0, hi, 8), rng.integers(10, 200, 8))]
    outstanding = (np.array([s for s, _ in out_iv], float),
                   np.array([e for _, e in out_iv], float))
    got = parts.reduce(devices, host, lo, hi, outstanding)
    want_scope, want_idle = _brute(devices, host, lo, hi, out_iv)
    assert got.scope_s.keys() == want_scope.keys()
    for p, v in want_scope.items():
        assert got.scope_s[p] == pytest.approx(v, abs=1e-15)
    for layer in parts.LAYERS:
        assert got.idle_by_layer_s[layer] == pytest.approx(
            want_idle[layer], abs=1e-15)

    # The layers add up to the idle time behind device.idle_share, and the
    # parts to the busy time, as bench/trace.py reduces the same trace.
    red = tr.reduce(tr.Trace(devices=devices, programs={}, host=host,
                             anchor_ns=None), lo, hi, outstanding)
    assert sum(got.idle_by_layer_s.values()) == pytest.approx(
        red.outstanding_s - red.busy_outstanding_s, abs=1e-15)
    assert sum(got.scope_s.values()) == pytest.approx(red.busy_s,
                                                      abs=1e-15)


def test_program_without_parts_reduces_to_none():
    dev = {"/device:TPU:0": tr.Events.of([("", 0, 50), ("", 80, 20)])}
    got = parts.reduce(dev, {}, 0, 100, (np.array([0.0]), np.array([100.0])))
    assert got.scope_s == {"": pytest.approx(70e-9)}
    assert got.idle_by_layer_s == {"executor": 0.0, "frontend": 0.0,
                                   "none": pytest.approx(30e-9)}
    with pytest.raises(ValueError):
        parts.reduce({}, {}, 0, 1, (np.zeros(1), np.ones(1)))


def _trace_of(monkeypatch, planes):
    """``ProfileData.from_file`` gives ``planes``: (name, runs, ops), each a
    list of (event name, start, duration)."""
    from types import SimpleNamespace as NS
    import jax.profiler

    def line(name, rows):
        return NS(name=name, events=[
            NS(name=n, start_ns=s, duration_ns=d, stats=[])
            for n, s, d in rows])
    data = NS(planes=[NS(name=name, lines=[line("XLA Modules", runs),
                                           line("XLA Ops", ops)])
                      for name, runs, ops in planes]
              + [NS(name="/host:CPU", lines=[line("python", [])])])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))


TEXT = """HloModule jit_plan_query_batch, entry_computation_layout={{()}}
ENTRY %main {{
  %fusion.7 = f32[{t},5]{{1,0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="jit(plan_query_batch)/vmap({a})/add"}}
  %while.2 = (s32[], f32[{t}]{{0}}) while(%t), condition=%c, body=%b, metadata={{op_name="jit(plan_query_batch)/while"}}
  ROOT %copy.1 = f32[{t},5]{{1,0}} copy(%fusion.7)
}}
"""


def test_device_ops_takes_each_run_s_parts_from_its_own_module(monkeypatch):
    """Two modules of one program at T 2 and T 3 reuse instruction names;
    each run's operations take their parts from the module whose shapes
    they have."""
    t2 = TEXT.format(t=2, a="planner.cardinality")
    t3 = TEXT.format(t=3, a="planner.estimate")
    ops = []
    for t, at in ((3, 0), (2, 100), (3, 200)):
        ops += [(f"%fusion.7 = f32[{t},5]{{1,0}} fusion(f32[8] %p)", at, 10),
                (f"%while.2 = (s32[], f32[{t}]{{0}}) while()", at + 10, 30),
                (f"%copy.1 = f32[{t},5]{{1,0}} copy()", at + 40, 5)]
    ops.append(("%fusion.99 = f32[1] fusion()", 400, 5))    # in no run
    runs = [("jit_plan_query_batch(1)", at, 60) for at in (0, 100, 200)]
    _trace_of(monkeypatch, [("/device:TPU:0", runs, ops)])

    got = parts.device_ops("x.xplane.pb", [t2, t3])["/device:TPU:0"]
    fus = ["planner.estimate", "planner.cardinality", "planner.estimate"]
    want = [x for f in fus for x in (f, "", "")] + [""]
    assert [got.name(i) for i in range(len(ops))] == want
    both = parts.device_ops("x.xplane.pb", [t3, t2],
                            lambda op, part: f"{op} {part}")
    e = both["/device:TPU:0"]
    assert e.name(3) == "fusion.7 planner.cardinality"
    assert e.name(4) == "while.2 " and e.name(len(ops) - 1) == "fusion.99 None"


def test_device_ops_reads_parts_from_a_compiled_planner():
    """The lookup reads a real compiled module: each fusion of the planner's
    program, named as a TPU trace names it, gets the part of its op_name,
    and all three planner parts are found."""
    import re
    import jax.numpy as jnp
    from unittest import mock
    from types import SimpleNamespace as NS
    sys.path.insert(0, str(ROOT / "tests"))
    from conftest import small_workload, TEST_GRID_BINS
    from repro.core import engine, scopes
    from repro.core.types import EngineConfig, PAD_KEY

    wl = small_workload(seed=0, n_queries=4)
    cfg = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS)
    text = engine.plan_query_batch.lower(
        wl.store, wl.relax, jnp.full((4, 3), PAD_KEY, jnp.int32), cfg,
        "specqp").compile().as_text()
    lines = [ln for ln in text.splitlines() if " fusion(" in ln]
    want = [parts.part_of(re.search(r'op_name="([^"]*)"', ln).group(1))
            if "op_name" in ln else "" for ln in lines]
    rows = [NS(name=ln.strip().split(", kind=")[0], start_ns=i * 10,
               duration_ns=5, stats=[]) for i, ln in enumerate(lines)]
    run = NS(name="jit_plan_query_batch(7)", start_ns=0,
             duration_ns=len(rows) * 10, stats=[])
    data = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[run]),
        NS(name="XLA Ops", events=rows)])])
    with mock.patch("jax.profiler.ProfileData.from_file",
                    lambda path: data):
        got = parts.device_ops("x.xplane.pb", [text])["/device:TPU:0"]
    assert [got.name(i) for i in range(len(rows))] == want
    assert set(want) >= set(scopes.PLANNER)
