"""Reduce a JAX profiler trace to device busy and idle time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things: the device's operation intervals (one list per device plane)
and the host's spans (one list per host thread). ``reduce`` then measures,
inside a window and on the trace's own clock:

- busy: the union of the device's operation intervals, averaged over the
  devices;
- busy and idle while at least one query is outstanding (the benchmark
  passes the outstanding intervals, mapped onto the trace clock by the
  ``bench.anchor`` span it writes at the window's start);
- the operations that took most device time;
- the longest idle gaps while queries were outstanding, each named by the
  benchmark span (``bench.*``) the host was in at the gap's midpoint and
  the innermost host event inside it.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

ANCHOR = "bench.anchor"
# Device lines, most detailed first: an operation line gives busy time and
# operation names; a module line (one event per program run) is the
# fallback when a device reports no operation line.
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def short_name(name: str) -> str:
    """An operation's HLO name without its text (``%fusion.12 = f32[..]
    fusion(..)`` -> ``fusion.12``) or a program's name without its
    fingerprint (``jit_f(1234)`` -> ``jit_f``)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return head.split("(", 1)[0] if head.startswith("jit_") else head


@dataclasses.dataclass
class Events:
    """Named intervals: parallel arrays, times in ns on the trace clock.
    Names are kept once each (``table``); ``ids`` index into it."""

    table: list[str]
    ids: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, rows, rename=None) -> "Events":
        index: dict[str, int] = {}
        table, ids, start, dur = [], [], [], []
        for name, s, d in rows:
            i = index.get(name)
            if i is None:
                i = index[name] = len(table)
                table.append(rename(name) if rename else name)
            ids.append(i)
            start.append(s)
            dur.append(d)
        start = np.asarray(start, np.float64)
        return cls(table, np.asarray(ids, np.int64), start,
                   start + np.asarray(dur, np.float64))

    def name(self, i: int) -> str:
        return self.table[self.ids[i]]


@dataclasses.dataclass
class Trace:
    devices: dict[str, Events]       # device plane -> operation intervals
    programs: dict[str, Events]      # device plane -> program runs
    host: dict[str, Events]          # host thread line -> spans
    anchor_ns: float | None          # start of the bench.anchor span


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _rows(line):
    return ((e.name, e.start_ns, e.duration_ns) for e in line.events)


def load(path: str) -> Trace:
    """Read the device and host planes of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, programs, host, anchor = {}, {}, {}, None
    for plane in data.planes:
        # Line names repeat: every Python thread's line is "python".
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            named = {ln.name: ln for ln in lines}
            ops = next((named[n] for n in OP_LINES if n in named), None)
            mods = next((named[n] for n in MODULE_LINES if n in named), None)
            if ops is not None or mods is not None:
                devices[plane.name] = Events.of(_rows(ops or mods),
                                                short_name)
            if mods is not None:
                programs[plane.name] = Events.of(_rows(mods), short_name)
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(lines):
                ev = Events.of(_rows(ln))
                host[f"{plane.name}/{ln.name}#{i}"] = ev
                if anchor is None and ANCHOR in ev.table:
                    hit = ev.ids == ev.table.index(ANCHOR)
                    anchor = float(ev.start[hit][0])
    return Trace(devices=devices, programs=programs, host=host,
                 anchor_ns=anchor)


def union(start: np.ndarray, end: np.ndarray
          ) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals into disjoint sorted ones."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(len(s), bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], reach[last]


def clip(start, end, lo: float, hi: float):
    s, e = np.maximum(start, lo), np.minimum(end, hi)
    keep = e > s
    return s[keep], e[keep]


def intersect(a, b) -> tuple[np.ndarray, np.ndarray]:
    """Intersection of two sets of disjoint sorted intervals."""
    (as_, ae), (bs, be) = a, b
    out_s, out_e = [], []
    i = j = 0
    while i < len(as_) and j < len(bs):
        lo, hi = max(as_[i], bs[j]), min(ae[i], be[j])
        if hi > lo:
            out_s.append(lo)
            out_e.append(hi)
        if ae[i] < be[j]:
            i += 1
        else:
            j += 1
    return np.asarray(out_s, np.float64), np.asarray(out_e, np.float64)


def gaps_within(busy, cover) -> tuple[np.ndarray, np.ndarray]:
    """Parts of ``cover`` (disjoint sorted) where ``busy`` is not."""
    (bs, be), (cs, ce) = busy, cover
    out_s, out_e = [], []
    j = 0
    for lo, hi in zip(cs, ce):
        cur = lo
        while j < len(bs) and be[j] <= lo:
            j += 1
        k = j
        while k < len(bs) and bs[k] < hi:
            if bs[k] > cur:
                out_s.append(cur)
                out_e.append(bs[k])
            cur = max(cur, be[k])
            k += 1
        if cur < hi:
            out_s.append(cur)
            out_e.append(hi)
    return np.asarray(out_s, np.float64), np.asarray(out_e, np.float64)


def host_label(host: dict[str, Events], t: float) -> str:
    """The outermost ``bench.*`` span and the innermost host event that
    hold instant ``t``, on any host thread."""
    bench, inner, inner_len = None, None, np.inf
    for ev in host.values():
        hit = np.flatnonzero((ev.start <= t) & (ev.end >= t))
        for i in hit:
            n, length = ev.name(i), ev.end[i] - ev.start[i]
            if n.startswith("bench.") and n != ANCHOR:
                if bench is None or length > bench[1]:
                    bench = (n, length)
            elif length < inner_len:
                inner, inner_len = n, length
    outer = bench[0] if bench else "bench.none"
    return outer if inner is None else f"{outer}>{inner}"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # device busy in the window, mean over devices
    outstanding_s: float          # time with >= 1 query outstanding
    busy_outstanding_s: float     # device busy within that time
    top_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]
    program_s: dict[str, float]   # device time per program, mean over devices


def reduce(trace: Trace, lo: float, hi: float,
           outstanding: tuple[np.ndarray, np.ndarray], top: int = 10
           ) -> Reduced:
    """Reduce ``trace`` over the window [lo, hi] (trace-clock ns).

    ``outstanding`` holds the (start, end) intervals of the queries, in
    trace-clock ns; their union is the time in which work was waiting."""
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    out = clip(*union(*outstanding), lo, hi)
    out_len = float(np.sum(out[1] - out[0]))
    busy_sum = busy_out_sum = 0.0
    op_time: dict[str, float] = {}
    gaps = []
    for ev in trace.devices.values():
        s, e = clip(ev.start, ev.end, lo, hi)
        busy = union(s, e)
        busy_sum += float(np.sum(busy[1] - busy[0]))
        both = intersect(busy, out)
        busy_out_sum += float(np.sum(both[1] - both[0]))
        _add_time(op_time, ev, lo, hi, self_time(ev))
        gs, ge = gaps_within(busy, out)
        gaps += list(zip(gs, ge))
    n_dev = len(trace.devices)
    prog_time: dict[str, float] = {}
    for ev in trace.programs.values():
        _add_time(prog_time, ev, lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [(host_label(trace.host, 0.5 * (a + b)), (b - a) * 1e-9)
                for a, b in gaps[:top]]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_sum / n_dev * 1e-9,
        outstanding_s=out_len * 1e-9,
        busy_outstanding_s=busy_out_sum / n_dev * 1e-9,
        top_ops=[(n, v / n_dev * 1e-9) for n, v in top_ops],
        idle_gaps=top_gaps,
        program_s={n: v / n_dev * 1e-9 for n, v in prog_time.items()})


def self_time(ev: Events) -> np.ndarray:
    """Each event's duration less that of the events nested in it (a loop
    operation holds its body's operations on the same line)."""
    own = ev.end - ev.start
    order = np.lexsort((-ev.end, ev.start))
    stack: list[int] = []
    for i in order.tolist():
        while stack and ev.end[stack[-1]] <= ev.start[i]:
            stack.pop()
        if stack and ev.end[i] <= ev.end[stack[-1]]:
            own[stack[-1]] -= ev.end[i] - ev.start[i]
        stack.append(i)
    return own


def _add_time(acc: dict[str, float], ev: Events, lo: float, hi: float,
              own: np.ndarray | None = None) -> None:
    """Add each name's time inside [lo, hi] to ``acc``; with ``own``, the
    events' self time, scaled by the share of each inside the window."""
    span = ev.end - ev.start
    dur = np.clip(np.minimum(ev.end, hi) - np.maximum(ev.start, lo), 0, None)
    if own is not None:
        dur = own * np.divide(dur, span, out=np.zeros_like(dur),
                              where=span > 0)
    tot = np.bincount(ev.ids, weights=dur, minlength=len(ev.table))
    for i, v in enumerate(tot):
        if v > 0:
            acc[ev.table[i]] = acc.get(ev.table[i], 0.0) + float(v)
