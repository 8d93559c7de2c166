"""Split a traced window's device time by program part, and its idle time
by host layer.

``bench/trace.py`` measures whole programs. The program names its parts:
every device operation of the planner and the executor runs under a
``jax.named_scope`` (``repro.core.scopes``: ``planner.cardinality``,
``executor.probe``, ...), and the serving layer opens host spans
(``frontend.collect``, ``executor.call``, ``executor.wait``, ...). This
module reduces a trace by them:

- ``scope_s``: each part's device self time in the window, mean over the
  devices; ``""`` holds the time of operations in no part;
- ``idle_by_layer_s``: the device's idle time while a query is
  outstanding, split by the host span open at that instant on any host
  thread: ``executor`` (an ``executor.*`` or ``planner.dispatch`` span),
  ``frontend`` (a ``frontend.*`` span and no executor span) or ``none``.
  The three add up to the idle time behind ``device.idle_share``.

A trace names each device operation (``%fusion.12 = f32[3,5]{1,0}
fusion(...)``) but does not give its ``op_name``. So each program run is
matched with the compiled module text that holds its operations, and the
operation takes the innermost part scope of its ``op_name`` there. A
program that names no part (one that predates the scopes) reduces to all
of its device time in ``""`` and all idle time in ``none``.

    python3 bench/parts.py --workload <name> --seed <n> --seconds <s>

runs one traced run of a cell, as ``bench/run.py --trace 1`` does, and
prints its result line with a ``parts`` object added: ms per query
answered in the window for each part and idle layer, the programs it
matched, and every operation's part and self time.
"""
from __future__ import annotations

import dataclasses
import re
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tracelib  # noqa: E402

# A part scope is a dotted name under one of the two programs' layers.
PART = re.compile(r"(planner|executor)\.[a-z_]+")
EXECUTOR_SPANS = ("executor.", "planner.")
FRONTEND_SPANS = ("frontend.",)
LAYERS = ("executor", "frontend", "none")


def part_of(op_name: str) -> str:
    """The innermost part scope in an operation's ``op_name`` metadata
    (``jit(f)/vmap(planner.cardinality)/while/body/add`` ->
    ``planner.cardinality``), or ``""``. Transform wrappers such as
    ``vmap(...)`` around a scope are looked through."""
    for comp in reversed(op_name.split("/")):
        while comp.endswith(")") and "(" in comp:
            comp = comp[comp.index("(") + 1:-1]
        if PART.fullmatch(comp):
            return comp
    return ""


@dataclasses.dataclass
class Parts:
    scope_s: dict[str, float]          # part ("" = none) -> device s
    idle_by_layer_s: dict[str, float]  # LAYERS -> idle s while outstanding


def _measure(iv) -> float:
    return float(np.sum(iv[1] - iv[0]))


def _spans(host: dict[str, tracelib.Events], prefixes) -> tuple:
    """Union of the host spans whose names start with ``prefixes``."""
    starts, ends = [], []
    for ev in host.values():
        hit = np.array([n.startswith(prefixes) for n in ev.table], bool)
        if len(hit):
            keep = hit[ev.ids]
            starts.append(ev.start[keep])
            ends.append(ev.end[keep])
    if not starts:
        return np.zeros(0), np.zeros(0)
    return tracelib.union(np.concatenate(starts), np.concatenate(ends))


def reduce(parts: dict[str, tracelib.Events],
           host: dict[str, tracelib.Events], lo: float, hi: float,
           outstanding: tuple[np.ndarray, np.ndarray]) -> Parts:
    """Reduce over the window [lo, hi] (trace-clock ns).

    ``parts`` holds each device plane's operations with their part as the
    name (``device_ops``); ``host`` the host threads' spans and
    ``outstanding`` the queries' intervals, as ``tracelib.reduce`` takes
    them."""
    if not parts:
        raise ValueError("the trace holds no device plane")
    out = tracelib.clip(*tracelib.union(*outstanding), lo, hi)
    executor = _spans(host, EXECUTOR_SPANS)
    frontend = _spans(host, FRONTEND_SPANS)
    scope: dict[str, float] = {}
    idle = dict.fromkeys(LAYERS, 0.0)
    for ev in parts.values():
        tracelib._add_time(scope, ev, lo, hi, tracelib.self_time(ev))
        busy = tracelib.union(*tracelib.clip(ev.start, ev.end, lo, hi))
        gaps = tracelib.gaps_within(busy, out)
        in_exec = tracelib.intersect(gaps, executor)
        front_only = (_measure(tracelib.intersect(gaps, frontend))
                      - _measure(tracelib.intersect(in_exec, frontend)))
        idle["executor"] += _measure(in_exec)
        idle["frontend"] += front_only
        idle["none"] += _measure(gaps) - _measure(in_exec) - front_only
    n = len(parts)
    return Parts(scope_s={k: v / n * 1e-9 for k, v in scope.items()},
                 idle_by_layer_s={k: v / n * 1e-9 for k, v in idle.items()})


# One instruction of a compiled module's text (``Compiled.as_text()``):
# ``%fusion.12 = f32[3,5]{1,0} fusion(...), ..., metadata={op_name="..."}``.
# A TPU trace names its operations by the same text, up to the operands.
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (.+?) [a-z][\w-]*\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(text: str) -> dict[str, tuple[str, str]]:
    """Each instruction of a compiled module: name -> (shape, part)."""
    out = {}
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), part_of(op.group(1)) if op else "")
    return out


def _head(event_name: str) -> tuple[str, str | None]:
    """An operation event's instruction name, and its shape where the
    event gives its HLO text."""
    m = INSTRUCTION.match(event_name)
    if m:
        return m.group(1), m.group(2)
    return tracelib.short_name(event_name), None


def _best(heads: set, modules: list[dict]) -> dict:
    """The module that holds the most of one program run's operations, by
    name and shape, then by name alone. Modules of one program at other
    shapes (T 2 and T 3) reuse instruction names."""
    def score(mod):
        return (sum(mod[n][0] == s for n, s in heads if n in mod),
                sum(n in mod for n, _ in heads))
    return max(modules, key=score, default={})


def device_ops(path: str, texts: list[str],
               label=lambda op, part: part or "") -> dict[str, tracelib.Events]:
    """Each device plane's ``XLA Ops`` events from an ``.xplane.pb``, named
    ``label(operation, part)``: by default, by the part alone.

    The trace gives an operation's name but not its ``op_name`` metadata,
    so each program run (an ``XLA Modules`` event) is matched with the
    compiled module, among ``texts``, that holds its operations, and each
    operation takes its part from there. ``part`` is None for an
    operation that no module holds."""
    from jax.profiler import ProfileData

    modules = [instructions(t) for t in texts]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        named = {ln.name: ln for ln in plane.lines}
        ops = next((named[n] for n in tracelib.OP_LINES if n in named), None)
        if ops is None:
            continue
        mods = next((named[n] for n in tracelib.MODULE_LINES if n in named),
                    None)
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in (mods.events if mods is not None else []))
        r_start = np.array([s for s, _ in runs] or [np.inf])
        r_end = np.array([e for _, e in runs] or [-np.inf])
        events = [(e.name, e.start_ns, e.duration_ns) for e in ops.events]
        start = np.array([s for _, s, _ in events], np.float64)
        run = np.searchsorted(r_start, start, "right") - 1
        run = np.where((run >= 0) & (start < r_end[run.clip(0)]), run, -1)
        head_of: dict[str, tuple[str, str | None]] = {}
        for n, _, _ in events:
            if n not in head_of:
                head_of[n] = _head(n)
        heads = [head_of[n] for n, _, _ in events]
        by_run: dict[int, set] = {}
        for r, h in zip(run.tolist(), heads):
            by_run.setdefault(r, set()).add(h)
        match = {r: _best(hs, modules) if r >= 0 else {}
                 for r, hs in by_run.items()}
        rows = []
        for (_, s, d), h, r in zip(events, heads, run.tolist()):
            found = match[r].get(h[0])
            rows.append((label(h[0], found[1] if found else None), s, d))
        out[plane.name] = tracelib.Events.of(rows)
    return out


SERVED = ("plan_query_batch", "run_query_stream_with_masks")


def _record_programs(engine, seen: dict) -> dict:
    """Wrap the engine's served programs so that each distinct call
    (shapes and static arguments) is kept in ``seen``; returns the
    originals, to be put back."""
    orig = {name: getattr(engine, name) for name in SERVED}

    def wrap(name, fn):
        def call(*args):
            key = (name,) + tuple(getattr(a, "shape", a) for a in args[2:])
            seen.setdefault(key, (fn, args))
            return fn(*args)
        return call

    for name, fn in orig.items():
        setattr(engine, name, wrap(name, fn))
    return orig


def traced_run(c, seed: int, seconds: float, t_proc: float) -> dict:
    """``cell.run`` with the trace on, reducing the window by part as well
    before the trace is deleted; the result line gains ``parts``. Every
    program the run called is compiled again after the window (from the
    compile cache) for its module text."""
    from bench import cell
    from repro.core import engine

    found, seen = {}, {}

    def window(log_dir, win, anchor):
        path = tracelib.find_xplane(log_dir)
        tr = tracelib.load(path)
        off = tr.anchor_ns - anchor["perf"] * 1e9
        done = np.where(win.answered, win.done, np.inf)
        lo, hi = win.t0 * 1e9 + off, (win.t0 + win.seconds) * 1e9 + off
        outstanding = (win.due * 1e9 + off, done * 1e9 + off)
        texts = [fn.lower(*args).compile().as_text()
                 for fn, args in seen.values()]
        ops = device_ops(path, texts, lambda op, part: f"{op}\t{part}")
        by_op: dict[str, float] = {}
        for ev in ops.values():
            tracelib._add_time(by_op, ev, lo, hi, tracelib.self_time(ev))
        by_part = {}
        for name, ev in ops.items():
            parts = [t.split("\t", 1)[1] for t in ev.table]
            by_part[name] = tracelib.Events(
                ["" if p == "None" else p for p in parts], ev.ids, ev.start,
                ev.end)
        found["parts"] = reduce(by_part, tr.host, lo, hi, outstanding)
        found["by_op"] = by_op
        found["programs"] = sorted("{} {}".format(k[0], k[1]) for k in seen)
        found["n"], found["n_dev"] = win.answered_in_window, len(ops)
        ok = win.answered
        found["p50"] = cell.percentile((win.done[ok] - win.due[ok]) * 1e3,
                                       50)
        return tracelib.reduce(tr, lo, hi, outstanding)

    whole = cell._trace_window
    cell._trace_window = window
    orig = _record_programs(engine, seen)
    try:
        out = cell.run(c, seed, seconds, True, t_proc)
    finally:
        cell._trace_window = whole
        for name, fn in orig.items():
            setattr(engine, name, fn)
    p, n, n_dev = found["parts"], found["n"], found["n_dev"]
    ms = {f"{part}_ms": v / n * 1e3 for part, v in p.scope_s.items()
          if part}
    ms["device.unscoped_ms"] = p.scope_s.get("", 0.0) / n * 1e3
    for layer, name in (("executor", "executor.idle_ms"),
                        ("frontend", "frontend.idle_ms"),
                        ("none", "device.idle_unspanned_ms")):
        ms[name] = p.idle_by_layer_s[layer] / n * 1e3
    by_op = sorted(found["by_op"].items(), key=lambda kv: -kv[1])
    unmatched = sum(v for k, v in by_op if k.endswith("\tNone"))
    out["parts"] = {
        "ms_per_query": ms, "answered_in_window": n,
        "latency_p50_ms": found["p50"], "programs": found["programs"],
        "unmatched_ms": unmatched / n_dev * 1e-6 / n,
        "ops": [[*k.split("\t"), v / n_dev * 1e-9] for k, v in by_op]}
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import time

    from bench import cell

    t_proc = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    out = traced_run(cell.load_cell(args.workload), args.seed,
                      args.seconds, t_proc)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
