"""One run of one benchmark cell: set-up, the open-loop window, the check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file (``configs``' ``file``), its traffic file
(``bench/traffic/<traffic>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``). Adding any of them adds files and entries;
no code here changes.

The window drives the served path as ``launch/serve.py`` builds it: a
``MicroBatcher`` in front of a refill ``BatchExecutor`` in the configured
mode. Queries arrive open-loop on an absolute schedule drawn from the seed;
each is timed from when it was due to when its future resolved. After the
window every answer is compared with the numpy reference (``reference``).
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import gen, reference, traffic
from bench import trace as tracelib

ROOT = Path(__file__).resolve().parents[1]
LEAD_S = 0.2                 # the first arrival is due this long after start
DRAIN_S = 60.0               # how long past the close answers are awaited

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# --------------------------------------------------------------- the spec

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    tr = json.loads((root / "bench" / "traffic" /
                     f"{w['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=tr,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- the device

class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how often the
    backend compiled, read from JAX's monitoring events. Listeners cannot
    be removed, so a process makes one (``compile_clock``)."""

    def __init__(self):
        from jax.monitoring import (register_event_duration_secs_listener,
                                    register_event_listener)
        self.seconds = 0.0
        self.backend_compiles = 0
        self.traces = 0
        self.cache_hits = 0
        self.cache_misses = 0
        register_event_duration_secs_listener(self._on_duration)
        register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.traces += event == _COMPILE_EVENTS[0]
            self.backend_compiles += event == _COMPILE_EVENTS[-1]

    def _on_event(self, event: str, **_) -> None:
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"
        self.cache_misses += event == "/jax/compilation_cache/cache_misses"

    def count(self) -> int:
        return self.traces + self.backend_compiles


_CLOCK: list[CompileClock] = []


def compile_clock() -> CompileClock:
    if not _CLOCK:
        _CLOCK.append(CompileClock())
    return _CLOCK[0]


def device_info(min_count: int, require_tpu: bool = True) -> dict:
    """The device as JAX reports it; refuses anything but a TPU with at
    least ``min_count`` chips unless ``require_tpu`` is off."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {info['platform']}")
    if require_tpu and info["count"] < min_count:
        raise SystemExit(f"need {min_count} chips, JAX sees {info['count']}")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the program puts it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else a fixed directory in the
    checkout), keeping every program however quickly it compiled."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# --------------------------------------------------------- the system

@dataclasses.dataclass
class Call:
    """One executor call made by the MicroBatcher, on the host clock."""

    start: float
    qids: list[int]
    plan_s: float
    exec_s: float
    t_bucket: int


def timed_executor_class():
    """``BatchExecutor`` with a record of every call the front end makes
    (the subclass is built on first use so that importing this module does
    not import JAX)."""
    import jax
    from repro.launch import batching

    class TimedExecutor(batching.BatchExecutor):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.calls: list[Call] = []
            self.qid_of: dict[int, int] = {}

        def run_batch(self, group, masks=None):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                out = super().run_batch(group, masks)
            with self._lock:
                st = self.stats[-1]
            self.calls.append(Call(
                start=t0, qids=[self.qid_of[id(q)] for q in group],
                plan_s=st.plan_s, exec_s=st.exec_s, t_bucket=st.t_bucket))
            return out

    return TimedExecutor


@dataclasses.dataclass
class System:
    raw: gen.RawWorkload
    executor: object
    store_bytes: int
    t_buckets: tuple[int, ...]


def build_system(config: dict, n_queries: int, seed: int,
                 score_dtype: str | None = None,
                 sizes_seed: int | None = None) -> System:
    """Generate the pool's lists, ingest them through the program, place
    the store on the device and build the executor."""
    import jax
    import jax.numpy as jnp
    from repro.core import kg
    from repro.core.types import EngineConfig
    from repro.launch import batching

    # Every slot of the configuration's store holds a generated list: the
    # pool's patterns first, then those of filler queries that are never
    # offered. One store shape for every run lets the programs compiled
    # once serve every seed from the persistent cache.
    g = config["generator"]
    n_pat = int(config["store_patterns"])
    raw = gen.generate(g["kind"], seed=seed, n_entities=g["n_entities"],
                       list_len=g["list_len"], n_queries=n_queries,
                       n_relax=g["n_relax"], tp_range=tuple(g["tp_range"]),
                       sizes_seed=(None if sizes_seed is None
                                   else [int(sizes_seed), 0]),
                       n_patterns=n_pat)
    lo, hi = g["tp_range"]
    store = kg.build_store(raw.patterns, list_len=raw.list_len)
    relax = kg.build_relax_table(n_pat, raw.rules, max_relax=raw.n_relax)
    if score_dtype is not None:
        # The control: the program serves scores rounded to a lower
        # precision, while the reference keeps the configuration's.
        store = dataclasses.replace(store, scores=store.scores.astype(
            jnp.dtype(score_dtype)).astype(jnp.float32))
    leaves = jax.tree_util.tree_leaves((store, relax))
    jax.block_until_ready(leaves)
    t_set = tuple(range(lo, hi + 1))
    b = config["batching"]
    bcfg = batching.BatchingConfig(
        max_batch=b["max_batch"], max_wait_s=b["max_wait_s"],
        q_buckets=tuple(b["q_buckets"]), t_buckets=t_set,
        refill=b["refill"], lanes=b["lanes"],
        refill_depth=b["refill_depth"])
    ex = timed_executor_class()(store, relax, EngineConfig(**config["engine"]),
                                config["mode"], bcfg)
    return System(raw=raw, executor=ex,
                  store_bytes=sum(x.nbytes for x in leaves), t_buckets=t_set)


def warm(sys_: System, depths: list[int] | None = None) -> int:
    """Compile and run once every (queue depth, T bucket) program that the
    traffic's front-end groups reach: ``depths`` (the traffic file's
    ``warm_depths``), else every depth up to the bucket that covers
    ``max_batch``, with all-pad queues (one executor trip each; the
    planner's cost does not shrink for pad rows). Calls are made as
    ``BatchExecutor.run_stream`` makes them, so they share its jit cache
    entries. Returns the number of programs."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine
    from repro.launch import batching

    ex = sys_.executor
    bcfg = ex.bcfg
    all_depths = sorted({b for b in bcfg.q_buckets
                         if b <= bcfg.refill_depth} | {bcfg.refill_depth})
    reach = batching.bucket_for(bcfg.max_batch, tuple(all_depths))
    wanted = [batching.bucket_for(d, tuple(all_depths)) for d in depths or []]
    lanes = bcfg.lanes or bcfg.max_batch
    n = 0
    for t_b in sys_.t_buckets:
        for m_b in wanted or (d for d in all_depths if d <= reach):
            dummy = jnp.full((m_b, t_b), -1, jnp.int32)
            masks = engine.plan_query_batch(ex.store, ex.relax, dummy,
                                            ex.cfg, ex.mode)
            jax.block_until_ready(engine.run_query_stream_with_masks(
                ex.store, ex.relax, dummy, masks, ex.cfg, min(lanes, m_b)
            ).scores)
            n += 2
    return n


# ------------------------------------------------------------- the window

@dataclasses.dataclass
class Window:
    """What one open-loop window recorded (host perf_counter seconds)."""

    seconds: float
    t0: float
    due: np.ndarray
    submit: np.ndarray
    done: np.ndarray             # nan where no answer came
    results: list                # ServedResult or None, per arrival
    errors: list                 # exception or None, per arrival
    pool_index: np.ndarray       # pool query each arrival took
    calls: list[Call]

    @property
    def answered(self) -> np.ndarray:
        return np.isfinite(self.done)

    @property
    def answered_in_window(self) -> int:
        return int(np.sum(self.done[self.answered] <= self.t0 + self.seconds))


def drive(sys_: System, sched: traffic.Schedule, seconds: float,
          on_open=None, on_close=None) -> Window:
    """Offer ``sched``'s arrivals to a MicroBatcher in front of the
    executor, each at its due time, and wait for every answer, at most
    ``DRAIN_S`` past the close. ``on_open`` runs just before the first
    arrival is due (the traced run writes its clock anchor there) and
    ``on_close`` when the window closes, while the front end's thread
    still runs (the traced run stops the profiler there: the trace covers
    the window alone, and a thread that has ended loses its host spans)."""
    from repro.launch import batching

    ex = sys_.executor
    n = len(sched.offsets)
    queries = [np.array(sys_.raw.queries[j], np.int32) for j in sched.query]
    ex.qid_of = {id(q): i for i, q in enumerate(queries)}
    ex.calls = []
    due = np.empty(n)
    submit = np.empty(n)
    done = np.full(n, np.nan)
    futs = []

    def mark(i):
        return lambda _f: done.__setitem__(i, time.perf_counter())

    mb = batching.MicroBatcher(ex)
    t0 = time.perf_counter() + LEAD_S
    if on_open is not None:
        on_open(t0)
    for i in range(n):
        due[i] = t0 + sched.offsets[i]
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        submit[i] = time.perf_counter()
        f = mb.submit(queries[i])
        f.add_done_callback(mark(i))
        futs.append(f)
    close = t0 + seconds
    time.sleep(max(0.0, close - time.perf_counter()))
    if on_close is not None:
        on_close()
    _, pending = concurrent.futures.wait(
        futs, timeout=max(0.0, close + DRAIN_S - time.perf_counter()))
    if not pending:
        mb.close()
    results, errors = [], []
    for f in futs:
        if f.done() and f.exception() is None:
            results.append(f.result())
            errors.append(None)
        else:
            results.append(None)
            errors.append(f.exception() if f.done() else None)
    done = np.where([r is not None for r in results], done, np.nan)
    return Window(seconds=seconds, t0=t0, due=due, submit=submit, done=done,
                  results=results, errors=errors, pool_index=sched.query,
                  calls=list(ex.calls))


# -------------------------------------------------------------- the check

@dataclasses.dataclass
class Check:
    score_gap: float         # widest gap of a served answer, under its plan
    precision: np.ndarray    # per answered query, against every relaxation


def check(sys_: System, win: Window, k: int) -> Check:
    ref = reference.Reference(sys_.raw.patterns, sys_.raw.rules,
                              sys_.raw.n_entities, sys_.raw.n_relax)
    gap, prec = 0.0, []
    for j, r in zip(win.pool_index, win.results):
        if r is None:
            continue
        q = sys_.raw.queries[j]
        tot = ref.totals(q, np.asarray(r.relax_mask))
        _, ref_s = ref.topk(tot, k)
        gap = max(gap, reference.score_gap(r.keys, r.scores, ref_s, tot))
        full_k, _ = ref.topk(ref.totals(q), k)
        prec.append(reference.precision(r.keys, full_k))
    return Check(score_gap=gap, precision=np.asarray(prec))


# --------------------------------------------------------------- the run

@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader reads (``bench/metrics/<name>.py``)."""

    window: Window
    trace: tracelib.Reduced | None


def _trace_window(log_dir: str, win: Window, anchor: dict
                  ) -> tracelib.Reduced:
    tr = tracelib.load(tracelib.find_xplane(log_dir))
    if tr.anchor_ns is None:
        raise RuntimeError("the trace holds no bench.anchor span")
    # host perf_counter seconds -> trace-clock ns
    off = tr.anchor_ns - anchor["perf"] * 1e9
    # A query still unanswered at the close is outstanding to the close.
    done = np.where(win.answered, win.done, np.inf)
    return tracelib.reduce(tr, win.t0 * 1e9 + off,
                           (win.t0 + win.seconds) * 1e9 + off,
                           (win.due * 1e9 + off, done * 1e9 + off))


def answered_qps(win: Window) -> float:
    """Queries answered over the time from the window's opening to the
    later of its close and the last answer: the offered rate while the
    system keeps up, the rate it sustains once it falls behind. Counting
    answers inside the window alone would step by one query in
    ``seconds`` with whether the last answer beat the close."""
    ok = win.answered
    span = max(win.seconds, float(np.max(win.done[ok], initial=win.t0))
               - win.t0)
    return float(ok.sum()) / span


def percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else float("nan")


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_proc: float,
        *, require_tpu: bool = True, score_dtype: str | None = None,
        compile_cache: bool = True) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    info = device_info(cell.chips, require_tpu)
    if compile_cache:
        enable_compile_cache()
    clock = compile_clock()
    seed = int(seed) % 2**63
    sched = traffic.schedule(cell.traffic, seconds,
                             np.random.default_rng([seed, 1]))
    t_gen = time.perf_counter()
    sys_ = build_system(cell.config, sched.pool, seed, score_dtype,
                        cell.traffic.get("sizes_seed"))
    say("setup", patterns=len(sys_.raw.patterns), pool=sched.pool,
        t_buckets=sys_.t_buckets, store_bytes=sys_.store_bytes,
        build_s=time.perf_counter() - t_gen)
    t_warm = time.perf_counter()
    programs = warm(sys_, cell.traffic.get("warm_depths"))
    say("warm", programs=programs, warm_s=time.perf_counter() - t_warm,
        compile_s=clock.seconds, backend_compiles=clock.backend_compiles,
        cache_hits=clock.cache_hits, cache_misses=clock.cache_misses)

    log_dir, anchor = None, {}
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(log_dir,
                                 profiler_options=_profile_options())

    def open_window(_t0):
        with jax.profiler.TraceAnnotation(tracelib.ANCHOR):
            anchor["perf"] = time.perf_counter()

    compiles0 = clock.count()
    setup_s = time.perf_counter() + LEAD_S - t_proc
    win = drive(sys_, sched, seconds, on_open=open_window,
                on_close=jax.profiler.stop_trace if trace else None)
    compiles_in_window = clock.count() - compiles0
    reduced = None
    if trace:
        t_red = time.perf_counter()
        reduced = _trace_window(log_dir, win, anchor)
        shutil.rmtree(log_dir, ignore_errors=True)
        say("trace", reduce_s=time.perf_counter() - t_red,
            window_s=reduced.window_s, busy_s=reduced.busy_s,
            outstanding_s=reduced.outstanding_s,
            busy_outstanding_s=reduced.busy_outstanding_s)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:cell.chips])

    n = len(win.due)
    ok = win.answered
    lat_ms = (win.done[ok] - win.due[ok]) * 1e3
    late_ms = (win.submit - win.due) * 1e3
    in_window = win.answered_in_window
    failed = int(n - ok.sum())
    say("window", offered=n, answered=int(ok.sum()), answered_in_window=
        in_window, calls=len(win.calls), compiles_in_window=
        compiles_in_window, latency_samples=len(lat_ms),
        samples_beyond_p95=int(np.sum(lat_ms > percentile(lat_ms, 95))),
        lateness_p50_ms=percentile(late_ms, 50),
        lateness_p99_ms=percentile(late_ms, 99),
        lateness_max_ms=float(late_ms.max()),
        first_error=next((repr(e) for e in win.errors if e is not None),
                         None),
        peak_bytes_in_use=peak)

    # Free the program's state before the reference runs.
    k = int(cell.config["engine"]["k"])
    sys_.executor = None
    gc.collect()
    t_chk = time.perf_counter()
    chk = check(sys_, win, k)
    limits = cell.config["checks"]
    checks = {"score_gap": {"value": chk.score_gap,
                            "limit": limits["score_gap"]},
              "failed": {"value": failed, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    say("check", queries=len(chk.precision),
        check_s=time.perf_counter() - t_chk)

    if trace:
        rec = RunRecord(window=win, trace=reduced)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {
            "setup_s": setup_s,
            "latency_p50_ms": percentile(lat_ms, 50),
            "latency_p95_ms": percentile(lat_ms, 95),
            "answered_qps": answered_qps(win),
            "precision_at_k": (float(chk.precision.mean())
                               if len(chk.precision) else float("nan")),
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = dict(info, memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": n, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in reduced.top_ops],
                            "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    out["checks"] = checks
    return out


def _profile_options():
    """Host spans (ours and JAX's dispatch) without the Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
