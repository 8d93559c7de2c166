"""The benchmark's copy of the synthetic scored-KG generator.

A copy of the program's ``repro.data.kg_synth.make_workload`` that stops
before ingest: it returns the raw per-pattern lists (keys, raw scores), the
relaxation rules and the queries, so that the program's own ingest
(``kg.build_store``) builds the store and the reference reads the same
lists without touching anything the program made. For the same seed and
parameters it draws the same random numbers in the same order as
``kg_synth``, so the lists are the same (tests/bench checks it).

XKG-like: 2-4 patterns per query, 10 weighted relaxations per pattern,
original lists filled to 50-100% of the list length. Twitter-like: 2-3
patterns, 5 relaxations, originals filled to 10-45%. Scores are power-law
(Zipf-like rank scores with lognormal noise); about 30% of relaxations are
strays whose answers miss the query's answer pool.
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

KINDS = {
    # name: (default queries, relaxations, patterns per query, base fill)
    "xkg": (65, 10, (2, 4), (0.5, 1.0)),
    "twitter": (50, 5, (2, 3), (0.10, 0.45)),
}


@dataclasses.dataclass(frozen=True)
class RawWorkload:
    patterns: list[tuple[np.ndarray, np.ndarray]]  # (keys int32, raw scores f64)
    rules: dict[int, list[tuple[int, float]]]      # pattern -> [(relaxed, w)]
    queries: np.ndarray                            # (Q, T_max) int32, -1 padded
    n_entities: int
    n_relax: int
    list_len: int


def _powerlaw_scores(rng, n, alpha):
    ranks = np.arange(1, n + 1, dtype=np.float64)
    base = ranks ** (-alpha)
    noise = rng.lognormal(0.0, 0.25, size=n)
    return np.sort(base * noise)[::-1] * 1000.0


def _place_list(rng, core, cover, front, n_extra, n_entities, list_len):
    n_core = int(cover * len(core))
    if cover > 0:
        n_core = max(2, n_core)
    own_core = rng.choice(core, size=n_core, replace=False)
    extra = rng.choice(n_entities, size=n_extra, replace=False)
    extra = np.setdiff1d(extra, own_core)
    keys = np.concatenate([own_core, extra])
    pri = np.concatenate([
        rng.uniform(0.0, max(front, 1e-3), size=len(own_core)),
        rng.uniform(0.0, 1.0, size=len(extra)),
    ])
    order = np.argsort(pri, kind="stable")
    return keys[order][:list_len]


def generate(kind: str, *, seed: int, n_entities: int, list_len: int,
             n_queries: int | None = None, n_relax: int | None = None,
             tp_range: tuple[int, int] | None = None,
             sizes_seed: int | None = None,
             n_patterns: int | None = None) -> RawWorkload:
    """Draw a workload of ``kind`` ("xkg" or "twitter") from ``seed``.

    With ``sizes_seed`` the queries' shapes (patterns per query, list
    fills, how well each list covers the answer pool and where, relaxation
    weights, which relaxations stray) are drawn from it instead, and only
    the entities, their order and the score noise from ``seed``: every
    seed then gets the same set of query sizes. Without it every draw
    comes from ``seed`` in ``kg_synth``'s order.

    With ``n_patterns`` the lists fill exactly that many store slots: more
    queries are drawn after the ``n_queries`` the same way, their lists
    fill the slots the pool leaves, and they are not returned as queries;
    relaxations past the last slot are dropped from the filler's rules."""
    if kind not in KINDS:
        raise ValueError(f"unknown generator {kind!r}; have {sorted(KINDS)}")
    d_queries, d_relax, d_range, base_fill = KINDS[kind]
    n_queries = n_queries or d_queries
    n_relax = n_relax or d_relax
    tp_range = tuple(tp_range or d_range)
    rng = np.random.default_rng(seed)
    shape = rng if sizes_seed is None else np.random.default_rng(sizes_seed)
    patterns: list[tuple[np.ndarray, np.ndarray]] = []
    rules: dict[int, list[tuple[int, float]]] = {}
    queries = []

    def add_pattern(keys, alpha):
        patterns.append((keys.astype(np.int32),
                         _powerlaw_scores(rng, len(keys), alpha)))
        return len(patterns) - 1

    for qi in itertools.count():
        if (qi == n_queries and n_patterns is not None
                and len(patterns) > n_patterns):
            raise ValueError(f"{n_queries} queries need {len(patterns)} "
                             f"patterns; the store holds {n_patterns}")
        if qi >= n_queries and len(patterns) >= (n_patterns or 0):
            break
        T = int(shape.integers(tp_range[0], tp_range[1] + 1))
        alpha = float(shape.uniform(0.8, 1.4))
        core_size = int(shape.uniform(0.05, 0.25) * list_len)
        core = rng.choice(n_entities, size=max(core_size, 3 * 20),
                          replace=False)
        qids = []
        for _t in range(T):
            n_base = int(shape.uniform(*base_fill) * list_len)
            cover = float(shape.uniform(0.15, 1.0))
            front = float(shape.uniform(0.05, 1.0))
            pid = add_pattern(_place_list(rng, core, cover, front, n_base,
                                          n_entities, list_len), alpha)
            qids.append(pid)
            w0 = float(shape.uniform(0.25, 0.95))
            rl = []
            for j in range(n_relax):
                w = float(np.clip(w0 * (0.9 ** j) * shape.uniform(0.85, 1.0),
                                  0.02, 0.95))
                rel_cover = (0.0 if shape.random() < 0.3
                             else float(shape.uniform(0.3, 1.0)))
                rel_front = float(shape.uniform(0.05, 0.8))
                n_rel = int(shape.uniform(0.3, 1.0) * list_len)
                rid = add_pattern(_place_list(rng, core, rel_cover, rel_front,
                                              n_rel, n_entities, list_len),
                                  alpha)
                rl.append((rid, w))
            rules[pid] = rl
        if qi < n_queries:
            queries.append(qids + [-1] * (tp_range[1] - T))
    if n_patterns is not None:
        patterns = patterns[:n_patterns]
        rules = {p: [(r, w) for r, w in rl if r < n_patterns]
                 for p, rl in rules.items() if p < n_patterns}
    return RawWorkload(patterns=patterns, rules=rules,
                       queries=np.asarray(queries, np.int32),
                       n_entities=n_entities, n_relax=n_relax,
                       list_len=list_len)
