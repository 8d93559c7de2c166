"""Plain numpy reference for top-k relaxed star joins, and the comparisons.

The answer to a star query over patterns t = 1..T is every entity that
matches each pattern, either the pattern itself or one of its weighted
relaxations; its score is the sum over patterns of the best weighted score
among the pattern's sources (Definition 8's max over rewritings). Scores
are each list's raw scores divided by the list's maximum (Definition 5).
A plan mask (T, R) restricts which relaxations may contribute.

The reference works in float64 on the generator's raw lists and rules. It
imports nothing of the program and reads nothing the program built.
"""
from __future__ import annotations

import numpy as np


class Reference:
    """Exact top-k answers over one generated workload."""

    def __init__(self, patterns, rules, n_entities: int, n_relax: int):
        self.n_entities = n_entities
        self.n_relax = n_relax
        self.lists = []
        for keys, raw in patterns:
            raw = np.asarray(raw, np.float64)
            top = raw.max() if len(raw) else 0.0
            self.lists.append((np.asarray(keys, np.int64),
                               raw / top if top > 0 else raw))
        # Relaxation slots in the order a relaxation table lays them out:
        # by weight, heaviest first (a stable sort), at most n_relax.
        self.relax = {p: sorted(rl, key=lambda t: -t[1])[:n_relax]
                      for p, rl in rules.items()}

    def totals(self, query: np.ndarray, mask: np.ndarray | None = None
               ) -> np.ndarray:
        """(n_entities,) float64 answer scores, -inf for non-answers.

        ``query`` holds pattern ids, -1 padded; ``mask`` is (T, R) over the
        query's real patterns in order, or None for every relaxation."""
        pids = [int(p) for p in np.asarray(query) if p >= 0]
        total = np.zeros(self.n_entities)
        answer = np.ones(self.n_entities, bool)
        for t, pid in enumerate(pids):
            best = np.full(self.n_entities, -np.inf)
            sources = [(pid, 1.0)] + [
                (rid, w) for r, (rid, w) in enumerate(self.relax.get(pid, []))
                if mask is None or bool(mask[t, r])]
            for sid, w in sources:
                keys, sc = self.lists[sid]
                best[keys] = np.maximum(best[keys], sc * w)
            hit = best > -np.inf
            total += np.where(hit, best, 0.0)
            answer &= hit
        return np.where(answer, total, -np.inf)

    def topk(self, totals: np.ndarray, k: int
             ) -> tuple[np.ndarray, np.ndarray]:
        """Top-k (keys, scores) of ``totals``, ties by the smaller key;
        keys -1 and scores -inf past the last answer."""
        k_eff = min(k, len(totals))
        idx = np.argpartition(-totals, k_eff - 1)[:k_eff]
        idx = idx[np.lexsort((idx, -totals[idx]))]
        sc = totals[idx]
        keys = np.where(sc > -np.inf, idx, -1)
        out_k = np.full(k, -1, np.int64)
        out_s = np.full(k, -np.inf)
        out_k[:k_eff], out_s[:k_eff] = keys, sc
        return out_k, out_s


def score_gap(keys: np.ndarray, scores: np.ndarray, ref_scores: np.ndarray,
              totals: np.ndarray) -> float:
    """Widest gap between a served top-k and the reference's, as a share of
    the reference's best score.

    Two gaps are read at each rank: the served score against the
    reference's score at that rank, and the served score against what the
    reference gives the served key. A missing answer reads as 0, so a
    served non-answer, a dropped answer or a wrong score all show. Ties in
    score may order keys differently; neither gap depends on it."""
    keys = np.asarray(keys, np.int64)
    s = np.asarray(scores, np.float64)
    s = np.where(np.isfinite(s) & (keys >= 0), s, 0.0)
    r = np.where(np.isfinite(ref_scores), ref_scores, 0.0)
    own = np.where(keys >= 0, totals[np.clip(keys, 0, len(totals) - 1)],
                   0.0)
    own = np.where(np.isfinite(own), own, 0.0)
    scale = max(float(r[0]) if len(r) else 0.0, 1e-30)
    return float(max(np.abs(s - r).max(initial=0.0),
                     np.abs(s - own).max(initial=0.0)) / scale)


def precision(keys: np.ndarray, ref_keys: np.ndarray) -> float:
    """Share of the reference's top-k keys that the served top-k holds."""
    want = set(int(x) for x in ref_keys if x >= 0)
    got = set(int(x) for x in keys if x >= 0)
    return len(want & got) / max(len(want), 1)
