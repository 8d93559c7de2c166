"""Knowledge-graph ingest: build a TripleStore + RelaxTable from host data.

The ingest path is host-side numpy (this is the "database load" phase); the
result is a pytree of device arrays that every engine entry point consumes.
``build_store_host`` stops before the device, for callers that place the
arrays themselves (the sharded build puts each shard on its own device).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.types import TripleStore, RelaxTable, PAD_KEY, KEY_SENTINEL
from repro.core import sketches as sketchlib


def compute_pattern_stats(scores: np.ndarray, length: int) -> np.ndarray:
    """The paper's four statistics (m, sigma_r, S_r, S_m) for one pattern.

    ``scores`` must be sorted descending and normalized to [0, 1].
    r is the smallest rank whose cumulative score mass reaches 80 % of the
    total (§3.1.1 two-bucket model / 80-20 rule).
    """
    m = float(length)
    if length == 0:
        return np.array([0.0, 0.5, 0.0, 0.0], dtype=np.float32)
    s = scores[:length].astype(np.float64)
    total = float(s.sum())
    if total <= 0.0:
        return np.array([m, 0.5, 0.0, 0.0], dtype=np.float32)
    cum = np.cumsum(s)
    r = int(np.searchsorted(cum, 0.8 * total, side="left"))
    r = min(r, length - 1)
    sigma_r = float(s[r])
    # Degenerate guard: sigma must be strictly inside (0, 1) for the
    # two-bucket pdf to be well defined.
    sigma_r = min(max(sigma_r, 1e-4), 1.0 - 1e-4)
    S_r = float(cum[r])
    return np.array([m, sigma_r, S_r, total], dtype=np.float32)


def bitmap_words(domain: int, list_len: int) -> int:
    """uint32 words of a key bitmap row over keys ``[0, domain)``.

    ⌈domain / 32⌉ rounded up to a multiple of 128 (a TPU lane row), or 0
    where the row would hold more bytes than a pattern's list data in the
    store (4·Wk > 12·L: keys, scores and sorted keys), i.e. roughly where
    the domain is wider than 96·L. A zero width leaves the exact planner
    on binary search over ``sorted_keys``.
    """
    words = -(-max(domain, 0) // 32)
    words = -(-words // 128) * 128
    return 0 if 4 * words > 12 * list_len else words


def key_words_for(pattern_keys, list_len: int) -> int:
    """``bitmap_words`` for these lists: the domain runs to the largest key.
    Lists with a negative key, or none at all, get no bitmap."""
    ks = [np.asarray(k) for k in pattern_keys if len(k)]
    if not ks or min(int(k.min()) for k in ks) < 0:
        return 0
    return bitmap_words(max(int(k.max()) for k in ks) + 1, list_len)


def build_key_bits(keys: np.ndarray, words: int) -> np.ndarray:
    """(P, words) uint32 key bitmaps of a (P, L) ``PAD_KEY``-padded key
    array: bit ``k % 32`` of word ``k // 32`` in row p is set iff key k is
    in row p."""
    P = keys.shape[0]
    if words == 0:
        return np.zeros((P, 0), np.uint32)
    rows, cols = np.nonzero(keys != int(PAD_KEY))
    k = keys[rows, cols].astype(np.int64)
    if len(k) and (k.min() < 0 or k.max() >= 32 * words):
        raise ValueError(f"keys in [{k.min()}, {k.max()}] do not fit "
                         f"{words} bitmap words")
    # Keys are unique within a list, so no bit of a word is set twice and
    # the sum of the bits is their OR (exact in float64 below 2**53).
    acc = np.bincount(rows * words + (k >> 5),
                      weights=np.left_shift(1, k & 31).astype(np.float64),
                      minlength=P * words)
    return acc.astype(np.uint32).reshape(P, words)


def build_store(pattern_lists: list[tuple[np.ndarray, np.ndarray]],
                list_len: int | None = None,
                normalize: bool = True,
                sketch_lanes: int = sketchlib.SKETCH_LANES,
                sketch_words: int | None = None,
                key_words: int | None = None) -> TripleStore:
    """``build_store_host`` placed on the default device."""
    return jax.tree_util.tree_map(
        jnp.asarray, build_store_host(pattern_lists, list_len, normalize,
                                      sketch_lanes, sketch_words, key_words))


def build_store_host(pattern_lists: list[tuple[np.ndarray, np.ndarray]],
                     list_len: int | None = None,
                     normalize: bool = True,
                     sketch_lanes: int = sketchlib.SKETCH_LANES,
                     sketch_words: int | None = None,
                     key_words: int | None = None) -> TripleStore:
    """Build a host (numpy) TripleStore from per-pattern (keys, raw_scores).

    Scores are normalized per Definition 5 (divide by the list max) unless
    ``normalize=False`` (used by the sharded build, where normalization by
    the *global* max already happened). Lists are sorted by score desc and
    padded to a common length. Bitmap key signatures for the sketched
    planner (``sketch_lanes`` × W words, DESIGN.md §6) are computed here,
    once per ingest — the sharded build therefore gets shard-local
    signatures whose estimates psum to global totals. The signature width
    W is sized adaptively from the ingest's longest list by default
    (``sketches.adaptive_words``: short lists get narrow cheap sketches,
    lists ≫ 2k keys no longer saturate linear counting); pass
    ``sketch_words`` explicitly to pin a fixed geometry (the sharded build
    does, so every shard's signatures stack and psum). Signatures are
    built unconditionally (also for exact-mode users): the one-time host
    cost is small next to the sort/stats pass, and a store carrying
    signatures can serve either ``cardinality_mode`` per query without
    re-ingest. The exact planner's key bitmaps (``key_bits``) are sized
    from the data by default (``key_words_for``: zero-width where the key
    domain is too wide for the lists); an explicit ``key_words`` pins the
    width (the sharded build passes the global one, so shard stores
    stack) and must hold every key.
    """
    P = len(pattern_lists)
    if list_len is None:
        list_len = max((len(k) for k, _ in pattern_lists), default=1)
        list_len = max(list_len, 1)
    keys = np.full((P, list_len), int(PAD_KEY), dtype=np.int32)
    scores = np.zeros((P, list_len), dtype=np.float32)
    sorted_keys = np.full((P, list_len), int(KEY_SENTINEL), dtype=np.int32)
    lengths = np.zeros((P,), dtype=np.int32)
    stats = np.zeros((P, 4), dtype=np.float32)

    for p, (k, s) in enumerate(pattern_lists):
        k = np.asarray(k, dtype=np.int32)
        s = np.asarray(s, dtype=np.float64)
        assert len(k) == len(s)
        assert len(k) <= list_len, (len(k), list_len)
        if len(np.unique(k)) != len(k):
            raise ValueError(f"pattern {p}: keys must be unique within a list")
        n = len(k)
        lengths[p] = n
        if n:
            mx = s.max()
            if not normalize:
                mx = 1.0
            sn = (s / mx if mx > 0 else s).astype(np.float32)
            order = np.argsort(-sn, kind="stable")
            keys[p, :n] = k[order]
            scores[p, :n] = sn[order]
            sorted_keys[p, :n] = np.sort(k)
            stats[p] = compute_pattern_stats(scores[p], n)
        else:
            stats[p] = compute_pattern_stats(scores[p], 0)

    if sketch_words is None:
        sketch_words = sketchlib.adaptive_words(
            max((len(k) for k, _ in pattern_lists), default=1))
    sketch = sketchlib.build_sketches([k for k, _ in pattern_lists],
                                      lanes=sketch_lanes, words=sketch_words)
    if key_words is None:
        key_words = key_words_for([k for k, _ in pattern_lists], list_len)
    return TripleStore(keys=keys, scores=scores, lengths=lengths,
                       sorted_keys=sorted_keys, stats=stats,
                       sketch=sketch,
                       key_bits=build_key_bits(keys, key_words))


def build_relax_table(P: int,
                      rules: dict[int, list[tuple[int, float]]],
                      max_relax: int | None = None) -> RelaxTable:
    """Build a RelaxTable from {pattern: [(relaxed_pattern, weight), ...]}.

    Relaxations are sorted by weight descending; PLANGEN evaluates every
    slot (its plan is per-relaxation), so the order only affects layout.
    """
    if max_relax is None:
        max_relax = max((len(v) for v in rules.values()), default=1)
        max_relax = max(max_relax, 1)
    ids = np.full((P, max_relax), int(PAD_KEY), dtype=np.int32)
    weights = np.zeros((P, max_relax), dtype=np.float32)
    for p, rl in rules.items():
        rl = sorted(rl, key=lambda t: -t[1])[:max_relax]
        for j, (q2, w) in enumerate(rl):
            assert 0.0 <= w <= 1.0
            ids[p, j] = q2
            weights[p, j] = w
    return RelaxTable(ids=jnp.asarray(ids), weights=jnp.asarray(weights))
