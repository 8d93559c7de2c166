"""Pallas TPU kernel: blocked scored equi-join probe (the rank-join hot path).

Probes a block of B join keys against a unique-key scored seen-buffer of
length N. The equality matrix (B × TILE_N) contracted against the score
vector is exactly a QKᵀ-shaped MXU tile — this is the TPU-native form of
the paper's rank-join inner loop (DESIGN.md §2).

Grid: sequential over N/TILE_N seen tiles, accumulating into the (B, 1)
outputs (constant output block mapping ⇒ revisiting accumulation). The live
count ``seen_cnt`` is a (1, 1) scalar in SMEM: under the engine's lane and
stream ``vmap`` it becomes one SMEM scalar per grid step of the batch axes,
where a VMEM block of it would break the (8, 128) tiling rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAD_KEY = -1


def _lookup_kernel(cnt_ref, probe_ref, keys_ref, scores_ref,
                   out_s_ref, out_f_ref, *, tile_n: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_s_ref[...] = jnp.zeros_like(out_s_ref)
        out_f_ref[...] = jnp.zeros_like(out_f_ref)

    probes = probe_ref[...]                  # (B, 1) int32
    keys = keys_ref[...]                     # (1, TILE_N) int32
    scores = scores_ref[...]                 # (1, TILE_N) f32
    pos = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    valid = (keys != PAD_KEY) & (pos < cnt_ref[0, 0])
    eq = (probes == keys) & valid            # (B, TILE_N)
    eqf = eq.astype(jnp.float32)
    # MXU contraction: matched score (sum == the unique match) and count.
    # The score contraction keeps full f32 precision (the match is exact).
    out_s_ref[...] += jax.lax.dot_general(
        eqf, jnp.where(valid, scores, 0.0),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    out_f_ref[...] += jax.lax.dot_general(
        eqf, valid.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def rank_join_lookup(seen_keys: jax.Array, seen_scores: jax.Array,
                     probe_keys: jax.Array, seen_cnt: jax.Array,
                     tile_n: int = 512, interpret: bool = True):
    """Pallas-backed lookup. Returns (scores (B,) f32, found (B,) bool)."""
    n = seen_keys.shape[0]
    b = probe_keys.shape[0]
    n_pad = -n % tile_n
    if n_pad:
        seen_keys = jnp.pad(seen_keys, (0, n_pad), constant_values=PAD_KEY)
        seen_scores = jnp.pad(seen_scores, (0, n_pad))
    grid = (seen_keys.shape[0] // tile_n,)

    out_s, out_f = pl.pallas_call(
        functools.partial(_lookup_kernel, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((b, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, tile_n), lambda j: (0, j)),
            pl.BlockSpec((1, tile_n), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((b, 1), lambda j: (0, 0)),
            pl.BlockSpec((b, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        interpret=interpret,
    )(seen_cnt.reshape(1, 1), probe_keys[:, None],
      seen_keys[None, :], seen_scores[None, :])

    found = (out_f[:, 0] > 0.5) & (probe_keys != PAD_KEY)
    scores = jnp.where(found, out_s[:, 0], 0.0)
    return scores, found
