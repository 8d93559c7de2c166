"""Pallas TPU kernel: blockwise online-softmax attention (forward).

Covers the attention variants in the assigned LM pool: GQA head grouping,
causal masking, sliding windows (gemma2/gemma3 local layers, starcoder2)
and logit soft-capping (gemma2). Online-softmax running (m, l, acc) live in
VMEM scratch across the sequential key-tile grid axis; fully-masked
(q-tile, k-tile) pairs are skipped via the block-level causal/window test,
so a W-window layer does O(S·W) work, not O(S²).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 tile_q: int, tile_k: int, seq_k: int, seq_q: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Absolute positions: queries sit at the *end* of the key axis
    # (decode/prefill-friendly offset).
    q_base = seq_k - seq_q + qi * tile_q
    k_base = ki * tile_k
    # Block-level skip: no overlap with the causal/window band.
    live = True
    if causal:
        live = live & (k_base <= q_base + tile_q - 1)
    if window > 0:
        live = live & (k_base + tile_k - 1 > q_base - window)

    @pl.when(live)
    def _run():
        q = q_ref[0, 0].astype(jnp.float32)        # (TQ, D)
        k = k_ref[0, 0].astype(jnp.float32)        # (TK, D)
        v = v_ref[0, 0].astype(jnp.float32)        # (TK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, bool)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                 # (TQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_scr[...][:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_scr[...][:, :1]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "tile_q", "tile_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None, scale: float | None = None,
                    tile_q: int = 128, tile_k: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D); Hq % Hkv == 0 → (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale_v = scale if scale is not None else D ** -0.5
    win = int(window) if window else 0
    cap = float(softcap) if softcap else 0.0
    tq = min(tile_q, Sq)
    tk = min(tile_k, Sk)
    assert Sq % tq == 0 and Sk % tk == 0, (Sq, tq, Sk, tk)

    grid = (B, Hq, Sq // tq, Sk // tk)
    kernel = functools.partial(
        _attn_kernel, scale=scale_v, causal=causal, window=win, softcap=cap,
        tile_q=tq, tile_k=tk, seq_k=Sk, seq_q=Sq)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, tk, D),
                         lambda b, h, qi, ki, g=g: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, tk, D),
                         lambda b, h, qi, ki, g=g: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
