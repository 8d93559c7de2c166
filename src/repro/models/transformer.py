"""Decoder-only LM covering the assigned pool: gemma2/gemma3 (local:global
alternation, softcaps, GeGLU), starcoder2 (sliding window, plain GELU),
deepseek-v3 (MLA + shared/routed MoE + MTP), granite-moe.

Layer stacking: layers with the same FFN kind form one scanned *stack*; the
per-layer sliding window is carried as scan xs so local/global alternation
shares one compiled body (DESIGN.md §7). Decode regroups each stack into
RLE runs of equal cache length so local layers keep W-length ring buffers
while global layers keep full-length caches.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models import common as cm
from repro.models import attention as attn
from repro.models import moe as ffnlib
from repro.models.common import param, ParamLeaf


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float = 10_000.0
    window_pattern: tuple[int, ...] = (0,)   # cycled; 0 = global attention
    attn_softcap: float | None = None
    logit_softcap: float | None = None
    gated_ffn: bool = True
    ffn_act: str = "silu"
    post_norms: bool = False                 # gemma2/3 sandwich norms
    embed_scale: bool = False                # gemma: x *= sqrt(D)
    tie_embeddings: bool = True
    mla: attn.MLAConfig | None = None
    moe: ffnlib.MoEConfig | None = None
    first_dense_layers: int = 0              # deepseek: dense-FFN prefix
    mtp_depth: int = 0
    aux_loss_weight: float = 0.01
    mtp_loss_weight: float = 0.3
    norm_eps: float = 1e-6
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    attn_impl: str = "blocked_causal"
    attn_chunk_q: int = 512
    attn_chunk_k: int = 1024
    remat: str = "full"                      # none | full | dots
    moe_chunk: int = 4096

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def windows(self) -> tuple[int, ...]:
        pat = self.window_pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))

    def attn_cfg(self) -> attn.AttnConfig:
        return attn.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv=self.n_kv,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            softcap=self.attn_softcap, mla=self.mla,
            attn_chunk_q=self.attn_chunk_q, attn_chunk_k=self.attn_chunk_k)

    def ffn_cfg(self, dense: bool) -> ffnlib.FFNConfig:
        return ffnlib.FFNConfig(
            d_model=self.d_model, d_ff=self.d_ff, gated=self.gated_ffn,
            act=self.ffn_act,
            moe=None if dense else self.moe and dataclasses.replace(
                self.moe, chunk=self.moe_chunk))

    def stacks(self) -> list[tuple[bool, int, int]]:
        """[(is_dense_ffn, start_layer, n_layers)] — uniform scan groups."""
        if self.moe is None:
            return [(True, 0, self.n_layers)]
        out = []
        if self.first_dense_layers:
            out.append((True, 0, self.first_dense_layers))
        out.append((False, self.first_dense_layers,
                    self.n_layers - self.first_dense_layers))
        return out


# ------------------------------------------------------------------ init

def _init_layer(key, cfg: LMConfig, dense_ffn: bool):
    ks = jax.random.split(key, 6)
    p = {
        "attn_norm": param(ks[0], (cfg.d_model,), ("embed",), init="zeros"),
        "attn": attn.init(ks[1], cfg.attn_cfg(), cfg.pdtype),
        "ffn_norm": param(ks[2], (cfg.d_model,), ("embed",), init="zeros"),
        "ffn": ffnlib.init_ffn(ks[3], cfg.ffn_cfg(dense_ffn), cfg.pdtype),
    }
    if cfg.post_norms:
        p["attn_post"] = param(ks[4], (cfg.d_model,), ("embed",),
                               init="zeros")
        p["ffn_post"] = param(ks[5], (cfg.d_model,), ("embed",),
                              init="zeros")
    return p


def init(key, cfg: LMConfig):
    ks = jax.random.split(key, 4 + len(cfg.stacks()))
    p: dict[str, Any] = {
        "embed": param(ks[0], (cfg.vocab, cfg.d_model),
                       ("vocab", "embed_fsdp"),
                       scale=1.0, dtype=cfg.pdtype),
        "final_norm": param(ks[1], (cfg.d_model,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = param(ks[2], (cfg.d_model, cfg.vocab),
                             ("embed_fsdp", "vocab"), dtype=cfg.pdtype)
    for si, (dense, start, count) in enumerate(cfg.stacks()):
        layers = [_init_layer(cm.fold_key(ks[3], si, i), cfg, dense)
                  for i in range(count)]
        p[f"stack_{si}"] = cm.stack_layers(layers)
    if cfg.mtp_depth:
        mk = jax.random.split(ks[3 + len(cfg.stacks())], 2)
        p["mtp"] = {
            "proj": param(mk[0], (2 * cfg.d_model, cfg.d_model),
                          ("embed", "embed_fsdp"), dtype=cfg.pdtype),
            "layer": _init_layer(mk[1], cfg, dense_ffn=cfg.moe is None),
        }
    return cm.split(p)


# --------------------------------------------------------------- forward

def _layer_fwd(lp, cfg: LMConfig, dense: bool, x, positions, window):
    h = cm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    h = attn.forward(lp["attn"], cfg.attn_cfg(), h, positions, window,
                     cfg.attn_impl)
    if cfg.post_norms:
        h = cm.rms_norm(h, lp["attn_post"], cfg.norm_eps)
    x = x + h
    h = cm.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    h, aux = ffnlib.ffn(lp["ffn"], cfg.ffn_cfg(dense), h)
    if cfg.post_norms:
        h = cm.rms_norm(h, lp["ffn_post"], cfg.norm_eps)
    return x + h, aux


def _stack_fwd(stack_params, cfg: LMConfig, dense: bool, x, positions,
               windows: jax.Array):
    def body(x, xs):
        lp, win = xs
        def inner(x_):
            # Barrier: keeps the scan's saved-residual stack in the carry's
            # own dtype (bf16) — without it XLA hoists the backward's f32
            # convert into the stacking write, doubling activation memory.
            x_ = jax.lax.optimization_barrier(x_)
            return _layer_fwd(lp, cfg, dense, x_, positions, win)
        if cfg.remat == "full":
            inner = jax.checkpoint(
                inner, policy=jax.checkpoint_policies.nothing_saveable)
        elif cfg.remat == "dots":
            inner = jax.checkpoint(
                inner,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        x, aux = inner(x)
        # Sequence-parallel residual stream (Megatron-SP): the carried
        # activation (and therefore the per-layer saved-residual stack) is
        # sharded over the model axis on its seq dim; XLA inserts the
        # gather/scatter around attention/MLP. Cuts activation stacks by
        # the TP width.
        x = sharding.constrain(x, "batch", "act_seq", None)
        return x, aux

    x, auxs = jax.lax.scan(body, x, (stack_params, windows))
    return x, jnp.sum(auxs)


def _embed_table(params):
    return sharding.constrain(params["embed"], "vocab", "embed_fsdp")


def backbone(params, cfg: LMConfig, tokens):
    """tokens (B, S) → final hidden states (B, S, D), aux loss."""
    B, S = tokens.shape
    x = _embed_table(params)[tokens].astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
    x = sharding.constrain(x, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    wins = cfg.windows()
    aux_total = jnp.float32(0.0)
    for si, (dense, start, count) in enumerate(cfg.stacks()):
        w = jnp.asarray(wins[start:start + count], jnp.int32)
        x, aux = _stack_fwd(params[f"stack_{si}"], cfg, dense, x,
                            positions, w)
        aux_total += aux
    return x, aux_total


def logits_from_hidden(params, cfg: LMConfig, x):
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(x.dtype)
    logits = jnp.einsum("...d,dv->...v", x, head)
    return sharding.constrain(
        logits, "batch", *(None,) * (logits.ndim - 2), "vocab")


def _lm_head_loss(params, cfg: LMConfig, x, labels):
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        head = _embed_table(params).T
    else:
        head = sharding.constrain(params["lm_head"], "embed_fsdp", "vocab")
    return cm.chunked_cross_entropy(x, head.astype(x.dtype), labels,
                                    softcap_val=cfg.logit_softcap)


def loss_fn(params, cfg: LMConfig, tokens, labels):
    """Causal LM loss (+ aux balance + MTP). tokens/labels: (B, S)."""
    x, aux = backbone(params, cfg, tokens)
    loss = _lm_head_loss(params, cfg, x, labels)
    metrics = {"lm_loss": loss, "aux_loss": aux}
    if cfg.mtp_depth:
        # MTP: predict t+2 from [h_t ; emb(label_t)] through one extra layer.
        emb_next = _embed_table(params)[jnp.maximum(labels, 0)] \
            .astype(x.dtype)
        emb_next = sharding.constrain(emb_next, "batch", "act_seq", None)
        h = jnp.concatenate([x, emb_next], axis=-1)
        h = jnp.einsum("bsd,dk->bsk", h, params["mtp"]["proj"].astype(x.dtype))
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h, mtp_aux = _layer_fwd(params["mtp"]["layer"], cfg,
                                cfg.moe is None, h, positions, jnp.int32(0))
        mtp_labels = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        mtp_loss = _lm_head_loss(params, cfg, h, mtp_labels)
        aux = aux + mtp_aux
        loss = loss + cfg.mtp_loss_weight * mtp_loss
        metrics["mtp_loss"] = mtp_loss
    total = loss + cfg.aux_loss_weight * aux
    metrics["loss"] = total
    return total, metrics


# ------------------------------------------------------- decode machinery

def _runs(cfg: LMConfig, max_seq: int):
    """RLE runs of (stack_idx, local_start, count, window, cache_len)."""
    wins = cfg.windows()
    runs = []
    for si, (dense, start, count) in enumerate(cfg.stacks()):
        i = 0
        while i < count:
            w = wins[start + i]
            j = i
            while j < count and wins[start + j] == w:
                j += 1
            cache_len = min(w, max_seq) if w > 0 else max_seq
            runs.append((si, i, j - i, w, cache_len))
            i = j
    return runs


def _slice_stack(stack, lo, n):
    return jax.tree_util.tree_map(lambda a: a[lo:lo + n], stack)


def prefill(params, cfg: LMConfig, tokens, max_seq: int):
    """Run the prompt, build per-run caches. Returns (last_logits, caches)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    caches = []
    for (si, lo, n, w, clen) in _runs(cfg, max_seq):
        dense = cfg.stacks()[si][0]
        stack = _slice_stack(params[f"stack_{si}"], lo, n)

        def body(x, lp):
            cache = attn.prefill_cache(lp["attn"], cfg.attn_cfg(),
                                       cm.rms_norm(x, lp["attn_norm"],
                                                   cfg.norm_eps),
                                       positions, clen)
            x, _ = _layer_fwd(lp, cfg, dense, x, positions, jnp.int32(w))
            return x, cache

        x, cache = jax.lax.scan(body, x, stack)
        caches.append(cache)
    logits = logits_from_hidden(params, cfg, x[:, -1:])
    return logits, caches


def decode_step(params, cfg: LMConfig, token, pos, caches, step):
    """One decode step. token: (B,) int32; pos: (B,) abs position;
    step: () int32 ring-write counter. Returns (logits (B, V), caches)."""
    B = token.shape[0]
    x = params["embed"][token][:, None].astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
    new_caches = []
    # Run boundaries are max_seq-independent; cache lengths come from the
    # cache arrays themselves.
    for run, (si, lo, n, w, _clen) in zip(caches, _runs(cfg, 1)):
        dense = cfg.stacks()[si][0]
        stack = _slice_stack(params[f"stack_{si}"], lo, n)

        def body(x, xs):
            lp, cache = xs
            h = cm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            h, new_cache = attn.decode(lp["attn"], cfg.attn_cfg(), h, pos,
                                       jnp.int32(w), cache, step)
            if cfg.post_norms:
                h = cm.rms_norm(h, lp["attn_post"], cfg.norm_eps)
            x = x + h
            h = cm.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
            h, _ = ffnlib.ffn(lp["ffn"], cfg.ffn_cfg(dense), h)
            if cfg.post_norms:
                h = cm.rms_norm(h, lp["ffn_post"], cfg.norm_eps)
            return x + h, new_cache

        x, new_run = jax.lax.scan(body, x, (stack, run))
        new_caches.append(new_run)
    logits = logits_from_hidden(params, cfg, x)[:, 0]
    return logits, new_caches
