"""two-tower-retrieval [RecSys'19 (YouTube)]: embed_dim 256, tower MLP
1024-512-256, dot interaction, sampled softmax. Huge sparse tables (2×20M
rows × 256) shard over the full mesh; the embedding bag IS the hot path.

``retrieval_cand`` applies the paper's technique: Spec-QP speculative
block pruning over the candidate corpus (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.configs import base
from repro.models import recsys as model
from repro.kernels import ops as kops
from repro.train import loop as train_loop
from repro.train import optimizer as opt_lib

ARCH = "two-tower-retrieval"
FAMILY = "recsys"
SHAPES = ["train_batch", "serve_p99", "serve_bulk", "retrieval_cand"]
SKIP_SHAPES: dict[str, str] = {}

CORPUS = 1_048_576          # cached item embeddings for the serve shapes
N_CAND = 1_000_000          # retrieval_cand logical size
N_CAND_PAD = 1_048_576      # padded: divides 256- and 512-way shard × tile
TOPK = 100
TILE = 512                  # per-shard scoring tile (zero-row padded)

TRAIN_CFG = train_loop.TrainConfig(
    opt=opt_lib.AdamWConfig(lr=1e-3, moment_dtype="bfloat16"))


def config() -> model.TwoTowerConfig:
    return model.TwoTowerConfig(
        name=ARCH, embed_dim=256, tower_mlp=(1024, 512, 256),
        user_vocab=20_000_000, item_vocab=20_000_000,
        user_slots=32, item_slots=8, n_dense_feat=16, topk_tile=TILE)


def smoke_config() -> model.TwoTowerConfig:
    return dataclasses.replace(
        config(), embed_dim=32, tower_mlp=(64, 32), user_vocab=2000,
        item_vocab=2000, user_slots=4, item_slots=2, n_dense_feat=4,
        topk_tile=256)


def _batch_specs(cfg, B):
    f32, i32 = jnp.float32, jnp.int32
    return {
        "user_ids": base.spec((B, cfg.user_slots), i32),
        "user_w": base.spec((B, cfg.user_slots), f32),
        "user_dense": base.spec((B, cfg.n_dense_feat), f32),
        "item_ids": base.spec((B, cfg.item_slots), i32),
        "item_w": base.spec((B, cfg.item_slots), f32),
        "item_dense": base.spec((B, cfg.n_dense_feat), f32),
        "item_logq": base.spec((B,), f32),
    }


def _batch_axes(cfg, with_items=True):
    ax = {
        "user_ids": ("batch", None), "user_w": ("batch", None),
        "user_dense": ("batch", None),
        "item_ids": ("batch", None), "item_w": ("batch", None),
        "item_dense": ("batch", None), "item_logq": ("batch",),
    }
    return ax


def make_cell(shape: str) -> base.CellSpec:
    cfg = config()
    key = jax.random.PRNGKey(0)
    init_fn = lambda k: model.init(k, cfg)

    if shape == "train_batch":
        B = 65_536
        state, state_axes = base.train_state_specs(init_fn, key, TRAIN_CFG)
        loss = lambda p, b: model.loss_fn(p, cfg, b)
        step = train_loop.make_train_step(loss, TRAIN_CFG)
        return base.CellSpec(ARCH, shape, "train", step,
                             (state, _batch_specs(cfg, B)),
                             (state_axes, _batch_axes(cfg)))

    p_shapes, p_axes = base.eval_shape_with_axes(init_fn, key)

    if shape in ("serve_p99", "serve_bulk"):
        B = 512 if shape == "serve_p99" else 262_144
        fn = partial(_serve, cfg=cfg, k=TOPK)
        cand = base.spec((CORPUS, cfg.embed_dim), jnp.float32)
        return base.CellSpec(
            ARCH, shape, "serve", fn,
            (p_shapes, _batch_specs(cfg, B), cand),
            (p_axes, _batch_axes(cfg), ("candidates", None)))

    if shape == "retrieval_cand":
        fn = partial(_retrieve, k=TOPK, tile=TILE)
        q = base.spec((cfg.embed_dim,), jnp.float32)
        cand = base.spec((N_CAND_PAD, cfg.embed_dim), jnp.float32)
        return base.CellSpec(ARCH, shape, "retrieval", fn, (q, cand),
                             ((None,), ("candidates", None)))
    raise KeyError(shape)


def _serve(params, batch, cand_emb, *, cfg, k):
    return model.serve_batch(params, cfg, batch, cand_emb, k)


def _retrieve(query, cand_emb, *, k, tile):
    """Speculative top-k over a (possibly device-sharded) corpus.

    Per-shard Spec-QP pruned scoring runs under shard_map with local block
    bounds; a gather+top-k tree merges shard-local top-k's — identical
    two-level structure to the KG engine's distributed rank-join merge.
    """
    if sharding.active():
        mesh = sharding._state.mesh
        axes = tuple(mesh.axis_names)

        def local(q, cand):
            cand = cand.reshape((-1, cand.shape[-1]))
            bounds = kops.block_bounds_cauchy(q, cand, tile)
            s, i, n = kops.topk_score_pruned(q, cand, bounds, k, tile)
            # global candidate ids
            flat = jax.lax.axis_index(axes[0])
            for ax in axes[1:]:
                flat = flat * mesh.shape[ax] + jax.lax.axis_index(ax)
            i = jnp.where(i >= 0, i + flat * cand.shape[0], -1)
            for ax in axes:
                s_all = jax.lax.all_gather(s, ax).reshape(-1)
                i_all = jax.lax.all_gather(i, ax).reshape(-1)
                s, top = jax.lax.top_k(s_all, k)
                i = i_all[top]
                n = jax.lax.psum(n, ax)
            return s, i, n

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(axes, None)),
            out_specs=(P(), P(), P()),
            check_vma=False)(query, cand_emb)

    bounds = kops.block_bounds_cauchy(query, cand_emb, tile)
    return kops.topk_score_pruned(query, cand_emb, bounds, k, tile)


def smoke():
    cfg = smoke_config()
    key = jax.random.PRNGKey(0)
    params, _ = model.init(key, cfg)
    import numpy as np
    rng = np.random.default_rng(0)
    B = 32
    batch = {
        "user_ids": jnp.asarray(
            rng.integers(0, cfg.user_vocab, (B, cfg.user_slots)), jnp.int32),
        "user_w": jnp.ones((B, cfg.user_slots), jnp.float32),
        "user_dense": jnp.asarray(
            rng.standard_normal((B, cfg.n_dense_feat)), jnp.float32),
        "item_ids": jnp.asarray(
            rng.integers(0, cfg.item_vocab, (B, cfg.item_slots)), jnp.int32),
        "item_w": jnp.ones((B, cfg.item_slots), jnp.float32),
        "item_dense": jnp.asarray(
            rng.standard_normal((B, cfg.n_dense_feat)), jnp.float32),
        "item_logq": jnp.zeros((B,), jnp.float32),
    }
    tc = train_loop.TrainConfig(opt=opt_lib.AdamWConfig(lr=1e-3))
    state = train_loop.make_train_state(params, tc)
    step = jax.jit(train_loop.make_train_step(
        lambda p, b: model.loss_fn(p, cfg, b), tc))
    state, metrics = step(state, batch)
    # speculative retrieval exactness on a small corpus
    cand = jnp.asarray(rng.standard_normal((1024, cfg.embed_dim)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((cfg.embed_dim,)), jnp.float32)
    s, i, n = model.score_candidates(params, cfg, q, cand, 8)
    return metrics, (s, i, n)
