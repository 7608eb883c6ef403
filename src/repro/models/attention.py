"""Grouped-query attention: training, prefill, and KV-cache decode paths.

Sharding design (see DESIGN.md §5): projection kernels are stored
*flattened* — ``wq (D, H*hd)``, ``wk/wv (D, KV*hd)``, ``wo (H*hd, D)`` —
because the flattened fan-out is divisible by the 16-way ``model`` axis
for every assigned architecture, while raw head counts (40, 24, 56, 14,
6…) are not, and jit ``in_shardings`` require even division. Activations
are reshaped to (B,S,H,hd) and head-sharded via *constraints*, where GSPMD
tolerates uneven (padded) sharding. GQA K/V are broadcast to the full head
count at compute time (the Megatron convention when tp > kv_heads); the
cache stores only the KV heads.

The full-sequence causal path runs through either the XLA einsum
implementation (default — what the dry-run lowers and ``cost_analysis``
meters) or the Pallas flash-attention kernel
(``set_attention_impl("pallas")``; TPU target, interpret-mode on CPU).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.module import spec

_IMPL = "xla"


def set_attention_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("xla", "pallas", "pallas_interpret"), impl
    _IMPL = impl


def get_attention_impl() -> str:
    return _IMPL


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_spec(cfg):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": spec((d, h * hd), ("embed", "heads_flat")),
        "wk": spec((d, kv * hd), ("embed", "kv_flat")),
        "wv": spec((d, kv * hd), ("embed", "kv_flat")),
        "wo": spec((h * hd, d), ("heads_flat", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = spec((h * hd,), ("heads_flat",), "zeros")
        p["bk"] = spec((kv * hd,), ("kv_flat",), "zeros")
        p["bv"] = spec((kv * hd,), ("kv_flat",), "zeros")
    return p


def _heads(cfg):
    return cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim


def _project_q(p, cfg, x):
    h, _, hd = _heads(cfg)
    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    return q.reshape(x.shape[0], x.shape[1], h, hd)


def _project_kv(p, cfg, src, dtype):
    _, kv, hd = _heads(cfg)
    k = src @ p["wk"].astype(dtype)
    v = src @ p["wv"].astype(dtype)
    if "bk" in p:
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    shp = (src.shape[0], src.shape[1], kv, hd)
    return k.reshape(shp), v.reshape(shp)


def _out_proj(p, ctx, dtype):
    b, s = ctx.shape[:2]
    return ctx.reshape(b, s, -1) @ p["wo"].astype(dtype)


def _expand_kv(cfg, k):
    """(B,S,KV,hd) -> (B,S,H,hd) by broadcasting each KV head over its group."""
    h, kv, hd = _heads(cfg)
    if kv == h:
        return k
    b, s = k.shape[:2]
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, h // kv, hd))
    return k.reshape(b, s, h, hd)


# ---------------------------------------------------------------------------
# Core attention math (XLA path); all tensors (B,S,H,hd) with full heads
# ---------------------------------------------------------------------------


def dot_attention(q, k, v, mask=None, score_shard=None):
    """q (B,Sq,H,hd), k/v (B,Sk,H,hd). mask broadcastable (B,1,Sq,Sk).

    fp32 softmax for fp32 inputs (smoke tests, small models). For bf16
    models the XLA path keeps the S x S tensor in bf16 with max-subtracted
    softmax — halving score-tensor HBM/ICI traffic — mirroring the memory
    profile of the Pallas flash kernel, which instead never materializes
    scores and accumulates in fp32 (exact path on real TPU).
    """
    hd = q.shape[-1]
    stat_dtype = jnp.float32 if q.dtype == jnp.float32 else q.dtype
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=stat_dtype)
    if score_shard is not None:
        scores = jax.lax.with_sharding_constraint(scores, score_shard)
    scores = scores * jnp.asarray(hd ** -0.5, stat_dtype)
    neg = jnp.asarray(-1e30 if stat_dtype == jnp.float32 else -3e38 / 4,
                      stat_dtype)
    if mask is not None:
        scores = jnp.where(mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


# Above this many query tokens the XLA path switches to a q-block scan so
# the S x S score tensor is never materialized (peak: block_q x S per head).
CHUNK_THRESHOLD = 4096
CHUNK_Q = 512


def causal_attention(q, k, v):
    if _IMPL in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q, k, v, causal=True, interpret=(_IMPL == "pallas_interpret"))
    sq, sk = q.shape[1], k.shape[1]
    if sq > CHUNK_THRESHOLD and sq % CHUNK_Q == 0:
        return _chunked_causal_attention(q, k, v, CHUNK_Q)
    mask = (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None])[None, None]
    return dot_attention(q, k, v, mask)


def _chunked_causal_attention(q, k, v, block_q: int):
    b, sq, h, hd = q.shape
    nblk = sq // block_q
    qb = jnp.moveaxis(q.reshape(b, nblk, block_q, h, hd), 1, 0)

    # checkpoint the chunk body: backward recomputes the chunk's scores
    # instead of saving stacked (nblk, B, H, bq, S) probabilities — the
    # same residual policy as the flash-attention kernel.
    @jax.checkpoint
    def blk_fn(i, qi):
        offs = i * block_q
        mask = (jnp.arange(sq)[None, :]
                <= (offs + jnp.arange(block_q))[:, None])[None, None]
        return dot_attention(qi, k, v, mask)

    def blk(carry, inp):
        i, qi = inp
        return carry, blk_fn(i, qi)

    _, ctx = jax.lax.scan(blk, 0, (jnp.arange(nblk), qb))
    return jnp.moveaxis(ctx, 0, 1).reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# Layer-level entry points
# ---------------------------------------------------------------------------


def _constrain(shard, name, x):
    if shard is None:
        return x
    s = shard(name, x.shape)
    return jax.lax.with_sharding_constraint(x, s) if s is not None else x


@jax.named_scope("attention")
def apply_self_attn(p, cfg, x, positions, shard=None, causal=True):
    """Full-sequence self-attention. Returns (y, (k_cache, v_cache))."""
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x, x.dtype)
    q = _apply_rope(cfg, q, positions)
    k = _apply_rope(cfg, k, positions)
    kc, vc = k, v
    q = _constrain(shard, "acts_qkv", q)
    kf = _expand_kv(cfg, k)
    vf = _expand_kv(cfg, v)
    sq = q.shape[1]
    if sq > CHUNK_THRESHOLD and sq % CHUNK_Q == 0 and shard is not None:
        # hoist K/V to a replicated-over-model layout BEFORE the q-chunk
        # scan: one all-gather per layer instead of one per chunk
        kf = _constrain(shard, "acts_kv_repl", kf)
        vf = _constrain(shard, "acts_kv_repl", vf)
    else:
        kf = _constrain(shard, "acts_qkv", kf)
        vf = _constrain(shard, "acts_qkv", vf)
    if causal:
        ctx = causal_attention(q, kf, vf)
    else:
        ctx = dot_attention(q, kf, vf)
    ctx = _constrain(shard, "acts_qkv", ctx)
    return _out_proj(p, ctx, x.dtype), (kc, vc)


@jax.named_scope("attention")
def apply_cross_attn(p, cfg, x, memory, shard=None):
    """Cross-attention to (B,M,D) memory (no mask, no RoPE)."""
    q = _constrain(shard, "acts_qkv", _project_q(p, cfg, x))
    k, v = _project_kv(p, cfg, memory, x.dtype)
    ctx = dot_attention(q, _expand_kv(cfg, k), _expand_kv(cfg, v))
    return _out_proj(p, ctx, x.dtype), (k, v)


@jax.named_scope("attention")
def decode_self_attn(p, cfg, x_t, cache, pos, shard=None):
    """One-token decode. x_t (B,1,D); cache {"k","v"} (B,Smax,KV,hd);
    pos (B,) positions (attention masks per-request).

    The cache WRITE is a masked elementwise select at the
    batch-synchronized step offset ``pos[0]`` — it stays fully local on a
    seq-sharded cache, whereas a scatter (or a DUS at a dynamic offset)
    makes GSPMD regather the whole cache per layer. Ragged per-request
    positions are the engine's job (slot-aligned continuous batching);
    attention masking stays per-request via ``pos``.
    """
    q = _project_q(p, cfg, x_t)
    k_t, v_t = _project_kv(p, cfg, x_t, x_t.dtype)
    q = _apply_rope(cfg, q, pos[:, None])
    k_t = _apply_rope(cfg, k_t, pos[:, None])
    sel = (jnp.arange(cache["k"].shape[1]) == pos[0])[None, :, None, None]
    k = jnp.where(sel, k_t.astype(cache["k"].dtype)[:, :1], cache["k"])
    v = jnp.where(sel, v_t.astype(cache["v"].dtype)[:, :1], cache["v"])
    smax = k.shape[1]
    mask = (jnp.arange(smax)[None, :] <= pos[:, None])[:, None, None, None, :]
    # Grouped-query decode: the cache is NOT expanded to full heads (a 5x
    # traffic multiplier for 40q/8kv heads); scores stay sharded over the
    # cache-seq axis (flash-decoding split) — otherwise GSPMD gathers the
    # whole cache per layer.
    b = q.shape[0]
    h, kv, hd = _heads(cfg)
    g = h // kv
    q5 = q.reshape(b, 1, kv, g, hd)
    kq = k.astype(q.dtype)
    vq = v.astype(q.dtype)
    stat = jnp.float32 if q.dtype == jnp.float32 else q.dtype
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q5, kq,
                        preferred_element_type=stat)
    if shard is not None:
        ss = shard("decode_scores5", scores.shape)
        if ss is not None:
            scores = jax.lax.with_sharding_constraint(scores, ss)
    scores = scores * jnp.asarray(hd ** -0.5, stat)
    neg = jnp.asarray(-1e30 if stat == jnp.float32 else -3e38 / 4, stat)
    scores = jnp.where(mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs, vq)
    ctx = ctx.reshape(b, 1, h, hd)
    return _out_proj(p, ctx, x_t.dtype), {"k": k, "v": v}


@jax.named_scope("attention")
def decode_cross_attn(p, cfg, x_t, cache):
    q = _project_q(p, cfg, x_t)
    ctx = dot_attention(q, _expand_kv(cfg, cache["k"].astype(q.dtype)),
                        _expand_kv(cfg, cache["v"].astype(q.dtype)))
    return _out_proj(p, ctx, x_t.dtype), cache


def _apply_rope(cfg, x, positions):
    from repro.models.layers import apply_rope
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_style)


def kv_cache_shape(cfg, batch: int, seq: int):
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {"k": (batch, seq, kv, hd), "v": (batch, seq, kv, hd)}
