"""Grouped-query attention: training, prefill, and KV-cache decode paths.

Sharding design (see DESIGN.md §5): projection kernels are stored
*flattened* — ``wq (D, H*hd)``, ``wk/wv (D, KV*hd)``, ``wo (H*hd, D)`` —
because the flattened fan-out is divisible by the 16-way ``model`` axis
for every assigned architecture, while raw head counts (40, 24, 56, 14,
6…) are not, and jit ``in_shardings`` require even division. Activations
are reshaped to (B,S,H,hd) and head-sharded via *constraints*, where GSPMD
tolerates uneven (padded) sharding. On the XLA path GQA K/V are broadcast
to the full head count at compute time (the Megatron convention when
tp > kv_heads); the kernel and the cache take only the KV heads.

The full-sequence causal path runs through either the XLA einsum
implementation or the Pallas flash-attention kernel, which has its own
backward pass. ``causal_attention`` chooses from what it can observe
(``_flash_blocks``): the kernel on a TPU, unsharded, for square attention
of a head width the kernel supports, from ``FLASH_MIN_SEQ`` tokens; XLA
otherwise, so CPU runs keep the einsum path. ``attention_impl("xla")``
(or ``"pallas"``, ``"pallas_interpret"`` for tests) pins the choice while
a function is traced.

Latent attention (MLA, DeepSeek-V2/V3) is chosen by ``cfg.kv_lora_rank``.
Training and prefill use its expanded form: q and k heads of
qk_nope + qk_rope, where the rope part of k is one head that all heads
share, and v heads of ``v_head_dim``; they take the same causal path (and
the kernel) as GQA, and the cache holds the expanded per-head k and v.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.module import spec
from repro.obs import events
from repro.obs.metrics import MetricsRegistry

_IMPL = "auto"
IMPLS = ("auto", "xla", "pallas", "pallas_interpret")
# The shortest sequence at which the kernel made the whole train step
# faster on a TPU v5e (PERF.md, timing table)
FLASH_MIN_SEQ = 1024
# traces of the causal path by implementation (``flash``, ``xla``)
TRACES = MetricsRegistry(namespace="attention")


def set_attention_impl(impl: str) -> None:
    global _IMPL
    if impl not in IMPLS:
        raise ValueError(f"attention implementation {impl!r} not in {IMPLS}")
    _IMPL = impl


def get_attention_impl() -> str:
    return _IMPL


@contextlib.contextmanager
def attention_impl(impl: str):
    """Pin the implementation inside the block: of causal attention here
    and of the held experts' grouped matmul (``moe._grouped``), the one
    pin of the model's kernel paths. JAX caches a trace by function, so
    trace a function made inside the block."""
    was = _IMPL
    set_attention_impl(impl)
    try:
        yield
    finally:
        set_attention_impl(was)


def attention_key() -> str:
    """Which path full-sequence causal attention takes, as a cache of
    profiles keys it: ``xla``, ``flash>=<FLASH_MIN_SEQ>`` or the pinned
    kernel setting."""
    if _IMPL == "auto":
        return (f"flash>={FLASH_MIN_SEQ}" if jax.default_backend() == "tpu"
                else "xla")
    return _IMPL


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_spec(cfg):
    if cfg.kv_lora_rank:
        return _mla_spec(cfg)
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


def _mla_spec(cfg):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": spec((d, h * (dn + dr)), ("embed", "heads_flat")),
        "wkv_a": spec((d, r + dr), ("embed", None)),      # latent + rope key
        "kv_norm": L.norm_spec(cfg, r),
        "wkv_b": spec((r, h * (dn + dv)), (None, "heads_flat")),
        "wo": spec((h * dv, d), ("heads_flat", "embed")),
    }


def _heads(cfg):
    return cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim


def _qkv(p, cfg, x):
    """Self-attention's q (B,S,H,d_qk), k (B,S,KV,d_qk), v (B,S,KV,d_v),
    before RoPE."""
    if cfg.kv_lora_rank:
        return _mla_qkv(p, cfg, x)
    return (_project_q(p, cfg, x), *_project_kv(p, cfg, x, x.dtype))


def _mla_qkv(p, cfg, x):
    """MLA expanded: q = x W_q; [c_kv, k_pe] = x W_kva; [k_nope, v] =
    rmsnorm(c_kv) W_kvb per head; k = [k_nope, k_pe], k_pe broadcast over
    the heads."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, dn + dr)
    ckv = x @ p["wkv_a"].astype(x.dtype)
    c = L.apply_norm(p["kv_norm"], ckv[..., :r], cfg.norm_eps)
    kv = (c @ p["wkv_b"].astype(x.dtype)).reshape(b, s, h, -1)
    k_pe = jnp.broadcast_to(ckv[..., None, r:], (b, s, h, dr))
    return q, jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]


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
    h, kv = cfg.num_heads, cfg.num_kv_heads
    if kv == h:
        return k
    b, s, hd = k.shape[0], k.shape[1], k.shape[-1]
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


def _xla_kv(cfg, q, k, v, shard):
    """K/V expanded to q's heads and laid out for the XLA path."""
    sq = q.shape[1]
    k, v = _expand_kv(cfg, k), _expand_kv(cfg, v)
    if sq > CHUNK_THRESHOLD and sq % CHUNK_Q == 0 and shard is not None:
        # hoist K/V to a replicated-over-model layout BEFORE the q-chunk
        # scan: one all-gather per layer instead of one per chunk
        return (_constrain(shard, "acts_kv_repl", k),
                _constrain(shard, "acts_kv_repl", v))
    return _constrain(shard, "acts_qkv", k), _constrain(shard, "acts_qkv", v)


def _flash_blocks(q, k, shard):
    """The kernel's (block_q, block_k) if this call takes it, else None."""
    if _IMPL == "xla":
        return None
    from repro.kernels import ops as kops
    sq, sk, hd = q.shape[1], k.shape[1], q.shape[-1]
    if _IMPL == "auto" and (
            jax.default_backend() != "tpu" or shard is not None or sq != sk
            or sq < FLASH_MIN_SEQ or sq % kops.LANES
            or hd not in kops.FLASH_HEAD_DIMS):
        return None
    # a pinned kernel takes every shape, and ops refuses one it cannot tile
    return kops.flash_blocks(sq, hd)


def causal_attention(cfg, q, k, v, positions, shard=None):
    """q (B,S,H,hd), k/v (B,S,KV,hd) with KV dividing H, before RoPE ->
    the context (B,S,H,hd) and the rotated k (the cache's)."""
    blocks = _flash_blocks(q, k, shard)
    impl = "xla" if blocks is None else "flash"
    TRACES.counter(impl, f"causal attention traced on the {impl} path").inc()
    events.emit("attention_impl", impl=impl, seq=q.shape[1],
                head_dim=q.shape[-1], blocks=blocks)
    if blocks is not None:
        from repro.kernels import ops as kops
        heads = lambda t: jnp.swapaxes(t, 1, 2)
        # heads lead before RoPE, so that XLA writes the rotated q and k in
        # the kernel's layout in one fusion (after it, the rotation's
        # halves are copied to that layout one by one)
        rope = lambda t: _apply_rope(cfg, heads(t)[..., None, :],
                                     positions[..., None, :])[..., 0, :]
        qh, kh = rope(q), rope(k)
        with jax.named_scope("flash"):
            ctx = kops.flash_attention_bhsd(
                qh, kh, heads(v), causal=True, blocks=blocks,
                interpret=(_IMPL == "pallas_interpret"))
        return heads(ctx), heads(kh)
    q = _constrain(shard, "acts_qkv", _apply_rope(cfg, q, positions))
    k = _apply_rope(cfg, k, positions)
    kf, vf = _xla_kv(cfg, q, k, v, shard)
    sq, sk = q.shape[1], k.shape[1]
    if sq > CHUNK_THRESHOLD and sq % CHUNK_Q == 0:
        return _chunked_causal_attention(q, kf, vf, CHUNK_Q), k
    mask = (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None])[None, None]
    return dot_attention(q, kf, vf, mask), k


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
    return jnp.moveaxis(ctx, 0, 1).reshape(b, sq, h, v.shape[-1])


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
    q, k, v = _qkv(p, cfg, x)
    if causal:
        ctx, k = causal_attention(cfg, q, k, v, positions, shard)
    else:
        q = _constrain(shard, "acts_qkv", _apply_rope(cfg, q, positions))
        k = _apply_rope(cfg, k, positions)
        ctx = dot_attention(q, *_xla_kv(cfg, q, k, v, shard))
    ctx = _constrain(shard, "acts_qkv", ctx)
    return _out_proj(p, ctx, x.dtype), (k, v)


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
    q, k_t, v_t = _qkv(p, cfg, x_t)
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
    b, hd = q.shape[0], q.shape[-1]
    h, kv = cfg.num_heads, cfg.num_kv_heads
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
    return _out_proj(p, ctx, x_t.dtype), {"k": k, "v": v}


@jax.named_scope("attention")
def decode_cross_attn(p, cfg, x_t, cache):
    q = _project_q(p, cfg, x_t)
    ctx = dot_attention(q, _expand_kv(cfg, cache["k"].astype(q.dtype)),
                        _expand_kv(cfg, cache["v"].astype(q.dtype)))
    return _out_proj(p, ctx, x_t.dtype), cache


def _apply_rope(cfg, x, positions):
    if cfg.qk_rope_head_dim:  # MLA: RoPE on the trailing rope dims alone
        n = x.shape[-1] - cfg.qk_rope_head_dim
        return jnp.concatenate(
            [x[..., :n], L.apply_rope(x[..., n:], positions, cfg.rope_theta)],
            axis=-1)
    return L.apply_rope(x, positions, cfg.rope_theta, cfg.rope_style)


def kv_cache_shape(cfg, batch: int, seq: int):
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.kv_lora_rank:
        dk, dv = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
        return {"k": (batch, seq, kv, dk), "v": (batch, seq, kv, dv)}
    return {"k": (batch, seq, kv, hd), "v": (batch, seq, kv, hd)}
