"""Shared neural-net layers: norms, RoPE, gated MLP, embeddings.

All apply functions take the params subtree produced from the matching
``*_spec`` function. Compute runs in the activation dtype; reductions that
need it (norm statistics, softmax, loss) run in fp32. Each layer runs
under a ``jax.named_scope`` (``norm``, ``mlp``, ``embed``, ``lm_head``;
``attention``, ``moe`` and ``ssm`` in their modules), which names its ops
in a profiler trace and changes nothing else.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.module import spec

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_spec(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": spec((d,), ("embed",), "ones", dtype=jnp.float32),
                "bias": spec((d,), ("embed",), "zeros", dtype=jnp.float32)}
    return {"scale": spec((d,), ("embed",), "ones", dtype=jnp.float32)}


@jax.named_scope("norm")
def apply_norm(p, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if "bias" in p:  # LayerNorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"] + p["bias"]
    else:  # RMSNorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float, rotary_dims: Optional[int] = None):
    rd = rotary_dims or head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    return inv  # (rd//2,)


def apply_rope(x, positions, theta: float, style: str = "full"):
    """x: (..., S, H, D). positions: broadcastable to (..., S) int32.

    style "full": rotate all D dims (Llama / Qwen / Phi).
    style "2d":   ChatGLM partial rotary — rotate only the first half of the
                  head dims, pass the second half through (the "2d" RoPE of
                  GLM applies position to half the channels).
    """
    d = x.shape[-1]
    rd = d // 2 if style == "2d" else d
    inv = rope_frequencies(d, theta, rd)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, rd//2)
    sin = jnp.sin(ang)[..., None, :]  # (..., S, 1, rd//2) broadcast over heads
    cos = jnp.cos(ang)[..., None, :]
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = jnp.concatenate([rot, x[..., rd:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# ---------------------------------------------------------------------------


def mlp_spec(cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": spec((d, 2, f), ("embed", None, "mlp")),  # fused gate+up
        "wo": spec((f, d), ("mlp", "embed")),
    }


def swiglu(p, x):
    """The gated MLP's math, in no scope of its own (the MoE layer's shared
    experts run it under ``shared``)."""
    gu = jnp.einsum("...d,dgf->...gf", x, p["wi"].astype(x.dtype))
    g, u = gu[..., 0, :], gu[..., 1, :]
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(x.dtype))


apply_mlp = jax.named_scope("mlp")(swiglu)


# ---------------------------------------------------------------------------
# Embeddings / unembedding
# ---------------------------------------------------------------------------


def padded_vocab(v: int) -> int:
    """Vocab rows padded to a multiple of 256 so the table shards evenly on
    any mesh axis (51865, 50280, ... are not 16-divisible)."""
    return -(-v // 256) * 256


def embed_spec(cfg):
    v, d = padded_vocab(cfg.vocab_size), cfg.d_model
    if cfg.tie_embeddings:
        # One table, vocab-sharded: output projection is comm-free; the input
        # gather pays a (B,S,D) all-reduce over the model axis (see DESIGN.md).
        return {"table": spec((v, d), ("vocab", "embed"), "embed", scale=0.02)}
    return {
        # Input table embed-sharded: gather is comm-free, one AG to full D.
        "table": spec((v, d), (None, "mlp"), "embed", scale=0.02),
        "unembed": spec((d, v), ("embed", "vocab"), "normal"),
    }


@jax.named_scope("embed")
def embed_tokens(p, tokens, dtype):
    return jnp.take(p["table"], tokens, axis=0).astype(dtype)


@jax.named_scope("lm_head")
def unembed(p, x, softcap: float = 0.0, vocab: int = 0):
    w = p["table"].T if "unembed" not in p else p["unembed"]
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype)).astype(jnp.float32)
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    if vocab and vocab < logits.shape[-1]:
        # mask padded vocab columns out of the softmax
        pad_mask = jnp.arange(logits.shape[-1]) < vocab
        logits = jnp.where(pad_mask, logits, jnp.float32(-1e30))
    return logits


@jax.named_scope("lm_head")
def cross_entropy(logits, labels, mask=None):
    """Mean token cross-entropy in fp32. logits (..., V), labels (...) int."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
