"""Plain Qwen2 decoder and its training loss (arXiv:2407.10671, HF Qwen2).

Pre-norm decoder layers: RMSNorm, grouped-query causal self-attention
with rotary positions (rotate-half convention) and a bias on q, k and v,
RMSNorm, SwiGLU MLP; a final RMSNorm and the tied embedding as LM head.
The loss is the mean next-token cross-entropy over the published
vocabulary. Everything is fp32; products go through ``dot``. The program's
stored extra vocabulary rows (padding) are sliced off here.

Weights arrive in the program's layout (layers stacked on a leading axis,
``mlp/wi`` holding gate and up as ``[..., 0, :]`` and ``[..., 1, :]``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def loss(params, tokens, labels, cfg, dot):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], d // cfg["num_attention_heads"]
    g, eps, theta = h // kv, cfg["rms_norm_eps"], cfg["rope_theta"]
    table = params["embed"]["table"][: cfg["vocab_size"]]
    b, s = tokens.shape
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p):
        a, m = p["mixer"], p["mlp"]
        y = _rms(x, p["norm1"]["scale"], eps)
        q = (dot("bsd,de->bse", y, a["wq"]) + a["bq"]).reshape(b, s, kv, g, hd)
        k = (dot("bsd,de->bse", y, a["wk"]) + a["bk"]).reshape(b, s, kv, hd)
        v = (dot("bsd,de->bse", y, a["wv"]) + a["bv"]).reshape(b, s, kv, hd)
        q = _rope(q.reshape(b, s, h, hd), pos, theta).reshape(b, s, kv, g, hd)
        k = _rope(k, pos, theta)
        scores = dot("bqkgd,btkd->bkgqt", q, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = dot("bkgqt,btkd->bqkgd", probs, v).reshape(b, s, h * hd)
        x = x + dot("bse,ed->bsd", ctx, a["wo"])
        y = _rms(x, p["norm2"]["scale"], eps)
        gate = dot("bsd,df->bsf", y, m["wi"][:, 0, :])
        up = dot("bsd,df->bsf", y, m["wi"][:, 1, :])
        x = x + dot("bsf,fd->bsd", jax.nn.silu(gate) * up, m["wo"])
        return x, None

    x = table[tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"]["L0"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = dot("bsd,vd->bsv", x, table)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0])
    return jnp.mean(nll)
