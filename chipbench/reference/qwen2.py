"""Plain Qwen2 decoder and its training loss (arXiv:2407.10671, HF Qwen2).

Pre-norm decoder layers: RMSNorm, grouped-query causal self-attention
with rotary positions (rotate-half convention) and a bias on q, k and v,
RMSNorm, SwiGLU MLP; a final RMSNorm and the tied embedding as LM head.
The loss is the mean next-token cross-entropy over the published
vocabulary. Everything is fp32; products go through ``dot``. The program's
stored extra vocabulary rows (padding) are sliced off here.

Weights arrive in the program's layout (layers stacked on a leading axis,
``mlp/wi`` holding gate and up as ``[..., 0, :]`` and ``[..., 1, :]``),
which ``leaf_specs`` states for ``weights.py``. ``param_count`` and
``mixer_flops_per_token`` are the architecture's model FLOPs for
``flops.py``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import weights as W


def leaf_specs(cfg: Dict[str, Any]) -> W.Spec:
    """(shape, dtype, init) per leaf path, as the program stores the weights."""
    dt = W.DTYPES[cfg["torch_dtype"]]
    f32 = jnp.float32
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, n = d // h, cfg["num_hidden_layers"]
    w = ("normal", 0.02)
    lay = "layers/L0/"
    return {
        "embed/table": ((W.padded_vocab(cfg["vocab_size"]), d), dt, w),
        "final_norm/scale": ((d,), f32, ("const", 1.0)),
        lay + "norm1/scale": ((n, d), f32, ("const", 1.0)),
        lay + "norm2/scale": ((n, d), f32, ("const", 1.0)),
        lay + "mixer/wq": ((n, d, h * hd), dt, w),
        lay + "mixer/wk": ((n, d, kv * hd), dt, w),
        lay + "mixer/wv": ((n, d, kv * hd), dt, w),
        lay + "mixer/wo": ((n, h * hd, d), dt, w),
        lay + "mixer/bq": ((n, h * hd), dt, w),
        lay + "mixer/bk": ((n, kv * hd), dt, w),
        lay + "mixer/bv": ((n, kv * hd), dt, w),
        lay + "mlp/wi": ((n, d, 2, f), dt, w),
        lay + "mlp/wo": ((n, f, d), dt, w),
    }


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters, each counted once: the tied table once, as the LM
    head's matrix (its lookup is a gather), norm scales and biases like
    weights, the published vocabulary (not the stored padding)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d + (h + 2 * kv) * hd
    mlp = 3 * d * f
    layer = attn + mlp + 2 * d
    head = v * d * (1 if cfg["tie_word_embeddings"] else 2)
    return cfg["num_hidden_layers"] * layer + head + d


def mixer_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Causal attention's forward FLOPs per token: the Q.K and P.V
    products take ``2 * 2 * heads * head_dim`` per (query, key) pair, and
    a query at position i sees i + 1 keys, (S + 1) / 2 on average."""
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    return 4.0 * cfg["num_hidden_layers"] * h * hd * (seq + 1) / 2


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
