"""Plain DeepSeek-V3 decoder (Moonlight-16B-A3B) and its training loss.

The block as HF's ``deepseek_v3`` configuration states it, at one chip's
share of a layer divided by expert parallelism:

- latent attention (MLA) without a q LoRA: ``q = x W_q`` gives heads of
  qk_nope + qk_rope; ``[c_kv, k_pe] = x W_kva``, c_kv RMS-normed and
  brought up by ``W_kvb`` to per-head k_nope and v; k_pe is one rope key
  that every head shares; RoPE (``rope_theta``) on the rope part of q and
  k alone; softmax scale 1/sqrt(qk_nope + qk_rope) (no ``rope_scaling``,
  so no yarn mscale); no biases. Attention runs in blocks of at most
  ``Q_BLOCK`` query rows, each with an exact softmax over all the keys it
  sees;
- the first ``first_k_dense_replace`` layers with a dense SwiGLU of
  ``intermediate_size``;
- every later layer an expert layer: fp32 sigmoid scores over all the
  router's experts (``router_experts``); the top ``num_experts_per_tok`` of
  score + ``e_score_correction_bias`` (``noaux_tc``, one group); the chosen
  scores without the bias, normalised to sum 1 (``norm_topk_prob``) and
  scaled by ``routed_scaling_factor``. The ``n_routed_experts`` experts held
  here (the first ones) are computed densely over every token, each
  weighted by its gate (0 where a token did not choose it); the experts
  held elsewhere are left out, as the program leaves them. The shared
  SwiGLU of ``n_shared_experts * moe_intermediate_size`` adds its part
  once;
- a final RMSNorm and an untied LM head over the vocabulary held here; the
  loss is the mean next-token cross-entropy over it.

Memory: the MLPs, the expert layer and the LM head with its loss run over
blocks of ``ROW_BLOCK`` token rows, and each attention block and row
block is recomputed in the backward pass, so that the activations of
8192 tokens fit beside the five fp32 copies of the weights that
``train.py`` holds.

Everything is fp32; products go through ``dot``. Departures from HF's
``modeling_deepseek``: RoPE in rotate-half form on the rope dims as they
are stored, where HF first permutes interleaved pairs (a fixed relabelling
of random weights); no auxiliary loss (``seq_aux`` names none with a
weight). The selection bias is a weight that no gradient reaches.

Weights arrive in the program's layout (layers stacked on a leading axis,
the leading dense layers under ``dense_layers``, ``wi`` holding gate and
up as ``[..., 0, :]`` and ``[..., 1, :]``), which ``leaf_specs`` states.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import weights as W

Q_BLOCK = 512  # query rows of one attention block
ROW_BLOCK = 2048  # token rows of one block of per-token work


def router_experts(cfg: Dict[str, Any]) -> int:
    """The router's width: the published expert count where the file
    holds one chip's share of the experts."""
    cut = cfg.get("reduced") or {}
    return cut["n_routed_experts"]["published"] if "n_routed_experts" in cut \
        else cfg["n_routed_experts"]


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def leaf_specs(cfg: Dict[str, Any]) -> W.Spec:
    """(shape, dtype, init) per leaf path, as the program stores the weights."""
    dt = W.DTYPES[cfg["torch_dtype"]]
    f32 = jnp.float32
    d, h, r, dn, dr, dv = _sizes(cfg)
    w, one = ("normal", 0.02), ("const", 1.0)
    lead = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - lead
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    v = W.padded_vocab(cfg["vocab_size"])

    def layer(prefix, n):
        return {
            prefix + "norm1/scale": ((n, d), f32, one),
            prefix + "norm2/scale": ((n, d), f32, one),
            prefix + "mixer/wq": ((n, d, h * (dn + dr)), dt, w),
            prefix + "mixer/wkv_a": ((n, d, r + dr), dt, w),
            prefix + "mixer/kv_norm/scale": ((n, r), f32, one),
            prefix + "mixer/wkv_b": ((n, r, h * (dn + dv)), dt, w),
            prefix + "mixer/wo": ((n, h * dv, d), dt, w),
        }

    specs = {
        "embed/table": ((v, d), dt, w),
        "embed/unembed": ((d, v), dt, w),
        "final_norm/scale": ((d,), f32, one),
        **layer("layers/L0/", n),
        "layers/L0/mlp/router": ((n, d, router_experts(cfg)), f32, w),
        "layers/L0/mlp/router_bias": ((n, router_experts(cfg)), f32, ("const", 0.0)),
        "layers/L0/mlp/wi": ((n, held, d, 2, f), dt, w),
        "layers/L0/mlp/wo": ((n, held, f, d), dt, w),
        "layers/L0/mlp/shared/wi": ((n, d, 2, fs), dt, w),
        "layers/L0/mlp/shared/wo": ((n, fs, d), dt, w),
    }
    if lead:
        specs.update(layer("dense_layers/L0/", lead))
        specs["dense_layers/L0/mlp/wi"] = ((lead, d, 2, cfg["intermediate_size"]), dt, w)
        specs["dense_layers/L0/mlp/wo"] = ((lead, cfg["intermediate_size"], d), dt, w)
    return specs


def param_count(cfg: Dict[str, Any]) -> int:
    """The parameters one token's work uses at this chip's share, for
    ``flops.py``. Everything outside the routed experts counts once (norm
    scales and the selection bias like weights); the input table does not
    (its lookup is a gather); the routed experts count as
    ``num_experts_per_tok * n_routed_experts / router_experts`` of one
    expert per expert layer (0.75 at 8 of 64 held, top 6): a token's
    expected share of the experts held here."""
    d, h, r, dn, dr, dv = _sizes(cfg)
    attn = d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv) + h * dv * d
    f, e = cfg["moe_intermediate_size"], router_experts(cfg)
    lead = cfg["first_k_dense_replace"]
    dense = attn + 2 * d + 3 * d * cfg["intermediate_size"]
    expert = (attn + 2 * d + d * e + e + 3 * d * cfg["n_shared_experts"] * f
              + 3 * d * f * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / e)
    n = cfg["num_hidden_layers"] - lead
    return round(lead * dense + n * expert + cfg["vocab_size"] * d + d)


def mixer_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Causal MLA's forward FLOPs per token beyond its projections: Q.K over
    qk_nope + qk_rope and P.V over v_head_dim, ``2 * heads * width`` per
    (query, key) pair, and a query at position i sees i + 1 keys, (S + 1) / 2
    on average."""
    _, h, _, dn, dr, dv = _sizes(cfg)
    return 2.0 * cfg["num_hidden_layers"] * h * (dn + dr + dv) * (seq + 1) / 2


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _by_rows(fn, *xs):
    """``fn`` over blocks of ROW_BLOCK rows of the (T, ...) arrays ``xs``,
    each block recomputed in the backward pass."""
    t = xs[0].shape[0]
    blk = min(t, ROW_BLOCK)
    out = jax.lax.map(lambda b: jax.checkpoint(fn)(*b),
                      tuple(x.reshape(t // blk, blk, *x.shape[1:]) for x in xs))
    return out.reshape(t, *out.shape[2:])


def _swiglu(x, wi, wo, dot):
    gate = dot("td,df->tf", x, wi[:, 0, :])
    up = dot("td,df->tf", x, wi[:, 1, :])
    return dot("tf,fd->td", jax.nn.silu(gate) * up, wo)


def _attention(q, k, v, dot):
    """Causal softmax attention, in blocks of at most Q_BLOCK query rows."""
    b, s, h, dqk = q.shape
    blk = min(s, Q_BLOCK)
    nblk = s // blk
    qb = jnp.moveaxis(q.reshape(b, nblk, blk, h, dqk), 1, 0)

    @jax.checkpoint
    def one(args):
        i, qi = args
        scores = dot("bqhd,bkhd->bhqk", qi, k) / jnp.sqrt(jnp.float32(dqk))
        visible = jnp.arange(s)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return dot("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(one, (jnp.arange(nblk), qb))
    return jnp.moveaxis(ctx, 0, 1).reshape(b, s, h * v.shape[-1])


def _mla(x, a, cfg, dot):
    d, h, r, dn, dr, dv = _sizes(cfg)
    b, s, _ = x.shape
    pos = jnp.arange(s)
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    q = dot("bsd,de->bse", x, a["wq"]).reshape(b, s, h, dn + dr)
    ckv = dot("bsd,de->bse", x, a["wkv_a"])
    c = _rms(ckv[..., :r], a["kv_norm"]["scale"], eps)
    kv = dot("bsr,re->bse", c, a["wkv_b"]).reshape(b, s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, theta)], axis=-1)
    k_pe = jnp.broadcast_to(_rope(ckv[..., None, r:], pos, theta), (b, s, h, dr))
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    return dot("bse,ed->bsd", _attention(q, k, kv[..., dn:], dot), a["wo"])


def _experts(t, m, cfg, dot):
    """The expert layer on token rows t (T, D)."""
    scores = jax.nn.sigmoid(dot("td,de->te", t, m["router"]))
    bias = jax.lax.stop_gradient(m["router_bias"])
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    held = m["wi"].shape[0]
    gate = jnp.sum(w[..., None] * (idx[..., None] == jnp.arange(held)), axis=1)
    g = dot("td,edf->tef", t, m["wi"][:, :, 0, :])
    u = dot("td,edf->tef", t, m["wi"][:, :, 1, :])
    routed = dot("tef,efd->td", jax.nn.silu(g) * u * gate[..., None], m["wo"])
    return routed + _swiglu(t, m["shared"]["wi"], m["shared"]["wo"], dot)


def loss(params, tokens, labels, cfg, dot):
    eps = cfg["rms_norm_eps"]

    def layer(mlp):
        def run(x, p):
            x = x + _mla(_rms(x, p["norm1"]["scale"], eps), p["mixer"], cfg, dot)
            m = p["mlp"]
            if mlp == "dense":
                block = lambda t: _swiglu(t, m["wi"], m["wo"], dot)
            else:
                block = lambda t: _experts(t, m, cfg, dot)
            b, s, d = x.shape
            y = _rms(x, p["norm2"]["scale"], eps).reshape(b * s, d)
            return x + _by_rows(block, y).reshape(b, s, d), None
        return jax.checkpoint(run)

    x = params["embed"]["table"][tokens]
    if "dense_layers" in params:
        x, _ = jax.lax.scan(layer("dense"), x, params["dense_layers"]["L0"])
    x, _ = jax.lax.scan(layer("experts"), x, params["layers"]["L0"])
    x = _rms(x, params["final_norm"]["scale"], eps)

    def nll(t, gold):
        logits = dot("td,dv->tv", t, params["embed"]["unembed"])[:, : cfg["vocab_size"]]
        return (jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, gold[:, None], axis=-1)[:, 0])

    return jnp.mean(_by_rows(nll, x.reshape(-1, x.shape[-1]), labels.reshape(-1)))
