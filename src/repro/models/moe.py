"""Mixture-of-Experts with grouped one-hot (GSPMD-style) dispatch.

TPU-native adaptation: instead of gather/scatter (MegaBlocks-style, a GPU
pattern), tokens are routed with capacity-bounded one-hot dispatch/combine
einsums — the XLA partitioner turns the expert-sharded einsums into
all-to-alls on the ``model`` axis. Tokens are split into groups of
``cfg.moe_group_size`` so the dispatch tensor stays
``tokens × (group·k·capacity_factor)`` elements, independent of E.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.module import spec

AUX_LOSS_WEIGHT = 0.01


def moe_spec(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": spec((d, e), ("embed", None), "small", dtype=jnp.float32),
        "wi": spec((e, d, 2, f), ("experts", "embed", None, "mlp")),
        "wo": spec((e, f, d), ("experts", "mlp", "embed")),
    }


def capacity(cfg, group_size: int) -> int:
    c = int(group_size * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4, >= 4


def _constrain(shard, name, x):
    if shard is None:
        return x
    sh = shard(name, x.shape)
    import jax.lax
    return jax.lax.with_sharding_constraint(x, sh) if sh is not None else x


@jax.named_scope("moe")
def apply_moe(p, cfg, x, shard=None):
    """x (B,S,D) -> (y (B,S,D), aux_loss scalar fp32)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    gs = min(cfg.moe_group_size, b * s)
    tokens = b * s
    xt = x.reshape(tokens, d)
    if tokens % gs:  # pad to a group multiple; padded rows are sliced off
        pad = gs - tokens % gs
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    g = xt.shape[0] // gs
    xg = xt.reshape(g, gs, d)

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)  # (G,gs,E) fp32

    top_vals, top_idx = jax.lax.top_k(gates, k)  # (G,gs,k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    c = capacity(cfg, gs)
    eh = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (G,gs,k,E)
    # Priority order: token-major, then choice rank.
    ehf = eh.reshape(g, gs * k, e)
    pos = jnp.cumsum(ehf, axis=1) - ehf  # (G,gs*k,E) slot within expert
    keep = (pos < c).astype(jnp.float32) * ehf
    disp = keep[..., None] * jax.nn.one_hot(pos.astype(jnp.int32), c,
                                            dtype=jnp.float32)  # (G,gs*k,E,C)
    comb = disp * top_vals.reshape(g, gs * k)[..., None, None]
    # Fold the k choices back onto tokens (each (token,expert) pair unique).
    disp4 = disp.reshape(g, gs, k, e, c).sum(axis=2).astype(x.dtype)
    comb4 = comb.reshape(g, gs, k, e, c).sum(axis=2).astype(x.dtype)
    disp4 = _constrain(shard, "moe_disp", disp4)
    comb4 = _constrain(shard, "moe_disp", comb4)

    xe = jnp.einsum("gsec,gsd->gecd", disp4, xg)  # (G,E,C,D) dispatch
    xe = _constrain(shard, "moe_xe", xe)
    hi = jnp.einsum("gecd,ednf->gecnf", xe, p["wi"].astype(x.dtype))
    h = jax.nn.silu(hi[..., 0, :].astype(jnp.float32)).astype(x.dtype) * hi[..., 1, :]
    ye = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(x.dtype))
    ye = _constrain(shard, "moe_xe", ye)
    y = jnp.einsum("gecd,gsec->gsd", ye, comb4)  # combine
    y = y.reshape(-1, d)[:tokens]

    # Load-balance auxiliary loss (Switch-style): E * sum(frac_tokens * frac_prob)
    frac_tokens = jnp.mean(eh.sum(axis=2), axis=(0, 1))  # (E,)
    frac_prob = jnp.mean(gates, axis=(0, 1))  # (E,)
    aux = e * jnp.sum(frac_tokens * frac_prob) * AUX_LOSS_WEIGHT

    return y.reshape(b, s, d), aux


def moe_flops_per_token(cfg) -> int:
    """Forward matmul FLOPs per token (routing + experts at capacity)."""
    d, f, k, cf = cfg.d_model, cfg.d_ff, cfg.top_k, cfg.capacity_factor
    expert = 2 * k * cf * d * 3 * f
    dispatch = 2 * 2 * (cfg.moe_group_size * k * cf) * d
    router = 2 * d * cfg.num_experts
    return int(expert + dispatch + router)
