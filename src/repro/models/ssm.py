"""Mamba2 (SSD — state-space duality) blocks: chunked train path + decode.

The chunked SSD algorithm (arXiv:2405.21060) splits the sequence into
chunks of length Q: a quadratic attention-like intra-chunk term plus a
sequential inter-chunk state recurrence of length L/Q. The pure-jnp path
below is the reference; ``repro.kernels.ssd_scan`` provides the Pallas TPU
kernel for the intra-chunk term.

Projections are stored as separate tensors per semantic chunk (z, x, B, C,
dt) so each can carry its own logical sharding axis (d_inner -> ``mlp`` on
the model axis, state dims replicated).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.module import spec

_IMPL = "xla"


def set_ssd_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("xla", "pallas", "pallas_interpret"), impl
    _IMPL = impl


def ssm_spec(cfg):
    d, di, n, h, w = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                      cfg.ssm_heads, cfg.ssm_conv)
    return {
        "in_z": spec((d, di), ("embed", "mlp")),
        "in_x": spec((d, di), ("embed", "mlp")),
        "in_b": spec((d, n), ("embed", None)),
        "in_c": spec((d, n), ("embed", None)),
        "in_dt": spec((d, h), ("embed", "heads")),
        "conv_x": spec((w, di), (None, "mlp"), scale=0.5),
        "conv_b": spec((w, n), (None, None), scale=0.5),
        "conv_c": spec((w, n), (None, None), scale=0.5),
        "conv_bias_x": spec((di,), ("mlp",), "zeros"),
        "conv_bias_b": spec((n,), (None,), "zeros"),
        "conv_bias_c": spec((n,), (None,), "zeros"),
        "a_log": spec((h,), ("heads",), "zeros", dtype=jnp.float32),
        "d_skip": spec((h,), ("heads",), "ones", dtype=jnp.float32),
        "dt_bias": spec((h,), ("heads",), "zeros", dtype=jnp.float32),
        "norm_scale": spec((di,), ("mlp",), "ones", dtype=jnp.float32),
        "out": spec((di, d), ("mlp", "embed")),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv. x (B,L,C), w (W,C), b (C,)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):  # W is 4: unrolled adds, no conv primitive needed
        out = out + xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype)
    return out + b.astype(x.dtype)


def _conv_step(buf, x_t, w, b):
    """Single-token causal conv. buf (B,W-1,C) past inputs; x_t (B,C)."""
    window = jnp.concatenate([buf, x_t[:, None, :]], axis=1)  # (B,W,C)
    y = jnp.einsum("bwc,wc->bc", window, w.astype(x_t.dtype)) + b.astype(x_t.dtype)
    return y, window[:, 1:, :]


def ssd_chunked(xb, dt, a_neg, bmat, cmat, chunk: int):
    """Chunked SSD scan (fp32 decay math).

    xb (B,L,H,P) pre-scaled inputs (x*dt); dt (B,L,H); a_neg (H,) negative;
    bmat/cmat (B,L,N). Returns y (B,L,H,P), final state (B,H,N,P) fp32.
    """
    if _IMPL in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return kops.ssd_scan(xb, dt, a_neg, bmat, cmat, chunk,
                             interpret=(_IMPL == "pallas_interpret"))
    return ssd_chunked_ref(xb, dt, a_neg, bmat, cmat, chunk)


def ssd_chunked_ref(xb, dt, a_neg, bmat, cmat, chunk: int):
    b, l, h, p = xb.shape
    n = bmat.shape[-1]
    q = min(chunk, l)
    if l % q:
        # pad to a chunk multiple: x=0 contributes nothing to outputs or
        # state, dt=0 makes the padded decay exactly 1 (state preserved)
        pad = q - l % q
        y, s = ssd_chunked_ref(
            jnp.pad(xb, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(dt, ((0, 0), (0, pad), (0, 0))),
            a_neg,
            jnp.pad(bmat, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(cmat, ((0, 0), (0, pad), (0, 0))), chunk)
        return y[:, :l], s
    nc = l // q
    dtype = xb.dtype

    loga = (dt.astype(jnp.float32) * a_neg).reshape(b, nc, q, h)  # <= 0
    xc = xb.reshape(b, nc, q, h, p)
    bc = bmat.reshape(b, nc, q, n).astype(jnp.float32)
    cc = cmat.reshape(b, nc, q, n).astype(jnp.float32)

    cum = jnp.cumsum(loga, axis=2)  # (B,C,Q,H) inclusive
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,C,Q,Q,H) t,s
    causal = jnp.tril(jnp.ones((q, q), jnp.bool_))
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)

    # Intra-chunk (quadratic) term.
    cb = jnp.einsum("bctn,bcsn->bcts", cc, bc)
    y_intra = jnp.einsum("bcts,bctsh,bcshp->bcthp",
                         cb, decay, xc.astype(jnp.float32))

    # Per-chunk contribution to the carried state.
    w_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,C,Q,H) decay to chunk end
    s_chunk = jnp.einsum("bcsh,bcsn,bcshp->bchnp",
                         w_end, bc, xc.astype(jnp.float32))
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,C,H) total chunk decay

    def step(s, inp):
        s_c, dec = inp  # (B,H,N,P), (B,H)
        s_new = s * dec[..., None, None] + s_c
        return s_new, s

    s0 = jnp.zeros((b, h, n, p), jnp.float32)
    s_final, s_prev = jax.lax.scan(
        step, s0, (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    s_prev = jnp.moveaxis(s_prev, 0, 1)  # (B,C,H,N,P) state entering chunk

    # Inter-chunk term: y_t += C_t . (decay-from-chunk-start * S_prev)
    w_start = jnp.exp(cum)  # (B,C,Q,H)
    cs = jnp.einsum("bctn,bchnp->bcthp", cc, s_prev)  # C_t . S_prev
    y_inter = w_start[..., None] * cs

    y = (y_intra + y_inter).astype(dtype).reshape(b, l, h, p)
    return y, s_final


@jax.named_scope("ssm")
def apply_ssm(p, cfg, x, return_cache: bool = False):
    """Full-sequence Mamba2 block. x (B,L,D) -> (y (B,L,D), cache_or_state)."""
    b, l, d = x.shape
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    w = cfg.ssm_conv
    z = jnp.einsum("bld,de->ble", x, p["in_z"].astype(x.dtype))
    xi_raw = jnp.einsum("bld,de->ble", x, p["in_x"].astype(x.dtype))
    bm_raw = jnp.einsum("bld,dn->bln", x, p["in_b"].astype(x.dtype))
    cm_raw = jnp.einsum("bld,dn->bln", x, p["in_c"].astype(x.dtype))
    dt = jnp.einsum("bld,dh->blh", x, p["in_dt"].astype(x.dtype))

    xi = jax.nn.silu(_causal_conv(xi_raw, p["conv_x"], p["conv_bias_x"])
                     .astype(jnp.float32)).astype(x.dtype)
    bm = jax.nn.silu(_causal_conv(bm_raw, p["conv_b"], p["conv_bias_b"])
                     .astype(jnp.float32)).astype(x.dtype)
    cm = jax.nn.silu(_causal_conv(cm_raw, p["conv_c"], p["conv_bias_c"])
                     .astype(jnp.float32)).astype(x.dtype)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,L,H)
    a_neg = -jnp.exp(p["a_log"])  # (H,)
    xh = xi.reshape(b, l, h, pdim)
    xb = (xh.astype(jnp.float32) * dt[..., None]).astype(x.dtype)

    y, s_final = ssd_chunked(xb, dt, a_neg, bm, cm, cfg.ssm_chunk)
    y = y + (p["d_skip"][:, None] * xh.astype(jnp.float32)).astype(x.dtype)
    y = y.reshape(b, l, cfg.d_inner)

    # Gated RMSNorm then output projection.
    y = _gated_norm(y, z, p["norm_scale"])
    out = jnp.einsum("ble,ed->bld", y, p["out"].astype(x.dtype))
    if return_cache:
        cache = {"state": s_final,
                 "conv_x": xi_raw[:, l - (w - 1):, :],
                 "conv_b": bm_raw[:, l - (w - 1):, :],
                 "conv_c": cm_raw[:, l - (w - 1):, :]}
        return out, cache
    return out, s_final


def _gated_norm(y, z, scale, eps: float = 1e-6):
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(ms + eps) * scale).astype(y.dtype)


@jax.named_scope("ssm")
def decode_ssm(p, cfg, x_t, cache):
    """Single-token Mamba2 step. x_t (B,1,D); cache {"state","conv_*"}."""
    b = x_t.shape[0]
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    xt = x_t[:, 0]
    z = xt @ p["in_z"].astype(xt.dtype)
    xi = xt @ p["in_x"].astype(xt.dtype)
    bm = xt @ p["in_b"].astype(xt.dtype)
    cm = xt @ p["in_c"].astype(xt.dtype)
    dt = xt @ p["in_dt"].astype(xt.dtype)

    xi, conv_x = _conv_step(cache["conv_x"], xi, p["conv_x"], p["conv_bias_x"])
    bm, conv_b = _conv_step(cache["conv_b"], bm, p["conv_b"], p["conv_bias_b"])
    cm, conv_c = _conv_step(cache["conv_c"], cm, p["conv_c"], p["conv_bias_c"])
    xi = jax.nn.silu(xi.astype(jnp.float32)).astype(xt.dtype)
    bm = jax.nn.silu(bm.astype(jnp.float32))
    cm = jax.nn.silu(cm.astype(jnp.float32))

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = jnp.exp(dt * -jnp.exp(p["a_log"]))  # (B,H) decay
    xh = xi.reshape(b, h, pdim).astype(jnp.float32)
    s = cache["state"]  # (B,H,N,P) fp32
    s = s * a[..., None, None] + jnp.einsum(
        "bn,bhp->bhnp", bm, xh * dt[..., None])
    y = jnp.einsum("bn,bhnp->bhp", cm, s)
    y = y + p["d_skip"][:, None] * xh
    y = y.reshape(b, cfg.d_inner).astype(x_t.dtype)
    y = _gated_norm(y, z, p["norm_scale"])
    out = (y @ p["out"].astype(y.dtype))[:, None, :]
    new_cache = {"state": s, "conv_x": conv_x, "conv_b": conv_b, "conv_c": conv_c}
    return out, new_cache


def ssm_cache_shape(cfg, batch: int):
    w, di, n = cfg.ssm_conv, cfg.d_inner, cfg.ssm_state
    return {
        "state": (batch, cfg.ssm_heads, n, cfg.ssm_head_dim),
        "conv_x": (batch, w - 1, di),
        "conv_b": (batch, w - 1, n),
        "conv_c": (batch, w - 1, n),
    }
