"""Jitted wrappers: model-facing entry points for the Pallas kernels.

These adapt model-layout tensors to kernel layouts and pick block sizes
on the TPU's tiling. A shape they cannot tile raises ``ValueError``;
nothing falls back, so the kernel that runs is the kernel that was asked
for.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ref as ref_lib
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_scan as ssd


# rows of a block must be a multiple of this, unless the block spans the
# whole extent (the (8, 128) tiling's sublane side)
SUBLANES = 8


def _pick_block(s: int, target: int) -> int:
    """The largest block of at most ``target`` rows that tiles ``s``."""
    if s <= target:
        return s
    b = target
    while b >= SUBLANES:
        if s % b == 0 and b % SUBLANES == 0:
            return b
        b //= 2
    raise ValueError(f"cannot tile an extent of {s} with blocks of at most "
                     f"{target} rows in multiples of {SUBLANES}")


@partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, causal: bool = True, interpret: bool = False):
    """Model layout: q (B,S,H,hd), k/v (B,S,H,hd) (pre-expanded GQA)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, hd)
    bq = _pick_block(sq, 128)
    bk = _pick_block(sk, 128)
    o = fa.flash_attention_bhsd(qf, kf, vf, causal=causal, block_q=bq,
                                block_k=bk, interpret=interpret)
    return jnp.moveaxis(o.reshape(b, h, sq, hd), 1, 2)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xb, dt, a_neg, bmat, cmat, chunk: int, interpret: bool = False):
    """Model layout: xb (B,L,H,P), dt (B,L,H), bmat/cmat (B,L,N).

    Returns (y (B,L,H,P), final_state (B,H,N,P)) matching
    ``repro.models.ssm.ssd_chunked_ref``. The final state (needed only by
    prefill) is reconstructed with one extra lightweight pass.
    """
    if chunk < xb.shape[1] and chunk % SUBLANES:
        raise ValueError(f"chunk {chunk} is off the {SUBLANES}-row tiling")
    xh = jnp.moveaxis(xb, 1, 2)   # (B,H,L,P)
    dth = jnp.moveaxis(dt, 1, 2)  # (B,H,L)
    y = ssd.ssd_scan_bhlp(xh, dth, a_neg, bmat, cmat, chunk,
                          interpret=interpret)
    y = jnp.moveaxis(y, 1, 2)
    # final state: cheap closed form over the full sequence (O(L·N·P))
    loga = dth.astype(jnp.float32) * a_neg[None, :, None]  # (B,H,L)
    cum = jnp.cumsum(loga, axis=-1)
    w_end = jnp.exp(cum[..., -1:] - cum)  # (B,H,L)
    s = jnp.einsum("bhl,bln,blhp->bhnp", w_end, bmat.astype(jnp.float32),
                   jnp.moveaxis(xh, 1, 2).astype(jnp.float32))
    return y, s


@partial(jax.jit, static_argnames=("interpret",))
def rmsnorm(x, gain, interpret: bool = False):
    """x (..., D) -> normalized; flattens leading dims for the row kernel."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r = x2.shape[0]
    br = _pick_block(r, 256)
    y = rn.rmsnorm_2d(x2, gain, block_rows=br, interpret=interpret)
    return y.reshape(shape)
