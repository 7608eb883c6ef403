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


# (seq, head_dim) -> (block_q, block_k): the fastest forward + backward of
# a sweep on a TPU v5e chip (PERF.md); other shapes take the
# largest square block of at most DEFAULT_BLOCK rows that tiles them
FLASH_BLOCKS = {(512, 64): (512, 512), (1024, 64): (1024, 1024),
                (2048, 64): (2048, 2048), (4096, 64): (2048, 2048),
                (8192, 192): (1024, 1024)}
DEFAULT_BLOCK = 1024
# the query/key head widths the kernel is built and tested for (192: latent
# attention's qk_nope 128 + qk_rope 64, with 128-wide value heads)
FLASH_HEAD_DIMS = (64, 128, 192)
# a block of a row vector (the log-sum-exp) is a multiple of the lane
# width unless it spans the whole extent
LANES = fa.LANES


def flash_blocks(seq: int, head_dim: int):
    """(block_q, block_k) for a square ``seq`` x ``seq`` attention."""
    if (seq, head_dim) in FLASH_BLOCKS:
        return FLASH_BLOCKS[seq, head_dim]
    b = _pick_block(seq, DEFAULT_BLOCK)
    if b != seq and b % LANES:
        raise ValueError(f"cannot tile an extent of {seq} with blocks of at "
                         f"most {DEFAULT_BLOCK} rows in multiples of {LANES}")
    return b, b


@partial(jax.jit, static_argnames=("causal", "blocks", "interpret"))
def flash_attention_bhsd(q, k, v, causal: bool = True, blocks=None,
                         interpret: bool = False):
    """Heads leading: q (B,H,S,d_qk), k (B,KV,S,d_qk), v (B,KV,S,d_v) with
    KV dividing H; q is scaled by 1/sqrt(d_qk).

    ``blocks`` is (block_q, block_k); by default ``flash_blocks``'s.
    Differentiable: the backward pass is the kernels' own.
    """
    sq, sk, hd = q.shape[2], k.shape[2], q.shape[-1]
    if blocks is None:
        blocks = flash_blocks(sq, hd)[0], flash_blocks(sk, hd)[1]
    qs = q * jnp.asarray(hd ** -0.5, q.dtype)
    return fa.flash_attention_bhsd(qs, k, v, causal, *blocks, interpret)


@partial(jax.jit, static_argnames=("causal", "blocks", "interpret"))
def flash_attention(q, k, v, causal: bool = True, blocks=None,
                    interpret: bool = False):
    """Model layout: q/k (B,S,H|KV,d_qk), v (B,S,KV,d_v); see
    ``flash_attention_bhsd``."""
    heads = lambda t: jnp.swapaxes(t, 1, 2)
    return heads(flash_attention_bhsd(heads(q), heads(k), heads(v), causal,
                                      blocks, interpret))


# (rows, contraction, columns) tile of the megablox grouped matmul: the
# fastest forward + backward of the expert layer's SwiGLU at
# Moonlight-16B-A3B's widths in a sweep on a TPU v5e (PERF.md)
GMM_TILING = (512, 1024, 1024)


def grouped_matmul(lhs, rhs, sizes, interpret: bool = False):
    """Rows of ``lhs`` (M, K) in consecutive groups of ``sizes`` (G,) times
    their group's matrix of ``rhs`` (G, K, N), through the megablox ``gmm``
    kernel, whose work follows the rows the groups hold. Rows past the
    groups are left unwritten: the caller masks them. Differentiable (the
    kernel's own backward)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    (m, k), n = lhs.shape, rhs.shape[-1]
    tm, tk, tn = GMM_TILING
    tiling = (_pick_block(m, tm), min(k, tk), min(n, tn))
    return gmm(lhs, rhs, sizes, lhs.dtype, tiling, None, None, False, interpret)


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
