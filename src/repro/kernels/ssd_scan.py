"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

Grid (batch, heads, chunks) with the chunk axis innermost: the carried
SSD state (N x P, fp32) lives in VMEM scratch across the sequential chunk
sweep, exactly the recurrence structure of the SSD algorithm. Each chunk
step does three MXU matmuls — C·Bᵀ (Q x Q), the masked-decay intra-chunk
product (Q x P), and the state update (N x P) — on VMEM-resident tiles,
so HBM traffic per chunk is the operand tiles only.

Inputs are pre-scaled x̄ = x·dt and pre-activated B/C (the layer applies
conv+SiLU before the scan). The per-chunk running sum of the log-decays
dt·A is taken in XLA before the call, because Mosaic has no ``cumsum``;
the kernel reads it twice, as a (Q, 1) column and a (1, Q) row, so the
(Q, Q) decay matrix is one broadcast subtraction with no transpose.
Every block's last two dims are either the array's own or (chunk, full),
which keeps the chunk axis on the (8, 128) tiling. Decay math is fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, col_ref, row_ref, b_ref, c_ref, y_ref, state_ref,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    cum = col_ref[0, 0, 0]                        # (Q, 1) inclusive, <= 0
    cum_row = row_ref[0, 0, 0]                    # (1, Q) the same sums
    # whole-chunk decay as a scalar: Mosaic cannot broadcast a (1, 1)
    # slice taken at lane Q-1, but it can broadcast a reduction's result
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    cum_end = jnp.sum(jnp.where(lane == chunk - 1, cum_row, 0.0))
    bm = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    # intra-chunk: (C B^T * decay) x
    seg = cum - cum_row                           # (Q, Q) t,s
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(cb * decay, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: C_t . (exp(cum_t) * S_prev)
    s_prev = state_ref[...]                       # (N, P)
    cs = jax.lax.dot_general(cm, s_prev, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_inter = jnp.exp(cum) * cs

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S = exp(cum_end) * S_prev + sum_s w_s B_s x_s^T
    w_end = jnp.exp(cum_end - cum)                # (Q, 1)
    s_new = (jnp.exp(cum_end) * s_prev
             + jax.lax.dot_general(bm * w_end, x,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    state_ref[...] = s_new


def ssd_scan_bhlp(xb, dt, a_neg, bmat, cmat, chunk: int,
                  interpret: bool = False):
    """xb (B,H,L,P); dt (B,H,L); a_neg (H,); bmat/cmat (B,L,N).

    Returns y (B,H,L,P). (The final state is recomputed by callers that
    need it via the reference path; the train path only needs y.)
    """
    b, h, l, p = xb.shape
    n = bmat.shape[-1]
    chunk = min(chunk, l)
    if l % chunk:
        raise ValueError(f"chunk {chunk} does not divide length {l}")
    nc = l // chunk

    loga = dt.astype(jnp.float32) * a_neg.astype(jnp.float32)[None, :, None]
    cum = jnp.cumsum(loga.reshape(b, h, nc, chunk), axis=-1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, 0, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, 0, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, l, p), xb.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xb, cum[..., None], cum[..., None, :], bmat[:, None], cmat[:, None])
