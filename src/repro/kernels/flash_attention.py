"""Flash attention as Pallas TPU kernels: a forward pass and its backward.

Blockwise online softmax over (block_q x block_k) tiles, so the S x S
scores never reach HBM. The layout is head-major: q and k (B, H|KV, S,
d_qk), v (B, KV, S, d_v), H a multiple of KV (grouped-query attention);
the value width may differ from the query/key width (latent attention's
192-wide q/k and 128-wide v). A query head reads its group's K/V block
through the BlockSpec index map, so K/V are never expanded to H heads.
The caller scales q by 1/sqrt(d_qk).

Forward: grid (B, H, q blocks, k blocks), k innermost. The running max,
the running sum and the output accumulator stay in f32 VMEM scratch over
the k sweep. The per-row log-sum-exp (f32) is kept for the backward pass,
which recomputes the probabilities from it.

Backward, two kernels: dQ on the forward's grid, and dK/dV on the grid
(B, KV, k blocks, group, q blocks), which sums over the group's query
heads and the q blocks in VMEM and writes each K/V block once.
delta = rowsum(dO * O) comes from XLA.

MXU operands keep the input dtype (bf16 on the train path) and
accumulate in f32; max, sum, exp and the accumulators are f32, and P and
dS are cast to the input dtype for their products. A value head narrower
than the MXU (d_v < 128) leaves it spare columns: V carries a ones column
there, so P·V also gives the softmax's row sums.

Causal: a block strictly above the diagonal is neither computed nor
fetched. The index map clamps the K-block index (the Q-block index in
dK/dV) to the last (first) block a row block needs, and a block index
that does not change issues no copy. Only blocks that cross the diagonal
build the iota mask; with square blocks that is the diagonal block, which
runs in bands of rows, each only as far as the diagonal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite, so a masked score gives exp(NEG_INF - m) == 0
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
# scoped VMEM the kernels may use: a 2048 x 2048 tile's f32 scores take
# 16 MiB, more than the compiler's default; v5e has 128 MiB
_VMEM_LIMIT = 64 * 2**20
# rows of a band of the diagonal tile (see ``_tiles``)
DIAG_BAND = 256
# the MXU's width: a head narrower than this leaves spare columns
LANES = 128


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _last_k_block(qi, bq, bk):
    """The last k block that q block ``qi`` sees under the causal mask."""
    return ((qi + 1) * bq - 1) // bk


def _first_q_block(ki, bq, bk):
    """The first q block that sees k block ``ki`` under the causal mask."""
    return (ki * bk) // bq


def _tiles(body, causal, qi, ki, bq, bk, rows_are_q=True):
    """Run ``body(rows, cols, masked)`` over the part of one (q block,
    k block) tile that the causal mask leaves visible. ``rows`` and
    ``cols`` are static (start, size) ranges inside the tile, whose rows
    are queries (keys when not ``rows_are_q``). A tile wholly below the
    diagonal runs whole and unmasked, one above it not at all. With
    square blocks the crossing tile is the diagonal one: it runs as bands
    of ``DIAG_BAND`` rows, each only as far as the diagonal, so half of it
    is not computed."""
    shape = (bq, bk) if rows_are_q else (bk, bq)
    whole = (0, shape[0]), (0, shape[1])
    if not causal:
        body(*whole, False)
        return
    below = ki * bk + bk - 1 <= qi * bq
    pl.when(below)(lambda: body(*whole, False))
    if bq != bk:
        crosses = jnp.logical_and(ki * bk <= qi * bq + bq - 1,
                                  jnp.logical_not(below))
        pl.when(crosses)(lambda: body(*whole, True))
        return

    @pl.when(qi == ki)
    def _diagonal():
        band = DIAG_BAND if bq % DIAG_BAND == 0 else bq
        for lo in range(0, bq, band):
            if rows_are_q:   # queries lo.. see keys ..lo + band
                body((lo, band), (0, lo + band), True)
            else:            # keys lo.. are seen by queries lo..
                body((lo, band), (lo, bq - lo), True)


def _visible(q_start, k_start, shape, q_axis):
    """Causal mask of one tile: query position >= key position."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return qpos >= kpos


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, acc_sc, *l_sc,
                causal: bool, bq: int, bk: int, dv: int):
    # without ``l_sc`` the row sums come out of the MXU: v carries a ones
    # column at lane ``dv`` (see ``_ones_column``)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        for ref in l_sc:
            ref[...] = jnp.zeros_like(ref)

    def body(r, c, masked):
        rows, cols = pl.ds(*r), pl.ds(*c)
        s = _dot(q_ref[rows, :], k_ref[cols, :], _NT)
        if masked:
            s = jnp.where(_visible(qi * bq + r[0], ki * bk + c[0], s.shape, 0),
                          s, NEG_INF)
        m_prev = m_sc[rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_sc[rows, :] = alpha * acc_sc[rows, :] + _dot(
            p.astype(v_ref.dtype), v_ref[cols, :], _NN)
        for ref in l_sc:
            ref[rows, :] = alpha * ref[rows, :] + p.sum(axis=1, keepdims=True)
        m_sc[rows, :] = m_new

    _tiles(body, causal, qi, ki, bq, bk)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        acc = acc_sc[...]
        l = l_sc[0][...] if l_sc else acc[:, dv:dv + 1]
        o_ref[...] = (acc[:, :dv] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[...] + jnp.log(l))[:, 0]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_sc,
               *, causal: bool, bq: int, bk: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(r, c, masked):
        rows, cols = pl.ds(*r), pl.ds(*c)
        k = k_ref[cols, :]
        s = _dot(q_ref[rows, :], k, _NT)
        if masked:
            s = jnp.where(_visible(qi * bq + r[0], ki * bk + c[0], s.shape, 0),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, rows][:, None])
        dp = _dot(do_ref[rows, :], v_ref[cols, :], _NT)
        ds = p * (dp - di_ref[0, rows][:, None])
        acc_sc[rows, :] += _dot(ds.astype(k.dtype), k, _NN)

    _tiles(body, causal, qi, ki, bq, bk)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[...] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, causal: bool, bq: int, bk: int):
    ki, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def body(r, c, masked):   # rows are keys, columns queries
        rows, cols = pl.ds(*r), pl.ds(*c)
        q, do = q_ref[cols, :], do_ref[cols, :]
        st = _dot(k_ref[rows, :], q, _NT)
        if masked:
            st = jnp.where(_visible(qi * bq + c[0], ki * bk + r[0], st.shape, 1),
                           st, NEG_INF)
        pt = jnp.exp(st - lse_ref[:, cols])
        dv_sc[rows, :] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v_ref[rows, :], do, _NT)
        dst = pt * (dpt - di_ref[:, cols])
        dk_sc[rows, :] += _dot(dst.astype(q.dtype), q, _NN)

    _tiles(body, causal, qi, ki, bq, bk, rows_are_q=False)

    @pl.when(jnp.logical_and(g == pl.num_programs(3) - 1,
                             qi == pl.num_programs(4) - 1))
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _params(n_parallel: int, n_arbitrary: int):
    return pltpu.CompilerParams(
        dimension_semantics=(("parallel",) * n_parallel
                             + ("arbitrary",) * n_arbitrary),
        vmem_limit_bytes=_VMEM_LIMIT)


def _check(q, k, bq, bk):
    b, h, sq, dqk = q.shape
    kv, sk = k.shape[1], k.shape[2]
    if h % kv:
        raise ValueError(f"{h} query heads do not group over {kv} KV heads")
    if sq % bq or sk % bk:
        raise ValueError(f"cannot tile q {sq} x k {sk} with blocks {bq} x {bk}")
    return b, h, kv, sq, sk, dqk


def _ones_column(v):
    """v (B, KV, S, d_v) padded to LANES lanes with ones at lane d_v, so P·V
    also gives P's row sums in columns the MXU computes anyway."""
    b, kv, s, dv = v.shape
    pad = [jnp.ones((b, kv, s, 1), v.dtype),
           jnp.zeros((b, kv, s, LANES - dv - 1), v.dtype)]
    return jnp.concatenate([v] + pad, axis=-1)


def _q_major_specs(group, causal, bq, bk):
    """On the grid (B, H, q blocks, k blocks): the BlockSpec of a block of
    rows of a (B, H, S, width) array, the index map of the group's K/V
    block (clamped under the causal mask to the last block the q block
    sees), and the BlockSpec of a block of a row vector."""
    def kv_map(bi, hi, qi, ki):
        if causal:
            ki = jnp.minimum(ki, _last_k_block(qi, bq, bk))
        return bi, hi // group, ki, 0

    q_spec = lambda w: pl.BlockSpec((None, None, bq, w),
                                    lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    row_spec = pl.BlockSpec((None, None, 1, bq), lambda bi, hi, qi, ki: (bi, hi, 0, qi))
    return q_spec, kv_map, row_spec


def _forward(q, k, v, causal, bq, bk, interpret):
    b, h, kv, sq, sk, dqk = _check(q, k, bq, bk)
    dv = v.shape[-1]
    fold = dv < LANES
    if fold:
        v = _ones_column(v)
    q_spec, kv_map, row_spec = _q_major_specs(h // kv, causal, bq, bk)
    kernel = functools.partial(_fwd_kernel, causal=causal, bq=bq, bk=bk, dv=dv)
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq // bq, sk // bk),
        in_specs=[q_spec(dqk), pl.BlockSpec((None, None, bk, dqk), kv_map),
                  pl.BlockSpec((None, None, bk, v.shape[-1]), kv_map)],
        out_specs=[q_spec(dv), row_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, v.shape[-1]), jnp.float32)]
                       + ([] if fold else [pltpu.VMEM((bq, 1), jnp.float32)]),
        compiler_params=_params(3, 1),
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)


def _backward(q, k, v, o, lse, do, causal, bq, bk, interpret):
    b, h, kv, sq, sk, dqk = _check(q, k, bq, bk)
    dv = v.shape[-1]
    group = h // kv
    nq, nk = sq // bq, sk // bk
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, :, None, :]                          # (B,H,1,S)

    # dQ: the forward's grid
    q_spec, kv_map, row_spec = _q_major_specs(group, causal, bq, bk)
    k_spec = pl.BlockSpec((None, None, bk, dqk), kv_map)
    v_spec = pl.BlockSpec((None, None, bk, dv), kv_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, bq=bq, bk=bk),
        grid=(b, h, nq, nk),
        in_specs=[q_spec(dqk), k_spec, v_spec, q_spec(dv), row_spec, row_spec],
        out_specs=q_spec(dqk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dqk), jnp.float32)],
        compiler_params=_params(3, 1),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, di)

    # dK/dV: (B, KV, k blocks, group, q blocks); the last two are summed
    def q_map(bi, kvi, ki, g, qi):
        if causal:
            qi = jnp.maximum(qi, _first_q_block(ki, bq, bk))
        return bi, kvi * group + g, qi, 0

    def row_map(bi, kvi, ki, g, qi):
        _, hi, qi, _ = q_map(bi, kvi, ki, g, qi)
        return bi, hi, 0, qi

    q_spec = lambda w: pl.BlockSpec((None, None, bq, w), q_map)
    kv_spec = lambda w: pl.BlockSpec((None, None, bk, w),
                                     lambda bi, kvi, ki, g, qi: (bi, kvi, ki, 0))
    row_spec = pl.BlockSpec((None, None, 1, bq), row_map)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, bq=bq, bk=bk),
        grid=(b, kv, nk, group, nq),
        in_specs=[q_spec(dqk), kv_spec(dqk), kv_spec(dv), q_spec(dv), row_spec,
                  row_spec],
        out_specs=[kv_spec(dqk), kv_spec(dv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dqk), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=_params(3, 2),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv_


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_bhsd(q, k, v, causal: bool, block_q: int, block_k: int,
                         interpret: bool = False):
    """q (B, H, Sq, d_qk) pre-scaled, k (B, KV, Sk, d_qk), v (B, KV, Sk,
    d_v) -> (B, H, Sq, d_v)."""
    return _forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _forward(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, causal, block_q, block_k, interpret)


flash_attention_bhsd.defvjp(_vjp_fwd, _vjp_bwd)
