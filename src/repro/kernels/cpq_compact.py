"""Pallas TPU kernels of the c-PQ compaction (core/cpq.py, phase 3).

The compaction fills cap slots per query row with the strict objects
(count > threshold) in id order, then the ties (count == threshold) in id
order.  It works on blocks of BLOCK = 128 consecutive ids, the lane width,
and reads the count matrix twice, as it is (never padded, sliced or
copied):

  block counts   strict[q, b] = #{n in block b : counts[q, n] >  thr[q]}
                 ties[q, b]   = #{n in block b : counts[q, n] == thr[q]}
                 one streamed pass over every count;
  slot windows   win[q, j, :] = counts[q, 128 b : 128 b + 128] for the
                 block b of slot j: one DMA of a whole window per slot,
                 never a gather of single elements.

Block sums on the MXU.  Within a step the strict and tie masks of a
[TQ, TN] count tile are stacked into one int8 0/1 operand [2 TQ, TN]; its
product with the block-diagonal ones matrix E [TN, 128] (E[n, b] = 1 iff
n // 128 == b, TN <= 128 * 128) sums each 128-lane block into lane b of a
lane-dense [2 TQ, 128] result, so no count moves from lanes to sublanes.
Exact: int8 operands, int32 accumulation, at most 128 per sum.  The
columns past the logical N of a ragged last tile are masked out of both
classes, as kernels/cpq_hist.py masks them.

Windows by DMA.  A DMA out of HBM moves whole (8, 128) tiles of the count
matrix's tiled layout, so each slot's copy is the tile of its row's group
of 8 rows at its block, and the row is picked out in VMEM.  The tiles of
the last row group and the last block reach into the padding of the
buffer's last tiles.  It holds no object, and its lanes come after every
object of the last block, so a rank among that block's objects is reached
before them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128       # ids per block: the lane width, a hardware constant
SUBLANES = 8      # rows of an int32 HBM tile
TILE_Q = 32       # query rows per block-count step (2 * TQ mask rows)
TILE_N = BLOCK * BLOCK   # ids per step: one lane-dense row of block sums
CHUNK_N = 4096    # ids per MXU product inside a step


def block_ones(tile_n: int) -> np.ndarray:
    """E int8 [tile_n, BLOCK]: E[n, b] = 1 iff n // BLOCK == b (a constant
    of the program, not computed on each call)."""
    n = np.arange(tile_n)[:, None]
    return (n // BLOCK == np.arange(BLOCK)[None, :]).astype(np.int8)


def _cpq_block_counts_kernel(thr_ref, c_ref, e_ref, s_ref, t_ref, *,
                             n_valid: int, chunk: int):
    j = pl.program_id(1)
    tq, tn = c_ref.shape
    thr = thr_ref[...]                                          # [TQ, 1]
    acc = jnp.zeros((2 * tq, BLOCK), jnp.int32)
    for k in range(tn // chunk):
        c = c_ref[:, k * chunk:(k + 1) * chunk]                 # [TQ, C]
        col = (j * tn + k * chunk
               + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1))
        live = col < n_valid      # past the logical N: stale data, no class
        mask = jnp.concatenate(
            [((c > thr) & live).astype(jnp.int32),
             ((c == thr) & live).astype(jnp.int32)], axis=0).astype(jnp.int8)
        acc = acc + jnp.dot(mask, e_ref[k * chunk:(k + 1) * chunk, :],
                            preferred_element_type=jnp.int32)
    s_ref[...] = acc[:tq]
    t_ref[...] = acc[tq:]


def block_counts_pallas(counts: jnp.ndarray, threshold: jnp.ndarray, *,
                        tile_q: int, tile_n: int, interpret=False):
    """(strict, ties) int32 [Q, 128 * cdiv(N, tile_n)]: block b's counts at
    column b, 0 past cdiv(N, 128).  counts int32 [Q, N], any Q and N;
    threshold int32 [Q].  tile_q % 8 == 0, tile_n % 128 == 0, tile_n <=
    TILE_N."""
    qn, nn = counts.shape
    assert tile_q % 8 == 0 and tile_n % BLOCK == 0 and tile_n <= TILE_N
    kernel = functools.partial(_cpq_block_counts_kernel, n_valid=nn,
                               chunk=math.gcd(tile_n, CHUNK_N))
    steps = pl.cdiv(nn, tile_n)
    out = jax.ShapeDtypeStruct((qn, BLOCK * steps), jnp.int32)
    out_spec = pl.BlockSpec((tile_q, BLOCK), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(qn, tile_q), steps),
        in_specs=[pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
                  pl.BlockSpec((tile_n, BLOCK), lambda i, j: (0, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[out, out],
        interpret=interpret,
    )(threshold.reshape(qn, 1), counts, block_ones(tile_n))


def _cpq_slot_windows_kernel(blk_ref, c_hbm, w_ref, buf, sem, *, n_rows: int):
    rows, slots, _ = w_ref.shape
    row0 = pl.multiple_of(pl.program_id(0) * rows, rows)
    for r in range(rows):
        def copy(j, r=r):
            start = pl.multiple_of(blk_ref[r * slots + j] * BLOCK, BLOCK)
            return pltpu.make_async_copy(
                c_hbm.at[pl.ds(row0, rows), pl.ds(start, BLOCK)],
                buf.at[j], sem)

        @pl.when(row0 + r < n_rows)
        def _window(r=r, copy=copy):
            jax.lax.fori_loop(0, slots, lambda j, z: (copy(j).start(), z)[1], 0)
            jax.lax.fori_loop(0, slots, lambda j, z: (copy(j).wait(), z)[1], 0)
            w_ref[r] = buf[:, r, :]


def slot_windows_pallas(counts: jnp.ndarray, blocks: jnp.ndarray, *,
                        interpret=False):
    """win int32 [Q, S, 128]: win[q, j] = the 128 counts of block
    blocks[q, j] of row q.  counts int32 [Q, N]; blocks int32 [Q, S] with
    0 <= blocks < cdiv(N, 128) and S % 128 == 0.  The lanes past N read
    the padding of the buffer's last tiles.  On the chip that padding is
    part of the buffer; the interpreter, whose buffer has none, is given
    the counts padded to whole tiles."""
    qn, slots = blocks.shape
    rows = min(SUBLANES, qn)
    if interpret:
        counts = jnp.pad(counts, ((0, -qn % rows),
                                  (0, -counts.shape[1] % BLOCK)))
    kernel = functools.partial(_cpq_slot_windows_kernel, n_rows=qn)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(qn, rows),),
        in_specs=[pl.BlockSpec((rows * slots,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, slots, BLOCK), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, slots, BLOCK), jnp.int32),
        scratch_shapes=[pltpu.VMEM((slots, rows, BLOCK), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(blocks.reshape(qn * slots), counts)
