"""Pallas TPU kernels: packed COSINE match-count via XOR + popcount.

Signatures arrive bit-packed (core/packing.py): 32 signs per int32 word, so
a [N, V] int8 sign matrix streams as [N, ceil(V/32)] words -- 8x fewer bytes
off HBM.  The agreement count is recovered without unpacking:

    counts[q, n] = bits_total - popcount(q_words[q] XOR d_words[n])

where bits_total = 32 * W_logical and the packing guarantees query tail bits
(past V in the last word) are 1 while data tail bits are 0, so every tail
bit is a disagreement and the identity needs no knowledge of V.  Word-axis
pad (to the column-sweep group) is 0 on both sides: XOR 0 -> popcount 0,
combine-neutral.  The words are walked by `common.column_sweep`, one 2-D
[TQ, TN] XOR+popcount per word, over the grouped query and transposed data
layouts ops.py builds.  Counts are bit-for-bit identical to the wide MXU kernel
(cosine_count.py) -- the FLASH trick (Wang et al., 1709.01190) on the VPU.

Two entry points:
  packed_cosine_count_pallas  -- counts int32 [Q, N] (grid (qi, nj), whole
      packed width per block; W is 32x smaller than V so it always fits).
  packed_cosine_topk_pallas   -- the fused match -> count -> per-tile local
      top-k: each (qi, nj) tile extracts its kc best (count desc, id asc)
      candidates in VMEM and writes only [n_tiles, Q, kc] id/count buffers
      to HBM instead of the full [Q, N] count matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

TILE_Q = 128
TILE_N = 256

# plain ints (not jnp scalars): module-level arrays would be captured as
# pallas kernel constants, which pallas_call rejects
_NEG_INF = -(2**31) + 1
_POS_INF = 2**31 - 1


def _disagree(q, d):
    return jax.lax.population_count(q ^ d)


def _xor_popcount_counts(q_ref, d_ref, *, bits_total: int) -> jnp.ndarray:
    """Agreement counts [TQ, TN] from a grouped query word block
    [Wp/GROUP, TQ, GROUP] and a transposed data word block [Wp, TN]."""
    acc = jnp.zeros((q_ref.shape[1], d_ref.shape[1]), dtype=jnp.int32)
    return bits_total - common.column_sweep([q_ref], d_ref, _disagree, acc)


def local_topk_tile(counts: jnp.ndarray, gid: jnp.ndarray, kc: int):
    """Per-tile local top-k by iterative extraction, (count desc, id asc).

    counts int32 [TQ, TN] (pad columns pre-masked to -1), gid int32 [TQ, TN]
    global object ids.  Returns (ids [TQ, kc], counts [TQ, kc]); exhausted
    slots (only pads left) emit id -1 / count -1.  Equal-count candidates
    appear in ascending-id order, which topk_from_candidates' stable merge
    relies on for the global tie-break.

    The kc extraction passes run as a `lax.fori_loop` that writes slot t by
    a lane-iota select, so the body stays 2-D and compile time does not
    grow with k.
    """
    slot = jax.lax.broadcasted_iota(jnp.int32, (counts.shape[0], kc), 1)

    def extract(t, carry):
        work, ids, cnts = carry
        best = jnp.max(work, axis=1, keepdims=True)               # [TQ, 1]
        at_best = work == best
        bid = jnp.min(jnp.where(at_best, gid, jnp.int32(_POS_INF)), axis=1,
                      keepdims=True)
        here = slot == t
        ids = jnp.where(here, jnp.where(best < 0, jnp.int32(-1), bid), ids)
        cnts = jnp.where(here, jnp.maximum(best, jnp.int32(-1)), cnts)
        work = jnp.where(gid == bid, jnp.int32(_NEG_INF), work)
        return work, ids, cnts

    empty = jnp.full((counts.shape[0], kc), -1, dtype=jnp.int32)
    _, ids, cnts = jax.lax.fori_loop(0, kc, extract, (counts, empty, empty))
    return ids, cnts


def _count_kernel(q_ref, d_ref, o_ref, *, bits_total: int):
    o_ref[...] = _xor_popcount_counts(q_ref, d_ref, bits_total=bits_total)


def packed_cosine_count_pallas(
    data_t: jnp.ndarray,
    query_groups: jnp.ndarray,
    *,
    bits_total: int,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """counts int32 [Q, N] from transposed data words [Wp, N] and grouped
    query words [Wp/GROUP, Q, GROUP].  Inputs pre-padded and laid out
    (ops.py): Q % tile_q == 0, N % tile_n == 0, word axis 0-padded;
    bits_total = 32 * W_logical."""
    n_groups, qn, group = query_groups.shape
    wp, nn = data_t.shape
    assert group == common.GROUP and wp == n_groups * group
    assert qn % tile_q == 0 and nn % tile_n == 0
    grid = (qn // tile_q, nn // tile_n)
    kernel = functools.partial(_count_kernel, bits_total=bits_total)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_groups, tile_q, group), lambda i, j: (0, i, 0)),
            pl.BlockSpec((wp, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, nn), jnp.int32),
        interpret=interpret,
    )(query_groups.astype(jnp.int32), data_t.astype(jnp.int32))


def _topk_kernel(q_ref, d_ref, ids_ref, cnt_ref, *,
                 bits_total: int, tile_n: int, kc: int, n_logical: int):
    j = pl.program_id(1)
    counts = _xor_popcount_counts(q_ref, d_ref, bits_total=bits_total)
    gid = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, counts.shape, 1)
    counts = jnp.where(gid < n_logical, counts, jnp.int32(-1))
    ids, cnts = local_topk_tile(counts, gid, kc)
    ids_ref[...] = ids
    cnt_ref[...] = cnts


def packed_cosine_topk_pallas(
    data_t: jnp.ndarray,
    query_groups: jnp.ndarray,
    *,
    bits_total: int,
    n_logical: int,
    k: int,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused match -> count -> local top-k.  Returns (ids, counts), both
    int32 [n_tiles, Q, kc] with kc = min(k, tile_n): tile j's candidates in
    (count desc, id asc) order, pads as id -1 / count -1.  Only these
    candidate buffers touch HBM -- the [Q, N] count matrix never leaves
    VMEM.  The tile axis leads so each (tile_q, kc) block spans the whole
    minor axis (Mosaic's block rule); ops.py flattens it to [Q, n_tiles*kc]."""
    n_groups, qn, group = query_groups.shape
    wp, nn = data_t.shape
    assert group == common.GROUP and wp == n_groups * group
    assert qn % tile_q == 0 and nn % tile_n == 0
    kc = min(k, tile_n)
    n_tiles = nn // tile_n
    grid = (qn // tile_q, n_tiles)
    kernel = functools.partial(
        _topk_kernel, bits_total=bits_total,
        tile_n=tile_n, kc=kc, n_logical=n_logical,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_groups, tile_q, group), lambda i, j: (0, i, 0)),
            pl.BlockSpec((wp, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, tile_q, kc), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, tile_q, kc), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, qn, kc), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, qn, kc), jnp.int32),
        ],
        interpret=interpret,
    )(query_groups.astype(jnp.int32), data_t.astype(jnp.int32))
