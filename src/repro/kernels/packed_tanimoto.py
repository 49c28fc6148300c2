"""Pallas TPU kernels: packed TANIMOTO match-count (uint8 minhash buckets).

When the minhash rehash domain fits a byte (core/packing.py caps it at 253;
254/255 are the pad sentinels), bucket ids narrow from int32 to uint8 -- 4x
fewer bytes off HBM for the dominant data stream -- and the match stays the
same equality compare.  Counts are bit-for-bit identical
to the wide kernel (tanimoto_count.py).  The data stays uint8 in HBM and
VMEM, transposed to [M, N]; `common.column_sweep` widens one 32-row byte
tile at a time (the 8-bit sublane tile) and compares it against the query
columns, which ops.py groups as int32 (queries are small).

Two entry points:
  packed_tanimoto_count_pallas -- counts int32 [Q, N]; the signature axis m
      streams through the grid exactly like the wide kernel (FLASH-scale m
      never resides whole in VMEM), just in quarter-width slabs.
  packed_tanimoto_topk_pallas  -- fused match -> count -> per-tile local
      top-k (grid (qi, nj), whole packed m per block): each tile extracts
      its kc best (count desc, id asc) candidates in VMEM and writes only
      [n_tiles, Q, kc] id/count buffers to HBM instead of [Q, N] counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.packed_cosine import local_topk_tile

TILE_Q = 128
TILE_N = 256
TILE_M = 512


def _eq(q, d):
    return (q == d).astype(jnp.int32)


def _byte_collision_counts(q_ref, d_ref, acc) -> jnp.ndarray:
    """acc + collision counts [TQ, TN] from a grouped int32 query block
    [Mp/GROUP_BYTES, TQ, GROUP_BYTES] and a transposed uint8 block [Mp, TN]."""
    return common.column_sweep([q_ref], d_ref, _eq, acc,
                               group=common.GROUP_BYTES)


def _count_kernel(q_ref, d_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = _byte_collision_counts(q_ref, d_ref, o_ref[...])


def packed_tanimoto_count_pallas(
    data_t: jnp.ndarray,
    query_groups: jnp.ndarray,
    *,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    tile_m: int = TILE_M,
    interpret: bool = False,
) -> jnp.ndarray:
    """counts int32 [Q, N] from transposed uint8 data [M, N] and grouped
    int32 queries [M/GROUP_BYTES, Q, GROUP_BYTES].  Inputs pre-padded
    (ops.py): Q % tile_q == 0, N % tile_n == 0, M % tile_m == 0 with the
    254/255 sentinels in the pad."""
    _, qn, group = query_groups.shape
    m, nn = data_t.shape
    assert group == common.GROUP_BYTES and tile_m % group == 0
    assert qn % tile_q == 0 and nn % tile_n == 0 and m % tile_m == 0
    grid = (qn // tile_q, nn // tile_n, m // tile_m)
    return pl.pallas_call(
        _count_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m // group, tile_q, group), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, nn), jnp.int32),
        interpret=interpret,
    )(query_groups.astype(jnp.int32), data_t.astype(jnp.uint8))


def _topk_kernel(q_ref, d_ref, ids_ref, cnt_ref, *,
                 tile_n: int, kc: int, n_logical: int):
    j = pl.program_id(1)
    acc = jnp.zeros((q_ref.shape[1], d_ref.shape[1]), dtype=jnp.int32)
    counts = _byte_collision_counts(q_ref, d_ref, acc)
    gid = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, counts.shape, 1)
    counts = jnp.where(gid < n_logical, counts, jnp.int32(-1))
    ids, cnts = local_topk_tile(counts, gid, kc)
    ids_ref[...] = ids
    cnt_ref[...] = cnts


def packed_tanimoto_topk_pallas(
    data_t: jnp.ndarray,
    query_groups: jnp.ndarray,
    *,
    n_logical: int,
    k: int,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused match -> count -> local top-k.  Returns (ids, counts), both
    int32 [n_tiles, Q, kc] with kc = min(k, tile_n), in the candidate order
    of packed_cosine_topk_pallas.  Holds the whole packed m per block (byte
    slabs are 4x smaller than the wide kernel's)."""
    n_groups, qn, group = query_groups.shape
    mp, nn = data_t.shape
    assert group == common.GROUP_BYTES and mp == n_groups * group
    assert qn % tile_q == 0 and nn % tile_n == 0
    kc = min(k, tile_n)
    n_tiles = nn // tile_n
    grid = (qn // tile_q, n_tiles)
    kernel = functools.partial(
        _topk_kernel, tile_n=tile_n, kc=kc, n_logical=n_logical
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_groups, tile_q, group), lambda i, j: (0, i, 0)),
            pl.BlockSpec((mp, tile_n), lambda i, j: (0, j)),
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
    )(query_groups.astype(jnp.int32), data_t.astype(jnp.uint8))
