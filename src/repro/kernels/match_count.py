"""Pallas TPU kernel: EQ match-count (LSH signature compare).

counts[q, n] = sum_i (data_sigs[n, i] == query_sigs[q, i])

This is GENIE's inverted-index scan re-expressed for the TPU: instead of
scanning postings lists with atomic counter updates, each grid cell compares
a query-signature block against a data-signature block held in VMEM and
emits a dense [TILE_Q, TILE_N] count tile.  The compare walks the signature
columns with `common.column_sweep`: one 2-D [TQ, TN] compare per column, in
a loop whose body does not grow with m.  The wrapper (ops.py) hands it the
grouped query and transposed data layouts that sweep reads.

Grid: (Q/TILE_Q, N/TILE_N); each cell is independent.  The query block's
index does not change along the inner N axis, so it is fetched once per
query tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

TILE_Q = 128   # query rows per grid cell
TILE_N = 256   # objects per grid cell (minor-most in the output tile)


def _eq(q, d):
    return (q == d).astype(jnp.int32)


def _match_count_kernel(q_ref, d_ref, o_ref):
    acc = jnp.zeros(o_ref.shape, dtype=jnp.int32)
    o_ref[...] = common.column_sweep([q_ref], d_ref, _eq, acc)


def match_count_pallas(
    data_t: jnp.ndarray,
    query_groups: jnp.ndarray,
    *,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """counts int32 [Q, N] from transposed data int32 [Mp, N] and grouped
    queries int32 [Mp/GROUP, Q, GROUP].  Inputs must already be padded and
    laid out (ops.match_count does both): Q % tile_q == 0, N % tile_n == 0,
    Mp % GROUP == 0, with distinct data/query sentinels in the pad."""
    n_groups, qn, group = query_groups.shape
    mp, nn = data_t.shape
    assert group == common.GROUP and mp == n_groups * group, (query_groups.shape, mp)
    assert qn % tile_q == 0 and nn % tile_n == 0, (qn, nn, tile_q, tile_n)
    grid = (qn // tile_q, nn // tile_n)
    return pl.pallas_call(
        _match_count_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_groups, tile_q, group), lambda i, j: (0, i, 0)),
            pl.BlockSpec((mp, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, nn), jnp.int32),
        interpret=interpret,
    )(query_groups.astype(jnp.int32), data_t.astype(jnp.int32))
