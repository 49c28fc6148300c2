"""Pallas TPU kernel: RANGE match-count (relational range queries).

counts[q, n] = sum_d (q_lo[q, d] <= data_vals[n, d] <= q_hi[q, d])

The relational inverted index of paper Example 2.1 maps each (attribute,
value) pair to a postings list and a query item to a contiguous run of
lists; the equivalent dense computation is a per-attribute interval test.
Same grid/tiling scheme as match_count: the attribute columns are walked by
`common.column_sweep`, two compares per attribute on a 2-D [TQ, TN] tile,
over the grouped lo/hi and transposed data layouts ops.py builds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

TILE_Q = 128
TILE_N = 256


def _in_range(lo, hi, x):
    return ((x >= lo) & (x <= hi)).astype(jnp.int32)


def _range_count_kernel(lo_ref, hi_ref, x_ref, o_ref):
    acc = jnp.zeros(o_ref.shape, dtype=jnp.int32)
    o_ref[...] = common.column_sweep([lo_ref, hi_ref], x_ref, _in_range, acc)


def range_count_pallas(
    data_t: jnp.ndarray,
    lo_groups: jnp.ndarray,
    hi_groups: jnp.ndarray,
    *,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """counts int32 [Q, N] from transposed data [Dp, N] and grouped bounds
    [Dp/GROUP, Q, GROUP] (padded attributes hold an empty range)."""
    n_groups, qn, group = lo_groups.shape
    dp, nn = data_t.shape
    assert group == common.GROUP and dp == n_groups * group
    assert qn % tile_q == 0 and nn % tile_n == 0
    grid = (qn // tile_q, nn // tile_n)
    return pl.pallas_call(
        _range_count_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_groups, tile_q, group), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_groups, tile_q, group), lambda i, j: (0, i, 0)),
            pl.BlockSpec((dp, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, nn), jnp.int32),
        interpret=interpret,
    )(lo_groups.astype(jnp.int32), hi_groups.astype(jnp.int32),
      data_t.astype(jnp.int32))
