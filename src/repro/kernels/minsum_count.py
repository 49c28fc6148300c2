"""Pallas TPU kernel: MINSUM match-count (SA n-gram multiset intersection).

counts[q, n] = sum_v min(data_cnt[n, v], query_cnt[q, v])

Lemma 5.1's ordered-n-gram match count over per-gram-type multiplicity
vectors.  The gram-vocabulary axis V is tiled through the grid (third grid
dim) so arbitrarily large vocabularies stream through VMEM; within a V block
`common.column_sweep` adds one 2-D [TQ, TN] min per gram, and partial sums
accumulate into the output tile across the V grid steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

TILE_Q = 128
TILE_N = 256
TILE_V = 512


def _minsum_kernel(q_ref, d_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = common.column_sweep([q_ref], d_ref, jnp.minimum, o_ref[...])


def minsum_count_pallas(
    data_t: jnp.ndarray,
    query_groups: jnp.ndarray,
    *,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    tile_v: int = TILE_V,
    interpret: bool = False,
) -> jnp.ndarray:
    """counts int32 [Q, N] from transposed data [V, N] and grouped queries
    [V/GROUP, Q, GROUP]; V % tile_v == 0 with 0 in the pad."""
    _, qn, group = query_groups.shape
    v, nn = data_t.shape
    assert group == common.GROUP and tile_v % group == 0
    assert qn % tile_q == 0 and nn % tile_n == 0 and v % tile_v == 0
    grid = (qn // tile_q, nn // tile_n, v // tile_v)
    return pl.pallas_call(
        _minsum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_v // group, tile_q, group), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((tile_v, tile_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, nn), jnp.int32),
        interpret=interpret,
    )(query_groups.astype(jnp.int32), data_t.astype(jnp.int32))
