"""Pallas TPU kernel: TANIMOTO match-count (minhash sketch collisions).

counts[q, n] = sum_i (data_sigs[n, i] == query_sigs[q, i])

Minhash collision counting -- Pr[h(S) = h(T)] = J(S, T), so counts are
Binomial(m, J) draws and c/m is the Jaccard MLE (FLASH, Wang et al.,
1709.01190).  Unlike the EQ kernel (match_count.py), which holds the whole
signature width in VMEM per block, FLASH-scale sketches use thousands of hash
functions, so here the signature axis m is the third grid dimension: [TM]
column slabs of the grouped query and transposed data layouts stream through
VMEM, `common.column_sweep` compares them one 2-D [TQ, TN] tile per column,
and partial collision counts accumulate into the output tile across the M
grid steps (same streaming pattern as the MINSUM vocabulary axis).

Grid: (Q/TILE_Q, N/TILE_N, M/TILE_M), output revisited along the last axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

TILE_Q = 128
TILE_N = 256
TILE_M = 512


def _eq(q, d):
    return (q == d).astype(jnp.int32)


def _tanimoto_kernel(q_ref, d_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = common.column_sweep([q_ref], d_ref, _eq, o_ref[...])


def tanimoto_count_pallas(
    data_t: jnp.ndarray,
    query_groups: jnp.ndarray,
    *,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    tile_m: int = TILE_M,
    interpret: bool = False,
) -> jnp.ndarray:
    """counts int32 [Q, N] from transposed data [M, N] and grouped queries
    [M/GROUP, Q, GROUP].  Inputs pre-padded (ops.py): Q % tile_q == 0,
    N % tile_n == 0, M % tile_m == 0 with non-colliding sentinels in the pad."""
    _, qn, group = query_groups.shape
    m, nn = data_t.shape
    assert group == common.GROUP and tile_m % group == 0
    assert qn % tile_q == 0 and nn % tile_n == 0 and m % tile_m == 0
    grid = (qn // tile_q, nn // tile_n, m // tile_m)
    return pl.pallas_call(
        _tanimoto_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m // group, tile_q, group), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, nn), jnp.int32),
        interpret=interpret,
    )(query_groups.astype(jnp.int32), data_t.astype(jnp.int32))
