"""Pallas TPU kernel: COSINE match-count (sign-agreement via +-1 MXU matmul).

counts[q, n] = (V + sum_v query_sgn[q, v] * data_sgn[n, v]) / 2

Sign-quantized (simhash-style) cosine at billion scale (Johnson et al.,
1702.08734): the agreement count of sign bits equals the shifted +-1 inner
product, so the compare rides the MXU as a tiled matmul with bf16 +-1 inputs
(exact products).  Each V grid step's partial dot lies in [-tile_v, tile_v]
-- exact in f32 -- and is cast to int32 before accumulating into the output
tile, so the running sum and the final (V + dot) // 2 shift are pure integer
arithmetic: the kernel emits int32 counts with no f32 magnitude bound on V
(the old f32 accumulator capped exactness at 2^24).  Zero pad rows
(`plan.pad_to_multiple` fill) floor to V // 2 and are masked upstream by
global id.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_Q = 128
TILE_N = 256
TILE_V = 512


def _cosine_kernel(q_ref, d_ref, o_ref, *, v_logical: int, n_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # per-step dot <= tile_v in magnitude: exact in f32, lossless int32 cast
    step = jnp.dot(q_ref[...], d_ref[...].T, preferred_element_type=jnp.float32)
    o_ref[...] += step.astype(jnp.int32)

    @pl.when(k == n_steps - 1)
    def _finalize():
        # agreements = (V + dot) // 2; exact -- V + dot is even whenever the
        # row is genuinely +-1, and integer floor-div matches the reference
        # for zero pad rows.
        o_ref[...] = (v_logical + o_ref[...]) // 2


def cosine_count_pallas(
    data_sgn: jnp.ndarray,
    query_sgn: jnp.ndarray,
    *,
    v_logical: int,
    tile_q: int = TILE_Q,
    tile_n: int = TILE_N,
    tile_v: int = TILE_V,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns int32 [Q, N] agreement counts (one dtype contract with the
    packed XOR+popcount path in packed_cosine.py).

    Inputs are +-1 (bf16/f32/int) pre-padded by ops.py: zero-fill on the V
    axis is dot-neutral, so `v_logical` (the unpadded V) sets the shift.
    """
    qn, v = query_sgn.shape
    nn = data_sgn.shape[0]
    assert qn % tile_q == 0 and nn % tile_n == 0 and v % tile_v == 0
    grid = (qn // tile_q, nn // tile_n, v // tile_v)
    kernel = functools.partial(
        _cosine_kernel, v_logical=v_logical, n_steps=v // tile_v
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, tile_v), lambda i, j, k: (i, k)),
            pl.BlockSpec((tile_n, tile_v), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, nn), jnp.int32),
        interpret=interpret,
    )(query_sgn.astype(jnp.bfloat16), data_sgn.astype(jnp.bfloat16))
