"""Pallas TPU kernel: c-PQ count histogram (the Gate's ZipperArray source).

hist[q, t] = #{ n : counts[q, n] == t },  t in [0, max_count]

The c-PQ Gate (paper section III-C) needs ZA[t] = #{count >= t}; since counts
live in the bounded domain [0, max_count] (the Bitmap-Counter observation),
ZA is the suffix-sum of this histogram.  The kernel streams count tiles from
HBM and accumulates per-query histograms across the N grid axis; the
AuditThreshold and candidate compaction are computed from the histogram in
core/cpq.py.

Digit product.  Each count is split into two base-D digits, t = hi*D + lo,
with D the least power of two such that D*D >= max_count + 1 (4 for 15 bins,
16 for 238).  The count tile [TQ, TN] stays lane-dense: for every digit
value d the kernel compares the whole tile against d, and stacks the D
results digit-major into 0/1 operands [D*TQ, TN] (row d*TQ + r is query row
r's one-hot for digit d).  One MXU product contracting over the objects,

    P = onehot_hi . onehot_lo^T        [D*TQ, D*TQ]

gives P[d1*TQ + r, d2*TQ + r] = #{n : hi = d1, lo = d2} = hist[r, d1*D + d2]
on the entries where both rows belong to the same query; the rest mix two
queries and are discarded.  So the VPU does 2*D compares per count instead of
one per bin, and no count ever moves from lanes to sublanes.

Exactness: the operands are 0/1 in int8 and the products accumulate in
int32 (the MXU's integer path), summed across steps in an int32 block, so a
bin holds any count up to 2**31 - 1.  The last step of a row block reads
its histogram off P's same-query entries with two 0/1 selection products
per byte of the int32 sums, in bfloat16 with float32 accumulation, each
exact (at most one nonzero byte per output).

Counts outside [0, max_count] land in no bin the wrapper returns: a
negative one (the -1 of padded objects) has a negative high digit under the
arithmetic shift, one of D*D or more a high digit of D or more, and neither
matches a one-hot row; those in max_count + 1 .. D*D - 1 land in bins the
wrapper slices off.  The columns past the logical N of a ragged last tile
are masked the same way, so the count matrix is never padded.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

MXU_ROWS = 128  # stacked one-hot rows a step fills: TQ = MXU_ROWS // D (>= 8)
TILE_N = 16384  # counts per query row per grid step (512 KB at TQ = 8)
CHUNK_N = 4096  # counts per row per MXU product inside a step


def digit_base(max_count: int) -> int:
    """D: the least power of two with D * D >= max_count + 1."""
    base = 1
    while base * base < max_count + 1:
        base *= 2
    return base


def query_tile(base: int) -> int:
    """Query rows per step: fill the MXU's rows, at least one sublane tile."""
    return max(8, MXU_ROWS // base)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _stacked_onehot(digits: jnp.ndarray, base: int) -> jnp.ndarray:
    """[TQ, C] digits -> int8 [base*TQ, C], row d*TQ + r = (digits[r] == d)."""
    return jnp.concatenate(
        [(digits == d).astype(jnp.int32) for d in range(base)], axis=0
    ).astype(jnp.int8)


def _same_query_bins(acc: jnp.ndarray, tq: int, base: int, width: int):
    """int32 [tq, width]: out[r, d1*base + d2] = acc[d1*tq + r, d2*tq + r].

    Two 0/1 selection products per byte of acc (non-negative int32): spread
    moves column block d2 to the columns c with c % base == d2, keep drops
    the rows whose digit d1 != c // base, and gather sums the base rows of
    query r, of which one is left.  Every output is one byte: exact."""
    dt = base * tq
    rows, cols = _iota((dt, dt), 0), _iota((dt, dt), 1)
    same = jax.lax.rem(rows, tq) == jax.lax.rem(cols, tq)
    acc = jnp.where(same, acc, 0)
    t, c = _iota((dt, width), 0), _iota((dt, width), 1)
    spread = ((jax.lax.div(t, tq) == jax.lax.rem(c, base))
              & (c < base * base)).astype(jnp.bfloat16)
    keep = jax.lax.div(c, base) == jax.lax.div(t, tq)
    gather = (jax.lax.rem(_iota((tq, dt), 1), tq)
              == _iota((tq, dt), 0)).astype(jnp.bfloat16)
    out = jnp.zeros((tq, width), jnp.int32)
    for b in range(4):
        byte = ((acc >> (8 * b)) & 255).astype(jnp.float32).astype(jnp.bfloat16)
        f = jnp.dot(byte, spread, preferred_element_type=jnp.float32)
        f = jnp.where(keep, f, 0.0).astype(jnp.bfloat16)
        g = jnp.dot(gather, f, preferred_element_type=jnp.float32)
        out = out + (g.astype(jnp.int32) << (8 * b))
    return out


def _cpq_hist_kernel(c_ref, h_ref, acc_ref, *, base: int, n_valid: int,
                     chunk: int):
    j = pl.program_id(1)
    tq, tn = c_ref.shape
    dt = base * tq
    shift = base.bit_length() - 1

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def product(k, p):
        start = pl.multiple_of(k * chunk, chunk)
        c = c_ref[:, pl.ds(start, chunk)]                           # [TQ, C]
        col = j * tn + start + _iota((1, chunk), 1)
        # past the logical N a ragged tile holds stale data: digit -1 matches
        # no one-hot row.  A negative count's high digit is negative too
        # (arithmetic shift), and one of D*D or more has a high digit >= D.
        hi = jnp.where(col < n_valid, c >> shift, -1)
        lo = c & (base - 1)
        return p + jax.lax.dot_general(
            _stacked_onehot(hi, base), _stacked_onehot(lo, base),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)

    acc_ref[...] += jax.lax.fori_loop(0, tn // chunk, product,
                                      jnp.zeros((dt, dt), jnp.int32))

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        h_ref[...] = _same_query_bins(acc_ref[...], tq, base, h_ref.shape[1])


def cpq_hist_pallas(
    counts: jnp.ndarray,
    max_count: int,
    *,
    tile_q: int,
    tile_n: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """hist int32 [Q, W], W = D*D rounded up to 128 lanes, bin t at column t
    for t <= max_count; counts int32 [Q, N], any Q and N (ragged edge
    blocks are masked in the kernel).  tile_q % 8 == 0, tile_n % 128 == 0."""
    qn, nn = counts.shape
    base = digit_base(max_count)
    chunk = math.gcd(tile_n, CHUNK_N)
    assert tile_q % 8 == 0 and tile_n % 128 == 0
    width = common.ceil_to(base * base, 128)
    dt = base * tile_q
    kernel = functools.partial(_cpq_hist_kernel, base=base, n_valid=nn,
                               chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(qn, tile_q), pl.cdiv(nn, tile_n)),
        in_specs=[pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tile_q, width), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, width), jnp.int32),
        scratch_shapes=[pltpu.VMEM((dt, dt), jnp.int32)],
        interpret=interpret,
    )(counts)
