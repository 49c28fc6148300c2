"""Plain reference of the deep-image-96-angular deployment: SimHash signs of
float64 NumPy projections, agreement counts as m - Hamming distance on the
unpacked bits against every corpus row, exact top-k under (count desc,
id asc).  No packing, kernel, segments or batching: the corpus is walked in
blocks only to bound memory, and the blocks' top-k merge on the host.

It imports nothing of the program: the SimHash planes are drawn from the
seed the service is given, the way SimHash draws them (one standard normal
[m, dim] float32 matrix from that seed's PRNGKey), and the corpus is made
again, block by block, from the run's seed.  A bit is set where the
projection is >= 0, as SimHash sets it.

The control is this reference with its projections in bfloat16: points and
planes rounded to bfloat16 before the float64 product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import deep96
from plain import service_seed

GROUP = 8        # bit columns compared per step of the count loop
BLOCK = 1 << 17  # most corpus rows per block: the chip's top-k of a block
                 # of [rows, BLOCK] keys must fit its vector memory


def planes(cfg: dict, seed: int) -> np.ndarray:
    """The SimHash planes [m, dim], as float64."""
    v = jax.random.normal(jax.random.PRNGKey(service_seed(seed)),
                          (cfg["m"], cfg["dim"]), dtype=jnp.float32)
    return np.asarray(v, dtype=np.float64)


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float32).astype(jnp.bfloat16).astype(np.float64)


def signs(x, v: np.ndarray, control: bool = False) -> np.ndarray:
    """SimHash bits uint8 [n, m]: 1 where the projection onto a plane is >= 0."""
    x = np.asarray(x, dtype=np.float64)
    if control:
        x, v = _bf16(x), _bf16(v)
    return (x @ v.T >= 0).astype(np.uint8)


@functools.partial(jax.jit, static_argnames=("k",))
def _block(bits, qbits, served, base, *, k: int):
    """(counts [q, k], ids [q, k]) of this block's top-k and the counts of
    the served ids that lie in it (0 elsewhere)."""
    m = bits.shape[1]
    pad = (-m) % GROUP
    s = jnp.pad(bits, ((0, 0), (0, pad))).T.astype(jnp.int32)        # [M, B]
    q = jnp.pad(qbits, ((0, 0), (0, pad))).T.astype(jnp.int32)       # [M, Q]

    def step(g, acc):
        sg = jax.lax.dynamic_slice_in_dim(s, g * GROUP, GROUP, 0)
        qg = jax.lax.dynamic_slice_in_dim(q, g * GROUP, GROUP, 0)
        return acc + jnp.sum(qg[:, :, None] != sg[:, None, :], axis=0,
                             dtype=jnp.int32)

    # the pad columns are 0 on both sides, so they add no distance
    hamming = jax.lax.fori_loop(0, s.shape[0] // GROUP, step,
                                jnp.zeros((q.shape[1], s.shape[1]), jnp.int32))
    counts = m - hamming
    rows = s.shape[1]
    low = max(1, (rows - 1).bit_length())
    if (m + 1) << low > 1 << 31:
        raise ValueError(f"counts up to {m} over blocks of {rows} rows do "
                         f"not fit one int32 key")
    local = jnp.arange(rows, dtype=jnp.int32)
    # unique keys whose descending order is count desc, then id asc
    keys = (counts << low) | ((1 << low) - 1 - local)[None, :]
    top = jax.lax.top_k(keys, k)[0]
    mine = served - base
    inside = (mine >= 0) & (mine < rows)
    got = jnp.take_along_axis(counts, jnp.clip(mine, 0, rows - 1), axis=1)
    return (top >> low, base + (1 << low) - 1 - (top & ((1 << low) - 1)),
            jnp.where(inside, got, 0))


def reference(cfg: dict, seed: int, query_rows: np.ndarray,
              served_ids: np.ndarray, k: int, control: bool = False):
    """(ids [q, k], counts [q, k], counts of the served ids [q, k]); slots
    past the corpus hold id -1 and count -1."""
    v = planes(cfg, seed)
    qbits = jnp.asarray(signs(query_rows, v, control))
    served = jnp.asarray(served_ids, jnp.int32)
    bounds = deep96.segment_bounds(cfg)
    counts, ids, recount = [], [], 0
    for s in range(len(bounds) - 1):
        bits = signs(np.asarray(deep96.points(cfg, seed, s)), v, control)
        lo = bounds[s]
        for block in np.array_split(bits, -(-bits.shape[0] // BLOCK)):
            c, i, got = _block(jnp.asarray(block), qbits, served, jnp.int32(lo),
                               k=min(k, block.shape[0]))
            lo += block.shape[0]
            counts.append(np.asarray(c))
            ids.append(np.asarray(i))
            recount = recount + np.asarray(got)
    counts = np.concatenate(counts, axis=1).astype(np.int64)
    ids = np.concatenate(ids, axis=1).astype(np.int64)
    order = np.argsort(-((counts << 32) - ids), axis=1, kind="stable")[:, :k]
    top_ids = np.take_along_axis(ids, order, axis=1)
    top_counts = np.take_along_axis(counts, order, axis=1)
    short = k - top_ids.shape[1]
    if short > 0:
        fill = np.full((top_ids.shape[0], short), -1, np.int64)
        top_ids = np.concatenate([top_ids, fill], axis=1)
        top_counts = np.concatenate([top_counts, fill], axis=1)
    # an empty slot (id -1) holds count -1, as the served path writes it
    recount = np.where(np.asarray(served_ids) < 0, -1, recount)
    return (top_ids.astype(np.int32), top_counts.astype(np.int32),
            recount.astype(np.int32))
