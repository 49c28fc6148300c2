"""Plain reference of the Adult deployment: per row, the number of
attributes whose bin lies in the query's [lo, hi], then the exact top-k
under (count desc, id asc).  It imports nothing of the program; the table
is made again from the run's seed, in blocks of rows.

The control breaks the order the configuration guarantees: among equal
counts it keeps the highest ids instead of the lowest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import adult
from plain import decode_keys, merge_topk, order_keys, to_host

BLOCKS = 4


@functools.partial(jax.jit, static_argnames=("k", "n_objects", "max_count",
                                             "ties_descending"))
def _block(rows, lo, hi, served, base, *, k: int, n_objects: int,
           max_count: int, ties_descending: bool):
    """Top-k keys and the counts of the served ids that lie in this block."""
    cols, lo_t, hi_t = rows.T, lo.T, hi.T

    def step(a, acc):
        x = cols[a][None, :]
        return acc + ((lo_t[a][:, None] <= x) & (x <= hi_t[a][:, None])
                      ).astype(jnp.int32)

    counts = jax.lax.fori_loop(0, rows.shape[1], step,
                               jnp.zeros((lo.shape[0], rows.shape[0]), jnp.int32))
    ids = base + jnp.arange(rows.shape[0], dtype=jnp.int32)
    keys = order_keys(counts, ids[None, :], n_objects, max_count,
                      ties_descending=ties_descending)
    local = served - base
    inside = (local >= 0) & (local < rows.shape[0])
    got = jnp.take_along_axis(counts, jnp.clip(local, 0, rows.shape[0] - 1), axis=1)
    return jax.lax.top_k(keys, k)[0], jnp.where(inside, got, 0)


def reference(cfg: dict, seed: int, query_rows: np.ndarray,
              served_ids: np.ndarray, k: int, control: bool = False):
    """(ids [q, k], counts [q, k], counts of the served ids [q, k]);
    `query_rows` is [q, 2, d] stacked (lo, hi)."""
    q = jnp.asarray(query_rows, jnp.int32)
    served = jnp.asarray(served_ids, jnp.int32)
    data = adult.table(cfg, seed)
    n = cfg["n_objects"]
    bounds = np.linspace(0, n, BLOCKS + 1).astype(int)
    tops, recount = [], jnp.zeros(served.shape, jnp.int32)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        top, got = _block(data[lo:hi], q[:, 0, :], q[:, 1, :], served,
                          jnp.int32(lo), k=k, n_objects=n, max_count=cfg["m"],
                          ties_descending=control)
        tops.append(top)
        recount = recount + got
    ids, counts = decode_keys(merge_topk(tops, k), n, ties_descending=control)
    return to_host(ids, counts, recount)
