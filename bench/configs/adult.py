"""The Adult deployment (configs/adult.json) as a tenant of the served path.

The table is made on the device from the seed, one inverse-CDF draw per
attribute value, and loaded with one `add` into an `IndexService` over a
RANGE `SegmentedIndex`.  Requests send their rows stacked as [q, 2, d]
(lo, hi), which the service's query adapter splits.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from plain import data_key, int_bytes

TENANT = "adult"
TABLE, QUERIES = 0, 1      # data streams of one seed


def split_bounds(stacked):
    """[q, 2, d] stacked (lo, hi) -> the RANGE engine's (lo, hi) pair."""
    return stacked[:, 0, :], stacked[:, 1, :]


def _attribute_tables(cfg: dict):
    """Per attribute: the cumulative distribution over its values and the
    bin of each value, padded to the widest attribute.  The last value's
    cumulative share is set above 1, so every uniform draw lands on a value."""
    width = max(a["card"] for a in cfg["attributes"])
    cdf = np.full((len(cfg["attributes"]), width), 2.0, np.float32)
    bins = np.zeros((len(cfg["attributes"]), width), np.int32)
    for i, a in enumerate(cfg["attributes"]):
        v = np.arange(a["card"])
        p = (1.0 + v) ** -float(a["zipf"])
        cdf[i, :a["card"] - 1] = (np.cumsum(p) / p.sum())[:-1]
        bins[i, :a["card"]] = np.floor((v + 0.5) * cfg["n_buckets"] / a["card"])
    return cdf, bins


@functools.partial(jax.jit, static_argnames=("rows",))
def _tuples(key, cdf, bins, *, rows: int):
    """[rows, d] int32 bins, one inverse-CDF draw per attribute."""
    u = jax.random.uniform(key, (cdf.shape[0], rows), jnp.float32)
    vals = jax.vmap(lambda c, x: jnp.searchsorted(c, x, side="right"))(cdf, u)
    return jnp.take_along_axis(bins, vals, axis=1).T.astype(jnp.int32)


def table(cfg: dict, seed: int):
    """The whole table on the device [n_objects, d] int32."""
    cdf, bins = _attribute_tables(cfg)
    return _tuples(data_key(seed, TABLE), cdf, bins, rows=cfg["n_objects"])


def queries(cfg: dict, seed: int, n: int) -> np.ndarray:
    """The query pool [n, 2, d] int32: tuples widened by the half-width."""
    cdf, bins = _attribute_tables(cfg)
    t = np.asarray(_tuples(data_key(seed, QUERIES), cdf, bins, rows=n))
    h = cfg["range_halfwidth"]
    return np.stack([t - h, t + h], axis=1).astype(np.int32)


def build(cfg: dict, seed: int, frontend, log):
    """Create, register and load the tenant; returns its backend."""
    from repro.core import Engine, SegmentedIndex
    from repro.serve.frontend import IndexService

    t0 = time.perf_counter()
    rows = jax.block_until_ready(table(cfg, seed))
    t1 = time.perf_counter()
    svc = frontend.register(TENANT, IndexService(
        SegmentedIndex(engine=Engine.RANGE), query_adapter=split_bounds))
    frontend.add(TENANT, None, embeddings=rows)
    del rows
    stats = svc.index.stats
    if stats.n_segments != cfg["n_segments"]:
        raise RuntimeError(f"expected {cfg['n_segments']} segments, got "
                           f"{stats.n_segments}")
    log(f"set-up points: {t1 - t0:.2f} s; adds: {time.perf_counter() - t1:.2f} s "
        f"({stats.n_objects} rows in {stats.n_segments} segments)")
    return svc


def least_bytes(cfg: dict, rows: int, k: int) -> int:
    """The fewest HBM bytes one dispatch of `rows` query rows can move: every
    stored value once at the narrowest width its bins allow, each row's lo
    and hi bounds, and k ids and counts out per row."""
    width = int_bytes(cfg["n_buckets"])
    return (cfg["n_objects"] * cfg["m"] * width + rows * 2 * cfg["m"] * width
            + rows * k * 8)
