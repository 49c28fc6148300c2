"""The deep-image-96-angular deployment (configs/deep96.json) as a tenant of
the served path.

Points and queries are unit-length clustered Gaussian vectors made on the
device from the seed; the tenant is a `RetrievalService` with SimHash and
the PACKED signature layout (32 signs to an int32 word), created through
`ServingFrontend.create_tenant` and filled by `n_segments` calls of `add`,
one sealed segment each.  Its searches take the fused match -> count ->
local top-k kernel.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from plain import data_key, service_seed

TENANT = "deep96"
CENTRES, POINTS, QUERIES = 0, 1, 2     # data streams of one seed


@functools.partial(jax.jit, static_argnames=("rows", "dim", "clusters"))
def _unit_clustered(centre_key, key, *, rows: int, dim: int, clusters: int,
                    centre_scale: float, cluster_std: float):
    centres = jax.random.normal(centre_key, (clusters, dim), jnp.float32)
    kl, kn = jax.random.split(key)
    labels = jax.random.randint(kl, (rows,), 0, clusters)
    noise = jax.random.normal(kn, (rows, dim), jnp.float32)
    x = centres[labels] * centre_scale + noise * cluster_std
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


def segment_bounds(cfg: dict) -> np.ndarray:
    return np.linspace(0, cfg["n_objects"], cfg["n_segments"] + 1).astype(int)


def _points(cfg: dict, seed: int, stream, rows: int):
    return _unit_clustered(data_key(seed, CENTRES), stream, rows=rows,
                           dim=cfg["dim"], clusters=cfg["clusters"],
                           centre_scale=cfg["centre_scale"],
                           cluster_std=cfg["cluster_std"])


def points(cfg: dict, seed: int, segment: int):
    """The corpus rows of one added segment, on the device [rows, dim]."""
    lo, hi = segment_bounds(cfg)[segment:segment + 2]
    return _points(cfg, seed,
                   jax.random.fold_in(data_key(seed, POINTS), segment),
                   int(hi - lo))


def queries(cfg: dict, seed: int, n: int) -> np.ndarray:
    """The query pool [n, dim] float32, as requests send it."""
    return np.asarray(_points(cfg, seed, data_key(seed, QUERIES), n))


def build(cfg: dict, seed: int, frontend, log):
    """Create and fill the tenant; returns its backend."""
    svc = frontend.create_tenant(
        TENANT, embed_fn=np.asarray, scheme=cfg["scheme"],
        signature_layout=cfg["signature_layout"], m_override=cfg["m"],
        seed=service_seed(seed))
    bounds = segment_bounds(cfg)
    made = added = 0.0
    for s in range(cfg["n_segments"]):
        t0 = time.perf_counter()
        pts = jax.block_until_ready(points(cfg, seed, s))
        t1 = time.perf_counter()
        frontend.add(TENANT, range(bounds[s], bounds[s + 1]), embeddings=pts)
        del pts
        made += t1 - t0
        added += time.perf_counter() - t1
    stats = svc.index_stats
    if stats.n_segments != cfg["n_segments"] or stats.compaction_count:
        raise RuntimeError(f"expected {cfg['n_segments']} segments and no "
                           f"compaction, got {stats.n_segments} / "
                           f"{stats.compaction_count}")
    log(f"set-up points: {made:.2f} s; adds: {added:.2f} s "
        f"({stats.n_objects} rows in {stats.n_segments} segments, "
        f"{stats.bytes_device} bytes of packed signatures)")
    return svc


def least_bytes(cfg: dict, rows: int, k: int) -> int:
    """The fewest HBM bytes one dispatch of `rows` query rows can move: every
    stored object's m bits once, at whole bytes, the query rows' bits, and
    k ids and counts out per row."""
    sig = math.ceil(cfg["m"] / 8)
    return cfg["n_objects"] * sig + rows * sig + rows * k * 8
