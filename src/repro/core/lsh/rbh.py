"""Random Binning Hashing (RBH) for the Laplacian kernel (paper section IV-A3).

Rahimi & Recht random features: for a separable kernel k(p,q) = prod_d k1(|p_d - q_d|)
whose per-dim kernel k1 has p(g) = g * k1''(g) a valid density on g >= 0, impose a
randomly shifted grid with pitch g ~ p(g) and shift u ~ U[0, g] per dimension:

    h(p) = [ floor((p_1 - u_1)/g_1), ..., floor((p_d - u_d)/g_d) ]      (paper Eqn 2)

Then Pr[h(p) = h(q)] = k(p, q).  For the Laplacian kernel
k(p,q) = exp(-||p-q||_1 / sigma), the pitch density per dimension is
p(g) = (g / sigma^2) exp(-g / sigma), i.e. Gamma(shape=2, scale=sigma).

The signature is a d-dimensional integer vector -- a huge space -- so GENIE
re-hashes it into [0, D) with r(.) (rehash.rehash_vector).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.lsh import rehash as _rehash
from repro.runtime import tracing


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RBHParams:
    g: jnp.ndarray            # [m, d] grid pitches ~ Gamma(2, sigma)
    u: jnp.ndarray            # [m, d] shifts ~ U[0, g]
    dim_seeds: jnp.ndarray    # [m, d] uint32 per-coordinate combine seeds
    sigma: float = dataclasses.field(metadata=dict(static=True))
    n_buckets: int = dataclasses.field(metadata=dict(static=True))


def make(key, d: int, m: int, sigma: float, n_buckets: int = 8192) -> RBHParams:
    kg, ku, ks = jax.random.split(key, 3)
    # Gamma(shape=2, scale=sigma): sum of two Exp(scale=sigma) draws.
    g = sigma * (jax.random.gamma(kg, 2.0, (m, d), dtype=jnp.float32))
    u = jax.random.uniform(ku, (m, d), dtype=jnp.float32) * g
    dim_seeds = jax.random.randint(ks, (m, d), 0, 2**31 - 1, dtype=jnp.int32).astype(jnp.uint32)
    return RBHParams(g=g, u=u, dim_seeds=dim_seeds, sigma=sigma, n_buckets=n_buckets)


def raw_hash(params: RBHParams, x: jnp.ndarray) -> jnp.ndarray:
    """Grid coordinates int32 [..., m, d]."""
    # x: [..., d];  g,u: [m, d]
    x = x[..., None, :]  # [..., 1, d]
    return jnp.floor((x - params.u) / params.g).astype(jnp.int32)


@tracing.scoped(tracing.HASH)
def hash_points(params: RBHParams, x: jnp.ndarray) -> jnp.ndarray:
    """Signatures int32 [..., m] in [0, n_buckets) (vector signature re-hashed)."""
    cells = raw_hash(params, x)  # [..., m, d]
    m, d = params.g.shape
    # rehash_vector folds the d grid coordinates of each function; vmap over m.
    def fold_one(cells_m, seeds_m):
        return _rehash.rehash_vector(cells_m, seeds_m, params.n_buckets)

    # cells: [..., m, d] -> move m first for vmap
    cells_mf = jnp.moveaxis(cells, -2, 0)  # [m, ..., d]
    folded = jax.vmap(fold_one)(cells_mf, params.dim_seeds)  # [m, ...]
    return jnp.moveaxis(folded, 0, -1)  # [..., m]


def kernel(x: jnp.ndarray, y: jnp.ndarray, sigma: float) -> jnp.ndarray:
    """Laplacian kernel k(p,q) = exp(-||p-q||_1 / sigma) == expected collision prob."""
    return jnp.exp(-jnp.sum(jnp.abs(x - y), axis=-1) / sigma)


def median_heuristic_sigma(points: jnp.ndarray, key, n_pairs: int = 2048) -> float:
    """Kernel-width heuristic used in the paper (Jaakkola et al.): mean pairwise
    l1 distance over a random sample."""
    n = points.shape[0]
    ki, kj = jax.random.split(key)
    i = jax.random.randint(ki, (n_pairs,), 0, n)
    j = jax.random.randint(kj, (n_pairs,), 0, n)
    d = jnp.sum(jnp.abs(points[i] - points[j]), axis=-1)
    return float(jnp.mean(d))
