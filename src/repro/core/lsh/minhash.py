"""MinHash LSH for Jaccard similarity over sets (paper section II-B1: "Jaccard
kernel for sets").

h_i(S) = min_{e in S} pi_i(e) with pi_i a random permutation (approximated by
the Murmur fmix32 bijection keyed per function).  Pr[h(S) = h(T)] = J(S, T),
which satisfies GENIE's LSH definition (Eqn 1) exactly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.lsh import rehash as _rehash
from repro.runtime import tracing


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MinHashParams:
    seeds: jnp.ndarray        # [m] uint32 per-function permutation seeds
    rehash_seeds: jnp.ndarray  # [m] uint32 seeds for the bucket projection
    n_buckets: int = dataclasses.field(metadata=dict(static=True))


def make(key, m: int, n_buckets: int = 8192, d: int | None = None) -> MinHashParams:
    """`d` is accepted (and ignored) so the scheme registry's uniform
    make_params(key, d=..., m=..., ...) call works -- minhash is
    dimension-free (permutations act on element ids, not coordinates)."""
    k1, k2 = jax.random.split(key)
    return MinHashParams(
        seeds=_rehash.make_seeds(k1, m),
        rehash_seeds=_rehash.make_seeds(k2, m),
        n_buckets=n_buckets,
    )


def hash_sets(params: MinHashParams, elements: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """MinHash signatures for padded element-id sets.

    elements: int32 [..., L]  element ids (padded rows allowed).
    valid:    bool  [..., L]  mask of real elements.
    returns:  int32 [..., m]  signatures in [0, n_buckets).
    """
    e = elements.astype(jnp.uint32)[..., None, :]          # [..., 1, L]
    seeds = params.seeds[:, None]                          # [m, 1]
    perm = _rehash.fmix32(e ^ seeds)                       # [..., m, L]
    big = jnp.uint32(0xFFFFFFFF)
    perm = jnp.where(valid[..., None, :], perm, big)
    mins = jnp.min(perm, axis=-1)                          # [..., m]
    return _rehash.rehash(mins.astype(jnp.int32), params.rehash_seeds, params.n_buckets)


@tracing.scoped(tracing.HASH)
def hash_points(params: MinHashParams, x: jnp.ndarray) -> jnp.ndarray:
    """MinHash dense vectors via their positive-support feature set.

    A vector x is read as the set {i : x_i > 0} (binarised feature support --
    the sparse ultra-high-dimensional regime FLASH targets), then minhashed
    with `hash_sets`.  Gives the scheme registry the uniform
    hash_points(params, x [..., d]) -> sigs [..., m] signature.
    """
    x = jnp.asarray(x)
    d = x.shape[-1]
    elems = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32), x.shape)
    return hash_sets(params, elems, x > 0)


def jaccard(a_elems, a_valid, b_elems, b_valid) -> float:
    """Host-side exact Jaccard for validation."""
    sa = set(int(x) for x, v in zip(a_elems, a_valid) if v)
    sb = set(int(x) for x, v in zip(b_elems, b_valid) if v)
    if not sa and not sb:
        return 1.0
    return len(sa & sb) / len(sa | sb)
