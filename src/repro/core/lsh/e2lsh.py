"""E2LSH: p-stable locality sensitive hashing (paper Eqn 10/11, Datar et al.).

h(q) = floor((a . q + b) / w) with `a` drawn from a p-stable distribution
(Gaussian for l2, Cauchy for l1) and b ~ U[0, w).

The collision probability (paper Eqn 11)

    psi_p(delta) = Pr[h(p) = h(q)]
                 = int_0^w (1/delta) phi_p(t/delta) (1 - t/w) dt

is strictly monotonically decreasing in delta = ||p - q||_p, so it defines the
similarity measure sim_lp (Eqn 12) under which GENIE performs tau-ANN search.
Closed forms are implemented below for l1 and l2.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.lsh import rehash as _rehash
from repro.runtime import tracing


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class E2LSHParams:
    a: jnp.ndarray          # [m, d] p-stable projection vectors
    b: jnp.ndarray          # [m]    uniform shifts in [0, w)
    seeds: jnp.ndarray      # [m]    uint32 rehash seeds
    w: float = dataclasses.field(metadata=dict(static=True))
    p: int = dataclasses.field(metadata=dict(static=True))
    n_buckets: int = dataclasses.field(metadata=dict(static=True))


def make(key, d: int, m: int, w: float, p: int = 2, n_buckets: int = 8192) -> E2LSHParams:
    """Create m independent p-stable LSH functions for d-dim points."""
    ka, kb, ks = jax.random.split(key, 3)
    if p == 2:
        a = jax.random.normal(ka, (m, d), dtype=jnp.float32)
    elif p == 1:
        a = jax.random.cauchy(ka, (m, d), dtype=jnp.float32)
    else:
        raise ValueError(f"p-stable sampling implemented for p in (1, 2), got {p}")
    b = jax.random.uniform(kb, (m,), minval=0.0, maxval=w, dtype=jnp.float32)
    return E2LSHParams(a=a, b=b, seeds=_rehash.make_seeds(ks, m), w=w, p=p, n_buckets=n_buckets)


def raw_hash(params: E2LSHParams, x: jnp.ndarray) -> jnp.ndarray:
    """floor((a.x + b)/w) -> int32 [..., m] (pre-rehash bucket coordinates)."""
    # HIGHEST: the TPU's default f32 matmul rounds its inputs to bf16, which
    # moves projections across bucket edges; full f32 hashes the same
    # function on every backend
    proj = jnp.einsum("...d,md->...m", x.astype(jnp.float32), params.a,
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.floor((proj + params.b) / params.w).astype(jnp.int32)


@tracing.scoped(tracing.HASH)
def hash_points(params: E2LSHParams, x: jnp.ndarray) -> jnp.ndarray:
    """Full GENIE transform: signatures int32 [..., m] in [0, n_buckets)."""
    return _rehash.rehash(raw_hash(params, x), params.seeds, params.n_buckets)


# ---------------------------------------------------------------------------
# Collision probability psi_p (paper Eqn 11) -- closed forms.
# ---------------------------------------------------------------------------

def collision_prob_l2(dist, w: float):
    """psi_2(delta) for Gaussian projections (Datar et al. Eqn in section 3.2)."""
    dist = jnp.maximum(jnp.asarray(dist, dtype=jnp.float32), 1e-12)
    r = w / dist
    # 1 - 2*Phi(-r) - (2/(sqrt(2 pi) r)) * (1 - exp(-r^2/2))
    phi_neg = 0.5 * (1.0 + jax.scipy.special.erf(-r / math.sqrt(2.0)))
    return 1.0 - 2.0 * phi_neg - (2.0 / (math.sqrt(2.0 * math.pi) * r)) * (
        1.0 - jnp.exp(-(r**2) / 2.0)
    )


def collision_prob_l1(dist, w: float):
    """psi_1(delta) for Cauchy projections."""
    dist = jnp.maximum(jnp.asarray(dist, dtype=jnp.float32), 1e-12)
    r = w / dist
    return (2.0 * jnp.arctan(r) / math.pi) - (1.0 / (math.pi * r)) * jnp.log1p(r**2)


def collision_prob(dist, w: float, p: int):
    if p == 2:
        return collision_prob_l2(dist, w)
    if p == 1:
        return collision_prob_l1(dist, w)
    raise ValueError(f"unsupported p={p}")


def similarity(params: E2LSHParams, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """sim_lp(p, q) = psi_p(||p-q||_p)  (paper Eqn 12)."""
    if params.p == 2:
        d = jnp.linalg.norm(x - y, axis=-1)
    else:
        d = jnp.sum(jnp.abs(x - y), axis=-1)
    return collision_prob(d, params.w, params.p)
