"""SimHash (signed random projection) LSH for angular / cosine similarity.

Charikar (paper ref [5]): h_v(p) = sign(v . p) with v ~ N(0, I) satisfies

    Pr[h(p) = h(q)] = 1 - theta(p, q) / pi

which is a valid GENIE LSH family (Eqn 1) under the angular similarity
sim(p,q) = 1 - theta/pi.  Signatures are single bits, so the match-count
domain is exactly m and no re-hashing is needed (D = 2; the 1/D re-hash
collision term of Theorem 4.1 does not apply because r is the identity).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import tracing


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimHashParams:
    v: jnp.ndarray  # [m, d]


def make(key, d: int, m: int) -> SimHashParams:
    return SimHashParams(v=jax.random.normal(key, (m, d), dtype=jnp.float32))


@tracing.scoped(tracing.HASH)
def hash_points(params: SimHashParams, x: jnp.ndarray) -> jnp.ndarray:
    # HIGHEST: full f32 projections, so a sign is the same on every backend
    # (the TPU's default rounds matmul inputs to bf16; see e2lsh.raw_hash)
    proj = jnp.einsum("...d,md->...m", x.astype(jnp.float32), params.v,
                      precision=jax.lax.Precision.HIGHEST)
    return (proj >= 0).astype(jnp.int32)


def mle_cosine(count, m: int):
    """Cosine estimate from a sign-agreement count (the COSINE engine's MLE).

    c agreements out of m bits give Pr[agree] = 1 - theta/pi (Charikar), so
    theta_hat = pi * (1 - c/m) and cos_hat = cos(theta_hat).  Host-side, like
    tau_ann.mle_similarity (Eqn 7).
    """
    frac = np.clip(np.asarray(count, dtype=np.float64) / float(m), 0.0, 1.0)
    return np.cos(math.pi * (1.0 - frac))


def similarity(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Angular similarity 1 - theta/pi."""
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    yn = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-12)
    cos = jnp.clip(jnp.sum(xn * yn, axis=-1), -1.0, 1.0)
    return 1.0 - jnp.arccos(cos) / math.pi
