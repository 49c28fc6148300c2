"""Plain reference of the SIFT deployment: E2LSH in float32 at the highest
matmul precision, EQ counts against every corpus row, exact top-k under
(count desc, id asc).  It imports nothing of the program: the LSH
parameters are drawn from the same seed the service is given, the way
E2LSH defines them (Gaussian projections, uniform shifts in [0, w), uint32
rehash seeds, MurmurHash3's finalizer into the bucket domain), and the
corpus is made again, segment by segment, from the run's seed.

The control is this reference with its projections at bfloat16 three-pass
precision (`Precision.HIGH`), written out so that it computes the same on
every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import sift
from plain import decode_keys, merge_topk, order_keys, service_seed, to_host

GROUP = 8        # signature columns compared per step of the count loop


def lsh_params(cfg: dict, seed: int):
    """(a [m, d], b [m], seeds [m] uint32) drawn as E2LSH draws them."""
    ka, kb, ks = jax.random.split(jax.random.PRNGKey(service_seed(seed)), 3)
    m, d, w = cfg["m"], cfg["dim"], cfg["w"]
    a = jax.random.normal(ka, (m, d), dtype=jnp.float32)
    b = jax.random.uniform(kb, (m,), minval=0.0, maxval=w, dtype=jnp.float32)
    seeds = jax.random.randint(ks, (m,), minval=0, maxval=2**31 - 1,
                               dtype=jnp.int32).astype(jnp.uint32)
    return a, b, seeds


def _bf16(v):
    """`v` rounded to bfloat16's mantissa, kept in float32.  XLA never
    elides a `reduce_precision`, where it may fold a round trip through
    bfloat16 back to `v` (and so the low halves below to zero)."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _project(x, a, control: bool):
    dot = functools.partial(jnp.einsum, "nd,md->nm",
                            precision=jax.lax.Precision.HIGHEST)
    if not control:
        return dot(x, a)
    # bfloat16 three-pass: each product of two bfloat16 values is exact in
    # float32, so the passes compute the same on every backend
    xh, ah = _bf16(x), _bf16(a)
    xl, al = _bf16(x - xh), _bf16(a - ah)
    return dot(xh, al) + dot(xl, ah) + dot(xh, ah)


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


@functools.partial(jax.jit, static_argnames=("w", "n_buckets", "control"))
def signatures(x, a, b, seeds, *, w: float, n_buckets: int, control: bool):
    """E2LSH signatures int32 [n, m] in [0, n_buckets)."""
    raw = jnp.floor((_project(x, a, control) + b) / w).astype(jnp.int32)
    mixed = _fmix32(raw.astype(jnp.uint32) ^ seeds)
    return (mixed % jnp.uint32(n_buckets)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "n_objects", "max_count"))
def _block(sig, qsig, served, base, *, k: int, n_objects: int, max_count: int):
    """Top-k keys and the counts of the served ids that lie in this block."""
    pad = (-sig.shape[1]) % GROUP
    s = jnp.pad(sig, ((0, 0), (0, pad)), constant_values=-1).T   # [M, B]
    q = jnp.pad(qsig, ((0, 0), (0, pad)), constant_values=-2).T  # [M, Q]

    def step(g, acc):
        sg = jax.lax.dynamic_slice_in_dim(s, g * GROUP, GROUP, 0)
        qg = jax.lax.dynamic_slice_in_dim(q, g * GROUP, GROUP, 0)
        return acc + jnp.sum(qg[:, :, None] == sg[:, None, :], axis=0,
                             dtype=jnp.int32)

    counts = jax.lax.fori_loop(0, s.shape[0] // GROUP, step,
                               jnp.zeros((q.shape[1], s.shape[1]), jnp.int32))
    ids = base + jnp.arange(s.shape[1], dtype=jnp.int32)
    keys = order_keys(counts, ids[None, :], n_objects, max_count)
    local = served - base
    inside = (local >= 0) & (local < s.shape[1])
    got = jnp.take_along_axis(counts, jnp.clip(local, 0, s.shape[1] - 1), axis=1)
    return jax.lax.top_k(keys, k)[0], jnp.where(inside, got, 0)


def reference(cfg: dict, seed: int, query_rows: np.ndarray,
              served_ids: np.ndarray, k: int, control: bool = False):
    """(ids [q, k], counts [q, k], counts of the served ids [q, k])."""
    a, b, seeds = lsh_params(cfg, seed)
    sig_fn = functools.partial(signatures, a=a, b=b, seeds=seeds, w=cfg["w"],
                               n_buckets=cfg["n_buckets"], control=control)
    qsig = sig_fn(jnp.asarray(query_rows, jnp.float32))
    served = jnp.asarray(served_ids, jnp.int32)
    bounds = sift.segment_bounds(cfg)
    tops, recount = [], jnp.zeros(served.shape, jnp.int32)
    for s in range(cfg["n_segments"]):
        top, got = _block(sig_fn(sift.points(cfg, seed, s)), qsig, served,
                          jnp.int32(bounds[s]), k=k, n_objects=cfg["n_objects"],
                          max_count=cfg["m"])
        tops.append(top)
        recount = recount + got
    ids, counts = decode_keys(merge_topk(tops, k), cfg["n_objects"])
    return to_host(ids, counts, recount)
