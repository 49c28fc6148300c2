"""Hierarchical top-k merge (paper section III-D's host merge, generalised).

The paper's multiple-loading strategy searches index parts independently and
merges per-part top-k results on the CPU.  At pod scale the same reduction
becomes a collective: every shard produces a cap-sized candidate buffer
(c-PQ Hash Table) and buffers are merged pairwise/hierarchically -- the merge
of two valid top-k buffers is a valid top-k buffer of the union (counts are
per-object totals when objects are *partitioned* across shards, so no
cross-shard count summation is needed).

These primitives are called only from the unified executor (core/plan.py),
which picks the strategy per layout: `merge_ragged` for host-streamed
heterogeneous parts, `merge_topk` for the distributed all-gather.

merge_topk    -- host/XLA merge of stacked per-part results.
tree_merge    -- log2(S) pairwise merge (the collective-friendly schedule).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import cpq as _cpq
from repro.core.types import TopKResult
from repro.runtime import tracing


@tracing.scoped(tracing.MERGE)
def merge_topk(ids: jnp.ndarray, counts: jnp.ndarray, k: int) -> TopKResult:
    """Merge per-part results.  ids/counts: int32 [S, Q, kp] (part-LOCAL top-k,
    ids already globalised) -> overall top-k [Q, k]."""
    s, q, kp = ids.shape
    flat_ids = jnp.transpose(ids, (1, 0, 2)).reshape(q, s * kp)
    flat_counts = jnp.transpose(counts, (1, 0, 2)).reshape(q, s * kp)
    out_ids, out_counts = _cpq.topk_from_candidates(flat_ids, flat_counts, k)
    return TopKResult(ids=out_ids, counts=out_counts, threshold=out_counts[:, -1])


@tracing.scoped(tracing.MERGE)
def merge_ragged(ids_list, counts_list, k: int) -> TopKResult:
    """Merge per-part top-k buffers of *heterogeneous* widths.

    ids_list/counts_list: per-part int32 [Q, kp_i] buffers (kp_i may differ --
    a part smaller than k contributes only min(k, n_part) candidates), ids
    already globalised.  Parts must partition the object set and arrive in
    ascending global-id order: the flattened candidate row is then globally
    id-ascending within equal counts, so the stable selection reproduces the
    monolithic (count desc, id asc) ordering exactly.
    """
    ids = jnp.concatenate(ids_list, axis=-1)
    counts = jnp.concatenate(counts_list, axis=-1)
    if ids.shape[-1] < k:  # fewer total candidates than k: pad empty slots
        pad = jnp.full((ids.shape[0], k - ids.shape[-1]), -1, dtype=jnp.int32)
        ids = jnp.concatenate([ids, pad], axis=-1)
        counts = jnp.concatenate([counts, pad], axis=-1)
    out_ids, out_counts = _cpq.topk_from_candidates(ids, counts, k)
    return TopKResult(ids=out_ids, counts=out_counts, threshold=out_counts[:, -1])


def merge_two(
    ids_a: jnp.ndarray, counts_a: jnp.ndarray, ids_b: jnp.ndarray, counts_b: jnp.ndarray, k: int
):
    """Pairwise merge of two [Q, k] buffers -> [Q, k]."""
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    counts = jnp.concatenate([counts_a, counts_b], axis=-1)
    return _cpq.topk_from_candidates(ids, counts, k)


@tracing.scoped(tracing.MERGE)
def tree_merge(ids: jnp.ndarray, counts: jnp.ndarray, k: int):
    """log2(S) pairwise merge of [S, Q, kp] buffers (ids globalised).

    Mirrors the recursive-doubling schedule a pod-level collective merge uses;
    produces identical results to merge_topk (tested).
    """
    s = ids.shape[0]
    while s > 1:
        half = (s + 1) // 2
        a_ids, a_cnt = ids[:half], counts[:half]
        b_ids = jnp.concatenate([ids[half:], jnp.full_like(ids[: 2 * half - s], -1)], axis=0)
        b_cnt = jnp.concatenate(
            [counts[half:], jnp.full_like(counts[: 2 * half - s], -1)], axis=0
        )
        merged_ids, merged_cnt = merge_two(a_ids, a_cnt, b_ids, b_cnt, min(k, a_ids.shape[-1] + b_ids.shape[-1]))
        ids, counts = merged_ids, merged_cnt
        s = half
    out_ids, out_counts = _cpq.topk_from_candidates(ids[0], counts[0], k)
    return TopKResult(ids=out_ids, counts=out_counts, threshold=out_counts[:, -1])
