"""Distributed GENIE search over a (pod, data, model) TPU mesh: placement.

Objects are partitioned across *every* mesh axis (a pure data-parallel object
shard -- the match-count of an object depends only on its own data row),
queries are replicated, each shard runs the dense match + top-k on its local
partition, and the per-shard Hash-Table buffers are merged with an
all-gather + small-buffer select.  This is the paper's multiple-loading merge
turned into a collective, and is the search step lowered by the multi-pod
dry-run.

The search itself is a DISTRIBUTED `QueryPlan` (core/plan.py), executed on
the mesh the data is placed on:

    plan = plan_search(engine, k, max_count, layout=Layout.DISTRIBUTED,
                       n_objects=n, mesh_axes=tuple(mesh.axis_names),
                       hierarchical=...)
    result = execute(plan, jax.device_put(data, data_sharding(mesh)),
                     queries, mesh=mesh)

`n_objects` masks engine-filled pad rows (`SegmentedIndex.concat_data` pads
a ragged corpus up to mesh divisibility), and `hierarchical=True` on a mesh
with a leading "pod" axis merges pod-locally over ICI first, then across
pods over DCN -- merge order does not change the result, only the inter-pod
traffic (P_pods*Q*k instead of S*Q*k pairs).  This module holds the two
placements such a plan expects.

Communication cost per query batch: S * Q * k * 8 bytes of (id, count) pairs
-- independent of N, the point of shipping candidate buffers instead of
counts.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def data_sharding(mesh: jax.sharding.Mesh) -> jax.sharding.NamedSharding:
    """NamedSharding for the object-partitioned data matrix [N, ...]."""
    return jax.sharding.NamedSharding(mesh, P(tuple(mesh.axis_names)))


def replicated(mesh: jax.sharding.Mesh, ndim: int) -> jax.sharding.NamedSharding:
    return jax.sharding.NamedSharding(mesh, P(*([None] * ndim)))
