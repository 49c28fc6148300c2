"""Distributed GENIE search over a (pod, data, model) TPU mesh.

Objects are partitioned across *every* mesh axis (a pure data-parallel object
shard -- the match-count of an object depends only on its own data row),
queries are replicated, each shard runs the dense match + top-k on its local
partition, and the per-shard Hash-Table buffers are merged with an
all-gather + small-buffer select.  This is the paper's multiple-loading merge
turned into a collective, and is the `search_step` lowered by the multi-pod
dry-run.

Both step builders are thin adapters over the unified planner (core/plan.py):
they describe the search as a DISTRIBUTED `QueryPlan` and return the planner's
compiled executable, so the shard_map body -- match dispatch, pad masking,
selection, collective merge -- lives in exactly one place and is cached per
(engine, layout, k, method, use_kernel) across repeated step constructions.

Engines are resolved through the MatchModel registry (core/engines.py): pass
an `Engine`, its string value, a `MatchModel`, or a raw canonical callable
``fn(data, queries) -> counts`` -- every registered engine (EQ, RANGE,
MINSUM, IP, TANIMOTO, COSINE) shards identically because the canonical
signature hides the query pytree shape (RANGE replicates its (lo, hi) pair).
`SearchParams.use_kernel` selects the per-shard match implementation, so the
Pallas kernels run *inside* shard_map on each shard's local partition --
kernel dispatch is no longer reference-only at pod scale.

Communication cost per query batch: S * Q * k * 8 bytes of (id, count) pairs
-- independent of N, the point of shipping candidate buffers instead of
counts.
"""
from __future__ import annotations

from typing import Any, Callable, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import engines as _engines
from repro.core import plan as _plan
from repro.core.types import Engine, SearchParams, SignatureLayout, TopKResult

MatchLike = Union[Engine, str, "_engines.MatchModel",
                  Callable[[jnp.ndarray, Any], jnp.ndarray]]


def _plan_sharded(mesh: jax.sharding.Mesh, params: SearchParams,
                  match_fn: MatchLike, n_objects: int | None,
                  hierarchical: bool,
                  signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
                  ) -> _plan.QueryPlan:
    return _plan.plan_search(
        match_fn, params.k, params.max_count, layout=_plan.Layout.DISTRIBUTED,
        n_objects=n_objects, method=params.method,
        candidate_cap=params.candidate_cap, use_kernel=params.use_kernel,
        hierarchical=hierarchical, mesh_axes=tuple(mesh.axis_names),
        signature_layout=signature_layout,
    )


def make_search_step(
    mesh: jax.sharding.Mesh,
    params: SearchParams,
    match_fn: MatchLike,
    n_objects: int | None = None,
    signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
) -> Callable[[jnp.ndarray, Any], TopKResult]:
    """Build the jittable distributed search step.

    data:    [N, ...] (N divisible by the total mesh size; sharded dim 0).
    queries: canonical query pytree, replicated (each leaf [Q, ...]).
    Returns replicated TopKResult with global object ids.

    `params.use_kernel` picks the per-shard match path (Pallas kernel vs
    jnp reference) when `match_fn` resolves through the registry.

    `n_objects` enables the *segmented* shard layout: data is segments
    concatenated in global-id order and padded up to mesh divisibility
    (SegmentedIndex.concat_data), and rows with global id >= n_objects are
    pad fill -- their counts are forced to -1 before per-shard selection so
    they can never reach any candidate buffer.

    `signature_layout=PACKED` dispatches the packed per-shard match kernels:
    data and queries must arrive already packed (core/packing.py -- a PACKED
    SegmentedIndex's concat_data / prepare_queries_for produce them), so
    every shard moves the bit-packed bytes and the all-gathered candidate
    traffic is unchanged.
    """
    plan = _plan_sharded(mesh, params, match_fn, n_objects, hierarchical=False,
                         signature_layout=signature_layout)
    return _plan.executable(plan, mesh=mesh)


def make_hierarchical_search_step(
    mesh: jax.sharding.Mesh,
    params: SearchParams,
    match_fn: MatchLike,
    n_objects: int | None = None,
    signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
):
    """Two-level merge variant: reduce candidate buffers inside a pod first
    (cheap ICI), then across pods (expensive DCN) -- merge order does not
    change the result (merge is associative on partitioned objects), but the
    inter-pod traffic drops from S*Q*k to P_pods*Q*k pairs.

    Only meaningful on meshes with a leading "pod" axis; falls back to the
    flat merge otherwise.  `n_objects` masks segmented-layout pad rows,
    exactly as in `make_search_step`.
    """
    hier = tuple(mesh.axis_names)[0] == "pod"
    plan = _plan_sharded(mesh, params, match_fn, n_objects, hierarchical=hier,
                         signature_layout=signature_layout)
    return _plan.executable(plan, mesh=mesh)


def data_sharding(mesh: jax.sharding.Mesh) -> jax.sharding.NamedSharding:
    """NamedSharding for the object-partitioned data matrix [N, ...]."""
    return jax.sharding.NamedSharding(mesh, P(tuple(mesh.axis_names)))


def replicated(mesh: jax.sharding.Mesh, ndim: int) -> jax.sharding.NamedSharding:
    return jax.sharding.NamedSharding(mesh, P(*([None] * ndim)))
