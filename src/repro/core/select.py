"""Unified top-k selection: one pipeline for every search path.

`select_topk` dispatches on `SearchParams.method` (c-PQ gate / SPQ bucket
narrowing / full sort) and optionally consumes the fused Pallas histogram
(kernels/cpq_hist) so the Gate reconstruction never re-reads the counts
matrix on the kernel path.

Its only caller is the unified executor (core/plan.py) -- the monolithic,
segmented, and distributed layouts all select through the same
per-part step there, which is what makes the selection strategy a
*parameter* of a search rather than a property of the call site: every
layout honours `method` exactly like single-device search does.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import cpq as _cpq
from repro.core import spq as _spq
from repro.core.types import SearchParams, TopKMethod, TopKResult
from repro.runtime import tracing


def select_topk(
    counts: jnp.ndarray,
    params: SearchParams,
    hist: jnp.ndarray | None = None,
    use_fused_hist: bool = False,
) -> TopKResult:
    """Exact top-k by match count.  counts: int [Q, N] -> TopKResult [Q, k].

    hist:           precomputed count histogram [Q, max_count + 1] (optional).
    use_fused_hist: compute the histogram with the Pallas kernel when `hist`
                    is not supplied (single-device kernel path; scan/shard_map
                    callers default to the jnp reference histogram).
    """
    if params.method == TopKMethod.CPQ:
        if hist is None and use_fused_hist:
            from repro.kernels import ops as kops

            with tracing.scope(tracing.HIST):
                hist = kops.cpq_hist(counts, params.max_count)
        return _cpq.cpq_select(counts, params, hist=hist)
    if params.method == TopKMethod.SPQ:
        return _spq.spq_select(counts, params)
    if params.method == TopKMethod.SORT:
        return _cpq.sort_select(counts, params)
    raise ValueError(f"unknown top-k method {params.method}")
