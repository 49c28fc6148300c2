"""Unified query planning + execution: one plan -> execute pipeline for every
GENIE search path.

Every search door -- `GenieIndex.search`, `SegmentedIndex.search`, and the
mesh-sharded serving path -- describes its search as a `QueryPlan` and
delegates here, so engine dispatch, pad masking, per-part k-clamping, and
top-k merging live in one place (the Faiss plan/execute split of Johnson et
al. 1702.08734, FLASH's host-orchestrated part streaming for memory-bound
corpora):

  * `plan_search(...)` is the single entry point that describes a search as a
    `QueryPlan`: the engine, the part layout (monolithic / streamed parts /
    mesh shards), the pad policy, the per-part k clamp, and the merge
    strategy.
  * `execute(plan, data, queries)` is the ONLY code in the system that calls
    match kernels, pad masking, `select_topk`, and the `core/merge` buffers.
  * Compiled executables are cached per plan (`_EXEC_CACHE`): repeated
    queries with the same (engine, layout shape, k, method, use_kernel)
    reuse the jitted program instead of re-tracing.  `trace_count(plan)`
    exposes the per-plan trace counter so tests (and the serve-latency
    benchmark) can assert cache hits.

The three layouts and their merge strategies:

  MONOLITHIC   one device-resident part; selection IS the merge.
  SEGMENTED    host loop over parts of any (heterogeneous) row counts, each
               put on the device in turn -- the paper's multiple loading
               (section III-D) for corpora larger than device memory.  Per-
               part buffers of width min(k, rows) merge exactly by
               `merge_ragged` (parts partition the object set).
  DISTRIBUTED  mesh shards under shard_map; per-shard buffers all-gathered
               and merged collectively (optionally hierarchically: pod-local
               first, then across pods).

Invariants owned here: pad-never-in-topk (counts of rows with global id >=
n_objects are forced to -1 *before* selection), the (count desc, id asc)
tie-break (stable buffer merges over id-ascending parts), and the ragged
per-part k clamp.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import cpq as _cpq
from repro.core import engines as _engines
from repro.core import merge as _merge
from repro.core import routing as _routing
from repro.core.routing import Routing
from repro.core.select import select_topk
from repro.core.types import (Engine, SearchParams, SignatureLayout,
                              TopKMethod, TopKResult)
from repro.runtime import tracing

MatchLike = Union[Engine, str, "_engines.MatchModel",
                  Callable[[jnp.ndarray, Any], jnp.ndarray]]


class Layout(str, enum.Enum):
    """Part layout of a planned search (the taxonomy in docs/EXECUTION.md)."""

    MONOLITHIC = "monolithic"      # one device-resident data matrix
    SEGMENTED = "segmented"        # host loop streaming parts through the device
    DISTRIBUTED = "distributed"    # object shards across a device mesh


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """A fully-resolved description of one search: who matches, over which
    parts, how pads are masked, how much each part contributes to the merge.

    Hashable by construction -- the plan IS the executable-cache key.
    """

    match: Callable[[jnp.ndarray, Any], jnp.ndarray]  # canonical match fn
    params: SearchParams
    layout: Layout
    part_rows: tuple[int, ...] = ()    # physical rows per part ((): deferred)
    n_objects: Optional[int] = None    # real corpus rows; None = nothing padded
    engine: Optional[Engine] = None    # None when `match` is a raw callable
    pad_value: Any = None              # engine fill for padded rows
    fused_hist: bool = False           # single-device fused Pallas histogram
    hierarchical: bool = False         # DISTRIBUTED: pod-local merge first
    mesh_axes: tuple[str, ...] = ()    # DISTRIBUTED: mesh axis names
    # signature storage format the match fn expects (core/packing.py); part
    # of the plan hash, so WIDE and PACKED executables never collide in cache
    signature_layout: SignatureLayout = SignatureLayout.WIDE
    # fused match->count->local-top-k kernel fn(data, queries, k) ->
    # (ids, counts) candidate buffers; None => count matrix + select_topk
    fused_match: Optional[Callable[[jnp.ndarray, Any, int], tuple]] = None
    # coarse routing mode (core/routing.py): NONE scans every part; ROUTED /
    # ROUTED_VERIFIED prune via a Router built from segment summaries.  Part
    # of the plan hash, so routed and full-scan executables never collide.
    routing: Routing = Routing.NONE
    # probe width for ROUTED/ROUTED_VERIFIED; None = Router's sqrt(S) default
    nprobe: Optional[int] = None
    # tuned kernel tile sizes as canonical sorted ((knob, value), ...) pairs
    # (core/autotune.py; engines.canonical_tile_overrides).  Part of the plan
    # hash: tuned and default executables never collide in cache, and the
    # memoized tile-bound match callables keep equal plans key-equal.
    tile_overrides: tuple = ()

    # -- derived layout facts ----------------------------------------------
    @property
    def n_parts(self) -> int:
        return len(self.part_rows)

    @property
    def total_rows(self) -> int:
        return sum(self.part_rows)

    @property
    def pad_rows(self) -> int:
        if self.n_objects is None or not self.part_rows:
            return 0
        return self.total_rows - self.n_objects

    def part_k(self, rows: int) -> int:
        """Ragged k clamp: a part smaller than k contributes only
        min(k, rows) candidates (SEGMENTED parts)."""
        return min(self.params.k, rows)

    def merge_strategy(self) -> str:
        if self.layout == Layout.MONOLITHIC:
            return "none"
        if self.layout == Layout.DISTRIBUTED:
            return "collective-hierarchical" if self.hierarchical else "collective"
        return "ragged-buffer"

    def describe(self) -> dict:
        """Host-side plan summary (surfaced by launch/dryrun cost reports)."""
        rows = list(self.part_rows)
        # both per-part lists truncate identically: a "..." marker past 32
        # parts, never a silent cut (the lists must stay row-aligned)
        truncated = len(rows) > 32
        part_k = [self.part_k(r) for r in rows[:32]]
        return dict(
            layout=self.layout.value,
            engine=self.engine.value if self.engine else "<callable>",
            k=self.params.k,
            method=self.params.method.value,
            use_kernel=self.params.use_kernel,
            n_parts=self.n_parts,
            part_rows=rows[:32] + ["..."] if truncated else rows,
            part_k=part_k + ["..."] if truncated else part_k,
            n_objects=self.n_objects,
            pad_rows=self.pad_rows,
            merge=self.merge_strategy(),
            hierarchical=self.hierarchical,
            mesh_axes=list(self.mesh_axes),
            fused_hist=self.fused_hist,
            signature_layout=self.signature_layout.value,
            fused_match=self.fused_match is not None,
            routing=self.routing.value,
            nprobe=self.nprobe,
            tile_overrides=dict(self.tile_overrides),
        )


def plan_search(
    engine: MatchLike,
    k: int,
    max_count: int,
    *,
    layout: Layout = Layout.MONOLITHIC,
    part_rows: Optional[Sequence[int]] = None,
    n_objects: Optional[int] = None,
    method: TopKMethod = TopKMethod.CPQ,
    candidate_cap: Optional[int] = None,
    use_kernel: bool = True,
    hierarchical: bool = False,
    mesh_axes: Sequence[str] = (),
    signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
    routing: Routing | str = Routing.NONE,
    nprobe: Optional[int] = None,
    tile_overrides: Optional[Any] = None,
    autotune: Optional[Any] = None,
    tune_width: Optional[int] = None,
) -> QueryPlan:
    """The single planning entry point: resolve the engine, lay out the
    parts, fix the pad policy and merge strategy, return the QueryPlan.

    `engine` may be an Engine, its string value, a MatchModel, or a raw
    canonical callable ``fn(data, queries) -> counts``.

    Layout shape: pass `part_rows` (explicit, possibly ragged part sizes;
    `segments.even_segments` gives an even split).  `n_objects` marks the
    rows at and past it as engine-filled pad (`pad_to_multiple`), masked out
    of every candidate buffer.  DISTRIBUTED plans defer the shape to compile
    time (shard_map splits whatever data arrives).

    `signature_layout` selects the storage format the data/queries arrive in
    (core/packing.py): PACKED plans dispatch the packed match fns and -- on
    the single-device kernel paths with nothing padded -- the fused
    match->count->local-top-k kernel, so the [Q, N] count matrix never
    leaves VMEM.  Engines without a packed format reject PACKED here.

    `routing` plans coarse segment/shard pruning (core/routing.py): ROUTED
    and ROUTED_VERIFIED plans execute against a Router built from segment
    summaries (`execute(..., router=...)`) and skip the parts/shards the
    router rules out.  Routing prunes host-streamed parts or mesh shards, so
    it requires SEGMENTED or DISTRIBUTED -- a MONOLITHIC plan is one
    device program with nothing to skip and rejects it here.

    `tile_overrides` binds kernel tile sizes (tile_q/tile_n/tile_v/tile_m --
    the knobs kernels/ops.py accepts) onto the kernel dispatch path; it is
    rejected for use_kernel=False plans and raw callables.  `autotune`
    consults a measured-knob cache (core/autotune.py: True for the default
    cache, a path, or an AutotuneCache) and fills tile_overrides /
    candidate_cap / nprobe / fused-match preference for whatever the caller
    left unset -- explicit arguments always win, and a cache miss (including
    a hardware-fingerprint mismatch) silently keeps the defaults.
    `tune_width` is the physical signature width hint for cache bucketing.
    """
    sig_layout = SignatureLayout(signature_layout)
    model: Optional[_engines.MatchModel] = None
    match: Any = None
    if callable(engine) and not isinstance(engine, (_engines.MatchModel, Engine, str)):
        # raw callables own the layout contract; the plan just records it
        match = engine
    else:
        model = _engines.get(engine)
        sig_layout = model.require_layout(sig_layout)

    tiles = _engines.canonical_tile_overrides(tile_overrides)
    tuned_fused: Optional[bool] = None
    if autotune is not None and autotune is not False and model is not None:
        # lazy import: the autotuner times candidate plans through this very
        # module, so a top-level import would be circular
        from repro.core import autotune as _autotune

        n_hint = n_objects
        if n_hint is None and part_rows is not None:
            n_hint = sum(int(r) for r in part_rows)
        entry = _autotune.consult(
            autotune, engine=model.engine, signature_layout=sig_layout,
            n=n_hint, width=tune_width,
        )
        if entry is not None:
            # tuned knobs fill only what the caller left unset: explicit
            # arguments always win over the cache.  Tile sizes and the fused
            # preference are kernel-path knobs; candidate_cap and nprobe
            # shape selection on every dispatch path (incl. use_kernel=False
            # plans like the dry-run's lowered XLA fallback).
            if use_kernel:
                if not tiles and entry.tile_overrides:
                    tiles = _engines.canonical_tile_overrides(
                        entry.tile_overrides)
                tuned_fused = entry.fused_match
            if candidate_cap is None and entry.candidate_cap is not None:
                candidate_cap = int(entry.candidate_cap)
            if (nprobe is None and entry.nprobe is not None
                    and Routing(routing) is not Routing.NONE):
                nprobe = int(entry.nprobe)
    if tiles:
        if model is None:
            raise ValueError(
                "tile_overrides require a registered engine; a raw match "
                "callable owns its own tiling"
            )
        if not use_kernel:
            raise ValueError(
                "tile_overrides only apply to kernel dispatch; "
                "use_kernel=False plans take none"
            )
    if model is not None:
        match = model.match_fn(use_kernel, sig_layout, tiles)

    layout = Layout(layout)
    rows = tuple(int(r) for r in part_rows) if part_rows is not None else ()
    if layout == Layout.SEGMENTED and not rows:
        raise ValueError(f"{layout.value} layout requires part_rows")
    if layout == Layout.MONOLITHIC and len(rows) > 1:
        raise ValueError(f"monolithic layout got {len(rows)} parts")
    if any(r < 1 for r in rows):
        raise ValueError(f"part_rows must be positive, got {rows}")

    routing = Routing(routing)
    if routing is not Routing.NONE:
        if layout == Layout.MONOLITHIC:
            raise ValueError(
                f"routing={routing.value!r} prunes host-streamed parts or "
                f"mesh shards; a {layout.value} plan is one device program "
                f"with nothing to skip -- use routing='none', or a SEGMENTED "
                f"or DISTRIBUTED layout"
            )
        if nprobe is not None and int(nprobe) < 1:
            raise ValueError(f"nprobe must be >= 1, got {nprobe}")
        nprobe = None if nprobe is None else int(nprobe)
    else:
        nprobe = None  # keep full-scan plans' cache keys canonical

    params = SearchParams(k=k, max_count=max_count, method=method,
                          candidate_cap=candidate_cap, use_kernel=use_kernel)
    # The fused Pallas histogram runs on the single-device paths only; the
    # shard_map path keeps the jnp reference histogram.
    fused = use_kernel and layout in (Layout.MONOLITHIC, Layout.SEGMENTED)
    # The fused match->count->local-top-k kernel replaces the whole
    # count+select pipeline.  Same single-device gating as fused_hist, plus
    # n_objects None: the kernel masks pad columns by *physical* row id, so
    # engine-filled pad rows (`pad_to_multiple`, e.g. mesh divisibility)
    # must not be present -- padded plans keep the packed count kernel + the
    # structural _mask_pad_counts instead.
    fused_topk = None
    if (model is not None and sig_layout is SignatureLayout.PACKED
            and use_kernel and n_objects is None
            and layout in (Layout.MONOLITHIC, Layout.SEGMENTED)
            and tuned_fused is not False):
        fused_topk = model.fused_topk_fn(tiles)
    return QueryPlan(
        match=match, params=params, layout=layout, part_rows=rows,
        n_objects=n_objects, engine=model.engine if model else None,
        pad_value=model.pad_value_for(sig_layout) if model else None,
        fused_hist=fused,
        hierarchical=bool(hierarchical), mesh_axes=tuple(mesh_axes),
        signature_layout=sig_layout, fused_match=fused_topk,
        routing=routing, nprobe=nprobe, tile_overrides=tiles,
    )


# ---------------------------------------------------------------------------
# Batch compatibility (the serving front-end's coalescing key)
# ---------------------------------------------------------------------------

def k_bucket(k: int) -> int:
    """Round k up to the next power of two (floor 1).

    The serving front-end (serve/frontend.py) coalesces concurrent requests
    into one device dispatch; bucketing k means requests for k=5 and k=8
    share the k=8 executable instead of fragmenting the plan cache per exact
    k.  Truncating a top-8 result to a request's own k is bit-for-bit
    identical to searching at that k: the (count desc, id asc) order is
    total, so a top-k result is a prefix of any larger top-k' result."""
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    return 1 << (int(k) - 1).bit_length()


def batch_compat_key(
    engine: Engine | str,
    layout: Layout | str,
    signature_layout: SignatureLayout | str,
    routing: Routing | str,
    method: TopKMethod | str,
    k: int,
    *,
    nprobe: Optional[int] = None,
    candidate_cap: Optional[int] = None,
) -> tuple:
    """The coalescing key of one serving request: two requests with equal
    keys can share a single planned dispatch (stacked queries, one
    executable) and still scatter bit-for-bit per-request results.

    The axes are exactly the ones the executable cache keys on -- engine x
    layout x signature_layout x routing x method x k-bucket -- plus the two
    knobs that change a plan's selection behaviour (nprobe, candidate_cap).
    An explicit candidate_cap disables k-bucketing: the effective buffer
    capacity is max(cap, k), so bucketing k would silently change the cap
    the caller pinned."""
    kb = int(k) if candidate_cap is not None else k_bucket(k)
    return (
        Engine(engine) if not isinstance(engine, Engine) else engine,
        Layout(layout),
        SignatureLayout(signature_layout),
        Routing(routing),
        TopKMethod(method),
        kb,
        nprobe,
        candidate_cap,
    )


# ---------------------------------------------------------------------------
# Pad policy (the only pad masking / pad filling in the system)
# ---------------------------------------------------------------------------

def _mask_pad_counts(counts: jnp.ndarray, offset, n_objects: Optional[int]) -> jnp.ndarray:
    """Force pad columns (global id >= n_objects) to count -1 *before*
    selection, so pad rows can never crowd real candidates out of a candidate
    buffer.  This makes pad safety structural for every engine: the
    `pad_value` fill only has to be representable, not score-neutral
    (COSINE's zero rows, for instance, score V/2 against any query)."""
    if n_objects is None:
        return counts
    gcol = offset + jnp.arange(counts.shape[-1], dtype=jnp.int32)
    return jnp.where((gcol < n_objects)[None, :], counts, -1)


def _mask_invalid(gids: jnp.ndarray, counts: jnp.ndarray, n_objects: Optional[int]):
    """Drop padding rows post-selection: ids at/above the true object count
    never merge (belt to `_mask_pad_counts`'s braces)."""
    valid = gids >= 0
    if n_objects is not None:
        valid &= gids < n_objects
    return jnp.where(valid, gids, -1), jnp.where(valid, counts, -1)


def pad_to_multiple(data: np.ndarray, multiple: int, pad_value) -> tuple[np.ndarray, int]:
    """(padded host data, true row count): append engine-fill rows up to the
    next multiple (mesh divisibility).  Host-side, so a corpus bound for a
    sharded placement never gathers on one device."""
    n = int(data.shape[0])
    pad = (-n) % max(int(multiple), 1)
    if pad:
        fill = np.full((pad,) + data.shape[1:], pad_value, dtype=data.dtype)
        data = np.concatenate([data, fill], axis=0)
    return data, n


# ---------------------------------------------------------------------------
# The executable cache + per-plan trace counter
# ---------------------------------------------------------------------------

_EXEC_CACHE: dict = {}
_TRACE_COUNTS: dict = {}
# FIFO bound on retained executables: jitted wrappers pin their compiled
# programs, so a long-lived serving process interleaving adds and searches
# must not accumulate stale entries forever.
PLAN_CACHE_CAP = 256


def _note_trace(key) -> None:
    # runs at trace time only (python body of a jitted function): counts how
    # often an executable was actually re-traced vs served from cache
    _TRACE_COUNTS[key] = _TRACE_COUNTS.get(key, 0) + 1


def trace_count(plan: QueryPlan) -> int:
    """How many times this plan's executables have been traced (a cache hit
    leaves the counter unchanged).  SEGMENTED plans sum their per-part
    kernels (parts with equal row counts share one); distributed plans sum
    across meshes."""
    if plan.layout == Layout.SEGMENTED:
        return sum(_TRACE_COUNTS.get(k, 0)
                   for k in {_part_key(plan, r) for r in plan.part_rows})
    if plan.layout == Layout.DISTRIBUTED:
        return sum(v for k, v in _TRACE_COUNTS.items()
                   if k[0] == "dist" and k[1] == plan)
    return _TRACE_COUNTS.get(("mono", plan), 0)


def plan_cache_size() -> int:
    return len(_EXEC_CACHE)


def clear_plan_cache() -> None:
    _EXEC_CACHE.clear()
    _TRACE_COUNTS.clear()


def _cached(key, builder):
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        while len(_EXEC_CACHE) >= PLAN_CACHE_CAP:
            evicted = next(iter(_EXEC_CACHE))             # FIFO eviction
            _EXEC_CACHE.pop(evicted)
            _TRACE_COUNTS.pop(evicted, None)  # drop the counter twin too, or
            # the leak guard merely relocates the leak into the trace dict
        fn = _EXEC_CACHE[key] = builder()
    return fn


# ---------------------------------------------------------------------------
# Executors: the ONLY callers of match kernels, pad masks, select, and merge
# ---------------------------------------------------------------------------

def _part_topk(plan: QueryPlan, data: jnp.ndarray, queries: Any, offset,
               k: Optional[int] = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One part's candidate buffer: match -> pad mask -> select -> globalise.

    The shared core of every layout.  Returns (global ids, counts), both
    [Q, k], empty slots -1."""
    params = plan.params if k is None or k == plan.params.k \
        else dataclasses.replace(plan.params, k=k)
    with tracing.scope(tracing.MATCH):
        counts = plan.match(data, queries)
    counts = _mask_pad_counts(counts, offset, plan.n_objects)
    local = select_topk(counts, params, use_fused_hist=plan.fused_hist)
    gids = jnp.where(local.ids >= 0, local.ids + offset, -1)
    return _mask_invalid(gids, local.counts, plan.n_objects)


def _fused_candidates_topk(fused_match, data, queries, k: int):
    """Run a fused match->count->local-top-k kernel and reduce its per-tile
    candidate buffers to the final (ids, counts) [Q, k].

    Per-tile buffers arrive in (count desc, id asc) order with tiles in
    ascending global-id ranges, so the buffer as a whole is id-ascending
    within equal counts -- exactly what topk_from_candidates' stable merge
    needs for the global tie-break."""
    with tracing.scope(tracing.TOPK):
        cids, ccnt = fused_match(data, queries, k)
    with tracing.scope(tracing.ORDER):
        if cids.shape[1] < k:  # tiny corpus: fewer candidate slots than k
            fill = jnp.full((cids.shape[0], k - cids.shape[1]), -1, jnp.int32)
            cids = jnp.concatenate([cids, fill], axis=1)
            ccnt = jnp.concatenate([ccnt, fill], axis=1)
        return _cpq.topk_from_candidates(cids, ccnt, k)


def _candidate_slots(plan: QueryPlan, part, queries) -> dict:
    """The `candidates` stat of a host-loop part span: the slots per query
    row a fused plan's kernel writes for the part (n_tiles * min(k, tile_n),
    whatever the number of query rows), {} for a plan without one."""
    if plan.fused_match is None:
        return {}
    k = plan.part_k(int(part.shape[0]))
    key = ("slots", plan.fused_match, tuple(part.shape), str(part.dtype), k)

    def build():
        spec = jax.ShapeDtypeStruct(part.shape, part.dtype)
        cids, _ = jax.eval_shape(lambda d, q: plan.fused_match(d, q, k),
                                 spec, queries)
        return int(cids.shape[1])

    return {"candidates": _cached(key, build)}


def _build_monolithic(plan: QueryPlan, key):
    if plan.fused_match is not None:
        k = plan.params.k

        def run_fused(data: jnp.ndarray, queries: Any) -> TopKResult:
            _note_trace(key)
            ids, counts = _fused_candidates_topk(plan.fused_match, data,
                                                 queries, k)
            return TopKResult(ids=ids, counts=counts, threshold=counts[:, -1])

        return jax.jit(run_fused)

    def run(data: jnp.ndarray, queries: Any) -> TopKResult:
        _note_trace(key)
        with tracing.scope(tracing.MATCH):
            counts = plan.match(data, queries)
        counts = _mask_pad_counts(counts, 0, plan.n_objects)
        # selection is the merge: return select_topk's result (threshold
        # included) exactly as the pre-planner single-device search did
        return select_topk(counts, plan.params, use_fused_hist=plan.fused_hist)

    return jax.jit(run)


def _part_key(plan: QueryPlan, rows: int) -> tuple:
    """Cache key of a host-loop per-part kernel: only what the part program
    actually closes over -- NOT the whole plan, so growing the corpus (new
    part_rows / n_objects) keeps reusing kernels compiled for the same part
    shape (the id offset and pad boundary are traced scalars)."""
    params = dataclasses.replace(plan.params, k=plan.part_k(rows))
    return ("part", plan.match, params, plan.fused_hist, plan.fused_match,
            plan.n_objects is not None, rows)


def _part_fn(plan: QueryPlan, rows: int):
    """Cached per-part jitted kernel for the SEGMENTED host loop: parts with
    the same row count share one compiled program across searches AND across
    corpus growth, so a 40-segment corpus of equal seals compiles once."""
    key = _part_key(plan, rows)
    match, fused = plan.match, plan.fused_hist
    fused_match = plan.fused_match
    params = dataclasses.replace(plan.params, k=plan.part_k(rows))
    masked = plan.n_objects is not None

    def build():
        def run(part, queries, offset, n_limit):
            _note_trace(key)
            if fused_match is not None:
                # fused plans are never masked (plan_search gates on
                # n_objects None): the kernel's own physical-row masking is
                # exhaustive, and parts arrive unpadded
                ids, cnts = _fused_candidates_topk(fused_match, part,
                                                   queries, params.k)
                return jnp.where(ids >= 0, ids + offset, -1), cnts
            with tracing.scope(tracing.MATCH):
                counts = match(part, queries)
            if masked:
                counts = _mask_pad_counts(counts, offset, n_limit)
            local = select_topk(counts, params, use_fused_hist=fused)
            gids = jnp.where(local.ids >= 0, local.ids + offset, -1)
            if masked:
                return _mask_invalid(gids, local.counts, n_limit)
            return gids, local.counts

        return jax.jit(run)

    return _cached(key, build)


def _scan_host_parts(plan: QueryPlan, parts, queries,
                     part_mask: Optional[np.ndarray] = None) -> TopKResult:
    """One pass of the host loop over the (optionally masked) parts: each
    scanned part is swapped through the device, selected into a buffer of
    width min(k, rows), and the ragged buffers merge exactly.  Skipped parts
    never touch the device -- their rows' global ids simply advance the
    offset, so scanned parts keep their true id ranges."""
    n_limit = jnp.int32(plan.n_objects if plan.n_objects is not None else 0)
    buf_ids, buf_counts = [], []
    offset = 0
    for i, (part, rows) in enumerate(zip(parts, plan.part_rows)):
        if int(part.shape[0]) != rows:
            raise ValueError(f"part has {int(part.shape[0])} rows, plan says {rows}")
        if part_mask is None or part_mask[i]:
            with tracing.span(tracing.PART, part=i, rows=rows,
                              **_candidate_slots(plan, part, queries)):
                part = jax.device_put(part)
                gids, gcnt = _part_fn(plan, rows)(part, queries,
                                                  jnp.int32(offset), n_limit)
            buf_ids.append(gids)
            buf_counts.append(gcnt)
        offset += rows
    if not buf_ids:  # defensive: a router always selects >= 1 segment
        q = jax.tree_util.tree_leaves(queries)[0].shape[0]
        empty = jnp.full((q, plan.params.k), -1, dtype=jnp.int32)
        return TopKResult(ids=empty, counts=empty, threshold=empty[:, -1])
    with tracing.span(tracing.MERGE, parts=len(buf_ids)):
        return _merge.merge_ragged(buf_ids, buf_counts, plan.params.k)


def _route(plan: QueryPlan, router: Optional["_routing.Router"],
           queries, route_queries) -> tuple[np.ndarray, np.ndarray]:
    """Resolve the routed plan's (segment mask, upper bounds) on the host.

    `route_queries` are the canonical WIDE queries the summaries were built
    against; they default to the execution queries (correct whenever the
    plan's signature_layout is WIDE)."""
    if router is None:
        raise ValueError(
            f"a routing={plan.routing.value!r} plan needs router= (built "
            f"from segment summaries, e.g. SegmentedIndex.router())"
        )
    if plan.layout == Layout.SEGMENTED \
            and tuple(router.part_rows) != plan.part_rows:
        raise ValueError(
            f"router summarises parts {tuple(router.part_rows)} but the plan "
            f"lays out {plan.part_rows}; rebuild the router from the current "
            f"segments"
        )
    rq = queries if route_queries is None else route_queries
    with tracing.span(tracing.ROUTE):
        return router.select(rq, plan.nprobe)


def _skipped_could_contribute(result: TopKResult, ubs: np.ndarray,
                              verify_mask: np.ndarray) -> bool:
    """ROUTED_VERIFIED's fallback predicate: could any unscanned segment
    still place a member in the top-k?  True when a skipped segment's upper
    bound reaches the routed result's k-th count -- `>=`, not `>`, because a
    tied count with a smaller id displaces the k-th slot under the
    (count desc, id asc) order, and because an unfilled slot (threshold -1)
    must always force the fallback (every bound is >= a real count of 0)."""
    if not verify_mask.any():
        return False
    thresholds = np.asarray(result.threshold).astype(np.float64)  # [Q]
    return bool((ubs[:, verify_mask] >= thresholds[:, None]).any())


def _run_host_parts(plan: QueryPlan, parts, queries, router=None,
                    route_queries=None) -> TopKResult:
    """Host-orchestrated part streaming (the SEGMENTED layout),
    with coarse routing when the plan asks for it: ROUTED scans only the
    router-selected parts; ROUTED_VERIFIED additionally checks the skipped
    parts' upper bounds against the routed threshold and falls back to the
    full scan when a skipped part could still contribute -- making it
    bit-for-bit identical to routing=NONE."""
    if len(parts) != plan.n_parts:
        raise ValueError(f"plan lays out {plan.n_parts} parts, got {len(parts)}")
    if plan.routing is Routing.NONE:
        return _scan_host_parts(plan, parts, queries)
    mask, ubs = _route(plan, router, queries, route_queries)
    routed = _scan_host_parts(plan, parts, queries, part_mask=mask)
    if plan.routing is Routing.ROUTED:
        return routed
    if not _skipped_could_contribute(routed, ubs, ~mask):
        return routed
    return _scan_host_parts(plan, parts, queries)


def _mesh_key(mesh: jax.sharding.Mesh) -> tuple:
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def _build_sharded(plan: QueryPlan, mesh: jax.sharding.Mesh, key):
    """The distributed executor: every shard runs the shared part kernel on
    its local object partition, then the cap-sized candidate buffers merge
    collectively (all-gather + small-buffer select; hierarchical plans merge
    pod-locally over cheap ICI first, then across pods over DCN).

    Routed plans take a third operand, `shard_active` int32 [n_shards]
    (replicated): inactive shards blank their candidate buffers to -1 before
    the gather, so unrouted shards contribute nothing to the merge.  Under
    SPMD every shard still runs the match (the savings routing buys on the
    host loops become result-masking here); an all-ones mask makes the
    program a bit-exact full scan, which is what the verified fallback
    re-runs -- same compiled executable, no second trace."""
    axes = tuple(mesh.axis_names)
    hier = plan.hierarchical and axes[0] == "pod"
    inner_axes = axes[1:] if hier else axes
    routed = plan.routing is not Routing.NONE

    def _local(data_local: jnp.ndarray, queries: Any,
               shard_active: Optional[jnp.ndarray] = None) -> TopKResult:
        _note_trace(key)
        n_local = data_local.shape[0]
        shard = _shard_linear_index(axes)
        gids, gcnt = _part_topk(plan, data_local, queries, shard * n_local)
        if shard_active is not None:
            on = shard_active[shard] > 0
            gids = jnp.where(on, gids, -1)
            gcnt = jnp.where(on, gcnt, -1)
        if not hier:
            all_ids = jax.lax.all_gather(gids, axis_name=axes, axis=0, tiled=False)
            all_cnt = jax.lax.all_gather(gcnt, axis_name=axes, axis=0, tiled=False)
            return _merge.merge_topk(all_ids, all_cnt, plan.params.k)
        # level 1: merge within the pod (over data/model axes)
        ids_in = jax.lax.all_gather(gids, axis_name=inner_axes, axis=0, tiled=False)
        cnt_in = jax.lax.all_gather(gcnt, axis_name=inner_axes, axis=0, tiled=False)
        pod = _merge.merge_topk(ids_in, cnt_in, plan.params.k)
        # level 2: merge across pods
        ids_out = jax.lax.all_gather(pod.ids, axis_name=("pod",), axis=0, tiled=False)
        cnt_out = jax.lax.all_gather(pod.counts, axis_name=("pod",), axis=0, tiled=False)
        return _merge.merge_topk(ids_out, cnt_out, plan.params.k)

    out_specs = TopKResult(ids=P(None, None), counts=P(None, None),
                           threshold=P(None))
    if routed:
        sharded = jax.shard_map(
            _local, mesh=mesh,
            in_specs=(P(axes), P(None, None), P(None)),
            out_specs=out_specs, check_vma=False,
        )
    else:
        sharded = jax.shard_map(
            lambda data_local, queries: _local(data_local, queries), mesh=mesh,
            in_specs=(P(axes), P(None, None)),
            out_specs=out_specs, check_vma=False,
        )
    return jax.jit(sharded)


def executable(plan: QueryPlan, mesh: Optional[jax.sharding.Mesh] = None):
    """The compiled-callable for a plan, from the cache when the same
    (engine, layout shape, k, method, use_kernel) was planned before.

    Returns ``fn(data, queries) -> TopKResult`` where `data`'s form follows
    the layout: one array (MONOLITHIC / DISTRIBUTED-sharded) or a list of
    per-part arrays (SEGMENTED).  Routed DISTRIBUTED executables take a
    third operand, `shard_active` int32 [n_shards]; routed SEGMENTED
    callables take `router=` / `route_queries=` keywords (both orchestrated
    by `execute`)."""
    if plan.layout == Layout.DISTRIBUTED:
        if mesh is None:
            raise ValueError("a DISTRIBUTED plan executes on a mesh; pass mesh=")
        key = ("dist", plan, _mesh_key(mesh))
        return _cached(key, lambda: _build_sharded(plan, mesh, key))
    if plan.layout == Layout.MONOLITHIC:
        key = ("mono", plan)
        return _cached(key, lambda: _build_monolithic(plan, key))
    # SEGMENTED: the python orchestration is free to rebuild; the per-part
    # compiled kernels underneath are the cached hot path
    return lambda parts, queries, router=None, route_queries=None: \
        _run_host_parts(plan, parts, queries, router=router,
                        route_queries=route_queries)


def _run_routed_sharded(plan: QueryPlan, data, queries,
                        mesh: jax.sharding.Mesh,
                        router: Optional["_routing.Router"],
                        route_queries) -> TopKResult:
    """Routed DISTRIBUTED execution: segments map onto the shards whose row
    ranges they overlap, unrouted shards blank their candidate buffers, and
    ROUTED_VERIFIED re-runs the same executable with an all-ones mask (a
    bit-exact full scan) when a segment with any inactive shard could still
    reach the routed threshold."""
    mask, ubs = _route(plan, router, queries, route_queries)
    n_total = int(data.shape[0])
    n_shards = int(np.prod(mesh.devices.shape))
    n_local = max(n_total // n_shards, 1)
    if sum(router.part_rows) > n_total:
        raise ValueError(
            f"router summarises {sum(router.part_rows)} rows but the sharded "
            f"data holds {n_total}; rebuild the router from the current "
            f"segments"
        )
    active = _routing.shard_mask(router.part_rows, mask, n_local, n_shards)
    fn = executable(plan, mesh=mesh)
    res = fn(data, queries, jnp.asarray(active, dtype=jnp.int32))
    if plan.routing is Routing.ROUTED:
        return res
    # a segment fully covered by active shards was scanned (possibly as a
    # bonus rider on a routed neighbour's shard) -- verify only the rest
    verify = _routing.segments_needing_verify(router.part_rows, active, n_local)
    if not _skipped_could_contribute(res, ubs, verify):
        return res
    return fn(data, queries, jnp.ones((n_shards,), dtype=jnp.int32))


def execute(plan: QueryPlan, data, queries,
            mesh: Optional[jax.sharding.Mesh] = None,
            router: Optional["_routing.Router"] = None,
            route_queries=None) -> TopKResult:
    """Run a planned search.  The only public door to the match/select/merge
    machinery -- every index/serving entry point delegates here.

    Routed plans (`plan.routing` != NONE) need `router=` -- a
    `routing.Router` over the current segments' summaries
    (`SegmentedIndex.router()`).  `route_queries=` supplies the canonical
    WIDE query pytree the summaries score against; it defaults to `queries`
    and must be passed whenever `queries` are PACKED (the router cannot read
    packed words)."""
    if plan.routing is not Routing.NONE and plan.layout == Layout.DISTRIBUTED:
        if mesh is None:
            raise ValueError("a DISTRIBUTED plan executes on a mesh; pass mesh=")
        return _run_routed_sharded(plan, data, queries, mesh, router,
                                   route_queries)
    if plan.layout == Layout.SEGMENTED:
        return executable(plan, mesh=mesh)(data, queries, router=router,
                                           route_queries=route_queries)
    return executable(plan, mesh=mesh)(data, queries)


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------

def _shard_linear_index(axes: tuple[str, ...]) -> jnp.ndarray:
    """Linearised shard index over the given mesh axes (row-major)."""
    idx = jnp.int32(0)
    for name in axes:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx
