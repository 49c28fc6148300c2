"""SegmentedIndex: incremental append + compaction over immutable segments.

`RetrievalService.add` used to rebuild its GenieIndex from scratch on every
call, so filling a corpus of N items in B batches cost O(N^2/B) device work
and re-uploaded all signatures each time.  This module fixes that bug the way
FAISS shards billion-scale GPU indexes (Johnson et al. 1702.08734): each
`add()` seals the batch into an immutable per-segment `GenieIndex` (O(batch)
device work), `search()` builds a SEGMENTED QueryPlan over the sealed parts
and delegates to the unified executor (core/plan.py) which matches, selects,
and merges the cap-sized candidate buffers exactly, and
`compact(max_segments)` coalesces adjacent segments so steady-state search
cost stays flat as the corpus grows.

The SEGMENTED host loop puts one segment at a time on the device, so it is
also the paper's multiple loading (section III-D) for corpora larger than
device memory: seal the corpus as `even_segments(n, parts)` batches (with
`host_resident=True` to keep them in host memory) and search as usual.

The merge is exact, not approximate: segments *partition* the object set, so
an object's match count is computed entirely inside its own segment (the same
invariant the distributed shard merge relies on).  Any global top-k member
is a top-min(k, n_seg) member of its segment, hence per-segment buffers of
width min(k, n_seg) always contain the global top-k, and the merged ordering
(count desc, global id asc) is identical to a monolithic search -- ids and
counts match exactly for every registered engine (tests/test_segments.py).

Compaction only ever merges *adjacent* segments: global ids are assigned by
cumulative segment offset in append order, and concatenating neighbours
preserves that order, so compaction never remaps an id.

    seg = SegmentedIndex(Engine.EQ)
    seg.add(sigs_batch_0)              # seals segment 0
    seg.add(sigs_batch_1)              # seals segment 1 -- no rebuild
    res = seg.search(queries, k=10)    # == monolithic GenieIndex search
    seg.compact(max_segments=1)        # coalesce; ids unchanged
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engines as _engines
from repro.core import plan as _plan
from repro.core import routing as _routing
from repro.core.index import GenieIndex
from repro.core.types import (Engine, IndexStats, SignatureLayout,
                              TopKMethod, TopKResult)


def even_segments(n_objects: int, n_segments: int) -> list[int]:
    """Row counts of an even split of `n_objects` into `n_segments` parts."""
    if n_segments < 1:
        raise ValueError(f"n_segments must be >= 1, got {n_segments}")
    base, rem = divmod(n_objects, n_segments)
    return [base + (1 if i < rem else 0) for i in range(n_segments)]


def layout_accounting(segment_rows, row_bytes: int) -> dict:
    """Host-side accounting for a segmented layout (surfaced by launch/dryrun)."""
    rows = [int(r) for r in segment_rows]
    return dict(
        n_segments=len(rows),
        segment_rows=rows,
        total_rows=sum(rows),
        bytes_per_segment=[r * int(row_bytes) for r in rows],
        bytes_total=sum(rows) * int(row_bytes),
    )


@dataclasses.dataclass
class SegmentedIndex:
    """An append-only sequence of immutable per-batch GenieIndex segments.

    `max_count` may be left None: the first `add` resolves it through the
    engine's derived bound (engines without one -- MINSUM, IP -- require it
    up front, exactly like `GenieIndex.build`), and every later segment is
    pinned to the same bound so counts stay comparable across segments.
    """

    engine: Engine
    max_count: Optional[int] = None
    use_kernel: bool = True
    segments: list[GenieIndex] = dataclasses.field(default_factory=list)
    compaction_count: int = 0
    compaction_seconds: float = 0.0
    # storage format every segment is sealed into (core/packing.py)
    signature_layout: SignatureLayout = SignatureLayout.WIDE
    # keep sealed segments in host memory: for a caller that serves from the
    # sharded placement concat_data() feeds, so that no one device ever
    # holds the whole corpus
    host_resident: bool = False

    def __post_init__(self):
        self.signature_layout = self.model.require_layout(self.signature_layout)

    # ------------------------------------------------------------------
    @property
    def model(self) -> _engines.MatchModel:
        return _engines.get(self.engine)

    @property
    def n_objects(self) -> int:
        return sum(s.stats.n_objects for s in self.segments)

    def __len__(self) -> int:
        return self.n_objects

    @property
    def segment_rows(self) -> list[int]:
        return [s.stats.n_objects for s in self.segments]

    @property
    def stats(self) -> IndexStats:
        """Aggregate IndexStats with per-segment build/compaction accounting."""
        segs = self.segments
        return IndexStats(
            n_objects=self.n_objects,
            n_lists=segs[0].stats.n_lists if segs else 0,
            total_postings=sum(s.stats.total_postings for s in segs),
            max_list_len=max((s.stats.max_list_len for s in segs), default=0),
            bytes_device=sum(s.stats.bytes_device for s in segs),
            build_seconds=sum(s.stats.build_seconds for s in segs),
            signature_layout=self.signature_layout.value,
            bytes_signatures_wide=sum(s.stats.bytes_signatures_wide for s in segs),
            bytes_signatures_packed=sum(s.stats.bytes_signatures_packed for s in segs),
            n_segments=len(segs),
            segment_rows=self.segment_rows,
            segment_build_seconds=[s.stats.build_seconds for s in segs],
            compaction_count=self.compaction_count,
            compaction_seconds=self.compaction_seconds,
            extra={"engine": self.engine.value},
        )

    # ------------------------------------------------------------------
    # Append
    # ------------------------------------------------------------------
    def add(self, raw_data) -> GenieIndex:
        """Seal one batch into a new immutable segment: O(batch) device work,
        no re-hash or re-upload of earlier segments."""
        shape = np.shape(raw_data)
        if not shape or shape[0] == 0:
            # an empty segment would poison every later search (0-row match)
            raise ValueError(f"cannot add an empty batch (shape {shape})")
        seg = GenieIndex.build(self.engine, raw_data, max_count=self.max_count,
                               use_kernel=self.use_kernel,
                               signature_layout=self.signature_layout)
        if self.segments:
            want = self.segments[0].data.shape[1:]
            if seg.data.shape[1:] != want:
                raise ValueError(
                    f"segment width mismatch: existing segments hold "
                    f"{tuple(want)} rows, new batch holds {tuple(seg.data.shape[1:])}"
                )
        if self.max_count is None:
            self.max_count = seg.max_count
        if self.host_resident:
            seg.data = np.asarray(seg.data)
        self.segments.append(seg)
        return seg

    # ------------------------------------------------------------------
    # Coarse routing (core/routing.py)
    # ------------------------------------------------------------------
    def router(self) -> _routing.Router:
        """A Router over the sealed segments' summaries (built at seal time,
        merged through compaction).  Raises when any segment lacks one --
        e.g. a GenieIndex assembled by hand outside build()."""
        if not self.segments:
            raise ValueError("empty SegmentedIndex: add() first")
        missing = [i for i, s in enumerate(self.segments) if s.summary is None]
        if missing:
            raise ValueError(
                f"segments {missing} carry no routing summary (assembled "
                f"outside GenieIndex.build?); routing needs per-segment "
                f"summaries"
            )
        return _routing.Router(engine=self.engine,
                               summaries=[s.summary for s in self.segments])

    def _routed_execute(self, plan, queries, routing: _routing.Routing,
                        router: _routing.Router | None = None) -> TopKResult:
        # the router scores canonical WIDE queries; the executor gets them
        # packed when the segments are PACKED
        q_wide = self.model.prepare_queries(queries)
        q_exec = q_wide
        if self.signature_layout is SignatureLayout.PACKED:
            q_exec = self.model.pack_queries(q_wide)
        if routing is _routing.Routing.NONE:
            router = None
        elif router is None:
            router = self.router()
        return _plan.execute(plan, [s.data for s in self.segments], q_exec,
                             router=router, route_queries=q_wide)

    # ------------------------------------------------------------------
    # Search: per-segment match + select, exact cap-buffer merge
    # ------------------------------------------------------------------
    def _tune_width(self) -> int:
        """Physical stored width (words/bytes when PACKED) for cache lookup."""
        return int(self.segments[0].data.shape[1])

    def search(self, queries, k: int, method: TopKMethod = TopKMethod.CPQ,
               candidate_cap: int | None = None,
               routing: _routing.Routing | str = _routing.Routing.NONE,
               nprobe: int | None = None,
               router: _routing.Router | None = None,
               tile_overrides=None, autotune=None) -> TopKResult:
        """`router` lets a caller that caches the Router across searches
        (serve/retrieval.py keys it on the corpus fingerprint) skip the
        per-search rebuild; ignored when routing is NONE.

        `autotune` consults the measured-knob cache (core/autotune.py) for
        the knobs the caller left unset.
        """
        if not self.segments:
            raise ValueError("empty SegmentedIndex: add() first")
        routing = _routing.Routing(routing)
        plan = _plan.plan_search(
            self.engine, k, self.max_count, layout=_plan.Layout.SEGMENTED,
            part_rows=tuple(self.segment_rows), method=method,
            candidate_cap=candidate_cap, use_kernel=self.use_kernel,
            signature_layout=self.signature_layout,
            routing=routing, nprobe=nprobe,
            tile_overrides=tile_overrides, autotune=autotune,
            tune_width=self._tune_width(),
        )
        return self._routed_execute(plan, queries, routing, router=router)

    # ------------------------------------------------------------------
    # Compaction
    # ------------------------------------------------------------------
    def compact(self, max_segments: int = 1) -> None:
        """Coalesce adjacent segments (smallest combined pair first) until at
        most `max_segments` remain.  Global ids are preserved: neighbours
        concatenate in append order.  O(n) device copy, no re-hash."""
        if max_segments < 1:
            raise ValueError(f"max_segments must be >= 1, got {max_segments}")
        if len(self.segments) <= max_segments:
            return
        segs = list(self.segments)
        t_total = 0.0
        while len(segs) > max_segments:
            sizes = [s.stats.n_objects for s in segs]
            i = min(range(len(segs) - 1), key=lambda j: sizes[j] + sizes[j + 1])
            # perf_counter, not time(): a wall-clock (NTP) step must never
            # record a negative compaction duration
            t0 = time.perf_counter()
            a, b = segs[i].stats, segs[i + 1].stats
            concat = np.concatenate if self.host_resident else jnp.concatenate
            arr = concat([segs[i].data, segs[i + 1].data], axis=0)
            jax.block_until_ready(arr)
            t_total += time.perf_counter() - t0
            # aggregate the sources' stats instead of recomputing on `arr`:
            # every field is additive (or a max), and a PACKED `arr` holds
            # words/bytes -- build_stats would misread its width as signature
            # slots.  The merged segment keeps its sources' *build* time; the
            # concat cost is compaction accounting, not build accounting.
            stats = IndexStats(
                n_objects=a.n_objects + b.n_objects,
                n_lists=a.n_lists,
                total_postings=a.total_postings + b.total_postings,
                max_list_len=max(a.max_list_len, b.max_list_len),
                bytes_device=a.bytes_device + b.bytes_device,
                build_seconds=a.build_seconds + b.build_seconds,
                signature_layout=self.signature_layout.value,
                bytes_signatures_wide=(a.bytes_signatures_wide
                                       + b.bytes_signatures_wide),
                bytes_signatures_packed=(a.bytes_signatures_packed
                                         + b.bytes_signatures_packed),
                extra={"engine": self.engine.value},
            )
            # routing summaries merge like the stats: bounds widen, sketches
            # OR, centroids row-weight -- no recompute on the (possibly
            # packed) concatenated array.  A hand-assembled summary-less
            # source poisons the merge to None (router() then explains why).
            summary = None
            if segs[i].summary is not None and segs[i + 1].summary is not None:
                summary = _routing.merge_summaries(segs[i].summary,
                                                   segs[i + 1].summary)
            segs[i:i + 2] = [GenieIndex(engine=self.engine, max_count=self.max_count,
                                        data=arr, stats=stats,
                                        use_kernel=self.use_kernel,
                                        signature_layout=self.signature_layout,
                                        summary=summary)]
        self.segments = segs
        self.compaction_count += 1
        self.compaction_seconds += t_total

    # ------------------------------------------------------------------
    # Export for the distributed (sharded) layout
    # ------------------------------------------------------------------
    def concat_data(self, pad_multiple: int = 1) -> tuple[np.ndarray, int]:
        """(data, n_objects) for the distributed shard layout: segments
        concatenated on the host in global-id order, row count padded up to
        a multiple of `pad_multiple` with the engine's pad fill.  A sharded
        `jax.device_put` of the result sends each device only its shard.
        Pass `n_objects` to a DISTRIBUTED `plan_search` so pad rows are
        masked out of every shard's candidate buffer."""
        if not self.segments:
            raise ValueError("empty SegmentedIndex: add() first")
        data = np.concatenate([np.asarray(s.data) for s in self.segments], axis=0)
        return _plan.pad_to_multiple(
            data, pad_multiple, self.model.pad_value_for(self.signature_layout))
