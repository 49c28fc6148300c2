"""GENIE retrieval service: the paper's technique as a first-class serving
feature.

A RetrievalService wraps an embedding function (e.g. mean-pooled hidden
states of any registered LM, or raw feature vectors), an LSH scheme resolved
from the scheme registry (core/lsh/__init__.py), and a SegmentedIndex;
`add`/`search` give tau-ANN document retrieval for retrieval-augmented
serving (examples/serve_batch.py drives it at batch 1024+, the paper's
throughput regime).

Selecting a scheme by name selects the whole engine stack: each LshScheme
names the match engine that consumes its signatures (e2lsh/rbh -> EQ bucket
collisions, minhash -> TANIMOTO sketch collisions, simhash -> COSINE
sign agreements on the MXU) and the MLE that converts match counts back to
similarity estimates, so `RetrievalService(scheme="simhash")` serves
quantized cosine and `scheme="minhash"` serves Jaccard with no other change.

`add` may be called repeatedly: each batch is hashed once and sealed into an
immutable index *segment* (core/segments.py) -- O(batch) device work per
call, no rebuild or re-upload of earlier batches.  When the segment count
exceeds `max_segments` the index compacts adjacent segments down to
`max_segments // 2`, so steady-state search cost stays flat while adds stay
cheap.  Search merges per-segment candidate buffers exactly (segments
partition the object set), so results are identical to a monolithic rebuild.

Sharded serving: pass `mesh=` (a jax device mesh) and `search` plans the
segmented corpus across the mesh via the DISTRIBUTED layout -- segments are
concatenated in global-id order, padded up to mesh divisibility, sharded
over every mesh axis, and served through the same unified executor
(core/plan.py) as single-device search, so results are identical.  The
sharded placement is cached between searches and refreshed only when the
corpus changes (an `add` or a compaction).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SegmentedIndex, TopKMethod, distributed
from repro.core import engines as engines_lib
from repro.core import lsh as lsh_lib
from repro.core import plan as plan_lib
from repro.core import routing as routing_lib
from repro.core.lsh import tau_ann
from repro.core.types import SignatureLayout
from repro.runtime import tracing


@dataclasses.dataclass
class RetrievalService:
    embed_fn: Callable[[np.ndarray], np.ndarray]   # raw items -> [n, d] embeddings
    scheme: str = "e2lsh"                          # any registered LshScheme name
    eps: float = 0.06
    delta: float = 0.06
    n_buckets: int = 8192
    w: float = 4.0
    sigma: float = 1.0
    seed: int = 0
    m_override: Optional[int] = None
    max_segments: int = 16                         # compaction trigger for add()
    mesh: Optional[jax.sharding.Mesh] = None       # serve sharded when set
    # signature storage for the sealed segments (core/packing.py): PACKED
    # bit/byte-packs each segment at seal time for engines with a packed
    # format (simhash -> COSINE sign words; minhash -> TANIMOTO uint8 buckets
    # when n_buckets <= 254).  Results are identical to WIDE; only the device
    # footprint and match-phase HBM traffic shrink.
    signature_layout: SignatureLayout | str = SignatureLayout.WIDE
    # measured-knob cache (core/autotune.py): True = the default per-user
    # cache file, a path = that file, an AutotuneCache = itself.  Consulted
    # by every search plan; a miss or a hardware-fingerprint mismatch keeps
    # today's defaults.  Deliberately NOT part of batch_compat_key: the
    # front-end coalesces per tenant and a tenant's autotune spec is fixed
    # for the service's lifetime, so equal keys still share one executable
    # (docs/SERVING.md).
    autotune: object = None

    def __post_init__(self):
        self.m = self.m_override or tau_ann.required_m(self.eps, self.delta)
        if self.max_segments < 1:
            raise ValueError(f"max_segments must be >= 1, got {self.max_segments}")
        self._scheme = lsh_lib.get_scheme(self.scheme)
        # fail at construction, not at the first add(): WIDE-only engines
        # (e2lsh/rbh -> EQ) reject PACKED here
        self.signature_layout = engines_lib.get(
            self._scheme.engine).require_layout(self.signature_layout)
        self._params = None
        self._dim: Optional[int] = None
        self._index: Optional[SegmentedIndex] = None
        self._items: list = []
        # sharded-serving placement cache: (corpus fingerprint, data, n)
        self._placed: Optional[tuple] = None
        # router cache: (corpus fingerprint, Router) -- invalidated by the
        # same fingerprint that refreshes the sharded placement
        self._routed: Optional[tuple] = None

    def _make_params(self, d: int):
        key = jax.random.PRNGKey(self.seed)
        return self._scheme.make_params(
            key, d=d, m=self.m,
            w=self.w, sigma=self.sigma, n_buckets=self.n_buckets,
        )

    def _hash(self, x: np.ndarray):
        return self._scheme.hash_points(self._params, jnp.asarray(x))

    def _embed(self, items, embeddings: Optional[np.ndarray], expect_rows=None):
        emb = self.embed_fn(items) if embeddings is None else np.asarray(embeddings)
        if emb.ndim != 2:
            raise ValueError(f"embeddings must be [n, d], got shape {emb.shape}")
        if expect_rows is not None and emb.shape[0] != expect_rows:
            raise ValueError(
                f"embeddings row count {emb.shape[0]} != {expect_rows} "
                f"items/queries"
            )
        if self._dim is not None and emb.shape[-1] != self._dim:
            raise ValueError(
                f"embedding dim {emb.shape[-1]} != dim {self._dim} fixed by the "
                f"first add(); the LSH parameters are built once per service"
            )
        return emb

    def add(self, items, embeddings: Optional[np.ndarray] = None) -> None:
        """Add items to the corpus: hashes the batch once and seals it into a
        new index segment (O(batch) device work; earlier segments untouched)."""
        items = list(items)
        if not items:
            raise ValueError("cannot add an empty batch of items")
        emb = self._embed(items, embeddings, expect_rows=len(items))
        if self._params is None:
            self._dim = int(emb.shape[-1])
            self._params = self._make_params(self._dim)
        if self._index is None:
            # a meshed service serves from the sharded placement, so its
            # sealed segments wait on the host
            self._index = SegmentedIndex(engine=self._scheme.engine,
                                         max_count=self.m,
                                         signature_layout=self.signature_layout,
                                         host_resident=self.mesh is not None)
        self._index.add(self._hash(emb))
        self._items.extend(items)
        if len(self._index.segments) > self.max_segments:
            self._index.compact(max(1, self.max_segments // 2))

    def __len__(self) -> int:
        return len(self._items)

    @property
    def index_stats(self):
        """Aggregate IndexStats with per-segment build/compaction accounting."""
        if self._index is None:
            raise ValueError(
                "RetrievalService index is empty (no items added yet): "
                "call add() before reading index_stats"
            )
        return self._index.stats

    def _corpus_fingerprint(self) -> tuple:
        idx = self._index
        return (len(idx.segments), idx.n_objects, idx.compaction_count)

    def _sharded_corpus(self) -> tuple:
        """(sharded data, n_objects), cached until the corpus changes."""
        fp = self._corpus_fingerprint()
        if self._placed is None or self._placed[0] != fp:
            data, n = self._index.concat_data(pad_multiple=self.mesh.size)
            data = jax.device_put(data, distributed.data_sharding(self.mesh))
            self._placed = (fp, data, n)
        return self._placed[1], self._placed[2]

    def signatures(self, embeddings: np.ndarray) -> np.ndarray:
        """Hash embeddings [n, d] with this service's LSH parameters, as
        add() and search() do before the engine prepares them; fetched to
        the host."""
        if self._params is None:
            raise ValueError("RetrievalService has no LSH parameters yet: "
                             "call add() first")
        return np.asarray(self._hash(self._embed(None, embeddings)))

    def corpus_signatures(self) -> np.ndarray:
        """The stored (engine-prepared) signatures [n, width] in global-id
        order, fetched to the host: from the sharded placement on a meshed
        service, from the sealed segments otherwise."""
        if self._index is None:
            raise ValueError("RetrievalService index is empty (no items added yet)")
        if self.mesh is not None:
            data, n = self._sharded_corpus()
            return np.asarray(data)[:n]
        return self._index.concat_data()[0]

    def _router(self) -> routing_lib.Router:
        """Router over the current segments' summaries, cached until the
        corpus changes (same fingerprint as the sharded placement)."""
        fp = self._corpus_fingerprint()
        if self._routed is None or self._routed[0] != fp:
            self._routed = (fp, self._index.router())
        return self._routed[1]

    def resolve_queries(self, queries, embeddings: Optional[np.ndarray] = None
                        ) -> np.ndarray:
        """Materialise and embed one query batch, validating it eagerly:
        iterators are listed before len(), row counts and dims are checked,
        and an empty batch raises a ValueError naming the contract (the
        mirror of the empty-`add()` check) instead of failing downstream
        with a shape error.  The serving front-end (serve/frontend.py) calls
        this on the submitter's thread so bad requests fail synchronously."""
        if queries is not None:
            # materialise iterators/generators before len() -- same contract
            # as add(items); embed_fn receives the list either way
            queries = list(queries)
        eshape = None if embeddings is None else np.shape(embeddings)
        empty = (len(queries) == 0 if queries is not None
                 else bool(eshape) and eshape[0] == 0)
        if empty:
            # checked before embed_fn/shape validation so the caller sees
            # the contract, not a downstream shape error
            raise ValueError(
                "cannot search an empty batch of queries (the mirror of the "
                "empty-add() contract): pass at least one query or embedding "
                "row"
            )
        return self._embed(queries, embeddings,
                           expect_rows=None if queries is None else len(queries))

    def batch_compat_key(self, k: int, method: TopKMethod,
                         routing: routing_lib.Routing | str, *,
                         nprobe: Optional[int] = None,
                         candidate_cap: Optional[int] = None) -> tuple:
        """The coalescing key of a search against this service (core/plan.py
        `batch_compat_key`): two submissions with equal keys reuse one
        cached executable and can stack into one device dispatch.  The
        layout axis is resolved the way `search` will execute -- DISTRIBUTED
        on a mesh-backed service, SEGMENTED otherwise."""
        layout = (plan_lib.Layout.DISTRIBUTED if self.mesh is not None
                  else plan_lib.Layout.SEGMENTED)
        return plan_lib.batch_compat_key(
            self._scheme.engine, layout, self.signature_layout, routing,
            method, k, nprobe=nprobe, candidate_cap=candidate_cap)

    def search(self, queries, k: int = 10, *, embeddings: Optional[np.ndarray] = None,
               method: TopKMethod = TopKMethod.CPQ,
               candidate_cap: Optional[int] = None,
               routing: routing_lib.Routing | str = routing_lib.Routing.NONE,
               nprobe: Optional[int] = None):
        """tau-ANN retrieval over the sealed corpus.

        `routing` plugs the coarse router (core/routing.py) in front of the
        exact match: 'routed' scans only the segments/shards the router
        selects (approximate), 'routed_verified' additionally verifies the
        result threshold against the skipped segments' upper bounds and falls
        back to the full scan when one could still contribute (results then
        bit-for-bit identical to 'none').  Router state is rebuilt whenever
        the corpus fingerprint changes (an add or a compaction)."""
        if self._index is None:
            # a real exception, not an assert: asserts vanish under python -O
            raise ValueError(
                "RetrievalService index is empty (no items added yet): "
                "call add() before search()"
            )
        routing = routing_lib.Routing(routing)
        with tracing.span(tracing.HASH) as sp:
            emb = self.resolve_queries(queries, embeddings)
            sp.set_metadata(rows=int(emb.shape[0]))
            qsigs = self._hash(emb)
        if self.mesh is None:
            # the cached per-tenant router (fingerprint-keyed) rides into the
            # segment search, so interleaved add/search only rebuild routing
            # state when the corpus actually changed
            router = (self._router()
                      if routing is not routing_lib.Routing.NONE else None)
            res = self._index.search(qsigs, k=k, method=method,
                                     candidate_cap=candidate_cap,
                                     routing=routing, nprobe=nprobe,
                                     router=router, autotune=self.autotune)
        else:
            # sharded serving: the segmented corpus planned across the mesh
            # via the DISTRIBUTED layout, served by the same executor --
            # results are identical to the single-device segment merge
            data, n = self._sharded_corpus()
            plan = plan_lib.plan_search(
                self._scheme.engine, k, self._index.max_count,
                layout=plan_lib.Layout.DISTRIBUTED, n_objects=n, method=method,
                candidate_cap=candidate_cap,
                use_kernel=self._index.use_kernel,
                mesh_axes=tuple(self.mesh.axis_names),
                signature_layout=self.signature_layout,
                routing=routing, nprobe=nprobe,
                autotune=self.autotune,
                tune_width=int(data.shape[1]),
            )
            model = engines_lib.get(self._scheme.engine)
            # the router scores canonical WIDE queries; the executor gets
            # them packed when the corpus is PACKED
            q_wide = model.prepare_queries(qsigs)
            canonical = q_wide
            if SignatureLayout(self.signature_layout) is SignatureLayout.PACKED:
                canonical = model.pack_queries(q_wide)
            qq = jax.device_put(canonical, distributed.replicated(self.mesh, 2))
            router = (self._router()
                      if routing is not routing_lib.Routing.NONE else None)
            res = plan_lib.execute(plan, data, qq, mesh=self.mesh,
                                   router=router, route_queries=q_wide)
        # scheme-paired MLE: c/m for bucketed families (Eqn 7), the simhash
        # angle inversion for COSINE
        with tracing.span(tracing.WAIT):
            counts = np.asarray(res.counts)
        sims = self._scheme.mle(counts, self.m)
        return res, sims

    def tune(self, queries, k: int = 10, *,
             embeddings: Optional[np.ndarray] = None,
             method: TopKMethod = TopKMethod.CPQ,
             routing: routing_lib.Routing | str = routing_lib.Routing.NONE,
             budget: int = 32, repeats: int = 3,
             cache=None, save: bool = True):
        """Autotune this service's serving shape against a representative
        query batch (core/autotune.py) and return the winning TunedEntry.

        Measures the SEGMENTED search the unmeshed path actually runs (the
        host loop that streams one segment at a time: the paper's multiple
        loading) -- tile sizes, fused preference, candidate_cap, and (when
        `routing` is routed) nprobe.  The winner
        lands in `cache` (defaulting to this service's `autotune` spec; an
        in-memory cache is created and installed when neither is set), so
        every later `search` picks the tuned knobs up automatically.
        """
        from repro.core import autotune as autotune_lib

        if self._index is None:
            raise ValueError(
                "RetrievalService index is empty (no items added yet): "
                "call add() before tune()"
            )
        routing = routing_lib.Routing(routing)
        with tracing.span(tracing.HASH) as sp:
            emb = self.resolve_queries(queries, embeddings)
            sp.set_metadata(rows=int(emb.shape[0]))
            qsigs = self._hash(emb)
        model = engines_lib.get(self._scheme.engine)
        q_wide = model.prepare_queries(qsigs)
        q_exec = q_wide
        if SignatureLayout(self.signature_layout) is SignatureLayout.PACKED:
            q_exec = model.pack_queries(q_wide)
        stored = jnp.concatenate([s.data for s in self._index.segments], axis=0)
        resolved = autotune_lib.resolve_cache(
            cache if cache is not None else self.autotune)
        if resolved is None:
            resolved = autotune_lib.AutotuneCache()
        entry = autotune_lib.tune(
            model, stored, q_exec, k, self._index.max_count,
            signature_layout=self.signature_layout, method=method,
            part_rows=tuple(self._index.segment_rows),
            router=(self._router()
                    if routing is not routing_lib.Routing.NONE else None),
            routing=routing, budget=budget, repeats=repeats,
            cache=resolved, save=save, prepared=True, route_queries=q_wide,
        )
        if self.autotune is None or self.autotune is False:
            self.autotune = resolved
        return entry

    def items_for(self, result_ids: np.ndarray) -> list:
        """Resolve result ids to the stored items; -1 (empty top-k slots)
        resolve to None.  Ids outside [0, len(self)) raise a ValueError
        naming the offender instead of surfacing an IndexError (or, worse,
        a silently wrong negatively-indexed item)."""
        n = len(self._items)
        rows = np.asarray(result_ids)
        bad = rows[(rows >= n) | (rows < -1)]
        if bad.size:
            # "0..-1" is not a range: name the empty corpus explicitly
            valid = f"valid ids are 0..{n - 1}" if n else "no ids are valid"
            raise ValueError(
                f"items_for: id {int(bad.flat[0])} is outside the corpus "
                f"({n} items indexed; {valid}, or -1 for an empty top-k slot)"
            )
        return [[self._items[int(i)] if i >= 0 else None for i in row] for row in rows]
