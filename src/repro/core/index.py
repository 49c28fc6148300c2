"""GenieIndex: the user-facing GENIE index (paper sections II-III).

Holds device-resident transformed data (signatures / count vectors / binary
vectors / discretized tuples) and resolves *everything* engine-specific --
data preparation, query canonicalisation, kernel-vs-reference match dispatch,
index statistics, count-domain bounds -- through the MatchModel registry
(core/engines.py).  Searches are thin adapters over the unified planner
(core/plan.py): `search` builds a MONOLITHIC QueryPlan and delegates to the
one executor that owns match dispatch, pad masking, top-k selection, and
merging (docs/EXECUTION.md).

    index = GenieIndex.build(Engine.EQ, sigs)            # generic builder
    index = GenieIndex.build_lsh(sigs, max_count=m)      # named alias
    result = index.search(query_sigs, k=100)             # TopKResult

Larger-than-memory data is a `SegmentedIndex` (core/segments.py), whose
SEGMENTED host loop streams one part at a time through the device -- the
paper's multiple loading; multi-device search is a DISTRIBUTED plan over
the sharded data matrix (core/distributed.py places it).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import engines as _engines
from repro.core import plan as _plan
from repro.core import routing as _routing
from repro.core.types import (Engine, IndexStats, SignatureLayout,
                              TopKMethod, TopKResult)


@dataclasses.dataclass
class GenieIndex:
    engine: Engine
    max_count: int
    data: jnp.ndarray                      # EQ: sigs [N,m]; MINSUM: counts [N,V];
    data_hi: Optional[jnp.ndarray] = None  # unused (reserved for interval data)
    stats: IndexStats = dataclasses.field(default_factory=IndexStats)
    use_kernel: bool = True
    # storage format of `data` (core/packing.py); PACKED indexes hold the
    # bit/byte-packed array and dispatch the packed match kernels
    signature_layout: SignatureLayout = SignatureLayout.WIDE
    # routing summary over the *wide* prepared array (core/routing.py),
    # computed at seal time; None for indexes assembled outside build()
    summary: Optional[_routing.SegmentSummary] = None

    # ------------------------------------------------------------------
    # Builders
    # ------------------------------------------------------------------
    @classmethod
    def build(cls, engine: Engine | str, data, max_count: int | None = None,
              use_kernel: bool = True,
              signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
              ) -> "GenieIndex":
        """Generic builder: any registered engine, one code path.

        `max_count` defaults to the engine's derived count bound (e.g. m for
        EQ, #attributes for RANGE); engines without a derivable bound
        (MINSUM, IP) require it explicitly.

        `signature_layout=PACKED` packs the prepared array once at seal time
        (COSINE signs -> uint32-word bitfields, TANIMOTO bucket ids -> uint8)
        for engines with a packed format; counts and top-k results are
        bit-for-bit identical to WIDE, only the device footprint and HBM
        traffic shrink.
        """
        model = _engines.get(engine)
        layout = model.require_layout(signature_layout)
        # perf_counter, not time(): a wall-clock (NTP) step must never record
        # a negative build duration
        t0 = time.perf_counter()
        arr = model.prepare_data(data)
        # stats, postings, the count bound, and the routing summary all read
        # the *logical* WIDE shape -- resolve them before packing (the packed
        # array's width is words/bytes, not signature slots)
        stats = model.build_stats(arr)
        summary = _routing.summarize(model.engine, arr)
        max_count = model.resolve_max_count(arr, max_count)
        if layout is SignatureLayout.PACKED:
            arr = model.pack_data(arr)
            stats.signature_layout = layout.value
            stats.bytes_device = int(arr.size) * arr.dtype.itemsize
        # block: prepare_data dispatches async jnp ops; without this the
        # timer reports dispatch time, not build time
        jax.block_until_ready(arr)
        stats.build_seconds = time.perf_counter() - t0
        return cls(engine=model.engine, max_count=max_count,
                   data=arr, stats=stats, use_kernel=use_kernel,
                   signature_layout=layout, summary=summary)

    # Thin named aliases kept for API compatibility with existing callers.
    @classmethod
    def build_lsh(cls, signatures, max_count: int | None = None, use_kernel: bool = True):
        """EQ engine over LSH signatures int32 [N, m]."""
        return cls.build(Engine.EQ, signatures, max_count=max_count, use_kernel=use_kernel)

    @classmethod
    def build_minsum(cls, count_vectors, max_count: int, use_kernel: bool = True):
        """MINSUM engine over n-gram count vectors int [N, V]."""
        return cls.build(Engine.MINSUM, count_vectors, max_count=max_count,
                         use_kernel=use_kernel)

    @classmethod
    def build_ip(cls, binary_vectors, max_count: int, use_kernel: bool = True):
        """IP engine over binary word vectors [N, V]."""
        return cls.build(Engine.IP, binary_vectors, max_count=max_count,
                         use_kernel=use_kernel)

    @classmethod
    def build_relational(cls, discrete_tuples, use_kernel: bool = True):
        """RANGE engine over discretized tuples int32 [N, d]."""
        return cls.build(Engine.RANGE, discrete_tuples, use_kernel=use_kernel)

    @classmethod
    def build_tanimoto(cls, minhash_sigs, max_count: int | None = None,
                       use_kernel: bool = True,
                       signature_layout: SignatureLayout | str = SignatureLayout.WIDE):
        """TANIMOTO engine over minhash sketches int32 [N, m]."""
        return cls.build(Engine.TANIMOTO, minhash_sigs, max_count=max_count,
                         use_kernel=use_kernel, signature_layout=signature_layout)

    @classmethod
    def build_cosine(cls, vectors, max_count: int | None = None,
                     use_kernel: bool = True,
                     signature_layout: SignatureLayout | str = SignatureLayout.WIDE):
        """COSINE engine over raw vectors [N, V] (sign-quantized at build)."""
        return cls.build(Engine.COSINE, vectors, max_count=max_count,
                         use_kernel=use_kernel, signature_layout=signature_layout)

    # ------------------------------------------------------------------
    # Matching + selection
    # ------------------------------------------------------------------
    @property
    def model(self) -> _engines.MatchModel:
        return _engines.get(self.engine)

    def prepare_queries(self, queries):
        """Raw queries -> canonical pytree in this index's signature layout."""
        return self.model.prepare_queries_for(queries, self.signature_layout)

    def match_counts(self, queries) -> jnp.ndarray:
        """counts int32 [Q, N] under this index's engine."""
        return self.model.match_counts(self.data, queries, self.use_kernel,
                                       self.signature_layout)

    def search(self, queries, k: int, method: TopKMethod = TopKMethod.CPQ,
               candidate_cap: int | None = None,
               tile_overrides=None, autotune=None) -> TopKResult:
        plan = _plan.plan_search(
            self.engine, k, self.max_count, layout=_plan.Layout.MONOLITHIC,
            part_rows=(self.stats.n_objects,), method=method,
            candidate_cap=candidate_cap, use_kernel=self.use_kernel,
            signature_layout=self.signature_layout,
            tile_overrides=tile_overrides, autotune=autotune,
            tune_width=int(self.data.shape[1]),
        )
        return _plan.execute(plan, self.data, self.prepare_queries(queries))
