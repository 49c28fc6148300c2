"""Core types for the GENIE match-count / top-k search framework."""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Optional

import jax
import jax.numpy as jnp


class Engine(str, enum.Enum):
    """Match-count execution engines (see DESIGN.md section 2).

    EQ       -- signature equality compare (LSH-transformed data).
    RANGE    -- per-attribute interval predicate (relational data).
    MINSUM   -- multiset intersection  sum_v min(c_data, c_query)  (SA n-grams).
    IP       -- binary inner product on the MXU (SA documents / sets).
    TANIMOTO -- minhash collision count estimating Jaccard over sets (FLASH).
    COSINE   -- sign-agreement count of sign-quantized vectors on the MXU
                (simhash-angle cosine, Johnson et al. 1702.08734).
    """

    EQ = "eq"
    RANGE = "range"
    MINSUM = "minsum"
    IP = "ip"
    TANIMOTO = "tanimoto"
    COSINE = "cosine"


class TopKMethod(str, enum.Enum):
    CPQ = "cpq"          # the paper's c-PQ (histogram gate, Theorem 3.1)
    SPQ = "spq"          # baseline: bucket k-selection (paper appendix / GPU-SPQ)
    SORT = "sort"        # baseline: full lax.top_k (sort-based)


class SignatureLayout(str, enum.Enum):
    """Device-resident signature storage format (core/packing.py).

    WIDE    -- one signature slot per array element (the historical layout:
               int8 +-1 signs for COSINE, int32 bucket ids for TANIMOTO).
    PACKED  -- bit/byte-packed: COSINE signs become uint32-word bitfields
               matched by XOR+popcount (FLASH, Wang et al. 1709.01190),
               TANIMOTO bucket ids narrow to one byte matched by byte
               compare.  Counts are bit-for-bit identical to WIDE; only the
               bytes moved per object shrink (4-8x).  Engines without a
               packed format reject PACKED plans at build/plan time.
    """

    WIDE = "wide"
    PACKED = "packed"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TopKResult:
    """Result of a top-k match-count query batch.

    ids:       int32 [Q, k]  object ids (-1 padding when fewer than k objects).
    counts:    int32 [Q, k]  match-count values, non-increasing along k.
    threshold: int32 [Q]     AT-1 per Theorem 3.1 == match count of the k-th object.
    """

    ids: jnp.ndarray
    counts: jnp.ndarray
    threshold: jnp.ndarray

    @property
    def k(self) -> int:
        return self.ids.shape[-1]


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Static parameters of a GENIE search."""

    k: int
    max_count: int                 # count-domain bound (e.g. m for LSH, #attrs for tables)
    method: TopKMethod = TopKMethod.CPQ
    candidate_cap: Optional[int] = None  # capacity of the candidate buffer (default 2k)
    use_kernel: bool = True        # Pallas kernels (interpreted on CPU) vs pure jnp

    def cap(self) -> int:
        if self.candidate_cap is not None:
            return max(self.candidate_cap, self.k)
        return max(2 * self.k, self.k + 16)


@dataclasses.dataclass
class IndexStats:
    """Host-side statistics recorded at index-build time.

    The segment fields describe a SegmentedIndex (core/segments.py): a
    monolithic GenieIndex is the degenerate single-segment case
    (`n_segments=1`, empty per-segment lists, no compactions).
    """

    n_objects: int = 0
    n_lists: int = 0
    total_postings: int = 0
    max_list_len: int = 0
    bytes_device: int = 0
    build_seconds: float = 0.0
    # signature storage accounting: bytes the corpus occupies under each
    # layout (bytes_device equals whichever layout is actually resident;
    # bytes_signatures_packed is 0 for engines without a packed format)
    signature_layout: str = SignatureLayout.WIDE.value
    bytes_signatures_wide: int = 0
    bytes_signatures_packed: int = 0
    # per-segment build/compaction accounting (core/segments.py)
    n_segments: int = 1
    segment_rows: list[int] = dataclasses.field(default_factory=list)
    segment_build_seconds: list[float] = dataclasses.field(default_factory=list)
    compaction_count: int = 0
    compaction_seconds: float = 0.0
    extra: dict[str, Any] = dataclasses.field(default_factory=dict)
