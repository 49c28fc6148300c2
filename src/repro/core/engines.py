"""MatchModel registry: one descriptor per match-count engine.

GENIE's central claim is *genericity* -- one inverted-index machinery serving
many data types and similarity measures (paper section II).  This module makes
that claim structural: every engine (EQ, RANGE, MINSUM, IP, TANIMOTO, COSINE,
and any future measure) is a single `MatchModel` descriptor bundling

  * the reference match function (core/match.py -- the semantics oracle),
  * the Pallas kernel wrapper (kernels/ops.py -- the TPU hot path),
  * query canonicalisation (so every engine exposes the same
    ``fn(data, queries) -> counts[Q, N]`` signature; RANGE queries are the
    pytree ``(lo, hi)``),
  * data preparation + index statistics (what GenieIndex.build_* duplicated),
  * the count-dtype policy (Bitmap-Counter bit-bounding, paper III-C),
  * the pad-row fill (a value that can never out-score real rows).

GenieIndex, SegmentedIndex, core.plan, and launch.dryrun all resolve
engines through `get()` -- there is exactly one dispatch point in the system.
Registering a new similarity measure is one `register(MatchModel(...))` call;
see docs/ENGINES.md for the contract and a worked example.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import match as _match
from repro.core import packing as _packing
from repro.core.types import Engine, IndexStats, SignatureLayout

# Tile-knob alignment floors (kernels/common.py::pick_tile enforces them at
# dispatch): tile_q is a sublane dim (8), tile_n / tile_v / tile_m are lane
# dims (128) -- the TPU min-tile widths every kernel's BlockSpec assumes.
TILE_ALIGN: dict[str, int] = {
    "tile_q": 8,
    "tile_n": 128,
    "tile_v": 128,
    "tile_m": 128,
}


def canonical_tile_overrides(tile_overrides) -> tuple[tuple[str, int], ...]:
    """Normalise a mapping / pair-sequence of tile knobs to the sorted tuple
    form QueryPlan hashes on, validating names and alignment floors."""
    if tile_overrides is None:
        return ()
    items = (tile_overrides.items() if hasattr(tile_overrides, "items")
             else tile_overrides)
    out = []
    for name, value in items:
        name = str(name)
        if name not in TILE_ALIGN:
            raise ValueError(
                f"unknown tile knob {name!r}; known knobs: "
                f"{sorted(TILE_ALIGN)}"
            )
        value = int(value)
        if value < TILE_ALIGN[name]:
            raise ValueError(
                f"{name}={value} is below the alignment floor "
                f"{TILE_ALIGN[name]} (TPU min-tile width); tuned tiles must "
                f"be >= the floor"
            )
        out.append((name, value))
    if len({n for n, _ in out}) != len(out):
        raise ValueError(f"duplicate tile knob in {tile_overrides!r}")
    return tuple(sorted(out))


# Tile-bound match callables, memoized so two plans with equal
# (model, use_kernel, layout, overrides, fused) share ONE callable identity:
# QueryPlan hashes its match/fused_match fields, so memoisation here is what
# lets tuned plans hit the executable cache instead of re-tracing per call.
_TILED_FN_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class MatchModel:
    """Descriptor for one match-count engine (paper Definition 2.1).

    The canonical match signature is ``fn(data, queries) -> counts [Q, N]``
    where `queries` is this engine's canonical query pytree (produced by
    `prepare_queries`).  Both `reference` and `kernel` use it, so segmented
    streaming, distributed sharding, and serving are engine-agnostic.
    """

    engine: Engine
    description: str
    # raw user data -> device-resident index array (dtype/canonical form)
    prepare_data: Callable[[Any], jnp.ndarray]
    # raw queries -> canonical query pytree of device arrays
    prepare_queries: Callable[[Any], Any]
    # pure-jnp reference semantics (core/match.py), canonical signature
    reference: Callable[[jnp.ndarray, Any], jnp.ndarray]
    # Pallas kernel wrapper (kernels/ops.py), canonical signature; lazily
    # imports the kernels so CPU-only uses never pay for them
    kernel: Callable[[jnp.ndarray, Any], jnp.ndarray]
    # index statistics: postings count for this data layout
    postings_count: Callable[[jnp.ndarray], int]
    # default count-domain bound, or None when the caller must supply one
    default_max_count: Callable[[jnp.ndarray], Optional[int]]
    # pad-row fill (`plan.pad_to_multiple`: SEGMENTED or DISTRIBUTED plans
    # with n_objects): padded rows must never beat real rows
    pad_value: Any = -1
    # seeded conformance data: (np rng, n, q) -> (raw_data, raw_queries,
    # max_count | None).  Engines that provide it get the engine-matrix
    # parity/pad/tie conformance tests (tests/test_engine_matrix.py) for free.
    example: Optional[Callable[[Any, int, int], tuple]] = None

    # -- PACKED signature layout (core/packing.py) --------------------------
    # All None/unset => the engine is WIDE-only and PACKED plans are rejected.
    # pack_data / pack_queries transform *prepared* (canonical WIDE) arrays
    # once at index-seal / query-canonicalisation time; packed_reference and
    # packed_kernel keep the canonical ``fn(data, queries) -> counts [Q, N]``
    # signature on the packed arrays, with counts bit-for-bit equal to WIDE.
    pack_data: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None
    pack_queries: Optional[Callable[[Any], Any]] = None
    packed_reference: Optional[Callable[[jnp.ndarray, Any], jnp.ndarray]] = None
    packed_kernel: Optional[Callable[[jnp.ndarray, Any], jnp.ndarray]] = None
    # fused match -> count -> per-tile local top-k on packed arrays:
    # fn(data, queries, k) -> (ids, counts) candidate buffers [Q, n_tiles*kc]
    # in per-tile (count desc, id asc) order, pads id -1 / count -1
    packed_fused_topk: Optional[Callable[[jnp.ndarray, Any, int], tuple]] = None
    # pad-row fill in the packed domain (same never-out-scores contract
    # as pad_value; pad rows are id-masked upstream regardless)
    packed_pad_value: Any = None
    # packed footprint in bytes, computed from the WIDE prepared array
    packed_bytes: Optional[Callable[[jnp.ndarray], int]] = None

    # -- tile knobs (core/autotune.py) --------------------------------------
    # The tile kwargs each kernel wrapper accepts (kernels/ops.py): the
    # autotuner's searchable axes for this engine.  Empty => the path takes
    # no tile overrides (reference fns never do).
    kernel_tile_knobs: frozenset = frozenset()
    packed_tile_knobs: frozenset = frozenset()
    packed_fused_tile_knobs: frozenset = frozenset()

    @property
    def supports_packed(self) -> bool:
        return self.pack_data is not None

    def require_layout(self, layout: SignatureLayout | str) -> SignatureLayout:
        layout = SignatureLayout(layout)
        if layout is SignatureLayout.PACKED and not self.supports_packed:
            raise ValueError(
                f"engine {self.engine.value!r} has no packed signature format; "
                f"use SignatureLayout.WIDE"
            )
        return layout

    def pad_value_for(self, layout: SignatureLayout | str) -> Any:
        if SignatureLayout(layout) is SignatureLayout.PACKED:
            self.require_layout(layout)
            return self.packed_pad_value
        return self.pad_value

    def tile_knobs(
        self,
        use_kernel: bool,
        signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
        fused: bool = False,
    ) -> frozenset:
        """The tile knob names this engine's dispatch path accepts."""
        if not use_kernel:
            return frozenset()
        if self.require_layout(signature_layout) is SignatureLayout.PACKED:
            return (self.packed_fused_tile_knobs if fused
                    else self.packed_tile_knobs)
        return self.kernel_tile_knobs

    def _tiled(self, base: Callable, overrides: tuple, knobs: frozenset,
               tag: str) -> Callable:
        """Memoized wrapper binding the tile kwargs `base` accepts.  Knobs the
        path does not take (e.g. tile_m on a fused kernel that chunks the
        signature axis internally) are dropped, so one tuned entry can drive
        both the count and fused dispatchers."""
        kw = {n: v for n, v in overrides if n in knobs}
        if not kw:
            return base
        key = (tag, self, base, tuple(sorted(kw.items())))
        fn = _TILED_FN_CACHE.get(key)
        if fn is None:
            if tag == "fused":
                def fn(data, queries, k, _base=base, _kw=kw):
                    return _base(data, queries, k, **_kw)
            else:
                def fn(data, queries, _base=base, _kw=kw):
                    return _base(data, queries, **_kw)
            _TILED_FN_CACHE[key] = fn
        return fn

    # -- dispatch -----------------------------------------------------------
    def match_fn(
        self,
        use_kernel: bool,
        signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
        tile_overrides: tuple = (),
    ) -> Callable[[jnp.ndarray, Any], jnp.ndarray]:
        """The canonical match callable for this engine (kernel or reference),
        operating on arrays in the given signature layout.  `tile_overrides`
        (canonical ``((knob, value), ...)`` pairs, see
        `canonical_tile_overrides`) bind kernel tile kwargs; the returned
        callable is memoized per override set so equal plans share one
        identity (the executable cache keys on it)."""
        layout = self.require_layout(signature_layout)
        if layout is SignatureLayout.PACKED:
            base = self.packed_kernel if use_kernel else self.packed_reference
        else:
            base = self.kernel if use_kernel else self.reference
        if not tile_overrides or not use_kernel:
            return base
        return self._tiled(base, tile_overrides,
                           self.tile_knobs(use_kernel, layout), "match")

    def fused_topk_fn(
        self,
        tile_overrides: tuple = (),
    ) -> Optional[Callable[[jnp.ndarray, Any, int], tuple]]:
        """The fused packed match->count->local-top-k callable with tile
        overrides bound (same memoisation contract as match_fn)."""
        if self.packed_fused_topk is None or not tile_overrides:
            return self.packed_fused_topk
        return self._tiled(self.packed_fused_topk, tile_overrides,
                           self.packed_fused_tile_knobs, "fused")

    def prepare_queries_for(
        self, queries: Any,
        signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
    ) -> Any:
        """Raw queries -> canonical query pytree in the given layout
        (canonicalise WIDE first, then pack)."""
        q = self.prepare_queries(queries)
        if self.require_layout(signature_layout) is SignatureLayout.PACKED:
            q = self.pack_queries(q)
        return q

    def match_counts(self, data: jnp.ndarray, queries: Any, use_kernel: bool,
                     signature_layout: SignatureLayout | str = SignatureLayout.WIDE) -> jnp.ndarray:
        """counts int32 [Q, N]; `queries` may be raw (canonicalised here) and
        `data` must already be in `signature_layout`."""
        return self.match_fn(use_kernel, signature_layout)(
            data, self.prepare_queries_for(queries, signature_layout))

    # -- build-time policy --------------------------------------------------
    def build_stats(self, data: jnp.ndarray) -> IndexStats:
        """Index statistics from the *prepared WIDE* array (postings, count
        bounds, and the packed footprint all read the logical layout -- call
        this before pack_data, never on the packed array)."""
        wide_bytes = int(data.size) * data.dtype.itemsize
        return IndexStats(
            n_objects=int(data.shape[0]),
            n_lists=int(data.shape[1]),
            total_postings=int(self.postings_count(data)),
            bytes_device=wide_bytes,
            bytes_signatures_wide=wide_bytes,
            bytes_signatures_packed=(
                int(self.packed_bytes(data)) if self.packed_bytes else 0
            ),
            extra={"engine": self.engine.value},
        )

    def resolve_max_count(self, data: jnp.ndarray, max_count: Optional[int]) -> int:
        if max_count is not None:
            return int(max_count)
        derived = self.default_max_count(data)
        if derived is None:
            raise ValueError(
                f"engine {self.engine.value!r} has no derivable count bound; "
                f"pass max_count explicitly"
            )
        return int(derived)

    def count_dtype(self, max_count: int) -> jnp.dtype:
        """Bitmap-Counter policy: narrowest lossless count dtype (III-C)."""
        probe = _match.as_count_dtype(jnp.zeros((), jnp.int32), max_count)
        return probe.dtype

    def as_count_dtype(self, counts: jnp.ndarray, max_count: int) -> jnp.ndarray:
        return _match.as_count_dtype(counts, max_count)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[Engine, MatchModel] = {}


def register(model: MatchModel) -> MatchModel:
    """Register (or replace) the descriptor for `model.engine`."""
    _REGISTRY[model.engine] = model
    return model


def get(engine: Engine | str | MatchModel) -> MatchModel:
    """Resolve an Engine, its string value, or a MatchModel to a descriptor."""
    if isinstance(model := engine, MatchModel):
        return model
    eng = Engine(engine)
    try:
        return _REGISTRY[eng]
    except KeyError:
        raise KeyError(
            f"no MatchModel registered for engine {eng.value!r}; "
            f"known: {sorted(m.value for m in _REGISTRY)}"
        ) from None


def available() -> tuple[Engine, ...]:
    return tuple(_REGISTRY)


def resolve_match_fn(engine, use_kernel: bool = False,
                     signature_layout: SignatureLayout | str = SignatureLayout.WIDE):
    """Engine/str/MatchModel/callable -> canonical match callable.

    Raw callables pass through untouched (code that plans a bare
    ``fn(data, queries)``) -- the caller owns the layout contract in that
    case.
    """
    if callable(engine) and not isinstance(engine, (MatchModel, Engine, str)):
        return engine
    return get(engine).match_fn(use_kernel, signature_layout)


# ---------------------------------------------------------------------------
# Built-in engines (paper sections IV-V)
# ---------------------------------------------------------------------------

def _kernel_eq(data, queries, **tiles):
    from repro.kernels import ops as kops

    return kops.match_count(data, queries, **tiles)


def _kernel_range(data, queries, **tiles):
    from repro.kernels import ops as kops

    lo, hi = queries
    return kops.range_count(data, lo, hi, **tiles)


def _kernel_minsum(data, queries, **tiles):
    from repro.kernels import ops as kops

    return kops.minsum_count(data, queries, **tiles)


def _kernel_ip(data, queries, **tiles):
    from repro.kernels import ops as kops

    return kops.ip_count(data, queries, **tiles)


def _kernel_tanimoto(data, queries, **tiles):
    from repro.kernels import ops as kops

    return kops.tanimoto_count(data, queries, **tiles)


def _kernel_cosine(data, queries, **tiles):
    from repro.kernels import ops as kops

    return kops.cosine_count(data, queries, **tiles)


def _kernel_packed_cosine(data, queries, **tiles):
    from repro.kernels import ops as kops

    return kops.packed_cosine_count(data, queries, **tiles)


def _kernel_packed_cosine_topk(data, queries, k, **tiles):
    from repro.kernels import ops as kops

    return kops.packed_cosine_topk(data, queries, k=k, **tiles)


def _kernel_packed_tanimoto(data, queries, **tiles):
    from repro.kernels import ops as kops

    return kops.packed_tanimoto_count(data, queries, **tiles)


def _kernel_packed_tanimoto_topk(data, queries, k, **tiles):
    from repro.kernels import ops as kops

    return kops.packed_tanimoto_topk(data, queries, k=k, **tiles)


def _sign_quantize(x) -> jnp.ndarray:
    """Raw vectors -> {-1, +1} int8 (floats by sign; {0,1} bits map to -1/+1)."""
    x = jnp.asarray(x)
    return jnp.where(x > 0, 1, -1).astype(jnp.int8)


register(MatchModel(
    engine=Engine.EQ,
    description="signature equality compare over LSH signatures int32 [N, m]",
    prepare_data=lambda x: jnp.asarray(x, dtype=jnp.int32),
    prepare_queries=lambda q: jnp.asarray(q, dtype=jnp.int32),
    reference=_match.match_eq,
    kernel=_kernel_eq,
    postings_count=lambda a: int(a.shape[0]) * int(a.shape[1]),
    default_max_count=lambda a: int(a.shape[1]),          # m hash functions
    pad_value=-1,                                          # never equals a sig
    example=lambda rng, n, q: (rng.integers(0, 8, (n, 16)).astype(np.int32),
                               rng.integers(0, 8, (q, 16)).astype(np.int32), None),
    kernel_tile_knobs=frozenset({"tile_q", "tile_n"}),
))

register(MatchModel(
    engine=Engine.RANGE,
    description="per-attribute interval predicate over discretized tuples int32 [N, d]",
    prepare_data=lambda x: jnp.asarray(x, dtype=jnp.int32),
    prepare_queries=lambda q: (jnp.asarray(q[0], dtype=jnp.int32),
                               jnp.asarray(q[1], dtype=jnp.int32)),
    reference=lambda d, q: _match.match_range(d, q[0], q[1]),
    kernel=_kernel_range,
    postings_count=lambda a: int(a.size),
    default_max_count=lambda a: int(a.shape[1]),          # #attributes
    pad_value=np.iinfo(np.int32).min,                     # below any query lo
    example=lambda rng, n, q: (
        rng.integers(0, 10, (n, 6)).astype(np.int32),
        (lambda lo: (lo, lo + 3))(rng.integers(0, 6, (q, 6)).astype(np.int32)),
        None),
    kernel_tile_knobs=frozenset({"tile_q", "tile_n"}),
))

register(MatchModel(
    engine=Engine.MINSUM,
    description="multiset intersection sum_v min(c_data, c_query) over count vectors [N, V]",
    prepare_data=lambda x: jnp.asarray(x, dtype=jnp.int32),
    prepare_queries=lambda q: jnp.asarray(q, dtype=jnp.int32),
    reference=_match.match_minsum,
    kernel=_kernel_minsum,
    postings_count=lambda a: int(np.asarray(jnp.sum(a))),
    default_max_count=lambda a: None,                     # caller supplies bound
    pad_value=-1,                                          # min(-1, q) sums < 0
    example=lambda rng, n, q: (rng.integers(0, 4, (n, 24)).astype(np.int32),
                               rng.integers(0, 4, (q, 24)).astype(np.int32), 96),
    kernel_tile_knobs=frozenset({"tile_q", "tile_n", "tile_v"}),
))

register(MatchModel(
    engine=Engine.IP,
    description="binary inner product on the MXU over word vectors [N, V]",
    prepare_data=jnp.asarray,                              # keep caller dtype
    prepare_queries=jnp.asarray,
    reference=_match.match_ip,
    kernel=_kernel_ip,
    postings_count=lambda a: int(np.asarray(jnp.sum(a.astype(jnp.int32)))),
    default_max_count=lambda a: None,                     # caller supplies bound
    pad_value=0,                                           # zero dot product
    example=lambda rng, n, q: (rng.integers(0, 2, (n, 32)).astype(np.int32),
                               rng.integers(0, 2, (q, 32)).astype(np.int32), 32),
    kernel_tile_knobs=frozenset({"tile_q", "tile_n", "tile_v"}),
))

register(MatchModel(
    engine=Engine.TANIMOTO,
    description="minhash collision count over set sketches int32 [N, m] (Jaccard MLE c/m)",
    prepare_data=lambda x: jnp.asarray(x, dtype=jnp.int32),
    prepare_queries=lambda q: jnp.asarray(q, dtype=jnp.int32),
    reference=_match.match_tanimoto,
    kernel=_kernel_tanimoto,
    postings_count=lambda a: int(a.shape[0]) * int(a.shape[1]),
    default_max_count=lambda a: int(a.shape[1]),          # m minhash functions
    pad_value=-1,                                          # outside bucket range
    example=lambda rng, n, q: (rng.integers(0, 64, (n, 20)).astype(np.int32),
                               rng.integers(0, 64, (q, 20)).astype(np.int32), None),
    # PACKED: uint8 bucket ids (rehash domain <= 253; 254/255 pad sentinels)
    pack_data=_packing.pack_buckets,
    pack_queries=_packing.pack_buckets,
    packed_reference=_packing.packed_tanimoto_match,
    packed_kernel=_kernel_packed_tanimoto,
    packed_fused_topk=_kernel_packed_tanimoto_topk,
    packed_pad_value=_packing.PACKED_BUCKET_PAD_DATA,      # never collides
    packed_bytes=_packing.packed_bytes_tanimoto,
    kernel_tile_knobs=frozenset({"tile_q", "tile_n", "tile_m"}),
    packed_tile_knobs=frozenset({"tile_q", "tile_n", "tile_m"}),
    # the fused kernel chunks the signature axis in VMEM itself: no tile_m
    packed_fused_tile_knobs=frozenset({"tile_q", "tile_n"}),
))

register(MatchModel(
    engine=Engine.COSINE,
    description="sign-agreement count of sign-quantized vectors {-1,+1} [N, V] on the MXU",
    prepare_data=_sign_quantize,
    prepare_queries=_sign_quantize,
    reference=_match.match_cosine,
    kernel=_kernel_cosine,
    postings_count=lambda a: int(a.size),                  # every sign is a posting
    default_max_count=lambda a: int(a.shape[1]),          # V sign agreements max
    pad_value=0,                                           # dot-neutral; id-masked
    example=lambda rng, n, q: (rng.standard_normal((n, 32)).astype(np.float32),
                               rng.standard_normal((q, 32)).astype(np.float32), None),
    # PACKED: 32 signs per int32 word, matched by XOR+popcount; query tail
    # bits 1 vs data tail bits 0 keep counts exact without knowing V
    pack_data=_packing.pack_signs_data,
    pack_queries=_packing.pack_signs_queries,
    packed_reference=_packing.packed_cosine_match,
    packed_kernel=_kernel_packed_cosine,
    packed_fused_topk=_kernel_packed_cosine_topk,
    packed_pad_value=0,                                    # all-zero words; id-masked
    packed_bytes=_packing.packed_bytes_cosine,
    kernel_tile_knobs=frozenset({"tile_q", "tile_n", "tile_v"}),
    # packed words chunk the bit axis in VMEM: only the [Q, N] tiles tune
    packed_tile_knobs=frozenset({"tile_q", "tile_n"}),
    packed_fused_tile_knobs=frozenset({"tile_q", "tile_n"}),
))
