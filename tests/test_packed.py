"""Packed signature storage (core/packing.py) + fused kernels: unit sweeps.

The layout contract: packing is storage-only.  Counts, ids, and candidate
buffers computed on packed arrays are bit-for-bit equal to the WIDE
references for every signature width -- including widths that don't divide
the 32-bit word (COSINE tail bits) and tile sizes that don't divide N/Q
(kernel grid padding).  System-level parity (engine x layout x method) lives
in tests/test_engine_matrix.py and tests/test_plan.py; this module pins the
packing primitives and the Pallas kernels themselves.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cpq, engines, match, packing
from repro.core.types import Engine, SearchParams
from repro.kernels import ops


def _signs(rng, n, v):
    return (rng.integers(0, 2, (n, v)) * 2 - 1).astype(np.int8)


# ---------------------------------------------------------------------------
# Bit-packing round trip + tail-bit convention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v", [1, 31, 32, 33, 64, 513])
def test_pack_signs_round_trip(v):
    rng = np.random.default_rng(v)
    signs = _signs(rng, 9, v)
    words = packing.pack_signs_data(jnp.asarray(signs))
    assert words.shape == (9, packing.packed_words(v))
    assert words.dtype == jnp.int32
    back = np.asarray(packing.unpack_signs(words, v))
    assert np.array_equal(back, signs)


@pytest.mark.parametrize("v", [1, 31, 33, 95])
def test_packed_cosine_tail_bits_exact(v):
    """Data tail bits 0 vs query tail bits 1: every tail bit disagrees, so
    agreements = 32W - popcount(xor) without storing V in the words."""
    rng = np.random.default_rng(v)
    d, q = _signs(rng, 13, v), _signs(rng, 3, v)
    want = np.asarray(match.match_cosine(jnp.asarray(d), jnp.asarray(q)))
    got = np.asarray(packing.packed_cosine_match(
        packing.pack_signs_data(jnp.asarray(d)),
        packing.pack_signs_queries(jnp.asarray(q))))
    assert np.array_equal(got, want)


def test_pack_buckets_domain_validation():
    ok = jnp.asarray([[0, 253], [7, 100]], dtype=jnp.int32)
    packed = packing.pack_buckets(ok)
    assert packed.dtype == jnp.uint8
    for bad in ([[254]], [[255]], [[-1]]):
        with pytest.raises(ValueError, match="bucket"):
            packing.pack_buckets(jnp.asarray(bad, dtype=jnp.int32))


def test_packed_tanimoto_reference_matches_wide():
    rng = np.random.default_rng(0)
    d = rng.integers(0, 200, (17, 9)).astype(np.int32)
    q = rng.integers(0, 200, (4, 9)).astype(np.int32)
    want = np.asarray(match.match_tanimoto(jnp.asarray(d), jnp.asarray(q)))
    got = np.asarray(packing.packed_tanimoto_match(
        packing.pack_buckets(jnp.asarray(d)),
        packing.pack_buckets(jnp.asarray(q))))
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# Pallas count kernels (interpret mode on CPU) vs wide reference counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,q,v", [(7, 3, 33), (130, 5, 64), (64, 4, 513)])
def test_packed_cosine_count_kernel(n, q, v):
    rng = np.random.default_rng(n * v)
    d, s = _signs(rng, n, v), _signs(rng, q, v)
    want = np.asarray(match.match_cosine(jnp.asarray(d), jnp.asarray(s)))
    got = np.asarray(ops.packed_cosine_count(
        packing.pack_signs_data(jnp.asarray(d)),
        packing.pack_signs_queries(jnp.asarray(s))))
    assert got.dtype == np.int32
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n,q,m", [(7, 3, 5), (130, 5, 17), (64, 4, 40)])
def test_packed_tanimoto_count_kernel(n, q, m):
    rng = np.random.default_rng(n * m)
    d = rng.integers(0, 250, (n, m)).astype(np.int32)
    s = rng.integers(0, 250, (q, m)).astype(np.int32)
    want = np.asarray(match.match_tanimoto(jnp.asarray(d), jnp.asarray(s)))
    got = np.asarray(ops.packed_tanimoto_count(
        packing.pack_buckets(jnp.asarray(d)),
        packing.pack_buckets(jnp.asarray(s))))
    assert got.dtype == np.int32
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# Fused match->count->local-top-k kernels: candidate buffers hold the top-k
# ---------------------------------------------------------------------------

def _assert_candidates_cover_topk(ids, cnts, counts_ref, k, n):
    """The fused kernel's [Q, n_tiles*kc] buffers, merged by
    topk_from_candidates, must equal the sort oracle exactly."""
    got = cpq.topk_from_candidates(jnp.asarray(ids), jnp.asarray(cnts),
                                   min(k, ids.shape[1]))
    want = cpq.sort_select(jnp.asarray(counts_ref),
                           SearchParams(k=k, max_count=int(counts_ref.max()) + 1))
    kk = min(k, got[0].shape[1])
    assert np.array_equal(np.asarray(got[0])[:, :kk],
                          np.asarray(want.ids)[:, :kk])
    assert np.array_equal(np.asarray(got[1])[:, :kk],
                          np.asarray(want.counts)[:, :kk])
    # physical pad rows (>= n) may never appear in any candidate slot
    assert np.asarray(ids).max() < n


@pytest.mark.parametrize("n,q,v,k", [(7, 3, 33, 3), (130, 5, 64, 10),
                                     (300, 4, 95, 7)])
def test_packed_cosine_fused_topk(n, q, v, k):
    rng = np.random.default_rng(n + v)
    d, s = _signs(rng, n, v), _signs(rng, q, v)
    counts = np.asarray(match.match_cosine(jnp.asarray(d), jnp.asarray(s)))
    ids, cnts = ops.packed_cosine_topk(
        packing.pack_signs_data(jnp.asarray(d)),
        packing.pack_signs_queries(jnp.asarray(s)), k=k)
    _assert_candidates_cover_topk(ids, cnts, counts, k, n)


@pytest.mark.parametrize("n,q,m,k", [(7, 3, 5, 3), (130, 5, 17, 10)])
def test_packed_tanimoto_fused_topk(n, q, m, k):
    rng = np.random.default_rng(n + m)
    d = rng.integers(0, 250, (n, m)).astype(np.int32)
    s = rng.integers(0, 250, (q, m)).astype(np.int32)
    counts = np.asarray(match.match_tanimoto(jnp.asarray(d), jnp.asarray(s)))
    ids, cnts = ops.packed_tanimoto_topk(
        packing.pack_buckets(jnp.asarray(d)),
        packing.pack_buckets(jnp.asarray(s)), k=k)
    _assert_candidates_cover_topk(ids, cnts, counts, k, n)


def test_fused_tie_break_is_count_desc_id_asc():
    """All-equal counts: the fused buffers must surface the lowest ids so the
    merged ordering matches every other selection path."""
    d = jnp.ones((40, 8), dtype=jnp.int8)          # identical sign rows
    s = jnp.ones((2, 8), dtype=jnp.int8)
    ids, cnts = ops.packed_cosine_topk(
        packing.pack_signs_data(d), packing.pack_signs_queries(s), k=5)
    got_ids, got_cnts = cpq.topk_from_candidates(ids, cnts, 5)
    assert np.array_equal(np.asarray(got_ids),
                          np.tile(np.arange(5, dtype=np.int32), (2, 1)))
    assert np.all(np.asarray(got_cnts) == 8)


# ---------------------------------------------------------------------------
# Engine-registry integration: tiny-corpus fill, storage accounting
# ---------------------------------------------------------------------------

def test_packed_search_tiny_corpus_fills_missing_slots():
    """n < k: the packed fused path pads its candidate buffer to k columns
    with (-1, -1), exactly like the wide selector's empty slots."""
    from repro.core import GenieIndex

    rng = np.random.default_rng(3)
    raw = rng.standard_normal((3, 16)).astype(np.float32)
    q = rng.standard_normal((2, 16)).astype(np.float32)
    wide = GenieIndex.build_cosine(raw).search(q, k=8)
    packed = GenieIndex.build_cosine(raw, signature_layout="packed").search(q, k=8)
    assert np.array_equal(np.asarray(packed.ids), np.asarray(wide.ids))
    assert np.array_equal(np.asarray(packed.counts), np.asarray(wide.counts))
    assert np.all(np.asarray(packed.ids)[:, 3:] == -1)


def test_build_stats_report_signature_footprint():
    rng = np.random.default_rng(0)
    raw = rng.standard_normal((64, 256)).astype(np.float32)
    model = engines.get(Engine.COSINE)
    stats = model.build_stats(model.prepare_data(raw))
    assert stats.bytes_signatures_wide == 64 * 256          # int8 signs
    assert stats.bytes_signatures_packed == 64 * 8 * 4      # 8 words/row
    assert stats.bytes_signatures_packed * 8 == stats.bytes_signatures_wide

    sk = rng.integers(0, 64, (64, 20)).astype(np.int32)
    tstats = engines.get(Engine.TANIMOTO).build_stats(jnp.asarray(sk))
    assert tstats.bytes_signatures_wide == 64 * 20 * 4
    assert tstats.bytes_signatures_packed == 64 * 20        # uint8 buckets


# ---------------------------------------------------------------------------
# The fused plans' candidate reduction: ties at the k-th count across parts
# ---------------------------------------------------------------------------

def _tied_corpus(engine, rng):
    """(data, queries): 6 distinct rows tiled 50 times, so every count is
    shared by 50 rows, straddling the segments below."""
    if engine is Engine.COSINE:
        base = rng.standard_normal((6, 40)).astype(np.float32)
        q = rng.standard_normal((3, 40)).astype(np.float32)
    else:
        base = rng.integers(0, 4, (6, 40)).astype(np.int32)
        q = rng.integers(0, 4, (3, 40)).astype(np.int32)
    return np.tile(base, (50, 1)), q


@pytest.mark.parametrize("engine", [Engine.COSINE, Engine.TANIMOTO])
def test_fused_reduction_equals_wide_with_ties_at_the_kth_count(engine):
    """With rows forced to tie at the k-th count across three segments, and
    with a corpus smaller than k, the fused PACKED plans (per-tile buffers
    sorted by core/plan.py `_fused_candidates_topk`) give the WIDE count
    path's result bit for bit: the lowest ids of the tied count."""
    from repro.core import GenieIndex, SegmentedIndex
    from repro.core import plan as plan_lib

    data, q = _tied_corpus(engine, np.random.default_rng(7))
    got = {}
    for layout in ("wide", "packed"):
        idx = SegmentedIndex(engine=engine, signature_layout=layout)
        for lo in (0, 100, 200):
            idx.add(data[lo:lo + 100])
        res = idx.search(q, k=7)
        tiny = GenieIndex.build(engine, data[:3], signature_layout=layout
                                ).search(q, k=8)
        got[layout] = [np.asarray(a) for a in (res.ids, res.counts,
                                               tiny.ids, tiny.counts)]
        plan = plan_lib.plan_search(engine, 7, idx.max_count,
                                    layout=plan_lib.Layout.SEGMENTED,
                                    part_rows=(100,) * 3,
                                    signature_layout=layout)
        assert (plan.fused_match is not None) == (layout == "packed")
    for w, p in zip(got["wide"], got["packed"]):
        assert np.array_equal(w, p)
    ids, counts = got["packed"][:2]
    # the 7 best are ties of one count: the lowest ids of that count's rows
    assert np.all(counts == counts[:, :1])
    assert np.all(np.diff(ids, axis=1) == 6)
    assert np.all(got["packed"][2][:, 3:] == -1)
