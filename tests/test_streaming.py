"""Multiple loading (paper section III-D) and merge invariants.

Multiple loading is the SEGMENTED layout: the parts stream through the device
one at a time and their candidate buffers merge exactly.

Formerly hypothesis property tests; rewritten as seeded-random parametrized
cases so the tier-1 suite runs on environments without hypothesis.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, match, merge
from repro.core import plan as plan_lib
from repro.core.segments import even_segments
from repro.core.types import Engine


@pytest.mark.parametrize("case", range(10))
def test_multiload_scan_equals_full_search(case):
    """An even split into 1-6 host-streamed parts == the monolithic search."""
    draw = np.random.default_rng(5000 + case)
    n = int(draw.integers(20, 201))
    parts = int(draw.integers(1, 7))
    k = int(draw.integers(1, 9))
    sigs = draw.integers(0, 8, (n, 12)).astype(np.int32)
    qs = draw.integers(0, 8, (3, 12)).astype(np.int32)
    idx = GenieIndex.build_lsh(sigs, use_kernel=False)
    full = idx.search(qs, k=k)
    seg = SegmentedIndex(engine=Engine.EQ, max_count=idx.max_count,
                         use_kernel=False, host_resident=True)
    for part in np.split(sigs, np.cumsum(even_segments(n, parts))[:-1]):
        seg.add(part)
    got = seg.search(qs, k=k)
    assert np.array_equal(np.asarray(full.counts), np.asarray(got.counts))
    assert np.array_equal(np.asarray(full.ids), np.asarray(got.ids))


def test_multiload_host_loop_matches_scan(rng):
    """A raw match callable planned SEGMENTED over host parts == the full
    search."""
    sigs = rng.integers(0, 8, (120, 12)).astype(np.int32)
    qs = rng.integers(0, 8, (4, 12)).astype(np.int32)
    parts = [sigs[i * 40 : (i + 1) * 40] for i in range(3)]
    plan = plan_lib.plan_search(match.match_eq, 5, 12,
                                layout=plan_lib.Layout.SEGMENTED,
                                part_rows=(40, 40, 40))
    host = plan_lib.execute(plan, parts, jnp.asarray(qs))
    idx = GenieIndex.build_lsh(sigs, use_kernel=False)
    full = idx.search(qs, k=5)
    assert np.array_equal(np.asarray(host.counts), np.asarray(full.counts))
    assert np.array_equal(np.asarray(host.ids), np.asarray(full.ids))


def test_merge_with_unequal_part_k(rng):
    """Merging buffers whose per-part k exceeds the global k still works."""
    ids = rng.integers(0, 1000, (3, 2, 9)).astype(np.int32)
    counts = np.sort(rng.integers(0, 50, (3, 2, 9)), axis=-1)[..., ::-1].astype(np.int32)
    res = merge.merge_topk(jnp.asarray(ids), jnp.asarray(counts), k=4)
    flat = counts.transpose(1, 0, 2).reshape(2, -1)
    want = np.sort(flat, axis=-1)[:, ::-1][:, :4]
    assert np.array_equal(np.asarray(res.counts), want)


def test_merge_part_order_invariant(rng):
    """Merge of disjoint partitions is invariant to part order (the property
    the hierarchical multi-pod merge relies on).  NOTE: parts must be
    disjoint -- merge never sums counts across parts (documented contract)."""
    counts = np.sort(rng.integers(0, 30, (4, 2, 6)), axis=-1)[..., ::-1].astype(np.int32)
    ids = np.arange(4 * 2 * 6, dtype=np.int32).reshape(4, 2, 6)  # disjoint ids
    fwd = merge.merge_topk(jnp.asarray(ids), jnp.asarray(counts), k=6)
    perm = [2, 0, 3, 1]
    rev = merge.merge_topk(jnp.asarray(ids[perm]), jnp.asarray(counts[perm]), k=6)
    assert np.array_equal(np.asarray(fwd.counts), np.asarray(rev.counts))
    assert set(map(tuple, np.asarray(fwd.ids))) == set(map(tuple, np.asarray(fwd.ids)))


def test_count_dtype_bounding():
    """The Bitmap-Counter bit-bounding helper (paper section III-C)."""
    c = jnp.arange(10, dtype=jnp.int32)
    assert match.as_count_dtype(c, 100).dtype == jnp.int8
    assert match.as_count_dtype(c, 1000).dtype == jnp.int16
    assert match.as_count_dtype(c, 10**6).dtype == jnp.int32


def test_match_eq_int8_matches_int32(rng):
    """Hillclimb C1: int8 signatures are bit-identical to int32."""
    d8 = rng.integers(0, 67, (200, 24)).astype(np.int8)
    q8 = rng.integers(0, 67, (4, 24)).astype(np.int8)
    got8 = np.asarray(match.match_eq(jnp.asarray(d8), jnp.asarray(q8)))
    got32 = np.asarray(match.match_eq(jnp.asarray(d8.astype(np.int32)),
                                      jnp.asarray(q8.astype(np.int32))))
    assert np.array_equal(got8, got32)
