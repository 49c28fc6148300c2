"""c-PQ exactness (paper Theorem 3.1) and selection-method agreement.

Formerly hypothesis property tests; rewritten as seeded-random parametrized
cases so the tier-1 suite runs on environments without hypothesis (same
coverage: each case draws its shape/k/max_count from an independent seed).
"""
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cpq, merge, spq
from repro.core.types import SearchParams


def _sorted_counts(counts, k):
    return np.sort(counts, axis=1)[:, ::-1][:, :k]


@pytest.mark.parametrize("case", range(25))
def test_cpq_matches_sort_topk(case):
    draw = np.random.default_rng(1000 + case)
    q = int(draw.integers(1, 5))
    n = int(draw.integers(1, 201))
    mx = int(draw.integers(1, 41))
    k = int(draw.integers(1, 21))
    counts = draw.integers(0, mx + 1, size=(q, n)).astype(np.int32)
    p = SearchParams(k=k, max_count=mx)
    res = cpq.cpq_select(jnp.asarray(counts), p)
    want = _sorted_counts(counts, k)
    got = np.asarray(res.counts)
    kk = min(k, n)
    assert np.array_equal(got[:, :kk], want[:, :kk])
    if n < k:  # padding contract
        assert np.all(got[:, n:] == -1)


@pytest.mark.parametrize("case", range(25))
def test_threshold_is_kth_count(case):
    """Theorem 3.1: AT - 1 == MC_k (count of the k-th object)."""
    draw = np.random.default_rng(2000 + case)
    n = int(draw.integers(1, 301))
    mx = int(draw.integers(1, 31))
    k = int(draw.integers(1, 11))
    counts = draw.integers(0, mx + 1, size=(2, n)).astype(np.int32)
    p = SearchParams(k=k, max_count=mx)
    res = cpq.cpq_select(jnp.asarray(counts), p)
    if n >= k:
        kth = np.sort(counts, axis=1)[:, ::-1][:, k - 1]
        assert np.array_equal(np.asarray(res.threshold), kth)


def test_returned_ids_have_returned_counts(rng):
    counts = rng.integers(0, 20, size=(3, 500)).astype(np.int32)
    p = SearchParams(k=9, max_count=20)
    res = cpq.cpq_select(jnp.asarray(counts), p)
    ids, vals = np.asarray(res.ids), np.asarray(res.counts)
    for qi in range(3):
        assert np.array_equal(counts[qi, ids[qi]], vals[qi])
        # non-increasing
        assert np.all(np.diff(vals[qi]) <= 0)


@pytest.mark.parametrize("case", range(15))
def test_spq_matches_sort(case):
    draw = np.random.default_rng(3000 + case)
    n = int(draw.integers(2, 201))
    mx = int(draw.integers(1, 26))
    k = int(draw.integers(1, 13))
    counts = draw.integers(0, mx + 1, size=(2, n)).astype(np.int32)
    p = SearchParams(k=k, max_count=mx)
    res = spq.spq_select(jnp.asarray(counts), p)
    want = _sorted_counts(counts, min(k, n))
    assert np.array_equal(np.asarray(res.counts)[:, : min(k, n)], want)


def test_gate_audit_threshold_properties(rng):
    """ZA[AT] < k <= ZA[AT-1] (Lemma 3.1)."""
    counts = rng.integers(0, 15, size=(4, 300)).astype(np.int32)
    hist = cpq.count_histogram(jnp.asarray(counts), 15)
    za = np.asarray(cpq.zipper_array(hist))
    at, thr = cpq.audit_threshold(hist, 7)
    at = np.asarray(at)
    for qi in range(4):
        if at[qi] <= 15:
            assert za[qi, at[qi]] < 7
        assert za[qi, at[qi] - 1] >= 7


@pytest.mark.parametrize("case", range(15))
def test_merge_equals_global_topk(case):
    """Merging per-part top-k == top-k of the union (multiload correctness)."""
    draw = np.random.default_rng(4000 + case)
    parts = int(draw.integers(1, 6))
    n_per = int(draw.integers(1, 61))
    k = int(draw.integers(1, 9))
    q = 3
    all_counts = draw.integers(0, 30, size=(q, parts * n_per)).astype(np.int32)
    per_ids, per_counts = [], []
    for pi in range(parts):
        seg = all_counts[:, pi * n_per : (pi + 1) * n_per]
        p = SearchParams(k=k, max_count=30)
        r = cpq.cpq_select(jnp.asarray(seg), p)
        per_ids.append(np.where(np.asarray(r.ids) >= 0, np.asarray(r.ids) + pi * n_per, -1))
        per_counts.append(np.asarray(r.counts))
    res = merge.merge_topk(jnp.asarray(np.stack(per_ids)), jnp.asarray(np.stack(per_counts)), k)
    kk = min(k, parts * n_per)
    want = _sorted_counts(all_counts, kk)
    assert np.array_equal(np.asarray(res.counts)[:, :kk], want)
    # tree merge agrees
    res2 = merge.tree_merge(jnp.asarray(np.stack(per_ids)), jnp.asarray(np.stack(per_counts)), k)
    assert np.array_equal(np.asarray(res.counts), np.asarray(res2.counts))


def _compact_by_scatter(counts, threshold, cap):
    """Reference: each object scattered to its slot, the N-wide formulation
    the gather form replaced (strict first, then ties, each in id order)."""
    q, n = counts.shape
    c = counts.astype(jnp.int32)
    thr = threshold[:, None]
    strict, tie = c > thr, c == thr
    n_strict = jnp.sum(strict.astype(jnp.int32), axis=-1, keepdims=True)
    pos_strict = jnp.cumsum(strict.astype(jnp.int32), axis=-1) - 1
    pos_tie = n_strict + jnp.cumsum(tie.astype(jnp.int32), axis=-1) - 1
    pos = jnp.minimum(jnp.where(strict, pos_strict, jnp.where(tie, pos_tie, cap)), cap)
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (q, n))
    out_ids = jnp.full((q, cap + 1), -1, dtype=jnp.int32)
    out_vals = jnp.full((q, cap + 1), -1, dtype=jnp.int32)
    out_ids = jax.vmap(lambda o, p, v: o.at[p].set(v, mode="drop"))(out_ids, pos, ids)
    out_vals = jax.vmap(lambda o, p, v: o.at[p].set(v, mode="drop"))(out_vals, pos, c)
    return out_ids[:, :cap], out_vals[:, :cap]


def _compaction_case(name, draw):
    """(counts [Q, N], threshold [Q], cap) for one named case."""
    mx = 15
    if name == "n_below_cap":
        counts = draw.integers(0, mx + 1, size=(3, 37))
        return counts, draw.integers(0, mx + 1, size=3), 64
    if name == "n_is_1":
        return draw.integers(0, mx + 1, size=(2, 1)), np.array([0, 20]), 8
    if name == "all_ties":
        return np.full((2, 300), 7), np.array([7, 7]), 40
    if name == "no_ties":
        counts = draw.integers(0, mx + 1, size=(2, 300))
        return np.where(counts == 5, 6, counts), np.array([5, 5]), 200
    if name == "threshold_0":
        counts = draw.integers(0, 3, size=(2, 250))
        return counts, np.array([0, 0]), 50
    if name == "threshold_above_max":
        return draw.integers(0, mx + 1, size=(2, 250)), np.array([mx + 1, 99]), 50
    if name == "ties_past_cap":
        counts = draw.integers(0, 4, size=(2, 500))
        counts[:, draw.choice(500, 5, replace=False)] = 9
        return counts, np.array([3, 3]), 20
    if name == "rows_with_other_thresholds":
        counts = draw.integers(0, mx + 1, size=(5, 400))
        return counts, np.array([0, 3, 8, 14, 15]), 100
    q, n = int(draw.integers(1, 6)), int(draw.integers(1, 700))
    counts = draw.integers(0, mx + 1, size=(q, n))
    return counts, draw.integers(0, mx + 2, size=q), int(draw.integers(1, 120))


@pytest.mark.parametrize("name", [
    "n_below_cap", "n_is_1", "all_ties", "no_ties", "threshold_0",
    "threshold_above_max", "ties_past_cap", "rows_with_other_thresholds",
    "random_0", "random_1", "random_2", "random_3",
])
def test_compaction_equals_scatter_formulation(name):
    """The gathered buffer equals the scattered one bit for bit, ids and
    counts, including the -1 padding and the id-order cut of ties at cap."""
    draw = np.random.default_rng(5000 + zlib.crc32(name.encode()))
    counts, threshold, cap = _compaction_case(name, draw)
    counts = jnp.asarray(counts, dtype=jnp.int32)
    threshold = jnp.asarray(threshold, dtype=jnp.int32)
    got = cpq._compact_candidates(counts, threshold, cap)
    want = _compact_by_scatter(counts, threshold, cap)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_compaction_lowers_without_a_scatter():
    """A scatter over N is serialised per element on the TPU; the compaction
    fills its cap slots by gathers and must stay free of scatters."""
    counts = jax.ShapeDtypeStruct((16, 4096), jnp.int32)
    threshold = jax.ShapeDtypeStruct((16,), jnp.int32)
    lowered = jax.jit(cpq._compact_candidates, static_argnums=2).lower(
        counts, threshold, 200)
    assert not re.search(r"stablehlo\.scatter", lowered.as_text())
    assert not re.search(r"\sscatter\(", lowered.compile().as_text())
