"""End-to-end behaviour tests for the GENIE system (paper sections III-VI)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, TopKMethod
from repro.core.lsh import e2lsh, rbh, tau_ann
from repro.core.postings import PostingsIndex
from repro.core.segments import even_segments
from repro.data.pipeline import synthetic_points


def _build_ann_index(rng, n=800, d=16, m=64):
    pts, labels = synthetic_points(n, d, n_clusters=10, seed=3)
    params = e2lsh.make(jax.random.PRNGKey(0), d=d, m=m, w=4.0, n_buckets=67)
    sigs = e2lsh.hash_points(params, jnp.asarray(pts))
    return pts, labels, params, GenieIndex.build_lsh(sigs, max_count=m)


def test_ann_search_finds_perturbed_points(rng):
    pts, _, params, idx = _build_ann_index(rng)
    q = pts[:16] + rng.standard_normal((16, 16)).astype(np.float32) * 0.05
    qsigs = e2lsh.hash_points(params, jnp.asarray(q))
    res = idx.search(qsigs, k=5)
    assert np.array_equal(np.asarray(res.ids)[:, 0], np.arange(16))


def test_ann_approximation_ratio_close_to_one(rng):
    """Paper Fig 14: approximation ratio stays near 1."""
    pts, _, params, idx = _build_ann_index(rng, n=1000, m=128)
    q = pts[:8] + rng.standard_normal((8, 16)).astype(np.float32) * 0.2
    qsigs = e2lsh.hash_points(params, jnp.asarray(q))
    res = idx.search(qsigs, k=10)
    dists = np.linalg.norm(pts[None] - q[:, None], axis=-1)  # [Q, N]
    true_knn = np.sort(dists, axis=1)[:, :10]
    got = np.take_along_axis(dists, np.asarray(res.ids), axis=1)
    ratio = float(np.mean(np.sort(got, axis=1) / np.maximum(true_knn, 1e-9)))
    assert ratio < 1.6, ratio


def test_knn_label_prediction_rbh(rng):
    """Paper Table V analogue: 1NN prediction via RBH Laplacian-kernel ANN."""
    pts, labels, _, _ = _build_ann_index(rng)
    sigma = rbh.median_heuristic_sigma(jnp.asarray(pts), jax.random.PRNGKey(1))
    params = rbh.make(jax.random.PRNGKey(2), d=16, m=128, sigma=sigma, n_buckets=8192)
    train, test = pts[100:], pts[:100]
    ltrain, ltest = labels[100:], labels[:100]
    idx = GenieIndex.build_lsh(rbh.hash_points(params, jnp.asarray(train)), max_count=128)
    res = idx.search(rbh.hash_points(params, jnp.asarray(test)), k=1)
    pred = ltrain[np.asarray(res.ids)[:, 0]]
    acc = float(np.mean(pred == ltest))
    assert acc > 0.9, acc


def test_multiload_matches_single_load(rng):
    pts, _, params, idx = _build_ann_index(rng)
    q = pts[:8] + 0.05
    qsigs = e2lsh.hash_points(params, jnp.asarray(q))
    full = idx.search(qsigs, k=6)
    seg = SegmentedIndex(engine=idx.engine, max_count=idx.max_count,
                         use_kernel=idx.use_kernel, host_resident=True)
    sigs = np.asarray(idx.data)
    for part in np.split(sigs, np.cumsum(even_segments(len(sigs), 5))[:-1]):
        seg.add(part)
    parts = seg.search(qsigs, k=6)
    assert np.array_equal(np.asarray(full.counts), np.asarray(parts.counts))


def test_all_topk_methods_agree(rng):
    _, _, params, idx = _build_ann_index(rng)
    q = rng.standard_normal((4, 16)).astype(np.float32)
    qsigs = e2lsh.hash_points(params, jnp.asarray(q))
    r1 = idx.search(qsigs, k=9, method=TopKMethod.CPQ)
    r2 = idx.search(qsigs, k=9, method=TopKMethod.SORT)
    r3 = idx.search(qsigs, k=9, method=TopKMethod.SPQ)
    assert np.array_equal(np.asarray(r1.counts), np.asarray(r2.counts))
    assert np.array_equal(np.asarray(r1.counts), np.asarray(r3.counts))


def test_postings_engine_matches_dense(rng):
    """The GPU-faithful CSR postings engine == the TPU dense engine."""
    n, m, buckets = 300, 12, 32
    sigs = rng.integers(0, buckets, size=(n, m)).astype(np.int32)
    keywords = sigs + (np.arange(m, dtype=np.int32) * buckets)[None, :]
    pidx = PostingsIndex.build(keywords, n_keywords=m * buckets)
    q = keywords[:5]
    counts_np = pidx.scan_counts_numpy(q)
    from repro.core import match

    counts_dense = np.asarray(match.match_eq(jnp.asarray(sigs), jnp.asarray(sigs[:5])))
    assert np.array_equal(counts_np, counts_dense)
    # tiled (load-balanced) device scan agrees too
    tiles, tile_kw = pidx.split_tiles(limit=64)
    counts_tiled = np.asarray(
        pidx.scan_counts_tiled(jnp.asarray(tiles), jnp.asarray(tile_kw), jnp.asarray(q))
    )
    assert np.array_equal(counts_tiled, counts_np)


def test_retrieval_service_end_to_end(rng):
    from repro.serve.retrieval import RetrievalService

    pts, labels, _, _ = _build_ann_index(rng)
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=96)
    svc.add(list(range(len(pts))), embeddings=pts)
    res, sims = svc.search(None, k=3, embeddings=pts[:5] + 0.02)
    assert np.array_equal(np.asarray(res.ids)[:, 0], np.arange(5))
    assert sims.shape == (5, 3)
    assert np.all(sims <= 1.0) and np.all(sims >= 0.0)
