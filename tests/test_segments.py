"""Segment/merge invariants: SegmentedIndex == monolithic GenieIndex, exactly.

Segments partition the object set, so per-segment match counts are complete
and the cap-buffer merge is exact -- segmented search must return identical
ids *and* counts to a monolithic index over the concatenated data, for every
registered engine, every selection method, uneven segment sizes (including a
segment smaller than k), after compaction, and with the segments held in
host memory and streamed through the device (the paper's multiple loading).
RetrievalService's old rebuild-on-add path is the oracle for the
serving-layer invariant.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, engines, merge
from repro.core.segments import even_segments
from repro.core.types import Engine, TopKMethod

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ALL_ENGINES = sorted(engines.available(), key=lambda e: e.value)

# uneven on purpose: a 1-row segment, a segment smaller than k, a big one
CUTS = [0, 3, 4, 40, 90, 101]


def _case(engine: Engine, n=101, q=4, seed=0):
    model = engines.get(engine)
    raw, queries, mc = model.example(np.random.default_rng(seed), n, q)
    return model, raw, queries, mc


def _segmented(engine, raw, mc, cuts=CUTS, **kw):
    seg = SegmentedIndex(engine=engine, max_count=mc, use_kernel=False, **kw)
    for a, b in zip(cuts, cuts[1:]):
        seg.add(raw[a:b])
    return seg


def _assert_same(got, want, label=""):
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids)), label
    assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts)), label
    assert np.array_equal(np.asarray(got.threshold), np.asarray(want.threshold)), label


@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("method", [TopKMethod.CPQ, TopKMethod.SPQ, TopKMethod.SORT])
def test_segmented_equals_monolithic(engine, method):
    """Exact ids/counts parity across uneven segments for every engine and
    every selection method."""
    model, raw, queries, mc = _case(engine)
    mono = GenieIndex.build(engine, raw, max_count=mc, use_kernel=False)
    seg = _segmented(engine, raw, mc)
    assert seg.n_objects == mono.stats.n_objects
    got = seg.search(queries, k=9, method=method)
    want = mono.search(queries, k=9, method=method)
    _assert_same(got, want, f"{engine.value} {method.value}")


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_segmented_streamed_equals_monolithic(engine):
    """Host-resident segments of heterogeneous sizes, streamed through the
    device one at a time, search exactly like the monolithic index."""
    model, raw, queries, mc = _case(engine)
    mono = GenieIndex.build(engine, raw, max_count=mc, use_kernel=False)
    seg = _segmented(engine, raw, mc, host_resident=True)
    assert all(isinstance(s.data, np.ndarray) for s in seg.segments)
    got = seg.search(queries, k=9)
    _assert_same(got, mono.search(queries, k=9), engine.value)


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_segmented_after_compaction(engine):
    """Compaction coalesces adjacent segments without remapping ids."""
    model, raw, queries, mc = _case(engine)
    mono = GenieIndex.build(engine, raw, max_count=mc, use_kernel=False)
    want = mono.search(queries, k=9)
    seg = _segmented(engine, raw, mc)
    for max_segments in (3, 1):
        seg.compact(max_segments)
        assert len(seg.segments) == max_segments
        assert seg.n_objects == mono.stats.n_objects
        _assert_same(seg.search(queries, k=9), want,
                     f"{engine.value} compact({max_segments})")
    assert seg.compaction_count == 2


def test_host_resident_segments_stay_on_host():
    """host_resident keeps every sealed and compacted segment a NumPy array,
    and searches and exports exactly like the device-resident index."""
    model, raw, queries, mc = _case(Engine.EQ)
    mono = GenieIndex.build(Engine.EQ, raw, max_count=mc, use_kernel=False)
    seg = SegmentedIndex(engine=Engine.EQ, max_count=mc, use_kernel=False,
                         host_resident=True)
    for a, b in zip(CUTS, CUTS[1:]):
        seg.add(raw[a:b])
    assert all(type(s.data) is np.ndarray for s in seg.segments)
    _assert_same(seg.search(queries, k=9), mono.search(queries, k=9), "host")
    seg.compact(2)
    assert all(type(s.data) is np.ndarray for s in seg.segments)
    _assert_same(seg.search(queries, k=9), mono.search(queries, k=9), "compacted")
    data, n = seg.concat_data()
    np.testing.assert_array_equal(data, np.asarray(mono.data))
    assert n == mono.stats.n_objects


def test_segment_stats_accounting():
    model, raw, _, mc = _case(Engine.EQ)
    seg = _segmented(Engine.EQ, raw, mc)
    st = seg.stats
    assert st.n_segments == len(CUTS) - 1
    assert st.segment_rows == [b - a for a, b in zip(CUTS, CUTS[1:])]
    assert st.n_objects == 101 and sum(st.segment_rows) == 101
    assert len(st.segment_build_seconds) == st.n_segments
    assert all(s >= 0 for s in st.segment_build_seconds)
    assert st.compaction_count == 0
    seg.compact(2)
    st = seg.stats
    assert st.n_segments == 2 and st.compaction_count == 1
    assert st.compaction_seconds >= 0
    assert sum(st.segment_rows) == 101
    # monolithic stats keep the degenerate single-segment defaults
    mono = GenieIndex.build(Engine.EQ, raw, use_kernel=False)
    assert mono.stats.n_segments == 1 and mono.stats.compaction_count == 0


def test_segmented_add_validates_width():
    model, raw, _, mc = _case(Engine.EQ)
    seg = _segmented(Engine.EQ, raw, mc)
    with pytest.raises(ValueError, match="width"):
        seg.add(raw[:5, :8])


def test_segmented_rejects_empty_batch(rng):
    """An empty add() would seal a 0-row segment and poison every later
    search; it must raise instead (service layer included)."""
    from repro.serve.retrieval import RetrievalService

    model, raw, queries, mc = _case(Engine.EQ)
    seg = _segmented(Engine.EQ, raw, mc)
    with pytest.raises(ValueError, match="empty batch"):
        seg.add(raw[:0])
    seg.search(queries, k=3)                                   # still healthy
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=16)
    with pytest.raises(ValueError, match="empty batch"):
        svc.add([], embeddings=np.zeros((0, 8), np.float32))


def test_segmented_empty_and_bad_args():
    seg = SegmentedIndex(engine=Engine.EQ)
    with pytest.raises(ValueError, match=r"add\(\) first"):
        seg.search(np.zeros((1, 4), np.int32), k=1)
    with pytest.raises(ValueError, match="max_segments"):
        seg.compact(0)


def test_segmented_resolves_max_count_on_first_add():
    model, raw, queries, _ = _case(Engine.EQ)
    seg = SegmentedIndex(engine=Engine.EQ, use_kernel=False)   # no max_count
    seg.add(raw[:50])
    assert seg.max_count == raw.shape[1]                       # m, like build()
    seg.add(raw[50:])
    mono = GenieIndex.build(Engine.EQ, raw, use_kernel=False)
    _assert_same(seg.search(queries, k=7), mono.search(queries, k=7))


def test_merge_ragged_pads_when_fewer_candidates_than_k():
    model, raw, queries, mc = _case(Engine.EQ, n=5)
    seg = _segmented(Engine.EQ, raw, mc, cuts=[0, 2, 5])
    res = seg.search(queries, k=9)
    ids = np.asarray(res.ids)
    assert ids.shape == (4, 9)
    assert np.all(ids[:, 5:] == -1)                            # only 5 objects
    assert np.all(np.asarray(res.counts)[:, 5:] == -1)


def test_concat_data_pads_and_masks():
    model, raw, _, mc = _case(Engine.EQ)
    seg = _segmented(Engine.EQ, raw, mc)
    data, n = seg.concat_data(pad_multiple=8)
    assert n == 101 and data.shape[0] == 104
    assert np.array_equal(np.asarray(data[:101]), np.asarray(raw))
    assert np.all(np.asarray(data[101:]) == engines.get(Engine.EQ).pad_value)


# ---------------------------------------------------------------------------
# Serving layer: repeated add vs the old rebuild path as oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["e2lsh", "simhash", "minhash"])
def test_retrieval_service_add_matches_rebuild_oracle(scheme, rng):
    """B incremental adds == one monolithic rebuild over all signatures (the
    pre-segmentation behaviour), exact ids and counts, every paired engine."""
    import jax.numpy as jnp

    from repro.core import lsh as lsh_lib
    from repro.serve.retrieval import RetrievalService

    pts = rng.standard_normal((130, 16)).astype(np.float32)
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), scheme=scheme,
                           m_override=96)
    for a, b in [(0, 30), (30, 37), (37, 90), (90, 130)]:
        svc.add(list(range(a, b)), embeddings=pts[a:b])
    assert len(svc) == 130
    assert svc.index_stats.n_segments == 4

    sch = lsh_lib.get_scheme(scheme)
    sigs = sch.hash_points(svc._params, jnp.asarray(pts))
    oracle = GenieIndex.build(sch.engine, sigs, max_count=svc.m)  # old rebuild

    q = pts[88:96] + 0.01
    res, sims = svc.search(None, k=5, embeddings=q)
    want = oracle.search(sch.hash_points(svc._params, jnp.asarray(q)), k=5)
    _assert_same(res, want, scheme)
    assert sims.shape == (8, 5)


def test_retrieval_service_compacts_past_max_segments(rng):
    from repro.serve.retrieval import RetrievalService

    pts = rng.standard_normal((120, 8)).astype(np.float32)
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=32,
                           max_segments=3)
    for i in range(0, 120, 20):
        svc.add(list(range(i, i + 20)), embeddings=pts[i:i + 20])
    assert len(svc._index.segments) <= 3
    assert svc.index_stats.compaction_count >= 1
    res, _ = svc.search(None, k=1, embeddings=pts[100:105] + 0.001)
    assert np.array_equal(np.asarray(res.ids)[:, 0], np.arange(100, 105))


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------

def test_retrieval_service_rejects_dim_mismatch(rng):
    """Second add with a different embedding dim must raise, naming both dims
    (the LSH params are built once, from the first add's dim)."""
    from repro.serve.retrieval import RetrievalService

    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=16)
    svc.add([0, 1], embeddings=rng.standard_normal((2, 16)).astype(np.float32))
    with pytest.raises(ValueError, match="8.*16|16.*8"):
        svc.add([2], embeddings=rng.standard_normal((1, 8)).astype(np.float32))
    # search queries are validated against the same dim
    with pytest.raises(ValueError, match="dim"):
        svc.search(None, k=1, embeddings=rng.standard_normal((1, 8)).astype(np.float32))


def test_retrieval_service_rejects_row_count_mismatch(rng):
    from repro.serve.retrieval import RetrievalService

    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=16)
    with pytest.raises(ValueError, match="row count"):
        svc.add([0, 1, 2], embeddings=rng.standard_normal((2, 16)).astype(np.float32))
    # search validates the same alignment when queries are supplied
    svc.add([0, 1], embeddings=rng.standard_normal((2, 16)).astype(np.float32))
    with pytest.raises(ValueError, match="row count"):
        svc.search([0, 1], k=1,
                   embeddings=rng.standard_normal((3, 16)).astype(np.float32))


@pytest.mark.parametrize("n_parts", [0, -1, -7])
def test_search_multiload_rejects_bad_n_parts(n_parts, rng):
    """An even split into zero or a negative number of parts must raise a
    ValueError naming the part count, never divide by zero or return an
    empty layout."""
    with pytest.raises(ValueError, match="n_segments"):
        even_segments(20, n_parts)


def test_build_seconds_measures_completed_build():
    """stats.build_seconds must time the materialised build (block_until_ready),
    not async dispatch; it is recorded and non-negative for every engine."""
    for eng in ALL_ENGINES:
        model, raw, _, mc = _case(eng, n=64)
        idx = GenieIndex.build(eng, raw, max_count=mc, use_kernel=False)
        assert idx.stats.build_seconds >= 0.0
        # the data is materialised by the time build() returns
        np.asarray(idx.data)


# ---------------------------------------------------------------------------
# Distributed segmented shard layout (subprocess: forced multi-device CPU)
# ---------------------------------------------------------------------------

def test_distributed_segmented_layout_parity():
    """A ragged (non-divisible) segmented corpus through a DISTRIBUTED plan:
    concat_data pads to mesh divisibility and n_objects masks the pad tail,
    so results equal the monolithic reference exactly."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SegmentedIndex, distributed, engines, cpq
        from repro.core import plan as plan_lib
        from repro.core.types import Engine, SearchParams
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_mesh((2, 4), ('data', 'model'))
        n_dev = 8
        for eng in (Engine.EQ, Engine.COSINE):
            model = engines.get(eng)
            raw, rawq, mc = model.example(np.random.default_rng(0), 101, 4)
            seg = SegmentedIndex(engine=eng, max_count=mc, use_kernel=False)
            for a, b in [(0, 3), (3, 40), (40, 101)]:
                seg.add(raw[a:b])
            data, n_objects = seg.concat_data(pad_multiple=n_dev)
            assert n_objects == 101 and data.shape[0] == 104
            queries = model.prepare_queries(rawq)
            mx = seg.max_count
            params = SearchParams(k=7, max_count=mx, use_kernel=False)
            plan = plan_lib.plan_search(
                eng, 7, mx, layout=plan_lib.Layout.DISTRIBUTED,
                n_objects=n_objects, use_kernel=False,
                mesh_axes=tuple(mesh.axis_names))
            dd = jax.device_put(data, distributed.data_sharding(mesh))
            qq = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, distributed.replicated(mesh, 2)),
                queries)
            res = plan_lib.execute(plan, dd, qq, mesh=mesh)
            want = cpq.sort_select(
                model.reference(model.prepare_data(raw), queries), params)
            assert np.array_equal(np.asarray(res.ids), np.asarray(want.ids)), eng
            assert np.array_equal(np.asarray(res.counts),
                                  np.asarray(want.counts)), eng
            assert int(np.asarray(res.ids).max()) < 101
        print('distributed segmented parity OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "distributed segmented parity OK" in out.stdout
