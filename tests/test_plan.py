"""Planner parity suite: execute(plan) must reproduce the sort-select oracle
bit-for-bit for every engine x layout x selection method.

Each index type has one door into core/plan.py (GenieIndex.search is
MONOLITHIC, SegmentedIndex.search is SEGMENTED -- the paper's multiple
loading -- and the mesh path plans DISTRIBUTED); this suite pins the one
executor to identical ids, counts, and thresholds against the oracle, across

    6 engines x {monolithic, segmented, distributed} x {CPQ, SPQ, SORT}

plus the plan cache contract (same layout shape -> no retrace, counted via
the per-plan trace counter) and the sharded-serving parity leg
(RetrievalService(mesh=...) == single-device service, subprocess with 8
forced CPU devices).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, cpq, engines
from repro.core import plan as plan_lib
from repro.core.types import Engine, SearchParams, TopKMethod

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ALL_ENGINES = sorted(engines.available(), key=lambda e: e.value)
ALL_METHODS = [TopKMethod.CPQ, TopKMethod.SPQ, TopKMethod.SORT]

# uneven on purpose: a 1-row segment, a segment smaller than k, a big one
CUTS = [0, 3, 4, 40, 90, 101]


def _case(engine: Engine, n=101, q=4, seed=0):
    model = engines.get(engine)
    raw, queries, mc = model.example(np.random.default_rng(seed), n, q)
    data = model.prepare_data(raw)
    return model, raw, data, queries, model.resolve_max_count(data, mc)


def _assert_same(got, want, label=""):
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids)), label
    assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts)), label


# ---------------------------------------------------------------------------
# Parity: engine x layout x method (single-process layouts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("method", ALL_METHODS)
def test_planner_layout_parity(engine, method):
    """MONOLITHIC and SEGMENTED plans both reproduce the sort oracle's ids
    and counts exactly, and their thresholds agree with the k-th count
    (Theorem 3.1)."""
    k = 9
    model, raw, data, queries, mc = _case(engine)
    oracle = cpq.sort_select(
        model.reference(data, model.prepare_queries(queries)),
        SearchParams(k=k, max_count=mc),
    )

    idx = GenieIndex.build(engine, raw, max_count=mc, use_kernel=False)
    seg = SegmentedIndex(engine=engine, max_count=mc, use_kernel=False)
    for a, b in zip(CUTS, CUTS[1:]):
        seg.add(raw[a:b])

    results = {
        "monolithic": idx.search(queries, k=k, method=method),
        "segmented": seg.search(queries, k=k, method=method),
    }
    for layout, got in results.items():
        _assert_same(got, oracle, f"{engine.value} {method.value} {layout}")
        if layout == "monolithic" and method == TopKMethod.SPQ:
            continue  # SPQ's bucket threshold is its own (pre-planner) value
        assert np.array_equal(np.asarray(got.threshold),
                              np.asarray(oracle.counts)[:, -1]), \
            f"{engine.value} {method.value} {layout} threshold"


# The old test_planner_is_the_only_selector string-grep lived here; the
# invariant is now enforced repo-wide by genielint's executor-sovereignty
# rule (real call-site analysis over every module under src/, not a
# substring scan of four files) -- see tools/genielint/rules_spine.py and
# tests/test_lint.py::test_executor_sovereignty_at_head.


# ---------------------------------------------------------------------------
# Plan cache: same (engine, layout shape, k, method, use_kernel) -> no retrace
# ---------------------------------------------------------------------------

def _mono_plan(idx: GenieIndex, k: int, method=TopKMethod.CPQ) -> plan_lib.QueryPlan:
    return plan_lib.plan_search(
        idx.engine, k, idx.max_count, layout=plan_lib.Layout.MONOLITHIC,
        part_rows=(idx.stats.n_objects,), method=method,
        use_kernel=idx.use_kernel,
    )


def test_plan_cache_no_retrace_on_repeat():
    """Repeated searches with the same layout shape reuse the compiled
    executable: the per-plan trace counter stays at 1."""
    model, raw, data, queries, mc = _case(Engine.EQ)
    idx = GenieIndex.build(Engine.EQ, raw, max_count=mc, use_kernel=False)
    plan_lib.clear_plan_cache()

    first = idx.search(queries, k=5)
    key = _mono_plan(idx, 5)
    assert plan_lib.trace_count(key) == 1

    again = idx.search(queries, k=5)                       # same shape: cached
    _assert_same(again, first)
    assert plan_lib.trace_count(key) == 1, "same shape re-traced"

    other_queries = raw[:4]                                # same [4, m] shape
    idx.search(other_queries, k=5)
    assert plan_lib.trace_count(key) == 1, "same query shape re-traced"

    idx.search(queries, k=7)                               # new k: new plan
    assert plan_lib.trace_count(key) == 1
    assert plan_lib.trace_count(_mono_plan(idx, 7)) == 1


def test_plan_cache_segmented_and_scan_paths():
    """The host-loop per-part kernels and the monolithic executor are cached
    side by side: a second identical search traces nothing new."""
    model, raw, data, queries, mc = _case(Engine.EQ)
    seg = SegmentedIndex(engine=Engine.EQ, max_count=mc, use_kernel=False)
    for a, b in zip(CUTS, CUTS[1:]):
        seg.add(raw[a:b])
    idx = GenieIndex.build(Engine.EQ, raw, max_count=mc, use_kernel=False)
    plan_lib.clear_plan_cache()

    seg.search(queries, k=5)
    idx.search(queries, k=5)
    size_after_first = plan_lib.plan_cache_size()
    traces_after_first = sum(plan_lib._TRACE_COUNTS.values())

    seg.search(queries, k=5)
    idx.search(queries, k=5)
    assert plan_lib.plan_cache_size() == size_after_first
    assert sum(plan_lib._TRACE_COUNTS.values()) == traces_after_first, \
        "repeat search re-traced a cached executable"


# ---------------------------------------------------------------------------
# Plan construction: layout validation, pad accounting, describe()
# ---------------------------------------------------------------------------

def test_part_kernels_survive_corpus_growth():
    """Growing a segmented corpus must not re-trace per-part kernels for
    part shapes already compiled: the kernel key is the part shape (+ match,
    clamped k, pad-mask flag), not the whole corpus layout."""
    model, raw, data, queries, mc = _case(Engine.EQ, n=150)
    seg = SegmentedIndex(engine=Engine.EQ, max_count=mc, use_kernel=False)
    plan_lib.clear_plan_cache()
    seg.add(raw[:50])
    first = seg.search(queries, k=5)
    traces = sum(plan_lib._TRACE_COUNTS.values())
    seg.add(raw[50:100])                       # same 50-row seal shape
    seg.add(raw[100:150])
    grown = seg.search(queries, k=5)
    assert sum(plan_lib._TRACE_COUNTS.values()) == traces, \
        "corpus growth re-traced an already-compiled part kernel"
    mono = GenieIndex.build(Engine.EQ, raw, max_count=mc, use_kernel=False)
    _assert_same(grown, mono.search(queries, k=5))
    mono50 = GenieIndex.build(Engine.EQ, raw[:50], max_count=mc, use_kernel=False)
    _assert_same(first, mono50.search(queries, k=5))


def test_plan_cache_is_bounded(monkeypatch):
    """The executable cache evicts FIFO past PLAN_CACHE_CAP instead of
    pinning stale jitted programs forever."""
    model, raw, data, queries, mc = _case(Engine.EQ, n=24)
    monkeypatch.setattr(plan_lib, "PLAN_CACHE_CAP", 3)
    plan_lib.clear_plan_cache()
    idx = GenieIndex.build(Engine.EQ, raw, max_count=mc, use_kernel=False)
    for k in (1, 2, 3, 4, 5):
        idx.search(queries, k=k)
    assert plan_lib.plan_cache_size() <= 3


def test_plan_search_validates_layout():
    assert [l.value for l in plan_lib.Layout] == [
        "monolithic", "segmented", "distributed"]
    with pytest.raises(ValueError, match="part_rows"):
        plan_lib.plan_search(Engine.EQ, 3, 16,
                             layout=plan_lib.Layout.SEGMENTED)
    with pytest.raises(ValueError, match="monolithic"):
        plan_lib.plan_search(Engine.EQ, 3, 16, part_rows=(4, 4))
    with pytest.raises(ValueError, match="positive"):
        plan_lib.plan_search(Engine.EQ, 3, 16,
                             layout=plan_lib.Layout.SEGMENTED, part_rows=(4, 0))


def test_plan_layout_accounting_and_describe():
    plan = plan_lib.plan_search(
        Engine.EQ, 7, 16, layout=plan_lib.Layout.SEGMENTED,
        part_rows=(26, 26, 26, 26), n_objects=101, use_kernel=False,
    )
    assert plan.part_rows == (26, 26, 26, 26)
    assert plan.pad_rows == 3 and plan.total_rows == 104
    assert plan.part_k(2) == 2 and plan.part_k(50) == 7
    d = plan.describe()
    assert d["layout"] == "segmented" and d["engine"] == "eq"
    assert d["merge"] == "ragged-buffer" and d["pad_rows"] == 3

    mono = plan_lib.plan_search(Engine.EQ, 7, 16, part_rows=(101,),
                                use_kernel=False)
    assert mono.describe()["merge"] == "none"
    dist = plan_lib.plan_search(
        Engine.EQ, 7, 16, layout=plan_lib.Layout.DISTRIBUTED, n_objects=101,
        hierarchical=True, mesh_axes=("pod", "data", "model"),
    )
    assert dist.describe()["merge"] == "collective-hierarchical"


def test_pad_and_stack_fills_with_engine_pad():
    """pad_to_multiple appends the plan's engine fill up to the multiple and
    leaves an already-divisible corpus untouched."""
    model, raw, data, queries, mc = _case(Engine.EQ)
    plan = plan_lib.plan_search(
        Engine.EQ, 7, mc, layout=plan_lib.Layout.SEGMENTED,
        part_rows=(26, 26, 26, 26), n_objects=101, use_kernel=False,
    )
    padded, n = plan_lib.pad_to_multiple(np.asarray(data), plan.n_parts,
                                         plan.pad_value)
    assert n == 101 and padded.shape[0] == plan.total_rows == 104
    assert np.array_equal(padded[:101], np.asarray(data))
    assert np.all(padded[101:] == model.pad_value)
    same, n = plan_lib.pad_to_multiple(padded, 4, plan.pad_value)
    assert n == 104 and same is padded


# ---------------------------------------------------------------------------
# PACKED signature layout: layout parity, plan-cache keying, describe()
# ---------------------------------------------------------------------------

PACKED_ENGINES = [e for e in ALL_ENGINES if engines.get(e).supports_packed]


@pytest.mark.parametrize("engine", PACKED_ENGINES)
@pytest.mark.parametrize("method", ALL_METHODS)
def test_planner_packed_layout_parity(engine, method):
    """Every single-process layout under signature_layout=PACKED reproduces
    the WIDE sort oracle's ids and counts exactly, for both match paths
    (use_kernel=True is the fused match->count->local-top-k kernel on the
    MONOLITHIC/SEGMENTED layouts)."""
    k = 9
    model, raw, data, queries, mc = _case(engine)
    oracle = cpq.sort_select(
        model.reference(data, model.prepare_queries(queries)),
        SearchParams(k=k, max_count=mc),
    )
    for use_kernel in (False, True):
        idx = GenieIndex.build(engine, raw, max_count=mc, use_kernel=use_kernel,
                               signature_layout="packed")
        seg = SegmentedIndex(engine=engine, max_count=mc, use_kernel=use_kernel,
                             signature_layout=plan_lib.SignatureLayout.PACKED)
        for a, b in zip(CUTS, CUTS[1:]):
            seg.add(raw[a:b])
        seg.compact(max_segments=2)            # packed segments concat cleanly
        results = {
            "monolithic": idx.search(queries, k=k, method=method),
            "segmented": seg.search(queries, k=k, method=method),
        }
        for layout, got in results.items():
            _assert_same(got, oracle,
                         f"{engine.value} {method.value} {layout} packed "
                         f"kernel={use_kernel}")


def test_packed_plans_cache_separately_from_wide():
    """WIDE and PACKED plans for the same layout shape are distinct cache
    keys (their executables consume different array formats), and the fused
    kernel only rides the layouts whose rows are physical object ids."""
    mk = lambda layout_name, **kw: plan_lib.plan_search(
        Engine.COSINE, 5, 32, layout=plan_lib.Layout[layout_name],
        use_kernel=True, **kw)
    wide = mk("MONOLITHIC", part_rows=(64,))
    packed = mk("MONOLITHIC", part_rows=(64,), signature_layout="packed")
    assert wide != packed
    assert hash(wide) != hash(packed)
    assert wide.describe()["signature_layout"] == "wide"
    assert packed.describe()["signature_layout"] == "packed"
    assert not wide.describe()["fused_match"]
    assert packed.describe()["fused_match"]

    seg = mk("SEGMENTED", part_rows=(40, 24), signature_layout="packed")
    assert seg.describe()["fused_match"]
    # engine-filled pad rows (pad_to_multiple, mesh divisibility) are masked
    # by count, which the fused kernel cannot see -> no fusion there
    padded = mk("SEGMENTED", part_rows=(26,) * 4, n_objects=101,
                signature_layout="packed")
    assert not padded.describe()["fused_match"]
    dist = plan_lib.plan_search(
        Engine.COSINE, 5, 32, layout=plan_lib.Layout.DISTRIBUTED,
        n_objects=101, use_kernel=True, mesh_axes=("data",),
        signature_layout="packed")
    assert not dist.describe()["fused_match"]
    # reference path (use_kernel=False) has no fused kernel either
    ref = plan_lib.plan_search(
        Engine.COSINE, 5, 32, part_rows=(64,), use_kernel=False,
        signature_layout="packed")
    assert not ref.describe()["fused_match"]


def test_packed_plan_rejects_unsupported_engines():
    with pytest.raises(ValueError, match="no packed signature format"):
        plan_lib.plan_search(Engine.EQ, 5, 16, part_rows=(64,),
                             signature_layout="packed")


def test_retrieval_service_rejects_packed_for_wide_only_scheme():
    """Schemes hashing to WIDE-only engines (e2lsh -> EQ) fail at service
    construction, not at the first add()."""
    from repro.serve.retrieval import RetrievalService

    with pytest.raises(ValueError, match="no packed signature format"):
        RetrievalService(embed_fn=lambda x: np.asarray(x), scheme="e2lsh",
                         m_override=16, signature_layout="packed")


def test_retrieval_service_packed_serving_parity(rng):
    """simhash/minhash services sealed PACKED serve identical results to
    WIDE, and index_stats reports the signature footprint win."""
    from repro.serve.retrieval import RetrievalService

    pts = rng.standard_normal((130, 16)).astype(np.float32)
    for scheme in ("simhash", "minhash"):
        svcs = {
            # n_buckets=128: the packed TANIMOTO layout stores uint8 bucket
            # ids, so the minhash rehash domain must be <= 253
            layout: RetrievalService(embed_fn=lambda x: np.asarray(x),
                                     scheme=scheme, m_override=96,
                                     n_buckets=128, signature_layout=layout)
            for layout in ("wide", "packed")
        }
        for svc in svcs.values():
            for a, b in [(0, 30), (30, 37), (37, 90), (90, 130)]:
                svc.add(list(range(a, b)), embeddings=pts[a:b])
        q = pts[88:96] + 0.01
        rw, sw = svcs["wide"].search(None, k=5, embeddings=q)
        rp, sp = svcs["packed"].search(None, k=5, embeddings=q)
        _assert_same(rp, rw, scheme)
        assert np.allclose(sw, sp), scheme
        stats = svcs["packed"].index_stats
        assert stats.signature_layout == "packed"
        assert 0 < stats.bytes_signatures_packed < stats.bytes_signatures_wide
        assert stats.bytes_signatures_packed <= stats.bytes_signatures_wide / 4
        assert svcs["wide"].index_stats.signature_layout == "wide"


# ---------------------------------------------------------------------------
# Distributed layout parity (subprocess: forced multi-device CPU)
# ---------------------------------------------------------------------------

def test_planner_distributed_parity():
    """Every engine x {CPQ, SPQ, SORT} through the DISTRIBUTED layout (flat
    and hierarchical meshes) equals the sort oracle exactly -- the same plan
    executor as single-device, merged collectively."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import cpq, distributed, engines
        from repro.core import plan as plan_lib
        from repro.core.types import SearchParams, TopKMethod
        from repro.launch import mesh as mesh_lib

        meshes = [mesh_lib.make_mesh((2, 4), ('data', 'model')),
                  mesh_lib.make_mesh((2, 2, 2), ('pod', 'data', 'model'))]
        for eng in sorted(engines.available(), key=lambda e: e.value):
            model = engines.get(eng)
            raw, rawq, mc = model.example(np.random.default_rng(0), 128, 4)
            data = model.prepare_data(raw)
            queries = model.prepare_queries(rawq)
            mx = model.resolve_max_count(data, mc)
            want = cpq.sort_select(model.reference(data, queries),
                                   SearchParams(k=7, max_count=mx))
            for mesh in meshes:
                dd = jax.device_put(data, distributed.data_sharding(mesh))
                qq = jax.tree_util.tree_map(
                    lambda x: jax.device_put(x, distributed.replicated(mesh, 2)),
                    queries)
                for method in TopKMethod:
                    for hier in (False, True):
                        plan = plan_lib.plan_search(
                            eng, 7, mx, layout=plan_lib.Layout.DISTRIBUTED,
                            method=method, use_kernel=False, hierarchical=hier,
                            mesh_axes=tuple(mesh.axis_names))
                        res = plan_lib.execute(plan, dd, qq, mesh=mesh)
                        label = (eng.value, tuple(mesh.axis_names),
                                 method.value, hier)
                        assert np.array_equal(np.asarray(res.ids),
                                              np.asarray(want.ids)), label
                        assert np.array_equal(np.asarray(res.counts),
                                              np.asarray(want.counts)), label
        print('planner distributed parity OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "planner distributed parity OK" in out.stdout


def test_planner_distributed_packed_parity():
    """PACKED x {reference, kernel} through a DISTRIBUTED plan equals
    the WIDE sort oracle: a packed segmented corpus exported by concat_data
    (pad rows filled with the packed pad value, masked via n_objects) and
    packed replicated queries, with the packed match running inside
    shard_map on each shard's local words/bytes."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SegmentedIndex, cpq, distributed, engines
        from repro.core import plan as plan_lib
        from repro.core.types import Engine, SearchParams, SignatureLayout
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_mesh((2, 4), ('data', 'model'))
        for eng in (Engine.COSINE, Engine.TANIMOTO):
            model = engines.get(eng)
            raw, rawq, mc = model.example(np.random.default_rng(0), 130, 4)
            data = model.prepare_data(raw)
            mx = model.resolve_max_count(data, mc)
            want = cpq.sort_select(model.reference(data, model.prepare_queries(rawq)),
                                   SearchParams(k=7, max_count=mx))
            seg = SegmentedIndex(engine=eng, max_count=mx,
                                 signature_layout=SignatureLayout.PACKED)
            seg.add(raw[:40]); seg.add(raw[40:130])
            pdata, n = seg.concat_data(pad_multiple=mesh.size)
            assert pdata.shape[0] == 136 and n == 130
            dd = jax.device_put(pdata, distributed.data_sharding(mesh))
            qq = jax.device_put(
                model.prepare_queries_for(rawq, SignatureLayout.PACKED),
                distributed.replicated(mesh, 2))
            for use_kernel in (False, True):
                plan = plan_lib.plan_search(
                    eng, 7, mx, layout=plan_lib.Layout.DISTRIBUTED,
                    n_objects=n, use_kernel=use_kernel,
                    mesh_axes=tuple(mesh.axis_names),
                    signature_layout=SignatureLayout.PACKED)
                res = plan_lib.execute(plan, dd, qq, mesh=mesh)
                label = (eng.value, use_kernel)
                assert np.array_equal(np.asarray(res.ids),
                                      np.asarray(want.ids)), label
                assert np.array_equal(np.asarray(res.counts),
                                      np.asarray(want.counts)), label
        print('distributed packed parity OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "distributed packed parity OK" in out.stdout


def test_retrieval_service_sharded_serving_parity():
    """RetrievalService(mesh=...) serves a segmented corpus sharded across 8
    devices with ids/counts/sims identical to the single-device service, and
    the sharded placement cache refreshes when the corpus changes."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.launch import mesh as mesh_lib
        from repro.serve.retrieval import RetrievalService

        mesh = mesh_lib.make_mesh((2, 4), ('data', 'model'))
        rng = np.random.default_rng(0)
        pts = rng.standard_normal((130, 16)).astype(np.float32)
        for scheme in ('e2lsh', 'simhash', 'minhash'):
            single = RetrievalService(embed_fn=lambda x: np.asarray(x),
                                      scheme=scheme, m_override=96)
            sharded = RetrievalService(embed_fn=lambda x: np.asarray(x),
                                       scheme=scheme, m_override=96, mesh=mesh)
            for a, b in [(0, 30), (30, 37), (37, 90), (90, 130)]:
                single.add(list(range(a, b)), embeddings=pts[a:b])
                sharded.add(list(range(a, b)), embeddings=pts[a:b])
            assert all(isinstance(s.data, np.ndarray)
                       for s in sharded._index.segments), 'segment on a device'
            assert np.array_equal(single.corpus_signatures(),
                                  sharded.corpus_signatures()), scheme
            if scheme != 'simhash':     # COSINE stores signs, not the bits
                assert np.array_equal(single.signatures(pts),
                                      single.corpus_signatures()), scheme
            q = pts[88:96] + 0.01
            r1, s1 = single.search(None, k=5, embeddings=q)
            r2, s2 = sharded.search(None, k=5, embeddings=q)
            assert np.array_equal(np.asarray(r1.ids), np.asarray(r2.ids)), scheme
            assert np.array_equal(np.asarray(r1.counts),
                                  np.asarray(r2.counts)), scheme
            assert np.allclose(s1, s2), scheme
            placed = sharded._placed
            sharded.search(None, k=5, embeddings=q)
            assert sharded._placed is placed, 'placement not cached'
            sharded.add([999], embeddings=pts[:1])
            sharded.search(None, k=5, embeddings=q)
            assert sharded._placed is not placed, 'placement not refreshed'
            assert sharded.items_for(np.asarray(r2.ids))[0][0] is not None
        print('sharded serving parity OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "sharded serving parity OK" in out.stdout


# ---------------------------------------------------------------------------
# Serving-layer satellites: clear errors for empty service / bad ids
# ---------------------------------------------------------------------------

def test_retrieval_service_empty_search_names_service_state():
    from repro.serve.retrieval import RetrievalService

    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=16)
    with pytest.raises(ValueError, match="RetrievalService.*empty.*add"):
        svc.search(None, k=3, embeddings=np.zeros((1, 8), np.float32))
    with pytest.raises(ValueError, match="RetrievalService.*empty.*add"):
        svc.index_stats


def test_retrieval_service_items_for_validates_ids(rng):
    from repro.serve.retrieval import RetrievalService

    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=16)
    svc.add([10, 11, 12], embeddings=rng.standard_normal((3, 8)).astype(np.float32))
    assert svc.items_for(np.asarray([[0, 2, -1]])) == [[10, 12, None]]
    with pytest.raises(ValueError, match="3 items.*0..2|id 3"):
        svc.items_for(np.asarray([[0, 3]]))
    with pytest.raises(ValueError, match="id -5"):
        svc.items_for(np.asarray([[-5]]))
