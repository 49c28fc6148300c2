"""Coarse-routing conformance suite (core/routing.py + the routed executors
in core/plan.py).

The load-bearing guarantee: ROUTED_VERIFIED is bit-for-bit identical to the
full scan -- identical ids, counts, AND thresholds -- across

    6 engines x {CPQ, SPQ, SORT} x {SEGMENTED, DISTRIBUTED (subprocess,
    8 forced CPU devices)}

because the router's per-engine scores are true *upper bounds* on any row's
match count, and the verified mode falls back to the full scan whenever a
skipped segment's bound reaches the routed threshold (`>=`: a tied count
with a smaller id displaces the k-th slot).  The suite also pins:

  * upper-bound soundness per engine (UB >= the real per-segment max count),
    through merge_summaries (compaction) as well;
  * that routed searches genuinely skip device work for cold segments (no
    part kernel traced for a pruned row count) and genuinely fall back when
    a skipped bound ties the threshold;
  * plan-level plumbing: routing rejected on the single-program layouts,
    routing/nprobe in describe() and in the plan cache key, router=
    validation at execute();
  * RetrievalService routing: parity, router-cache invalidation on add;
  * PR-7 satellites: iterator queries to search(), candidate_cap threading,
    describe() truncation consistency, the empty-corpus items_for message,
    monotonic build/compaction clocks, dead merge._offset_ids removal.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, cpq, engines
from repro.core import plan as plan_lib
from repro.core import routing as routing_lib
from repro.core.types import Engine, SearchParams, TopKMethod, TopKResult

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ALL_ENGINES = sorted(engines.available(), key=lambda e: e.value)
ALL_METHODS = [TopKMethod.CPQ, TopKMethod.SPQ, TopKMethod.SORT]

# uneven on purpose (mirrors test_plan.py): a 1-row segment, a segment
# smaller than k, a big one -- routing must stay exact on ragged parts
CUTS = [0, 3, 4, 40, 90, 101]


def _case(engine: Engine, n=101, q=4, seed=0):
    model = engines.get(engine)
    raw, queries, mc = model.example(np.random.default_rng(seed), n, q)
    data = model.prepare_data(raw)
    return model, raw, data, queries, model.resolve_max_count(data, mc)


def _segmented(engine: Engine, raw, mc) -> SegmentedIndex:
    seg = SegmentedIndex(engine=engine, max_count=mc, use_kernel=False)
    for a, b in zip(CUTS, CUTS[1:]):
        seg.add(raw[a:b])
    return seg


def _assert_same(got, want, label=""):
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids)), label
    assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts)), label
    assert np.array_equal(np.asarray(got.threshold),
                          np.asarray(want.threshold)), label


# ---------------------------------------------------------------------------
# Conformance: ROUTED_VERIFIED == full scan, engine x method x host layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("method", ALL_METHODS)
def test_routed_verified_equals_full_scan(engine, method):
    """ROUTED_VERIFIED at the most aggressive pruning (nprobe=1) reproduces
    the full scan bit-for-bit on the SEGMENTED host loop, and ROUTED with
    every probe open is trivially the full scan too."""
    k = 9
    model, raw, data, queries, mc = _case(engine)
    seg = _segmented(engine, raw, mc)
    n_seg = len(seg.segments)
    full = seg.search(queries, k, method=method)
    verified = seg.search(queries, k, method=method,
                          routing="routed_verified", nprobe=1)
    _assert_same(verified, full, f"{engine.value} {method.value} verified")
    wide_open = seg.search(queries, k, method=method,
                           routing="routed", nprobe=n_seg)
    _assert_same(wide_open, full, f"{engine.value} {method.value} all-probes")


# ---------------------------------------------------------------------------
# Upper-bound soundness: the router's whole contract, per engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_upper_bound_is_sound_per_segment(engine):
    """For every engine, segment, and query: upper_bound(summary, q) >= the
    true max match count any of the segment's rows reaches (the reference
    count matrix is the oracle).  This is the property ROUTED_VERIFIED's
    exactness rests on."""
    model, raw, data, queries, mc = _case(engine, q=6, seed=3)
    prepared_q = model.prepare_queries(queries)
    counts = np.asarray(model.reference(data, prepared_q))  # [Q, N]
    wide = np.asarray(data)
    for a, b in zip(CUTS, CUTS[1:]):
        summ = routing_lib.summarize(engine, wide[a:b])
        ub = routing_lib.upper_bound(summ, prepared_q)
        actual = counts[:, a:b].max(axis=1)
        assert (ub >= actual - 1e-9).all(), \
            f"{engine.value} segment [{a}:{b}]: UB {ub} < actual {actual}"


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_merged_summary_stays_sound(engine):
    """merge_summaries (what compaction aggregates) still upper-bounds the
    concatenated segment, and merges bookkeeping row-weighted."""
    model, raw, data, queries, mc = _case(engine, n=90, q=5, seed=7)
    prepared_q = model.prepare_queries(queries)
    counts = np.asarray(model.reference(data, prepared_q))
    wide = np.asarray(data)
    a = routing_lib.summarize(engine, wide[:40])
    b = routing_lib.summarize(engine, wide[40:])
    merged = routing_lib.merge_summaries(a, b)
    assert merged.n_rows == 90
    assert np.allclose(merged.centroid,
                       (a.centroid * 40 + b.centroid * 50) / 90)
    ub = routing_lib.upper_bound(merged, prepared_q)
    assert (ub >= counts.max(axis=1) - 1e-9).all(), \
        f"{engine.value}: merged UB {ub} < actual {counts.max(axis=1)}"


@pytest.mark.parametrize("engine", [Engine.EQ, Engine.COSINE, Engine.RANGE])
def test_compaction_merges_summaries_and_keeps_parity(engine):
    """compact() carries routing through: merged segments keep (merged)
    summaries and ROUTED_VERIFIED stays bit-for-bit after compaction."""
    model, raw, data, queries, mc = _case(engine)
    seg = _segmented(engine, raw, mc)
    full = seg.search(queries, 9)
    seg.compact(2)
    assert len(seg.segments) == 2
    assert all(s.summary is not None for s in seg.segments), \
        "compaction dropped a routing summary"
    verified = seg.search(queries, 9, routing="routed_verified", nprobe=1)
    _assert_same(verified, full, f"{engine.value} post-compaction")


# ---------------------------------------------------------------------------
# The router actually skips -- and actually falls back
# ---------------------------------------------------------------------------

def test_routed_skips_cold_segment_without_device_work():
    """A segment the router rules out (UB strictly under the threshold) is
    never traced: no per-part kernel exists for its row count.  Two EQ
    segments with disjoint bucket values make the pruning deterministic."""
    cold = np.zeros((40, 16), dtype=np.int32)
    hot = np.full((35, 16), 7, dtype=np.int32)
    seg = SegmentedIndex(engine=Engine.EQ, use_kernel=False)
    seg.add(cold)
    seg.add(hot)
    q = np.full((2, 16), 7, dtype=np.int32)
    plan_lib.clear_plan_cache()
    verified = seg.search(q, 5, routing="routed_verified", nprobe=1)
    traced_rows = {key[-1] for key in plan_lib._TRACE_COUNTS
                   if key[0] == "part"}
    assert 35 in traced_rows, "the routed segment was not scanned"
    assert 40 not in traced_rows, \
        "the pruned segment was traced -- routing did no device-work pruning"
    _assert_same(verified, seg.search(q, 5), "cold-segment skip")


def test_verified_falls_back_on_tied_upper_bound():
    """When a skipped segment's bound TIES the routed threshold the verified
    mode must rescan (a tied count with a smaller id displaces the k-th
    slot): identical segments force the tie, and both row counts trace."""
    seg = SegmentedIndex(engine=Engine.EQ, use_kernel=False)
    seg.add(np.full((40, 16), 7, dtype=np.int32))
    seg.add(np.full((35, 16), 7, dtype=np.int32))
    q = np.full((2, 16), 7, dtype=np.int32)
    plan_lib.clear_plan_cache()
    verified = seg.search(q, 5, routing="routed_verified", nprobe=1)
    traced_rows = {key[-1] for key in plan_lib._TRACE_COUNTS
                   if key[0] == "part"}
    assert {35, 40} <= traced_rows, \
        f"tied upper bound must force the full-scan fallback, traced {traced_rows}"
    _assert_same(verified, seg.search(q, 5), "tied-bound fallback")


def test_unfilled_topk_slot_forces_fallback():
    """threshold == -1 (an unfilled k-th slot) must always trigger the
    fallback: any sound bound (>= 0) reaches it.  Strictly smaller bounds
    must not."""
    two = np.full((1, 2), -1, dtype=np.int32)
    res = TopKResult(ids=two, counts=two, threshold=np.array([-1]))
    verify = np.array([False, True])
    assert plan_lib._skipped_could_contribute(res, np.zeros((1, 2)), verify)
    res3 = TopKResult(ids=two, counts=two, threshold=np.array([3]))
    assert not plan_lib._skipped_could_contribute(
        res3, np.array([[9.0, 2.0]]), verify)
    assert plan_lib._skipped_could_contribute(
        res3, np.array([[0.0, 3.0]]), verify), \
        "UB == threshold must fall back (tie displaces the k-th slot)"


# ---------------------------------------------------------------------------
# Plan plumbing: validation, describe(), cache key, execute() contracts
# ---------------------------------------------------------------------------

def test_plan_rejects_routing_on_single_program_layouts():
    with pytest.raises(ValueError, match="nothing to skip"):
        plan_lib.plan_search(Engine.EQ, 5, 16, routing="routed")
    with pytest.raises(ValueError, match="nothing to skip"):
        plan_lib.plan_search(Engine.EQ, 5, 16, part_rows=(101,),
                             routing="routed_verified")
    with pytest.raises(ValueError, match="nprobe"):
        plan_lib.plan_search(Engine.EQ, 5, 16,
                             layout=plan_lib.Layout.SEGMENTED,
                             part_rows=(3, 4), routing="routed", nprobe=0)


def test_plan_routing_in_describe_and_cache_key():
    common = dict(layout=plan_lib.Layout.SEGMENTED, part_rows=(3, 4),
                  use_kernel=False)
    full = plan_lib.plan_search(Engine.EQ, 5, 16, **common)
    routed = plan_lib.plan_search(Engine.EQ, 5, 16, routing="routed_verified",
                                  nprobe=2, **common)
    assert full != routed and hash(full) != hash(routed), \
        "routed and full plans must be distinct executor-cache keys"
    d = routed.describe()
    assert d["routing"] == "routed_verified" and d["nprobe"] == 2
    assert full.describe()["routing"] == "none"
    # a full-scan plan ignores nprobe so its cache key stays canonical
    assert plan_lib.plan_search(Engine.EQ, 5, 16, nprobe=7, **common,
                                ).nprobe is None


def test_routed_plans_share_part_kernels_with_full_scans():
    """The per-part kernel cache key deliberately excludes routing: a routed
    plan and its full-scan twin compile the same part programs once."""
    common = dict(layout=plan_lib.Layout.SEGMENTED, part_rows=(3, 4),
                  use_kernel=False)
    full = plan_lib.plan_search(Engine.EQ, 5, 16, **common)
    routed = plan_lib.plan_search(Engine.EQ, 5, 16, routing="routed",
                                  **common)
    for rows in (3, 4):
        assert plan_lib._part_key(full, rows) == plan_lib._part_key(routed, rows)


def test_execute_validates_router():
    model, raw, data, queries, mc = _case(Engine.EQ)
    seg = _segmented(Engine.EQ, raw, mc)
    plan = plan_lib.plan_search(
        Engine.EQ, 5, mc, layout=plan_lib.Layout.SEGMENTED,
        part_rows=tuple(seg.segment_rows), use_kernel=False, routing="routed")
    parts = [s.data for s in seg.segments]
    q = model.prepare_queries(queries)
    with pytest.raises(ValueError, match="router="):
        plan_lib.execute(plan, parts, q)
    stale = SegmentedIndex(engine=Engine.EQ, max_count=mc, use_kernel=False)
    stale.add(raw[:50])
    stale.add(raw[50:])
    with pytest.raises(ValueError, match="rebuild the router"):
        plan_lib.execute(plan, parts, q, router=stale.router())


def test_router_and_summary_validation():
    with pytest.raises(ValueError, match="at least one"):
        routing_lib.Router(engine=Engine.EQ, summaries=[])
    with pytest.raises(ValueError, match="non-empty"):
        routing_lib.summarize(Engine.EQ, np.zeros((0, 4), dtype=np.int32))
    with pytest.raises(ValueError, match="non-empty"):
        routing_lib.summarize(Engine.EQ, np.zeros(4, dtype=np.int32))
    a = routing_lib.summarize(Engine.EQ, np.zeros((3, 4), dtype=np.int32))
    b = routing_lib.summarize(Engine.COSINE, np.ones((3, 4), dtype=np.int8))
    with pytest.raises(ValueError, match="engines"):
        routing_lib.merge_summaries(a, b)
    wide = routing_lib.summarize(Engine.EQ, np.zeros((3, 6), dtype=np.int32))
    with pytest.raises(ValueError, match="widths"):
        routing_lib.merge_summaries(a, wide)
    with pytest.raises(ValueError, match="add\\(\\) first"):
        SegmentedIndex(engine=Engine.EQ).router()
    # a hand-assembled segment without a seal-time summary is named
    model, raw, data, queries, mc = _case(Engine.EQ)
    seg = _segmented(Engine.EQ, raw, mc)
    seg.segments[0] = dataclasses.replace(seg.segments[0], summary=None)
    with pytest.raises(ValueError, match="segments \\[0\\]"):
        seg.router()


# ---------------------------------------------------------------------------
# RetrievalService routing (single device; the mesh leg runs in a subprocess)
# ---------------------------------------------------------------------------

def _clustered_service(rng, mesh=None, n_clusters=5, per_cluster=30, d=12):
    from repro.serve.retrieval import RetrievalService

    centers = rng.standard_normal((n_clusters, d)).astype(np.float32)
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), scheme="simhash",
                           m_override=64, mesh=mesh)
    for c in range(n_clusters):
        pts = (centers[c] + 0.1 * rng.standard_normal(
            (per_cluster, d))).astype(np.float32)
        svc.add([f"c{c}-{i}" for i in range(per_cluster)], embeddings=pts)
    return svc, centers


def test_service_routing_parity_and_router_cache():
    rng = np.random.default_rng(0)
    svc, centers = _clustered_service(rng)
    qe = (centers[:2] + 0.05 * rng.standard_normal(
        centers[:2].shape)).astype(np.float32)
    full, sims_full = svc.search(None, k=5, embeddings=qe)
    verified, sims_ver = svc.search(None, k=5, embeddings=qe,
                                    routing="routed_verified", nprobe=1)
    _assert_same(verified, full, "service routed_verified")
    assert np.allclose(sims_ver, sims_full)
    # router cached until the corpus fingerprint changes
    router = svc._router()
    assert svc._router() is router, "router not cached across searches"
    svc.add(["late"], embeddings=centers[:1])
    assert svc._router() is not router, "router not invalidated by add()"
    refreshed, _ = svc.search(None, k=5, embeddings=qe,
                              routing="routed_verified", nprobe=1)
    _assert_same(refreshed, svc.search(None, k=5, embeddings=qe)[0],
                 "service routed_verified after corpus growth")


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------

def test_service_search_accepts_iterator_queries():
    """search(queries) must materialise iterators/generators before len()
    (the add() contract) instead of crashing on a generator."""
    from repro.serve.retrieval import RetrievalService

    svc = RetrievalService(
        embed_fn=lambda items: np.asarray(
            [[float(i), float(i) + 1.0] for i in items], dtype=np.float32),
        scheme="simhash", m_override=32)
    svc.add(range(8))
    from_list, _ = svc.search([2, 3], k=3)
    from_gen, _ = svc.search((i for i in [2, 3]), k=3)
    _assert_same(from_gen, from_list, "generator queries")
    from_iter, _ = svc.search(iter([2, 3]), k=3)
    _assert_same(from_iter, from_list, "iterator queries")


def test_candidate_cap_threads_through_host_loops_and_service(monkeypatch):
    """candidate_cap must reach the CPQ candidate buffer on every entry point
    that forwards it: SegmentedIndex.search, GenieIndex.search, and
    RetrievalService.search.  The observable
    is the cap the compaction kernel is traced with: max(candidate_cap, k),
    or the max(2k, k+16) default when unset."""
    seen = []
    orig = cpq._compact_candidates

    def spy(counts, threshold, cap):
        seen.append(int(cap))
        return orig(counts, threshold, cap)

    monkeypatch.setattr(cpq, "_compact_candidates", spy)
    model, raw, data, queries, mc = _case(Engine.EQ)
    seg = _segmented(Engine.EQ, raw, mc)

    plan_lib.clear_plan_cache()
    seg.search(queries, 5, candidate_cap=31)
    assert 31 in seen, f"SegmentedIndex.search dropped candidate_cap: {seen}"

    seen.clear()
    plan_lib.clear_plan_cache()
    idx = GenieIndex.build(Engine.EQ, raw, max_count=mc, use_kernel=False)
    idx.search(queries, 5, candidate_cap=29)
    assert 29 in seen, f"GenieIndex.search dropped candidate_cap: {seen}"

    seen.clear()
    plan_lib.clear_plan_cache()
    rng = np.random.default_rng(1)
    svc, centers = _clustered_service(rng, n_clusters=3, per_cluster=20)
    svc._index.use_kernel = False  # keep the spy on the reference CPQ path
    svc.search(None, k=5, embeddings=centers[:1], candidate_cap=27)
    assert 27 in seen, f"RetrievalService.search dropped candidate_cap: {seen}"

    seen.clear()
    plan_lib.clear_plan_cache()
    seg.search(queries, 5)
    assert 21 in seen, f"default cap should be max(2k, k+16)=21: {seen}"


def test_describe_truncation_is_consistent():
    """A >32-part plan truncates part_rows AND part_k the same way: both
     33 entries long, both ending in the explicit '...' marker (part_k used
    to truncate silently)."""
    big = plan_lib.plan_search(Engine.EQ, 2, 16,
                               layout=plan_lib.Layout.SEGMENTED,
                               part_rows=(3,) * 40, use_kernel=False)
    d = big.describe()
    assert len(d["part_rows"]) == 33 and d["part_rows"][-1] == "..."
    assert len(d["part_k"]) == 33 and d["part_k"][-1] == "..."
    assert d["part_rows"][:32] == [3] * 32 and d["part_k"][:32] == [2] * 32
    small = plan_lib.plan_search(Engine.EQ, 2, 16,
                                 layout=plan_lib.Layout.SEGMENTED,
                                 part_rows=(3,) * 4, use_kernel=False)
    ds = small.describe()
    assert ds["part_rows"] == [3] * 4 and ds["part_k"] == [2] * 4


def test_items_for_empty_corpus_message():
    """items_for on an empty corpus must not print the non-range '0..-1'."""
    from repro.serve.retrieval import RetrievalService

    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=16)
    with pytest.raises(ValueError, match="no ids are valid"):
        svc.items_for(np.asarray([[0]]))
    svc.add([10, 11], embeddings=np.eye(2, dtype=np.float32))
    with pytest.raises(ValueError, match=r"valid ids are 0\.\.1"):
        svc.items_for(np.asarray([[5]]))


def test_build_and_compaction_clocks_are_monotonic():
    """Durations recorded by index build / compaction / postings must come
    from the monotonic clock -- a wall-clock (NTP) step must never record a
    negative duration."""
    import inspect

    from repro.core import index as index_mod
    from repro.core import postings as postings_mod
    from repro.core import segments as segments_mod

    for mod in (index_mod, segments_mod, postings_mod):
        src = inspect.getsource(mod)
        assert "time.time()" not in src, \
            f"{mod.__name__} times durations with the wall clock"
        assert "perf_counter" in src


def test_merge_dead_offset_helper_removed():
    from repro.core import merge as merge_mod

    assert not hasattr(merge_mod, "_offset_ids"), \
        "dead merge._offset_ids resurfaced"


# ---------------------------------------------------------------------------
# DISTRIBUTED routing (subprocess: 8 forced CPU devices)
# ---------------------------------------------------------------------------

def test_distributed_routing_parity():
    """ROUTED_VERIFIED at nprobe=1 on the DISTRIBUTED layout (shard masking
    + all-ones-mask fallback) equals the sort oracle bit-for-bit for every
    engine x method; ROUTED with every probe open is the full scan too."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core import SegmentedIndex, cpq, distributed, engines
        from repro.core import plan as plan_lib
        from repro.core.types import Engine, SearchParams, TopKMethod
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_mesh((2, 4), ('data', 'model'))
        CUTS = [0, 3, 4, 40, 90, 101]
        for eng in sorted(engines.available(), key=lambda e: e.value):
            model = engines.get(eng)
            raw, rawq, mc = model.example(np.random.default_rng(0), 101, 4)
            seg = SegmentedIndex(engine=eng, max_count=mc, use_kernel=False)
            for a, b in zip(CUTS, CUTS[1:]):
                seg.add(raw[a:b])
            data, n = seg.concat_data(pad_multiple=mesh.size)
            queries = model.prepare_queries(rawq)
            mx = seg.max_count
            want = cpq.sort_select(
                model.reference(model.prepare_data(raw), queries),
                SearchParams(k=7, max_count=mx))
            dd = jax.device_put(data, distributed.data_sharding(mesh))
            qq = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, distributed.replicated(mesh, 2)),
                queries)
            router = seg.router()
            for method in TopKMethod:
                modes = [('routed_verified', 1)]
                # one wide-open ROUTED leg pins the no-fallback early return
                # without doubling the (engine x method) compile matrix
                if method is TopKMethod.CPQ and eng is Engine.EQ:
                    modes.append(('routed', len(CUTS) - 1))
                for mode, npb in modes:
                    plan = plan_lib.plan_search(
                        eng, 7, mx, layout=plan_lib.Layout.DISTRIBUTED,
                        n_objects=n, method=method, use_kernel=False,
                        mesh_axes=tuple(mesh.axis_names),
                        routing=mode, nprobe=npb)
                    res = plan_lib.execute(plan, dd, qq, mesh=mesh,
                                           router=router,
                                           route_queries=queries)
                    label = (eng.value, method.value, mode)
                    assert np.array_equal(np.asarray(res.ids),
                                          np.asarray(want.ids)), label
                    assert np.array_equal(np.asarray(res.counts),
                                          np.asarray(want.counts)), label
        print('distributed routing parity OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "distributed routing parity OK" in out.stdout


def test_distributed_service_routing_parity():
    """RetrievalService(mesh=...) with routing: identical to its own full
    scan AND to the single-device service, candidate_cap reaches the sharded
    CPQ buffers, and the router cache refreshes when the corpus changes."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import cpq as cpq_lib
        from repro.core import plan as plan_lib
        from repro.launch import mesh as mesh_lib
        from repro.serve.retrieval import RetrievalService

        mesh = mesh_lib.make_mesh((2, 4), ('data', 'model'))
        rng = np.random.default_rng(0)
        centers = rng.standard_normal((6, 16)).astype(np.float32)

        def mk(m):
            return RetrievalService(embed_fn=lambda x: np.asarray(x),
                                    scheme='simhash', m_override=64, mesh=m)

        sharded, single = mk(mesh), mk(None)
        base = 0
        for c in range(6):
            pts = (centers[c] + 0.1 * rng.standard_normal(
                (40, 16))).astype(np.float32)
            ids = list(range(base, base + 40)); base += 40
            sharded.add(ids, embeddings=pts)
            single.add(ids, embeddings=pts)
        q = (np.repeat(centers[:3], 2, axis=0)
             + 0.05 * rng.standard_normal((6, 16))).astype(np.float32)
        full, _ = sharded.search(None, k=5, embeddings=q)

        seen = []
        orig = cpq_lib._compact_candidates
        def spy(counts, threshold, cap):
            seen.append(int(cap))
            return orig(counts, threshold, cap)
        cpq_lib._compact_candidates = spy
        plan_lib.clear_plan_cache()
        ver, _ = sharded.search(None, k=5, embeddings=q,
                                routing='routed_verified', candidate_cap=31)
        assert 31 in seen, seen
        assert np.array_equal(np.asarray(ver.ids), np.asarray(full.ids))
        assert np.array_equal(np.asarray(ver.counts), np.asarray(full.counts))
        ones, _ = single.search(None, k=5, embeddings=q,
                                routing='routed_verified')
        assert np.array_equal(np.asarray(ones.ids), np.asarray(ver.ids))

        router = sharded._router()
        assert sharded._router() is router, 'router not cached'
        sharded.add([999], embeddings=centers[:1])
        single.add([999], embeddings=centers[:1])
        assert sharded._router() is not router, 'router not refreshed'
        ver2, _ = sharded.search(None, k=5, embeddings=q,
                                 routing='routed_verified')
        full2, _ = single.search(None, k=5, embeddings=q)
        assert np.array_equal(np.asarray(ver2.ids), np.asarray(full2.ids))
        assert np.array_equal(np.asarray(ver2.counts),
                              np.asarray(full2.counts))
        print('distributed service routing OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "distributed service routing OK" in out.stdout
