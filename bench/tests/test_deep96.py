"""The deep-image-96-angular configuration and the cells added with it, on
the CPU at a small size: the tenant through `ServingFrontend` against the
plain reference (rows tied at the k-th count, fewer rows than k), the
control, the harness's `deep96-closed` cell, and the
readers of the fused kernel's metrics on a synthetic window."""
import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import check
import deep96
import deep96_reference
import devtrace
import peaks
import run
import scopes

TINY = dict(n_objects=12_000, n_segments=4, queries_per_batch=16,
            query_pool=256, check_requests=4)


def tiny_cfg(**over):
    cfg = run.read_json(run.find("configs", "deep96", ".json"))
    return dict(cfg, **dict(TINY, **over))


def serve(cfg: dict, seed: int, q: np.ndarray, k: int, trace_dir=None):
    """(ids, counts) of one request of rows `q` to a tenant built as the
    benchmark builds it; the search traced into `trace_dir` when given."""
    from repro.serve import ServingFrontend

    fe = ServingFrontend(max_batch=cfg["queries_per_batch"])
    try:
        deep96.build(cfg, seed, fe, lambda msg: None)
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        res, _ = fe.submit(deep96.TENANT, None, k=k, embeddings=q).result(timeout=300)
        if trace_dir is not None:
            jax.profiler.stop_trace()
    finally:
        fe.close()
    return np.asarray(res.ids), np.asarray(res.counts)


def part_spans(trace_dir) -> list:
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [dict(ev.stats) for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name == "genie.part"]


def compare(cfg, seed, q, ids, counts, k, control=False):
    ref_ids, ref_counts, recount = deep96_reference.reference(
        cfg, seed, q, ids, k, control=control)
    return check.compare(ids, counts, ref_ids, ref_counts, recount)


def test_served_tenant_matches_the_reference(tmp_path):
    """Four segments of 3,000 unit vectors, 237 SimHash bits, k = 10: every
    slot equals the reference's, and the search took the fused kernel, whose
    part spans say how many candidate slots per row it wrote."""
    cfg, seed = tiny_cfg(), 2**31 + 96
    q = deep96.queries(cfg, seed, 16)
    ids, counts = serve(cfg, seed, q, 10, trace_dir=tmp_path)
    nums = compare(cfg, seed, q, ids, counts, 10)
    assert nums == {"count_mismatch": 0.0, "rank_mismatch": 0.0}
    assert ids.shape == (16, 10) and np.all(np.diff(counts, axis=1) <= 0)
    parts = part_spans(tmp_path)
    # 3,000 rows: 12 tiles of 256, each writing k = 16 (10 bucketed) slots
    assert [(p["rows"], p["candidates"]) for p in parts] == [(3000, 192)] * 4


def test_rows_tied_at_the_kth_count(monkeypatch):
    """Every segment holds copies of the same 40 vectors, so each count is
    shared by 300 rows across all segments: the served top-10 is the lowest
    ids among the rows of the best count, as in the reference."""
    cfg, seed = tiny_cfg(), 11
    real = deep96.points

    def tied(cfg, seed, segment):
        base = real(cfg, seed, 0)[:40]
        rows = int(np.diff(deep96.segment_bounds(cfg))[segment])
        return jax.numpy.tile(base, (rows // 40 + 1, 1))[:rows]

    monkeypatch.setattr(deep96, "points", tied)
    q = deep96.queries(cfg, seed, 8)
    ids, counts = serve(cfg, seed, q, 10)
    nums = compare(cfg, seed, q, ids, counts, 10)
    assert nums == {"count_mismatch": 0.0, "rank_mismatch": 0.0}
    assert np.all(counts == counts[:, :1])            # ten ties of one count
    # the lowest ids of the best count's rows: copies of one or two vectors
    assert np.all(np.diff(ids, axis=1) > 0) and ids.max() < 400


def test_fewer_rows_than_k():
    """Eight rows in four segments against k = 10: eight answers, then empty
    slots (id -1, count -1) as the reference gives them."""
    cfg, seed = tiny_cfg(n_objects=8), 5
    q = deep96.queries(cfg, seed, 3)
    ids, counts = serve(cfg, seed, q, 10)
    nums = compare(cfg, seed, q, ids, counts, 10)
    assert nums == {"count_mismatch": 0.0, "rank_mismatch": 0.0}
    assert sorted(ids[0, :8]) == list(range(8))
    assert np.all(ids[:, 8:] == -1) and np.all(counts[:, 8:] == -1)


def test_control_fails_the_limits():
    """The control (projections in bfloat16) put in the program's place,
    compared as a run compares the program."""
    cfg = tiny_cfg()
    for seed in (3, 4, 5):
        q = deep96.queries(cfg, seed, 32)
        ids, counts, _ = deep96_reference.reference(
            cfg, seed, q, np.zeros((32, 10), np.int32), 10, control=True)
        nums = compare(cfg, seed, q, ids, counts, 10)
        assert not check.judge(nums, cfg["limits"]), nums


def test_reference_is_the_same_in_any_blocks(monkeypatch):
    """The reference walks the corpus in blocks only to bound memory: blocks
    of 1,000 rows, across the segments' bounds, give what whole segments do."""
    cfg, seed = tiny_cfg(), 3
    q = deep96.queries(cfg, seed, 8)
    whole = deep96_reference.reference(cfg, seed, q, np.zeros((8, 10), np.int32), 10)
    monkeypatch.setattr(deep96_reference, "BLOCK", 1000)
    ids, counts, recount = deep96_reference.reference(cfg, seed, q, whole[0], 10)
    assert np.array_equal(ids, whole[0]) and np.array_equal(counts, whole[1])
    assert np.array_equal(recount, whole[1])


def test_least_bytes():
    cfg = run.read_json(run.find("configs", "deep96", ".json"))
    # 237 bits in 30 whole bytes per object and query row; 10 ids and counts
    assert deep96.least_bytes(cfg, 128, 10) == 9_990_000 * 30 + 128 * 30 + 128 * 80


CELLS = {"deep96-closed": (dict(TINY), dict(clients=2))}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_new_cell_resolves_and_its_tiny_run_is_correct(name, tmp_path):
    cell, cfg, traffic, layer = run.resolve(name)
    assert cell["chips"] == 1
    assert cell["end_to_end"] == ["qps", "latency_p50_ms", "setup_s"]
    over_cfg, over_traffic = CELLS[name]
    res = run.run_cell(cell, dict(cfg, **over_cfg), dict(traffic, **over_traffic),
                       layer, 2**33 + 15, 1.0, False, time.perf_counter(),
                       out_dir=str(tmp_path))
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "latency_p50_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_every_traffic_mix_and_configuration_is_run_by_a_cell():
    """A data file no cell names is never measured: every mix under
    `traffic/` and every configuration under `configs/` has a cell."""
    doc = run.read_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    mixes = {os.path.basename(p)[:-len(".json")]
             for p in glob.glob(os.path.join(run.BENCH, "traffic", "*.json"))}
    configs = {os.path.basename(p)[:-len(".json")]
               for p in glob.glob(os.path.join(run.BENCH, "configs", "*.json"))}
    assert mixes == {w["traffic"] for w in doc["workloads"]}
    assert configs == {w["config"] for w in doc["workloads"]}


# ---------------------------------------------------------------------------
# The readers of the fused kernel's metrics, on a synthetic window
# ---------------------------------------------------------------------------

def _window():
    """Two 128-row searches: the fused kernel's custom calls (2 x 3 ms), its
    wrapper's transpose, the reduction's sort."""
    search = lambda seq, s, e: devtrace.Span("bench.search", s, e, {"seq": seq})
    ops = [devtrace.Op("packed_cosine_topk.1", 1_000, 3_001_000),
           devtrace.Op("transpose.3", 3_001_000, 3_101_000),
           devtrace.Op("packed_cosine_topk.1", 5_000_000, 8_000_000),
           devtrace.Op("fusion.7", 8_000_000, 9_000_000)]
    return devtrace.reduce(devtrace.Trace(
        ops=[ops], spans=[search(0, 0, 4_000_000), search(1, 4_500_000, 10_000_000)]))


def _analysis(win, parts):
    ev = lambda name, s, e, **st: scopes.HostEvent(name, 0, s, e, st)
    spans = [ev("genie.part", s, s + 100, part=i, rows=624_375, **st)
             for i, (s, st) in enumerate(parts)]
    scope_ns = {"genie.topk": 6_100_000.0, "genie.order": 1_000_000.0,
                "genie.merge": 1_000.0}
    return scopes.Analysis(
        scope_ns=scope_ns, busy_ns=sum(scope_ns.values()), unattributed_ops=[],
        source_ns={}, dispatches=[], spans=spans, self_ns={}, idle_gaps=[], gc={})


def _ctx(win, cfg, rows=256):
    return dict(cfg=cfg, k=16, peaks=peaks.peaks("TPU v5 lite"),
                dispatches=len(win.searches), dispatch_seconds=0.0095, rows=rows,
                least_bytes=2 * deep96.least_bytes(cfg, 128, 10))


def test_fused_kernel_readers_on_a_synthetic_window(monkeypatch):
    cell, cfg, _, layer = run.resolve("deep96-closed")
    new = ("topk_us_per_query", "order_us_per_query", "topk_hbm_roofline",
           "candidates_per_query")
    assert [m["name"] for m in layer] == list(new)
    win = _window()
    parts = [(100 + 10 * i, {"candidates": 39_024}) for i in range(32)]
    monkeypatch.setattr(scopes, "analyse", lambda w: _analysis(w, parts))
    got = {k: v["value"] for k, v in run.per_layer(layer, win, _ctx(win, cfg)).items()}
    kernel_ns = 6_000_000
    assert got["topk_us_per_query"] == pytest.approx(kernel_ns * 1e-3 / 256)
    assert got["order_us_per_query"] == pytest.approx(1_000_000 * 1e-3 / 256)
    least_s = 2 * deep96.least_bytes(cfg, 128, 10) / 819e9
    assert got["topk_hbm_roofline"] == pytest.approx(100 * least_s / (kernel_ns * 1e-9))
    assert 0 < got["topk_hbm_roofline"] < 100
    # 16 parts of 39,024 slots for each of the two searches
    assert got["candidates_per_query"] == pytest.approx(16 * 39_024)


def test_fused_kernel_readers_fall_silent_without_their_sources(monkeypatch):
    """A program without the fused kernel's name, the `candidates` stat or
    the program's marks: each reader returns nothing and raises nothing."""
    _, cfg, _, layer = run.resolve("deep96-closed")
    win = _window()
    other = dict(cfg, match_kernel="match_count")
    monkeypatch.setattr(scopes, "analyse",
                        lambda w: _analysis(w, [(100, {}), (200, {})]))
    got = run.per_layer(layer, win, _ctx(win, other))
    assert set(got) == {"order_us_per_query"}
    monkeypatch.setattr(scopes, "analyse", lambda w: None)
    assert run.per_layer(layer, win, _ctx(win, other)) == {}
    assert set(run.per_layer(layer, win, _ctx(win, cfg, rows=0))) == {
        "topk_hbm_roofline"}
