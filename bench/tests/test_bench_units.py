"""The benchmark's arithmetic: intervals, Poisson due times, peaks, least
bytes, result keys, warm-up shapes."""
import os

import numpy as np
import pytest

import adult
import devtrace
import load
import peaks
import plain
import run
import sift

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_and_gaps_of_intervals():
    spans = [(0, 10), (5, 20), (30, 40), (38, 45), (50, 50)]
    assert devtrace.union_ns(spans, 0, 100) == 20 + 15
    assert devtrace.union_ns(spans, 8, 35) == 12 + 5
    assert devtrace.gaps_ns(spans, 0, 100) == [(20, 30), (45, 100)]
    assert devtrace.union_ns([], 0, 10) == 0
    assert devtrace.gaps_ns([], 0, 10) == [(0, 10)]


def test_reduce_synthetic_trace():
    search = lambda seq, s, e: devtrace.Span("bench.search", s, e, {"seq": seq})
    ops = [devtrace.Op("cpq_hist.3", 100, 200, "jit(search)/jit(cpq_hist)/pallas_call"),
           devtrace.Op("match_count.1", 150, 250, "jit(search)/jit(match_count)/pallas_call"),
           devtrace.Op("fusion.2", 400, 500),
           devtrace.Op("outside", 2000, 3000)]
    tr = devtrace.Trace(ops=[ops], spans=[search(4, 50, 600), search(5, 700, 1000),
                                          devtrace.Span("bench.submit", 600, 650, {})])
    win = devtrace.reduce(tr)
    assert (win.start, win.end) == (50, 1000)
    assert win.busy_ns == 150 + 100
    assert win.kernel_ns("cpq_hist") == 100 and win.kernel_ns("match_count") == 100
    assert [s.stats["seq"] for s in win.searches] == [4, 5]
    gaps = dict((round(v * 1e9), k) for k, v in win.idle_gaps)
    assert gaps == {500: "inside bench.search", 150: "inside bench.search",
                    50: "inside bench.search"}
    bd = devtrace.breakdown(win)
    assert bd["device_ops"][0][1] == pytest.approx(100e-9)
    assert "match_count.1 | jit(search)/jit(match_count)/pallas_call" in dict(bd["device_ops"])
    assert [g[1] for g in bd["idle_gaps"]] == pytest.approx([500e-9, 150e-9, 50e-9])
    assert devtrace.reduce(devtrace.Trace(ops=[], spans=tr.spans)) is None
    assert devtrace.reduce(devtrace.Trace(ops=[ops], spans=[])) is None


def test_kernel_time_leaves_out_its_wrappers_ops():
    """The pads, transposes and slices of a kernel's jitted wrapper carry the
    wrapper's name in their source path only: they are not kernel time."""
    search = devtrace.Span("bench.search", 0, 1000, {"seq": 0})
    ops = [devtrace.Op("pad_bitcast_fusion", 0, 100, "jit(search)/jit(match_count)/transpose"),
           devtrace.Op("pad.6", 100, 130, "jit(search)/jit(match_count)/jit(_pad)/pad"),
           devtrace.Op("match_count.1", 130, 400, "jit(search)/jit(match_count)/pallas_call"),
           devtrace.Op("match_count.2.clone", 400, 450, "jit(search)/jit(match_count)/pallas_call"),
           devtrace.Op("slice.4", 450, 470, "jit(search)/jit(match_count)/slice"),
           devtrace.Op("cpq_hist.1", 470, 500, "jit(search)/jit(cpq_hist)/pallas_call"),
           devtrace.Op("fusion.9", 500, 560, "jit(search)/jit(cpq_hist)/pad")]
    win = devtrace.reduce(devtrace.Trace(ops=[ops], spans=[search]))
    assert win.kernel_ns("match_count") == 270 + 50
    assert win.kernel_ns("cpq_hist") == 30
    assert win.kernel_ns("range_count") == 0
    assert win.busy_ns == 560


@pytest.mark.parametrize("rate,seconds", [(16.0, 30.0), (3.5, 10.0), (200.0, 2.0)])
def test_poisson_due_offsets_same_work_for_every_seed(rate, seconds):
    a = load.poisson_due_offsets(rate, seconds, seed=1)
    b = load.poisson_due_offsets(rate, seconds, seed=2**31 + 5)
    n = int(round(rate * seconds))
    assert a.size == b.size == n
    assert np.all(np.diff(a) > 0) and a[0] > 0
    # the same set of gaps in another order
    gaps_a = np.sort(np.diff(np.concatenate([[0.0], a])))
    gaps_b = np.sort(np.diff(np.concatenate([[0.0], b])))
    np.testing.assert_allclose(gaps_a, gaps_b)
    assert not np.array_equal(a, b)
    # mean gap is 1 / rate up to the quantile grid's truncated tail
    assert gaps_a.mean() == pytest.approx(1.0 / rate, rel=0.15)
    assert a[-1] < seconds


def test_traffic_validation():
    load.validate({"loop": "closed", "clients": 2, "rows": 1, "k": 10})
    load.validate({"loop": "open", "rate": 2.0, "rows": 1, "k": 10})
    for bad in ({"loop": "closed", "rows": 1, "k": 10},
                {"loop": "open", "rate": 0, "rows": 1, "k": 10},
                {"loop": "burst", "rows": 1, "k": 10}):
        with pytest.raises(ValueError):
            load.validate(bad)


def test_peaks_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks("TPU v9")


def test_least_bytes():
    cfg_s = run.read_json(run.find("configs", "sift", ".json"))
    cfg_a = run.read_json(run.find("configs", "adult", ".json"))
    # one 1-byte read of every SIFT signature (67 buckets), 16 query rows
    # of 237 bytes, 100 ids and counts out per row
    assert sift.least_bytes(cfg_s, 16, 100) == 4_500_000 * 237 + 16 * 237 + 16 * 800
    # Adult's 1024 bins need 2 bytes; each query row carries lo and hi
    assert adult.least_bytes(cfg_a, 128, 100) == (980_000 * 14 * 2 + 128 * 2 * 14 * 2
                                                  + 128 * 800)
    assert [plain.int_bytes(d) for d in (2, 67, 256, 257, 1024, 70000)] == [1, 1, 1, 2, 2, 4]


def test_order_keys_order_and_decode():
    import jax.numpy as jnp

    counts = jnp.array([[3, 5, 5, 0, 5]], jnp.int32)
    ids = jnp.arange(5, dtype=jnp.int32)[None, :]
    for desc, want in ((False, [1, 2, 4, 0, 3]), (True, [4, 2, 1, 0, 3])):
        keys = plain.order_keys(counts, ids, 5, 5, ties_descending=desc)
        top = jnp.sort(keys, axis=1)[:, ::-1]
        got_ids, got_counts = plain.decode_keys(top, 5, ties_descending=desc)
        assert got_ids.tolist() == [want]
        assert got_counts.tolist() == [[5, 5, 5, 3, 0]]
    with pytest.raises(ValueError):
        plain.order_keys(counts, ids, 1 << 24, 255)


def test_warm_shapes():
    closed1 = {"loop": "closed", "clients": 16, "rows": 1, "k": 100}
    closed8 = {"loop": "closed", "clients": 16, "rows": 8, "k": 100}
    open1 = {"loop": "open", "rate": 10.0, "rows": 1, "k": 100}
    assert run.warm_shapes(closed1, 64) == [1, 2, 4, 8, 16]
    assert run.warm_shapes(closed8, 128) == [8, 16, 32, 64, 128]
    assert run.warm_shapes(open1, 64) == [1, 2, 4, 8, 16, 32, 64]


def test_dispatch_rows_assigns_completions_to_calls():
    spans = [(0.0, 1.0), (1.1, 2.0), (2.1, 3.0)]
    rec = lambda done, rows, err=None: load.Record(0, np.arange(rows), 0.0,
                                                   done=done, error=err)
    records = [rec(1.01, 1), rec(1.02, 2), rec(2.05, 4), rec(3.5, 8),
               rec(2.06, 16, err=RuntimeError("x")), rec(None, 32)]
    assert run.dispatch_rows(spans, records) == [3, 4, 8]


def test_check_numbers_and_lines():
    import check

    ids = np.array([[1, 2, 3], [4, 5, 6]])
    counts = np.array([[9, 8, 8], [7, 7, 1]])
    nums = check.compare(ids, counts, ids, counts, counts)
    assert nums == {"count_mismatch": 0.0, "rank_mismatch": 0.0}
    bad = ids.copy()
    bad[1, 2] = 7
    nums = check.compare(bad, counts, ids, counts, np.array([[9, 8, 8], [7, 7, 2]]))
    assert nums == {"count_mismatch": 1 / 6, "rank_mismatch": 1 / 6}
    assert not check.judge(nums, {"count_mismatch": 0.1, "rank_mismatch": 0.5})
    assert check.judge(nums, {"count_mismatch": 0.2, "rank_mismatch": 0.2})
    assert check.lines({"a": 0.5}, {"a": 1}) == ["check a: 0.5 (limit 1)"]


def test_load_reads_the_benchmark_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here (the CPU backend has no device plane, so the
    reduction has no window): `load` finds the `bench.search` spans with
    their stats, in order, and `reduce` declines rather than invent one."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for seq in range(3):
        with jax.profiler.TraceAnnotation("bench.search", seq=seq, rows=16):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = devtrace.load(str(tmp_path))
    searches = tr.searches()
    assert [s.stats["seq"] for s in searches] == [0, 1, 2]
    assert all(s.stats["rows"] == 16 and s.end > s.start for s in searches)
    assert tr.ops == [] and devtrace.reduce(tr) is None


def test_reduce_a_trace_recorded_on_the_chip(tmp_path):
    """A 2 s `adult-closed` window traced on one TPU v5e: one 128-row
    dispatch.  The kernels' time is their own custom calls', whose event
    names on the TPU are their HLO text; the per-layer readers read it."""
    import gzip
    import re
    import shutil

    path = tmp_path / "adult-closed.xplane.pb"
    with gzip.open(os.path.join(DATA, "adult-closed.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    tr = devtrace.load(str(path))
    assert len(tr.ops) == 1
    assert [(s.stats["seq"], s.stats["rows"]) for s in tr.searches()] == [(5, 128)]
    win = devtrace.reduce(tr)
    assert 0 < win.busy_ns <= win.end - win.start
    assert [s.stats["seq"] for s in win.searches] == [5]

    def own(head):
        return sum(min(o.end, win.end) - max(o.start, win.start) for o in tr.ops[0]
                   if re.match(rf"%{head}\.\d+ = ", o.name)
                   and o.end > win.start and o.start < win.end)

    for kernel in ("range_count", "cpq_hist"):
        assert win.kernel_ns(kernel) == own(kernel) > 0
    assert win.kernel_ns("match_count") == 0
    assert own("pad") > 0   # the wrappers' pads run in the window, apart
    # ops that read the kernel's output name it in their HLO text: not its time
    assert any("%range_count.1" in o.name and o.instruction != "range_count"
               for o in tr.ops[0])

    bd = devtrace.breakdown(win)
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("%fusion.")
    assert all(len(name) <= devtrace.LABEL_CHARS for name, _ in bd["device_ops"])

    cell, cfg, traffic, layer = run.resolve("adult-closed")
    got = run.per_layer(layer, win, dict(
        cfg=cfg, k=100, peaks=peaks.peaks("TPU v5 lite"), dispatches=1,
        dispatch_seconds=1.3, rows=128, least_bytes=adult.least_bytes(cfg, 128, 100)))
    assert set(got) == {m["name"] for m in layer}
    v = {k: x["value"] for k, x in got.items()}
    assert v["match_us_per_query"] == pytest.approx(own("range_count") * 1e-3 / 128)
    assert v["hist_us_per_query"] == pytest.approx(own("cpq_hist") * 1e-3 / 128)
    assert (v["match_us_per_query"] + v["hist_us_per_query"] + v["xla_us_per_query"]
            == pytest.approx(win.busy_ns * 1e-3 / 128))
    assert 0 < v["device_idle_pct"] < 100 and 0 < v["search_hbm_roofline"] < 100
    assert v["dispatch_ms"] == pytest.approx(1300.0)


def test_qps_counts_the_dispatches_answered_by_the_close():
    """Four 1-row requests a dispatch, one a second over a 10 s window; the
    dispatch the close splits (three requests answered at 11 s) and a failed
    request leave `qps` alone, and every answered request has a latency."""
    rec = lambda due, done, err=None: load.Record(0, np.arange(1), due, done=done,
                                                  error=err)
    records = [rec(d - 1.0, float(d)) for d in range(1, 11) for _ in range(4)]
    records += [rec(10.0, 11.0)] * 3 + [rec(9.5, 10.5, err=RuntimeError("x"))]
    m = run.end_to_end(records, 0.0, 10.0, 5.0)
    assert m["qps"]["value"] == pytest.approx(4.0)
    assert m["latency_p50_ms"]["value"] == pytest.approx(1000.0)
    assert m["setup_s"] == {"value": 5.0, "unit": "s"}
    assert run.end_to_end(records[:40], 0.0, 10.0, 5.0)["qps"] == m["qps"]
