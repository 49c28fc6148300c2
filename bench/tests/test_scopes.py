"""The program's spans and scopes read back from a trace (bench/scopes.py,
bench/xspace.py): op metadata and programs the profiler records, the
attribution of each device op to one `genie.*` scope, host spans of a
served dispatch, and the readers of the metrics that rest on them."""
import gzip
import json
import os
import re
import shutil

import numpy as np
import pytest

import adult
import devtrace
import peaks
import run
import scopes
import xspace

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = "adult-closed.xplane.pb.gz"       # recorded before the program had marks
NEW = "adult-closed-scopes.xplane.pb.gz"  # recorded with the spans and scopes
NEW_READERS = ("compact_us_per_query", "merge_us_per_query", "hash_us_per_query",
               "queue_wait_ms", "dispatch_host_ms", "unattributed_pct")


def _unpack(name, tmp_path):
    """A committed trace, unpacked where `scopes.find_trace` looks for it."""
    path = tmp_path / "trace" / name.replace(".gz", "")
    path.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(os.path.join(DATA, name)) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(path)


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path / "trace"))
    scopes._ANALYSED.clear()
    yield tmp_path
    scopes._ANALYSED.clear()


def _read(name, window, rows):
    ctx = type("Ctx", (), dict(window=window, rows=rows))()
    return run.module("metrics", name).read(ctx)


def test_op_metadata_and_programs_of_the_committed_trace():
    """On the TPU an op's `tf_op` and `source` are stats of its event
    metadata: the kernels carry theirs, the compaction's scatter fusions
    none; the programs' optimized HLO is in /host:metadata."""
    meta = xspace.read(os.path.join(DATA, OLD), devtrace.DEVICE_PLANE)
    (plane,) = meta.ops
    by_head = {op.split()[0]: stats for op, found in meta.ops[plane].items()
               for stats in found}
    for kernel, src in (("cpq_hist", "kernels/cpq_hist.py:51"),
                        ("range_count", "kernels/range_count.py:49")):
        stats = by_head[f"%{kernel}.1"]
        assert stats["tf_op"].startswith(f"jit(run)/jit({kernel})/pallas_call")
        assert stats["source"].endswith(src)
    assert "tf_op" not in by_head["%fusion.2"] and "source" not in by_head["%fusion.2"]
    run_prog = meta.programs["jit_run(16224828801452629536)"]
    assert by_head["%fusion.2"]["program_id"] == 16224828801452629536
    fusion = run_prog.instructions["fusion.2"]
    assert fusion.opcode == "fusion" and fusion.called and fusion.op_name == ""
    assert any("scatter" in i.op_name for i in run_prog.calls(fusion))
    assert {n.split("(")[0] for n in meta.programs} >= {
        "jit_run", "jit_argsort", "jit_take_along_axis", "jit_negative"}


def test_existing_metrics_read_as_before_on_the_committed_trace(tmp_path):
    path = _unpack(OLD, tmp_path)
    win = devtrace.reduce(devtrace.load(path))
    cell, cfg, traffic, layer = run.resolve("adult-closed")
    old = [m for m in layer if m["name"] not in NEW_READERS]
    got = run.per_layer(old, win, dict(
        cfg=cfg, k=100, peaks=peaks.peaks("TPU v5 lite"), dispatches=1,
        dispatch_seconds=1.3, rows=128, least_bytes=adult.least_bytes(cfg, 128, 100)))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "dispatch_ms": 1300.0, "match_us_per_query": 34.3843203125,
        "hist_us_per_query": 547.0561015625, "xla_us_per_query": 9682.44953125,
        "device_idle_pct": 0.24081094598152886,
        "search_hbm_roofline": 0.0025604065807165156}, rel=1e-12)
    bd = devtrace.breakdown(win)
    assert list(bd) == ["device_ops", "idle_gaps"]
    assert [round(v, 9) for _, v in bd["device_ops"][:3]] == [
        0.578407565, 0.578200363, 0.070023181]
    assert [k for k, _ in bd["idle_gaps"]] == ["inside bench.search"] * 10


def test_new_readers_find_nothing_without_the_programs_marks(trace_root):
    """A trace of a program without spans or scopes (the parent's): every
    op is unattributed, the split still sums to the busy time, and the
    readers return nothing instead of raising."""
    path = _unpack(OLD, trace_root)
    win = devtrace.reduce(devtrace.load(path))
    a = scopes.analyse_trace(path, win)
    assert not a.has_program_marks
    assert set(a.scope_ns) == {scopes.UNATTRIBUTED}
    assert a.busy_ns == pytest.approx(win.busy_ns, rel=1e-9)
    assert scopes.analyse(win) is None
    for name in NEW_READERS:
        assert _read(name, win, 128) is None


def test_partition_gives_time_to_the_innermost_op():
    op = lambda name, s, e: devtrace.Op(name, s, e)
    ops = sorted([op("%while.1 = ", 0, 100), op("%body.1 = ", 10, 40),
                  op("%body.2 = ", 50, 60), op("%copy.1 = ", 120, 150),
                  op("%late.1 = ", 140, 170)], key=lambda o: (o.start, -o.end))
    got = {}
    for o, ns in scopes._partition(ops, 5, 160):
        got[o.name] = got.get(o.name, 0) + ns
    assert got == {"%while.1 = ": 55, "%body.1 = ": 30, "%body.2 = ": 10,
                   "%copy.1 = ": 20, "%late.1 = ": 20}
    assert sum(got.values()) == devtrace.union_ns([(o.start, o.end) for o in ops],
                                                  5, 160)


def test_attribution_order():
    ins = lambda name, op_name="", operands=(), called=(), opcode="fusion", \
        source="": xspace.Instruction(name, opcode, op_name, source,
                                      tuple(operands), tuple(called))
    instrs = [
        # a scatter fusion: the compiler rewrote the scatter without metadata,
        # kept the bare primitive on its update region and fused in a slice
        # of the match kernel's output
        ins("scatter.1", "scatter", opcode="parameter"),
        ins("slice.9", "jit(run)/genie.match/jit(range_count)/slice",
            opcode="slice"),
        ins("scatter.2", opcode="scatter"),
        ins("fusion.2", called=(1,)),
        ins("and.1", "jit(run)/genie.compact/vmap()/scatter", opcode="and"),
        # a loop fusion of data movement only
        ins("transpose.1", "jit(run)/genie.match/transpose", opcode="transpose"),
        ins("fusion.5", called=(3,)),
        # a reduce-window whose region kept only its source line
        ins("add.9", "reduce_window_sum", opcode="add", source="cpq.py:93"),
        ins("fusion.7", called=(4,)),
        ins("add.1", "jit(run)/genie.gate/add", opcode="add"),
        ins("add.2", "jit(run)/genie.order/add", opcode="add"),
        ins("add.3", "jit(run)/genie.order/add", opcode="add"),
        ins("tuple.1", opcode="tuple", operands=("add.1", "add.2", "add.3")),
        ins("while.1", called=(2,), opcode="while"),
        ins("copy.1", operands=("param.1", "fusion.2"), opcode="copy"),
        ins("param.1", opcode="parameter"),
        ins("copy.2", operands=("param.1",), opcode="copy"),
    ]
    prog = scopes._Program(xspace.Program(
        name="jit_run(1)", instructions={i.name: i for i in instrs},
        computations={1: ("fused", "scatter.2", ["scatter.1", "slice.9", "scatter.2"]),
                      2: ("body", "tuple.1", ["add.1", "add.2", "add.3", "tuple.1"]),
                      3: ("loop", "transpose.1", ["transpose.1"]),
                      4: ("region", "add.9", ["add.9"])}),
        {"fusion.2": {"tf_op": "", "program_id": 1},
         "cpq_hist.1": {"tf_op": "jit(run)/genie.hist/pallas_call:"},
         "sum.1": {"tf_op": "jit(run)/genie.compact/reduce_sum:",
                   "source": "cpq.py:93"},
         "cumsum.1": {"tf_op": "reduce_window_sum:", "source": "cpq.py:93"}})
    # 1: the op's own tf_op, or its source line's scope
    assert prog.scope("cpq_hist.1") == "genie.hist"
    assert prog.scope("cumsum.1") == "genie.compact"
    # 2: the computing instructions it calls, named or by source line; then
    # a bare primitive with one scope in the program; data movement last
    assert prog.scope("fusion.2") == "genie.compact"   # not the fused slice
    assert prog.scope("fusion.7") == "genie.compact"
    assert prog.scope("fusion.5") == "genie.match"
    assert prog.scope("while.1") == "genie.order"
    # 3: the first operand with a scope, one hop
    assert prog.scope("copy.1") == "genie.compact"
    assert prog.scope("copy.2") is None
    assert prog.scoped and not scopes._Program(None, {}).scoped
    assert scopes.scope_of("jit(run)/genie.merge/jit(argsort)/sort") == "genie.merge"


def test_launcher_follows_host_flows_back_to_the_span():
    """4: the program run's enqueue, on a runtime thread, leads through the
    producer/consumer flow ids back to the span open on the calling thread."""
    ev = lambda name, line, s, e, **st: scopes.HostEvent(name, line, s, e, st)
    lines = scopes._Lines([
        [ev("genie.search", 0, 0, 1000), ev("genie.merge", 0, 100, 300),
         ev("PJRT_LoadedExecutable_Execute linkage", 0, 150, 151, _p=7)],
        [ev("PJRT_LoadedExecutable_Execute", 1, 152, 190, _c=7),
         ev("tpu::System::Execute", 1, 160, 170, _p=9)],
        [ev("tpu::System::Execute=>IssueSequencedEvent", 2, 400, 420, _c=9),
         ev("DoEnqueueProgram", 2, 405, 410, run_id=3)],
        [ev("DoEnqueueProgram", 3, 500, 510, run_id=4)],
    ])
    assert lines.launcher(lines.lines[2][1]) == "genie.merge"
    assert lines.launcher(lines.lines[3][0]) is None
    assert lines.innermost_span(0, 50).name == "genie.search"


def _served(trace_root, blocked_s=0.0):
    """A trace recorded here of four 1-row requests through the front end,
    with the benchmark's `bench.search` wrapper; the search sleeps
    `blocked_s` first, as a dispatch thread blocked on the device does.
    Returns the window of the one traced search and its analysis."""
    import time

    import jax

    from repro.serve.frontend import ServingFrontend

    rng = np.random.default_rng(1)
    fe = ServingFrontend(max_batch=4, max_wait_us=200_000, start=False)
    backend = fe.create_tenant("t", embed_fn=np.asarray, scheme="e2lsh",
                               n_buckets=67, m_override=16)
    fe.add("t", range(400), embeddings=rng.normal(size=(400, 8)).astype(np.float32))
    search = backend.search

    def blocked(*args, **kwargs):
        time.sleep(blocked_s)
        return search(*args, **kwargs)

    backend.search = blocked
    spans = run.SearchSpans(backend)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    fe.start()
    fe.submit("t", None, k=5, embeddings=q).result()
    jax.profiler.start_trace(str(trace_root / "trace"))
    futs = [fe.submit("t", None, k=5, embeddings=q[i:i + 1]) for i in range(4)]
    for f in futs:
        f.result(timeout=60)
    jax.profiler.stop_trace()
    fe.close()
    spans.release()

    tr = devtrace.load(str(trace_root / "trace"))
    (search,) = tr.searches()
    win = devtrace.Window(start=search.start, end=search.end, busy_ns=0.0,
                          op_ns={}, instruction_ns={}, searches=[search],
                          idle_gaps=[])
    return win, scopes.analyse(win)


def test_readers_read_the_spans_of_a_served_dispatch(trace_root):
    """The dispatch's spans come back with their stats, and the span readers
    read them (the CPU backend has no device plane, so the device readers
    find nothing)."""
    win, a = _served(trace_root)
    (search,) = win.searches
    (d,) = a.dispatches
    assert d.stats["requests"] == 4 and d.stats["padded_rows"] == 4
    assert d.start < search.start and search.end <= d.end
    # the spans open inside the window (stacking and scattering lie outside)
    assert {s.name for s in a.spans} == {
        "genie.dispatch", "genie.search", "genie.hash", "genie.part",
        "genie.merge", "genie.wait"}
    assert _read("queue_wait_ms", win, 4) == pytest.approx(
        d.stats["queue_wait_us_sum"] / 4 * 1e-3)
    assert 0 <= d.stats["host_cpu_us"] <= (d.end - d.start) * 1e-3
    assert _read("dispatch_host_ms", win, 4) == pytest.approx(
        d.stats["host_cpu_us"] * 1e-3)
    for name in ("compact_us_per_query", "merge_us_per_query",
                 "hash_us_per_query", "unattributed_pct"):
        assert _read(name, win, 4) is None


def test_dispatch_host_time_leaves_out_a_wait_on_the_device(trace_root):
    """A dispatch thread blocked for 0.5 s inside the search, outside any
    `genie.wait` (as the merge's launches queue behind running programs on
    the chip): the dispatch lasts the 0.5 s, its host time does not."""
    win, a = _served(trace_root, blocked_s=0.5)
    (d,) = a.dispatches
    assert d.end - d.start >= 0.5e9
    assert _read("dispatch_host_ms", win, 4) < 0.1 * 500


def test_report_lines(trace_root):
    path = _unpack(OLD, trace_root)
    win = devtrace.reduce(devtrace.load(path))
    lines = scopes.report(scopes.analyse_trace(path, win), win)
    assert lines[0].startswith("scopes: device time by scope")
    gaps = next(x for x in lines if x.startswith("scopes: idle_gaps_by_span "))
    got = json.loads(gaps.split(" ", 2)[2])
    assert len(got) == 10 and {k for k, _ in got} == {scopes.NO_SPAN}
    assert re.search(r"unattributed 100\.000% of busy time", "\n".join(lines))


def test_split_of_a_trace_recorded_on_the_chip_with_the_marks(trace_root):
    """A 2 s `adult-closed` window traced on one TPU v5e with the program's
    spans and scopes: every op has one scope, the split adds up to the busy
    time, the compaction's two scatter fusions are `genie.compact`, under 5%
    of the busy time is unattributed, and the readers read it."""
    path = _unpack(NEW, trace_root)
    win = devtrace.reduce(devtrace.load(path))
    a = scopes.analyse(win)
    assert a is not None and a.has_program_marks
    assert a.busy_ns == pytest.approx(win.busy_ns, rel=1e-3)
    assert sum(a.source_ns.values()) == pytest.approx(a.busy_ns, rel=1e-9)
    assert a.scope_ns.get(scopes.UNATTRIBUTED, 0.0) < 0.05 * win.busy_ns

    meta = xspace.read(path, devtrace.DEVICE_PLANE)
    (prog_name,) = [n for n in meta.programs if n.startswith("jit_run(")]
    (plane,) = meta.ops
    pid = scopes._program_id(prog_name)
    prog = scopes._Program(meta.programs[prog_name], {
        scopes._instruction_name(o): st for o, found in meta.ops[plane].items()
        for st in found if st.get("program_id") == pid})
    scatters = [n for n, i in meta.programs[prog_name].instructions.items()
                if i.opcode == "fusion" and any(
                    c.opcode == "scatter"
                    for c in meta.programs[prog_name].calls(i))]
    assert len(scatters) >= 2
    assert {prog.scope(n) for n in scatters} == {"genie.compact"}
    # the kernels carry their own tf_op (step 1)
    assert prog.scope("range_count.1") == "genie.match"
    assert prog.scope("cpq_hist.1") == "genie.hist"
    # the device time of the kernels lies in their scopes
    assert a.scope_ns["genie.match"] >= win.kernel_ns("range_count") > 0
    assert a.scope_ns["genie.hist"] >= win.kernel_ns("cpq_hist") > 0
    # the eager merge modules: their host span (step 4)
    assert a.scope_ns.get("genie.merge", 0.0) > 0

    rows = 128 * len(win.searches)
    assert _read("compact_us_per_query", win, rows) == pytest.approx(
        a.scope_ns["genie.compact"] * 1e-3 / rows)
    assert a.scope_ns["genie.compact"] > 0.8 * win.busy_ns
    (d,) = a.dispatches                 # one 128-row dispatch in 2 s
    assert d.stats["rows"] == d.stats["padded_rows"] == rows
    assert _read("queue_wait_ms", win, rows) == pytest.approx(
        d.stats["queue_wait_us_sum"] / d.stats["requests"] * 1e-3)
    # the dispatch thread's CPU time, not the benchmark's wrapper blocking
    # on this result, which takes most of the dispatch
    assert d.end - d.start > win.end - win.start
    assert _read("dispatch_host_ms", win, rows) == pytest.approx(
        d.stats["host_cpu_us"] * 1e-3)
    assert d.stats["host_cpu_us"] * 1e3 < 0.1 * (d.end - d.start)
    assert _read("unattributed_pct", win, rows) == pytest.approx(
        100 * a.scope_ns.get(scopes.UNATTRIBUTED, 0.0) / a.busy_ns)
    assert _read("unattributed_pct", win, rows) < 5
    assert _read("hash_us_per_query", win, rows) is None     # no hashing here

    gaps = a.idle_gaps[:10]
    assert len(gaps) == 10
    assert all(k == scopes.NO_SPAN or k.startswith("genie.") for k, _ in gaps)
    assert [round(v, 9) for _, v in gaps] == [
        round(v, 9) for _, v in devtrace.breakdown(win)["idle_gaps"]]


def test_cli_windows_a_live_front_ends_trace_by_its_dispatches(monkeypatch,
                                                              capsys):
    """A trace without the benchmark's `bench.search` spans (an operator's,
    of a live front end): `python3 bench/scopes.py <trace>` takes the window
    from the program's `genie.dispatch` spans."""
    monkeypatch.setattr(devtrace, "SEARCH_SPAN", "no.such.span")
    assert scopes.main([os.path.join(DATA, NEW)]) == 0
    out = capsys.readouterr().out
    assert out.startswith("scopes: device time by scope, 1 searches")
    assert re.search(r"genie\.compact +\d+\.\d+ s", out)
