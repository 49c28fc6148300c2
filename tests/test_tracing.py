"""The program's trace marks (runtime/tracing.py): host spans of the serving
path written under a JAX profiler session, named scopes reaching the
compiled part program's HLO, the `genie.gc` hook, and the front end's queue
wait and padding counters."""
from __future__ import annotations

import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import plan as plan_lib
from repro.core.types import Engine
from repro.runtime import tracing
from repro.serve import ServingFrontend
from repro.serve.metrics import FrontendMetrics

SCOPES = (tracing.MATCH, tracing.HIST, tracing.GATE, tracing.COMPACT,
          tracing.ORDER)


def _spans(trace_dir) -> list:
    """(thread line, name, start, end, stats) of every genie.* host event."""
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("genie."):
                    out.append((f"{plane.name}/{i}", ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


@pytest.fixture(scope="module")
def traced_dispatch(tmp_path_factory):
    """Three 1-row requests coalesced into one dispatch of a two-segment
    E2LSH tenant, under a profiler session."""
    rng = np.random.default_rng(0)
    fe = ServingFrontend(max_batch=8, max_wait_us=200_000, start=False)
    fe.create_tenant("t", embed_fn=np.asarray, scheme="e2lsh", n_buckets=67,
                     m_override=16)
    for lo, n in ((0, 300), (300, 200)):
        fe.add("t", range(lo, lo + n),
               embeddings=rng.normal(size=(n, 8)).astype(np.float32))
    q = rng.normal(size=(3, 8)).astype(np.float32)
    fe.start()
    fe.submit("t", None, k=5, embeddings=q).result()      # compile outside
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    futs = [fe.submit("t", None, k=5, embeddings=q[i:i + 1]) for i in range(3)]
    for f in futs:
        f.result(timeout=60)
    jax.profiler.stop_trace()
    fe.close()
    return [f.request_seq for f in futs], _spans(trace_dir), fe.stats()


def test_dispatch_writes_nested_spans_with_request_ids(traced_dispatch):
    seqs, spans, _ = traced_dispatch
    named = lambda n: [s for s in spans if s[1] == n]
    assert sorted(s[4]["request"] for s in named(tracing.SUBMIT)) == seqs
    (d,) = named(tracing.DISPATCH)
    assert d[4]["requests"] == 3 and d[4]["first_request"] == seqs[0]
    assert d[4]["rows"] == 3 and d[4]["padded_rows"] == 4
    assert 0 <= d[4]["host_cpu_us"] <= (d[3] - d[2]) * 1e-3
    assert 0 < d[4]["queue_wait_us_max"] <= d[4]["queue_wait_us_sum"]
    for child in (tracing.STACK, tracing.SEARCH, tracing.SCATTER):
        (c,) = named(child)
        assert _inside(c, d), child
    (search,) = named(tracing.SEARCH)
    (h,) = named(tracing.HASH)
    assert _inside(h, search) and h[4]["rows"] == 4
    parts = sorted(named(tracing.PART), key=lambda s: s[2])
    assert [(p[4]["part"], p[4]["rows"]) for p in parts] == [(0, 300), (1, 200)]
    assert all(_inside(p, search) for p in parts)
    (m,) = named(tracing.MERGE)
    assert _inside(m, search) and m[4]["parts"] == 2
    waits = named(tracing.WAIT)
    # the MLE's read of the counts inside the search, then the dispatch's
    assert len(waits) == 2 and all(_inside(w, d) for w in waits)
    assert sum(_inside(w, search) for w in waits) == 1
    assert not named(tracing.ROUTE)     # an unrouted plan


def test_front_end_counts_queue_wait_and_padding(traced_dispatch):
    _, _, st = traced_dispatch
    # the untraced 3-row request and the traced dispatch of three 1-row ones
    assert st["queries_dispatched"] == 6 and st["padded_rows"] == 8
    assert st["row_occupancy"] == pytest.approx(0.75)
    assert st["queue_wait_ms_mean"] > 0


def test_metrics_queue_wait_and_occupancy():
    m = FrontendMetrics()
    assert m.snapshot()["queue_wait_ms_mean"] == 0.0
    assert m.snapshot()["row_occupancy"] == 0.0
    m.record_queue_wait([1000.0, 3000.0])
    m.record_dispatch(2, 3, padded_rows=4)
    m.record_dispatch(1, 8)                      # no padding given: none
    snap = m.snapshot()
    assert snap["queue_wait_ms_mean"] == pytest.approx(2.0)
    assert snap["padded_rows"] == 12
    assert snap["row_occupancy"] == pytest.approx(11 / 12, abs=1e-3)


def test_part_program_hlo_carries_the_scopes():
    plan = plan_lib.plan_search(Engine.EQ, 8, 16, layout=plan_lib.Layout.SEGMENTED,
                                part_rows=(512,))
    fn = plan_lib._part_fn(plan, 512)
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = fn.lower(spec((512, 16)), spec((4, 16)), spec(()), spec(())
                    ).compile().as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope


def test_fused_part_program_hlo_carries_the_topk_scope():
    """A PACKED plan's part program runs the fused match -> count -> local
    top-k kernel in `genie.topk` and sorts its candidates in `genie.order`;
    no count matrix, so no `genie.match`."""
    plan = plan_lib.plan_search(Engine.COSINE, 8, 64,
                                layout=plan_lib.Layout.SEGMENTED,
                                part_rows=(512,), signature_layout="packed")
    fn = plan_lib._part_fn(plan, 512)
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = fn.lower(spec((512, 2)), spec((4, 2)), spec(()), spec(())
                    ).compile().as_text()
    assert f"/{tracing.TOPK}/" in text and f"/{tracing.ORDER}/" in text
    assert f"/{tracing.MATCH}/" not in text


def test_hash_scope_reaches_a_jitted_caller():
    from repro.core import lsh

    scheme = lsh.get_scheme("e2lsh")
    params = scheme.make_params(jax.random.PRNGKey(0), d=8, m=4, w=4.0,
                                sigma=1.0, n_buckets=67)
    text = jax.jit(lambda x: scheme.hash_points(params, x)).lower(
        jnp.ones((2, 8))).as_text(debug_info=True)
    assert tracing.HASH in text


def test_gc_spans_follow_the_front_end_lifetime(tmp_path):
    """Starting a front end puts the `genie.gc` hook in place, once per
    process however many start; it stays after they close."""
    fe, other = ServingFrontend(), ServingFrontend()
    assert gc.callbacks.count(tracing._gc_callback) == 1
    jax.profiler.start_trace(str(tmp_path))
    gc.collect()
    jax.profiler.stop_trace()
    fe.close()
    fe.close()                                   # idempotent
    other.close()
    assert gc.callbacks.count(tracing._gc_callback) == 1
    spans = [s for s in _spans(tmp_path) if s[1] == tracing.GC]
    assert any(s[4]["generation"] == 2 for s in spans)   # gc.collect()'s
    assert all("collected" in s[4] for s in spans)


def test_spans_cost_nothing_but_a_check_without_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with tracing.span(tracing.DISPATCH, requests=1) as s:
        s.set_metadata(rows=2)
