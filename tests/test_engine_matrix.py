"""Engine conformance matrix: every registered engine x {reference, kernel}
x {search, segmented, distributed} must return identical top-k ids/counts.

This is the standing acceptance harness for the registry's genericity claim:
a new engine registered with an `example` generator (MatchModel.example) gets
the full parity matrix, the pad-value conformance check, and the tie-break
consistency sweep for free -- no new test code.  `test_matrix_covers_every_
engine` fails loudly if an engine is registered without conformance data.

All paths share select_topk's deterministic (count desc, id asc) ordering, so
ids are compared exactly, not just counts.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, cpq, engines, select
from repro.core import plan as plan_lib
from repro.core.segments import even_segments
from repro.core.types import Engine, SearchParams, TopKMethod

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MATRIX_ENGINES = sorted(engines.available(), key=lambda e: e.value)


def _example(engine: Engine, seed: int = 0, n: int = 96, q: int = 4):
    """(model, prepared data, raw queries, resolved max_count) from the
    engine's own conformance generator."""
    model = engines.get(engine)
    assert model.example is not None, f"{engine.value}: no MatchModel.example"
    raw, queries, mc = model.example(np.random.default_rng(seed), n, q)
    data = model.prepare_data(raw)
    return model, data, queries, model.resolve_max_count(data, mc)


def _assert_same_topk(got, want, label=""):
    assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts)), label
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids)), label


def test_matrix_covers_every_engine():
    """Every registered engine must ship conformance data -- future engines
    cannot silently opt out of the matrix."""
    missing = [e.value for e in engines.available() if engines.get(e).example is None]
    assert not missing, f"engines without MatchModel.example: {missing}"
    assert {Engine.TANIMOTO, Engine.COSINE} <= set(engines.available())


@pytest.mark.parametrize("engine", MATRIX_ENGINES)
def test_matrix_search_kernel_reference_parity(engine):
    """Single-device search: kernel and reference paths agree with the sort
    oracle on ids and counts."""
    model, data, queries, mc = _example(engine)
    oracle = cpq.sort_select(
        model.match_counts(data, queries, use_kernel=False),
        SearchParams(k=9, max_count=mc),
    )
    for use_kernel in (False, True):
        idx = GenieIndex.build(engine, data, max_count=mc, use_kernel=use_kernel)
        got = idx.search(queries, k=9)
        _assert_same_topk(got, oracle, f"{engine.value} kernel={use_kernel}")


@pytest.mark.parametrize("engine", MATRIX_ENGINES)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_matrix_multiload_parity(engine, use_kernel):
    """SEGMENTED streaming over an even split into 1, 3 and 5 parts (uneven
    row counts, both match paths) == the MONOLITHIC search."""
    model, data, queries, mc = _example(engine, n=97)   # uneven on purpose
    idx = GenieIndex.build(engine, data, max_count=mc, use_kernel=use_kernel)
    full = idx.search(queries, k=6)
    for n_parts in (1, 3, 5):
        seg = SegmentedIndex(engine=engine, max_count=mc, use_kernel=use_kernel)
        for part in np.split(np.asarray(data),
                             np.cumsum(even_segments(97, n_parts))[:-1]):
            seg.add(part)
        _assert_same_topk(seg.search(queries, k=6), full,
                          f"{engine.value} kernel={use_kernel} parts={n_parts}")


def test_matrix_distributed_parity():
    """Every engine x {reference, kernel} through a DISTRIBUTED plan (8
    forced CPU devices via subprocess: jax locks the device count at first
    init).  use_kernel=True runs the Pallas kernels *inside* shard_map."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed, engines, cpq
        from repro.core import plan as plan_lib
        from repro.core.types import SearchParams
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_mesh((2, 4), ('data', 'model'))
        for eng in sorted(engines.available(), key=lambda e: e.value):
            model = engines.get(eng)
            raw, rawq, mc = model.example(np.random.default_rng(0), 128, 4)
            data = model.prepare_data(raw)
            queries = model.prepare_queries(rawq)
            mx = model.resolve_max_count(data, mc)
            dd = jax.device_put(data, distributed.data_sharding(mesh))
            qq = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, distributed.replicated(mesh, 2)), queries)
            want = cpq.sort_select(model.reference(data, queries),
                                   SearchParams(k=7, max_count=mx))
            for use_kernel in (False, True):
                plan = plan_lib.plan_search(
                    eng, 7, mx, layout=plan_lib.Layout.DISTRIBUTED,
                    use_kernel=use_kernel, mesh_axes=tuple(mesh.axis_names))
                res = plan_lib.execute(plan, dd, qq, mesh=mesh)
                assert np.array_equal(np.asarray(res.counts), np.asarray(want.counts)), \\
                    (eng, use_kernel)
                assert np.array_equal(np.asarray(res.ids), np.asarray(want.ids)), \\
                    (eng, use_kernel)
        print('distributed matrix parity OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "distributed matrix parity OK" in out.stdout


# ---------------------------------------------------------------------------
# Pad-value conformance (the pad_to_multiple fill contract)
# ---------------------------------------------------------------------------

def _padded_parts_search(idx: GenieIndex, queries, k: int, n_parts: int):
    """Search `idx`'s stored rows padded with the engine fill
    (`pad_to_multiple`) up to `n_parts` equal SEGMENTED parts, with
    n_objects marking the pad tail."""
    padded, n = plan_lib.pad_to_multiple(
        np.asarray(idx.data), n_parts,
        idx.model.pad_value_for(idx.signature_layout))
    per = padded.shape[0] // n_parts
    plan = plan_lib.plan_search(
        idx.engine, k, idx.max_count, layout=plan_lib.Layout.SEGMENTED,
        part_rows=(per,) * n_parts, n_objects=n, use_kernel=idx.use_kernel,
        signature_layout=idx.signature_layout)
    parts = [padded[i * per:(i + 1) * per] for i in range(n_parts)]
    return plan_lib.execute(plan, parts, idx.prepare_queries(queries))


@pytest.mark.parametrize("engine", MATRIX_ENGINES)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_matrix_pad_rows_never_reach_topk(engine, use_kernel):
    """Engine-filled pad rows can never enter the top-k, even when the last
    part is almost entirely padding and k exceeds its real rows.  Pad columns
    are masked to count -1 before per-part selection, so the guarantee holds
    for every engine regardless of how its pad_value scores (COSINE's zero
    fill, for instance, scores V/2 against any query)."""
    n = 50
    model, data, queries, mc = _example(engine, n=n)
    idx = GenieIndex.build(engine, data, max_count=mc, use_kernel=use_kernel)
    # 8 parts of 7 -> last part has 1 real row + 6 pad rows; k=10 > real rows
    res = _padded_parts_search(idx, queries, k=10, n_parts=8)
    ids = np.asarray(res.ids)
    counts = np.asarray(res.counts)
    assert ids.max() < n, f"{engine.value}: pad id {ids.max()} in top-k"
    assert np.all(counts[ids < 0] == -1)            # empty slots stay sentinel
    full = idx.search(queries, k=10)
    _assert_same_topk(res, full, engine.value)


@pytest.mark.parametrize("engine", MATRIX_ENGINES)
def test_matrix_pad_value_representable(engine):
    """The declared pad_value must survive the round-trip into the prepared
    data dtype (the fill `pad_to_multiple` performs)."""
    model, data, _, _ = _example(engine, n=8)
    padded, n = plan_lib.pad_to_multiple(np.asarray(data), 10, model.pad_value)
    assert n == 8 and padded.shape == (10,) + data.shape[1:]
    fill = padded[n:]
    assert fill.dtype == data.dtype
    assert bool(np.all(fill == np.asarray(model.pad_value).astype(data.dtype)))
    assert np.array_equal(padded[:n], np.asarray(data))


# ---------------------------------------------------------------------------
# PACKED signature layout (core/packing.py): bit-for-bit parity with WIDE
# ---------------------------------------------------------------------------

PACKED_ENGINES = [e for e in MATRIX_ENGINES if engines.get(e).supports_packed]
WIDE_ONLY_ENGINES = [e for e in MATRIX_ENGINES if not engines.get(e).supports_packed]


def test_matrix_packed_covers_expected_engines():
    assert set(PACKED_ENGINES) == {Engine.TANIMOTO, Engine.COSINE}


@pytest.mark.parametrize("engine", PACKED_ENGINES)
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("method", [TopKMethod.CPQ, TopKMethod.SPQ, TopKMethod.SORT])
def test_matrix_packed_wide_parity(engine, use_kernel, method):
    """PACKED search returns bit-for-bit the WIDE ids and counts for every
    selection method and both match paths (use_kernel=True with PACKED takes
    the fused match->count->local-top-k kernel)."""
    model, data, queries, mc = _example(engine, n=97)   # V=32 words + ragged n
    wide = GenieIndex.build(engine, data, max_count=mc, use_kernel=use_kernel)
    packed = GenieIndex.build(engine, data, max_count=mc, use_kernel=use_kernel,
                              signature_layout="packed")
    want = wide.search(queries, k=9, method=method)
    got = packed.search(queries, k=9, method=method)
    _assert_same_topk(got, want,
                      f"{engine.value} kernel={use_kernel} {method.value}")


@pytest.mark.parametrize("engine", PACKED_ENGINES)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_matrix_packed_pad_rows_never_reach_topk(engine, use_kernel):
    """The packed pad fill (0 words / 255 bytes) can never enter the
    top-k -- same contract as the WIDE pad sweep above."""
    n = 50
    model, data, queries, mc = _example(engine, n=n)
    idx = GenieIndex.build(engine, data, max_count=mc, use_kernel=use_kernel,
                           signature_layout="packed")
    res = _padded_parts_search(idx, queries, k=10, n_parts=8)
    ids = np.asarray(res.ids)
    counts = np.asarray(res.counts)
    assert ids.max() < n, f"{engine.value}: pad id {ids.max()} in top-k"
    assert np.all(counts[ids < 0] == -1)
    full = idx.search(queries, k=10)
    _assert_same_topk(res, full, engine.value)


@pytest.mark.parametrize("engine", WIDE_ONLY_ENGINES)
def test_matrix_packed_rejects_unsupported_engines(engine):
    """Engines without a packed format fail loudly at build, not at search."""
    model, data, _, mc = _example(engine, n=8)
    with pytest.raises(ValueError, match="no packed signature format"):
        GenieIndex.build(engine, data, max_count=mc, signature_layout="packed")


# ---------------------------------------------------------------------------
# Tie-break consistency across selection methods
# ---------------------------------------------------------------------------

def _degenerate_counts():
    rng = np.random.default_rng(7)
    q, n = 3, 64
    return {
        "all-equal": np.full((q, n), 5, dtype=np.int32),
        "two-valued": rng.choice([2, 9], size=(q, n)).astype(np.int32),
        "k-boundary-tie": np.concatenate(       # k=5 cuts through the 5-ties
            [np.full((q, 3), 9, np.int32), np.full((q, n - 3), 5, np.int32)], axis=1),
        "all-zero": np.zeros((q, n), dtype=np.int32),
    }


@pytest.mark.parametrize("name", sorted(_degenerate_counts()))
@pytest.mark.parametrize("method", [TopKMethod.CPQ, TopKMethod.SPQ, TopKMethod.SORT])
def test_matrix_tie_break_consistency(name, method):
    """CPQ, SPQ, and sort agree *exactly* (ids included) on count-degenerate
    inputs: every path orders by (count desc, id asc) -- CPQ/SPQ fill their
    candidate buffers in id order and break count ties with a stable sort,
    lax.top_k returns the lowest index among ties.  Divergence here would
    make segmented/distributed results depend on the selection method."""
    counts = jnp.asarray(_degenerate_counts()[name])
    params = SearchParams(k=5, max_count=10, method=method)
    got = select.select_topk(counts, params)
    want = cpq.sort_select(counts, SearchParams(k=5, max_count=10))
    assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts)), name
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids)), name
    # the k-th count (Theorem 3.1's AT-1) must agree across methods too
    assert np.array_equal(np.asarray(got.counts[:, -1]),
                          np.asarray(want.counts[:, -1])), name
