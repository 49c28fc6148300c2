"""MatchModel registry round-trip: every engine through every search path.

The acceptance bar for the unified-engine refactor: all six engines (EQ,
RANGE, MINSUM, IP, TANIMOTO, COSINE) resolve through the registry with
kernel-vs-reference parity, the count-dtype policy is engine-uniform, and
segmented (streamed) and distributed searches agree with single-device
results.  The
exhaustive engine x path x match-impl sweep lives in test_engine_matrix.py.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, cpq, engines
from repro.core.segments import even_segments
from repro.core.types import Engine, SearchParams, TopKMethod

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _case(engine: Engine, rng, n=96, q=4):
    """(raw data, raw queries, max_count) for one engine -- the descriptor's
    own conformance generator (MatchModel.example), so there is exactly one
    per-engine data recipe in the system."""
    return engines.get(engine).example(rng, n, q)


ALL_ENGINES = [Engine.EQ, Engine.RANGE, Engine.MINSUM, Engine.IP,
               Engine.TANIMOTO, Engine.COSINE]


def test_all_engines_registered():
    assert set(engines.available()) >= set(ALL_ENGINES)
    for eng in ALL_ENGINES:
        model = engines.get(eng)
        assert model.engine == eng
        assert engines.get(eng.value) is model          # string lookup
        assert engines.get(model) is model              # idempotent


def test_unknown_engine_raises():
    with pytest.raises(ValueError):
        engines.get("no-such-engine")


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_kernel_matches_reference(engine, rng):
    data, queries, mc = _case(engine, rng)
    model = engines.get(engine)
    ref = np.asarray(model.match_counts(model.prepare_data(data), queries, use_kernel=False))
    ker = np.asarray(model.match_counts(model.prepare_data(data), queries, use_kernel=True))
    assert np.array_equal(ref, ker)


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_generic_build_equals_named_builder(engine, rng):
    data, queries, mc = _case(engine, rng)
    generic = GenieIndex.build(engine, data, max_count=mc, use_kernel=False)
    named = {
        Engine.EQ: lambda: GenieIndex.build_lsh(data, use_kernel=False),
        Engine.RANGE: lambda: GenieIndex.build_relational(data, use_kernel=False),
        Engine.MINSUM: lambda: GenieIndex.build_minsum(data, max_count=mc, use_kernel=False),
        Engine.IP: lambda: GenieIndex.build_ip(data, max_count=mc, use_kernel=False),
        Engine.TANIMOTO: lambda: GenieIndex.build_tanimoto(data, use_kernel=False),
        Engine.COSINE: lambda: GenieIndex.build_cosine(data, use_kernel=False),
    }[engine]()
    assert named.engine == generic.engine == engine
    assert named.max_count == generic.max_count
    assert named.stats.n_objects == generic.stats.n_objects
    assert named.stats.total_postings == generic.stats.total_postings
    a = generic.search(queries, k=7)
    b = named.search(queries, k=7)
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_build_requires_max_count_when_underivable(rng):
    data, _, _ = _case(Engine.MINSUM, rng)
    with pytest.raises(ValueError, match="max_count"):
        GenieIndex.build(Engine.MINSUM, data)


def test_count_dtype_policy():
    model = engines.get(Engine.EQ)
    assert model.count_dtype(100) == jnp.int8
    assert model.count_dtype(1000) == jnp.int16
    assert model.count_dtype(10**6) == jnp.int32


@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("method", [TopKMethod.CPQ, TopKMethod.SPQ, TopKMethod.SORT])
def test_search_methods_agree_per_engine(engine, method, rng):
    data, queries, mc = _case(engine, rng)
    idx = GenieIndex.build(engine, data, max_count=mc, use_kernel=False)
    got = idx.search(queries, k=9, method=method)
    want = cpq.sort_select(idx.match_counts(queries),
                           SearchParams(k=9, max_count=idx.max_count))
    assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts))


@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("n_parts", [1, 3, 5])
def test_multiload_parity_all_engines(engine, n_parts, rng):
    """Every registered engine streams through the SEGMENTED host loop over
    an even split into 1, 3 and 5 parts, uneven row counts included."""
    data, queries, mc = _case(engine, rng, n=97)   # uneven on purpose
    idx = GenieIndex.build(engine, data, max_count=mc, use_kernel=False)
    full = idx.search(queries, k=6)
    seg = SegmentedIndex(engine=engine, max_count=idx.max_count,
                         use_kernel=False)
    for part in np.split(np.asarray(data),
                         np.cumsum(even_segments(97, n_parts))[:-1]):
        seg.add(part)
    part = seg.search(queries, k=6)
    assert np.array_equal(np.asarray(full.counts), np.asarray(part.counts)), engine
    assert np.array_equal(np.asarray(full.ids), np.asarray(part.ids)), engine


def test_distributed_parity_all_engines():
    """All four engines through a DISTRIBUTED plan (8 forced CPU devices via
    subprocess: jax locks the device count at first init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed, engines, cpq
        from repro.core import plan as plan_lib
        from repro.core.types import Engine, SearchParams
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_mesh((2, 4), ('data', 'model'))
        rng = np.random.default_rng(0)
        cases = {
            Engine.EQ: (rng.integers(0, 6, (128, 16)).astype(np.int32),
                        jnp.asarray(rng.integers(0, 6, (4, 16)).astype(np.int32)), 16),
            Engine.MINSUM: (rng.integers(0, 3, (128, 32)).astype(np.int32),
                            jnp.asarray(rng.integers(0, 3, (4, 32)).astype(np.int32)), 96),
            Engine.IP: (rng.integers(0, 2, (128, 32)).astype(np.int32),
                        jnp.asarray(rng.integers(0, 2, (4, 32)).astype(np.int32)), 32),
        }
        lo = rng.integers(0, 5, (4, 6)).astype(np.int32)
        cases[Engine.RANGE] = (rng.integers(0, 10, (128, 6)).astype(np.int32),
                               (jnp.asarray(lo), jnp.asarray(lo + 3)), 6)
        for eng, (data, queries, mx) in cases.items():
            params = SearchParams(k=7, max_count=mx)
            plan = plan_lib.plan_search(
                eng, 7, mx, layout=plan_lib.Layout.DISTRIBUTED,
                mesh_axes=tuple(mesh.axis_names))
            dd = jax.device_put(data, distributed.data_sharding(mesh))
            qq = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, distributed.replicated(mesh, 2)), queries)
            res = plan_lib.execute(plan, dd, qq, mesh=mesh)
            counts = engines.get(eng).match_fn(False)(jnp.asarray(data), queries)
            want = cpq.sort_select(counts, params)
            assert np.array_equal(np.asarray(res.counts), np.asarray(want.counts)), eng
        print('distributed registry parity OK')
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "distributed registry parity OK" in out.stdout


def test_retrieval_service_search_before_add_raises(rng):
    """Regression: search() on an empty service raises ValueError (a bare
    assert would vanish under python -O) naming the service state."""
    from repro.serve.retrieval import RetrievalService

    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=16)
    with pytest.raises(ValueError, match=r"RetrievalService.*empty.*add\(\)"):
        svc.search(None, k=1, embeddings=rng.standard_normal((2, 8)).astype(np.float32))


@pytest.mark.parametrize("scheme,engine", [("simhash", Engine.COSINE),
                                           ("minhash", Engine.TANIMOTO),
                                           ("e2lsh", Engine.EQ)])
def test_retrieval_service_scheme_selects_engine(scheme, engine, rng):
    """Selecting an LSH scheme by name selects its paired match engine and
    similarity MLE end-to-end."""
    from repro.serve.retrieval import RetrievalService

    pts = rng.standard_normal((150, 16)).astype(np.float32)
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), scheme=scheme,
                           m_override=128)
    svc.add(list(range(150)), embeddings=pts)
    assert svc._index.engine == engine
    res, sims = svc.search(None, k=3, embeddings=pts[40:45] + 0.01)
    assert np.array_equal(np.asarray(res.ids)[:, 0], np.arange(40, 45))
    assert sims.shape == (5, 3)
    # self-similarity estimate must top each row and stay in the measure range
    assert np.all(sims[:, 0] + 1e-9 >= sims[:, 1:].max(axis=-1))
    assert sims.min() >= -1.0 and sims.max() <= 1.0


def test_retrieval_service_incremental_add(rng):
    """add() appends to the corpus instead of clobbering earlier adds."""
    from repro.serve.retrieval import RetrievalService

    pts = rng.standard_normal((120, 16)).astype(np.float32)
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=96)
    svc.add(list(range(60)), embeddings=pts[:60])
    svc.add(list(range(60, 120)), embeddings=pts[60:])
    assert len(svc) == 120
    res, _ = svc.search(None, k=1, embeddings=pts[90:95] + 0.01)
    assert np.array_equal(np.asarray(res.ids)[:, 0], np.arange(90, 95))


def test_lsh_scheme_registry():
    from repro.core import lsh

    assert set(lsh.scheme_names()) >= {"e2lsh", "rbh", "simhash", "minhash"}
    scheme = lsh.get_scheme("e2lsh")
    assert lsh.get_scheme(scheme) is scheme
    with pytest.raises(KeyError):
        lsh.get_scheme("no-such-scheme")
    # scheme -> engine pairing used by serving
    assert lsh.get_scheme("simhash").engine == Engine.COSINE
    assert lsh.get_scheme("minhash").engine == Engine.TANIMOTO
    assert lsh.get_scheme("e2lsh").engine == Engine.EQ


def test_minhash_estimate_tracks_exact_tanimoto(rng):
    """The TANIMOTO engine's collision counts converge to the exact
    sum-min/sum-max oracle (binary multisets -> set Jaccard)."""
    import jax

    from repro.core import lsh as lsh_lib
    from repro.core.match import tanimoto_exact

    vecs = (rng.random((12, 64)) < 0.4).astype(np.float32)     # binary multisets
    scheme = lsh_lib.get_scheme("minhash")
    params = scheme.make_params(jax.random.PRNGKey(0), d=64, m=2000,
                                n_buckets=1 << 20)
    sigs = scheme.hash_points(params, jnp.asarray(vecs))
    model = engines.get(Engine.TANIMOTO)
    counts = np.asarray(model.match_counts(sigs, sigs, use_kernel=False))
    est = counts / 2000.0
    exact = np.asarray(tanimoto_exact(jnp.asarray(vecs, dtype=jnp.int32),
                                      jnp.asarray(vecs, dtype=jnp.int32)))
    assert np.allclose(np.diag(exact), 1.0)
    assert np.abs(est - exact).max() < 0.05


def test_simhash_mle_cosine_inverts_counts(rng):
    """cos_hat = cos(pi(1 - c/m)) recovers the true cosine from COSINE-engine
    counts on simhash bits."""
    import jax

    from repro.core import lsh as lsh_lib
    from repro.core.lsh import simhash

    x = rng.standard_normal((8, 16)).astype(np.float32)
    scheme = lsh_lib.get_scheme("simhash")
    params = scheme.make_params(jax.random.PRNGKey(1), d=16, m=4000)
    sigs = scheme.hash_points(params, jnp.asarray(x))
    model = engines.get(Engine.COSINE)
    counts = np.asarray(model.match_counts(model.prepare_data(sigs), sigs,
                                           use_kernel=False))
    est = simhash.mle_cosine(counts, 4000)
    xn = x / np.linalg.norm(x, axis=-1, keepdims=True)
    true = xn @ xn.T
    assert np.abs(est - true).max() < 0.08
