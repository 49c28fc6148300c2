"""Autotuner contract suite: tuned plans are a pure perf knob.

Three properties pin the autotuner (core/autotune.py) to safety:

  * Parity -- a plan carrying adversarial-but-valid tile_overrides returns
    bit-identical ids/counts to the default plan, for every engine x
    signature layout x selection method.  Tile sizes change the grid, never
    the math (Theorem 3.1 count-bound semantics are tile-agnostic).
  * Fallback -- a missing/corrupt/foreign-machine cache silently keeps the
    defaults: autotuning is an accelerator, never a correctness dependency.
  * Keying -- tile_overrides are part of the QueryPlan hash (distinct
    executables) and surface in describe() (genielint retrace-hygiene).
"""
import json
import os

import numpy as np
import pytest

from repro.core import GenieIndex, SegmentedIndex, autotune, cpq, engines
from repro.core import plan as plan_lib
from repro.core.types import Engine, SearchParams, SignatureLayout, TopKMethod

ALL_ENGINES = sorted(engines.available(), key=lambda e: e.value)
PACKED_ENGINES = [e for e in ALL_ENGINES if engines.get(e).supports_packed]
ALL_METHODS = [TopKMethod.CPQ, TopKMethod.SPQ, TopKMethod.SORT]

# adversarial-but-valid: every knob at its alignment floor forces the
# largest possible grid (most steps, most edge tiles) the kernels support
FLOOR_TILES = {"tile_q": 8, "tile_n": 128, "tile_v": 128, "tile_m": 128}
# and oversized knobs clamp down to one big step via pick_tile
HUGE_TILES = {"tile_q": 4096, "tile_n": 65536, "tile_v": 8192, "tile_m": 8192}


def _case(engine: Engine, n=101, q=4, seed=0):
    model = engines.get(engine)
    raw, queries, mc = model.example(np.random.default_rng(seed), n, q)
    data = model.prepare_data(raw)
    return model, raw, data, queries, model.resolve_max_count(data, mc)


def _assert_same(got, want, label=""):
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids)), label
    assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts)), label


# ---------------------------------------------------------------------------
# Parity: adversarial tiles, engine x layout x method
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("method", ALL_METHODS)
@pytest.mark.parametrize("tiles", [FLOOR_TILES, HUGE_TILES],
                         ids=["floor", "huge"])
def test_tiled_plan_parity_wide(engine, method, tiles):
    """Kernel plans with floor/huge tile overrides reproduce the sort-select
    oracle bit-for-bit on the WIDE layout."""
    k = 9
    model, raw, data, queries, mc = _case(engine)
    q_wide = model.prepare_queries(queries)
    oracle = cpq.sort_select(model.reference(data, q_wide),
                             SearchParams(k=k, max_count=mc))
    plan = plan_lib.plan_search(model, k, mc, part_rows=(data.shape[0],),
                                method=method, use_kernel=True,
                                tile_overrides=tiles)
    assert dict(plan.tile_overrides)  # engine-relevant knobs survived
    got = plan_lib.execute(plan, data, q_wide)
    _assert_same(got, oracle, f"{engine.value} {method.value} {tiles}")


@pytest.mark.parametrize("engine", PACKED_ENGINES)
@pytest.mark.parametrize("method", ALL_METHODS)
def test_tiled_plan_parity_packed(engine, method):
    """PACKED plans (fused kernel path included) are tile-agnostic too."""
    k = 7
    model, raw, data, queries, mc = _case(engine, n=130)
    packed = model.pack_data(data)
    q_packed = model.prepare_queries_for(queries, SignatureLayout.PACKED)
    oracle = cpq.sort_select(model.reference(data, model.prepare_queries(queries)),
                             SearchParams(k=k, max_count=mc))
    default = plan_lib.plan_search(model, k, mc, part_rows=(data.shape[0],),
                                   method=method, use_kernel=True,
                                   signature_layout="packed")
    tiled = plan_lib.plan_search(model, k, mc, part_rows=(data.shape[0],),
                                 method=method, use_kernel=True,
                                 signature_layout="packed",
                                 tile_overrides=FLOOR_TILES)
    _assert_same(plan_lib.execute(default, packed, q_packed), oracle,
                 f"{engine.value} {method.value} packed default")
    _assert_same(plan_lib.execute(tiled, packed, q_packed), oracle,
                 f"{engine.value} {method.value} packed tiled")


def test_segmented_tiles_and_layout_switch_parity(tmp_path):
    """Tile overrides ride the host part loop, and a cache file written when
    entries still carried a part-layout choice ("layout") loads -- the stale
    key is dropped -- and its tiles serve identical results."""
    model, raw, data, queries, mc = _case(Engine.EQ, n=150)
    seg = SegmentedIndex(engine=Engine.EQ, max_count=mc, use_kernel=True)
    for a, b in ((0, 40), (40, 41), (41, 150)):
        seg.add(raw[a:b])
    base = seg.search(queries, k=5)
    _assert_same(seg.search(queries, k=5, tile_overrides={"tile_n": 128}),
                 base, "segmented tiled")

    old = dict(engine="eq", signature_layout="wide",
               n_bucket=autotune.shape_bucket(seg.n_objects),
               w_bucket=autotune.shape_bucket(raw.shape[1]),
               tile_overrides={"tile_n": 128}, layout="multiload_host",
               speedup=1.3)
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({
        "version": autotune.CACHE_VERSION,
        "fingerprint": autotune.hardware_fingerprint(),
        "entries": {autotune.cache_key("eq", "wide", old["n_bucket"],
                                       old["w_bucket"]): old}}))
    cache = autotune.AutotuneCache(path)
    (entry,) = cache.entries.values()
    assert not hasattr(entry, "layout")
    assert entry.tile_overrides == (("tile_n", 128),) and entry.speedup == 1.3
    plan = plan_lib.plan_search(Engine.EQ, 5, mc,
                                layout=plan_lib.Layout.SEGMENTED,
                                part_rows=tuple(seg.segment_rows),
                                autotune=cache, tune_width=raw.shape[1])
    assert dict(plan.tile_overrides) == {"tile_n": 128}
    _assert_same(seg.search(queries, k=5, autotune=cache), base,
                 "tuned tiles from an old cache file")


def test_genie_index_autotune_parity():
    """GenieIndex.search(autotune=cache) applies the cached tiles and still
    matches the untuned search exactly."""
    model, raw, data, queries, mc = _case(Engine.COSINE, n=140)
    idx = GenieIndex.build(Engine.COSINE, raw, max_count=mc, use_kernel=True)
    base = idx.search(queries, k=6)
    cache = autotune.AutotuneCache()
    cache.put(autotune.TunedEntry(
        engine="cosine", signature_layout="wide",
        n_bucket=autotune.shape_bucket(idx.stats.n_objects),
        w_bucket=autotune.shape_bucket(data.shape[1]),
        tile_overrides=(("tile_n", 128), ("tile_q", 8)), speedup=1.2))
    _assert_same(idx.search(queries, k=6, autotune=cache), base)


# ---------------------------------------------------------------------------
# Plan cache keying + describe()
# ---------------------------------------------------------------------------

def test_tile_overrides_key_the_plan_cache():
    """Plans differing only in tile_overrides are distinct cache keys with
    distinct executables -- and equal overrides (any spelling) are one key."""
    mk = lambda tiles: plan_lib.plan_search(
        Engine.EQ, 5, 16, part_rows=(64,), use_kernel=True,
        tile_overrides=tiles)
    a, b = mk(None), mk({"tile_n": 256})
    assert a != b and hash(a) != hash(b)
    c = mk([("tile_n", 256)])                 # pair-list spelling, same knobs
    assert b == c and hash(b) == hash(c)
    assert b.describe()["tile_overrides"] == {"tile_n": 256}

    plan_lib.clear_plan_cache()
    model, raw, data, queries, mc = _case(Engine.EQ, n=64)
    q_wide = model.prepare_queries(queries)
    p1 = plan_lib.plan_search(model, 5, mc, part_rows=(64,), use_kernel=True)
    p2 = plan_lib.plan_search(model, 5, mc, part_rows=(64,), use_kernel=True,
                              tile_overrides={"tile_n": 256})
    _assert_same(plan_lib.execute(p2, data, q_wide),
                 plan_lib.execute(p1, data, q_wide))
    assert plan_lib.trace_count(p1) == 1
    assert plan_lib.trace_count(p2) == 1      # separate executable, traced once


# ---------------------------------------------------------------------------
# Validation: pick_tile + plan_search rejections
# ---------------------------------------------------------------------------

def test_pick_tile_validates_inputs():
    from repro.kernels.common import pick_tile

    assert pick_tile(100, 256, 8) in range(8, 105)
    with pytest.raises(ValueError, match="tile_n"):
        pick_tile(100, 256, 0, knob="tile_n")
    with pytest.raises(ValueError, match="tile_q"):
        pick_tile(100, 4, 8, knob="tile_q")   # preferred below align


def test_plan_search_rejects_bad_tiles():
    with pytest.raises(ValueError, match="unknown tile knob"):
        plan_lib.plan_search(Engine.EQ, 3, 16, tile_overrides={"tile_x": 8})
    with pytest.raises(ValueError, match="alignment floor"):
        plan_lib.plan_search(Engine.EQ, 3, 16, tile_overrides={"tile_n": 64})
    with pytest.raises(ValueError, match="use_kernel=False"):
        plan_lib.plan_search(Engine.EQ, 3, 16, use_kernel=False,
                             tile_overrides={"tile_n": 128})
    with pytest.raises(ValueError, match="raw match"):
        plan_lib.plan_search(lambda d, q: None, 3, 16,
                             tile_overrides={"tile_n": 128})
    with pytest.raises(ValueError, match="duplicate"):
        engines.canonical_tile_overrides([("tile_n", 128), ("tile_n", 256)])


# ---------------------------------------------------------------------------
# Cache: round-trip, fingerprint gate, corrupt-file fallback, consult
# ---------------------------------------------------------------------------

def _entry(**kw):
    base = dict(engine="eq", signature_layout="wide", n_bucket=128,
                w_bucket=64, tile_overrides=(("tile_n", 512),), speedup=1.4)
    base.update(kw)
    return autotune.TunedEntry(**base)


def test_cache_roundtrip_and_fingerprint_gate(tmp_path):
    path = tmp_path / "autotune.json"
    cache = autotune.AutotuneCache(path)
    cache.put(_entry())
    cache.save()

    reloaded = autotune.AutotuneCache(path)
    assert reloaded.entries == cache.entries
    hit = reloaded.lookup("eq", "wide", n=100, width=60)  # buckets to 128|64
    assert hit == _entry()
    assert reloaded.lookup("eq", "wide", n=100) == _entry()  # width-agnostic
    assert reloaded.lookup("eq", "wide", n=5000) is None     # other bucket
    assert reloaded.lookup("eq", "wide", n=None) is None

    foreign = autotune.AutotuneCache(path)
    foreign.fingerprint = {"platform": "not-this-machine"}
    assert foreign.lookup("eq", "wide", n=100, width=60) is None


def test_corrupt_cache_degrades_to_defaults(tmp_path):
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    cache = autotune.AutotuneCache(path)
    assert cache.entries == {}
    path.write_text(json.dumps({"version": 99, "fingerprint": {},
                                "entries": {"x": {}}}))
    assert autotune.AutotuneCache(path).entries == {}  # version gate


def test_consult_resolves_specs(tmp_path, monkeypatch):
    assert autotune.consult(None, engine="eq", signature_layout="wide",
                            n=100) is None
    assert autotune.consult(False, engine="eq", signature_layout="wide",
                            n=100) is None
    path = tmp_path / "c.json"
    cache = autotune.AutotuneCache(path)
    cache.put(_entry())
    cache.save()
    autotune.clear_resolved_caches()
    got = autotune.consult(str(path), engine="eq", signature_layout="wide",
                           n=100, width=60)
    assert got == _entry()
    # spec=True routes through GENIE_AUTOTUNE_CACHE
    monkeypatch.setenv("GENIE_AUTOTUNE_CACHE", str(path))
    autotune.clear_resolved_caches()
    assert autotune.consult(True, engine="eq", signature_layout="wide",
                            n=100, width=60) == _entry()
    autotune.clear_resolved_caches()


def test_plan_search_applies_cache_and_explicit_args_win():
    cache = autotune.AutotuneCache()
    cache.put(_entry(tile_overrides=(("tile_n", 512),), candidate_cap=32))
    tuned = plan_lib.plan_search(Engine.EQ, 3, 16, part_rows=(100,),
                                 use_kernel=True, autotune=cache,
                                 tune_width=60)
    assert dict(tuned.tile_overrides) == {"tile_n": 512}
    assert tuned.params.candidate_cap == 32
    explicit = plan_lib.plan_search(Engine.EQ, 3, 16, part_rows=(100,),
                                    use_kernel=True, autotune=cache,
                                    tune_width=60, candidate_cap=48,
                                    tile_overrides={"tile_n": 256})
    assert dict(explicit.tile_overrides) == {"tile_n": 256}
    assert explicit.params.candidate_cap == 48
    # kernel-path knobs never leak onto the XLA path
    xla = plan_lib.plan_search(Engine.EQ, 3, 16, part_rows=(100,),
                               use_kernel=False, autotune=cache,
                               tune_width=60)
    assert xla.tile_overrides == ()
    assert xla.params.candidate_cap == 32


# ---------------------------------------------------------------------------
# tune() end-to-end (tiny budget) + service.tune smoke
# ---------------------------------------------------------------------------

def test_tune_end_to_end_parity_and_cache():
    """A real (tiny-budget) tuning run: the entry lands in the cache, keys
    the shape correctly, and searching through it changes nothing."""
    model, raw, data, queries, mc = _case(Engine.EQ, n=256, q=8)
    cache = autotune.AutotuneCache()
    entry = autotune.tune(model, raw, queries, 5, mc, budget=2, repeats=1,
                          cache=cache, save=False)
    assert entry.key() in cache.entries
    assert entry.n_bucket == autotune.shape_bucket(256)
    assert entry.speedup >= 1.0          # tuned never records a regression

    idx = GenieIndex.build(Engine.EQ, raw, max_count=mc, use_kernel=True)
    _assert_same(idx.search(queries, k=5, autotune=cache),
                 idx.search(queries, k=5))


def test_tune_prepared_requires_max_count():
    model, raw, data, queries, mc = _case(Engine.EQ, n=64)
    with pytest.raises(ValueError, match="max_count"):
        autotune.tune(model, data, model.prepare_queries(queries), 3,
                      None, prepared=True)


def test_service_tune_smoke():
    """RetrievalService.tune wires the serving corpus into the tuner and
    installs the winning cache; results stay bit-identical."""
    from repro.serve.retrieval import RetrievalService

    rng = np.random.default_rng(11)
    pts = rng.standard_normal((150, 16)).astype(np.float32)
    q = pts[40:45] + 0.01
    svc = RetrievalService(embed_fn=lambda x: np.asarray(x), m_override=32)
    svc.add(list(range(150)), embeddings=pts)
    base, _ = svc.search(None, k=4, embeddings=q)
    entry = svc.tune(None, k=4, embeddings=q, budget=2, repeats=1,
                     save=False)
    assert isinstance(entry, autotune.TunedEntry)
    assert svc.autotune is not None
    tuned, _ = svc.search(None, k=4, embeddings=q)
    _assert_same(tuned, base)
