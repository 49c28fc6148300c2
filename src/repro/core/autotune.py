"""Hardware-aware plan autotuner: measured cost for tile/fusion/routing knobs.

GENIE's pipeline runs at the hardware roofline only when its discrete knobs
match the machine (PAPER.md section 6): kernel tile sizes (the tile_q /
tile_n / tile_v / tile_m kwargs kernels/ops.py accepts but nothing drove),
fused vs. unfused packed match, the per-part `candidate_cap`, and the
routing probe width `nprobe`.  The right numbers differ per backend, engine,
and corpus shape -- Faiss makes the same point for GPU similarity search
(PAPERS.md) -- so this module closes the loop by *measuring*:

  * `tune()` greedily walks the knob space one axis at a time, timing real
    executions of real plans through `core.plan.execute` with
    `block_until_ready` (median of `repeats`, warmup pays compile), and
    never adopts a knob that does not beat the incumbent;
  * winners persist as `TunedEntry` rows in an `AutotuneCache` -- a JSON
    file keyed on a hardware fingerprint (platform, device kind, device
    count, memory) and a corpus-shape bucket, so tuning runs once per
    machine and a cache copied to different hardware silently disables
    itself;
  * `plan_search(autotune=...)` consults the cache via `consult()` and
    fills only the knobs the caller left unset; a miss (or fingerprint
    mismatch) keeps today's defaults, so tuned serving can never be worse
    than untuned by construction -- `tune()` stores the default knobs when
    no candidate beats them.

`price_plan()` additionally offers the lower-and-cost estimate (XLA
cost_analysis flops/bytes) folded in from the old benchmarks/hillclimb.py,
for ranking candidates without paying execution.
"""
from __future__ import annotations

import dataclasses
import json
import os
import statistics
import time
from pathlib import Path
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from repro.core import engines as _engines
from repro.core import plan as _plan
from repro.core.routing import Routing
from repro.core.types import Engine, SignatureLayout, TopKMethod

# ---------------------------------------------------------------------------
# Hardware fingerprint + shape bucketing (the cache key axes)
# ---------------------------------------------------------------------------

CACHE_VERSION = 1
# Candidate tiles whose estimated VMEM working set (_vmem_estimate) exceeds
# this are never even measured: 12 MiB of the chip's 16 MiB scoped-VMEM
# limit, the rest left to the kernel's temporaries.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

_CACHE_ENV = "GENIE_AUTOTUNE_CACHE"


def hardware_fingerprint() -> dict:
    """Identity of the machine a measurement is valid for.

    Platform + device kind + device count + per-device memory: a tuned tile
    size is a statement about one memory hierarchy, so any of these changing
    invalidates the cache (lookup simply returns None -> default knobs).
    """
    devices = jax.devices()
    dev = devices[0]
    memory = None
    stats_fn = getattr(dev, "memory_stats", None)
    if stats_fn is not None:
        try:
            stats = stats_fn()
            if stats:
                memory = int(stats.get("bytes_limit", 0)) or None
        except (RuntimeError, NotImplementedError):
            memory = None  # backends without allocator stats (CPU)
    return {
        "platform": jax.default_backend(),
        "device_kind": str(dev.device_kind),
        "device_count": len(devices),
        "memory_bytes": memory,
        "jax": jax.__version__,
    }


def shape_bucket(n: int) -> int:
    """Corpus-shape bucket: next power of two >= n (floor 1).

    A measurement at n=100_000 prices n=120_000 fine; bucketing keeps the
    cache small and lookups stable as a corpus grows within its bucket.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"shape_bucket needs n >= 1, got {n}")
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# TunedEntry + JSON cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TunedEntry:
    """One measured winner: the knob set for (engine, layout, shape bucket).

    `fused_match` False suppresses the fused packed kernel even where
    gating allows it; None leaves the default gating alone.  `speedup` is default_us/measured_us
    from the final head-to-head -- 1.0 entries record "defaults already
    win here", which stops re-tuning from re-measuring a settled bucket.
    """

    engine: str
    signature_layout: str
    n_bucket: int
    w_bucket: int
    tile_overrides: tuple = ()
    fused_match: Optional[bool] = None
    candidate_cap: Optional[int] = None
    nprobe: Optional[int] = None
    measured_us: float = 0.0
    default_us: float = 0.0
    speedup: float = 1.0

    def key(self) -> str:
        return cache_key(self.engine, self.signature_layout,
                         self.n_bucket, self.w_bucket)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["tile_overrides"] = dict(self.tile_overrides)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TunedEntry":
        d = dict(d)
        d["tile_overrides"] = _engines.canonical_tile_overrides(
            d.get("tile_overrides") or {})
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


def cache_key(engine: Engine | str, signature_layout: SignatureLayout | str,
              n_bucket: int, w_bucket: int) -> str:
    e = engine.value if isinstance(engine, Engine) else str(engine)
    s = (signature_layout.value if isinstance(signature_layout, SignatureLayout)
         else str(signature_layout))
    return f"{e}|{s}|{int(n_bucket)}|{int(w_bucket)}"


def default_cache_path() -> Path:
    env = os.environ.get(_CACHE_ENV)
    if env:
        return Path(env)
    return Path.home() / ".cache" / "genie" / "autotune.json"


class AutotuneCache:
    """JSON-persisted map of `TunedEntry` rows, gated on the fingerprint.

    `path=None` keeps the cache in memory (tests, one-shot tuning runs).
    A load failure of any kind degrades to an empty cache -- autotuning is
    an accelerator, never a correctness dependency.
    """

    def __init__(self, path: Optional[os.PathLike | str] = None,
                 fingerprint: Optional[dict] = None):
        self.path = Path(path) if path is not None else None
        self.fingerprint = fingerprint or hardware_fingerprint()
        self.entries: dict[str, TunedEntry] = {}
        if self.path is not None:
            self.load()

    def compatible(self) -> bool:
        """True when the stored fingerprint matches this machine."""
        return self.fingerprint == hardware_fingerprint()

    def load(self) -> None:
        if self.path is None or not self.path.exists():
            return
        try:
            raw = json.loads(self.path.read_text())
            if raw.get("version") != CACHE_VERSION:
                return
            self.fingerprint = dict(raw["fingerprint"])
            self.entries = {
                k: TunedEntry.from_dict(v)
                for k, v in raw.get("entries", {}).items()
            }
        except (OSError, json.JSONDecodeError, KeyError, TypeError,
                ValueError):
            # unreadable / stale-schema cache: fall back to empty (defaults)
            self.fingerprint = hardware_fingerprint()
            self.entries = {}

    def save(self) -> None:
        if self.path is None:
            return
        self.path.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "version": CACHE_VERSION,
            "fingerprint": self.fingerprint,
            "entries": {k: v.to_dict() for k, v in self.entries.items()},
        }
        tmp = self.path.with_suffix(self.path.suffix + ".tmp")
        tmp.write_text(json.dumps(payload, indent=2, sort_keys=True))
        tmp.replace(self.path)

    def put(self, entry: TunedEntry) -> None:
        self.entries[entry.key()] = entry

    def lookup(self, engine: Engine | str,
               signature_layout: SignatureLayout | str,
               n: Optional[int], width: Optional[int] = None
               ) -> Optional[TunedEntry]:
        """The tuned entry for this shape, or None (= keep defaults).

        With `width` the lookup is exact; without it, any width bucket
        tuned for (engine, layout, n bucket) serves, best speedup first.
        Fingerprint mismatch -> None unconditionally.
        """
        if n is None or not self.compatible():
            return None
        nb = shape_bucket(n)
        if width is not None:
            return self.entries.get(
                cache_key(engine, signature_layout, nb, shape_bucket(width)))
        prefix = cache_key(engine, signature_layout, nb, 1).rsplit("|", 1)[0]
        hits = [v for k, v in self.entries.items()
                if k.rsplit("|", 1)[0] == prefix]
        if not hits:
            return None
        return max(hits, key=lambda e: e.speedup)


_RESOLVED: dict[str, AutotuneCache] = {}


def resolve_cache(spec: Any) -> Optional[AutotuneCache]:
    """`autotune=` argument -> cache: True = the default per-user path,
    a str/Path = that file, an AutotuneCache = itself, None/False = off.
    File-backed caches are memoized per path so plan_search does not
    re-read JSON per query."""
    if spec is None or spec is False:
        return None
    if isinstance(spec, AutotuneCache):
        return spec
    path = default_cache_path() if spec is True else Path(spec)
    key = str(path)
    cache = _RESOLVED.get(key)
    if cache is None:
        cache = AutotuneCache(path)
        _RESOLVED[key] = cache
    return cache


def clear_resolved_caches() -> None:
    """Drop memoized file-backed caches (tests that rewrite cache files)."""
    _RESOLVED.clear()


def consult(spec: Any, *, engine: Engine | str,
            signature_layout: SignatureLayout | str,
            n: Optional[int], width: Optional[int] = None
            ) -> Optional[TunedEntry]:
    """plan_search's door: resolve the autotune spec and look the shape up.
    Any miss -- no cache, no entry, wrong machine -- returns None and the
    plan keeps its defaults."""
    cache = resolve_cache(spec)
    if cache is None:
        return None
    return cache.lookup(engine, signature_layout, n, width)


# ---------------------------------------------------------------------------
# Platform / XLA setup (SNIPPETS.md snippet 1 pattern)
# ---------------------------------------------------------------------------


def setup_platform(platform: Optional[str] = None,
                   host_devices: Optional[int] = None,
                   extra_xla_flags: Optional[str] = None) -> None:
    """Apply platform/XLA startup configuration.

    Only takes effect before the first JAX computation initialises the
    backend -- call it at process start (serve startup, benchmark mains).
    `host_devices` sets --xla_force_host_platform_device_count (the mesh
    tests' many-device CPU trick) *opt-in*, replacing the import-time
    hard-coding the old hillclimb benchmark did.
    """
    flags = []
    if host_devices is not None:
        n = int(host_devices)
        if n < 1:
            raise ValueError(f"host_devices must be >= 1, got {n}")
        flags.append(f"--xla_force_host_platform_device_count={n}")
    if extra_xla_flags:
        flags.append(str(extra_xla_flags))
    if flags:
        existing = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = " ".join(
            ([existing] if existing else []) + flags)
    if platform is not None:
        jax.config.update("jax_platform_name", platform)


# ---------------------------------------------------------------------------
# Measurement + pricing
# ---------------------------------------------------------------------------


def _median_us(fn: Callable[[], Any], repeats: int, warmup: int) -> float:
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append((time.perf_counter() - t0) * 1e6)
    return float(statistics.median(samples))


def measure_plan(plan: "_plan.QueryPlan", data, queries, *,
                 router=None, route_queries=None,
                 repeats: int = 3, warmup: int = 1) -> float:
    """Median wall microseconds of one real execution of `plan` (the same
    `core.plan.execute` door serving uses), device-synchronised."""
    def run():
        return _plan.execute(plan, data, queries, router=router,
                             route_queries=route_queries)
    return _median_us(run, repeats, warmup)


def compare_plans(plan_a: "_plan.QueryPlan", plan_b: "_plan.QueryPlan",
                  data, queries, *, router=None, route_queries=None,
                  rounds: int = 5) -> tuple[float, float]:
    """Interleaved head-to-head: (median_us_a, median_us_b).

    Sequential timing is biased on a warming machine (whichever plan runs
    last wins for free); alternating single executions after a joint warmup
    cancels the drift, so this is the arbiter `tune()` and the benchmark
    trust for the final tuned-vs-default verdict.
    """
    def runner(p):
        def run():
            return _plan.execute(p, data, queries, router=router,
                                 route_queries=route_queries)
        return run
    fa, fb = runner(plan_a), runner(plan_b)
    jax.block_until_ready(fa())
    jax.block_until_ready(fb())
    a_s, b_s = [], []
    for _ in range(max(rounds, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fa())
        a_s.append((time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        jax.block_until_ready(fb())
        b_s.append((time.perf_counter() - t0) * 1e6)
    return float(statistics.median(a_s)), float(statistics.median(b_s))


def price_plan(plan: "_plan.QueryPlan", data, queries, *,
               mode: str = "measure", router=None, route_queries=None,
               repeats: int = 3, warmup: int = 1) -> dict:
    """Price one candidate plan.

    mode="measure": run it (measure_plan) -> {"p50_us": ...}.
    mode="lower": lower+compile the single-program executable and read the
    XLA cost model (flops / bytes accessed) without executing -- the
    lower-and-cost loop folded in from the old benchmarks/hillclimb.py.
    Only MONOLITHIC plans are one lowerable program: SEGMENTED plans are
    host-orchestrated and DISTRIBUTED ones need a mesh, so both reject
    "lower".
    """
    if mode == "measure":
        return {
            "mode": "measure",
            "p50_us": measure_plan(plan, data, queries, router=router,
                                   route_queries=route_queries,
                                   repeats=repeats, warmup=warmup),
        }
    if mode != "lower":
        raise ValueError(f"mode must be 'measure' or 'lower', got {mode!r}")
    if plan.layout is not _plan.Layout.MONOLITHIC:
        raise ValueError(
            f"mode='lower' needs a single lowerable program; a "
            f"{plan.layout.value} plan is not one -- price it with "
            f"mode='measure'"
        )
    fn = _plan.executable(plan)
    lowered = fn.lower(data, queries)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jaxlibs wrap it in a list
        cost = cost[0] if cost else {}
    cost = cost or {}
    return {
        "mode": "lower",
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "cost_keys": sorted(cost)[:16],
    }


# ---------------------------------------------------------------------------
# Candidate generation
# ---------------------------------------------------------------------------

_TILE_CANDIDATES = {
    "tile_q": (8, 16, 32, 64, 128, 256, 512),
    "tile_n": (128, 256, 512, 1024, 2048),
    "tile_v": (128, 256, 512, 1024),
    "tile_m": (128, 256, 512, 1024),
}
# Greedy axis order: the object axis dominates grid shape, then queries,
# then the in-kernel chunk axes.
_TILE_AXIS_ORDER = ("tile_n", "tile_q", "tile_v", "tile_m")


def _effective_tile(size: int, preferred: int, align: int) -> int:
    """What pick_tile will actually use -- dedupes candidates that clamp to
    the same grid (e.g. tile_n=1024 and 2048 over a 600-row corpus)."""
    from repro.kernels.common import pick_tile
    return pick_tile(size, preferred, align)


def _vmem_estimate(tiles: dict, q: int, n: int, width: int, *,
                   sweep: bool = True, query_parts: int = 1) -> int:
    """Per-grid-step VMEM working set in bytes, as the kernels lay it out.

    The column-sweep kernels (every VPU count kernel) hold `query_parts`
    grouped query blocks [Wb/8, tile_q, 8], whose 8-wide minor axis pads to
    128 lanes, and a transposed data block [Wb, tile_n]; the MXU kernels
    (IP, wide COSINE) hold [tile_q, Wb] and [tile_n, Wb] blocks instead.
    Wb is the width one grid step sees (tile_v / tile_m where the kernel
    chunks it).  The Pallas pipeline double-buffers every block and its
    [tile_q, tile_n] out block, and the kernel adds a [tile_q, tile_n]
    accumulator.  Four bytes a word throughout, which over-counts the 8-bit
    kernels: conservative on purpose -- it only prunes, never admits."""
    tq = _ceil_to(min(tiles.get("tile_q", 128), max(q, 8)), 8)
    tn = _ceil_to(min(tiles.get("tile_n", 256), max(n, 128)), 128)
    wb = _ceil_to(min(width, tiles.get("tile_v", tiles.get("tile_m", width))), 8)
    if sweep:
        q_block = query_parts * (wb // 8) * tq * 128
        d_block = wb * tn
    else:
        q_block = tq * _ceil_to(wb, 128)
        d_block = tn * _ceil_to(wb, 128)
    out = tq * tn
    return 4 * (2 * (q_block + d_block + out) + out)


def _ceil_to(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _is_sweep(engine: Engine, layout: SignatureLayout) -> bool:
    """True when the engine's kernel walks signature columns on the VPU
    (common.column_sweep); False for the MXU kernels."""
    return not (engine in (Engine.IP, Engine.COSINE)
                and layout is SignatureLayout.WIDE)


def tile_candidates(knob: str, dim: int, *,
                    vmem_budget: int = VMEM_BUDGET_BYTES) -> list[int]:
    """Deduped candidate values for one knob against its actual dim."""
    align = _engines.TILE_ALIGN[knob]
    seen, out = set(), []
    for cand in _TILE_CANDIDATES[knob]:
        eff = _effective_tile(dim, cand, align)
        if eff in seen:
            continue
        seen.add(eff)
        out.append(cand)
    return out


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


def _split_parts(data, part_rows: Sequence[int]) -> list:
    parts, off = [], 0
    for r in part_rows:
        parts.append(data[off:off + r])
        off += r
    if off != data.shape[0]:
        raise ValueError(
            f"part_rows {tuple(part_rows)} covers {off} rows but data has "
            f"{data.shape[0]}")
    return parts


def tune(engine: Engine | str | _engines.MatchModel, data, queries, k: int,
         max_count: Optional[int] = None, *,
         signature_layout: SignatureLayout | str = SignatureLayout.WIDE,
         method: TopKMethod | str = TopKMethod.CPQ,
         part_rows: Optional[Sequence[int]] = None,
         router=None, routing: Routing | str = Routing.NONE,
         candidate_caps: Sequence[Optional[int]] = (),
         budget: int = 32, repeats: int = 3, warmup: int = 1,
         vmem_budget: int = VMEM_BUDGET_BYTES,
         cache: Optional[AutotuneCache] = None, save: bool = True,
         prepared: bool = False, route_queries=None,
         ) -> TunedEntry:
    """Measure-and-pick the knob set for one (engine, layout, shape).

    `data` / `queries` are raw engine inputs (`MatchModel.example` form);
    preparation and packing happen here exactly as GenieIndex does them.
    `prepared=True` instead takes `data` already in the stored layout (the
    full array; packed words for PACKED) and `queries` as the canonical
    stored-layout pytree -- the serving path, whose sealed segments cannot
    be un-packed; it requires an explicit `max_count` and, for routed
    PACKED tuning, `route_queries` (the canonical WIDE pytree the router
    scores).  With `part_rows` the search runs SEGMENTED and, given
    `router` + `routing`, adds the nprobe axis.
    `budget` caps measured candidates; the default-knob plan is always
    measured first as the baseline, and the returned entry falls back to
    default knobs whenever no candidate beats it (tuned can never regress).

    The winning entry is put (and saved) into `cache` when given.
    """
    model = engine if isinstance(engine, _engines.MatchModel) \
        else _engines.get(engine)
    sig_layout = model.require_layout(signature_layout)
    method = TopKMethod(method)
    routing = Routing(routing)

    if prepared:
        if max_count is None:
            raise ValueError(
                "tune(prepared=True) needs an explicit max_count; the "
                "stored-layout array cannot derive the count bound")
        stored, q_stored, mc = data, queries, int(max_count)
        route_q = route_queries
    else:
        wide = model.prepare_data(data)
        mc = model.resolve_max_count(wide, max_count)
        stored = model.pack_data(wide) if sig_layout is SignatureLayout.PACKED \
            else wide
        q_stored = model.prepare_queries_for(queries, sig_layout)
        route_q = (model.prepare_queries(queries)
                   if sig_layout is SignatureLayout.PACKED else None)
    n, width = int(stored.shape[0]), int(stored.shape[1])
    n_q = int(np.asarray(jax.tree_util.tree_leaves(q_stored)[0]).shape[0])

    part_rows = tuple(int(r) for r in part_rows) if part_rows else None
    base_layout = _plan.Layout.SEGMENTED if part_rows else _plan.Layout.MONOLITHIC
    plan_routing = routing if part_rows else Routing.NONE
    exec_data = _split_parts(stored, part_rows) if part_rows else stored

    knobs = model.tile_knobs(True, sig_layout)
    if sig_layout is SignatureLayout.PACKED:
        knobs = knobs | model.tile_knobs(True, sig_layout, fused=True)
    dims = {"tile_q": n_q, "tile_n": n, "tile_v": width, "tile_m": width}
    sweep = _is_sweep(model.engine, sig_layout)
    query_parts = 2 if model.engine is Engine.RANGE else 1   # lo and hi

    state = {"tiles": {}, "fused": None, "candidate_cap": None, "nprobe": None}

    def make_plan(st):
        p = _plan.plan_search(
            model, k, mc,
            layout=base_layout, part_rows=part_rows,
            method=method, candidate_cap=st["candidate_cap"],
            use_kernel=True, signature_layout=sig_layout,
            routing=plan_routing, nprobe=st["nprobe"],
            tile_overrides=st["tiles"] or None,
        )
        if st["fused"] is False and p.fused_match is not None:
            p = dataclasses.replace(p, fused_match=None)
        return p

    def run(st):
        return measure_plan(make_plan(st), exec_data, q_stored,
                            router=router, route_queries=route_q,
                            repeats=repeats, warmup=warmup)

    trials = 0
    default_us = run(state)
    best, best_us = dict(state, tiles=dict(state["tiles"])), default_us

    def try_state(st):
        nonlocal trials, best, best_us
        if trials >= budget:
            return
        trials += 1
        # every trial is an interleaved head-to-head against the incumbent:
        # a solo sequential measurement drifts with machine warmup, so the
        # sweep would crown whichever candidate happened to run at a calm
        # moment.  Re-anchor the incumbent's clock from the same interleave
        # so stale timings never survive the sweep.
        inc_us, cand_us = compare_plans(
            make_plan(best), make_plan(st), exec_data, q_stored,
            router=router, route_queries=route_q, rounds=max(repeats, 2))
        best_us = inc_us
        if cand_us < inc_us:
            best, best_us = dict(st, tiles=dict(st["tiles"])), cand_us

    # axis 1: tile sizes, greedy per knob
    for knob in _TILE_AXIS_ORDER:
        if knob not in knobs:
            continue
        for cand in tile_candidates(knob, dims[knob]):
            tiles = dict(best["tiles"])
            tiles[knob] = cand
            if _vmem_estimate(tiles, n_q, n, width, sweep=sweep,
                              query_parts=query_parts) > vmem_budget:
                continue
            try_state(dict(best, tiles=tiles))

    # axis 2: fused packed kernel off (on is the gated default)
    if sig_layout is SignatureLayout.PACKED \
            and make_plan(best).fused_match is not None:
        try_state(dict(best, tiles=dict(best["tiles"]), fused=False))

    # axis 3: candidate_cap
    for cap in candidate_caps:
        try_state(dict(best, tiles=dict(best["tiles"]),
                       candidate_cap=None if cap is None else int(cap)))

    # axis 4: routing probe width
    if part_rows and router is not None and routing is not Routing.NONE:
        for cand in (1, 2, 4, 8, 16):
            if cand > len(part_rows):
                break
            try_state(dict(best, tiles=dict(best["tiles"]), nprobe=cand))

    # head-to-head: interleaved re-measure of winner vs default (sequential
    # timing on a warming machine favours whoever runs last); keep defaults
    # unless the winner still wins
    default_state = {"tiles": {}, "fused": None, "candidate_cap": None,
                     "nprobe": None}
    if best != default_state:
        default_us, best_us = compare_plans(
            make_plan(default_state), make_plan(best), exec_data, q_stored,
            router=router, route_queries=route_q,
            rounds=max(repeats, 3))
    if best_us >= default_us:
        best = default_state
        best_us = default_us

    entry = TunedEntry(
        engine=model.engine.value,
        signature_layout=sig_layout.value,
        n_bucket=shape_bucket(n),
        w_bucket=shape_bucket(width),
        tile_overrides=_engines.canonical_tile_overrides(best["tiles"]),
        fused_match=best["fused"],
        candidate_cap=best["candidate_cap"],
        nprobe=best["nprobe"],
        measured_us=best_us,
        default_us=default_us,
        speedup=(default_us / best_us) if best_us > 0 else 1.0,
    )
    if cache is not None:
        cache.put(entry)
        if save:
            cache.save()
    return entry
