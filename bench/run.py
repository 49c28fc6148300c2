"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for.  Everything is found by name: the cell in BENCHMARK.json, its
configuration in bench/configs/<config>.json (sizes), <config>.py (data
from the seed, the tenant, the least bytes of a dispatch) and
<config>_reference.py (the plain reference), its traffic mix in
bench/traffic/<mix>.json, and each per-layer metric in
bench/metrics/<metric>.py.

A run: make the data and fill the tenant through `ServingFrontend`, warm up
every padded batch shape the mix can send, drive the mix for `--seconds`
(traced by the JAX profiler for its first TRACE_SECONDS with `--trace 1`),
wait for every request of the window, read the device's memory peak, free
the tenant, and compare a sample of the answered requests, drawn from the
seed, with the plain reference (bench/check.py).  Set-up phases, compile
counts and the generator's lateness go to standard error; the numbers
compared, each beside its limit, are its last lines.  The last line of
standard output is the result: `correct`, `attempted`, `failed`, `metrics`
(the cell's end-to-end metrics, or its per-layer metrics with `--trace 1`),
`device`, with `--trace 1` a `breakdown`, and `checks` last.

It exits 2 without a result outside a checkout of the program, and 1 when
JAX finds no TPU or fewer chips than the cell asks for.  JAX's persistent
compile cache lives at bench/.jax_cache in the checkout.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
OUT_DIR = os.path.join(BENCH, "out")
TRACE_SECONDS = 10.0      # longest traced stretch of a --trace 1 window
GRACE_SECONDS = 60.0      # how long after the close answers are awaited

for _p in (BENCH, os.path.join(BENCH, "configs")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import check  # noqa: E402
import load as load_lib  # noqa: E402


class Refused(Exception):
    """The run cannot start here; carries the exit code."""

    def __init__(self, message: str, code: int):
        super().__init__(message)
        self.code = code


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------

def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str, ext: str) -> str:
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise Refused(f"no {kind} file {os.path.relpath(path, ROOT)}", 2)
    return path


def module(kind: str, name: str) -> types.ModuleType:
    """Load bench/<kind>/<name>.py once, registered under its own name so a
    sibling (a reference importing its configuration) shares it."""
    key = f"{name}" if kind == "configs" else f"bench_{kind}_{name}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, find(kind, name, ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, doc: dict | None = None):
    """(cell, configuration, traffic mix, per-layer metrics) of a workload;
    the cell carries the names of the end-to-end metrics it reports."""
    if doc is None:
        doc = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in doc["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; cells: {sorted(cells)}", 2)
    cell = dict(cells[workload], end_to_end=[
        m["name"] for m in doc.get("end_to_end", [])
        if workload in m.get("workloads", [workload])])
    cfg = read_json(find("configs", cell["config"], ".json"))
    traffic = load_lib.validate(read_json(find("traffic", cell["traffic"], ".json")))
    layer = [m for m in doc["per_layer"]
             if workload in m.get("workloads", [workload])]
    return cell, cfg, traffic, layer


# ---------------------------------------------------------------------------
# JAX and the device
# ---------------------------------------------------------------------------

def start_jax():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}: {len(devices)} x {dev.platform} ({dev.device_kind})")
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU, JAX found platform {dev.platform!r}", 1)
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devices)}", 1)
    return devices


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class SearchSpans:
    """Wraps a tenant backend's `search`: one `bench.search` span per call,
    blocking on the result, with the call's sequence number and rows."""

    def __init__(self, backend):
        self.backend = backend
        self.search = backend.search
        self.spans: list[tuple[float, float]] = []
        backend.search = self

    def __call__(self, *args, **kwargs):
        import jax

        seq = len(self.spans)
        rows = int(np.shape(kwargs.get("embeddings", args[0] if args else None))[0])
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.search", seq=seq, rows=rows):
            res, sims = self.search(*args, **kwargs)
            jax.block_until_ready(res)
        self.spans.append((t0, time.perf_counter()))
        return res, sims

    def release(self) -> None:
        del self.backend.search
        self.backend = self.search = None


def warm_shapes(traffic: dict, max_batch: int) -> list[int]:
    """The padded row counts a dispatch of this mix can have: stacks of
    whole requests up to max_batch rows, padded to a power of two."""
    rows = int(traffic["rows"])
    most = max(1, max_batch // rows)
    if traffic["loop"] == "closed":
        most = min(most, int(traffic["clients"]))
    return sorted({1 << (j * rows - 1).bit_length() for j in range(1, most + 1)})


def dispatch_rows(spans, records) -> list[int]:
    """Real (unpadded) rows answered by each search call: a request completes
    after the call that answered it returns and before the next one begins."""
    ends = np.array([e for _, e in spans])
    rows = [0] * len(spans)
    for r in records:
        if r.done is not None and r.error is None:
            i = int(np.searchsorted(ends, r.done, side="right")) - 1
            if i >= 0:
                rows[i] += len(r.rows)
    return rows


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def end_to_end(records, t0: float, t_end: float, setup_s: float) -> dict:
    """`qps` is the rows answered by the close over the time from the open
    to the last of those answers: whole dispatches only, so neither the
    dispatch in flight at the close nor one the close splits (a closed-loop
    client whose answer comes just after the close does not send again)
    moves it.  The latencies are those of every request sent in the window,
    each waited for."""
    answered = [r for r in records if r.done is not None and r.error is None]
    by_close = [r for r in answered if r.done <= t_end]
    rows = sum(len(r.rows) for r in by_close)
    span = max((r.done for r in by_close), default=t0) - t0
    lat = np.array([r.latency for r in answered]) * 1e3
    p50, p95 = (np.percentile(lat, [50, 95]) if lat.size else (0.0, 0.0))
    log(f"window: {len(answered)} of {len(records)} requests answered, "
        f"{len(by_close)} with {rows} rows in {span:.3f} s by the close; "
        f"latency p50 {p50:.3f} ms, p95 {p95:.3f} ms "
        f"over {lat.size} requests ({int(lat.size * 0.05)} beyond p95)")
    return {
        "qps": {"value": rows / span if span > 0 else 0.0, "unit": "queries/s"},
        "latency_p50_ms": {"value": float(p50), "unit": "ms"},
        "latency_p95_ms": {"value": float(p95), "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }


def per_layer(metrics: list, window, ctx_fields: dict) -> dict:
    ctx = types.SimpleNamespace(window=window, **ctx_fields)
    out = {}
    for m in metrics:
        value = module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Serving:
    """One configuration's tenant behind a `ServingFrontend`, filled from the
    seed and warmed up for a traffic mix; `drive` runs one window on it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, t_start: float):
        from repro.serve.frontend import ServingFrontend

        self.build = module("configs", cfg["name"])
        self.compiles = CompileLog()
        self.k = int(traffic["k"])
        log(f"set-up jax: {time.perf_counter() - t_start:.2f} s")
        self.frontend = ServingFrontend(max_batch=cfg["queries_per_batch"])
        self.backend = self.build.build(cfg, seed, self.frontend, log)
        self.spans = SearchSpans(self.backend)
        self.tenant = self.build.TENANT
        self.pool = self.build.queries(cfg, seed, cfg["query_pool"])
        t0 = time.perf_counter()
        order = load_lib.pool_order(self.pool.shape[0], seed)
        shapes = warm_shapes(traffic, cfg["queries_per_batch"])
        for b in shapes:
            self.frontend.submit(self.tenant, None, k=self.k,
                                 embeddings=self.pool[order[:b]]).result()
        self.setup_s = time.perf_counter() - t_start
        log(f"set-up warm-up: {time.perf_counter() - t0:.2f} s for row shapes "
            f"{shapes}; {self.compiles.compiles} compiles so far "
            f"({self.compiles.seconds:.2f} s, {self.compiles.hits} cache hits); "
            f"set-up {self.setup_s:.2f} s")

    def submit(self, rec):
        import jax

        with jax.profiler.TraceAnnotation("bench.submit"):
            return self.frontend.submit(self.tenant, None, k=self.k,
                                        embeddings=self.pool[rec.rows])

    def drive(self, traffic: dict, seed: int, seconds: float,
              trace_dir: str | None = None):
        """One window of the mix: (load with its records, close time, whether
        every request came), traced for its first TRACE_SECONDS into
        `trace_dir` when given."""
        import jax

        load = load_lib.Load(traffic, self.submit, self.pool.shape[0], seed,
                             seconds)
        before = self.compiles.compiles
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        t_end = t0 + seconds
        load.start(t0, t_end)
        if trace_dir is not None:
            time.sleep(max(0.0, t0 + min(seconds, TRACE_SECONDS) - time.perf_counter()))
            jax.profiler.stop_trace()
        all_came = load.join(GRACE_SECONDS)
        late = load.lateness()
        log(f"window: {self.compiles.compiles - before} compiles inside; "
            f"generator lateness p50 {np.percentile(late, 50) * 1e3:.3f} ms, "
            f"p95 {np.percentile(late, 95) * 1e3:.3f} ms, "
            f"max {late.max() * 1e3:.3f} ms")
        return load, t_end, all_came

    def close(self) -> None:
        """Stop the front end and let go of the tenant and its device state."""
        self.frontend.close()
        stats = self.frontend.stats()
        log(f"front end: {stats['dispatches']} dispatches, "
            f"{stats['coalesce_ratio']} requests and {stats['batch_occupancy']} "
            f"rows per dispatch, {stats['tenants'][self.tenant]['shed']} shed")
        self.spans.release()
        self.frontend = self.backend = None
        gc.collect()


def run_cell(cell: dict, cfg: dict, traffic: dict, layer_metrics: list,
             seed: int, seconds: float, trace: bool, t_start: float,
             out_dir: str = OUT_DIR) -> dict:
    import jax

    import devtrace as trace_lib
    import peaks as peaks_lib

    dev = jax.devices()[0]
    serving = Serving(cfg, traffic, seed, t_start)
    build, pool, k = serving.build, serving.pool, serving.k
    trace_dir = os.path.join(out_dir, "trace", cell["name"]) if trace else None
    load, t_end, all_came = serving.drive(traffic, seed, seconds, trace_dir)
    records = load.records
    per_dispatch = dispatch_rows(serving.spans.spans, records)

    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    metrics = (None if trace else
               end_to_end(records, t_end - seconds, t_end, serving.setup_s))
    serving.close()
    reference = module("configs", cfg["name"] + "_reference")

    answered = [r for r in records if r.done is not None and r.error is None]
    failed = len(records) - len(answered)
    if not all_came:
        log(f"window: requests still unanswered {GRACE_SECONDS:.0f} s after the close")
    for r in records:
        if r.error is not None:
            log(f"window: request {r.index} failed: {r.error!r}")
            break
    numbers = {name: 1.0 for name in check.NUMBERS}
    if answered:
        rng = np.random.default_rng(seed)
        pick = np.sort(rng.choice(len(answered), min(cfg["check_requests"],
                                                     len(answered)), replace=False))
        sample = [answered[i] for i in pick]
        served_ids = np.concatenate([np.asarray(r.result[0].ids) for r in sample])
        served_counts = np.concatenate([np.asarray(r.result[0].counts) for r in sample])
        t0 = time.perf_counter()
        ref_ids, ref_counts, recount = reference.reference(
            cfg, seed, np.concatenate([pool[r.rows] for r in sample]),
            served_ids, k)
        log(f"reference: {served_ids.shape[0]} query rows of {len(sample)} "
            f"sampled requests in {time.perf_counter() - t0:.2f} s")
        numbers = check.compare(served_ids, served_counts, ref_ids, ref_counts,
                                recount)
    limits = dict(cfg["limits"])
    numbers["unanswered"], limits["unanswered"] = failed, 0
    correct = check.judge(numbers, limits)

    result = {"correct": correct, "attempted": len(records), "failed": failed}
    if trace:
        window = trace_lib.reduce(trace_lib.load(trace_dir))
        result["metrics"] = {}
        if window is not None:
            seqs = [int(s.stats["seq"]) for s in window.searches]
            device["busy_s"] = window.busy_ns * 1e-9
            device["window_s"] = window.seconds
            result["metrics"] = per_layer(layer_metrics, window, dict(
                cfg=cfg, k=k, peaks=peaks_lib.peaks(dev.device_kind),
                dispatches=len(seqs),
                dispatch_seconds=sum(s.end - s.start for s in window.searches) * 1e-9,
                rows=sum(per_dispatch[q] for q in seqs),
                least_bytes=sum(build.least_bytes(cfg, per_dispatch[q], k)
                                for q in seqs)))
            result["breakdown"] = trace_lib.breakdown(window)
            log(f"trace: {len(seqs)} dispatches in {window.seconds:.3f} s, "
                f"device busy {window.busy_ns * 1e-9:.3f} s")
    else:
        result["metrics"] = {k: v for k, v in metrics.items()
                             if k in cell["end_to_end"]}
    result["device"] = device
    if "breakdown" in result:
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = {n: {"value": numbers[n], "limit": limits[n]}
                        for n in numbers}
    for line in check.lines(numbers, limits):
        log(line)
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell, cfg, traffic, layer = resolve(args.workload)
        if not os.path.isdir(os.path.join(SRC, "repro")):
            raise Refused(f"no program under {SRC}: run from a checkout", 2)
        sys.path.insert(0, SRC)
        start_jax()
        check_devices(int(cell["chips"]))
        result = run_cell(cell, cfg, traffic, layer, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except Refused as e:
        log(f"bench: {e}")
        return e.code
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
