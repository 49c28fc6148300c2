"""Smoke run of the served GENIE path on a TPU, at the paper's SIFT deployment.

    python3 chip_smoke.py              # one chip: SEGMENTED host loop
    python3 chip_smoke.py --chips 4    # four chips: the DISTRIBUTED mesh path

The deployment is paper section VI-A1's SIFT cell (configs/genie_datasets.py):
4.5M points x 128 dims, E2LSH rehashed into 67 buckets with m = 237 hash
functions (w = 4.0), Q = 1024 queries per batch, k = 100.  The points are
made from `--seed` by `repro.data.pipeline.synthetic_points`; nothing is
downloaded.  Everything goes through the entry points a user calls:
`ServingFrontend.create_tenant` -> `add` (16 batches, one sealed segment
each, no compaction) -> `submit` -> `Future.result()`.

Checks, each of which fails the run:
  * the device is a TPU (no CPU fallback, no interpret mode);
  * the served ids and counts of sampled queries equal a plain NumPy
    reference bit for bit: EQ counts against every stored signature, then
    the top-k under (count desc, id asc);
  * (one chip) the device's E2LSH signatures agree with a float64 NumPy
    E2LSH on at least HASH_AGREEMENT_MIN of the values.

Timings printed on the way are wall times of this smoke run (set-up, first
round with compilation, second round), not device metrics.  The last line
of stdout is one JSON object: {"ok": true, "device": {...}}.

JAX's persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says
when it is set, and to <repo>/.jax_cache otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# the deployment (configs/genie_datasets.py "sift", paper section VI-A1)
N_POINTS = 4_500_000
DIM = 128
N_BUCKETS = 67
M = 237
W = 4.0
K = 100
N_BATCHES = 16                 # corpus adds; one sealed segment each
REQUESTS = 4                   # per round; REQUESTS * QUERIES = Q = 1024
QUERIES = 256
N_REFERENCE = 8                # queries checked against the NumPy reference
N_HASH_CHECK = 4096            # corpus points rehashed in float64
QUERY_NOISE = 0.05             # queries are perturbed corpus points

# Device E2LSH against a float64 reference, on the N_HASH_CHECK rows checked
# here (970,752 values, computed in NumPy on a CPU): a float32 projection
# agrees on 99.999897% of them, a projection that rounds its inputs to
# bf16, as the TPU's default matmul precision does, on 98.935670%.  The
# bound sits between the two, so it fails a bf16 hash by ten times its
# margin and leaves a float32 hash a thousand times its error.
HASH_AGREEMENT_MIN = 0.999


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def setup_compile_cache() -> str:
    """JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself), else the
    fixed <repo>/.jax_cache: the path is part of the cache key, so it never
    moves between runs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> str:
        return (f"backend compile {self.seconds:.2f} s, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


# ---------------------------------------------------------------------------
# Plain NumPy references, independent of repro.core
# ---------------------------------------------------------------------------

def numpy_topk(data_sigs: np.ndarray, query_sigs: np.ndarray, k: int,
               rows: int = 1 << 18) -> tuple[np.ndarray, np.ndarray]:
    """EQ counts of each query against every stored signature, then the
    top-k under (count desc, id asc).  Returns (ids, counts) [q, k]."""
    q = query_sigs.shape[0]
    counts = np.zeros((q, data_sigs.shape[0]), dtype=np.int32)
    for lo in range(0, data_sigs.shape[0], rows):
        block = data_sigs[lo:lo + rows]
        for i in range(q):
            counts[i, lo:lo + rows] = (block == query_sigs[i]).sum(axis=1)
    # a stable sort of -count keeps ascending ids within equal counts
    ids = np.argsort(-counts, axis=1, kind="stable")[:, :k].astype(np.int32)
    return ids, np.take_along_axis(counts, ids, axis=1)


def _fmix32(x: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer in uint32 arithmetic."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def numpy_e2lsh(points: np.ndarray, a: np.ndarray, b: np.ndarray,
                seeds: np.ndarray, w: float, n_buckets: int) -> np.ndarray:
    """GENIE's E2LSH transform in float64: floor((a.x + b) / w), rehashed
    into [0, n_buckets) with the seeded Murmur3 finalizer."""
    proj = points.astype(np.float64) @ a.astype(np.float64).T
    raw = np.floor((proj + b.astype(np.float64)) / w).astype(np.int64)
    mixed = _fmix32((raw & 0xFFFFFFFF).astype(np.uint32) ^ seeds.astype(np.uint32))
    return (mixed % np.uint32(n_buckets)).astype(np.int32)


# ---------------------------------------------------------------------------
# The served path
# ---------------------------------------------------------------------------

def run_round(frontend, queries: np.ndarray, label: str):
    """Submit REQUESTS requests of QUERIES rows at once and wait on every
    future, so a dispatch failure fails the run."""
    t0 = time.perf_counter()
    futures = [frontend.submit("sift", None, k=K,
                               embeddings=queries[i * QUERIES:(i + 1) * QUERIES])
               for i in range(queries.shape[0] // QUERIES)]
    results, waits = [], []
    for fut in futures:
        results.append(fut.result())
        waits.append(time.perf_counter() - t0)
    total = time.perf_counter() - t0
    log(f"{label}: {len(futures)} requests x {QUERIES} queries in {total:.2f} s "
        f"(per-request completion {', '.join(f'{s:.2f}' for s in waits)} s)")
    ids = np.concatenate([np.asarray(r.ids) for r, _ in results])
    counts = np.concatenate([np.asarray(r.counts) for r, _ in results])
    return ids, counts


def serve_and_check(*, n_points: int, dim: int, n_batches: int, seed: int,
                    mesh=None, max_batch: int = REQUESTS * QUERIES,
                    check_hashes: bool = True) -> None:
    """Build the tenant, serve two rounds, check them against NumPy."""
    import jax

    from repro.core import lsh
    from repro.data.pipeline import synthetic_points
    from repro.serve.frontend import ServingFrontend

    t0 = time.perf_counter()
    points, _ = synthetic_points(n_points, dim, seed=seed)
    rng = np.random.default_rng(seed + 1)
    picks = rng.choice(n_points, REQUESTS * QUERIES, replace=False)
    queries = (points[picks] + QUERY_NOISE * rng.standard_normal(
        (picks.size, dim))).astype(np.float32)
    log(f"set-up: {n_points} x {dim} points and {queries.shape[0]} queries "
        f"made in {time.perf_counter() - t0:.2f} s")

    with ServingFrontend(mesh=mesh, max_batch=max_batch) as frontend:
        svc = frontend.create_tenant(
            "sift", embed_fn=np.asarray, scheme="e2lsh", n_buckets=N_BUCKETS,
            m_override=M, w=W, seed=seed)
        t0 = time.perf_counter()
        bounds = np.linspace(0, n_points, n_batches + 1).astype(int)
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            frontend.add("sift", range(lo, hi), embeddings=points[lo:hi])
        stats = svc.index_stats
        log(f"set-up: {stats.n_objects} objects added as {stats.n_segments} "
            f"segments ({stats.compaction_count} compactions) in "
            f"{time.perf_counter() - t0:.2f} s")
        if stats.n_segments != n_batches or stats.compaction_count:
            fail(f"expected {n_batches} segments and no compaction, got "
                 f"{stats.n_segments} / {stats.compaction_count}")

        ids, counts = run_round(frontend, queries, "round 1 (compiles)")
        ids2, counts2 = run_round(frontend, queries, "round 2")
        if not (np.array_equal(ids, ids2) and np.array_equal(counts, counts2)):
            fail("round 2 returned different results from round 1")

        t0 = time.perf_counter()
        data_sigs = svc.corpus_signatures()
        sample = np.linspace(0, queries.shape[0] - 1, N_REFERENCE).astype(int)
        want_ids, want_counts = numpy_topk(data_sigs, svc.signatures(queries[sample]), K)
        log(f"reference: {sample.size} queries against {data_sigs.shape[0]} "
            f"signatures in {time.perf_counter() - t0:.2f} s")
        if not (np.array_equal(ids[sample], want_ids)
                and np.array_equal(counts[sample], want_counts)):
            bad = [int(q) for q, a, b in zip(sample, ids[sample], want_ids)
                   if not np.array_equal(a, b)]
            fail(f"served top-{K} differs from the NumPy reference "
                 f"(queries {bad})")
        log(f"reference: served ids and counts equal NumPy bit for bit "
            f"(top count {int(want_counts[:, 0].max())}, "
            f"k-th count {int(want_counts[:, -1].min())})")

        if check_hashes:
            params = lsh.get_scheme("e2lsh").make_params(
                jax.random.PRNGKey(seed), d=dim, m=M, w=W, n_buckets=N_BUCKETS)
            rows = np.linspace(0, n_points - 1, N_HASH_CHECK).astype(int)
            want = numpy_e2lsh(points[rows], np.asarray(params.a),
                               np.asarray(params.b), np.asarray(params.seeds),
                               W, N_BUCKETS)
            agree = float(np.mean(data_sigs[rows] == want))
            log(f"hashing: device E2LSH agrees with float64 NumPy on "
                f"{agree:.6%} of {want.size} values "
                f"(bound {HASH_AGREEMENT_MIN:.1%})")
            if agree < HASH_AGREEMENT_MIN:
                fail(f"hash agreement {agree:.6%} below {HASH_AGREEMENT_MIN:.1%}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded served path on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout of the repo", 2)
    sys.path.insert(0, SRC)
    cache = setup_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}: {len(devices)} x {dev.platform} "
        f"({dev.device_kind}); compile cache {cache}")
    if dev.platform != "tpu":
        fail(f"needs a TPU, found platform {dev.platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}")
    compiles = CompileLog()

    if args.chips == 1:
        serve_and_check(n_points=N_POINTS, dim=DIM, n_batches=N_BATCHES,
                        seed=args.seed)
    else:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((args.chips,), ("data",))
        # the DISTRIBUTED program holds each shard's [Q, N/4] count matrix
        # (core/plan.py _build_sharded): at N/4 = 1.125M rows it fits 16 GB
        # at Q = 256 per dispatch, not at the 1024 of a whole batch
        serve_and_check(n_points=N_POINTS, dim=DIM, n_batches=N_BATCHES,
                        seed=args.seed, mesh=mesh, max_batch=QUERIES,
                        check_hashes=False)
    if "repro.launch.dryrun" in sys.modules:
        fail("repro.launch.dryrun was imported: it rewrites XLA_FLAGS")
    log(compiles.summary())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
