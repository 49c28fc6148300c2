import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# Only the dry-run sees 512 placeholder devices; tests/benches see 1.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell and both production meshes
(16x16 single-pod, 2x16x16 multi-pod), lower + compile the appropriate step
function with full-size ShapeDtypeStruct inputs (no allocation), then record:

  * memory_analysis()  -- per-device argument/output/temp bytes (fit proof)
  * cost_analysis()    -- per-device HLO FLOPs / bytes accessed
  * collective bytes   -- parsed from the optimized HLO, by collective type
  * MODEL_FLOPS        -- analytic 6*N*D (train) / 2*N_active*D (inference)

plus the GENIE search_step cells (paper-scale index shapes, objects sharded
over the full mesh).  Results go to reports/dryrun/<cell>.json, one file per
cell, resumable.  Any sharding mismatch / unsupported collective / compile
OOM here is a bug in the system (and several were found and fixed this way).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k --mesh multi
  PYTHONPATH=src python -m repro.launch.dryrun --genie --mesh single
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as sh_lib
from repro.launch import shapes as shapes_lib
from repro.models.registry import get_api, get_config
from repro.train import step as train_step_lib

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "reports", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"\b(pred|s8|u8|s16|u16|f16|bf16|s32|u32|f32|s64|u64|f64|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in optimized HLO,
    grouped by op kind.  '-done' halves of async pairs are skipped."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        result_part = line.split("=", 1)[1].split(m.group(1))[0]
        total = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(result_part))
        out[op] = out.get(op, 0) + total
    return out


def _mem_dict(mem) -> dict:
    keys = [
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ]
    return {k: int(getattr(mem, k)) for k in keys if hasattr(mem, k)}


def _cost_dict(cost) -> dict:
    out = {}
    for k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds"):
        if k in cost:
            out[k.replace(" ", "_")] = float(cost[k])
    return out


def _report(lowered, compiled, seconds: float) -> dict:
    txt = compiled.as_text()
    cost = compiled.cost_analysis()
    return dict(
        ok=True,
        compile_seconds=round(seconds, 2),
        memory=_mem_dict(compiled.memory_analysis()),
        cost=_cost_dict(cost),
        collectives=collective_bytes(txt),
        hlo_ops=len(txt.splitlines()),
    )


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lower_lm(cfg, shape, mesh, accum_override=None):
    """Lower + compile the step function for one (cfg, shape) on `mesh`."""
    api = get_api(cfg)
    # training uses the per-arch DP/TP choice; serving always uses TP
    use_tp = cfg.use_tp if shape.kind == "train" else cfg.use_tp_serve
    with jax.sharding.set_mesh(mesh):
        batch_sds = shapes_lib.input_specs(cfg, shape)
        batch_sh = sh_lib.batch_shardings(batch_sds, mesh, use_tp)
        params_shapes = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
        params_sh = sh_lib.params_shardings(params_shapes, mesh, use_tp)

        if shape.kind == "train":
            # microbatch accumulation sized so each microbatch holds <=8k
            # tokens per device (the standard pod-scale recipe; saved scan
            # carries and logits scale down by `accum`): iteration 6.
            dp = mesh_lib.dp_size(mesh) * (1 if use_tp else mesh_lib.tp_size(mesh))
            tokens_per_dev = shape.global_batch * shape.seq_len // dp
            accum = 1
            while tokens_per_dev // accum > 8192 and shape.global_batch % (2 * accum) == 0:
                accum *= 2
            if accum_override is not None:
                accum = accum_override
            # bf16 Adam moments for >100B models: f32 moments alone exceed
            # 16 GB/chip at 256 chips for grok-1
            from repro.optim.adamw import AdamWConfig

            mdt = "bfloat16" if cfg.param_count() > 100e9 else "float32"
            hp = train_step_lib.TrainHParams(
                accum=accum, optimizer=AdamWConfig(moment_dtype=mdt))
            step_fn = train_step_lib.make_train_step(cfg, api, hp)
            state_sds = jax.eval_shape(
                lambda: train_step_lib.init_state(cfg, api, jax.random.PRNGKey(0), hp)
            )
            state_sh = sh_lib.state_shardings(state_sds, params_sh, mesh)
            out_sds = jax.eval_shape(step_fn, state_sds, batch_sds)
            metrics_sh = jax.tree_util.tree_map(lambda _: sh_lib.replicated(mesh), out_sds[1])
            jitted = jax.jit(
                step_fn, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, metrics_sh), donate_argnums=(0,),
            )
            lowered = jitted.lower(state_sds, batch_sds)

        elif shape.kind == "prefill":
            def prefill_fn(params, batch):
                return api.prefill(cfg, params, batch, cache_cap=shape.seq_len)

            jitted = jax.jit(prefill_fn, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params_shapes, batch_sds)

        else:  # decode
            cache_sds = shapes_lib.cache_specs(cfg, shape)
            cache_sh = sh_lib.cache_shardings(cfg, cache_sds, mesh)
            token_sds = shapes_lib.token_specs(cfg, shape)
            token_sh = sh_lib.batch_shardings({"t": token_sds}, mesh, use_tp)["t"]
            logits_sds = jax.ShapeDtypeStruct((shape.global_batch, cfg.vocab), jnp.float32)
            logits_sh = sh_lib.batch_shardings({"l": logits_sds}, mesh, use_tp)["l"]

            def decode_fn(params, token, cache, pos):
                return api.decode_step(cfg, params, token, cache, pos)

            jitted = jax.jit(
                decode_fn,
                in_shardings=(params_sh, token_sh, cache_sh, sh_lib.replicated(mesh)),
                out_shardings=(logits_sh, cache_sh),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(
                params_shapes, token_sds, cache_sds,
                jax.ShapeDtypeStruct((), jnp.int32),
            )

        compiled = lowered.compile()
    return lowered, compiled


def _layer_variants(cfg):
    """(cfg_1unit, cfg_2unit, n_units) for the unrolled cost extrapolation."""
    import dataclasses as dc

    if cfg.family == "hybrid":
        p = cfg.shared_attn_period
        return (
            dc.replace(cfg, n_layers=p, scan_unroll=True),
            dc.replace(cfg, n_layers=2 * p, scan_unroll=True),
            cfg.n_layers // p,
        )
    if cfg.family == "audio":
        return (
            dc.replace(cfg, n_layers=1, n_encoder_layers=1, scan_unroll=True),
            dc.replace(cfg, n_layers=2, n_encoder_layers=2, scan_unroll=True),
            cfg.n_layers,  # == n_encoder_layers for seamless
        )
    return (
        dc.replace(cfg, n_layers=1, scan_unroll=True),
        dc.replace(cfg, n_layers=2, scan_unroll=True),
        cfg.n_layers,
    )


def _extrapolated_costs(cfg, shape, mesh) -> dict:
    """HLO FLOPs / bytes / collectives at full depth, from two unrolled
    small-depth compiles.

    XLA's cost analysis counts a while-loop body ONCE regardless of trip
    count, so the scanned production program under-reports per-layer work.
    We lower the same cell with 1 and 2 layer-units, scans fully unrolled
    (no while loops), and extrapolate linearly:
        cost(L) = cost(1) + (L - 1) * (cost(2) - cost(1)).
    Exact for layer-homogeneous programs (all of ours are).
    """
    cfg1, cfg2, units = _layer_variants(cfg)
    # accum=1 for the cost variants: the accumulation lax.scan body would be
    # counted once by cost analysis (total FLOPs are accum-invariant anyway).
    _, comp1 = _lower_lm(cfg1, shape, mesh, accum_override=1)
    c1, coll1 = _cost_dict(comp1.cost_analysis()), collective_bytes(comp1.as_text())
    _, comp2 = _lower_lm(cfg2, shape, mesh, accum_override=1)
    c2, coll2 = _cost_dict(comp2.cost_analysis()), collective_bytes(comp2.as_text())
    ex_cost = {
        k: c1.get(k, 0.0) + (units - 1) * (c2.get(k, 0.0) - c1.get(k, 0.0))
        for k in set(c1) | set(c2)
    }
    ex_coll = {
        k: int(coll1.get(k, 0) + (units - 1) * (coll2.get(k, 0) - coll1.get(k, 0)))
        for k in set(coll1) | set(coll2)
    }
    return dict(cost=ex_cost, collectives=ex_coll, units=units,
                base=dict(cost=c1, collectives=coll1))


def run_lm_cell(arch: str, shape_name: str, mesh_kind: str) -> dict:
    cfg = get_config(arch)
    api = get_api(cfg)
    shape = shapes_lib.SHAPES[shape_name]
    supported, reason = shapes_lib.cell_supported(cfg, shape)
    if not supported:
        return dict(ok=True, skipped=True, reason=reason)
    if shape.kind == "decode" and not api.supports_decode:
        return dict(ok=True, skipped=True, reason="architecture has no decode step")

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.perf_counter()
    lowered, compiled = _lower_lm(cfg, shape, mesh)
    rep = _report(lowered, compiled, time.perf_counter() - t0)
    try:
        rep["extrapolated"] = _extrapolated_costs(cfg, shape, mesh)
    except (ValueError, NotImplementedError, RuntimeError) as e:
        # expected extrapolation failures: unsupported mesh arithmetic
        # (ValueError), collectives the model has no scaling law for
        # (NotImplementedError), XLA cost-analysis refusals (XlaRuntimeError
        # subclasses RuntimeError).  Anything else is a bug and propagates.
        rep["extrapolated"] = dict(error=f"{type(e).__name__}: {e}")
    # analytic model flops
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    factor = 6 if shape.kind == "train" else 2
    rep.update(
        param_count=int(n_params), active_param_count=int(n_active),
        tokens_per_step=int(tokens),
        model_flops=float(factor * n_active * tokens),
    )
    return rep


# ---------------------------------------------------------------------------
# GENIE search cells (the paper's own workload at pod scale)
# ---------------------------------------------------------------------------

def run_genie_cell(dataset: str, mesh_kind: str) -> dict:
    from repro.configs.genie_datasets import DATASETS
    from repro.core import plan as plan_lib
    from repro.core.types import SearchParams

    ds = DATASETS[dataset]
    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(np.prod(mesh.devices.shape))
    n = ((ds.n_objects + n_dev - 1) // n_dev) * n_dev
    q = ds.queries_per_batch
    # use_kernel=False: the dry-run lowers (and costs) the XLA fallback
    # engine; the Pallas path is costed analytically below.
    params = SearchParams(k=ds.default_k, use_kernel=False,
                          max_count=ds.m if ds.engine == "eq" else ds.dim)

    # Input shapes/dtypes are dataset metadata; the match function itself is
    # resolved from the MatchModel registry by engine name inside
    # plan_search -- no per-engine dispatch here.
    if ds.engine == "eq":
        # signature dtype: narrowest int that holds the rehash domain
        # (hillclimb C: int8 SIFT signatures quarter the dominant HBM stream)
        sig_dt = jnp.int8 if ds.n_buckets <= 127 else (
            jnp.int16 if ds.n_buckets <= 32767 else jnp.int32)
        data_sds = jax.ShapeDtypeStruct((n, ds.m), sig_dt)
        query_sds = jax.ShapeDtypeStruct((q, ds.m), sig_dt)
    elif ds.engine == "minsum":
        data_sds = jax.ShapeDtypeStruct((n, ds.m), jnp.int8)
        query_sds = jax.ShapeDtypeStruct((q, ds.m), jnp.int8)
        params = SearchParams(k=ds.default_k, max_count=127, use_kernel=False)
    elif ds.engine == "ip":
        data_sds = jax.ShapeDtypeStruct((n, ds.m), jnp.int8)
        query_sds = jax.ShapeDtypeStruct((q, ds.m), jnp.int8)
        params = SearchParams(k=ds.default_k, max_count=ds.dim * 4, use_kernel=False)
    else:  # range: queries are the canonical (lo, hi) pytree
        data_sds = jax.ShapeDtypeStruct((n, ds.dim), jnp.int32)
        query_sds = (
            jax.ShapeDtypeStruct((q, ds.dim), jnp.int32),
            jax.ShapeDtypeStruct((q, ds.dim), jnp.int32),
        )
        params = SearchParams(k=ds.default_k, max_count=ds.dim, use_kernel=False)

    t0 = time.perf_counter()
    with jax.sharding.set_mesh(mesh):
        # segmented shard layout: data is segments concatenated in global-id
        # order and padded up to mesh divisibility (SegmentedIndex.concat_data);
        # n_objects masks the ragged pad tail out of every shard's buffer.
        # The plan is built once and both costed (describe) and lowered
        # (executable) -- the dry-run prices exactly the program that serves.
        plan = plan_lib.plan_search(
            ds.engine, params.k, params.max_count,
            layout=plan_lib.Layout.DISTRIBUTED, n_objects=ds.n_objects,
            use_kernel=params.use_kernel,
            hierarchical=(mesh_kind == "multi"
                          and tuple(mesh.axis_names)[0] == "pod"),
            mesh_axes=tuple(mesh.axis_names),
        )
        step = plan_lib.executable(plan, mesh=mesh)
        lowered = step.lower(data_sds, query_sds)
        compiled = lowered.compile()
        # routed serving variant (core/routing.py): same sharded layout plus
        # the replicated shard_active mask operand that blanks unrouted
        # shards' candidate buffers.  Lowered + compiled alongside the full
        # scan so the dry-run prices both programs the service can dispatch
        # (ROUTED_VERIFIED's fallback re-runs this same executable with an
        # all-ones mask, so these two cells are the entire serving surface).
        routed_plan = plan_lib.plan_search(
            ds.engine, params.k, params.max_count,
            layout=plan_lib.Layout.DISTRIBUTED, n_objects=ds.n_objects,
            use_kernel=params.use_kernel,
            hierarchical=(mesh_kind == "multi"
                          and tuple(mesh.axis_names)[0] == "pod"),
            mesh_axes=tuple(mesh.axis_names),
            routing="routed_verified",
        )
        t1 = time.perf_counter()
        routed_step = plan_lib.executable(routed_plan, mesh=mesh)
        routed_lowered = routed_step.lower(
            data_sds, query_sds, jax.ShapeDtypeStruct((n_dev,), jnp.int32))
        routed_compiled = routed_lowered.compile()
        routed_seconds = time.perf_counter() - t1
    rep = _report(lowered, compiled, time.perf_counter() - t0)
    rep["plan"] = plan.describe()
    rep["routing"] = _report(routed_lowered, routed_compiled, routed_seconds)
    rep["routing"]["plan"] = routed_plan.describe()
    # tuned-plan pricing (core/autotune.py): when this machine's measured
    # knob cache holds an entry for the dataset's shape, lower + compile the
    # tuned variant of the same cell next to the default, so the dry-run
    # prices exactly what a tuned service would dispatch.  No entry (the
    # common CI case) -> fingerprint recorded, nothing extra compiled.
    from repro.core import autotune as autotune_lib

    rep["autotune"] = dict(fingerprint=autotune_lib.hardware_fingerprint(),
                           entry=None)
    tune_cache = autotune_lib.resolve_cache(True)
    entry = (tune_cache.lookup(ds.engine, "wide", n=ds.n_objects)
             if tune_cache is not None else None)
    if entry is not None:
        rep["autotune"]["entry"] = entry.to_dict()
        t2 = time.perf_counter()
        with jax.sharding.set_mesh(mesh):
            tuned_plan = plan_lib.plan_search(
                ds.engine, params.k, params.max_count,
                layout=plan_lib.Layout.DISTRIBUTED, n_objects=ds.n_objects,
                use_kernel=params.use_kernel,
                hierarchical=(mesh_kind == "multi"
                              and tuple(mesh.axis_names)[0] == "pod"),
                mesh_axes=tuple(mesh.axis_names),
                autotune=tune_cache,
                tune_width=ds.m if ds.engine != "range" else ds.dim,
            )
            tuned_step = plan_lib.executable(tuned_plan, mesh=mesh)
            tuned_compiled = tuned_step.lower(data_sds, query_sds).compile()
        tuned_rep = _report(None, tuned_compiled, time.perf_counter() - t2)
        tuned_rep["plan"] = tuned_plan.describe()
        rep["autotune"]["tuned"] = tuned_rep
    # Pallas kernel cost model (per device): the deployable TPU path streams
    # the signature matrix once per query batch with VMEM-resident count
    # tiles; the XLA fallback engine recorded above re-reads its [Q, N]
    # accumulator every m/chunk scan step.  Both are reported; roofline uses
    # the kernel model for GENIE rows.
    n_local = n // n_dev
    width = ds.m if ds.engine != "range" else ds.dim
    if ds.engine in ("minsum", "ip"):
        sig_bytes = 1
    elif ds.engine == "eq":
        sig_bytes = 1 if ds.n_buckets <= 127 else (2 if ds.n_buckets <= 32767 else 4)
    else:
        sig_bytes = 4
    kernel_flops = float(q) * n_local * width + float(q) * n_local  # match + hist
    if ds.engine == "ip":
        kernel_flops = 2.0 * q * n_local * width
    kernel_bytes = (
        n_local * width * sig_bytes        # signature/count matrix, read once
        + q * width * sig_bytes            # queries
        + 2.0 * q * n_local                # int8 counts write + hist read
    )
    rep.update(
        n_objects=int(n), n_queries=int(q), engine=ds.engine,
        # match cost: Q*N signature compares (the paper's "match" stage)
        model_flops=float(q) * n * (ds.m if ds.engine != "range" else ds.dim),
        kernel_model=dict(flops=kernel_flops, bytes_accessed=kernel_bytes),
    )
    # per-segment accounting for the streaming-ingest plan (core/segments.py):
    # the corpus arrives in 16 add()-sized batches, compacted 2:1 at serve
    # time; pad_rows is the ragged tail masked by the n_objects layout above.
    from repro.core import segments as seg_lib

    ingest_rows = seg_lib.even_segments(ds.n_objects, 16)
    compacted_rows = [sum(ingest_rows[i:i + 2]) for i in range(0, len(ingest_rows), 2)]
    rep["segmented"] = dict(
        pad_rows=int(n - ds.n_objects),
        ingest=seg_lib.layout_accounting(ingest_rows, width * sig_bytes),
        compacted=seg_lib.layout_accounting(compacted_rows, width * sig_bytes),
    )
    # signature-storage accounting (core/packing.py): wide vs PACKED bytes
    # per object, and the per-segment layouts a PACKED seal would produce.
    # The paper's five datasets serve WIDE-only engines (eq/minsum/ip/range
    # have no packed format), so packed reports None here; simhash/minhash
    # services (COSINE/TANIMOTO) shrink by the ratio gated in
    # benchmarks/roofline.py.
    from repro.core import engines as engines_lib

    model = engines_lib.get(ds.engine)
    packed_row_bytes = None
    if model.supports_packed:
        row_sds = jax.ShapeDtypeStruct((1, width), jnp.int32)
        packed_row_bytes = int(model.packed_bytes(row_sds))
    rep["segmented"]["signatures"] = dict(
        packed_supported=model.supports_packed,
        bytes_per_object_wide=int(width * sig_bytes),
        bytes_per_object_packed=packed_row_bytes,
        ingest_packed=(seg_lib.layout_accounting(ingest_rows, packed_row_bytes)
                       if packed_row_bytes else None),
        compacted_packed=(seg_lib.layout_accounting(compacted_rows, packed_row_bytes)
                          if packed_row_bytes else None),
    )
    return rep


# ---------------------------------------------------------------------------

def cell_path(kind: str, name: str, shape: str, mesh_kind: str) -> str:
    os.makedirs(REPORT_DIR, exist_ok=True)
    return os.path.join(REPORT_DIR, f"{kind}__{name}__{shape}__{mesh_kind}.json")


def run_and_save(kind: str, name: str, shape: str, mesh_kind: str, force: bool = False) -> dict:
    path = cell_path(kind, name, shape, mesh_kind)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    print(f"[dryrun] {kind} {name} {shape} {mesh_kind} ...", flush=True)
    try:
        rep = run_lm_cell(name, shape, mesh_kind) if kind == "lm" else run_genie_cell(name, mesh_kind)
    # Sweep boundary: a cell failure is a bug, but it must be recorded in
    # the grid (ok=False + traceback), not kill the remaining cells of an
    # hours-long compile sweep.
    # genielint: ignore[broad-except]
    except Exception as e:  # a failure here is a bug -- record it loudly
        rep = dict(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    rep.update(kind=kind, name=name, shape=shape, mesh=mesh_kind)
    with open(path, "w") as f:
        json.dump(rep, f, indent=1)
    status = "OK" if rep.get("ok") else "FAIL"
    if rep.get("skipped"):
        status = "SKIP"
    print(f"[dryrun] {kind} {name} {shape} {mesh_kind}: {status} "
          f"({rep.get('compile_seconds', 0)}s)", flush=True)
    jax.clear_caches()
    return rep


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(shapes_lib.SHAPES) + [None])
    ap.add_argument("--mesh", default=None, choices=["single", "multi", None])
    ap.add_argument("--genie", action="store_true", help="run GENIE search cells")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = [args.mesh] if args.mesh else ["single", "multi"]
    failures = 0
    if args.genie or args.all:
        from repro.configs.genie_datasets import DATASETS
        for name in DATASETS:
            for mk in meshes:
                rep = run_and_save("genie", name, "search_1024q", mk, args.force)
                failures += 0 if rep.get("ok") else 1
    if not args.genie or args.all:
        archs = [args.arch] if args.arch else ALL_ARCHS
        shapes = [args.shape] if args.shape else list(shapes_lib.SHAPES)
        for arch in archs:
            for shape in shapes:
                for mk in meshes:
                    rep = run_and_save("lm", arch, shape, mk, args.force)
                    failures += 0 if rep.get("ok") else 1
    print(f"[dryrun] done, failures={failures}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
