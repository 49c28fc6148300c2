"""Ahead-of-time compiles of the served kernels for a TPU v5e, at real widths.

Nothing runs: each case lowers a kernel wrapper for a described (not
attached) v5e chip and lets the TPU compiler accept or refuse it.  This is
what decides whether a kernel's blocks and in-kernel temporaries fit VMEM
and whether Mosaic can lower it -- interpret mode on the CPU checks neither.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, and a test
worker that is not given this file must not touch it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import autotune
from repro.core import plan as plan_lib
from repro.core.types import Engine
from repro.kernels import common, ops

# one SIFT segment (4.5M points in 16 sealed adds) against a Q = 1024 batch
SEG_ROWS = 281_250
Q = 1024
M = 237             # E2LSH hash functions (configs/genie_datasets.M_PRACTICAL)
K_BUCKET = 128      # k = 100 rounded up the way the front-end dispatches it
HBM_BYTES = 15.75 * 2**30   # what a v5e chip offers a program


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


KERNELS = {
    "match_count": (
        lambda d, q: ops.match_count(d, q, interpret=False),
        [((SEG_ROWS, M), jnp.int32), ((Q, M), jnp.int32)]),
    "cpq_hist": (
        lambda c: ops.cpq_hist(c, M, interpret=False),
        [((Q, SEG_ROWS), jnp.int32)]),
    # Adult's one segment at a 128-row dispatch, counts in 0..14: D = 4
    "cpq_hist_adult": (
        lambda c: ops.cpq_hist(c, 14, interpret=False),
        [((128, 980_000), jnp.int32)]),
    "packed_cosine_count": (
        lambda d, q: ops.packed_cosine_count(d, q, interpret=False),
        [((SEG_ROWS, 8), jnp.int32), ((Q, 8), jnp.int32)]),
    "packed_cosine_topk": (
        lambda d, q: ops.packed_cosine_topk(d, q, k=K_BUCKET, interpret=False),
        [((SEG_ROWS, 8), jnp.int32), ((Q, 8), jnp.int32)]),
    "packed_tanimoto_topk": (
        lambda d, q: ops.packed_tanimoto_topk(d, q, k=K_BUCKET, interpret=False),
        [((SEG_ROWS, M), jnp.uint8), ((Q, M), jnp.uint8)]),
    "packed_tanimoto_count": (
        lambda d, q: ops.packed_tanimoto_count(d, q, interpret=False),
        [((SEG_ROWS, M), jnp.uint8), ((Q, M), jnp.uint8)]),
    "tanimoto_count": (
        lambda d, q: ops.tanimoto_count(d, q, interpret=False),
        [((SEG_ROWS, M), jnp.int32), ((Q, M), jnp.int32)]),
    "range_count": (
        lambda d, lo, hi: ops.range_count(d, lo, hi, interpret=False),
        [((SEG_ROWS, 14), jnp.int32), ((Q, 14), jnp.int32), ((Q, 14), jnp.int32)]),
    "minsum_count": (
        lambda d, q: ops.minsum_count(d, q, interpret=False),
        [((SEG_ROWS, 512), jnp.int32), ((Q, 512), jnp.int32)]),
    "ip_count": (
        lambda d, q: ops.ip_count(d, q, interpret=False),
        [((SEG_ROWS, 512), jnp.int8), ((Q, 512), jnp.int8)]),
    "cosine_count": (
        lambda d, q: ops.cosine_count(d, q, interpret=False),
        [((SEG_ROWS, 256), jnp.int8), ((Q, 256), jnp.int8)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, *(_spec(one_chip, s, dt) for s, dt in shapes))
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.mark.parametrize("knob", ["tile_q", "tile_n"])
def test_largest_autotune_tiles_compile(knob, one_chip):
    """The largest tile the autotuner's VMEM model admits on each axis, the
    other at its default, compiles for EQ at the SIFT shapes: the model
    never hands the tuner a candidate the chip's compiler refuses."""
    dims = {"tile_q": Q, "tile_n": SEG_ROWS}
    admitted = [c for c in autotune.tile_candidates(knob, dims[knob])
                if autotune._vmem_estimate({knob: c}, Q, SEG_ROWS, M)
                <= autotune.VMEM_BUDGET_BYTES]
    tile = max(admitted)
    assert tile > 128, admitted        # the model admits more than the default
    compiled = _compile(
        lambda d, q: ops.match_count(d, q, interpret=False, **{knob: tile}),
        _spec(one_chip, (SEG_ROWS, M)), _spec(one_chip, (Q, M)))
    assert "tpu_custom_call" in compiled.as_text(), (knob, tile)


def test_sift_segment_program_compiles_and_fits(one_chip, monkeypatch):
    """The served per-segment program (match -> fused c-PQ histogram ->
    select) for one SIFT segment at Q = 1024 fits beside the resident
    corpus.  The plan's kernels resolve `interpret` from the default backend,
    which is the CPU here, so the test pins them to compiled mode."""
    monkeypatch.setattr(common, "use_interpret",
                        lambda interpret: False if interpret is None else interpret)
    plan = plan_lib.plan_search(Engine.EQ, K_BUCKET, M,
                                layout=plan_lib.Layout.SEGMENTED,
                                part_rows=(SEG_ROWS,) * 16)
    assert plan.fused_hist
    fn = plan_lib._part_fn(plan, SEG_ROWS)
    compiled = fn.lower(_spec(one_chip, (SEG_ROWS, M)), _spec(one_chip, (Q, M)),
                        _spec(one_chip, ()), _spec(one_chip, ())).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2       # match + histogram
    mem = compiled.memory_analysis()
    corpus = 16 * SEG_ROWS * M * 4                  # every segment stays resident
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert corpus + used < HBM_BYTES, (corpus, used)


def test_deep96_segment_program_compiles_and_fits(one_chip, monkeypatch):
    """The served per-segment program of the deep-image-96-angular
    deployment (9.99M SimHash rows, 237 bits packed into 8 words, 16 equal
    segments) at a 128-row dispatch, k = 10 bucketed to 16: the fused match
    -> count -> local top-k kernel, then the sort of its candidate buffer,
    fits beside the resident packed corpus."""
    monkeypatch.setattr(common, "use_interpret",
                        lambda interpret: False if interpret is None else interpret)
    rows = 624_375
    plan = plan_lib.plan_search(Engine.COSINE, 16, M,
                                layout=plan_lib.Layout.SEGMENTED,
                                part_rows=(rows,) * 16, signature_layout="packed")
    assert plan.fused_match is not None
    fn = plan_lib._part_fn(plan, rows)
    compiled = fn.lower(_spec(one_chip, (rows, 8)), _spec(one_chip, (128, 8)),
                        _spec(one_chip, ()), _spec(one_chip, ())).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "packed_cosine_topk" in text
    mem = compiled.memory_analysis()
    corpus = 16 * rows * 8 * 4
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert corpus + used < HBM_BYTES, (corpus, used)
