"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref

SHAPES = [(1, 5, 3), (3, 130, 17), (8, 300, 64), (5, 257, 33)]  # (Q, N, m)


@pytest.mark.parametrize("q,n,m", SHAPES)
@pytest.mark.parametrize("dtype", [np.int32, np.int16])
def test_match_count_sweep(q, n, m, dtype, rng):
    d = rng.integers(0, 9, size=(n, m)).astype(dtype)
    s = rng.integers(0, 9, size=(q, m)).astype(dtype)
    got = np.asarray(ops.match_count(jnp.asarray(d), jnp.asarray(s), tile_q=8, tile_n=128))
    want = np.asarray(ref.match_eq(jnp.asarray(d.astype(np.int32)), jnp.asarray(s.astype(np.int32))))
    assert got.shape == (q, n)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("q,n,d", [(2, 100, 5), (4, 300, 14), (1, 129, 31)])
def test_range_count_sweep(q, n, d, rng):
    x = rng.integers(0, 64, size=(n, d)).astype(np.int32)
    lo = rng.integers(0, 48, size=(q, d)).astype(np.int32)
    hi = lo + rng.integers(0, 20, size=(q, d)).astype(np.int32)
    got = np.asarray(ops.range_count(jnp.asarray(x), jnp.asarray(lo), jnp.asarray(hi),
                                     tile_q=8, tile_n=128))
    want = np.asarray(ref.match_range(jnp.asarray(x), jnp.asarray(lo), jnp.asarray(hi)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("q,n,v", [(2, 90, 64), (3, 260, 200), (1, 40, 513)])
@pytest.mark.parametrize("dtype", [np.int32, np.int8])
def test_minsum_count_sweep(q, n, v, dtype, rng):
    dc = rng.integers(0, 4, size=(n, v)).astype(dtype)
    qc = rng.integers(0, 4, size=(q, v)).astype(dtype)
    got = np.asarray(ops.minsum_count(jnp.asarray(dc), jnp.asarray(qc),
                                      tile_q=8, tile_n=128, tile_v=128))
    want = np.asarray(ref.match_minsum(jnp.asarray(dc.astype(np.int32)),
                                       jnp.asarray(qc.astype(np.int32))))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("q,n,v", [(2, 90, 64), (4, 300, 256)])
def test_ip_count_sweep(q, n, v, rng):
    db = (rng.random((n, v)) < 0.3).astype(np.int8)
    qb = (rng.random((q, v)) < 0.3).astype(np.int8)
    got = np.asarray(ops.ip_count(jnp.asarray(db), jnp.asarray(qb),
                                  tile_q=8, tile_n=128, tile_v=128))
    want = np.asarray(ref.match_ip(jnp.asarray(db), jnp.asarray(qb)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("q,n,m", [(1, 5, 3), (3, 130, 17), (2, 90, 600)])
def test_tanimoto_count_sweep(q, n, m, rng):
    """Collision counts with the signature axis tiled through the grid
    (FLASH-scale m streams through VMEM)."""
    d = rng.integers(0, 64, size=(n, m)).astype(np.int32)
    s = rng.integers(0, 64, size=(q, m)).astype(np.int32)
    got = np.asarray(ops.tanimoto_count(jnp.asarray(d), jnp.asarray(s),
                                        tile_q=8, tile_n=128, tile_m=128))
    want = np.asarray(ref.match_tanimoto(jnp.asarray(d), jnp.asarray(s)))
    assert got.shape == (q, n)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("q,n,v", [(2, 90, 33), (4, 300, 256), (1, 40, 513)])
def test_cosine_count_sweep(q, n, v, rng):
    """Sign-agreement counts via the +-1 MXU matmul, odd V included (the
    shift by logical V must ignore zero padding)."""
    db = rng.choice(np.array([-1, 1], np.int8), size=(n, v))
    qb = rng.choice(np.array([-1, 1], np.int8), size=(q, v))
    got = np.asarray(ops.cosine_count(jnp.asarray(db), jnp.asarray(qb),
                                      tile_q=8, tile_n=128, tile_v=128))
    want = np.asarray(ref.match_cosine(jnp.asarray(db), jnp.asarray(qb)))
    assert np.array_equal(got, want)
    assert got.min() >= 0 and got.max() <= v


SMALL_TILES = dict(tile_q=8, tile_n=128)
# The TPU interpreter, whose reads past an array's edge return 0, a real
# bin, as stale VMEM may on the chip (plain interpret mode returns INT_MIN,
# which no mask is needed to reject).
TPU_SIM = pltpu.InterpretParams(uninitialized_memory="zero")


@pytest.mark.parametrize("q,n,mx,tiles,pads", [
    (2, 100, 9, SMALL_TILES, 0),
    (4, 513, 31, SMALL_TILES, 0),
    (8, 64, 127, SMALL_TILES, 0),
    (16, 5000, 14, {}, 0),        # Adult's domain: D = 4, TQ = 16 of 32
    (16, 5000, 237, {}, 0),       # SIFT's domain: D = 16, TQ = 8
    (3, 700, 20, SMALL_TILES, 0),  # D = 8: bins 21..63 exist and read zero
    (40, 40_000, 14, {}, 0),      # Q % TQ (32) and N % TILE_N (16384) ragged
    (5, 3000, 237, {}, 300),      # -1 pad columns, as _mask_pad_counts sets
    (9, 1000, 63, SMALL_TILES, 50),
])
def test_cpq_hist_sweep(q, n, mx, tiles, pads, rng):
    counts = rng.integers(0, mx + 1, size=(q, n)).astype(np.int32)
    if pads:
        counts[:, n - pads:] = -1
        counts[rng.random((q, n)) < 0.05] = -1
    got = np.asarray(ops.cpq_hist(jnp.asarray(counts), mx, interpret=TPU_SIM,
                                  **tiles))
    want = np.asarray(ref.cpq_hist(jnp.asarray(counts), mx + 1))
    assert np.array_equal(got, want)
    assert got.sum(axis=1).max() <= n - pads


def test_cpq_hist_one_bin_holds_every_count():
    """A row of 70,001 equal counts fills one bin past 256 within a step
    and past 2**16 across steps: exact only if nothing accumulates in
    bfloat16 or int16."""
    n = 70_001
    counts = np.zeros((3, n), np.int32)
    counts[0] = 237
    counts[1] = 200
    counts[2, : n // 2] = 17
    got = np.asarray(ops.cpq_hist(jnp.asarray(counts), 237, interpret=TPU_SIM))
    want = np.zeros((3, 238), np.int32)
    want[0, 237] = want[1, 200] = n
    want[2, 17], want[2, 0] = n // 2, n - n // 2
    assert np.array_equal(got, want)


def test_kernel_vs_engine_end_to_end(rng):
    """GenieIndex with kernels on == engines off produce identical results."""
    from repro.core import GenieIndex

    sigs = rng.integers(0, 16, size=(300, 24)).astype(np.int32)
    qs = rng.integers(0, 16, size=(5, 24)).astype(np.int32)
    a = GenieIndex.build_lsh(sigs, use_kernel=True).search(qs, k=7)
    b = GenieIndex.build_lsh(sigs, use_kernel=False).search(qs, k=7)
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    assert np.array_equal(np.asarray(a.threshold), np.asarray(b.threshold))
