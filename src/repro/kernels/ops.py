"""Jit'd public wrappers around the Pallas kernels.

Each wrapper pads inputs to tile multiples (with values that cannot produce
spurious matches; cpq_hist masks its ragged edge tiles in the kernel
instead), lays them out the way the kernel reads them (the VPU count
kernels take grouped queries and transposed data, see
common.column_sweep), dispatches to the kernel (compiled on the TPU,
interpreted on the CPU backend), and slices the result back to logical
shape.  These are the functions the GENIE engines call; repro.kernels.ref
holds the oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels import cosine_count as _cos
from repro.kernels import cpq_compact as _cpq_compact
from repro.kernels import cpq_hist as _cpq_hist
from repro.kernels import ip_count as _ip
from repro.kernels import match_count as _mc
from repro.kernels import minsum_count as _ms
from repro.kernels import packed_cosine as _pcos
from repro.kernels import packed_tanimoto as _ptan
from repro.kernels import range_count as _rc
from repro.kernels import tanimoto_count as _tc

# Padding sentinels: data and query pads differ so padded rows/cols never match.
_PAD_DATA = -1
_PAD_QUERY = -2


def _tiles(q: int, n: int, tq_pref: int, tn_pref: int) -> tuple[int, int]:
    tq = common.pick_tile(q, tq_pref, 8, knob="tile_q")
    tn = common.pick_tile(n, tn_pref, 128, knob="tile_n")
    return tq, tn


def _grouped_queries(query, *, tq: int, cols: int, pad,
                     group: int = common.GROUP):
    """Queries for common.column_sweep: rows padded to tq and columns to a
    multiple of `cols` (itself a multiple of `group`) with `pad`, grouped
    to [Mp/group, Qp, group]."""
    q = common.pad_to(common.pad_to(query, tq, 0, pad), cols, 1, pad)
    return q.reshape(q.shape[0], -1, group).transpose(1, 0, 2)


def _transposed_data(data, *, tn: int, cols: int, pad):
    """Data for common.column_sweep: rows padded to tn and columns to a
    multiple of `cols` with `pad`, transposed to [Mp, Np]."""
    return common.pad_to(common.pad_to(data, tn, 0, pad), cols, 1, pad).T


def _sweep_layout(data, query, *, tq: int, tn: int, cols: int, d_pad, q_pad,
                  group: int = common.GROUP):
    """(transposed data, grouped queries) with distinct pad sentinels."""
    return (_transposed_data(data, tn=tn, cols=cols, pad=d_pad),
            _grouped_queries(query, tq=tq, cols=cols, pad=q_pad, group=group))


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "interpret"))
def match_count(
    data_sigs: jnp.ndarray,
    query_sigs: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """EQ engine kernel: counts int32 [Q, N]."""
    qn, m = query_sigs.shape
    nn = data_sigs.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _mc.TILE_Q, tile_n or _mc.TILE_N)
    d, q = _sweep_layout(data_sigs.astype(jnp.int32), query_sigs.astype(jnp.int32),
                         tq=tq, tn=tn, cols=common.GROUP,
                         d_pad=_PAD_DATA, q_pad=_PAD_QUERY)
    out = _mc.match_count_pallas(
        d, q, tile_q=tq, tile_n=tn, interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "interpret"))
def range_count(
    data_vals: jnp.ndarray,
    q_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """RANGE engine kernel: counts int32 [Q, N]."""
    qn, d = q_lo.shape
    nn = data_vals.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _rc.TILE_Q, tile_n or _rc.TILE_N)
    # Padded queries and attributes use an empty range (lo > hi); padded
    # data rows never matter because the output is sliced.
    x = _transposed_data(data_vals.astype(jnp.int32), tn=tn, cols=common.GROUP,
                         pad=_PAD_DATA)
    lo = _grouped_queries(q_lo.astype(jnp.int32), tq=tq, cols=common.GROUP, pad=1)
    hi = _grouped_queries(q_hi.astype(jnp.int32), tq=tq, cols=common.GROUP, pad=0)
    out = _rc.range_count_pallas(
        x, lo, hi, tile_q=tq, tile_n=tn, interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "tile_v", "interpret"))
def minsum_count(
    data_cnt: jnp.ndarray,
    query_cnt: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    tile_v: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """MINSUM engine kernel: counts int32 [Q, N]."""
    qn, v = query_cnt.shape
    nn = data_cnt.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _ms.TILE_Q, tile_n or _ms.TILE_N)
    tv = common.pick_tile(v, tile_v or _ms.TILE_V, 128, knob="tile_v")
    d, q = _sweep_layout(data_cnt.astype(jnp.int32), query_cnt.astype(jnp.int32),
                         tq=tq, tn=tn, cols=tv, d_pad=0, q_pad=0)
    out = _ms.minsum_count_pallas(
        d, q, tile_q=tq, tile_n=tn, tile_v=tv, interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "tile_v", "interpret"))
def ip_count(
    data_bin: jnp.ndarray,
    query_bin: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    tile_v: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """IP engine kernel: exact int32 counts [Q, N] (per-tile int32
    accumulation; no f32 magnitude bound)."""
    qn, v = query_bin.shape
    nn = data_bin.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _ip.TILE_Q, tile_n or _ip.TILE_N)
    tv = common.pick_tile(v, tile_v or _ip.TILE_V, 128, knob="tile_v")
    q = common.pad_to(common.pad_to(query_bin.astype(jnp.float32), tq, 0, 0), tv, 1, 0)
    d = common.pad_to(common.pad_to(data_bin.astype(jnp.float32), tn, 0, 0), tv, 1, 0)
    out = _ip.ip_count_pallas(
        d, q, tile_q=tq, tile_n=tn, tile_v=tv, interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "tile_m", "interpret"))
def tanimoto_count(
    data_sigs: jnp.ndarray,
    query_sigs: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    tile_m: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """TANIMOTO engine kernel: minhash collision counts int32 [Q, N]."""
    qn, m = query_sigs.shape
    nn = data_sigs.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _tc.TILE_Q, tile_n or _tc.TILE_N)
    tm = common.pick_tile(m, tile_m or _tc.TILE_M, 128, knob="tile_m")
    # Distinct sentinels on every padded axis: padded signature slots never
    # collide, padded rows/cols are sliced away.
    d, q = _sweep_layout(data_sigs.astype(jnp.int32), query_sigs.astype(jnp.int32),
                         tq=tq, tn=tn, cols=tm, d_pad=_PAD_DATA, q_pad=_PAD_QUERY)
    out = _tc.tanimoto_count_pallas(
        d, q, tile_q=tq, tile_n=tn, tile_m=tm, interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "tile_v", "interpret"))
def cosine_count(
    data_sgn: jnp.ndarray,
    query_sgn: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    tile_v: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """COSINE engine kernel: sign-agreement counts int32 [Q, N].

    Inputs are +-1 sign vectors; zero V-padding is dot-neutral and the kernel
    shifts by the logical V.  The kernel accumulates int32 (exact at any V).
    """
    qn, v = query_sgn.shape
    nn = data_sgn.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _cos.TILE_Q, tile_n or _cos.TILE_N)
    tv = common.pick_tile(v, tile_v or _cos.TILE_V, 128, knob="tile_v")
    q = common.pad_to(common.pad_to(query_sgn.astype(jnp.float32), tq, 0, 0), tv, 1, 0)
    d = common.pad_to(common.pad_to(data_sgn.astype(jnp.float32), tn, 0, 0), tv, 1, 0)
    out = _cos.cosine_count_pallas(
        d, q, v_logical=v, tile_q=tq, tile_n=tn, tile_v=tv,
        interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


# uint8 pad sentinels for packed TANIMOTO (buckets are capped at 253 by
# core/packing.py, so 254/255 can never collide with a real signature slot).
_PAD_DATA_U8 = 255
_PAD_QUERY_U8 = 254


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "interpret"))
def packed_cosine_count(
    data_words: jnp.ndarray,
    query_words: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Packed COSINE kernel: XOR+popcount agreement counts int32 [Q, N].

    Inputs are int32 word matrices from core/packing.py (query tail bits 1,
    data tail bits 0).  Pad rows are all-zero words -- their counts are
    garbage but sliced away; pad words are 0 on both sides, so XOR+popcount
    adds nothing for them.
    """
    qn, w = query_words.shape
    nn = data_words.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _pcos.TILE_Q, tile_n or _pcos.TILE_N)
    d, q = _sweep_layout(data_words.astype(jnp.int32), query_words.astype(jnp.int32),
                         tq=tq, tn=tn, cols=common.GROUP, d_pad=0, q_pad=0)
    out = _pcos.packed_cosine_count_pallas(
        d, q, bits_total=32 * w, tile_q=tq, tile_n=tn,
        interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


def _flatten_tiles(cand: jnp.ndarray, qn: int) -> jnp.ndarray:
    """Fused-kernel candidates [n_tiles, Qp, kc] -> [qn, n_tiles * kc]: tile
    j's (count desc, id asc) run lands at columns j*kc.., so the buffer
    stays id-ascending within equal counts across tiles."""
    n_tiles, qp, kc = cand.shape
    return cand.transpose(1, 0, 2).reshape(qp, n_tiles * kc)[:qn]


@functools.partial(jax.jit, static_argnames=("k", "tile_q", "tile_n", "interpret"))
def packed_cosine_topk(
    data_words: jnp.ndarray,
    query_words: jnp.ndarray,
    *,
    k: int,
    tile_q: int | None = None,
    tile_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused packed COSINE match->count->local-top-k.

    Returns (ids, counts) int32 [Q, n_tiles * min(k, tile_n)] candidate
    buffers in per-tile (count desc, id asc) order; ids are global object
    ids, pads are id -1 / count -1.  Data pad rows are masked in-kernel by
    global id, so they can never enter a tile's candidate list.
    """
    qn, w = query_words.shape
    nn = data_words.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _pcos.TILE_Q, tile_n or _pcos.TILE_N)
    d, q = _sweep_layout(data_words.astype(jnp.int32), query_words.astype(jnp.int32),
                         tq=tq, tn=tn, cols=common.GROUP, d_pad=0, q_pad=0)
    ids, cnts = _pcos.packed_cosine_topk_pallas(
        d, q, bits_total=32 * w, n_logical=nn, k=k, tile_q=tq, tile_n=tn,
        interpret=common.use_interpret(interpret)
    )
    return _flatten_tiles(ids, qn), _flatten_tiles(cnts, qn)


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "tile_m", "interpret"))
def packed_tanimoto_count(
    data_u8: jnp.ndarray,
    query_u8: jnp.ndarray,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    tile_m: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Packed TANIMOTO kernel: uint8 collision counts int32 [Q, N]."""
    qn, m = query_u8.shape
    nn = data_u8.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _ptan.TILE_Q, tile_n or _ptan.TILE_N)
    tm = common.pick_tile(m, tile_m or _ptan.TILE_M, 128, knob="tile_m")
    d, q = _sweep_layout(data_u8.astype(jnp.uint8), query_u8.astype(jnp.int32),
                         tq=tq, tn=tn, cols=tm, d_pad=_PAD_DATA_U8,
                         q_pad=_PAD_QUERY_U8, group=common.GROUP_BYTES)
    out = _ptan.packed_tanimoto_count_pallas(
        d, q, tile_q=tq, tile_n=tn, tile_m=tm, interpret=common.use_interpret(interpret)
    )
    return out[:qn, :nn]


@functools.partial(jax.jit, static_argnames=("k", "tile_q", "tile_n", "interpret"))
def packed_tanimoto_topk(
    data_u8: jnp.ndarray,
    query_u8: jnp.ndarray,
    *,
    k: int,
    tile_q: int | None = None,
    tile_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused packed TANIMOTO match->count->local-top-k (see
    packed_cosine_topk for the candidate-buffer contract)."""
    qn, m = query_u8.shape
    nn = data_u8.shape[0]
    tq, tn = _tiles(qn, nn, tile_q or _ptan.TILE_Q, tile_n or _ptan.TILE_N)
    d, q = _sweep_layout(data_u8.astype(jnp.uint8), query_u8.astype(jnp.int32),
                         tq=tq, tn=tn, cols=common.GROUP_BYTES, d_pad=_PAD_DATA_U8,
                         q_pad=_PAD_QUERY_U8, group=common.GROUP_BYTES)
    ids, cnts = _ptan.packed_tanimoto_topk_pallas(
        d, q, n_logical=nn, k=k, tile_q=tq, tile_n=tn,
        interpret=common.use_interpret(interpret)
    )
    return _flatten_tiles(ids, qn), _flatten_tiles(cnts, qn)


@functools.partial(jax.jit, static_argnames=("max_count", "tile_q", "tile_n", "interpret"))
def cpq_hist(
    counts: jnp.ndarray,
    max_count: int,
    *,
    tile_q: int | None = None,
    tile_n: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """c-PQ Gate histogram: int32 [Q, max_count + 1].

    The kernel counts over its digit grid of D * D bins (D from max_count,
    kernels/cpq_hist.py), lane-padded to a multiple of 128; the bins past
    max_count are sliced off here.  The count matrix is passed as it is:
    ragged edge tiles are masked in the kernel."""
    qn, nn = counts.shape
    base = _cpq_hist.digit_base(max_count)
    tq = common.pick_tile(qn, tile_q or _cpq_hist.query_tile(base), 8, knob="tile_q")
    tn = common.pick_tile(nn, tile_n or _cpq_hist.TILE_N, 128, knob="tile_n")
    out = _cpq_hist.cpq_hist_pallas(
        counts.astype(jnp.int32), max_count, tile_q=tq, tile_n=tn,
        interpret=common.use_interpret(interpret)
    )
    return out[:, : max_count + 1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cpq_block_counts(
    counts: jnp.ndarray,
    threshold: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """c-PQ compaction, first read: (strict, ties) int32 [Q, G, 128], the
    objects of each 128-id block with count > threshold[q] and
    == threshold[q], block b at [q, b // 128, b % 128] and 0 past
    cdiv(N, 128) (kernels/cpq_compact.py).  The count matrix is passed as
    it is: ragged edge tiles are masked in the kernel."""
    qn, nn = counts.shape
    tq = common.pick_tile(qn, _cpq_compact.TILE_Q, 8, knob="tile_q")
    tn = common.pick_tile(nn, _cpq_compact.TILE_N, _cpq_compact.BLOCK,
                          knob="tile_n")
    strict, ties = _cpq_compact.block_counts_pallas(
        counts.astype(jnp.int32), threshold.astype(jnp.int32), tile_q=tq,
        tile_n=tn, interpret=common.use_interpret(interpret))
    return (strict.reshape(qn, -1, _cpq_compact.BLOCK),
            ties.reshape(qn, -1, _cpq_compact.BLOCK))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cpq_slot_windows(
    counts: jnp.ndarray,
    blocks: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """c-PQ compaction, second read: int32 [Q, S, 128], slot j of row q
    the 128 counts of block blocks[q, j], ids 128 b .. 128 b + 127 (the
    lanes past N hold no object).  S % 128 == 0."""
    return _cpq_compact.slot_windows_pallas(
        counts.astype(jnp.int32), blocks.astype(jnp.int32),
        interpret=common.use_interpret(interpret))
