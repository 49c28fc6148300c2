"""c-PQ: Count Priority Queue (paper section III-C), TPU-native formulation.

The paper's c-PQ keeps a dense low-bit Bitmap Counter for every object, a Gate
(ZipperArray ZA + AuditThreshold AT) fed by atomic updates, and a small Hash
Table holding only objects whose count passed AT.  Theorem 3.1: when the scan
finishes, ZA[AT] < k <= ZA[AT-1], the k-th match count MC_k == AT - 1, and the
top-k candidates all sit in the Hash Table (|HT| = O(k * AT)).

TPU adaptation (DESIGN.md section 2): counts live in a bounded domain
[0, max_count], so the Gate state is reconstructed *exactly* from a count
histogram -- ZA[t] == #(count_n >= t) == suffix-sum of the histogram --
without any atomics:

  phase 1 (histogram):  hist[q, t] = #(counts[q, n] == t)   (Pallas kernel)
  phase 2 (gate):       AT = min(t >= 1 : ZA[t] < k);  threshold = AT - 1
  phase 3 (hash table): two-class compaction (strict > threshold first, then
                        ties == threshold, each in id order) into a fixed
                        buffer of size cap -- the Hash-Table analogue --
                        over blocks of 128 ids: a kernel counts each
                        block's strict objects and ties; a prefix over
                        blocks places each slot in its block; a DMA of
                        that block's 128-id window gives its lane.  One
                        read of the counts and cap windows per row; no
                        sort of N, no scatter over N, no element gathers.

Only the final cap-sized buffer (cap ~ 2k << N) is ordered, reproducing the
paper's "scan the small HT once" property.  Exactness versus a full sort is
property-tested in tests/test_cpq.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import SearchParams, TopKResult
from repro.kernels import common, ops as kops
from repro.kernels import cpq_compact as _cpq_compact
from repro.runtime import tracing


def count_histogram(counts: jnp.ndarray, max_count: int, bin_chunk: int = 8) -> jnp.ndarray:
    """hist[q, t] = #{n : counts[q, n] == t},  t in [0, max_count].

    lax.scan over bin chunks keeps the one-hot temp at [Q, N, bin_chunk]
    (a full [Q, N, max_count+1] one-hot is ~17 GB/device for the paper-scale
    SIFT cell; the Pallas kernel streams N tiles instead)."""
    nbins = max_count + 1
    c = counts.astype(jnp.int32)
    n_chunks = -(-nbins // bin_chunk)

    def step(_, start):
        bins = start + jnp.arange(bin_chunk, dtype=jnp.int32)
        part = jnp.sum((c[..., None] == bins).astype(jnp.int8), axis=1)
        return None, part.astype(jnp.int32)                  # [Q, bin_chunk]

    _, parts = jax.lax.scan(
        step, None, jnp.arange(n_chunks, dtype=jnp.int32) * bin_chunk
    )
    hist = jnp.moveaxis(parts, 0, 1).reshape(c.shape[0], n_chunks * bin_chunk)
    return hist[:, :nbins]


def zipper_array(hist: jnp.ndarray) -> jnp.ndarray:
    """ZA[q, t] = #{n : count >= t} (suffix sum of hist over the count axis)."""
    rev = jnp.flip(hist, axis=-1)
    return jnp.flip(jnp.cumsum(rev, axis=-1), axis=-1)


@tracing.scoped(tracing.GATE)
def audit_threshold(hist: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gate: AT[q] = min{t >= 1 : ZA[t] < k} (== max_count+1 when none).

    Returns (at, threshold) with threshold = AT - 1 == MC_k (Theorem 3.1).
    """
    za = zipper_array(hist)                      # [Q, max_count+1]
    max_count = hist.shape[-1] - 1
    below = za[:, 1:] < k                        # t = 1 .. max_count
    any_below = jnp.any(below, axis=-1)
    first = jnp.argmax(below, axis=-1) + 1       # first t with ZA[t] < k
    at = jnp.where(any_below, first, max_count + 1).astype(jnp.int32)
    return at, at - 1


@tracing.scoped(tracing.COMPACT)
def _compact_candidates(
    counts: jnp.ndarray, threshold: jnp.ndarray, cap: int, *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two-class compaction into a cap-sized buffer per query.

    Row q of the output holds the objects with count > threshold ("strict",
    provably < k of them by the Gate) in id order, then the ties
    (== threshold) in id order (the paper breaks ties randomly), cut at cap.
    Returns (ids [Q, cap], vals [Q, cap]), empty slots marked id=-1, val=-1.

    Blocked, in three steps over blocks of 128 consecutive ids:

      1. block counts: one kernel pass over the counts gives each block's
         number of strict objects and of ties (kernels/cpq_compact.py);
      2. place each slot: slot j wants the (j + 1)-th strict object while
         j < #strict, else the (j + 1 - #strict)-th tie.  Running sums over
         the totals of groups of 128 blocks give its group, then over that
         group's 128 block counts (taken by a one-hot product on the MXU)
         its block, and the rank left to find inside the block;
      3. window rank: a second kernel copies each slot's block, a whole
         128-id window, by DMA; the slot's object is the lane where the
         running count of its class reaches that rank.

    Steps 2 and 3 compare and sum over [Q, S, N / 16384] and [Q, S, 128]
    (S = cap rounded up to 128): no search, no gather of single elements
    and no scatter over N, which the TPU serialises per element, and
    nothing of size [Q, N] beside the counts.
    """
    q, n = counts.shape
    block = _cpq_compact.BLOCK
    c = counts.astype(jnp.int32)
    thr = threshold.astype(jnp.int32)
    strict, ties = kops.cpq_block_counts(c, thr, interpret=interpret)
    n_strict = jnp.sum(strict, axis=(1, 2))[:, None]
    slot = jnp.arange(common.ceil_to(cap, block), dtype=jnp.int32)[None, :]
    is_strict = slot < n_strict
    target = jnp.where(is_strict, slot + 1, slot + 1 - n_strict)

    def pick(s, t):              # each slot's row of its own class
        return jnp.where(is_strict[..., None], s, t)

    group, left = _place(pick(jnp.sum(strict, -1)[:, None],
                              jnp.sum(ties, -1)[:, None]), target)
    onehot = jnp.arange(strict.shape[1]) == group[..., None]

    def rows(x):
        # the slot's group of 128 block counts; exact in bfloat16: each
        # count is at most 128 and each output one count
        return jnp.einsum("qsg,qgb->qsb", onehot.astype(jnp.bfloat16),
                          x.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32).astype(jnp.int32)

    b, left = _place(pick(rows(strict), rows(ties)), left)
    blk = jnp.minimum(group * block + b, -(-n // block) - 1)
    win = kops.cpq_slot_windows(c, blk, interpret=interpret)   # [Q, S, 128]
    # the lanes past N follow every object of the last block, so the rank
    # is reached before them and they need no mask
    t = thr[:, None, None]
    lane, _ = _place(pick(win > t, win == t).astype(jnp.int32), left)
    vals = jnp.sum(jnp.where(jnp.arange(block) == lane[..., None], win, 0),
                   axis=-1)
    filled = slot < n_strict + jnp.sum(ties, axis=(1, 2))[:, None]
    out_ids = jnp.where(filled, blk * block + lane, -1)
    out_vals = jnp.where(filled, vals, -1)
    return out_ids[:, :cap], out_vals[:, :cap]


def _place(cnt: jnp.ndarray, target: jnp.ndarray):
    """Per slot, the first position p of its row cnt [Q, S, W] at which the
    running sum reaches target [Q, S], and target less the sum before p."""
    upto = jnp.cumsum(cnt, axis=-1)
    before = upto < target[..., None]
    pos = jnp.sum(before, axis=-1, dtype=jnp.int32)
    return pos, target - jnp.max(jnp.where(before, upto, 0), axis=-1)


def topk_from_candidates(ids: jnp.ndarray, vals: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Order a candidate buffer by (count desc, id asc) and take k.

    This is the "scan the Hash Table once" step: a full stable sort of the
    buffer, O(B log B) for B slots per row.  B is small for c-PQ's cap
    slots and for merges of per-part k-buffers, but not for a fused
    kernel's per-tile buffer (core/plan.py `_fused_candidates_topk`),
    n_tiles * min(k, tile_n) slots, N/16 of them at k = 16: 39,024 per
    row for a 624,375-row segment, whose sort takes a fifth of a
    deep-image-96 search on a v5e, beside the kernel's four fifths.
    """
    vals = vals.astype(jnp.int32)
    # Stable argsort on -vals keeps id-ascending order within equal counts
    # (buffers are filled in id order).
    order = jnp.argsort(-vals, axis=-1, stable=True)
    top = order[..., :k]
    return (
        jnp.take_along_axis(ids, top, axis=-1),
        jnp.take_along_axis(vals, top, axis=-1),
    )


def cpq_select(
    counts: jnp.ndarray,
    params: SearchParams,
    hist: jnp.ndarray | None = None,
) -> TopKResult:
    """Exact top-k by match count via the c-PQ gate.  counts: int [Q, N].

    `hist` may be supplied by the fused Pallas kernel (kernels/cpq_hist); when
    None it is computed with the pure-jnp reference.
    """
    if hist is None:
        with tracing.scope(tracing.HIST):
            hist = count_histogram(counts, params.max_count)
    _, threshold = audit_threshold(hist, params.k)
    cap = params.cap()
    cand_ids, cand_vals = _compact_candidates(counts, threshold, cap)
    with tracing.scope(tracing.ORDER):
        ids, vals = topk_from_candidates(cand_ids, cand_vals, params.k)
    return TopKResult(ids=ids, counts=vals, threshold=threshold)


def sort_select(counts: jnp.ndarray, params: SearchParams) -> TopKResult:
    """Baseline: full sort-based top-k (lax.top_k over all N)."""
    vals, ids = jax.lax.top_k(counts.astype(jnp.int32), params.k)
    return TopKResult(ids=ids.astype(jnp.int32), counts=vals, threshold=vals[:, -1])
