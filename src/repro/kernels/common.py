"""Shared utilities for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and compile
for it there.  On the CPU backend they run in interpret mode, executing the
kernel body through XLA for bit-exact validation against the ref.py oracles.
Any other backend is an error: a kernel never falls back to the interpreter
on an accelerator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def use_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's `interpret` flag: an explicit value wins; None
    means compiled on the TPU, interpreted on the CPU, and an error on any
    other backend."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and are interpreted only on the "
        f"CPU backend; the default backend is {backend!r}"
    )


def pad_to(x: jnp.ndarray, multiple: int, axis: int, value) -> jnp.ndarray:
    """Pad `axis` of x up to the next multiple with a constant."""
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - size)
    return jnp.pad(x, widths, constant_values=value)


def ceil_to(size: int, multiple: int) -> int:
    return -(-size // multiple) * multiple


def pick_tile(size: int, preferred: int, align: int, knob: str = "tile") -> int:
    """Tile size: `preferred` when the dim is big enough, else the whole
    (alignment-padded) dim.

    `preferred` may come from a tuned plan (core/autotune.py), so a bad value
    fails loudly with the caller's knob name instead of emitting a degenerate
    grid: alignment must be positive and `preferred` must reach the alignment
    floor (the TPU min-tile lane/sublane width the kernels assume)."""
    align = int(align)
    preferred = int(preferred)
    if align <= 0:
        raise ValueError(
            f"{knob}: tile alignment must be > 0, got align={align}"
        )
    if preferred < align:
        raise ValueError(
            f"{knob}={preferred} is below the alignment floor {align}: a "
            f"sub-aligned tile would emit a degenerate grid; tuned tiles "
            f"must be multiples of the min-tile width (>= {align})"
        )
    if size >= preferred:
        return preferred
    return ceil_to(max(size, 1), align)


# ---------------------------------------------------------------------------
# The column sweep shared by the VPU count kernels
# ---------------------------------------------------------------------------
#
# A count kernel sums one elementwise term per signature column i:
#   acc[q, n] += combine(query[q, i], data[n, i]).
# Each term is computed on a whole 2-D [TQ, TN] tile, so every lane does
# work and no 3-D temporary (whose narrow minor axis pads to 128 lanes)
# ever lives in VMEM.  For that, query column i must be a [TQ, 1] column
# and data column i a [1, TN] row:
#   queries  grouped, [Mp/G, Q, G]: group g is a [TQ, G] tile on the leading
#            axis, and its static lane j is column g*G + j;
#   data     transposed, [Mp, N]: column i is row i of a [Mp, TN] tile.
# The sweep is a lax.fori_loop over groups with a static G-step body, so
# compile time does not grow with the signature width.

GROUP = 8          # columns per loop step for 32-bit data (one sublane group)
GROUP_BYTES = 32   # columns per loop step for 8-bit data (its sublane tile)


def column_sweep(q_refs, d_ref, combine, acc: jnp.ndarray, *,
                 group: int = GROUP) -> jnp.ndarray:
    """acc + sum over the block's columns of combine(*q_cols, d_row).

    q_refs: grouped query blocks [n_groups, TQ, group] (several when a
    query has several parts, e.g. RANGE's lo and hi); d_ref: transposed
    data block [n_groups * group, TN].  combine maps [TQ, 1] query columns
    and a [1, TN] int32 data row to an int32 [TQ, TN] term."""
    n_groups = q_refs[0].shape[0]

    def step(g, acc):
        qs = [r[g] for r in q_refs]                                # [TQ, G]
        rows = d_ref[pl.ds(pl.multiple_of(g * group, group), group), :]
        rows = rows.astype(jnp.int32)                              # [G, TN]
        for j in range(group):
            cols = [qg[:, j:j + 1] for qg in qs]
            acc = acc + combine(*cols, rows[j:j + 1, :])
        return acc

    return jax.lax.fori_loop(0, n_groups, step, acc)
