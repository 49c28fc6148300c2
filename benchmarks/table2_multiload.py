"""Paper Table II/III: multiple-loading scalability + extra-step costs.

Multiple loading is a SegmentedIndex sealed as `even_segments(n, parts)`
host-resident parts: each search puts one part at a time on the device.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, ann_dataset, query_sigs, timeit, timeit_host
from repro.core import Engine, GenieIndex, SegmentedIndex
from repro.core.segments import even_segments


def run() -> list[Row]:
    pts, _, params, sigs = ann_dataset()
    idx = GenieIndex.build_lsh(sigs, use_kernel=False)
    qs, _ = query_sigs(params, pts, np.arange(128) % pts.shape[0])
    qs_j = jnp.asarray(qs)
    rows = []
    base = timeit(lambda: idx.search(qs_j, k=100).ids)
    rows.append(Row("table2.single_load", base, ""))
    for parts in (2, 4, 8):
        seg = SegmentedIndex(engine=Engine.EQ, max_count=idx.max_count,
                             use_kernel=False, host_resident=True)
        off = 0
        for n_rows in even_segments(idx.stats.n_objects, parts):
            seg.add(sigs[off:off + n_rows])
            off += n_rows
        us = timeit(lambda s=seg: s.search(qs_j, k=100).ids)
        rows.append(Row(f"table2.multiload_p{parts}", us, f"vs_single={us/base:.2f}x"))
    # Table III extra steps: per-part transfer + final merge
    part = np.asarray(sigs[: sigs.shape[0] // 4])
    rows.append(Row("table3.part_transfer", timeit_host(
        lambda: jax.device_put(part).block_until_ready(), iters=3), f"bytes={part.nbytes}"))
    from repro.core import cpq as _cpq
    ids = jnp.tile(jnp.arange(100, dtype=jnp.int32)[None], (128, 4))
    cnts = jnp.tile(jnp.arange(400, 0, -1, dtype=jnp.int32)[None, :400], (128, 1))
    merge_fn = jax.jit(lambda i, c: _cpq.topk_from_candidates(i, c, 100)[0])
    rows.append(Row("table3.result_merge", timeit(merge_fn, ids, cnts), "4 parts x k=100"))
    return rows
