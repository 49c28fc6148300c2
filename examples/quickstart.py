"""GENIE quickstart: build an inverted index through the MatchModel registry,
run a batched tau-ANN search, and inspect the c-PQ guarantees.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Engine, GenieIndex, SegmentedIndex, TopKMethod, engines
from repro.core.segments import even_segments
from repro.core import lsh as lsh_lib
from repro.core.lsh import tau_ann
from repro.data.pipeline import synthetic_points


def main():
    # 0. the registry is the system's single dispatch point: every engine is
    #    one descriptor, every search path resolves through it
    print("registered engines:",
          ", ".join(e.value for e in engines.available()))
    print("registered LSH schemes:", ", ".join(lsh_lib.scheme_names()))

    # 1. data: 20K clustered points (SIFT-like stand-in)
    pts, _ = synthetic_points(20_000, dim=32, n_clusters=64, seed=0)

    # 2. LSH transform via the scheme registry: the paper's practical m
    #    (Fig 8) at eps = delta = 0.06
    m = tau_ann.required_m(0.06, 0.06)
    print(f"hash functions m = {m} (paper: 237; Theorem 4.1 bound: "
          f"{tau_ann.m_theorem41(0.06, 0.06)})")
    scheme = lsh_lib.get_scheme("e2lsh")
    params = scheme.make_params(jax.random.PRNGKey(0), d=32, m=m, w=4.0, n_buckets=67)
    sigs = scheme.hash_points(params, jnp.asarray(pts))

    # 3. build the index: the generic registry builder (named aliases like
    #    build_lsh remain as thin wrappers)
    index = GenieIndex.build(Engine.EQ, sigs, use_kernel=False)
    print(f"index: {index.stats.n_objects} objects, "
          f"{index.stats.bytes_device/1e6:.1f} MB on device "
          f"(engine={index.stats.extra['engine']})")

    # 4. batched search: 128 noisy queries
    rng = np.random.default_rng(1)
    q = pts[:128] + rng.standard_normal((128, 32)).astype(np.float32) * 0.1
    qsigs = scheme.hash_points(params, jnp.asarray(q))
    res = index.search(qsigs, k=10, method=TopKMethod.CPQ)

    hit = float(np.mean(np.asarray(res.ids)[:, 0] == np.arange(128)))
    print(f"top-1 self-retrieval: {hit:.3f}")
    print(f"MC_k threshold (Theorem 3.1, AT-1) for query 0: {int(res.threshold[0])}")
    sims = tau_ann.mle_similarity(np.asarray(res.counts[:1]), m)
    print(f"similarity estimates (Eqn 7) for query 0: {np.round(sims, 3)}")

    # 5. multiple loading (paper section III-D): the same signatures sealed
    #    as 4 even parts held in host memory, streamed through the device one
    #    part per step -- identical counts, any registered engine
    parts = SegmentedIndex(engine=Engine.EQ, max_count=index.max_count,
                           use_kernel=False, host_resident=True)
    off = 0
    for rows in even_segments(index.stats.n_objects, 4):
        parts.add(sigs[off:off + rows])
        off += rows
    pres = parts.search(qsigs, k=10)
    same = bool(np.array_equal(np.asarray(res.counts), np.asarray(pres.counts)))
    print(f"multiload (4 streamed parts) counts identical: {same}")

    # 6. the same machinery, different measures: sign-quantized cosine
    #    (simhash bits -> COSINE sign agreements on the MXU) and Jaccard
    #    sketches (minhash -> TANIMOTO collision counts, FLASH-style)
    sub = jnp.asarray(pts[:4000])
    sh = lsh_lib.get_scheme("simhash")
    sh_params = sh.make_params(jax.random.PRNGKey(1), d=32, m=128)
    cos_idx = GenieIndex.build(sh.engine, sh.hash_points(sh_params, sub),
                               use_kernel=False)
    cres = cos_idx.search(sh.hash_points(sh_params, jnp.asarray(q[:16])), k=5)
    cos_hat = sh.mle(np.asarray(cres.counts[:1]), cos_idx.max_count)
    print(f"COSINE engine: top-1 self-retrieval "
          f"{float(np.mean(np.asarray(cres.ids)[:, 0] == np.arange(16))):.3f}, "
          f"cos estimates q0: {np.round(cos_hat[0], 3)}")

    # 6.5 incremental growth: seal each arriving batch into an immutable
    #     segment (O(batch) per add, no rebuild), search across segments with
    #     the exact cap-buffer merge, then compact -- results never change
    seg = SegmentedIndex(engine=Engine.EQ, max_count=m, use_kernel=False)
    for start in range(0, sigs.shape[0], 6000):       # uneven final batch
        seg.add(sigs[start:start + 6000])
    sres = seg.search(qsigs, k=10)
    same = bool(np.array_equal(np.asarray(res.ids), np.asarray(sres.ids)))
    print(f"segmented add ({seg.stats.n_segments} segments, rows "
          f"{seg.stats.segment_rows}): top-k identical to monolithic: {same}")
    seg.compact(max_segments=1)
    sres = seg.search(qsigs, k=10)
    print(f"after compact(1): {seg.stats.n_segments} segment, "
          f"{seg.stats.compaction_count} compaction, top-k identical: "
          f"{bool(np.array_equal(np.asarray(res.ids), np.asarray(sres.ids)))}")

    mh = lsh_lib.get_scheme("minhash")
    mh_params = mh.make_params(jax.random.PRNGKey(2), d=32, m=96, n_buckets=8192)
    tan_idx = GenieIndex.build(mh.engine, mh.hash_points(mh_params, sub),
                               use_kernel=False)
    tres = tan_idx.search(mh.hash_points(mh_params, jnp.asarray(q[:16])), k=5)
    print(f"TANIMOTO engine: top-1 self-retrieval "
          f"{float(np.mean(np.asarray(tres.ids)[:, 0] == np.arange(16))):.3f}, "
          f"Jaccard MLE q0: {np.round(mh.mle(np.asarray(tres.counts[:1]), 96)[0], 3)}")


if __name__ == "__main__":
    main()
