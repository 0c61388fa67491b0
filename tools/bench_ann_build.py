"""ANN at 1M scale (VERDICT r2 item 4 done-criterion).

Builds the repo's ANN indexes on a 1M x 128 corpus on the real TPU:

- HNSW via the device bulk-build path (engine/hnsw_build.py) — build
  vec/s + recall@10/QPS at several ef (host graph search).
- IVF-PQ (codes in posting lists + exact rescore) — build vec/s +
  QPS/recall@10 at several nprobe (device probe path).

Reference bar: hnsw/insert.go:226 is the production import path (Go,
~thousands of vec/s); a 1M build must be minutes, not hours, and serve
QPS@recall>=0.95.

Usage: PYTHONPATH=. python tools/bench_ann_build.py [--n 1000000]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--skip-hnsw", action="store_true")
    ap.add_argument("--skip-ivf", action="store_true")
    args = ap.parse_args()

    import numpy as np

    n, d, k = args.n, args.dim, 10
    rng = np.random.default_rng(0)
    # clustered mixture (the shape real embeddings have; bench.py uses the
    # same generator) — i.i.d. gaussian has no cluster structure at all,
    # which floors IVF recall by construction rather than measuring it
    n_clusters = max(n // 15, 1)
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, n)
    vecs = (centers[assign]
            + 0.35 * rng.standard_normal((n, d))).astype(np.float32)
    q = (vecs[rng.integers(0, n, args.queries)]
         + 0.05 * rng.standard_normal((args.queries, d))).astype(np.float32)
    sq = np.einsum("nd,nd->n", vecs, vecs)
    dmat = sq[None, :] - 2.0 * (q @ vecs.T)
    part = np.argpartition(dmat, k, 1)[:, :k]
    pd = np.take_along_axis(dmat, part, 1)
    gt = np.take_along_axis(part, np.argsort(pd, 1), 1)
    del dmat
    out = {"n": n, "dim": d}

    def recall_qps(idx, sweep_attr, values, batched=False):
        res = {}
        for v in values:
            setattr(idx, sweep_attr, v)
            if batched:
                # device path: one batched dispatch measures device QPS
                # (per-query calls would measure dispatch + fetch RTT)
                idx.search_by_vector_batch(q, k=k)  # warm/compile
                t0 = time.perf_counter()
                ids_b, _ = idx.search_by_vector_batch(q, k=k)
                dt = time.perf_counter() - t0
                hits = sum(len(set(ids_b[r].tolist()) & set(gt[r].tolist()))
                           for r in range(args.queries))
            else:
                t0 = time.perf_counter()
                hits = 0
                for r in range(args.queries):
                    ids, _ = idx.search_by_vector(q[r], k=k)
                    hits += len(set(ids.tolist()) & set(gt[r].tolist()))
                dt = time.perf_counter() - t0
            rec = hits / (args.queries * k)
            res[str(v)] = {"recall_at_10": round(rec, 4),
                           "qps": round(args.queries / dt, 1)}
            log(f"  {sweep_attr}={v}: recall {rec:.4f}, "
                f"{args.queries/dt:.0f} qps")
        return res

    # --- IVF-PQ -------------------------------------------------------------
    if args.skip_ivf:
        ivf_section = False
    else:
        ivf_section = True
    from weaviate_tpu.engine.ivf import IVFIndex

    idx = None if not ivf_section else IVFIndex(dim=d, train_threshold=min(n, 200_000),
                   delta_threshold=65536, quantization="pq")
    if ivf_section:
        t0 = time.perf_counter()
        step = 200_000
        for s in range(0, n, step):
            idx.add_batch(np.arange(s, min(s + step, n)), vecs[s:s + step])
        if not idx.trained:
            idx.train()
        idx.store.flush_delta()
        build_s = time.perf_counter() - t0
        log(f"IVF-PQ build: {n/build_s:.0f} vec/s ({build_s:.0f}s)")
        out["ivf_pq"] = {"build_vec_per_s": round(n / build_s),
                         "build_s": round(build_s, 1),
                         "sweep": {}}

    class _NprobeProxy:
        def __init__(self, idx):
            self.idx = idx
        def __setattr__(self, k2, v):
            if k2 == "idx":
                object.__setattr__(self, k2, v)
            else:
                self.idx.store.nprobe = v
        def search_by_vector(self, *a, **kw):
            return self.idx.search_by_vector(*a, **kw)
        def search_by_vector_batch(self, *a, **kw):
            return self.idx.search_by_vector_batch(*a, **kw)

    if ivf_section:
        # nprobe capped at 32: the probe gather at nprobe>=64 with ~2048-row
        # lists OOMs one chip (and 32 already clears recall 0.98)
        out["ivf_pq"]["sweep"] = recall_qps(
            _NprobeProxy(idx), "nprobe", [8, 16, 32], batched=True)
        del idx

    # --- HNSW bulk build ----------------------------------------------------
    if not args.skip_hnsw:
        from weaviate_tpu.engine.hnsw import HNSWIndex

        hidx = HNSWIndex(dim=d, capacity=n, flat_cutoff=0)
        t0 = time.perf_counter()
        hidx.add_batch(np.arange(n), vecs)
        build_s = time.perf_counter() - t0
        log(f"HNSW bulk build: {n/build_s:.0f} vec/s ({build_s:.0f}s)")
        out["hnsw_bulk"] = {"build_vec_per_s": round(n / build_s),
                            "build_s": round(build_s, 1),
                            "sweep": recall_qps(hidx, "ef",
                                                [12, 16, 24, 32, 64, 128])}

    print(json.dumps({"metric": "ann_build_1M", **out}), flush=True)


if __name__ == "__main__":
    main()
