"""Device-honest IVF benchmarks (VERDICT r3 item 2).

Two blocks:

1. 1M x 128 clustered, REAL IVF-PQ build: recall@10 through the full
   search path (probe + exact rescore) per nprobe, next to CHAINED
   device timing of the probe kernel itself (`_ivf_probe_topk_pq`) —
   the hoist-proof in-jit loop from bench.py (dispatch-level timing
   measures the fetch round trip).
2. 10M x 768 IVF-PQ with synthetically-filled lists (probe cost is
   value-independent given fill; a real 10M build is the build bench's
   job): chained device timing per nprobe, next to what the exhaustive
   BQ/PQ4 scans cost at the same scale (bench_capacity.py) so the
   crossover is visible.

Prints one JSON line.
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
    ap.add_argument("--n1", type=int, default=1_000_000)
    ap.add_argument("--skip-10m", action="store_true")
    ap.add_argument("--skip-1m", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from weaviate_tpu.engine.ivf import _ivf_probe_topk_pq

    out = {}

    @jax.jit
    def _triv(s):
        return s + 1.0

    np.asarray(_triv(jnp.float32(0)))
    _rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(_triv(jnp.float32(1)))
        _rtts.append(time.perf_counter() - t0)
    rtt_s = float(np.median(_rtts))
    log(f"fetch RTT {rtt_s*1e3:.1f} ms (subtracted)")

    def chained_ms(fn, arrays, reps=50):
        """fn(*arrays) -> (d, i). The carried distances taint the next
        iteration's query so XLA cannot hoist the loop-invariant probe."""
        @jax.jit
        def chained(*arrs):
            def body(_i, carry):
                zero = carry[0].reshape(-1)[0] * 0.0
                tainted = (arrs[0] + zero.astype(arrs[0].dtype),) + arrs[1:]
                d_, i_ = fn(*tainted)
                return (d_,)
            d0, _ = fn(*arrs)
            (d_,) = jax.lax.fori_loop(0, reps, body, (d0,))
            return d_
        np.asarray(chained(*arrays))
        t0 = time.perf_counter()
        np.asarray(chained(*arrays))
        return max(time.perf_counter() - t0 - rtt_s, 1e-3) / (reps + 1) * 1e3

    # ---- 1M x 128: real build, recall + device probe time ------------------
    if not args.skip_1m:
        from weaviate_tpu.engine.ivf import IVFIndex

        n, d, k, nq = args.n1, 128, 10, 256
        rng = np.random.default_rng(0)
        centers = rng.standard_normal((max(n // 15, 1), d)).astype(np.float32)
        vecs = (centers[rng.integers(0, len(centers), n)]
                + 0.35 * rng.standard_normal((n, d))).astype(np.float32)
        q = (vecs[rng.integers(0, n, nq)]
             + 0.05 * rng.standard_normal((nq, d))).astype(np.float32)
        sq = np.einsum("nd,nd->n", vecs, vecs)
        dmat = sq[None, :] - 2.0 * (q @ vecs.T)
        part = np.argpartition(dmat, k, 1)[:, :k]
        gt = np.take_along_axis(
            part, np.argsort(np.take_along_axis(dmat, part, 1), 1), 1)
        del dmat

        idx = IVFIndex(dim=d, train_threshold=min(n, 200_000),
                       delta_threshold=65536, quantization="pq")
        t0 = time.perf_counter()
        for s in range(0, n, 200_000):
            idx.add_batch(np.arange(s, min(s + 200_000, n)),
                          vecs[s:s + 200_000])
        if not idx.trained:
            idx.train()
        idx.store.flush_delta()
        build_s = time.perf_counter() - t0
        st = idx.store
        log(f"IVF-PQ 1M build {n/build_s:.0f} vec/s; nlist={st.nlist} "
            f"list_cap={st.list_cap}")
        out["ivf_pq_1M_128d"] = {"build_vec_per_s": round(n / build_s),
                                 "nlist": st.nlist, "sweep": {}}
        qd = jnp.asarray(q)
        from weaviate_tpu.engine.ivf import _dummy_bits

        allow = _dummy_bits()
        for nprobe in (8, 16, 32):
            # recall through the REAL search path (probe + exact rescore)
            st.nprobe = nprobe
            ids_b, _ = idx.search_by_vector_batch(q, k=k)
            rec = np.mean([len(set(ids_b[r].tolist()) & set(gt[r].tolist()))
                           / k for r in range(nq)])
            k_eff = min(k * st.rescore_limit, nprobe * st.list_cap)
            ms = chained_ms(
                lambda q_, c_, cn_, lc_, lv_, ls_, lt_, pc_:
                _ivf_probe_topk_pq(
                    q_, c_, cn_, lc_, lv_, ls_, lt_, pc_, allow,
                    k_eff, nprobe, "l2-squared", False),
                (qd, st.centroids, st._c_norms, st.list_codes,
                 st.list_valid, st.list_slots, st.list_tvals,
                 st.codebook.centroids))
            out["ivf_pq_1M_128d"]["sweep"][str(nprobe)] = {
                "recall_at_10": round(float(rec), 4),
                "device_probe_ms_b256": round(ms, 3),
                "device_qps": round(nq / (ms / 1e3)),
            }
            log(f"  nprobe={nprobe}: recall {rec:.4f}, device probe "
                f"{ms:.2f} ms/b{nq} -> {nq/(ms/1e3):.0f} qps")
        del idx, vecs

    # ---- 10M x 768 synthetic-fill probe timing ------------------------------
    if not args.skip_10m:
        n, d, m = 10_485_760, 768, 192
        nlist = 8192
        cap = 2048  # ~1.6x balanced fill of n/nlist=1280
        key = jax.random.PRNGKey(0)
        cent = jax.random.normal(key, (nlist, d), dtype=jnp.float32)
        cn = jnp.sum(cent * cent, axis=-1)
        # draw code bytes chunk-by-chunk into a DONATED accumulator —
        # whole-corpus RNG holds multi-GB u32 intermediates (observed
        # 24 GB HBM at [8192, 2048, 192]) and OOMs the chip
        import functools as _ft

        @_ft.partial(jax.jit, donate_argnums=(0,))
        def _put(acc, chunk, li):
            return jax.lax.dynamic_update_slice(acc, chunk, (li, 0, 0))

        list_codes = jnp.zeros((nlist, cap, m), jnp.uint8)
        step_l = 512
        for li in range(0, nlist, step_l):
            ck = jax.random.bits(jax.random.fold_in(key, li),
                                 (step_l, cap, m),
                                 dtype=jnp.uint8) & jnp.uint8(0x0F)
            list_codes = _put(list_codes, ck, jnp.int32(li))
        list_codes.block_until_ready()
        fill = jax.lax.broadcasted_iota(jnp.int32, (nlist, cap), 1) < (
            n // nlist)
        list_slots = (
            jax.lax.broadcasted_iota(jnp.int32, (nlist, cap), 0) * cap
            + jax.lax.broadcasted_iota(jnp.int32, (nlist, cap), 1))
        pqc = jax.random.normal(key, (m, 16, 4), dtype=jnp.float32)
        jax.block_until_ready(list_codes)
        gb = nlist * cap * m / 1e9
        log(f"IVF-PQ 10M x 768 synthetic lists: {nlist} lists x {cap} cap "
            f"({gb:.1f} GB codes)")
        out["ivf_pq_10M_768d"] = {"nlist": nlist, "list_cap": cap,
                                  "hbm_gb": round(gb, 2), "sweep": {}}
        list_tvals = jnp.zeros((nlist, cap), jnp.float32)
        from weaviate_tpu.engine.ivf import _dummy_bits

        for b in (64, 256):
            qb = jax.random.normal(jax.random.PRNGKey(2), (b, d),
                                   dtype=jnp.float32)
            allow = _dummy_bits()
            for nprobe in (8, 16, 32):
                k_eff = min(160, nprobe * cap)
                try:
                    ms = chained_ms(
                        lambda q_, c_, cn_, lc_, ls_, lt_, pc_, f_:
                        _ivf_probe_topk_pq(
                            q_, c_, cn_, lc_, f_, ls_, lt_, pc_, allow,
                            k_eff, nprobe, "l2-squared", False),
                        (qb, cent, cn, list_codes, list_slots, list_tvals,
                         pqc, fill),
                        reps=30)
                except Exception as e:  # noqa: BLE001
                    log(f"  b={b} nprobe={nprobe}: failed {e}")
                    continue
                frac = nprobe * cap / n
                out["ivf_pq_10M_768d"]["sweep"][f"b{b}_np{nprobe}"] = {
                    "device_probe_ms": round(ms, 2),
                    "qps": round(b / (ms / 1e3)),
                    "rows_touched_frac": round(frac, 4),
                }
                log(f"  b={b} nprobe={nprobe}: {ms:.2f} ms "
                    f"-> {b/(ms/1e3):.0f} qps ({frac*100:.2f}% of rows)")

    print(json.dumps({"metric": "ivf_device", **out}), flush=True)


if __name__ == "__main__":
    main()
