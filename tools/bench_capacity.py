"""Capacity-mode scans: 10M-vector corpora that only FIT compressed.

VERDICT r1 weak-item 4 ("nothing validates 10M+") + BASELINE config #4
(BQ, 1536-dim ada-002 shape, 10M vectors). An uncompressed 10M x 1536
corpus is 61 GB f32 / 31 GB bf16 — beyond one v5e chip's 16 GB HBM; BQ
packs it to 1.9 GB and 4-bit PQ to 1.9 GB (m=d/4 at 768d). This measures
the scan+select pipeline at that scale with in-jit chained timing
(dispatch-level timing measures the fetch round trip). Codes are
generated on-device (transferring a 10M-row host corpus would dominate;
scan cost is value-independent).

Prints one JSON line with device ms/scan + QPS per config.
"""

from __future__ import annotations

import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def mesh_capacity_demo(n_rows: int = 80_000_000, dim: int = 768):
    """VERDICT r2 item 1 done-criterion: ≥80M x 768d of BQ codes addressable
    on the 8-device virtual mesh through the real store path (allocation,
    row-sharded placement, donated scatter write, SPMD search with ICI
    merge). Run with --mesh; sets up the virtual CPU mesh itself."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from weaviate_tpu.engine.quantized import QuantizedVectorStore
    from weaviate_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    t0 = time.perf_counter()
    store = QuantizedVectorStore(
        dim=dim, quantization="bq", capacity=n_rows, chunk_size=131072,
        mesh=mesh, rescore="none",
    )
    words = store.codes.shape[1]
    total_gb = store.capacity * words * 4 / 1e9
    shards = store.codes.addressable_shards
    per_dev = {s.device.id: s.data.shape for s in shards}
    log(f"allocated {store.capacity:,} x {dim}d BQ codes "
        f"({total_gb:.1f} GB) across {len(per_dev)} devices "
        f"in {time.perf_counter()-t0:.1f}s; per-device {per_dev[0]}")
    assert len(per_dev) == 8
    assert all(shape[0] == store.capacity // 8 for shape in per_dev.values())

    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((256, dim)).astype(np.float32)
    t0 = time.perf_counter()
    slots = store.add(vecs)
    log(f"scatter-wrote 256 rows in {time.perf_counter()-t0:.1f}s")

    # one SPMD search across the full capacity (CPU-mesh correctness pass,
    # not a perf number — the perf regime is the single-chip TPU scan below)
    t0 = time.perf_counter()
    d, i = store.search(vecs[:2], k=4)
    dt = time.perf_counter() - t0
    assert i[0, 0] == slots[0] and i[1, 0] == slots[1], i[:, 0]
    log(f"SPMD search over {store.capacity:,} rows: {dt:.1f}s "
        f"(incl compile), self-hit ok")
    print(json.dumps({
        "metric": "mesh_capacity_bq",
        "rows": int(store.capacity),
        "dim": dim,
        "hbm_gb_total": round(total_gb, 2),
        "devices": 8,
        "self_hit": True,
    }), flush=True)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.ops import pq as pq_ops

    chunk = 131072
    out = {}

    # fetches cost one round trip: measure it, subtract it, and
    # amortize over enough reps that the residual is noise (round-2 used
    # reps=8 with no subtraction — those numbers were ~14 ms inflated)
    @jax.jit
    def _triv(s):
        return s + 1.0

    np.asarray(_triv(jnp.float32(0)))
    _rtts = []
    for _ in range(5):
        _t0 = time.perf_counter()
        np.asarray(_triv(jnp.float32(1)))
        _rtts.append(time.perf_counter() - _t0)
    rtt_s = float(np.median(_rtts))
    log(f"fetch RTT: {rtt_s*1e3:.1f} ms (subtracted)")

    def chained_ms(step_fn, arrays, reps=200):
        # the carried distances taint the next QUERY: id_offset alone only
        # feeds ids, leaving distances loop-invariant — XLA then hoists
        # the scan out of the loop (observed as above-HBM-peak "scans")
        @jax.jit
        def chained(*arrs):
            def body(_i, carry):
                zero = carry[0][0, 0] * 0.0
                tainted = (arrs[0] + zero.astype(arrs[0].dtype),) + arrs[1:]
                d_, _ = step_fn(zero.astype(jnp.int32), *tainted)
                return (d_,)
            d0, _ = step_fn(jnp.int32(0), *arrs)
            (d_,) = jax.lax.fori_loop(0, reps, body, (d0,))
            return d_
        np.asarray(chained(*arrays))
        t0 = time.perf_counter()
        np.asarray(chained(*arrays))
        # RTT jitter can exceed a sub-ms scan total — floor at 1 us so
        # downstream QPS math stays finite
        return max(time.perf_counter() - t0 - rtt_s, 1e-3) / (reps + 1) * 1e3

    key = jax.random.PRNGKey(0)

    # --- config #4 shape: BQ over 10M x 1536 (48 packed words/row) ----------
    n, d = 10 * chunk * 8, 1536  # 10.48M rows, chunk-aligned
    w = d // 32
    xw = jax.random.randint(key, (n, w), -2**31, 2**31 - 1, dtype=jnp.int32)
    xw = jax.lax.bitcast_convert_type(xw, jnp.uint32)
    xw.block_until_ready()
    log(f"BQ corpus: {n} x {d}d packed = {n*w*4/1e9:.2f} GB HBM")
    for b in (64, 256):
        qw = jax.lax.bitcast_convert_type(
            jax.random.randint(jax.random.PRNGKey(1), (b, w),
                               -2**31, 2**31 - 1, dtype=jnp.int32),
            jnp.uint32)
        ms = chained_ms(
            lambda off, q_, x_: bq_ops.bq_topk(
                q_, x_, k=100, chunk_size=chunk, use_pallas=True,
                id_offset=off),
            (qw, xw))
        out[f"bq_10M_1536d_b{b}"] = {
            "device_batch_ms": round(ms, 2),
            "qps": round(b / (ms / 1e3)),
        }
        log(f"BQ 10M x 1536 b={b}: {ms:.2f} ms/scan -> {b/(ms/1e3):.0f} qps")

    # --- two-stage prefix scan at the same scale ----------------------------
    # stage 1 reads only the 256-bit transposed prefix (16.7% of the bytes,
    # 1/6 of the stage-1 matmul FLOPs); stage 2 gathers refine*k full rows
    # and scores exact hamming. Scan cost is value-independent, so random
    # codes time it honestly; the RECALL cost of the prefix is measured on
    # clustered data in the 1M x 768 block below.
    for wp_bits in (128, 256):
        wp = wp_bits // 32
        xp_t = jnp.transpose(xw[:, :wp])
        for b in (64, 256):
            qw = jax.lax.bitcast_convert_type(
                jax.random.randint(jax.random.PRNGKey(1), (b, w),
                                   -2**31, 2**31 - 1, dtype=jnp.int32),
                jnp.uint32)
            ms = chained_ms(
                lambda off, q_, x_, xp_: bq_ops.bq_topk_twostage(
                    q_, x_, xp_, k=100, refine=8, id_offset=off),
                (qw, xw, xp_t))
            out[f"bq2stage{wp_bits}_10M_1536d_b{b}"] = {
                "device_batch_ms": round(ms, 2),
                "qps": round(b / (ms / 1e3)),
            }
            log(f"BQ 2-stage/{wp_bits} 10M x 1536 b={b}: {ms:.2f} ms/scan "
                f"-> {b/(ms/1e3):.0f} qps")
        del xp_t
    del xw

    # --- two-stage recall on CLUSTERED 1M x 768 (all on-device) ------------
    # generated on-device (the host transfer would dominate);
    # ground truth from the exact bf16 flat scan; end-to-end = stage1 prefix
    # -> stage2 full-hamming -> exact bf16 rescore of 100 candidates.
    from weaviate_tpu.ops.topk import chunked_topk_distances

    n1, d1 = 8 * chunk, 768
    kc, kq = jax.random.split(jax.random.PRNGKey(3))
    centers = jax.random.normal(kc, (65536, d1), dtype=jnp.float32)
    assign = jax.random.randint(kc, (n1,), 0, 65536)
    v = centers[assign] + 0.35 * jax.random.normal(kq, (n1, d1))
    qi = jax.random.randint(kq, (256,), 0, n1)
    q = v[qi] + 0.05 * jax.random.normal(kc, (256, d1))
    v_bf = v.astype(jnp.bfloat16)
    gt_d, gt_i = chunked_topk_distances(q, v_bf, k=10, chunk_size=chunk,
                                        selection="approx")
    xw1 = bq_ops.bq_encode(v)
    qw1 = bq_ops.bq_encode(q)
    def rescored(ids):
        rows = v_bf[jnp.clip(ids, 0, n1 - 1)].astype(jnp.float32)
        dd = jnp.sum((q[:, None, :] - rows) ** 2, axis=-1)
        dd = jnp.where(ids >= 0, dd, 3e38)
        kk, pos = jax.lax.top_k(-dd, 10)
        return jnp.take_along_axis(ids, pos, axis=1)
    gt_np = np.asarray(gt_i)
    full_d, full_i = bq_ops.bq_topk(qw1, xw1, k=100, use_pallas=True)
    r_full = np.mean([len(set(np.asarray(rescored(full_i))[r]) & set(gt_np[r])) / 10
                      for r in range(256)])
    recalls = {"bq_full_rescored": round(float(r_full), 4)}
    for wp_bits in (128, 256):
        wp = wp_bits // 32
        xp1 = jnp.transpose(xw1[:, :wp])
        d2, i2 = bq_ops.bq_topk_twostage(qw1, xw1, xp1, k=100, refine=8)
        r2 = np.mean([len(set(np.asarray(rescored(i2))[r]) & set(gt_np[r])) / 10
                      for r in range(256)])
        recalls[f"bq2stage{wp_bits}_rescored"] = round(float(r2), 4)
    out["recall_clustered_1M_768d_at10"] = recalls
    log(f"clustered 1M x 768 recall@10 (vs exact bf16 scan): {recalls}")
    del v, v_bf, centers, xw1

    # --- PQ4 over 10M x 768 (m=192 codes/row) -------------------------------
    n, d = 10 * chunk * 8, 768
    m = d // 4
    codes = jax.random.randint(key, (n, m), 0, 16,
                               dtype=jnp.int32).astype(jnp.uint8)
    codes.block_until_ready()
    cent = jax.random.normal(key, (m, 16, 4), dtype=jnp.float32)
    log(f"PQ4 corpus: {n} x {d}d codes = {n*m/1e9:.2f} GB HBM")
    for b in (64, 256):
        q = jax.random.normal(jax.random.PRNGKey(2), (b, d),
                              dtype=jnp.float32)
        ms = chained_ms(
            lambda off, q_, c_, ct_: pq_ops.pq4_topk(
                q_, c_, ct_, k=100, chunk_size=chunk,
                metric="l2-squared", id_offset=off),
            (q, codes, cent))
        out[f"pq4_10M_768d_b{b}"] = {
            "device_batch_ms": round(ms, 2),
            "qps": round(b / (ms / 1e3)),
        }
        log(f"PQ4 10M x 768 b={b}: {ms:.2f} ms/scan -> {b/(ms/1e3):.0f} qps")

    # --- two-stage PQ at the same scale (r4 verdict item 6) -----------------
    # stage 1: 128-bit BQ sign prefix scan (1.6% of the f32 bytes);
    # stage 2: gathered exact-ADC on refine*k rows (ops/pq.pq_topk_twostage)
    wp = 4
    xp_t = jax.lax.bitcast_convert_type(
        jax.random.randint(jax.random.PRNGKey(5), (wp, n), -2**31,
                           2**31 - 1, dtype=jnp.int32), jnp.uint32)
    xp_t.block_until_ready()
    for b in (64, 256):
        q = jax.random.normal(jax.random.PRNGKey(2), (b, d),
                              dtype=jnp.float32)
        qp = bq_ops.bq_encode(q[:, :wp * 32])
        ms = chained_ms(
            lambda off, q_, qp_, c_, ct_, xp_: pq_ops.pq_topk_twostage(
                q_, qp_, c_, ct_, xp_, k=100, refine=8,
                metric="l2-squared", id_offset=off),
            (q, qp, codes, cent, xp_t))
        out[f"pq2stage128_10M_768d_b{b}"] = {
            "device_batch_ms": round(ms, 2),
            "qps": round(b / (ms / 1e3)),
        }
        log(f"PQ 2-stage/128 10M x 768 b={b}: {ms:.2f} ms/scan -> "
            f"{b/(ms/1e3):.0f} qps")

    print(json.dumps({"metric": "capacity_scans_10M", **out}), flush=True)


if __name__ == "__main__":
    if "--mesh" in sys.argv:
        mesh_capacity_demo()
    else:
        main()
