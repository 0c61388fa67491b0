"""100M-per-chip capacity proof (VERDICT r4 item 2 / BASELINE north star).

One v5e chip, 100M x 768-dim corpus as BQ codes (24 words/row = 9.6 GB)
plus the 128-bit transposed sign prefix (1.6 GB) — the layout BASELINE
r4's index-selection verdict picked for the capacity regime. Two parts:

1. TIMING at 100M (synthetic codes; scan cost is value-independent):
   full-scan vs two-stage BQ at B=64/256, chained hoist-proof timing.
2. RECALL on a REAL clustered build at --real-n (default 30M): rows are
   generated per-row from fold_in(key, row) so any candidate row can be
   re-generated exactly for rescore without ever materializing the f32
   corpus (230 GB at 100M); ground truth comes from a streaming exact
   bf16 scan with carried top-k merges.

(IVF-PQ at this scale does not fit beside the BQ codes on one chip —
the unpacked uint8 4-bit codes alone are 19 GB at 100M x 768; the
side-by-side IVF comparison lives at 10M in tools/bench_ivf.py, where
the exhaustive two-stage scan already wins. That is itself the r4
index-selection datum.)

Usage: python tools/bench_100m.py [--n 100000000] [--real-n 30000000]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


CHUNK = 131072


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000_000)
    ap.add_argument("--dim", type=int, default=768)
    ap.add_argument("--real-n", type=int, default=30_000_000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--skip-recall", action="store_true")
    ap.add_argument("--skip-timing", action="store_true")
    args = ap.parse_args()

    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops import bq as bq_ops

    d = args.dim
    w = d // 32
    wp = 4  # 128-bit prefix
    n = (args.n // CHUNK) * CHUNK
    out = {"metric": "capacity_100M", "n": n, "dim": d,
           "hbm_gb": round(n * (w + wp) * 4 / 1e9, 2)}

    if args.skip_timing:
        if args.skip_recall:
            print(json.dumps(out), flush=True)
            return
        return part2(args, out)

    @jax.jit
    def _triv(s):
        return s + 1.0

    np.asarray(_triv(jnp.float32(0)))
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(_triv(jnp.float32(1)))
        rtts.append(time.perf_counter() - t0)
    rtt_s = float(np.median(rtts))
    log(f"fetch RTT {rtt_s*1e3:.1f} ms (subtracted)")

    def chained_ms(step_fn, arrays, reps):
        @jax.jit
        def chained(*arrs):
            def body(_i, carry):
                zero = carry[0][0, 0] * 0.0
                tainted = (arrs[0] + zero.astype(arrs[0].dtype),) + arrs[1:]
                d_, _ = step_fn(zero.astype(jnp.int32), *tainted)
                return (d_,)

            d0, _ = step_fn(jnp.int32(0), *arrs)
            (dd,) = jax.lax.fori_loop(0, reps, body, (d0,))
            return dd

        np.asarray(chained(*arrays))
        t0 = time.perf_counter()
        np.asarray(chained(*arrays))
        return max(time.perf_counter() - t0 - rtt_s, 1e-3) / (reps + 1) * 1e3

    # ---- part 1: timing at full scale (synthetic codes) -------------------
    # generate in donated chunked fills: a one-shot randint materializes
    # ~2x the 9.6 GB array and OOMs the 16 GB chip
    import functools

    key = jax.random.PRNGKey(0)
    gen_rows = CHUNK * 8

    @functools.partial(jax.jit, donate_argnums=0)
    def fill(buf, ci):
        blk = jax.lax.bitcast_convert_type(
            jax.random.randint(jax.random.fold_in(key, ci),
                               (gen_rows, w), -2**31, 2**31 - 1,
                               dtype=jnp.int32), jnp.uint32)
        return jax.lax.dynamic_update_slice(buf, blk, (ci * gen_rows, 0))

    xw = jnp.zeros((n, w), dtype=jnp.uint32)
    for ci in range(n // gen_rows):
        xw = fill(xw, ci)
    xw.block_until_ready()
    xp_t = jnp.transpose(xw[:, :wp]).copy()
    xp_t.block_until_ready()
    log(f"corpus: {n} x {d}d = {n*w*4/1e9:.1f} GB codes "
        f"+ {n*wp*4/1e9:.1f} GB prefix")
    # k_cand sweep: the 30M recall matrix (part 2) shows candidate count
    # must scale with rows-per-cluster at capacity densities — k=100
    # recalls 0.56, k=400 -> 0.958, k=1000 -> 0.973 (prefix width
    # irrelevant: 128 == 256 at every point)
    for kcand in (100, 400, 1000):
        for b in (64, 256):
            qw = jax.lax.bitcast_convert_type(
                jax.random.randint(jax.random.PRNGKey(1), (b, w), -2**31,
                                   2**31 - 1, dtype=jnp.int32), jnp.uint32)
            ms2 = chained_ms(
                lambda off, q_, x_, xp_: bq_ops.bq_topk_twostage(
                    q_, x_, xp_, k=kcand, refine=8, id_offset=off),
                (qw, xw, xp_t), args.reps)
            out[f"twostage128_k{kcand}_b{b}"] = {
                "device_batch_ms": round(ms2, 2),
                "qps": round(b / (ms2 / 1e3))}
            log(f"two-stage/128 100M k{kcand} b={b}: {ms2:.2f} ms -> "
                f"{b/(ms2/1e3):.0f} qps")
    # full scan only at B=64 (it is strictly worse; one point anchors it)
    qw = jax.lax.bitcast_convert_type(
        jax.random.randint(jax.random.PRNGKey(1), (64, w), -2**31,
                           2**31 - 1, dtype=jnp.int32), jnp.uint32)
    try:
        msf = chained_ms(
            lambda off, q_, x_: bq_ops.bq_topk(
                q_, x_, k=100, chunk_size=CHUNK, use_pallas=True,
                id_offset=off), (qw, xw), max(args.reps // 3, 5))
        out["fullscan_b64"] = {"device_batch_ms": round(msf, 2),
                               "qps": round(64 / (msf / 1e3))}
        log(f"full scan 100M b=64: {msf:.2f} ms -> {64/(msf/1e3):.0f} qps")
    except Exception as e:  # noqa: BLE001 — the 763-chunk scan program
        # can exceed the rig's compile-helper limits; the full scan is
        # strictly worse than two-stage, so its absence loses no decision
        out["fullscan_b64"] = {"error": str(e)[:200]}
        log(f"full scan 100M failed to compile on this rig: {e}")
    del xw, xp_t

    # ---- part 2: real clustered build + recall at --real-n -----------------
    if not args.skip_recall:
        return part2(args, out)
    print(json.dumps(out), flush=True)


def part2(args, out):
    import functools

    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops import bq as bq_ops

    d = args.dim
    w = d // 32
    wp = 4
    rn = (args.real_n // CHUNK) * CHUNK
    n_chunks = rn // CHUNK
    kc = jax.random.PRNGKey(7)
    n_centers = 65536
    centers = jax.random.normal(kc, (n_centers, d), dtype=jnp.float32)

    # centers/q are ARGUMENTS everywhere: a jit closure would ship
    # the 200 MB table as a compile-time constant (minutes-long
    # compiles)
    def _gen(rows, cents):
        keys = jax.vmap(lambda r: jax.random.fold_in(kc, r))(rows)
        a = jax.vmap(
            lambda kk: jax.random.randint(kk, (), 0, n_centers))(keys)
        noise = jax.vmap(
            lambda kk: jax.random.normal(kk, (d,)))(keys)
        return cents[a] + 0.35 * noise

    gen_rows = jax.jit(_gen)

    # queries: perturbed copies of existing rows
    qrows = jax.random.randint(jax.random.PRNGKey(9), (args.queries,),
                               0, rn)
    q = gen_rows(qrows, centers) + 0.05 * jax.random.normal(
        jax.random.PRNGKey(10), (args.queries, d))
    q.block_until_ready()
    log("queries generated; compiling build/gt steps...")

    codes = jnp.zeros((rn, w), dtype=jnp.uint32)
    prefix = jnp.zeros((wp, rn), dtype=jnp.uint32)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def build_step(ci, codes, prefix, cents):
        v = _gen(ci * CHUNK + jnp.arange(CHUNK), cents)
        cw = bq_ops.bq_encode(v)
        codes = jax.lax.dynamic_update_slice(
            codes, cw, (ci * CHUNK, 0))
        prefix = jax.lax.dynamic_update_slice(
            prefix, jnp.transpose(cw[:, :wp]), (0, ci * CHUNK))
        return codes, prefix

    @jax.jit
    def gt_step(ci, carry_d, carry_i, cents, q):
        v = _gen(ci * CHUNK + jnp.arange(CHUNK),
                 cents).astype(jnp.bfloat16).astype(jnp.float32)
        dd = (jnp.sum(q * q, -1)[:, None]
              - 2.0 * q @ v.T + jnp.sum(v * v, -1)[None, :])
        ids = ci * CHUNK + jax.lax.broadcasted_iota(
            jnp.int32, (1, CHUNK), 1)
        ids = jnp.broadcast_to(ids, (args.queries, CHUNK))
        negd, pos = jax.lax.top_k(-dd, 10)
        cd = -negd
        cid = jnp.take_along_axis(ids, pos, axis=1)
        md, mi = jnp.concatenate([carry_d, cd], 1), jnp.concatenate(
            [carry_i, cid], 1)
        negd2, pos2 = jax.lax.top_k(-md, 10)
        return -negd2, jnp.take_along_axis(mi, pos2, axis=1)

    t0 = time.perf_counter()
    gt_d = jnp.full((args.queries, 10), 3e38, jnp.float32)
    gt_i = jnp.full((args.queries, 10), -1, jnp.int32)
    for ci in range(n_chunks):
        codes, prefix = build_step(ci, codes, prefix, centers)
        gt_d, gt_i = gt_step(ci, gt_d, gt_i, centers, q)
        if ci % 32 == 0:
            codes.block_until_ready()
            el = time.perf_counter() - t0
            log(f"  build+gt chunk {ci}/{n_chunks} "
                f"({(ci+1)*CHUNK/max(el,1e-9):.0f} rows/s)")
    codes.block_until_ready()
    build_s = time.perf_counter() - t0
    log(f"real build {rn} rows in {build_s:.0f}s")

    qw = bq_ops.bq_encode(q)
    gt_np = np.asarray(gt_i)
    qn = np.asarray(q)
    recalls = {}
    # candidate count must scale with rows-per-cluster (~rn/65536
    # here): k=100 collapses at 30M, k=400 recovers >=0.95
    for kcand in (100, 400, 1000):
        d2, i2 = bq_ops.bq_topk_twostage(qw, codes, prefix, k=kcand,
                                         refine=8)
        cand = np.asarray(i2)
        recall_n = 0
        for r in range(args.queries):
            rows = np.asarray(gen_rows(jnp.asarray(
                np.clip(cand[r], 0, rn - 1)), centers))
            dd = ((qn[r][None, :] - rows) ** 2).sum(-1)
            dd[cand[r] < 0] = np.inf
            top = cand[r][np.argsort(dd)[:10]]
            recall_n += len(set(top.tolist()) & set(gt_np[r].tolist()))
        recalls[f"k{kcand}"] = round(
            recall_n / (args.queries * 10), 4)
        log(f"real clustered {rn} k_cand={kcand}: recall@10 "
            f"{recalls[f'k{kcand}']}")
    out["real_clustered"] = {
        "n": rn, "build_s": round(build_s, 1),
        "recall_at_10": recalls,
    }

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
