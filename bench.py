"""Benchmark: flat brute-force kNN on TPU + quantized scans + device-side
steady-state timing + compiled-kernel conformance + selection microbench.

North-star config #1 (BASELINE.json): flat index, l2-squared, SIFT1M-shaped
corpus (1M x 128), k=10. Measurements this emits:

- headline: flat kNN QPS at the batched operating point (host wall clock)
- ``device_batch_ms``: per-batch DEVICE time with R dispatches in flight
  (async dispatch pipeline, block at the end) for bf16 / f32-exact / BQ /
  PQ4 scans at several batch sizes, plus achieved HBM GB/s — so kernel
  regressions are visible through rig noise
- ``selection_microbench``: per-batch device time for selection="exact" /
  "approx" / "fused" on the same corpus, plus a k=1 fused floor so the
  SELECTION overhead (time above the raw distance scan) of each mode is
  separable — the round-6 fused-top-k acceptance gate
- ``filtered_scan``: selectivity sweep (0.1%/1%/10%/100%) of filtered
  dispatch strategies — per-query bitmask-batched vs gathered vs
  solo-dispatch baseline (the ISSUE 3 batched-filter acceptance gate)
- quantized scans measured on CLUSTERED data (mixture of gaussians — the
  shape real embeddings have) with exact-rescore recall@10
- ``kernel_conformance``: compiled (Mosaic, not interpret) Pallas kernels
  checked bit-exact against numpy on the chip

Sections run through ``run_section``: each one retries with backoff on
transient device-runtime errors, and the accumulated results JSON
is emitted incrementally after every section (stderr line + optional
BENCH_JSON_PATH file), so a mid-run infra failure still exits rc=0 with
every completed section in the final stdout JSON.

Every section entry carries ATTRIBUTION fields benchkeeper (the perf
gate, tools/benchkeeper) compares across runs: ``wall_ms`` (section wall
clock), ``device_ms`` (summed block_until_ready time of the section's
timed device fetches, recorded through the PR 2 tracing machinery —
run_section opens a forced-sampled trace and the timed helpers attach
``tracing.device_sync`` spans), ``host_ms`` (wall - device: Python,
numpy, and fetch round trips), ``transient_retries`` /
``attempts_used`` / ``attempt_wall_ms`` (noise telemetry: how hard the
rig fought back), and ``env_fingerprint`` (jax version, platform,
device count, mesh shape, dtype — runs are only ever compared
like-for-like). Knobs:

  BENCH_N / BENCH_BATCH / BENCH_CHUNK / BENCH_DTYPE   sizing
  BENCH_SECTIONS=a,b,c     run only these sections
  BENCH_SECTION_RETRIES=2  attempts = retries + 1
  BENCH_REPEATS=1          median-of-N for every timed device measurement
  BENCH_FAIL_SECTION=name  inject a persistent failure (resilience tests)
  BENCH_JSON_PATH=path     also write partial results JSON atomically

Prints ONE JSON line on stdout:
  {"metric": ..., "value": QPS, "unit": "qps", "vs_baseline": x,
   "sections": {...}, ...}
detail on stderr.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import traceback


def _watchdog(seconds: float):
    """Hard-exit with a sentinel line if the TPU runtime wedges (jax init can
    hang indefinitely when the device claim is stuck)."""
    def fire():
        print(json.dumps({
            "metric": "flat_knn_qps_synth1M_128d_k10",
            "value": 0.0,
            "unit": "qps",
            "vs_baseline": 0.0,
            "error": f"watchdog: no result within {seconds}s",
        }), flush=True)
        os._exit(2)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def clustered_corpus(rng, n, dim, n_clusters=65536, spread=0.35):
    """Mixture of gaussians — quantization-representative data (real
    embeddings cluster; i.i.d. gaussian is the adversarial floor). ~15
    members per cluster with within-cluster spread comparable to the
    quantization cell size — SIFT-like, not degenerate near-duplicates."""
    import numpy as np

    n_clusters = min(n_clusters, max(16, n // 8))
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32)
    assign = rng.integers(0, n_clusters, n)
    out = centers[assign] + spread * rng.standard_normal((n, dim)).astype(np.float32)
    return out.astype(np.float32)


# -- section harness ---------------------------------------------------------

RESULTS: dict = {"sections": {}}

#: run-level environment fingerprint; benchkeeper refuses to compare two
#: runs whose fingerprints differ (a CPU smoke run gated against TPU
#: baselines would "regress" by 1000x of pure noise)
_FINGERPRINT: dict | None = None


def _env_fingerprint() -> dict:
    """jax version / platform / device count / mesh shape / store dtype.
    Touches the backend only if something already initialized it — the
    fingerprint must not claim the TPU earlier than sec_device_setup
    (the watchdog exists because that claim can hang). ONE dict, updated
    IN PLACE once jax is up: sections recorded before device setup hold
    a reference to it, so the final (and every later partial) JSON shows
    the real platform on every entry, not a pre-jax stub."""
    global _FINGERPRINT
    if _FINGERPRINT is None:
        _FINGERPRINT = {"jax": "unknown", "platform": "uninitialized",
                        "device_count": 0, "mesh_shape": [],
                        "dtype": os.environ.get("BENCH_DTYPE", "bf16")}
    if _FINGERPRINT["platform"] == "uninitialized" \
            and "jax" in sys.modules:
        try:
            import jax

            _FINGERPRINT.update(jax=jax.__version__,
                                platform=jax.default_backend(),
                                device_count=len(jax.devices()),
                                mesh_shape=[len(jax.devices())])
        except Exception:  # backend init failed: keep the stub
            pass
    return _FINGERPRINT


def _tracing():
    """The PR 2 tracing module, or None when the package is unimportable
    (bench must degrade to wall-clock-only, not crash)."""
    try:
        from weaviate_tpu.runtime import tracing

        return tracing
    except Exception:
        return None


#: sections that measure the tracing substrate itself — wrapping them in
#: the harness's forced trace would contaminate their "plain" baselines
UNTRACED_SECTIONS = {"tracing_overhead", "observability_overhead"}


def _emit_partial():
    """Incremental results: atomically rewrite BENCH_JSON_PATH (if set)
    after every section, so even a hard crash leaves the completed
    sections on disk."""
    path = os.environ.get("BENCH_JSON_PATH")
    if not path:
        return
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(RESULTS, f)
    os.replace(tmp, path)


def run_section(name: str, fn, ctx: dict, deps: tuple = ()) -> bool:
    """Run one bench section with retry-with-backoff.

    Transient device-runtime errors get retries + 1 attempts with exponential backoff; a section
    that still fails is recorded as {"ok": false, "error": ...} and the
    run continues — partial results beat no results. ``deps`` names ctx
    keys earlier sections must have produced: a missing dep (skipped via
    BENCH_SECTIONS or failed upstream) skips this section immediately —
    deterministic, so no retries wasted."""
    wanted = os.environ.get("BENCH_SECTIONS")
    if wanted and name not in [s.strip() for s in wanted.split(",")]:
        return False
    missing = [d for d in deps if d not in ctx]
    if missing:
        RESULTS["sections"][name] = {
            "ok": False, "skipped_missing_deps": missing}
        log(f"[section {name}] skipped: missing {missing} "
            f"(upstream section skipped or failed)")
        _emit_partial()
        return False
    retries = int(os.environ.get("BENCH_SECTION_RETRIES", "2"))
    last: BaseException | None = None
    _TRANSIENT["count"] = 0  # per-section inner-retry tally
    # attempt-level wall clocks, INCLUDING attempts that died partway —
    # crashed runs still contribute noise statistics to benchkeeper
    attempt_wall_ms: list[float] = []
    tracing = None if name in UNTRACED_SECTIONS else _tracing()
    for attempt in range(retries + 1):
        t0 = time.perf_counter()
        try:
            if os.environ.get("BENCH_FAIL_SECTION") == name:
                raise RuntimeError(f"injected failure in section {name!r}")
            # forced-sampled trace: the timed helpers hang device_sync
            # spans off it, so device time is attributed separately from
            # host wall time
            trace_cm = (tracing.trace(f"bench.{name}", force=True)
                        if tracing else contextlib.nullcontext())
            with trace_cm:
                out = fn(ctx) or {}
                spans = tracing.current_timing() if tracing else []
            wall_ms = (time.perf_counter() - t0) * 1e3
            attempt_wall_ms.append(round(wall_ms, 3))
            # only the harness's own bench.* spans carry device_ms here
            # (engine-internal device_sync spans would double-count time
            # already inside an enclosing bench span)
            device_ms = sum(
                s.get("attrs", {}).get("device_ms", 0.0) for s in spans
                if str(s.get("name", "")).startswith("bench."))
            # rc + retry accounting (which sections survived only via
            # retries, and how many):
            # rc 0/1 per section, section-level attempts used, and the
            # count of transient device-call retries _retry_transient
            # absorbed inside this section
            entry = {"ok": True, "rc": 0,
                     "seconds": round(wall_ms / 1e3, 2),
                     "wall_ms": round(wall_ms, 3),
                     "device_ms": round(float(device_ms), 3),
                     "host_ms": round(max(wall_ms - device_ms, 0.0), 3),
                     "attempts_used": attempt + 1,
                     "attempt_wall_ms": attempt_wall_ms,
                     "transient_retries": _TRANSIENT["count"],
                     "env_fingerprint": _env_fingerprint()}
            entry.update(out)
            RESULTS["sections"][name] = entry
            log(json.dumps({"section": name, **entry}))
            _emit_partial()
            return True
        except BaseException as e:  # noqa: BLE001 — record, retry, move on
            if isinstance(e, (KeyboardInterrupt, SystemExit)):
                raise
            attempt_wall_ms.append(
                round((time.perf_counter() - t0) * 1e3, 3))
            last = e
            log(f"[section {name}] attempt {attempt + 1}/{retries + 1} "
                f"failed: {e!r}")
            traceback.print_exc(file=sys.stderr)
            if attempt < retries:
                time.sleep(min(2.0 * 2 ** attempt, 30.0))
    RESULTS["sections"][name] = {"ok": False, "rc": 1, "error": repr(last),
                                 "attempts": retries + 1,
                                 "attempts_used": retries + 1,
                                 "attempt_wall_ms": attempt_wall_ms,
                                 "transient_retries": _TRANSIENT["count"],
                                 "env_fingerprint": _env_fingerprint()}
    log(json.dumps({"section": name, "ok": False, "error": repr(last)}))
    _emit_partial()
    return False


# -- sections ----------------------------------------------------------------


def sec_setup(ctx):
    import numpy as np

    n = int(os.environ.get("BENCH_N", "1000000"))
    dim, k = 128, 10
    batch = min(int(os.environ.get("BENCH_BATCH", "1024")), n)
    n_query_batches = 8
    rng = np.random.default_rng(0)
    ctx.update(n=n, dim=dim, k=k, batch=batch,
               n_query_batches=n_query_batches, rng=rng)
    ctx["corpus"] = rng.standard_normal((n, dim)).astype(np.float32)
    ctx["queries"] = rng.standard_normal(
        (n_query_batches, batch, dim)).astype(np.float32)
    log(f"corpus {ctx['corpus'].nbytes/1e9:.2f} GB, "
        f"{n_query_batches}x{batch} queries")
    return {"n": n, "dim": dim, "k": k, "batch": batch}


def _cpu_exact_knn(corpus, qb, k, step=131072):
    """Chunked exact l2 kNN on host BLAS — the ground-truth/baseline scan
    shared by the random-corpus and clustered-corpus sections."""
    import numpy as np

    n = len(corpus)
    best_d = np.full((len(qb), k), np.inf, np.float32)
    best_i = np.zeros((len(qb), k), np.int64)
    cn = (corpus ** 2).sum(-1)
    qn = (qb ** 2).sum(-1)[:, None]
    for s in range(0, n, step):
        c = corpus[s:s + step]
        d = qn - 2.0 * qb @ c.T + cn[None, s:s + step]
        idx = np.argpartition(d, min(k, d.shape[1] - 1), axis=1)[:, :k]
        dd = np.take_along_axis(d, idx, axis=1)
        cat_d = np.concatenate([best_d, dd], 1)
        cat_i = np.concatenate([best_i, idx + s], 1)
        sel = np.argpartition(cat_d, k, axis=1)[:, :k]
        best_d = np.take_along_axis(cat_d, sel, 1)
        best_i = np.take_along_axis(cat_i, sel, 1)
    order = np.argsort(best_d, 1)
    return (np.take_along_axis(best_d, order, 1),
            np.take_along_axis(best_i, order, 1))


def sec_cpu_baseline(ctx):
    n, k, batch = ctx["n"], ctx["k"], ctx["batch"]

    t0 = time.perf_counter()
    gt_d, gt_i = _cpu_exact_knn(ctx["corpus"], ctx["queries"][0], k)
    cpu_s = time.perf_counter() - t0
    ctx["gt_i"] = gt_i
    ctx["cpu_qps"] = batch / cpu_s
    log(f"CPU BLAS exact scan: {cpu_s*1e3:.1f} ms/batch -> "
        f"{ctx['cpu_qps']:.1f} QPS")
    return {"cpu_qps": round(ctx["cpu_qps"], 1)}


def sec_device_setup(ctx):
    import numpy as np

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    log(f"device: {dev}, platform: {dev.platform}")
    n, dim = ctx["n"], ctx["dim"]
    store_dtype = (jnp.bfloat16
                   if os.environ.get("BENCH_DTYPE", "bf16") == "bf16"
                   else jnp.float32)
    chunk = min(int(os.environ.get("BENCH_CHUNK", "65536")), n)
    n_pad = -(-n // chunk) * chunk
    padded = np.zeros((n_pad, dim), dtype=np.float32)
    padded[:n] = ctx["corpus"]
    # the corpus upload is the single largest H2D transfer of the run
    x = _retry_transient(
        lambda: jax.device_put(jnp.asarray(padded, dtype=store_dtype),
                               dev),
        what="corpus upload")
    ctx.update(
        dev=dev, store_dtype=store_dtype, chunk=chunk, n_pad=n_pad, x=x,
        norms=jnp.sum(jnp.asarray(x, dtype=jnp.float32) ** 2, axis=-1),
        valid=jnp.asarray(np.arange(n_pad) < n),
    )
    # fetch RTT: one device->host fetch costs a full round trip —
    # measure and subtract from chained device timings, amortized over
    # enough reps that the residual error is <1% of the reading
    @jax.jit
    def _triv(s):
        return s + 1.0

    def _measure_rtt():
        np.asarray(_triv(jnp.float32(0)))  # compile + warm
        rtts = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(_triv(jnp.float32(1)))
            rtts.append(time.perf_counter() - t0)
        return rtts

    rtts = _retry_transient(_measure_rtt, what="fetch RTT probe")
    ctx["rtt_s"] = float(np.median(rtts))
    log(f"fetch RTT: {ctx['rtt_s']*1e3:.1f} ms (subtracted from device "
        f"timings)")
    return {"platform": dev.platform,
            "fetch_rtt_ms": round(ctx["rtt_s"] * 1e3, 1)}


#: transient device-call retries absorbed inside the current section
#: (reset by run_section, recorded into each section's JSON entry)
_TRANSIENT = {"count": 0}


def _retry_transient(fn, attempts: int = 3, what: str = "compile/warm"):
    """Retry a device call through transient device-runtime errors
    (they can hit mid-run, not just in warmup, so every device fetch in
    a timed section rides this). A still-failing call
    re-raises into run_section's retry, which records the section as
    failed and moves on instead of killing the run. Each absorbed
    failure counts into the section's ``transient_retries``."""
    for attempt in range(attempts):
        try:
            return fn()
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — transient infra errors
            if attempt == attempts - 1:
                raise
            _TRANSIENT["count"] += 1
            log(f"[retry] transient {what} failure "
                f"(attempt {attempt + 1}/{attempts}): {e!r}")
            time.sleep(min(2.0 * 2 ** attempt, 15.0))


def _bench_repeats() -> int:
    """Median-of-N repeat count for every timed device measurement
    (BENCH_REPEATS; the benchkeeper --update-baseline flow raises it so
    baseline reference numbers are medians, not single noisy draws)."""
    return max(1, int(os.environ.get("BENCH_REPEATS", "1")))


def _chained_ms(ctx, step_with_offset, arrays, reps=100):
    """step_with_offset(id_offset, *arrays) -> (d, i); ms/scan, device
    time, chained inside ONE jit so async dispatch can't lie. The carried
    distances TAINT the next iteration's query (adding a zero derived from
    them): id_offset alone only feeds the returned ids, so distances would
    be loop-invariant and XLA could hoist the whole scan out of the timing
    loop (observed: "scans" above HBM peak bandwidth).

    Each timed fetch splits dispatch / device / D2H-fetch time: the
    device part rides a ``bench.chained_scan`` tracing span (device_sync
    = block_until_ready under the section's forced-sampled trace), which
    is what run_section rolls up into the section's ``device_ms``.
    Repeated BENCH_REPEATS times; the median wall clock is the reading."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    tracing = _tracing()

    @jax.jit
    def chained(*arrs):
        def body(_i, carry):
            zero = carry[0][0, 0] * 0.0
            tainted = (arrs[0] + zero.astype(arrs[0].dtype),) + arrs[1:]
            d_, i_ = step_with_offset(zero.astype(jnp.int32), *tainted)
            return (d_,)
        d0, _ = step_with_offset(jnp.int32(0), *arrs)
        (d_,) = jax.lax.fori_loop(0, reps, body, (d0,))
        return d_
    _retry_transient(lambda: np.asarray(chained(*arrays)))  # compile + warm

    def _timed():
        # exactly ONE synchronization inside the timed window (one
        # fetch round trip, matching the single rtt_s subtraction):
        # device_sync blocks under the section's forced-sampled trace
        # and attributes the time; the block_until_ready after it is a
        # no-op then, and IS the sync when tracing is unavailable. The
        # [b, k] result is deliberately not fetched — its D2H transfer
        # is a second round trip of pure noise.
        span_cm = (tracing.span("bench.chained_scan")
                   if tracing else contextlib.nullcontext())
        with span_cm as sp:
            t0 = time.perf_counter()
            out = chained(*arrays)               # async dispatch (host)
            t_disp = time.perf_counter()
            if tracing:
                tracing.device_sync(sp, out)     # block: device time
            jax.block_until_ready(out)
            elapsed = time.perf_counter() - t0
            if tracing and sp is not None:
                sp.set(wall_ms=round(elapsed * 1e3, 3),
                       dispatch_ms=round((t_disp - t0) * 1e3, 3))
        return elapsed

    # the timed fetch itself retries too — a transient error can hit
    # AFTER warmup; a retry re-times from scratch so the reading
    # stays honest
    samples = [_retry_transient(_timed, what="timed device scan")
               for _ in range(_bench_repeats())]
    elapsed = float(np.median(samples))
    return max((elapsed - ctx["rtt_s"]), 1e-3) / (reps + 1) * 1e3


def sec_flat_headline(ctx):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops.topk import chunked_topk_distances

    n, k, batch, chunk = ctx["n"], ctx["k"], ctx["batch"], ctx["chunk"]
    x, valid, norms, dev = ctx["x"], ctx["valid"], ctx["norms"], ctx["dev"]

    def step(qb):
        return chunked_topk_distances(
            qb, x, k=k, chunk_size=chunk, metric="l2-squared",
            valid=valid, x_sq_norms=norms, selection="approx",
        )

    q0 = _retry_transient(
        lambda: jax.device_put(jnp.asarray(ctx["queries"][0]), dev),
        what="headline query upload")
    t0 = time.perf_counter()
    d, i = _retry_transient(
        lambda: jax.block_until_ready(step(q0)), what="headline compile")
    log(f"first call (incl compile): {time.perf_counter()-t0:.1f}s")

    out = {}
    if "gt_i" in ctx:
        # the recall id fetch is a full D2H transfer — transient
        # errors hit unretried fetches exactly like this one
        ids = _retry_transient(lambda: np.asarray(i),
                               what="recall id fetch")
        recall = np.mean([
            len(set(ids[r]) & set(ctx["gt_i"][r])) / k for r in range(batch)
        ])
        log(f"recall@{k} vs exact f32: {recall:.4f}")
        out["recall_at_10"] = round(float(recall), 4)
        ctx["recall"] = recall

    tracing = _tracing()
    times = []
    for _rep in range(3):
        for bi in range(ctx["n_query_batches"]):
            qb = _retry_transient(
                lambda bi=bi: jax.device_put(
                    jnp.asarray(ctx["queries"][bi]), dev),
                what="query upload")

            def _timed(qb=qb):
                span_cm = (tracing.span("bench.headline_scan")
                           if tracing else contextlib.nullcontext())
                with span_cm as sp:
                    t0 = time.perf_counter()
                    res = step(qb)            # async dispatch
                    if tracing:
                        tracing.device_sync(sp, res)  # device time
                    jax.block_until_ready(res)
                    elapsed = time.perf_counter() - t0
                    if tracing and sp is not None:
                        sp.set(wall_ms=round(elapsed * 1e3, 3))
                return elapsed

            times.append(_retry_transient(_timed, what="headline scan"))
    times = np.asarray(times[1:])
    per_batch = float(np.median(times))
    ctx["qps"] = batch / per_batch
    ctx["per_batch"] = per_batch
    log(f"median {per_batch*1e3:.2f} ms/batch of {batch} -> "
        f"{ctx['qps']:.0f} QPS; p95 {np.percentile(times, 95)*1e3:.2f} ms")
    out.update(qps=round(ctx["qps"], 1),
               p50_batch_ms=round(per_batch * 1e3, 2))
    return out


def sec_device_steady(ctx):
    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops.topk import chunked_topk_distances

    k, chunk, n_pad, dim = ctx["k"], ctx["chunk"], ctx["n_pad"], ctx["dim"]
    x, valid, norms = ctx["x"], ctx["valid"], ctx["norms"]
    store_dtype = ctx["store_dtype"]
    device_stats = {}
    bytes_scan = n_pad * dim * (2 if store_dtype == jnp.bfloat16 else 4)
    for b_dev in (64, 256, 1024):
        if b_dev > ctx["batch"]:
            continue
        qd = _retry_transient(
            lambda b_dev=b_dev: jax.device_put(
                jnp.asarray(ctx["queries"][0][:b_dev]), ctx["dev"]),
            what="steady query upload")
        ms = _chained_ms(
            ctx,
            lambda off, qd_, x_, v_, n_: chunked_topk_distances(
                qd_, x_, k=k, chunk_size=chunk, metric="l2-squared",
                valid=v_, x_sq_norms=n_, id_offset=off, selection="approx"),
            (qd, x, valid, norms))
        gbps = bytes_scan / (ms / 1e3) / 1e9
        flops = 2.0 * b_dev * n_pad * dim / (ms / 1e3)
        tag = "bf16" if store_dtype == jnp.bfloat16 else "f32"
        device_stats[f"flat_{tag}_b{b_dev}"] = {
            "device_batch_ms": round(ms, 3),
            "qps": round(b_dev / (ms / 1e3)),
            "hbm_gbps": round(gbps, 1),
            "tflops": round(flops / 1e12, 2),
        }
        log(f"[device] flat b={b_dev}: {ms:.2f} ms -> "
            f"{b_dev/(ms/1e3):.0f} qps, {gbps:.0f} GB/s, "
            f"{flops/1e12:.1f} TFLOP/s")
    ctx["device_stats"] = device_stats
    return {"stats": device_stats}


def sec_selection_microbench(ctx):
    """Fused vs approx vs exact selection on the SAME corpus/queries.

    Reports per-batch device ms for each mode plus a k=1 fused floor
    (distance scan with a near-free fold) so selection OVERHEAD — the time
    above the raw scan — is separable. Acceptance gate (round 6): fused
    overhead <= 0.5x the approx_max_k path's. On CPU backends the fused
    kernel runs through the (jitted) Pallas interpreter — those numbers
    validate mechanics, not perf; device numbers land here whenever a TPU
    is reachable."""
    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops.topk import chunked_topk_distances

    on_tpu = jax.default_backend() == "tpu"
    k, chunk = ctx["k"], ctx["chunk"]
    # CPU: the interpreter is O(grid) jitted emulation — keep it small
    n_sub = ctx["n_pad"] if on_tpu else min(ctx["n_pad"], 16384)
    n_sub = -(-n_sub // chunk) * chunk if n_sub >= chunk else n_sub
    x = ctx["x"][:n_sub]
    valid = ctx["valid"][:n_sub]
    norms = ctx["norms"][:n_sub]
    b = min(256 if on_tpu else 32, ctx["batch"])
    qd = _retry_transient(
        lambda: jax.device_put(jnp.asarray(ctx["queries"][0][:b]),
                               ctx["dev"]),
        what="selection query upload")
    cs = min(chunk, n_sub)

    out = {"rows": int(n_sub), "batch": int(b), "k": k}

    def time_mode(sel, kk):
        return _chained_ms(
            ctx,
            lambda off, qd_, x_, v_, n_: chunked_topk_distances(
                qd_, x_, k=kk, chunk_size=cs, metric="l2-squared",
                valid=v_, x_sq_norms=n_, id_offset=off, selection=sel),
            (qd, x, valid, norms),
            reps=100 if on_tpu else 3)

    ms = {sel: time_mode(sel, k) for sel in ("exact", "approx", "fused")}
    floor = time_mode("fused", 1)  # ~pure distance scan
    for sel, v in ms.items():
        out[f"{sel}_ms"] = round(v, 3)
        out[f"{sel}_selection_overhead_ms"] = round(max(v - floor, 0.0), 3)
    out["scan_floor_ms"] = round(floor, 3)
    approx_ov = max(ms["approx"] - floor, 1e-6)
    fused_ov = max(ms["fused"] - floor, 0.0)
    out["fused_over_approx_overhead"] = round(fused_ov / approx_ov, 3)
    out["device_numbers"] = on_tpu
    # correctness ride-along: fused == exact ids on this corpus (timed
    # device fetches — retried like every other device read)
    import numpy as np

    def _id_match():
        d_e, i_e = chunked_topk_distances(
            qd, x, k=k, chunk_size=cs, metric="l2-squared", valid=valid,
            x_sq_norms=norms, selection="exact")
        d_f, i_f = chunked_topk_distances(
            qd, x, k=k, chunk_size=cs, metric="l2-squared", valid=valid,
            x_sq_norms=norms, selection="fused")
        return float(np.mean(np.asarray(i_e) == np.asarray(i_f)))

    match = _retry_transient(_id_match, what="selection id-match fetch")
    out["fused_vs_exact_id_match"] = round(match, 4)
    log(f"[selection] exact {ms['exact']:.2f} ms, approx "
        f"{ms['approx']:.2f} ms, fused {ms['fused']:.2f} ms, floor "
        f"{floor:.2f} ms -> fused/approx overhead "
        f"{out['fused_over_approx_overhead']:.2f}, id match {match:.4f}")
    return out


def sec_filtered_scan(ctx):
    """Filtered-search microbench: selectivity sweep (0.1%/1%/10%/100%)
    of the three filtered dispatch strategies on the same corpus/queries:

    - ``batched_ms``: per-query packed allow bitmasks folded inside the
      scan kernels — B differently-filtered queries, ONE device program
      (the ISSUE 3 dataplane; selectivity-independent cost).
    - ``gathered_ms``: shared-filter gather cutover — gather the allowed
      rows into a dense pow2 buffer and scan that (store.py's
      low-selectivity path; cost linear in selectivity).
    - ``solo_ms``: per-dispatch baseline — one masked single-query
      program per request (the pre-batching filtered path), reported as
      per-query ms x batch for comparability.

    Per-section JSON mirrors the fused-selection microbench."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops.pallas_kernels import (mask_pad_cols,
                                                 pack_allow_bitmask)
    from weaviate_tpu.ops.topk import chunked_topk_distances

    on_tpu = jax.default_backend() == "tpu"
    k, chunk = ctx["k"], ctx["chunk"]
    n_sub = ctx["n_pad"] if on_tpu else min(ctx["n_pad"], 16384)
    n_sub = -(-n_sub // chunk) * chunk if n_sub >= chunk else n_sub
    x = ctx["x"][:n_sub]
    valid = ctx["valid"][:n_sub]
    norms = ctx["norms"][:n_sub]
    cs = min(chunk, n_sub)
    b = min(256 if on_tpu else 16, ctx["batch"])
    qd = _retry_transient(
        lambda: jax.device_put(jnp.asarray(ctx["queries"][0][:b]),
                               ctx["dev"]),
        what="filtered query upload")
    # fused = the TPU serving operating point; the interpreter makes it
    # pathological on CPU, where approx lowers to exact top_k anyway
    sel = "fused" if on_tpu else "approx"
    reps = 50 if on_tpu else 3
    rng = ctx["rng"]
    out = {"rows": int(n_sub), "batch": int(b), "k": k, "selection": sel,
           "device_numbers": on_tpu, "sweep": {}}

    # solo baseline cost is selectivity-independent (masked full scan):
    # time one single-query masked dispatch once, report x b per point
    solo_mask = rng.random(n_sub) < 0.10
    solo_mask[0] = True
    v_solo = jnp.logical_and(valid, jnp.asarray(solo_mask))
    ms_solo_1q = _chained_ms(
        ctx,
        lambda off, q_, x_, v_, n_: chunked_topk_distances(
            q_, x_, k=k, chunk_size=cs, metric="l2-squared", valid=v_,
            x_sq_norms=n_, id_offset=off, selection=sel),
        (qd[:1], x, v_solo, norms), reps=reps)

    for frac in (0.001, 0.01, 0.10, 1.0):
        masks = rng.random((b, n_sub)) < frac
        masks[:, 0] = True  # never an empty allow list
        bits = jnp.asarray(pack_allow_bitmask(masks, mask_pad_cols(n_sub)))
        ms_batched = _chained_ms(
            ctx,
            lambda off, q_, x_, v_, n_, ab_: chunked_topk_distances(
                q_, x_, k=k, chunk_size=cs, metric="l2-squared", valid=v_,
                x_sq_norms=n_, id_offset=off, selection=sel,
                allow_bits=ab_),
            (qd, x, valid, norms, bits), reps=reps)
        # gathered: shared filter at the same selectivity; the in-jit
        # row gather is part of the timed step, as in the serving path
        allowed = np.flatnonzero(masks[0])
        bucket = 1 << max(7, (len(allowed) - 1).bit_length())
        slot_buf = np.zeros(bucket, dtype=np.int32)
        slot_buf[:len(allowed)] = allowed
        slots_dev = jnp.asarray(slot_buf)
        g_valid = jnp.asarray(np.arange(bucket) < len(allowed))
        ms_gathered = _chained_ms(
            ctx,
            lambda off, q_, x_, s_, gv_: chunked_topk_distances(
                q_, x_[s_], k=min(k, bucket), chunk_size=bucket,
                metric="l2-squared", valid=gv_, id_offset=off,
                selection=sel),
            (qd, x, slots_dev, g_valid), reps=reps)
        out["sweep"][f"{frac:g}"] = {
            "batched_ms": round(ms_batched, 3),
            "gathered_ms": round(ms_gathered, 3),
            "solo_ms": round(ms_solo_1q * b, 3),
            "batched_qps": round(b / (ms_batched / 1e3)),
        }
        log(f"[filtered] sel={frac:g}: batched {ms_batched:.2f} ms, "
            f"gathered {ms_gathered:.2f} ms, solo {ms_solo_1q * b:.2f} ms "
            f"(per batch of {b})")
    # correctness ride-along on a SELECTIVE mask (the sweep's last masks
    # are all-True at frac=1.0, which would make this check vacuous):
    # batched-bitmask results must respect each query's own filter
    sel_masks = rng.random((b, n_sub)) < 0.01
    sel_masks[:, 0] = True

    def _masked_fetch():
        d_c, i_c = chunked_topk_distances(
            qd, x, k=k, chunk_size=cs, metric="l2-squared", valid=valid,
            x_sq_norms=norms, selection=sel,
            allow_bits=jnp.asarray(pack_allow_bitmask(
                sel_masks, mask_pad_cols(n_sub))))
        return np.asarray(i_c), np.asarray(d_c)

    i_np, d_np = _retry_transient(_masked_fetch,
                                  what="filtered ride-along fetch")
    live = (i_np >= 0) & (d_np < 1e37)
    violations = int(sum(
        (~sel_masks[r][i_np[r][live[r]]]).sum() for r in range(b)))
    out["mask_violations"] = violations
    log(f"[filtered] mask violations: {violations}")
    return out


def sec_tracing_overhead(ctx):
    """Per-query cost of the observability substrate (ISSUE 2 gate):
    untraced calls pay only no-op contextvar reads through every span
    point, and an UNSAMPLED trace adds no device synchronization — only
    sampled traces (?trace=true / TRACE_SAMPLE_RATE) buy block_until_
    ready device attribution. Host-dispatch-dominated sizing on purpose:
    the overhead under test is Python-side, not kernel-side."""
    import numpy as np

    from weaviate_tpu.engine.flat import FlatIndex
    from weaviate_tpu.runtime import tracing

    rng = np.random.default_rng(7)
    idx = FlatIndex(dim=64, capacity=8192)
    idx.add_batch(np.arange(4096),
                  rng.standard_normal((4096, 64)).astype(np.float32))
    q = rng.standard_normal((8, 64)).astype(np.float32)
    for _ in range(10):
        idx.search_by_vector_batch(q, 10)

    def best_ms(fn, reps=50, rounds=5):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(reps):
                fn()
            best = min(best, (time.perf_counter() - t0) / reps)
        return best * 1e3

    plain = best_ms(lambda: idx.search_by_vector_batch(q, 10))

    def traced(force):
        with tracing.trace("bench.query", force=force):
            idx.search_by_vector_batch(q, 10)

    unsampled = best_ms(lambda: traced(False))
    sampled = best_ms(lambda: traced(True))
    tracing.clear_traces()
    out = {
        "plain_ms": round(plain, 4),
        "unsampled_trace_ms": round(unsampled, 4),
        "sampled_trace_ms": round(sampled, 4),
        "unsampled_overhead_ms": round(unsampled - plain, 4),
        "unsampled_overhead_frac": round(
            max(unsampled - plain, 0.0) / max(plain, 1e-9), 4),
    }
    log(f"[tracing] plain {plain:.3f} ms, unsampled trace "
        f"{unsampled:.3f} ms (+{out['unsampled_overhead_ms']:.3f}), "
        f"sampled {sampled:.3f} ms")
    return out


def sec_observability_overhead(ctx):
    """Always-on attribution cost (ISSUE 15 gate): what the tailboard
    timeline adds to a served request, held to the <=3% budget.

    The gated metric is COMPOSED from two stable estimators rather than
    read off a direct throughput A/B — on a shared/noisy host, per-round
    served QPS moves +-10-15%, so a direct on/off ratio cannot resolve
    3% (the r05 lesson: a gate on a number noisier than its band is a
    coin flip). Instead:

    - ``timeline_cost_us``: tight-loop delta of the full edge machinery
      (timeline CM + root trace + phase folds + complete + amortized
      fold share) measured on-minus-off with drift-cancelling
      alternation — stable to fractions of a microsecond;
    - ``request_cpu_us``: per-request CPU time of a real served loop
      (concurrent clients through the query batcher), timeline off —
      the denominator a percentage overhead is meaningful against;
    - ``on_over_off_qps`` = 1 / (1 + cost/request_cpu): the throughput
      ratio those two numbers imply, which IS the gated entry.

    A direct concurrent A/B still runs and lands in the section output
    (``ab_on_qps``/``ab_off_qps``) for eyeball confirmation on quiet
    rigs; it is deliberately not the gate."""
    import threading as _threading

    import numpy as np

    from weaviate_tpu.engine.flat import FlatIndex
    from weaviate_tpu.runtime import tailboard, tracing
    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    rng = np.random.default_rng(11)
    idx = FlatIndex(dim=64, capacity=8192)
    idx.add_batch(np.arange(4096),
                  rng.standard_normal((4096, 64)).astype(np.float32))
    q = rng.standard_normal(64).astype(np.float32)
    qb = QueryBatcher(idx.search_by_vector_batch, max_batch=64)

    def served_one():
        # the REST edge stack in miniature: timeline CM, root trace,
        # batcher search (whose stamps fold into the timeline), complete
        with tailboard.request("bench"):
            with tracing.trace("rest.bench"):
                qb.search(q, 10)
            tailboard.complete(200)

    def edge_one():
        # the same per-request machinery minus the batcher round trip
        # (phases injected synthetically) — isolates the timeline cost
        with tailboard.request("bench"):
            with tracing.trace("rest.bench"):
                tailboard.phase("queue_wait", 0.0001)
                tailboard.phase("device", 0.0002)
            tailboard.complete(200)

    def tight_us(reps=20000, rounds=3):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(reps):
                edge_one()
            best = min(best, (time.perf_counter() - t0) / reps)
        return best * 1e6

    def served_round(clients=8, reps=150):
        def drive():
            for _ in range(reps):
                served_one()

        threads = [_threading.Thread(target=drive)
                   for _ in range(clients)]
        c0 = time.process_time()
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        n = clients * reps
        return (n / (time.perf_counter() - t0),
                (time.process_time() - c0) / n * 1e6)

    from weaviate_tpu.runtime import kernelscope

    def explain_one():
        # the ?explain=true request shape: request sink installed at the
        # edge, dispatch plan merged back after the batcher round trip
        token = kernelscope.explain_begin()
        try:
            served_one()
        finally:
            kernelscope.explain_end(token)

    def explain_us(reps=2000, rounds=3):
        # drift-cancelling alternation, same discipline as tight_us
        on_best = off_best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(reps):
                explain_one()
            on_best = min(on_best, (time.perf_counter() - t0) / reps)
            t0 = time.perf_counter()
            for _ in range(reps):
                served_one()
            off_best = min(off_best, (time.perf_counter() - t0) / reps)
        return max(0.0, (on_best - off_best) * 1e6)

    try:
        for state in (True, False, True):  # warm both states' caches
            tailboard.force_enabled(state)
            for _ in range(200):
                edge_one()
            for _ in range(30):
                served_one()
        # timeline cost: alternating on/off tight rounds, min of each
        # side (drift hits both; min-of discards preemption outliers)
        on_us, off_us = [], []
        for i in range(4):
            tailboard.force_enabled(i % 2 == 0)
            (on_us if i % 2 == 0 else off_us).append(tight_us())
        timeline_cost_us = max(0.0, min(on_us) - min(off_us))
        # explain cost: same composed-estimator treatment — the sink
        # install + per-section dict merges + plan fold, on-minus-off
        tailboard.force_enabled(True)
        explain_cost_us = explain_us()
        # served denominator + informational A/B
        ab_on_qps, _cpu_on = served_round()
        tailboard.force_enabled(False)
        ab_off_qps, request_cpu_us = served_round()
        # metering accuracy: serve two tenants through their own
        # batchers, then check the per-tenant meters SUM back to the
        # total device residency kernelscope attributed — the
        # apportionment rule (shares sum to the dispatch window) is the
        # invariant the 5% gate band pins
        kernelscope.reset_for_tests()
        tenants = []
        for t in ("t0", "t1"):
            tqb = QueryBatcher(idx.search_by_vector_batch, max_batch=64,
                               owner={"collection": "bench", "tenant": t})
            tenants.append(tqb)
        try:
            for tqb in tenants:
                for _ in range(100):
                    tqb.search(q, 10)
        finally:
            for tqb in tenants:
                tqb.stop()
        metered = sum(kernelscope.meters_snapshot().values())
        total_dev = kernelscope.total_device_seconds()
        metering_sum_over_total = (metered / total_dev
                                   if total_dev > 0 else 1.0)
        # driftwatch: one full cycle (canary probes through a REAL
        # query batcher + live-telemetry classification against a
        # self-sealed baseline) timed tight-loop. The plane runs on the
        # maintenance thread every interval_s, so its served-QPS cost
        # is the amortized single-core share cycle_s / interval_s —
        # composed into the same 1/(1+overhead) ratio shape as the
        # timeline and explain terms
        from weaviate_tpu.runtime import driftwatch

        driftwatch.reset_for_tests()
        cvecs = rng.standard_normal((1024, 64)).astype(np.float32)
        cids = np.arange(1024, dtype=np.int64)
        cidx = FlatIndex(dim=64, capacity=2048)
        cidx.add_batch(cids, cvecs)
        cqb = QueryBatcher(cidx.search_by_vector_batch, max_batch=64)

        def canary_search(queries, k):
            out = []
            for cq in np.asarray(queries, dtype=np.float32):
                ids, _ = cqb.search(cq, k)
                ids = np.asarray(ids)
                out.append(ids[ids >= 0].astype(np.int64))
            return out

        driftwatch.register_canary(
            "bench/obs/-", collection="bench", shard="obs",
            search_fn=canary_search,
            corpus_fn=lambda: (cids, cvecs),
            epoch_token_fn=lambda: (len(cidx),),
            pairwise_fn=lambda qs, vs:
                ((qs[:, None, :] - vs[None, :, :]) ** 2).sum(-1))
        try:
            driftwatch.run_cycle()  # seals GT + refs + live baseline
            t0 = time.perf_counter()
            drift_reps = 5
            for _ in range(drift_reps):
                driftwatch.run_cycle()
            drift_cycle_us = ((time.perf_counter() - t0)
                              / drift_reps * 1e6)
        finally:
            cqb.stop()
        drift_period_s = driftwatch.interval_s()
        drift_ratio = 1.0 / (1.0 + (drift_cycle_us / 1e6)
                             / max(drift_period_s, 1e-9))
    finally:
        tailboard.force_enabled(None)
        qb.stop()
        tracing.clear_traces()
        kernelscope.reset_for_tests()
        from weaviate_tpu.runtime import driftwatch as _dw

        _dw.reset_for_tests()
    overhead = timeline_cost_us / max(request_cpu_us, 1e-9)
    ratio = 1.0 / (1.0 + overhead)
    explain_ratio = 1.0 / (1.0 + explain_cost_us
                           / max(request_cpu_us, 1e-9))
    out = {
        "timeline_cost_us": round(timeline_cost_us, 3),
        "request_cpu_us": round(request_cpu_us, 2),
        "on_over_off_qps": round(ratio, 4),
        "overhead_frac": round(1.0 - ratio, 4),
        "explain_cost_us": round(explain_cost_us, 3),
        "explain_on_over_off_qps": round(explain_ratio, 4),
        "metering_sum_over_total": round(metering_sum_over_total, 4),
        "drift_cycle_us": round(drift_cycle_us, 1),
        "drift_period_s": drift_period_s,
        "drift_on_over_off_qps": round(drift_ratio, 4),
        "ab_on_qps": round(ab_on_qps, 1),
        "ab_off_qps": round(ab_off_qps, 1),
    }
    log(f"[observability] timeline {timeline_cost_us:.2f} us/req over "
        f"{request_cpu_us:.0f} us served cpu -> ratio {ratio:.4f} "
        f"(overhead {out['overhead_frac'] * 100:.2f}%); explain "
        f"{explain_cost_us:.2f} us -> {explain_ratio:.4f}; metering "
        f"sum/total {metering_sum_over_total:.4f}; drift cycle "
        f"{drift_cycle_us:.0f} us / {drift_period_s:.0f}s -> "
        f"{drift_ratio:.4f}; A/B {ab_on_qps:.0f}/{ab_off_qps:.0f} qps")
    return out


def sec_durability_tax(ctx):
    """What PERSISTENCE_WAL_SYNC costs (ISSUE 9): batched put throughput
    with the WAL fsync off vs on, group-commit (one frame + one fsync
    per put_many batch) vs per-record puts (one fsync each). Host-side
    by construction — the tax under test is fsync(2), not the device;
    every timing is wall. The benchkeeper guard is the group-commit
    GAIN ratio (batched-sync qps / per-record-sync qps): if batching
    stops amortizing the fsync (a per-record fsync sneaking into the
    batch path), durable imports collapse and this ratio goes to ~1."""
    import shutil
    import tempfile

    from weaviate_tpu.storage.kv import KVStore

    batch = 100
    payload = {"v": "x" * 64}

    def run_mode(sync: bool, batched: bool, n: int) -> float:
        # per-mode op counts: the synced modes pay a real fsync(2) per
        # frame (~2-40 ms depending on the FS), so they get fewer ops —
        # qps normalizes across modes
        d = tempfile.mkdtemp(prefix="benchdur-")
        try:
            store = KVStore(d, sync_wal=sync)
            b = store.bucket("objects", memtable_limit=256 << 20)
            t0 = time.perf_counter()
            if batched:
                for i in range(0, n, batch):
                    b.put_many([(f"k{j}".encode(), payload)
                                for j in range(i, i + batch)])
            else:
                for i in range(n):
                    b.put(f"k{i}".encode(), payload)
            took = time.perf_counter() - t0
            store.close()
            return n / took
        finally:
            shutil.rmtree(d, ignore_errors=True)

    out = {
        "batch_size": batch,
        "batched_sync_off_qps": round(run_mode(False, True, 5000), 1),
        "batched_sync_on_qps": round(run_mode(True, True, 1000), 1),
        "record_sync_off_qps": round(run_mode(False, False, 3000), 1),
        "record_sync_on_qps": round(run_mode(True, False, 150), 1),
    }
    out["sync_tax_frac"] = round(
        1.0 - out["batched_sync_on_qps"] /
        max(out["batched_sync_off_qps"], 1e-9), 4)
    out["group_commit_gain"] = round(
        out["batched_sync_on_qps"] / max(out["record_sync_on_qps"], 1e-9),
        2)
    log(f"[durability] batched put {out['batched_sync_off_qps']:.0f} -> "
        f"{out['batched_sync_on_qps']:.0f} qps with sync_wal "
        f"(tax {out['sync_tax_frac']:.1%}); per-record sync "
        f"{out['record_sync_on_qps']:.0f} qps "
        f"(group-commit gain {out['group_commit_gain']:.1f}x)")
    return out


def sec_mixed_rw(ctx):
    """Sustained mixed read/write on the epoch store (ISSUE 11): a
    steady interleave of put/delete/query against an epoch-stacked
    ``EpochStore``, then a delete-heavy tail and the background
    compaction policy — asserting HBM ledger bytes actually FALL after
    compaction (the reclamation single-buffer tombstones never gave
    back). The benchkeeper guard is ``hbm_reclaimed_frac``, a
    rig-independent ratio: if compaction stops folding tombstoned
    capacity out of the ledger, mixed read/write traffic grows HBM
    without bound again and this goes to ~0."""
    import numpy as np

    from weaviate_tpu.engine.epochs import EpochStore
    from weaviate_tpu.runtime import hbm_ledger
    from weaviate_tpu.runtime.hbm_ledger import ledger as _ledger

    rng = ctx["rng"]
    dim = 128
    rows = int(os.environ.get("BENCH_MIXED_ROWS",
                              str(min(ctx.get("n", 65536), 262144))))
    epoch_rows = max(rows // 8, 2048)
    k = 10
    qbatch = 64
    mbatch = 1024
    with hbm_ledger.owner("bench_mixed", "s0"):
        store = EpochStore(dim=dim, epoch_rows=epoch_rows,
                           capacity=min(epoch_rows, 8192),
                           chunk_size=min(epoch_rows, 8192))
    # phase A: bulk fill (the staged-scatter fast path, per-epoch)
    fill = rng.standard_normal((rows, dim)).astype(np.float32)
    t0 = time.perf_counter()
    for s in range(0, rows, 4096):
        _retry_transient(lambda s=s: store.add(fill[s:s + 4096]))
    _retry_transient(store.flush_staged)
    fill_s = time.perf_counter() - t0
    # phase B: steady mixed interleave — every iteration puts a batch,
    # tombstones an older batch, and serves a query batch
    iters = int(os.environ.get("BENCH_MIXED_ITERS", "16"))
    oldest = 0
    puts = dels = queries = 0
    t0 = time.perf_counter()
    for _ in range(iters):
        _retry_transient(lambda: store.add(
            rng.standard_normal((mbatch, dim)).astype(np.float32)))
        puts += mbatch
        store.delete(np.arange(oldest, oldest + mbatch, dtype=np.int64))
        oldest += mbatch
        dels += mbatch
        q = rng.standard_normal((qbatch, dim)).astype(np.float32)
        d, i = _retry_transient(lambda q=q: store.search(q, k))
        assert (i[:, 0] >= 0).all()
        queries += qbatch
    mixed_s = max(time.perf_counter() - t0, 1e-9)
    # phase C: delete-heavy tail, then the compaction policy reclaims
    hbm_before = _ledger.shard_bytes("bench_mixed", "s0")
    total = store.count
    doomed = np.arange(oldest, total, dtype=np.int64)
    store.delete(doomed[np.arange(len(doomed)) % 4 != 0])
    store.seal_active()
    compactions0 = store.compactions_total
    for _ in range(8):
        if not store.maintain():
            break
    hbm_after = _ledger.shard_bytes("bench_mixed", "s0")
    reclaimed = 1.0 - hbm_after / max(hbm_before, 1)
    if hbm_after >= hbm_before:
        raise RuntimeError(
            f"compaction reclaimed nothing: ledger {hbm_before} -> "
            f"{hbm_after} bytes")
    # survivors still serve after the folds
    d, i = store.search(fill[: qbatch], k)
    out = {
        "rows": rows,
        "epoch_rows": epoch_rows,
        "epochs_final": store.epoch_count,
        "fill_rows_per_s": round(rows / max(fill_s, 1e-9), 1),
        "mixed_put_per_s": round(puts / mixed_s, 1),
        "mixed_delete_per_s": round(dels / mixed_s, 1),
        "mixed_query_qps": round(queries / mixed_s, 1),
        "compactions": store.compactions_total - compactions0,
        "hbm_before_bytes": int(hbm_before),
        "hbm_after_bytes": int(hbm_after),
        "hbm_reclaimed_frac": round(reclaimed, 4),
    }
    log(f"[mixed_rw] {out['mixed_query_qps']:.0f} qps under sustained "
        f"put/delete ({out['mixed_put_per_s']:.0f}/s each); "
        f"{out['compactions']} compactions reclaimed "
        f"{reclaimed:.1%} of {hbm_before / 1e6:.1f} MB")
    return out


def sec_antientropy_convergence(ctx):
    """Anti-entropy heal rate (ISSUE 14): how many hashbeat rounds (and
    how many reconciled entries) it takes to converge N divergent
    entries across 3 replicas after a partition heals. The divergence
    is manufactured with the faultline topology layer: one node is
    isolated and written at consistency ONE, so the entries exist on
    exactly one replica; the heal then has to push every one of them to
    both peers. The benchkeeper guard is ``rounds_to_converge`` — a
    pure protocol metric, independent of the rig: ONE Merkle walk +
    push/pull per peer must repair a fresh divergence, and a second
    round appearing means the diff/propagate path stopped repairing
    everything it saw."""
    import shutil
    import tempfile

    from weaviate_tpu.cluster import transport
    from weaviate_tpu.runtime import faultline

    from tools.clusterchaos import checker
    from tools.clusterchaos.workload import ChaosCluster

    n_entries = int(os.environ.get("BENCH_ANTIENTROPY_ENTRIES", "96"))
    base = tempfile.mkdtemp(prefix="bench-antientropy-")
    cluster = None
    try:
        cluster = ChaosCluster(base)
        cluster.wait_members()
        cluster.create_collection()
        shard = cluster.shard_name()
        faultline.isolate("n0", name="bench-diverge")
        col = cluster.col("n0")
        t0 = time.perf_counter()
        with faultline.node_scope("n0"):
            for i in range(n_entries):
                col.put_object({"client": 0, "seq": i, "rev": i},
                               vector=[float(i % 7), 1.0],
                               uuid=f"be000000-0000-0000-0000-{i:012d}",
                               consistency="ONE")
        write_ms = (time.perf_counter() - t0) * 1000
        faultline.heal("bench-diverge")
        checker.wait_replicas_serving(cluster, shard)
        t0 = time.perf_counter()
        conv = checker.drive_convergence(cluster, shard, max_rounds=8)
        heal_ms = (time.perf_counter() - t0) * 1000
        if not conv["converged"]:
            raise RuntimeError(f"replicas never converged: {conv}")
        out = {
            "divergent_entries": n_entries,
            "replicas": 3,
            "rounds_to_converge": conv["rounds"],
            "entries_reconciled": conv["reconciled"],
            "divergent_write_wall_ms": round(write_ms, 1),
            "heal_wall_ms": round(heal_ms, 1),
            "reconcile_per_s": round(
                conv["reconciled"] / max(heal_ms / 1000, 1e-9), 1),
        }
        log(f"[antientropy] {n_entries} divergent entries x 3 replicas "
            f"converged in {out['rounds_to_converge']} round(s), "
            f"{out['entries_reconciled']} reconciled "
            f"({out['reconcile_per_s']:.0f}/s)")
        return out
    finally:
        faultline.heal()
        transport.reset_breakers()
        if cluster is not None:
            cluster.close()
        shutil.rmtree(base, ignore_errors=True)


def sec_quantized(ctx):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.ops import pq as pq_ops
    from weaviate_tpu.ops.topk import chunked_topk_distances

    n, dim, k, batch = ctx["n"], ctx["dim"], ctx["k"], ctx["batch"]
    n_pad, chunk, dev = ctx["n_pad"], ctx["chunk"], ctx["dev"]
    valid, rng = ctx["valid"], ctx["rng"]

    cl = clustered_corpus(rng, n, dim)
    cl_pad = np.zeros((n_pad, dim), dtype=np.float32)
    cl_pad[:n] = cl
    qcl = (cl[rng.integers(0, n, batch)]
           + 0.05 * rng.standard_normal((batch, dim))).astype(np.float32)
    _, gt_cl = _cpu_exact_knn(cl, qcl, k)

    x_cl = _retry_transient(
        lambda: jax.device_put(jnp.asarray(cl_pad, dtype=jnp.bfloat16),
                               dev),
        what="clustered corpus upload")
    norms_cl = jnp.sum(jnp.asarray(x_cl, dtype=jnp.float32) ** 2, axis=-1)
    q_cl_dev = _retry_transient(
        lambda: jax.device_put(jnp.asarray(qcl), dev),
        what="clustered query upload")

    quant = {}

    def rescore_recall(cand_ids, k_eff=None):
        k_eff = k_eff or k
        cand = np.asarray(cand_ids)
        out = np.empty((len(cand), k_eff), np.int64)
        for r in range(len(cand)):
            c = cand[r][cand[r] >= 0]
            c = c[c < n]
            dd = ((qcl[r][None] - cl[c]) ** 2).sum(-1)
            out[r] = c[np.argsort(dd)[:k_eff]]
        return np.mean([len(set(out[r]) & set(gt_cl[r])) / k_eff
                        for r in range(len(cand))])

    ms_bf16_cl = _chained_ms(
        ctx,
        lambda off, q_, x_, v_, n_: chunked_topk_distances(
            q_, x_, k=k, chunk_size=chunk, metric="l2-squared",
            valid=v_, x_sq_norms=n_, id_offset=off, selection="approx"),
        (q_cl_dev, x_cl, valid, norms_cl))
    quant["bf16_flat"] = {"device_batch_ms": round(ms_bf16_cl, 3),
                          "qps": round(batch / (ms_bf16_cl / 1e3))}
    x_f32 = _retry_transient(
        lambda: jax.device_put(jnp.asarray(cl_pad, dtype=jnp.float32),
                               dev),
        what="f32 corpus upload")
    ms_f32_cl = _chained_ms(
        ctx,
        lambda off, q_, x_, v_, n_: chunked_topk_distances(
            q_, x_, k=k, chunk_size=chunk, metric="l2-squared",
            valid=v_, x_sq_norms=n_, id_offset=off, selection="approx"),
        (q_cl_dev, x_f32, valid, norms_cl))
    quant["f32_flat"] = {"device_batch_ms": round(ms_f32_cl, 3),
                         "qps": round(batch / (ms_f32_cl / 1e3))}
    del x_f32

    # BQ (MXU): packed bits in HBM, 32x compression
    k_cand = 100
    xw = bq_ops.bq_encode(jnp.asarray(cl_pad))
    qw = bq_ops.bq_encode(q_cl_dev)
    ms_bq = _chained_ms(
        ctx,
        lambda off, qw_, xw_, v_: bq_ops.bq_topk(
            qw_, xw_, k=k_cand, chunk_size=chunk, valid=v_,
            use_pallas=True, id_offset=off),
        (qw, xw, valid))
    rec_bq = _retry_transient(
        lambda: rescore_recall(bq_ops.bq_topk(
            qw, xw, k=k_cand, chunk_size=chunk, valid=valid,
            use_pallas=True)[1]),
        what="bq recall fetch")
    quant["bq_mxu"] = {"device_batch_ms": round(ms_bq, 3),
                       "qps": round(batch / (ms_bq / 1e3)),
                       "recall_at_10_rescored": round(float(rec_bq), 4)}
    log(f"[quant] BQ: {ms_bq:.2f} ms, {batch/(ms_bq/1e3):.0f} qps, "
        f"rescored recall@10 {rec_bq:.4f}")

    # PQ4 (16 centroids, m=d/4): LUT-matmul ADC
    book = pq_ops.pq_fit(cl[:min(200_000, n)], m=dim // 4, k=16, iters=8)
    codes = jnp.asarray(pq_ops.pq_encode(book, cl_pad))
    ms_pq4 = _chained_ms(
        ctx,
        lambda off, q_, c_, cent_, v_: pq_ops.pq4_topk(
            q_, c_, cent_, k=k_cand, chunk_size=chunk,
            metric="l2-squared", valid=v_, id_offset=off),
        (q_cl_dev, codes, book.centroids, valid))
    rec_pq4 = _retry_transient(
        lambda: rescore_recall(pq_ops.pq4_topk(
            q_cl_dev, codes, book.centroids, k=k_cand, chunk_size=chunk,
            metric="l2-squared", valid=valid)[1]),
        what="pq4 recall fetch")
    quant["pq4_lut"] = {"device_batch_ms": round(ms_pq4, 3),
                        "qps": round(batch / (ms_pq4 / 1e3)),
                        "recall_at_10_rescored": round(float(rec_pq4), 4)}
    log(f"[quant] PQ4: {ms_pq4:.2f} ms, {batch/(ms_pq4/1e3):.0f} qps, "
        f"rescored recall@10 {rec_pq4:.4f}")

    # two-stage PQ (r4 verdict item 6): 128-bit BQ sign prefix stage 1 ->
    # gathered exact-ADC stage 2 (ops/pq.pq_topk_twostage)
    xp_t = jnp.transpose(xw[:, :4]).copy()
    ms_pq2 = _chained_ms(
        ctx,
        lambda off, q_, qw_, c_, cent_, xp_, v_: pq_ops.pq_topk_twostage(
            q_, qw_, c_, cent_, xp_, k=k_cand, refine=8,
            metric="l2-squared", valid=v_, id_offset=off),
        (q_cl_dev, qw, codes, book.centroids, xp_t, valid))
    rec_pq2 = _retry_transient(
        lambda: rescore_recall(pq_ops.pq_topk_twostage(
            q_cl_dev, qw, codes, book.centroids, xp_t, k=k_cand,
            refine=8, metric="l2-squared", valid=valid)[1]),
        what="pq twostage recall fetch")
    quant["pq_twostage128"] = {
        "device_batch_ms": round(ms_pq2, 3),
        "qps": round(batch / (ms_pq2 / 1e3)),
        "recall_at_10_rescored": round(float(rec_pq2), 4)}
    log(f"[quant] PQ 2-stage/128: {ms_pq2:.2f} ms, "
        f"{batch/(ms_pq2/1e3):.0f} qps, rescored recall@10 {rec_pq2:.4f}")
    ctx["quant"] = quant
    return {"stats": quant}


def sec_ivf_ann(ctx):
    """Learned partitioned ANN (ISSUE 16): residual IVF-PQ through the
    REAL serving path (multi-probe ADC + device plane rescore) on a
    clustered corpus, next to the exhaustive BQ flat scan at the SAME
    scale — the crossover partitioning exists to win.

    Reported: recall@10 through ``search()``, chained device ms of the
    probe kernel, the fraction of lists actually probed, and
    ``qps_vs_bq_flat`` (>1 = probing a few lists beats scanning every
    code)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.engine.ivf import (IVFIndex, _dummy_bits,
                                         _ivf_probe_topk_pq)
    from weaviate_tpu.ops import bq as bq_ops

    dim, k, batch = ctx["dim"], ctx["k"], ctx["batch"]
    rng, dev = ctx["rng"], ctx["dev"]
    # bounded build: the probe cost story is per-list, not per-corpus —
    # tools/bench_ivf.py owns the 1M/10M builds
    n = min(ctx["n"], 262_144)
    cl = clustered_corpus(rng, n, dim)
    q = (cl[rng.integers(0, n, batch)]
         + 0.05 * rng.standard_normal((batch, dim))).astype(np.float32)
    _, gt = _cpu_exact_knn(cl, q, k)

    idx = IVFIndex(dim=dim, train_threshold=min(n, 131_072),
                   delta_threshold=65_536, quantization="pq")
    t0 = time.perf_counter()
    for s in range(0, n, 65_536):
        idx.add_batch(np.arange(s, min(s + 65_536, n)),
                      cl[s:s + 65_536])
    if not idx.trained:
        idx.train()
    idx.store.flush_delta()
    build_s = time.perf_counter() - t0
    st = idx.store

    # recall + probe config through the real serving path
    ids, _ = _retry_transient(lambda: idx.search_by_vector_batch(q, k),
                              what="ivf recall search")
    ids = np.asarray(ids)
    rec = np.mean([len(set(ids[r][ids[r] >= 0].tolist())
                       & set(gt[r].tolist())) / k for r in range(batch)])
    h = st.search_async(q, k)
    h.result()
    nprobe = int(h.attrs["nprobe"])
    lists_frac = float(h.attrs["lists_frac"])

    qd = _retry_transient(lambda: jax.device_put(jnp.asarray(q), dev),
                          what="ivf query upload")
    allow = _dummy_bits()
    k_eff = min(k * st.rescore_limit, nprobe * st.list_cap)
    ms_ivf = _chained_ms(
        ctx,
        lambda off, q_, c_, cn_, lc_, lv_, ls_, lt_, pc_:
        _ivf_probe_topk_pq(q_, c_, cn_, lc_, lv_, ls_, lt_, pc_, allow,
                           k_eff, nprobe, "l2-squared", False),
        (qd, st.centroids, st._c_norms, st.list_codes, st.list_valid,
         st.list_slots, st.list_tvals, st.codebook.centroids))

    # exhaustive BQ flat at the SAME corpus size: the comparator the
    # qps ratio is defined against
    n_pad2 = 1 << (n - 1).bit_length()
    pad = np.zeros((n_pad2, dim), np.float32)
    pad[:n] = cl
    xw = _retry_transient(
        lambda: jax.block_until_ready(bq_ops.bq_encode(jnp.asarray(pad))),
        what="bq encode")
    qw = bq_ops.bq_encode(qd)
    valid2 = jnp.asarray(np.arange(n_pad2) < n)
    ms_bq = _chained_ms(
        ctx,
        lambda off, qw_, xw_, v_: bq_ops.bq_topk(
            qw_, xw_, k=min(100, n_pad2),
            chunk_size=min(ctx["chunk"], n_pad2), valid=v_,
            use_pallas=True, id_offset=off),
        (qw, xw, valid2))

    out = {
        "n": n, "nlist": st.nlist, "nprobe": nprobe,
        "lists_frac": round(lists_frac, 4),
        "recall_at_10": round(float(rec), 4),
        "device_probe_ms": round(ms_ivf, 3),
        "qps": round(batch / (ms_ivf / 1e3)),
        "bq_flat_ms": round(ms_bq, 3),
        "qps_vs_bq_flat": round(ms_bq / ms_ivf, 2),
        "build_vec_per_s": round(n / build_s),
    }
    log(f"[ivf_ann] recall@10 {rec:.4f} probing "
        f"{lists_frac*100:.1f}% of {st.nlist} lists; probe "
        f"{ms_ivf:.2f} ms vs BQ flat {ms_bq:.2f} ms "
        f"({out['qps_vs_bq_flat']}x)")
    ctx["ivf_ann"] = out
    return {"stats": out}


def sec_conformance(ctx):
    import numpy as np

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return {"skipped": "compiled (Mosaic) conformance needs a TPU"}

    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.ops.pallas_kernels import (bq_mxu_block,
                                                 distance_block,
                                                 pq4_lut_block)

    rng = ctx["rng"]
    dim = ctx["dim"]
    conformance = "ok"
    cq = rng.standard_normal((8, dim)).astype(np.float32)
    cx = rng.standard_normal((512, dim)).astype(np.float32)
    out = _retry_transient(
        lambda: np.asarray(distance_block(
            jnp.asarray(cq), jnp.asarray(cx), metric="l2-squared",
            interpret=False)),
        what="conformance distance fetch")
    ref = ((cq[:, None] - cx[None]) ** 2).sum(-1)
    if not np.allclose(out, ref, rtol=1e-4, atol=1e-3):
        conformance = f"distance_block mismatch {np.abs(out-ref).max()}"
    qb_ = bq_ops.bq_encode(jnp.asarray(cq))
    xb_ = bq_ops.bq_encode(jnp.asarray(cx))
    out = _retry_transient(
        lambda: np.asarray(bq_mxu_block(qb_, xb_, interpret=False)),
        what="conformance bq fetch")
    ref = bq_ops.bq_hamming_np(
        np.ascontiguousarray(np.asarray(qb_)),
        np.ascontiguousarray(np.asarray(xb_)))
    if not np.array_equal(out, ref):
        conformance = f"bq_mxu_block mismatch {np.abs(out-ref).max()}"
    m4 = dim // 4
    lut = rng.standard_normal((8, m4, 16)).astype(np.float32)
    codes4 = rng.integers(0, 16, (512, m4)).astype(np.uint8)
    out = _retry_transient(
        lambda: np.asarray(pq4_lut_block(
            jnp.asarray(lut), jnp.asarray(codes4), interpret=False)),
        what="conformance pq4 fetch")
    lut16 = np.asarray(jnp.asarray(lut, dtype=jnp.bfloat16), np.float32)
    ref = np.zeros((8, 512), np.float32)
    for s in range(m4):
        ref += lut16[:, s, :][:, codes4[:, s]]
    tol = 8e-3 * max(np.abs(ref).max(), 1.0)
    if not np.allclose(out, ref, atol=tol):
        conformance = f"pq4_lut_block mismatch {np.abs(out-ref).max()}"
    # fused top-k kernel, compiled (Mosaic) vs numpy ground truth
    from weaviate_tpu.ops.pallas_kernels import (fused_topk_scan,
                                                 pack_allow_bitmask)

    fi = _retry_transient(
        lambda: np.asarray(fused_topk_scan(
            jnp.asarray(cq), jnp.asarray(cx), k=10, interpret=False)[1]),
        what="conformance fused fetch")
    dist = ((cq[:, None] - cx[None]) ** 2).sum(-1)
    want_i = np.argsort(dist, axis=1, kind="stable")[:, :10]
    if not np.array_equal(fi, want_i):
        conformance = "fused_topk_scan id mismatch"
    # masked variant: per-query allow bitmask unpacked in VMEM (compiled)
    allow = rng.random((8, 512)) < 0.3
    allow[:, :16] = True  # never fewer than k allowed
    fi = _retry_transient(
        lambda: np.asarray(fused_topk_scan(
            jnp.asarray(cq), jnp.asarray(cx), k=10, interpret=False,
            allow_bits=jnp.asarray(pack_allow_bitmask(allow)))[1]),
        what="conformance masked fused fetch")
    want_m = np.argsort(np.where(allow, dist, np.inf), axis=1,
                        kind="stable")[:, :10]
    if not np.array_equal(fi, want_m):
        conformance = "fused_topk_scan masked id mismatch"
    ctx["conformance"] = conformance
    log(f"kernel conformance (compiled, on-device): {conformance}")
    return {"status": conformance}


def sec_served_pipeline(ctx):
    """Served-path pipeline microbench (ISSUE 7): the SAME continuous
    batcher driven by closed-loop concurrent clients, sync (the worker
    fetches batch N's results before dispatching N+1) vs the
    double-buffered zero-sync pipeline (batch N drains D2H on the
    transfer thread while N+1's program is already on the device).
    CPU-runnable — the overlap it measures is dispatch-vs-drain
    concurrency, which exists on every async-dispatch backend; on the
    TPU the drained window also covers the D2H transfer."""
    import threading

    import numpy as np

    from weaviate_tpu.engine.flat import FlatIndex
    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    rng = np.random.default_rng(7)
    n, dim, k = (int(os.environ.get("BENCH_SERVED_ROWS", "32768")), 64,
                 10)
    idx = FlatIndex(dim=dim, capacity=n, chunk_size=8192)
    idx.add_batch(np.arange(n),
                  rng.standard_normal((n, dim)).astype(np.float32))
    queries = rng.standard_normal((2048, dim)).astype(np.float32)
    duration = float(os.environ.get("BENCH_SERVED_S", "2.0"))
    clients = int(os.environ.get("BENCH_SERVED_CLIENTS", "8"))
    # warm the pow2 (B, k) buckets both modes will hit so neither run
    # pays jit compiles inside its timed window
    b = 1
    while b <= min(64, clients * 2):
        _retry_transient(lambda b=b: idx.search_by_vector_batch(
            np.tile(queries[:1], (b, 1)), 16), what=f"warm b={b}")
        b *= 2

    def drive(qb):
        stop_at = time.perf_counter() + duration
        counts = [0] * clients

        def worker(j):
            i = j
            while time.perf_counter() < stop_at:
                ids, _ = qb.search(queries[i % len(queries)], k)
                assert len(ids) == k
                counts[j] += 1
                i += clients

        ths = [threading.Thread(target=worker, args=(j,))
               for j in range(clients)]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        return sum(counts), time.perf_counter() - t0

    out = {"rows": n, "dim": dim, "k": k, "clients": clients,
           "duration_s": duration}
    for mode in ("sync", "async"):
        qb = QueryBatcher(
            idx.search_by_vector_batch, max_batch=64,
            async_batch_fn=(idx.search_by_vector_batch_async
                            if mode == "async" else None))
        try:
            qb.search(queries[0], k)  # settle the worker thread
            done, wall = drive(qb)
            out[mode] = {
                "qps": round(done / wall, 1),
                "dispatches": qb.dispatches,
                "mean_batch": round(qb.batched_queries
                                    / max(qb.dispatches, 1), 2),
            }
            if mode == "async":
                out[mode]["async_dispatches"] = qb.async_dispatches
                out[mode]["overlapped_dispatches"] = \
                    qb.overlapped_dispatches
        finally:
            qb.stop()
    out["async_over_sync"] = round(
        out["async"]["qps"] / max(out["sync"]["qps"], 1e-9), 3)
    log(f"[served_pipeline] sync {out['sync']['qps']} qps, async "
        f"{out['async']['qps']} qps ({out['async_over_sync']}x), "
        f"{out['async']['overlapped_dispatches']} overlapped dispatches")
    ctx["served_pipeline"] = out
    return out


def sec_hybrid_search(ctx):
    """Hybridplane (ISSUE 18): device-resident BM25 + sparse/dense
    fusion as ONE batched program, measured through the REAL serving
    path (posting pack -> fused dispatch -> single D2H), against the
    host scorer + serial dense leg it replaces.

    Reported per batch size: sparse-only (alpha=0), dense-only
    (alpha=1) and fused (alpha=0.5) served QPS, plus the fused
    program's device-side batch ms with operands prepacked (isolates
    the program from host posting-pack cost, which is reported once as
    ``pack_ms``). ``qps_vs_host`` is fused device QPS at the largest
    batch over the host-scorer baseline — the number the hybridplane
    exists to move (>1 = one fused program beats host MaxScore + a
    serial dense search per query)."""
    import tempfile

    import numpy as np

    from weaviate_tpu.db.database import Database
    from weaviate_tpu.schema.config import (CollectionConfig, DataType,
                                            Property, VectorConfig)

    rng = np.random.default_rng(18)
    n = int(os.environ.get("BENCH_HYBRID_ROWS", "4096"))
    dim, k = 64, 10
    vocab = [f"w{i:03d}" for i in range(256)]
    db = Database(tempfile.mkdtemp(prefix="bench-hybrid-"))
    try:
        col = db.create_collection(CollectionConfig(
            name="Hy",
            properties=[Property(name="body", data_type=DataType.TEXT)],
            vectors=[VectorConfig()],
        ))
        t0 = time.perf_counter()
        draws = rng.zipf(1.3, size=(n, 24)) % len(vocab)
        vecs = rng.standard_normal((n, dim)).astype(np.float32)
        for i in range(n):
            col.put_object({"body": " ".join(vocab[j] for j in draws[i])},
                           vector=vecs[i])
        build_s = time.perf_counter() - t0
        shard = list(col.shards.values())[0]
        idx = shard._hybrid_index("")
        assert idx is not None, "device hybrid path unavailable"

        qn = 256
        qtexts = [" ".join(rng.choice(vocab[:96], size=3)) for _ in range(qn)]
        qvecs = rng.standard_normal((qn, dim)).astype(np.float32)

        def op_for(j, alpha):
            return shard._hybrid_operand(idx, qtexts[j], k, alpha,
                                         "relativeScore", None, None)

        def drive(alpha, batch, iters):
            """Closed-loop served QPS: pack + fused dispatch + drain."""
            t0 = time.perf_counter()
            for it in range(iters):
                s = (it * batch) % (qn - batch + 1)
                ops = [op_for(s + j, alpha) for j in range(batch)]
                h = _retry_transient(
                    lambda: idx.hybrid_batch_async(
                        qvecs[s:s + batch], k, None, ops),
                    what=f"hybrid b={batch}")
                ids, _ = h.result()
                assert ids.shape == (batch, k)
            return (batch * iters) / (time.perf_counter() - t0)

        out = {"rows": n, "dim": dim, "k": k,
               "build_vec_per_s": round(n / build_s), "batches": {}}

        # posting-pack host cost, once (shared across paths)
        t0 = time.perf_counter()
        packed = [op_for(j, 0.5) for j in range(64)]
        out["pack_ms"] = round((time.perf_counter() - t0) / 64 * 1e3, 3)

        iters = int(os.environ.get("BENCH_HYBRID_ITERS", "64"))
        for batch in (1, 8, 32):
            row = {}
            for name, alpha in (("sparse", 0.0), ("dense", 1.0),
                                ("fused", 0.5)):
                drive(alpha, batch, 2)  # warm the (B, k) bucket
                row[f"{name}_qps"] = round(drive(alpha, batch, iters), 1)
            # fused device ms with operands prepacked: the program
            # alone, no per-iteration posting-pack work
            ops = (packed * batch)[:batch]
            h = idx.hybrid_batch_async(
                np.tile(qvecs[:1], (batch, 1)), k, None, ops)
            h.result()
            t0 = time.perf_counter()
            for _ in range(iters):
                idx.hybrid_batch_async(
                    np.tile(qvecs[:1], (batch, 1)), k, None,
                    ops).result()
            row["device_ms"] = round(
                (time.perf_counter() - t0) / iters * 1e3, 3)
            out["batches"][str(batch)] = row

        # host-scorer baseline: kill switch off -> host MaxScore BM25 +
        # a serial dense search + host fusion, one query at a time (the
        # host path has no batched form — that asymmetry IS the story)
        shard.device_hybrid = False
        try:
            for j in range(4):
                col.hybrid(qtexts[j], vector=qvecs[j], alpha=0.5, k=k,
                           fusion="relativeScore", include_objects=False)
            t0 = time.perf_counter()
            for it in range(iters):
                col.hybrid(qtexts[it % qn], vector=qvecs[it % qn],
                           alpha=0.5, k=k, fusion="relativeScore",
                           include_objects=False)
            out["host_fused_qps"] = round(
                iters / (time.perf_counter() - t0), 1)
        finally:
            shard.device_hybrid = True

        top = out["batches"]["32"]
        out["qps_vs_host"] = round(
            top["fused_qps"] / max(out["host_fused_qps"], 1e-9), 2)
        log(f"[hybrid_search] fused b32 {top['fused_qps']} qps "
            f"(device {top['device_ms']} ms, pack {out['pack_ms']} ms) "
            f"vs host scorer {out['host_fused_qps']} qps "
            f"({out['qps_vs_host']}x)")
        ctx["hybrid_search"] = out
        return out
    finally:
        db.close()


def sec_fabric(ctx):
    """Serving fabric (native data plane, null device) — isolates the C++
    gRPC fabric from the device. Best-effort:
    absent libnghttp2, reports skipped."""
    import numpy as np

    from weaviate_tpu.native import dataplane as dpn

    if not dpn.available():
        return {"skipped": "native dataplane unavailable"}
    import tempfile

    os.environ["WEAVIATE_TPU_NATIVE_DATAPLANE"] = "1"
    from weaviate_tpu.api.grpc import v1_pb2 as pbv
    from weaviate_tpu.config import ServerConfig
    from weaviate_tpu.server import Server

    srv = Server(ServerConfig(
        data_path=tempfile.mkdtemp(prefix="bench-fabric-"),
        rest_port=0, grpc_port=0, disable_telemetry=True)).start()
    try:
        if not hasattr(srv.grpc, "dp"):
            return {"skipped": "no native plane on grpc server"}
        col = srv.db.create_collection_from_dict({
            "class": "Fab",
            "vectorIndexType": "flat",
            "properties": [
                {"name": "seq", "dataType": ["int"]}],
        }) if hasattr(srv.db, "create_collection_from_dict") else None
        if col is None:
            from weaviate_tpu.schema.config import CollectionConfig, Property

            col = srv.db.create_collection(CollectionConfig(
                name="Fab",
                properties=[Property(name="seq", data_type="int")]))
        fr = np.random.default_rng(0)
        col.batch_put([
            {"properties": {"seq": i},
             "vector": fr.standard_normal(32).astype(np.float32)}
            for i in range(5000)])
        srv.grpc._maybe_register("Fab", warm=False)
        srv.grpc.warm_collection("Fab")
        shard = next(iter(col.shards.values()))
        cid = np.tile(np.arange(10, dtype=np.int64), (256, 1))
        cdd = np.tile(np.linspace(0.01, 0.1, 10, dtype=np.float32),
                      (256, 1))
        cnn = np.full(256, 10, np.int64)
        shard.vector_search_batch = (
            lambda qs, k2, vec_name="": (cid[:len(qs), :k2],
                                         cdd[:len(qs), :k2],
                                         cnn[:len(qs)]))
        # force the plane's sync fallback so the null-device stub above
        # is what actually serves (the pipelined path would dispatch the
        # real index and contaminate the fabric-only measurement)
        shard.vector_search_batch_async = lambda qs, k2, vec_name="": None
        head = pbv.SearchRequest(collection="Fab", limit=10,
                                 uses_123_api=True)
        head.metadata.uuid = True
        head.metadata.distance = True
        st = dpn.bench(srv.grpc.port, conns=8, streams=8,
                       duration_ms=4000, dim=32,
                       request_head=head.SerializeToString())
        fabric = {"qps": round(st["qps"]),
                  "p50_ms": round(st["p50_ms"], 2),
                  "p95_ms": round(st["p95_ms"], 2),
                  "streams": 64, "errors": st["errors"]}
        log(f"[fabric] native plane null-device: {fabric}")
        ctx["fabric"] = fabric
        return fabric
    finally:
        srv.stop()


# (name, fn, ctx keys produced upstream that the section requires)
def sec_hierarchical_merge(ctx):
    """ISSUE 13: flat 1-D merge vs the two-level ICI+DCN merge.

    Three parts, in decreasing rig-independence:

    1. ``dcn_bytes_ratio`` — the GATED metric: per-host cross-DCN
       candidate bytes, two-level / flat, computed from pure topology
       math for the reference 2-host x 4-device pod (the virtual mesh
       every parity test runs on). Rig-independent by construction —
       benchkeeper gates it with a tight band on any platform.
    2. A LIVE flat-vs-two-level BQ scan on the local devices arranged
       as a 2x(n/2) hierarchical mesh (skipped fields when the rig has
       fewer than 2 devices or an odd count): parity check + wall
       timings + QPS.
    3. The 1B-vector BQ DRY RUN: the full placement plan — shard-
       aligned capacity, per-component bytes, per-host HBM load — for
       1e9 x 768 BQ on the hierarchical mesh, no allocation (the codes
       alone are 96 GB; planning is what the ledger admission gates
       against).
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.parallel import partition
    from weaviate_tpu.parallel.mesh import (make_hierarchical_mesh,
                                            make_mesh)
    from weaviate_tpu.parallel.sharded_search import (
        replicate_array, shard_array, sharded_quantized_topk,
        topology_dcn_candidate_bytes)

    k = 32  # ICI-divisible on the 2x4 reference pod: zero slice padding
    ref_hosts, ref_local = 2, 4
    flat_bytes = topology_dcn_candidate_bytes(ref_hosts, ref_local, k,
                                              level="flat")
    two_bytes = topology_dcn_candidate_bytes(ref_hosts, ref_local, k,
                                             level="two_level")
    compact_bytes = topology_dcn_candidate_bytes(
        ref_hosts, ref_local, k, level="two_level", compact=True)
    out = {
        "ref_topology": f"{ref_hosts}x{ref_local}",
        "k": k,
        "dcn_bytes_flat_per_host": flat_bytes,
        "dcn_bytes_two_level_per_host": two_bytes,
        "dcn_bytes_two_level_compact_per_host": compact_bytes,
        "dcn_bytes_ratio": round(two_bytes / flat_bytes, 4),
        "dcn_bytes_ratio_compact": round(compact_bytes / flat_bytes, 4),
    }
    log(f"DCN candidate bytes/query/host on {ref_hosts}x{ref_local}: "
        f"flat {flat_bytes} (O(devices*k)) -> two-level {two_bytes} "
        f"(O(hosts*k), ratio {out['dcn_bytes_ratio']})")

    # live flat-vs-hierarchical run on whatever devices exist
    n_dev = len(jax.devices())
    if n_dev >= 2 and n_dev % 2 == 0:
        n = int(os.environ.get("BENCH_HIER_N", "131072"))
        dim, b = 128, 64
        rng = np.random.default_rng(3)
        # chunk-aligned rows per device
        n = max(n // n_dev, 1024) * n_dev
        xb = rng.standard_normal((n, dim)).astype(np.float32)
        qv = rng.standard_normal((b, dim)).astype(np.float32)
        codes = np.asarray(bq_ops.bq_encode(jnp.asarray(xb)))
        qw = np.asarray(bq_ops.bq_encode(jnp.asarray(qv)))
        valid = np.ones(n, dtype=bool)
        meshes = {"flat_1d": make_mesh(),
                  "two_level": make_hierarchical_mesh(n_hosts=2)}
        reps = max(_bench_repeats(), 3)
        results = {}
        parity = {}
        for name, mesh in meshes.items():
            args = (replicate_array(jnp.asarray(qv), mesh),
                    replicate_array(jnp.asarray(qw), mesh),
                    shard_array(jnp.asarray(codes), mesh),
                    shard_array(jnp.asarray(valid), mesh),
                    None, None)
            kw = dict(k=k, k_out=k, chunk_size=min(4096, n // n_dev),
                      quantization="bq", metric="l2-squared", mesh=mesh)

            def run_once(args=args, kw=kw):
                d, i = sharded_quantized_topk(*args, **kw)
                jax.block_until_ready((d, i))
                return d, i

            d, i = _retry_transient(run_once, what=f"hier/{name} warm")
            parity[name] = (np.asarray(d), np.asarray(i))
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                run_once()
                best = min(best, time.perf_counter() - t0)
            results[name] = {
                "batch_ms": round(best * 1e3, 3),
                "qps": round(b / best, 1),
            }
        parity_ok = bool(
            np.array_equal(parity["flat_1d"][0], parity["two_level"][0])
            and np.array_equal(parity["flat_1d"][1],
                               parity["two_level"][1]))
        # a parity break is a MERGE bug, not a perf datum — fail the
        # section loudly (the gated dcn_bytes_ratio is topology math
        # and cannot see wire-format regressions; this assert can)
        assert parity_ok, "two-level merge diverged from flat 1-D merge"
        out["live"] = {
            "n": n, "dim": dim, "batch": b,
            "mesh": f"2x{n_dev // 2}",
            **{name: r for name, r in results.items()},
            "parity_bit_identical": parity_ok,
        }
        log(f"live 2x{n_dev // 2} BQ {n} rows: "
            f"flat {results['flat_1d']['batch_ms']} ms vs two-level "
            f"{results['two_level']['batch_ms']} ms, parity="
            f"{out['live']['parity_bit_identical']}")
        mesh_1b = meshes["two_level"]
    else:
        out["live"] = {"skipped": f"{n_dev} device(s)"}
        mesh_1b = None

    # 1B-vector BQ dry run: plan only, zero allocation
    plan = partition.plan_corpus_placement(
        1_000_000_000, 768, mesh_1b, quantization="bq", chunk_size=4096)
    assert plan["capacity"] % plan["shards"] == 0
    assert sum(plan["perHostBytes"].values()) == plan["totalBytes"]
    out["dry_run_1b"] = {
        "rows": plan["rows"], "hosts": plan["hosts"],
        "rowsPerDevice": plan["rowsPerDevice"],
        "totalGB": round(plan["totalBytes"] / 1e9, 2),
        "perHostGB": {h: round(v / 1e9, 2)
                      for h, v in plan["perHostBytes"].items()},
        "dcnBytesPerQueryPerHost": topology_dcn_candidate_bytes(
            plan["hosts"], max(plan["shards"] // plan["hosts"], 1), k,
            level="two_level") if plan["hosts"] > 1 else 0,
    }
    log(f"1B x 768 BQ dry run: {out['dry_run_1b']['totalGB']} GB over "
        f"{plan['hosts']} host(s), {plan['rowsPerDevice']} rows/device")
    return out


SECTIONS = [
    ("setup", sec_setup, ()),
    ("cpu_baseline", sec_cpu_baseline, ("corpus", "queries")),
    ("device_setup", sec_device_setup, ("corpus",)),
    ("flat_headline", sec_flat_headline, ("x", "queries")),
    ("device_steady", sec_device_steady, ("x", "rtt_s")),
    ("selection_microbench", sec_selection_microbench, ("x", "rtt_s")),
    ("filtered_scan", sec_filtered_scan, ("x", "rtt_s")),
    ("quantized", sec_quantized, ("x", "rtt_s")),
    ("ivf_ann", sec_ivf_ann, ("rtt_s",)),
    ("tracing_overhead", sec_tracing_overhead, ()),
    ("observability_overhead", sec_observability_overhead, ()),
    ("durability_tax", sec_durability_tax, ()),
    ("antientropy_convergence", sec_antientropy_convergence, ()),
    ("mixed_rw", sec_mixed_rw, ("rng",)),
    ("kernel_conformance", sec_conformance, ("rng",)),
    ("hierarchical_merge", sec_hierarchical_merge, ()),
    ("served_pipeline", sec_served_pipeline, ()),
    ("hybrid_search", sec_hybrid_search, ()),
    ("serving_fabric", sec_fabric, ()),
]


def main():
    wd = _watchdog(float(os.environ.get("BENCH_WATCHDOG_S", "1500")))
    ctx: dict = {}
    for name, fn, deps in SECTIONS:
        run_section(name, fn, ctx, deps)

    wd.cancel()
    sections = RESULTS["sections"]
    headline = sections.get("flat_headline", {})
    cpu_qps = ctx.get("cpu_qps", 0.0)
    qps = ctx.get("qps", 0.0)
    final = {
        "metric": "flat_knn_qps_synth1M_128d_k10",
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / cpu_qps, 2) if cpu_qps else 0.0,
        "recall_at_10": headline.get("recall_at_10"),
        "p50_batch_ms": headline.get("p50_batch_ms"),
        "batch": ctx.get("batch"),
        "baseline_cpu_qps": round(cpu_qps, 1),
        "device": ctx.get("device_stats"),
        "selection_microbench": sections.get("selection_microbench"),
        "filtered_scan": sections.get("filtered_scan"),
        "quantized_clustered_1M_128d": ctx.get("quant"),
        "ivf_ann": ctx.get("ivf_ann"),
        "hybrid_search": ctx.get("hybrid_search"),
        "kernel_conformance": ctx.get("conformance"),
        "serving_fabric_null_device": ctx.get("fabric"),
        "fetch_rtt_ms": round(ctx.get("rtt_s", 0.0) * 1e3, 1),
        "env_fingerprint": _env_fingerprint(),
        "bench_repeats": _bench_repeats(),
        "sections": sections,
    }
    failed = [n for n, s in sections.items() if not s.get("ok")]
    if failed:
        final["failed_sections"] = failed
    final["perf_gate"] = _self_gate(RESULTS | final)
    RESULTS.update(final)
    _emit_partial()
    print(json.dumps(final), flush=True)
    # partial results are still results: rc=0 so the driver parses them
    # (the embedded perf_gate verdict + __graft_entry__.bench_gate /
    # `python -m tools.benchkeeper BENCH_rNN.json` carry the gate)
    sys.exit(0)


def _self_gate(run: dict) -> dict:
    """Self-gating (ROADMAP item 5 leftover): every bench round compares
    itself against tools/benchkeeper/baseline.json and EMBEDS the
    verdict summary, so a regression can't land silently even when the
    driver forgets the standalone `python -m tools.benchkeeper` step.
    A fingerprint refusal (e.g. this run is a CPU smoke, the baseline
    names the TPU rig) is recorded as refused, not failed. BENCH_GATE=0
    opts out."""
    if os.environ.get("BENCH_GATE", "1").lower() in ("0", "false", "off"):
        return {"skipped": "BENCH_GATE=0"}
    try:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools.benchkeeper import core as bk

        path = bk.default_baseline_path()
        verdict = bk.compare(run, bk.load_baseline(path),
                             baseline_path=path)
        bk.render(verdict, out=sys.stderr)
        if verdict.get("refused") is None:
            # same artifact the CLI writes — /v1/debug/perf and the
            # bench gauges pick this round up without a second command
            bk.write_verdict(verdict, bk.default_verdict_path())
        return {
            # a REFUSED comparison (cross-rig fingerprint) is not a gate
            # failure — benchkeeper keeps the states distinct (exit 1 vs
            # exit 2), and a driver keying on perf_gate["ok"] must not
            # fail every CPU smoke round against the TPU baseline; the
            # refusal itself rides the "refused" field
            "ok": bool(verdict["ok"]) or bool(verdict.get("refused")),
            "refused": (verdict["refused"] or {}).get("mismatched")
            if verdict.get("refused") else None,
            "checked": verdict.get("checked", 0),
            "regressions": verdict.get("regressions", 0),
            "stale": verdict.get("stale", 0),
            "missing": verdict.get("missing", 0),
            "failing_entries": [
                {"id": e["id"], "status": e["status"],
                 "gate_reason": e.get("gate_reason")}
                for e in verdict.get("entries", [])
                if e.get("status") not in (None, "pass")],
        }
    except Exception as e:  # noqa: BLE001 — the gate must not eat the run
        return {"error": repr(e)}


if __name__ == "__main__":
    main()
