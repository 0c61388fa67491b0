"""Kernelscope — the device-time truth plane (four faces, ISSUE 17).

The zero-sync serving path deliberately removed the only honest device
clock we had: ``block_until_ready`` attribution exists only on sampled
traces, so tailboard's ``device`` phase was dispatch *wall*-clock. This
module turns the stamps the pipeline already takes for free into
attributed chip time, on every request:

1. **Per-dispatch chip timing without host sync.** The
   TransferPipeline's drain thread blocks on each handle's D2H anyway;
   the batcher stamps dispatch-submit before the device call and the
   drain thread stamps transfer-complete after ``handle.result()``.
   That window is ``device + memcpy``; subtracting the measured memcpy
   EWMA (fed by the sampled ``transfer.d2h`` split, which times
   ``block_until_ready`` separately from the ``np.asarray`` copy)
   yields device residency with **zero** new syncs. Attribution is
   labeled ``source="drain"``; when no async twin serves (sync engines,
   null-device bench stubs) it degrades to the dispatch wall window
   with ``source="wall"`` instead of crashing or emitting zeros.
   Residency feeds an EWMA + histogram per (index-kind, batch-bucket,
   k-bucket) compiled variant and tailboard's per-request ``device``
   phase.

2. **Per-query EXPLAIN.** ``?explain=true`` (REST) / ``x-explain``
   (gRPC metadata) installs a request-level sink; the batcher installs
   a dispatch-level sink around the engine call on its worker thread;
   engine layers call :func:`explain_note` with cheap host-side ints
   only (no device reads — graftlint G1 stays empty). The batcher adds
   its coalescing decision and merges the dispatch plan back into the
   request sink on the request thread. Explain never changes what is
   dispatched: sync and async answers are bit-identical.

3. **Per-tenant device metering.** Each dispatch's residency is
   apportioned across the requests it coalesced (weighted by rows
   scanned; a batcher is per-(shard, vector) so the owner labels are
   uniform) into ``weaviate_tpu_device_seconds_total{collection,
   tenant}`` — the interference signal the QoS scheduler consumes.

4. **On-demand kernel profiles.** :func:`capture_profile` wraps the
   already-wired ``jax.profiler`` programmatic trace, parses the
   perfetto/chrome events into per-kernel device-ms ranked by
   :data:`KERNEL_REGISTRY`, and persists the last K captures under the
   data dir (``GET /v1/debug/profile?ms=N``).
"""

from __future__ import annotations

import contextlib
import contextvars
import glob
import gzip
import json
import os
import re
import tempfile
import threading
import time

from weaviate_tpu.runtime.metrics import (
    device_seconds_total,
    dispatch_device_seconds,
)

# -- face 1: drain-stamp device timing ----------------------------------------

#: EWMA weight for both the memcpy estimator and the per-variant
#: residency — heavy enough to track a recompile, light enough that one
#: preempted drain doesn't whipsaw the estimate.
_ALPHA = 0.2

_lock = threading.Lock()
# memcpy seconds per pow2-bytes bucket (bucket = nbytes.bit_length()),
# plus a global fallback for result shapes never seen on a sampled trace
_memcpy_ewma: dict[int, float] = {}
_memcpy_global: float | None = None
_memcpy_samples = 0
# (kind, b_bucket, k_bucket) -> {"ewma_ms", "last_ms", "n", "source"}
_variants: dict[tuple[str, int, int], dict] = {}
_meters: dict[tuple[str, str], float] = {}
_total_device_s = 0.0
_dispatches = {"drain": 0, "wall": 0}
# chip of the host (``placement.label``) -> [dispatches, device seconds]:
# what each chip's share of the attributed residency is where a
# collection's shards lie on several (runtime/placement.py)
_by_device: dict[str, list] = {}


def _bytes_bucket(nbytes: int) -> int:
    return int(nbytes).bit_length()


def observe_memcpy(seconds: float, nbytes: int) -> None:
    """Feed the memcpy estimator from a sampled ``transfer.d2h`` where
    device wait (``block_until_ready``) and the host copy were timed
    separately — the only place the split is directly measurable."""
    if seconds < 0 or nbytes < 0:
        return
    global _memcpy_global, _memcpy_samples
    bucket = _bytes_bucket(nbytes)
    with _lock:
        prev = _memcpy_ewma.get(bucket)
        _memcpy_ewma[bucket] = (seconds if prev is None
                                else _ALPHA * seconds + (1 - _ALPHA) * prev)
        _memcpy_global = (seconds if _memcpy_global is None
                          else _ALPHA * seconds
                          + (1 - _ALPHA) * _memcpy_global)
        _memcpy_samples += 1


def memcpy_estimate(nbytes: int) -> float:
    """Best-available memcpy seconds for a result of ``nbytes``: the
    pow2-bucket EWMA, else the global EWMA, else 0.0 (no sampled trace
    has run yet — the full drain window attributes to device, which is
    the pre-kernelscope behavior, never worse)."""
    with _lock:
        est = _memcpy_ewma.get(_bytes_bucket(nbytes))
        if est is None:
            est = _memcpy_global
    return 0.0 if est is None else est


def attribute(window_s: float, nbytes: int) -> tuple[float, float]:
    """Split a drain window (dispatch-submit .. transfer-complete) into
    ``(device_s, memcpy_s)``. The memcpy estimate is clamped into the
    window so both parts stay non-negative and sum to the window."""
    window_s = max(0.0, window_s)
    memcpy_s = min(max(0.0, memcpy_estimate(nbytes)), window_s)
    return window_s - memcpy_s, memcpy_s


def result_nbytes(value) -> int:
    """Total bytes of the numpy arrays in a transferred result pytree
    (tuple/list nesting); non-arrays contribute 0."""
    if value is None:
        return 0
    if isinstance(value, (tuple, list)):
        return sum(result_nbytes(v) for v in value)
    return int(getattr(value, "nbytes", 0) or 0)


def record_dispatch(kind: str, b_bucket: int, k_bucket: int,
                    device_s: float, source: str = "drain",
                    device: str = "") -> None:
    """One dispatch's attributed device residency for the (index-kind,
    batch-bucket, k-bucket) compiled variant. ``source`` is ``drain``
    (drain-thread stamps minus memcpy EWMA) or ``wall`` (sync/null-
    device fallback: dispatch wall window)."""
    global _total_device_s
    device_s = max(0.0, device_s)
    key = (str(kind), int(b_bucket), int(k_bucket))
    with _lock:
        v = _variants.get(key)
        ms = device_s * 1000.0
        if v is None:
            _variants[key] = {"ewma_ms": ms, "last_ms": ms, "n": 1,
                              "source": source}
        else:
            v["ewma_ms"] = _ALPHA * ms + (1 - _ALPHA) * v["ewma_ms"]
            v["last_ms"] = ms
            v["n"] += 1
            v["source"] = source
        _total_device_s += device_s
        _dispatches[source] = _dispatches.get(source, 0) + 1
        if device:
            on = _by_device.setdefault(device, [0, 0.0])
            on[0] += 1
            on[1] += device_s
    try:
        dispatch_device_seconds.labels(
            key[0], str(key[1]), str(key[2]), source).observe(device_s)
    except Exception:
        pass


def fold_dispatch(rec: dict, source: str, nbytes: int = 0
                  ) -> tuple[float, float]:
    """Residency of one dispatch, derived from its record's stamps (the
    batcher's flight record, ``rec["stamps"]``: ``exec`` = launch start,
    ``fetch1`` = drain complete, ``done`` = results routed) instead of
    from a call with numbers of its own: ``drain`` attributes
    ``exec..fetch1`` less the memcpy estimate for ``nbytes``, ``wall``
    the sync window ``exec..done``. Feeds the per-variant EWMA and
    histogram, writes ``device_ms`` / ``transfer_ms`` / ``t_source``
    back into the record (the waiters' phases read them there) and
    returns ``(device_s, transfer_s)``."""
    st = rec["stamps"]
    if source == "drain":
        device_s, transfer_s = attribute(st["fetch1"] - st["exec"], nbytes)
    else:
        device_s, transfer_s = max(0.0, st["done"] - st["exec"]), 0.0
    rec["device_ms"] = device_s * 1000.0
    rec["transfer_ms"] = transfer_s * 1000.0
    rec["t_source"] = source
    record_dispatch(rec.get("kind", ""), rec.get("b_pad") or 1,
                    rec.get("k") or 0, device_s, source,
                    rec.get("device", ""))
    return device_s, transfer_s


def apportion(device_s: float, weights: list[float]) -> list[float]:
    """Split one dispatch's residency across its coalesced requests,
    weighted (by rows scanned); degenerate weights split evenly. Shares
    sum exactly to ``device_s``."""
    n = len(weights)
    if n == 0:
        return []
    total = sum(w for w in weights if w > 0)
    if total <= 0:
        return [device_s / n] * n
    return [device_s * (max(w, 0.0) / total) for w in weights]


def meter(collection: str, tenant: str, device_s: float) -> None:
    """Accumulate attributed device seconds against a tenant — both the
    exported counter and an internal meter the accuracy check (sum of
    meters ~= total residency) reads back."""
    if device_s <= 0:
        return
    key = (str(collection or "-"), str(tenant or "-"))
    with _lock:
        _meters[key] = _meters.get(key, 0.0) + device_s
    try:
        device_seconds_total.labels(key[0], key[1]).inc(device_s)
    except Exception:
        pass


def total_device_seconds() -> float:
    with _lock:
        return _total_device_s


def meters_snapshot() -> dict[tuple[str, str], float]:
    with _lock:
        return dict(_meters)


# -- face 2: per-query EXPLAIN ------------------------------------------------

_explain_sink: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "kernelscope_explain_sink", default=None)


def explain_begin():
    """Install a fresh request-level explain sink on this thread;
    returns the reset token for :func:`explain_end`."""
    return _explain_sink.set({})


def explain_end(token) -> dict:
    plan = _explain_sink.get() or {}
    _explain_sink.reset(token)
    return plan


def explain_enabled() -> bool:
    return _explain_sink.get() is not None


@contextlib.contextmanager
def explain_scope(sink: dict):
    """Install ``sink`` as the ambient explain sink for the duration —
    how the batcher's worker thread captures engine notes for one
    dispatch without touching the request thread's sink."""
    token = _explain_sink.set(sink)
    try:
        yield sink
    finally:
        _explain_sink.reset(token)


def explain_note(section: str, **fields) -> None:
    """Record host-side plan facts under ``section`` in the ambient
    sink; a no-op (one contextvar read) when nobody asked to explain.
    Emission sites in ``engine/`` must pass plain host ints/strings —
    graftlint G5 pins that no device function feeds an argument."""
    sink = _explain_sink.get()
    if sink is None:
        return
    sec = sink.get(section)
    if sec is None:
        sink[section] = dict(fields)
    else:
        sec.update(fields)


def merge_plan(into: dict, plan: dict | None) -> None:
    """Fold a dispatch-level plan into a request-level sink, section by
    section (a multi-shard request keeps the last shard's engine
    sections; the batcher section is per-dispatch by construction)."""
    if not plan:
        return
    for section, fields in plan.items():
        if isinstance(fields, dict):
            cur = into.get(section)
            if isinstance(cur, dict):
                cur.update(fields)
            else:
                into[section] = dict(fields)
        else:
            into[section] = fields


def merge_into_request(plan: dict | None) -> None:
    sink = _explain_sink.get()
    if sink is None or not plan:
        return
    merge_plan(sink, plan)


# -- face 4: on-demand kernel profiles ----------------------------------------

#: friendly kernel name -> substrings matched (case-insensitive) against
#: trace event names. Mirrors the device programs the hot path compiles
#: (ops/pallas_kernels.py, ops/candidates.py, ops/topk.py, engine/ivf).
KERNEL_REGISTRY: tuple[tuple[str, tuple[str, ...]], ...] = (
    ("bq_scan_reduce", ("bq_scan", "bq_mxu", "bq_hamming")),
    ("pq4_scan_reduce", ("pq4_scan", "pq4_lut", "pq4_recon")),
    ("ivf_probe", ("ivf", "probe", "centroid")),
    ("gather_rescore_topk", ("gather_rescore", "shared_candidates",
                             "rescore")),
    ("merge_epoch_topk", ("merge_epoch", "merge_topk", "top_k", "topk")),
    ("distance_block", ("distance_block", "pairwise", "epoch_scan")),
)

_data_dir: str | None = None
_keep = 8
_capturer = None  # injectable trace capturer for tests (ms -> events)
_capture_seq = 0


def configure(data_dir: str | None = None, keep: int | None = None,
              capturer=None) -> None:
    """Server wiring: where captures persist (``<data_dir>/kernelscope``)
    and how many to keep. ``capturer`` overrides the jax.profiler-backed
    capture (tests inject synthetic trace events)."""
    global _data_dir, _keep, _capturer
    if data_dir is not None:
        _data_dir = str(data_dir)
    if keep is not None:
        _keep = max(1, int(keep))
    if capturer is not None:
        _capturer = capturer


def classify_kernel(event_name: str) -> str:
    low = str(event_name).lower()
    for friendly, pats in KERNEL_REGISTRY:
        if any(p in low for p in pats):
            return friendly
    return "other"


_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
_OPS_THREAD = "XLA Ops"
_GAPS_NAMED = 200  # only the longest gaps are matched against stages


def _merged(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def summarize_trace_events(events) -> dict:
    """Reduce chrome-trace events (``ph == "X"``: ``ts`` and ``dur`` in
    microseconds; ``ph == "M"`` process_name / thread_name metadata) the
    way the benchmark reduces the ``.xplane.pb`` (benchmarks/
    trace_reduce.py), so an operator reads from the server what the
    benchmark reads from the file:

    - DEVICE PLANES ONLY (processes named ``/device:TPU:n``; their
      ``XLA Ops`` thread where threads are named): host threads are in
      the same trace and are never device time. Per-kernel device-ms is
      ranked descending with the top raw event names kept for drill-down;
    - busy = the union of the device's op intervals (nested ops count
      once), mean over planes; idle share over the captured window;
    - the longest idle gaps, each named after the ``wtpu.<stage>``
      annotation (tailboard's dispatch stages, stamped by the batcher's
      worker and the drain thread) that covers most of it.

    A trace with no device plane (the CPU backend) has no device time:
    ``kernels`` is empty and ``device_planes`` says why."""
    procs: dict = {}
    threads: dict = {}
    spans = []
    for ev in events or ():
        if not isinstance(ev, dict):
            continue
        if ev.get("ph") == "M":
            label = (ev.get("args") or {}).get("name")
            if ev.get("name") == "process_name":
                procs[ev.get("pid")] = str(label)
            elif ev.get("name") == "thread_name":
                threads[(ev.get("pid"), ev.get("tid"))] = str(label)
        elif ev.get("ph") == "X":
            dur = float(ev.get("dur", 0) or 0)
            if dur > 0:
                spans.append((ev.get("pid"), ev.get("tid"),
                              str(ev.get("name", "")),
                              float(ev.get("ts", 0) or 0), dur))
    device_pids = sorted((pid for pid, name in procs.items()
                          if _DEVICE_PLANE.match(name)), key=str)
    by_kernel: dict[str, dict] = {}
    busy_us = []
    gaps: list[tuple[float, float]] = []
    first = min((ts for *_x, ts, _d in spans), default=0.0)
    last = max((ts + d for *_x, ts, d in spans), default=0.0)
    for pid in device_pids:
        named = any(p == pid and n == _OPS_THREAD
                    for (p, _t), n in threads.items())
        ops = [(name, ts, dur) for p, tid, name, ts, dur in spans
               if p == pid and (not named
                                or threads.get((p, tid)) == _OPS_THREAD)]
        merged = _merged([(ts, ts + dur) for _n, ts, dur in ops])
        busy_us.append(sum(e - st for st, e in merged))
        edges = [first] + [t for iv in merged for t in iv] + [last]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        for name, _ts, dur in ops:
            k = classify_kernel(name)
            agg = by_kernel.setdefault(
                k, {"kernel": k, "device_ms": 0.0, "events": 0,
                    "names": {}})
            agg["device_ms"] += dur / 1000.0
            agg["events"] += 1
            agg["names"][name] = agg["names"].get(name, 0.0) + dur / 1000.0
    kernels = []
    for agg in by_kernel.values():
        top = sorted(agg.pop("names").items(), key=lambda kv: -kv[1])[:5]
        agg["device_ms"] = round(agg["device_ms"], 3)
        agg["top_events"] = [{"name": n, "device_ms": round(ms, 3)}
                             for n, ms in top]
        kernels.append(agg)
    kernels.sort(key=lambda a: -a["device_ms"])
    out = {"kernels": kernels,
           "total_device_ms": round(sum(a["device_ms"] for a in kernels),
                                    3)}
    if not spans:
        return out
    out["device_planes"] = [procs[pid] for pid in device_pids]
    if not device_pids:
        return out
    stages = [(name, ts, ts + dur) for pid, _t, name, ts, dur in spans
              if pid not in device_pids and name.startswith("wtpu.")]
    by_stage: dict[str, float] = {}
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:_GAPS_NAMED]:
        cover: dict[str, float] = {}
        for name, s0, s1 in stages:
            overlap = min(s1, g1) - max(s0, g0)
            if overlap > 0:
                cover[name] = cover.get(name, 0.0) + overlap
        name = max(cover, key=cover.get) if cover else "no wtpu stage"
        by_stage[name] = by_stage.get(name, 0.0) + (g1 - g0) / 1000.0
    window_ms = (last - first) / 1000.0
    busy_ms = sum(busy_us) / 1000.0 / len(device_pids)
    out.update(
        window_ms=round(window_ms, 3), busy_ms=round(busy_ms, 3),
        device_idle_pct=round(100.0 * (1.0 - busy_ms / window_ms), 3)
        if window_ms > 0 else None,
        idle_gaps=[{"stage": n, "gap_ms": round(ms, 3)} for n, ms in
                   sorted(by_stage.items(), key=lambda kv: -kv[1])[:10]])
    return out


def _jax_capture(ms: int):
    """Programmatic jax.profiler capture: trace for ``ms`` into a
    tempdir, then parse whatever perfetto/chrome trace the runtime
    wrote. Returns a list of chrome-trace events (possibly empty on a
    backend that only writes xplane protos)."""
    import jax

    events: list = []
    with tempfile.TemporaryDirectory(prefix="kernelscope-") as td:
        try:
            jax.profiler.start_trace(td, create_perfetto_trace=True)
        except TypeError:  # older signature without the kwarg
            jax.profiler.start_trace(td)
        try:
            time.sleep(max(0, int(ms)) / 1000.0)
        finally:
            jax.profiler.stop_trace()
        for path in glob.glob(os.path.join(td, "**", "*.json.gz"),
                              recursive=True) + glob.glob(
                os.path.join(td, "**", "*.trace.json"), recursive=True):
            try:
                if path.endswith(".gz"):
                    with gzip.open(path, "rt") as f:
                        doc = json.load(f)
                else:
                    with open(path) as f:
                        doc = json.load(f)
            except Exception:
                continue
            evs = doc.get("traceEvents") if isinstance(doc, dict) else doc
            if isinstance(evs, list):
                events.extend(e for e in evs if isinstance(e, dict))
    return events


def _capture_dir() -> str | None:
    if not _data_dir:
        return None
    d = os.path.join(_data_dir, "kernelscope")
    os.makedirs(d, exist_ok=True)
    return d


def capture_profile(ms: int, capturer=None) -> dict:
    """One on-demand profile: capture ``ms`` of device activity, rank it
    by kernel, persist the capture (pruning past the configured K)."""
    global _capture_seq
    cap = capturer or _capturer or _jax_capture
    t_wall = time.time()
    events = cap(int(ms))
    summary = summarize_trace_events(events)
    with _lock:
        _capture_seq += 1
        seq = _capture_seq
    record = {"id": f"cap-{int(t_wall)}-{seq}", "ms": int(ms),
              "captured_at": round(t_wall, 3),
              "raw_events": len(events or ()), **summary}
    d = _capture_dir()
    if d is not None:
        try:
            path = os.path.join(d, record["id"] + ".json")
            with open(path, "w") as f:
                json.dump(record, f, indent=1, sort_keys=True)
            kept = sorted(glob.glob(os.path.join(d, "cap-*.json")),
                          key=os.path.getmtime)
            for stale in kept[:-_keep]:
                try:
                    os.remove(stale)
                except OSError:
                    pass
        except Exception:
            pass  # persistence is best-effort; the capture still returns
    return record


def list_captures() -> list[dict]:
    """Persisted captures, newest first (summary fields only — the
    paramless ``/v1/debug/profile`` response; never triggers a trace)."""
    d = _capture_dir()
    if d is None:
        return []
    out = []
    for path in sorted(glob.glob(os.path.join(d, "cap-*.json")),
                       key=os.path.getmtime, reverse=True):
        try:
            with open(path) as f:
                rec = json.load(f)
        except Exception:
            continue
        out.append({"id": rec.get("id"), "ms": rec.get("ms"),
                    "captured_at": rec.get("captured_at"),
                    "total_device_ms": rec.get("total_device_ms"),
                    "kernels": [k.get("kernel")
                                for k in rec.get("kernels", ())]})
    return out


def load_capture(capture_id: str) -> dict | None:
    d = _capture_dir()
    if d is None:
        return None
    path = os.path.join(d, os.path.basename(str(capture_id)))
    if not path.endswith(".json"):
        path += ".json"
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return None


# -- snapshot / reset ---------------------------------------------------------

def snapshot() -> dict:
    """Kernelscope state for ``/v1/debug/kernelscope``: per-variant
    residency EWMAs, the memcpy estimator, per-tenant meters, totals.
    The debug route's description also documents the ``?explain=true``
    flag this module serves."""
    with _lock:
        variants = {f"{k[0]}/b{k[1]}/k{k[2]}":
                    {kk: (round(vv, 4) if isinstance(vv, float) else vv)
                     for kk, vv in v.items()}
                    for k, v in sorted(_variants.items())}
        memcpy = {"samples": _memcpy_samples,
                  "global_us": (None if _memcpy_global is None
                                else round(_memcpy_global * 1e6, 2)),
                  "buckets": {str(b): round(s * 1e6, 2)
                              for b, s in sorted(_memcpy_ewma.items())}}
        meters = {f"{c}/{t}": round(s, 6)
                  for (c, t), s in sorted(_meters.items())}
        total = _total_device_s
        disp = dict(_dispatches)
        devices = {d: {"dispatches": n, "device_ms": round(sec * 1e3, 3)}
                   for d, (n, sec) in sorted(_by_device.items())}
    return {"variants": variants, "memcpy": memcpy, "meters": meters,
            "total_device_seconds": round(total, 6),
            "dispatches": disp, "devices": devices,
            "captures": len(list_captures())}


def reset_for_tests() -> None:
    """Drop all EWMA/meter/explain/capture state (conftest autouse —
    per-tenant meters leaking across tests would break the metering
    accuracy assertions)."""
    global _memcpy_global, _memcpy_samples, _total_device_s
    global _data_dir, _keep, _capturer, _capture_seq
    with _lock:
        _memcpy_ewma.clear()
        _memcpy_global = None
        _memcpy_samples = 0
        _variants.clear()
        _meters.clear()
        _total_device_s = 0.0
        _dispatches.clear()
        _dispatches.update({"drain": 0, "wall": 0})
        _by_device.clear()
        _capture_seq = 0
    _data_dir = None
    _keep = 8
    _capturer = None
