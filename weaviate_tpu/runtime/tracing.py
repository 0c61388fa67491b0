"""Request-scoped tracing with device-time attribution.

The reference exports ~70 Prometheus vecs but no per-request breakdown;
aggregate histograms can't say whether a slow hybrid query spent its time
in batching wait, host->device transfer, the Pallas scan, the ICI merge,
the cross-node scatter-gather, or the LSM object fetch. Worse, on an
async-dispatch runtime wall clock at the REST layer actively
MISATTRIBUTES device time: a dispatch returns as soon as the work is
enqueued, so the cost surfaces in whatever later blocks on the result
(usually ``np.asarray`` in an unrelated span).

Design:

- ``trace(name)`` opens a request root; ``span(name, **attrs)`` nests
  under whatever is current via a contextvar. Outside a trace ``span``
  is a no-op yielding a shared null span — instrumentation points cost
  one contextvar read on untraced paths.
- Cheap (host-clock) spans are ALWAYS recorded inside a trace. Device
  timing is the expensive part: ``device_sync(sp, *arrays)`` calls
  ``jax.block_until_ready`` ONLY when the trace is *sampled* (1-in-N
  per-process counter from TRACE_SAMPLE_RATE, or forced per request via
  ``?trace=true``). Unsampled requests take no device synchronization.
- Finished traces land in an in-memory ring buffer served by
  ``GET /v1/debug/traces``; roots slower than the slow-query threshold
  (QUERY_SLOW_LOG_ENABLED/QUERY_SLOW_LOG_THRESHOLD, reference:
  helpers/slow_queries.go) are logged with their span breakdown.
- Cross-node stitching: ``current_traceparent()`` emits a W3C-style
  ``00-{trace}-{span}-{flags}`` header the cluster transport forwards;
  the receiving node adopts it via ``remote_segment`` and EXPORTS its
  finished spans back in the RPC response, which the caller ``absorb``s
  into the live trace — one stitched trace per distributed query even
  across real process boundaries.
- Worker-thread propagation: ``contextvars`` do not flow into
  ``ThreadPoolExecutor`` workers; ``propagate(fn)`` captures the current
  (trace, span) and reinstates it around ``fn`` (used by the collection
  scatter-gather pool, the hybrid legs and the 2PC broadcast), and
  ``capture()``/``run_in`` do the same for the query batcher whose one
  dispatch serves many waiters.

Every finished span also feeds the ``weaviate_tpu_span_duration_seconds``
histogram (runtime/metrics.py) so traces and /metrics stay consistent.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import random
import threading
import time
from collections import deque

from weaviate_tpu.runtime import tailboard

logger = logging.getLogger(__name__)
slow_logger = logging.getLogger("weaviate_tpu.slow_query")

# active (trace, span) for this context; None outside a request
_current: contextvars.ContextVar = contextvars.ContextVar(
    "weaviate_tpu_trace", default=None)

# ids need uniqueness, not cryptography: uuid4 hits the urandom syscall
# (~100us on some kernels) THREE times per traced request — a PRNG
# seeded once from urandom is ~100x cheaper. getrandbits on a shared
# Random is a single C call, atomic under the GIL.
_rng = random.Random(int.from_bytes(os.urandom(16), "big"))


def _new_id(nbytes: int) -> str:
    return format(_rng.getrandbits(nbytes * 8), f"0{nbytes * 2}x")


class Span:
    """One timed operation. Mutable while open; serialized into its
    trace's span list (as a plain dict) when it finishes."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs",
                 "start_ms", "duration_ms", "_t0")

    def __init__(self, trace_id: str, parent_id: str | None, name: str,
                 attrs: dict, start_ms: float, t0: float | None = None):
        self.trace_id = trace_id
        self.span_id = _new_id(8)
        self.parent_id = parent_id
        self.name = name
        self.attrs = attrs
        self.start_ms = start_ms
        self.duration_ms = 0.0
        self._t0 = time.perf_counter() if t0 is None else t0

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_ms": round(self.start_ms, 3),
            "duration_ms": round(self.duration_ms, 3),
            "attrs": {k: _jsonable(v) for k, v in self.attrs.items()},
        }


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class _NullSpan:
    """Shared no-op span yielded outside any trace."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


class Trace:
    """Collects finished spans for one request (or one remote segment of
    a distributed request). Span appends are cross-thread safe."""

    MAX_SPANS = 512  # bound memory when an instrumented loop runs hot

    __slots__ = ("trace_id", "sampled", "spans", "dropped", "started_at",
                 "_t0", "remote", "_lock")

    def __init__(self, trace_id: str, sampled: bool, remote: bool = False):
        self.trace_id = trace_id
        self.sampled = sampled
        self.remote = remote
        self.spans: list[dict] = []
        self.dropped = 0
        self.started_at = time.time()
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    def now_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1000.0

    def add(self, span_dict: dict) -> None:
        with self._lock:
            if len(self.spans) >= self.MAX_SPANS:
                self.dropped += 1
                return
            self.spans.append(span_dict)

    def to_dict(self) -> dict:
        with self._lock:
            spans = sorted(self.spans, key=lambda s: s["start_ms"])
            dropped = self.dropped
        out = {
            "trace_id": self.trace_id,
            "sampled": self.sampled,
            "started_at": self.started_at,
            "spans": spans,
        }
        if dropped:
            out["dropped_spans"] = dropped
        return out


# -- sampling policy ----------------------------------------------------------

_sample_lock = threading.Lock()
_sample_counter = 0
_sample_every: int | None = None  # None = not yet read from the env


def _compute_sample_every() -> int:
    """0 = never, 1 = always, N = every Nth request."""
    raw = os.environ.get("TRACE_SAMPLE_RATE", "0").strip()
    try:
        rate = float(raw)
    except ValueError:
        logger.warning("TRACE_SAMPLE_RATE=%r is not a float; tracing "
                       "device sampling disabled", raw)
        return 0
    if rate <= 0.0:
        return 0
    if rate >= 1.0:
        return 1
    return max(1, round(1.0 / rate))


def should_sample() -> bool:
    """Per-process deterministic 1-in-N sampler (cheaper and steadier
    under load than per-request randomness)."""
    global _sample_counter, _sample_every
    if _sample_every is None:
        _sample_every = _compute_sample_every()
    if _sample_every == 0:
        return False
    with _sample_lock:
        _sample_counter += 1
        return _sample_counter % _sample_every == 0


# -- slow-query log -----------------------------------------------------------

_slow_threshold: float | None = None  # seconds; 0 = disabled; None = unread


def _compute_slow_threshold() -> float:
    from weaviate_tpu.config import _flag

    if not _flag(os.environ, "QUERY_SLOW_LOG_ENABLED"):
        return 0.0
    raw = os.environ.get("QUERY_SLOW_LOG_THRESHOLD", "2s").strip()
    try:
        if raw.endswith("ms"):
            return float(raw[:-2]) / 1000.0
        if raw.endswith("s"):
            return float(raw[:-1])
        return float(raw)
    except ValueError:
        return 2.0


def _get_slow_threshold() -> float:
    global _slow_threshold
    if _slow_threshold is None:
        _slow_threshold = _compute_slow_threshold()
    return _slow_threshold


def get_slow_threshold() -> float:
    """Public accessor for the lazily-cached slow-query threshold
    (seconds; 0 = disabled) — the one source for QUERY_SLOW_LOG_*."""
    return _get_slow_threshold()


def reset_policy_for_tests() -> None:
    """Re-read TRACE_SAMPLE_RATE / slow-log env on next use."""
    global _sample_every, _slow_threshold, _sample_counter
    _sample_every = None
    _slow_threshold = None
    _sample_counter = 0


# -- finished-trace ring buffer -----------------------------------------------

_RING_SIZE = 256
_ring: deque = deque(maxlen=_RING_SIZE)
_ring_lock = threading.Lock()


def recent_traces(limit: int = 50) -> list[dict]:
    """Newest-first finished traces for GET /v1/debug/traces."""
    with _ring_lock:
        items = list(_ring)
    return items[::-1][: max(0, limit)]


def clear_traces() -> None:
    with _ring_lock:
        _ring.clear()


# -- span plumbing ------------------------------------------------------------

def _observe_metric(name: str, duration_s: float) -> None:
    try:
        from weaviate_tpu.runtime.metrics import span_duration

        span_duration.labels(name).observe(duration_s)
    except Exception:  # metrics must never fail a request
        pass


def _finish(tr: Trace, sp: Span, t1: float | None = None) -> None:
    sp.duration_ms = ((time.perf_counter() if t1 is None else t1)
                      - sp._t0) * 1000.0
    tr.add(sp.to_dict())
    _observe_metric(sp.name, sp.duration_ms / 1000.0)


@contextlib.contextmanager
def trace(name: str, force: bool = False, **attrs):
    """Open a request root trace. Nested calls degrade to plain spans so
    layered entry points (REST -> gRPC handler reuse) compose."""
    if _current.get() is not None:
        with span(name, **attrs) as sp:
            yield sp
        return
    tr = Trace(_new_id(16), sampled=force or should_sample())
    root = Span(tr.trace_id, None, name, dict(attrs), 0.0)
    token = _current.set((tr, root))
    try:
        yield root
    finally:
        _finish(tr, root)
        _current.reset(token)
        _finalize(tr, root)


def _finalize(tr: Trace, root: Span) -> None:
    d = tr.to_dict()
    with _ring_lock:
        _ring.append(d)
    # tail-based retention (ISSUE 15): the keep/drop decision happens at
    # COMPLETION — inside an edge timeline the tailboard attaches this
    # trace and decides when the timeline closes (status known); outside
    # one it makes a standalone slow/fault decision
    try:
        tailboard.on_trace_complete(d, root.name, root.duration_ms)
    except Exception:  # observability must never fail the request
        pass
    threshold = _get_slow_threshold()
    took = root.duration_ms / 1000.0
    if threshold > 0 and took >= threshold:
        # structured slowlog (ISSUE 15 satellite): one machine-parseable
        # line AND a retrievable entry in the flight recorder's slowlog
        # ring (/v1/debug/flight) instead of free text only
        record = {
            "trace_id": tr.trace_id,
            "root": root.name,
            "duration_ms": round(root.duration_ms, 3),
            "threshold_ms": round(threshold * 1000.0, 3),
            "spans": [
                {"name": s["name"],
                 "duration_ms": round(s["duration_ms"], 3)}
                for s in sorted(tr.spans,
                                key=lambda s: -s["duration_ms"])[:8]],
        }
        import json as _json

        slow_logger.warning("slow_query %s", _json.dumps(record))
        try:
            tailboard.slow_root(record)
        except Exception:
            pass


class _SpanCM:
    """Class-based context manager (not @contextmanager: the generator
    machinery costs ~2x on the no-op path, and span() sits on query hot
    paths where it usually IS a no-op)."""

    __slots__ = ("name", "attrs", "_pair", "_token")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        cur = _current.get()
        if cur is None:
            self._pair = None
            return NULL_SPAN
        tr, parent = cur
        sp = Span(tr.trace_id, parent.span_id, self.name, self.attrs,
                  tr.now_ms())
        self._pair = (tr, sp)
        self._token = _current.set((tr, sp))
        return sp

    def __exit__(self, *exc):
        if self._pair is None:
            return False
        tr, sp = self._pair
        _finish(tr, sp)
        _current.reset(self._token)
        return False


class _StagedSpanCM(_SpanCM):
    """A span site that is also a stage of the always-on records
    (tailboard): ONE stamp at entry and one at exit, handed to both the
    trace (when there is one) and the request's or the dispatch's
    record. The stage is recorded with or without a trace."""

    __slots__ = ("stage", "_stage_token")

    def __init__(self, name: str, attrs: dict, stage: str):
        self.name = name
        self.attrs = attrs
        self.stage = stage

    def __enter__(self):
        t0 = time.perf_counter()
        self._stage_token = tailboard.open_stage(self.stage, t0)
        cur = _current.get()
        if cur is None:
            self._pair = None
            return NULL_SPAN
        tr, parent = cur
        sp = Span(tr.trace_id, parent.span_id, self.name, self.attrs,
                  (t0 - tr._t0) * 1000.0, t0)
        self._pair = (tr, sp)
        self._token = _current.set((tr, sp))
        return sp

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._stage_token is not None:
            tailboard.close_stage(self._stage_token, t1)
        if self._pair is None:
            return False
        tr, sp = self._pair
        _finish(tr, sp, t1)
        _current.reset(self._token)
        return False


def span(name: str, stage: str | None = None, **attrs) -> _SpanCM:
    """Nested span under the current trace; no-op outside one.
    ``stage`` names the tailboard stage this site also is (a request
    stage on a request thread, a dispatch stage on the batcher's worker
    or the drain thread): the site takes its stamps once for both."""
    if stage is None:
        return _SpanCM(name, attrs)
    return _StagedSpanCM(name, attrs, stage)


def record_span(name: str, start_s: float, end_s: float, **attrs) -> None:
    """Record an externally-timed span (perf_counter stamps) under the
    current span — how the query batcher's worker-side timings land in
    each waiter's trace without the worker holding their contexts."""
    cur = _current.get()
    if cur is None:
        return
    tr, parent = cur
    start_ms = (start_s - tr._t0) * 1000.0
    tr.add({
        "name": name,
        "span_id": _new_id(8),
        "parent_id": parent.span_id,
        "start_ms": round(start_ms, 3),
        "duration_ms": round((end_s - start_s) * 1000.0, 3),
        "attrs": {k: _jsonable(v) for k, v in attrs.items()},
    })
    _observe_metric(name, max(0.0, end_s - start_s))


def open_span(name: str, **attrs):
    """A span under the current one that is NOT made current, for work
    whose two halves are separate calls with other such spans open
    between them (a collection's fan-out over its shards): -> the
    context ``run_in`` runs either half under and ``close_span`` ends;
    None outside a trace."""
    cur = _current.get()
    if cur is None:
        return None
    tr, parent = cur
    return tr, Span(tr.trace_id, parent.span_id, name, attrs, tr.now_ms())


def close_span(ctx) -> None:
    if ctx is not None:
        _finish(*ctx)


def is_active() -> bool:
    return _current.get() is not None


def is_sampled() -> bool:
    cur = _current.get()
    return cur is not None and cur[0].sampled


def current_timing() -> list[dict]:
    """Spans recorded so far in the live trace (for per-query
    ``_debug.timing`` response breakdowns; the root is still open)."""
    cur = _current.get()
    if cur is None:
        return []
    tr, _ = cur
    with tr._lock:
        return sorted(list(tr.spans), key=lambda s: s["start_ms"])


def current_trace_id() -> str | None:
    cur = _current.get()
    return None if cur is None else cur[0].trace_id


# -- device-time attribution --------------------------------------------------

def device_sync(sp, *values) -> None:
    """Attribute device time to ``sp`` by blocking until ``values`` (jax
    arrays / pytrees) materialize — ONLY on sampled traces, so unsampled
    requests never add a device synchronization point."""
    cur = _current.get()
    if cur is None or not cur[0].sampled:
        return
    vals = [v for v in values if v is not None]
    if not vals:
        return
    try:
        import jax

        t0 = time.perf_counter()
        jax.block_until_ready(vals)
        sp.set(device_ms=round((time.perf_counter() - t0) * 1000.0, 3))
    except Exception:  # best-effort: a poisoned buffer raises at asarray
        pass


# -- device->host boundary ----------------------------------------------------

def d2h(*values):
    """THE sanctioned device->host transfer at the API boundary.

    Fetches ``values`` (jax arrays; ``None`` entries pass through) to
    numpy under a ``transfer.d2h`` span. Like ``device_sync``, the
    device wait is ATTRIBUTED (``device_ms``) only on sampled traces —
    there the device completion is timed separately (block_until_ready)
    from the host-side copy, so the span splits chip time from memcpy
    time. Unsampled/untraced callers still pay the transfer (that is the
    point of calling this), just without the extra sync for attribution.

    Hot-path modules (engine/, ops/, parallel/, the query batcher) must
    not fetch device values themselves (graftlint G1); they return
    device-resident handles (runtime/transfer.py) whose ``result()``
    funnels through here — one audited boundary instead of scattered
    ``np.asarray`` syncs.
    """
    import numpy as _np

    n_arrays = sum(1 for v in values if v is not None)
    with span("transfer.d2h", stage="d2h_wait", arrays=n_arrays) as sp:
        cur = _current.get()
        synced = False
        if cur is not None and cur[0].sampled and n_arrays:
            try:
                import jax

                t0 = time.perf_counter()
                jax.block_until_ready([v for v in values if v is not None])
                sp.set(device_ms=round(
                    (time.perf_counter() - t0) * 1000.0, 3))
                synced = True
            except Exception:  # a poisoned buffer raises at asarray below
                pass
        t_copy = time.perf_counter()
        out = tuple(None if v is None else _np.asarray(v) for v in values)
        if synced:
            # the device wait above already drained, so the asarray loop
            # here is (close to) pure memcpy — the only place the
            # device/copy split of a D2H window is directly measurable.
            # Kernelscope's EWMA turns these sampled splits into the
            # memcpy subtraction that makes the UNsampled drain-stamp
            # attribution honest.
            try:
                from weaviate_tpu.runtime import kernelscope

                kernelscope.observe_memcpy(
                    time.perf_counter() - t_copy,
                    sum(a.nbytes for a in out if a is not None))
            except Exception:
                pass
    return out


# -- cross-thread propagation -------------------------------------------------

def capture():
    """Opaque context handle for run_in (None outside a trace)."""
    return _current.get()


def run_in(ctx, fn, *args, **kwargs):
    """Run ``fn`` under a captured (trace, span) context."""
    if ctx is None:
        return fn(*args, **kwargs)
    token = _current.set(ctx)
    try:
        return fn(*args, **kwargs)
    finally:
        _current.reset(token)


def annotate(**attrs) -> None:
    """Attach attrs to the CURRENT span (no-op outside a trace) — how
    cross-cutting layers (faultline injections, degraded-read markers)
    tag whatever span happens to be active."""
    cur = _current.get()
    if cur is not None:
        cur[1].set(**attrs)


def propagate(fn):
    """Wrap ``fn`` to carry the CURRENT request context into worker
    threads (pool.map / Thread targets don't inherit contextvars).
    Carries the whole request quad: trace span, deadline budget, the
    degraded-marker sink, and the faultline node identity — a shard
    fan-out thread must spend the same budget, report into the same
    response, and issue its RPCs AS the same cluster node (the
    partition topology layer cuts links by (src, dst) node pair)."""
    from weaviate_tpu.runtime import degrade, faultline, retry

    ctx = _current.get()
    dl = retry.current_deadline()
    markers = degrade.current_markers()
    node = faultline.current_node()
    if ctx is None and dl is None and markers is None and node is None:
        return fn

    def wrapper(*args, **kwargs):
        tokens = (retry.set_deadline(dl), degrade.set_markers(markers))
        try:
            with faultline.node_scope(node):
                return run_in(ctx, fn, *args, **kwargs)
        finally:
            retry.reset_deadline(tokens[0])
            degrade.reset_markers(tokens[1])

    return wrapper


# -- traceparent propagation (cluster transport) ------------------------------

def current_traceparent() -> str | None:
    """W3C-shaped ``00-{trace_id}-{span_id}-{flags}`` naming the CURRENT
    span as the remote parent; flags bit 0 carries the sampled decision."""
    cur = _current.get()
    if cur is None:
        return None
    tr, sp = cur
    return f"00-{tr.trace_id}-{sp.span_id}-{'01' if tr.sampled else '00'}"


def parse_traceparent(header: str | None):
    """-> (trace_id, parent_span_id, sampled) or None on any malformation
    (an unparseable header must never fail the RPC carrying it)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    _ver, trace_id, parent_id, flags = parts
    if not trace_id or not parent_id:
        return None
    return trace_id, parent_id, flags == "01"


class RemoteSegment:
    """Handle yielded by ``remote_segment``: after the block exits,
    ``export()`` returns the segment's finished spans for the RPC
    response (None when there is nothing to ship)."""

    __slots__ = ("_trace",)

    def __init__(self, tr: Trace | None):
        self._trace = tr

    MAX_EXPORT = 64  # response-header budget

    def export(self) -> list[dict] | None:
        if self._trace is None:
            return None
        with self._trace._lock:
            spans = list(self._trace.spans)[: self.MAX_EXPORT]
        return spans or None


@contextlib.contextmanager
def remote_segment(traceparent: str | None, name: str = "rpc.server",
                   **attrs):
    """Adopt an incoming traceparent on the serving node: spans recorded
    inside chain to the caller's span id and are EXPORTED (via
    ``RemoteSegment``) instead of entering the local ring — the caller
    absorbs them, yielding one stitched trace."""
    parsed = parse_traceparent(traceparent)
    if parsed is None or _current.get() is not None:
        # no incoming context (or already tracing in-process): plain span
        with span(name, **attrs):
            yield RemoteSegment(None)
        return
    trace_id, parent_id, sampled = parsed
    tr = Trace(trace_id, sampled=sampled, remote=True)
    root = Span(trace_id, parent_id, name, dict(attrs), 0.0)
    token = _current.set((tr, root))
    try:
        yield RemoteSegment(tr)
    finally:
        _finish(tr, root)
        _current.reset(token)


def absorb(span_dicts: list[dict], base_ms: float = 0.0) -> None:
    """Merge spans exported by a remote segment into the live trace.
    ``base_ms``: the caller-side start of the RPC span, used to shift the
    remote segment's relative clock onto this trace's timeline."""
    cur = _current.get()
    if cur is None:
        return
    tr, _ = cur
    for d in span_dicts:
        if not isinstance(d, dict) or "name" not in d:
            continue
        shifted = dict(d)
        try:
            shifted["start_ms"] = round(float(d.get("start_ms", 0.0))
                                        + base_ms, 3)
        except (TypeError, ValueError):
            shifted["start_ms"] = base_ms
        attrs = shifted.get("attrs")
        if not isinstance(attrs, dict):  # corrupt spans must not fail the RPC
            attrs = {}
        shifted["attrs"] = {**attrs, "remote": True}
        tr.add(shifted)
