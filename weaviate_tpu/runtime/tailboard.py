"""Tailboard: the always-on latency-attribution plane (ISSUE 15).

PR 2's tracing answers "where did THIS request spend its time" — but only
for the 1-in-N requests the sampler picked, and the requests an operator
actually needs (the slow ones, the errored ones, the degraded ones) are
exactly the ones most likely to miss the ring. Aggregate histograms
(`weaviate_tpu_query_duration_seconds`) answer "how slow overall" but not
"which phase". This module closes both gaps with four pieces that share
one design rule: NOTHING here may add a device synchronization to an
unsampled request (graftlint G1 stays empty for engine/) and nothing may
cost more than a contextvar read plus a few ``perf_counter`` stamps on
the hot path.

1. **Timeline** — a per-request phase accumulator opened at the REST and
   gRPC edges on EVERY data-path request. Layers that already hold
   monotonic stamps (the query batcher's enqueue/dispatch/transfer
   stamps) fold them in via :func:`phase`; the edge closes the timeline
   and the phases land in
   ``weaviate_tpu_request_phase_seconds{operation,phase,collection,
   tenant}`` with ``phase`` one of ``queue_wait | device | transfer |
   host``. "device" here is the dispatch→drain-start WALL window of the
   batch the request rode in — attribution without ``block_until_ready``
   (real ``device_ms`` stays sampled-only, in tracing). Tenant and
   collection labels pass a top-K guard (:class:`LabelGuard`) so an
   adversarial tenant stream cannot grow the exposition unboundedly.

2. **Tail-based retention** — the keep/drop decision for a finished
   trace moves to request COMPLETION: slow (per-operation threshold),
   errored (5xx), deadline-exceeded, degraded, and fault-injected
   requests are ALWAYS kept in a separate tail ring, regardless of
   ``TRACE_SAMPLE_RATE``, served at ``GET /v1/debug/traces?tail=true``.
   Phase-histogram buckets carry OpenMetrics exemplars naming a retained
   trace id, so a dashboard bucket links to an actual trace.

3. **SLO engine** — declarative per-operation availability + latency
   objectives (``WEAVIATE_TPU_SLO`` JSON, or defaults), sliding-window
   good/bad counters, multi-window burn-rate gauges
   (``weaviate_tpu_slo_burn_rate{slo,window}``), ``GET /v1/debug/slo``.
   A fast-window burn past threshold flips the PR 8 component-health
   registry (``slo:<name>`` component) and snapshots the flight
   recorder to disk.

4. **Flight recorder** — a lock-free ring of recent dispatch records
   (query batcher + native plane: batch size, k bucket, queue depth,
   wait, epoch fanout, transfer-window occupancy) plus the structured
   slow-query log (the PR 2 free-text slow-root log, made retrievable),
   served at ``GET /v1/debug/flight`` and written to
   ``<data_dir>/flightrecorder/`` on incident — so an r05-style
   post-hoc investigation has the dispatch history that produced the
   regression. "Lock-free" is literal: writers claim a slot with
   ``next(itertools.count())`` (one atomic C call under the GIL) and
   write it; a torn read under wrap-around drops one record instead of
   ever blocking a dispatch loop.

5. **Stages** (ISSUE 25) — the same two records, stamped where the
   work happens. A *staged* request (gRPC Search) carries named stages
   from the wire to the reply (:data:`REQUEST_STAGES`: ``pool_wait`` ..
   ``send``, which sum to ``server_residency``, plus ``handler_cpu``)
   and folds them into
   ``weaviate_tpu_request_stage_seconds{operation,stage}`` from the same
   pending record the phases fold from. A dispatch record (the flight
   record of point 4) is the dispatch's stamp sheet: the batcher's
   worker and the transfer pipeline's drain thread each bind one *side*
   of it (:func:`bind_dispatch`) and time leaf-level, non-overlapping
   :data:`DISPATCH_STAGES` into it, each wrapped in a
   ``jax.profiler.TraceAnnotation("wtpu.<stage>")`` so a profiler
   session sees the program's own names on the device's clock. Sides
   fold into ``weaviate_tpu_dispatch_stage_seconds{kind,stage}`` off the
   dispatch loop. Annotations exist ONLY on those two threads: a trace
   reader that sums an event name's cover over threads would let an
   annotation on 32 request threads swallow every gap.

   A request that searches several local shards (a collection's
   fan-out, ISSUE 34) does so from its own thread. A plain one is ONE
   item on the collection's drain (ISSUE 42, db/drain.py) and is charged
   that drain's ``queue_wait``, ``device``, ``transfer`` and ``wake``;
   the drain's dispatch record is one a drain, so its ``launch``,
   ``d2h_wait`` and ``deliver`` stages cover one program a member
   shard. One with a filter or an allow list enqueues on every shard's
   batcher and waits for all of them; its stages stay additive by the
   **critical-path rule**: it is charged ONE ``queue_wait``, ``device``
   and ``transfer``, those of the shard whose answer arrived last. On
   both routes what passed between the first enqueue and the (last)
   delivery beyond those three is ``fanout_wait`` (the shards'
   snapshots of their queued vectors, on the drain), and the merge of
   the shards' answers is ``merge`` (:data:`FANOUT_STAGES`). The two
   are part of the sum for a fanned-out request and are observed for
   such a request only: a one-shard request makes the observations it
   always made.

6. **The interpreter's account** (ISSUE 39) — what one interpreter lock
   costs, measured and no longer inferred, under the same switch and
   with nothing new on a request's path. (a) At the scrape the kernel
   is asked what every thread of the process has used
   (:class:`ThreadAccount`: ``/proc/self/task/*/schedstat``), by a
   fixed set of roles (:data:`THREAD_ROLES`) found from a Python
   thread's name and a native thread's ``comm``:
   ``weaviate_tpu_thread_cpu_seconds_total{role}``,
   ``..._thread_runqueue_wait_seconds_total{role}``,
   ``weaviate_tpu_threads{role}``, ``weaviate_tpu_scrape_clock_seconds``,
   ``weaviate_tpu_thread_account_walk_seconds`` (what the walk took).
   (b) One daemon thread (``lock-probe``) sleeps 20 ms at a time and
   notes how late it is back: CPython hands the lock to any waiter, so
   its lateness has the distribution every thread pays to re-enter the
   interpreter after a blocking call
   (``weaviate_tpu_interpreter_wait_seconds``, folded at the scrape).
   (c) Every :data:`CPU_STAMP_EVERY`-th dispatch side of a thread (of
   one kind) takes ``time.thread_time()`` beside each wall stamp, and the fold gives
   ``weaviate_tpu_dispatch_stage_cpu_seconds{kind,stage}`` and
   ``<side>_cpu_ms`` in the flight record; a staged request's
   ``off_cpu`` (the handler's wall time less the waits it was meant to
   make, less its CPU) is computed at the fold from stamps it already
   took. ``WEAVIATE_TPU_TAILBOARD=0`` stops all three.

Env surface (all lazy-read, re-read after :func:`reset_for_tests`):

- ``WEAVIATE_TPU_TAILBOARD``        1 (default) / 0 — timeline on/off
- ``WEAVIATE_TPU_TAIL_SLOW_MS``     per-op slow threshold: a number, or
  JSON ``{"op-glob": ms, "*": ms}`` (default ``{"*": 250}``)
- ``WEAVIATE_TPU_TAIL_RING``        tail ring size (default 128)
- ``WEAVIATE_TPU_SLO``              JSON list of objectives
- ``WEAVIATE_TPU_SLO_WINDOWS``      csv seconds (default 60,300,3600)
- ``WEAVIATE_TPU_SLO_BURN_THRESHOLD`` incident burn rate (default 14.4,
  the classic fast-burn page threshold) evaluated on the shortest window
- ``WEAVIATE_TPU_FLIGHT_RING``      dispatch-record ring (default 256)
- ``WEAVIATE_TPU_TAILBOARD_MAX_TENANTS`` / ``_MAX_COLLECTIONS``
  top-K label guard (defaults 32 / 64)
"""

from __future__ import annotations

import contextvars
import ctypes
import fnmatch
import itertools
import json
import logging
import os
import threading
import time
from collections import deque

logger = logging.getLogger(__name__)

PHASES = ("queue_wait", "device", "transfer", "host")

#: additive stages of a staged request, in wire order; they sum to
#: ``server_residency`` up to the stamps' own gaps. ``queue_wait``,
#: ``device`` and ``transfer`` are the phases of the same name;
#: ``search_other`` is the collection call's wall time less the stages
#: stamped inside it (the shard/collection glue's own cost)
REQUEST_STAGES = ("pool_wait", "parse", "filter", "queue_wait", "device",
                  "transfer", "wake", "fetch", "search_other", "reply",
                  "send")
#: two stages more of a request that fanned out over several local
#: shards (:func:`fanout`), part of ITS sum and observed for it alone
FANOUT_STAGES = ("fanout_wait", "merge")
#: observed beside them, never part of the sum. ``off_cpu`` is computed
#: at the fold: the handler's wall time less queue_wait, device and
#: transfer (the waits it was meant to make; a fan-out's critical
#: path's) less ``handler_cpu``: its thread was meant to run and did not
REQUEST_EXTRAS = ("handler_cpu", "server_residency", "off_cpu")

#: leaf-level stages of one dispatch. ``idle`` (queue empty) and
#: ``slot_wait`` (transfer window full) are the worker's two waits;
#: ``finish`` is the drain thread's remainder (result routing glue)
DISPATCH_STAGES = ("idle", "slot_wait", "assemble", "mask_pack", "launch",
                   "d2h_wait", "rescore", "deliver", "finish")
#: the slot wait is the drain thread's busy time by construction: an
#: annotation over it would out-cover the drain's own stage names in
#: every device gap a trace reader attributes by summed cover
_UNANNOTATED = frozenset(("slot_wait",))
_DISPATCH_STAGE_SET = frozenset(DISPATCH_STAGES)

#: tail-retention reasons, in decision priority order
TAIL_REASONS = ("deadline", "error", "degraded", "fault", "slow")


def _mono() -> float:
    return time.monotonic()


_faultline_mod = None


def _faultline():
    """Cached faultline module ref — the per-request finalize consults
    ``armed()`` and a repeated ``from ... import`` is measurable there."""
    global _faultline_mod
    if _faultline_mod is None:
        from weaviate_tpu.runtime import faultline

        _faultline_mod = faultline
    return _faultline_mod


# -- env policy (lazy, cached) ------------------------------------------------

_policy_lock = threading.Lock()
_enabled_cached: bool | None = None
_forced: bool | None = None  # force_enabled() override (bench/tests)
_slow_map: dict[str, float] | None = None  # op-glob -> seconds
_data_dir: str | None = None


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() in ("1", "true", "on", "enabled")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def enabled() -> bool:
    """Is the always-on timeline armed? (``WEAVIATE_TPU_TAILBOARD``,
    overridable by :func:`force_enabled` for the overhead bench)."""
    global _enabled_cached
    if _forced is not None:
        return _forced
    if _enabled_cached is None:
        _enabled_cached = _env_flag("WEAVIATE_TPU_TAILBOARD", True)
    return _enabled_cached


def force_enabled(value: bool | None) -> None:
    """Bench/test hook: pin the timeline on/off (None = back to env)."""
    global _forced
    _forced = value


def _slow_thresholds() -> dict[str, float]:
    """op-glob -> seconds; ``"*"`` is the fallback."""
    global _slow_map
    if _slow_map is None:
        raw = os.environ.get("WEAVIATE_TPU_TAIL_SLOW_MS", "").strip()
        out: dict[str, float] = {}
        if raw:
            try:
                parsed = json.loads(raw)
                if isinstance(parsed, dict):
                    out = {str(k): float(v) / 1000.0
                           for k, v in parsed.items()}
                else:
                    out = {"*": float(parsed) / 1000.0}
            except (ValueError, TypeError):
                logger.warning("WEAVIATE_TPU_TAIL_SLOW_MS=%r unparseable; "
                               "using the 250ms default", raw)
        out.setdefault("*", 0.25)
        _slow_map = out
    return _slow_map


_slow_cache: dict[str, float] = {}


def slow_threshold_s(operation: str) -> float:
    """Per-operation tail slow threshold in seconds (0 disables).
    Resolved once per operation (bounded set: route classes + rpc
    names) — this sits on the per-request finalize path."""
    hit = _slow_cache.get(operation)
    if hit is not None:
        return hit
    table = _slow_thresholds()
    if operation in table:
        out = table[operation]
    else:
        out = table["*"]
        for pat, v in table.items():
            if pat != "*" and fnmatch.fnmatchcase(operation, pat):
                out = v
                break
    if len(_slow_cache) < 1024:
        _slow_cache[operation] = out
    return out


def set_data_dir(path: str | None) -> None:
    """Where incident flight-recorder snapshots land
    (``<path>/flightrecorder/``). Wired by Database/Server construction."""
    global _data_dir
    _data_dir = path


def configure(data_dir: str | None = None, enabled: bool | None = None,
              slos_json: str | None = None) -> None:
    """Server-start wiring: one call applies the ServerConfig surface.
    A malformed SLO config logs and falls back to the defaults — same
    lenient contract as the lazy env read; observability config must
    never stop the server from booting."""
    if data_dir is not None:
        set_data_dir(data_dir)
    if enabled is not None:
        # explicit config wins over env in BOTH directions, like every
        # other ServerConfig field (from_env feeds the env value here
        # anyway, so env-driven deployments are unchanged)
        force_enabled(bool(enabled))
    start_probe()
    if slos_json:
        try:
            slo_engine().configure_json(slos_json)
        except (ValueError, TypeError, KeyError) as e:
            logger.warning("WEAVIATE_TPU_SLO is unusable (%s); keeping "
                           "the default objectives", e)


# -- label-cardinality guard --------------------------------------------------


class LabelGuard:
    """Top-K distinct values for one label dimension; later arrivals
    collapse to the reserved ``other`` value so one adversarial stream
    of tenant/collection names cannot grow the exposition unboundedly.
    First-come-first-kept is deliberate: a steady production tenant set
    claims its slots at startup and keeps them."""

    __slots__ = ("cap", "_seen", "_lock")

    def __init__(self, cap: int):
        self.cap = max(1, int(cap))
        self._seen: set[str] = set()
        self._lock = threading.Lock()

    def clamp(self, value: str | None) -> str:
        if not value:
            return "-"
        value = str(value)
        if value in self._seen:  # benign race: set lookups are GIL-atomic
            return value
        with self._lock:
            if value in self._seen:
                return value
            if len(self._seen) < self.cap:
                self._seen.add(value)
                return value
        return "other"


_tenant_guard: LabelGuard | None = None
_collection_guard: LabelGuard | None = None

# (operation, phase, collection, tenant) -> histogram child. labels()
# takes the metric lock and rebuilds the key tuple on every call; this
# cache turns the per-request finalize into plain dict hits. Bounded:
# keys only form from guard-clamped values x the closed phase set.
_phase_child_cache: dict[tuple, object] = {}


def _phase_child(operation: str, phase_name: str, collection: str,
                 tenant: str):
    key = (operation, phase_name, collection, tenant)
    child = _phase_child_cache.get(key)
    if child is None:
        from weaviate_tpu.runtime.metrics import request_phase_seconds

        child = request_phase_seconds.labels(*key)
        if len(_phase_child_cache) < 8192:
            _phase_child_cache[key] = child
    return child


def _guards() -> tuple[LabelGuard, LabelGuard]:
    global _tenant_guard, _collection_guard
    if _tenant_guard is None:
        _tenant_guard = LabelGuard(
            _env_int("WEAVIATE_TPU_TAILBOARD_MAX_TENANTS", 32))
        _collection_guard = LabelGuard(
            _env_int("WEAVIATE_TPU_TAILBOARD_MAX_COLLECTIONS", 64))
    return _tenant_guard, _collection_guard


# -- the per-request timeline -------------------------------------------------


class Timeline:
    """Phase accumulator for one request. Mutated from the request
    thread only (the batcher folds its worker-side stamps in AFTER its
    waiter wakes, on the request thread), so no lock."""

    __slots__ = ("operation", "method", "collection", "tenant", "status",
                 "degraded", "fault", "phases", "trace", "_t0", "stages",
                 "_entry", "_mark", "_arrival", "_cpu0", "_return", "_term",
                 "_sides", "_record")

    def __init__(self, operation: str, method: str = "",
                 t_entry: float | None = None,
                 t_arrival: float | None = None):
        self.operation = operation
        self.method = method
        self.collection: str | None = None
        self.tenant: str | None = None
        self.status: int | None = None
        self.degraded = False
        self.fault = False
        self.phases: dict[str, float] = {}
        self.trace: dict | None = None  # attached by on_trace_complete
        # the phases' clock starts here, staged or not: ``host`` and the
        # request's duration read what they always read
        self._t0 = time.perf_counter()
        # staged requests only (an edge that passes its entry stamp):
        # stage name -> seconds, stamped on the request thread
        self.stages: dict[str, float] | None = None
        self._sides = None
        if t_entry is not None:
            self.stages = {}
            self._entry = self._mark = t_entry
            self._arrival = t_entry if t_arrival is None else t_arrival
            self._cpu0 = time.thread_time()
            self._return = self._term = 0.0
            self._record = None

    def add_phase(self, name: str, seconds: float) -> None:
        if seconds > 0.0:
            self.phases[name] = self.phases.get(name, 0.0) + seconds

    def add_stage(self, name: str, seconds: float) -> None:
        st = self.stages
        if st is not None and seconds > 0.0:
            st[name] = st.get(name, 0.0) + seconds

    def defer_to(self, context) -> None:
        """Hold the staged record until the RPC terminates
        (``context.add_callback``), so ``send`` and ``server_residency``
        are measured and not guessed. Whichever of handler return and
        termination comes second pushes the record (``next`` on a shared
        counter is atomic under the GIL). A context without the hook, or
        an RPC already over, leaves the timeline undeferred."""
        if self.stages is None:
            return
        self._sides = itertools.count()
        try:
            armed = context.add_callback(self._terminated)
        except Exception:  # noqa: BLE001 — tests stub the context
            armed = False
        if not armed:
            self._sides = None

    def _terminated(self) -> None:
        # runs on gRPC's serving thread: a stamp and (at most) one push
        self._term = time.perf_counter()
        if next(self._sides) == 1:
            _push_staged(self)


_timeline: contextvars.ContextVar[Timeline | None] = contextvars.ContextVar(
    "weaviate_tpu_timeline", default=None)


class _NullTimelineCM:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_TIMELINE_CM = _NullTimelineCM()


class _TimelineCM:
    __slots__ = ("_tl", "_token")

    def __init__(self, operation: str, method: str, t_entry, t_arrival):
        self._tl = Timeline(operation, method, t_entry, t_arrival)

    def __enter__(self):
        self._token = _timeline.set(self._tl)
        return self._tl

    def __exit__(self, exc_type, exc, tb):
        _timeline.reset(self._token)
        try:
            _finish_timeline(self._tl, exc)
        except Exception:  # observability must never fail a request
            logger.exception("tailboard timeline finalize failed")
        return False


def request(operation: str, method: str = "",
            t_entry: float | None = None, t_arrival: float | None = None):
    """Edge entry point: open the always-on timeline for one request.
    Cheap no-op when the tailboard is disabled. An edge that passes
    ``t_entry`` (its handler's first stamp) opens a STAGED timeline;
    ``t_arrival`` is the stamp taken before the handler's thread pool
    (``pool_wait`` runs from it to ``t_entry``)."""
    if not enabled():
        return _NULL_TIMELINE_CM
    return _TimelineCM(operation, method, t_entry, t_arrival)


def current() -> Timeline | None:
    return _timeline.get()


def phase(name: str, seconds: float) -> None:
    """Fold an externally-timed phase into the live timeline (no-op
    outside one). Called from layers that already hold the stamps —
    never adds a sync of its own."""
    tl = _timeline.get()
    if tl is not None:
        tl.add_phase(name, seconds)


def mark(stage: str) -> None:
    """Close a sequential stage of the live staged timeline at NOW: the
    time since the previous mark (or the handler's entry) is ``stage``'s.
    No-op outside a staged timeline."""
    tl = _timeline.get()
    if tl is not None and tl.stages is not None:
        now = time.perf_counter()
        st = tl.stages
        st[stage] = st.get(stage, 0.0) + now - tl._mark
        tl._mark = now


def request_stage(name: str, seconds: float) -> None:
    """Fold an externally-timed stage into the live staged timeline."""
    tl = _timeline.get()
    if tl is not None:
        tl.add_stage(name, seconds)


def fanout(wait_s: float, merge_s: float) -> None:
    """The two stages of a fan-out over several local shards, on the
    live staged timeline: ``wait_s`` is first enqueue -> last delivery
    less the charged shard's queue_wait, device and transfer; ``merge_s``
    the merge of the shards' answers. Kept at zero too: their presence
    is what marks the request as fanned out at the fold."""
    tl = _timeline.get()
    if tl is not None and tl.stages is not None:
        tl.stages["fanout_wait"] = max(0.0, wait_s)
        tl.stages["merge"] = max(0.0, merge_s)


def annotate(collection: str | None = None, tenant: str | None = None) -> None:
    """Attach collection/tenant identity to the live timeline (no-op
    outside one)."""
    tl = _timeline.get()
    if tl is None:
        return
    if collection:
        tl.collection = str(collection)
    if tenant:
        tl.tenant = str(tenant)


def complete(status: int, degraded: bool = False) -> None:
    """Edge exit point: record the response status before the timeline
    closes (the tail keep/drop decision and the SLO verdict need it)."""
    tl = _timeline.get()
    if tl is not None:
        tl.status = int(status)
        if degraded:
            tl.degraded = True


def note_fault() -> None:
    """Mark the live timeline fault-injected (called by faultline on the
    request thread; worker-thread injections are found by the armed-scan
    in the keep decision instead)."""
    tl = _timeline.get()
    if tl is not None:
        tl.fault = True


# -- tail ring ----------------------------------------------------------------

_tail_lock = threading.Lock()
_tail_ring: deque | None = None


def _tail() -> deque:
    global _tail_ring
    if _tail_ring is None:
        _tail_ring = deque(maxlen=_env_int("WEAVIATE_TPU_TAIL_RING", 128))
    return _tail_ring


def tail_traces(limit: int = 50) -> list[dict]:
    """Newest-first tail-retained entries for
    ``GET /v1/debug/traces?tail=true``."""
    with _tail_lock:
        items = list(_tail())
    return items[::-1][: max(0, limit)]


def clear_tail() -> None:
    """Drop the tail ring (tests; the tracing.clear_traces analog)."""
    with _tail_lock:
        _tail().clear()


def _keep_tail(entry: dict) -> None:
    with _tail_lock:
        _tail().append(entry)
    try:
        from weaviate_tpu.runtime.metrics import tail_retained_total

        tail_retained_total.labels(entry["reason"]).inc()
    except Exception:  # pragma: no cover
        pass


def _trace_has_fault(trace_dict: dict | None) -> bool:
    """Scan a finished trace for faultline annotations. Only called when
    a schedule is armed (chaos runs), never on the clean hot path."""
    if not trace_dict:
        return False
    for sp in trace_dict.get("spans", ()):
        if "fault_point" in (sp.get("attrs") or ()):
            return True
    return False


def _tail_reason(tl: Timeline, duration_s: float,
                 exc: BaseException | None) -> str | None:
    status = tl.status
    # fast path: a clean, fast 2xx/3xx/4xx with nothing flagged — the
    # overwhelming majority of requests — answers with two compares and
    # one cached threshold lookup
    if (exc is None and status is not None and status != 504
            and status < 500 and not tl.degraded and not tl.fault
            and duration_s < slow_threshold_s(tl.operation)
            and not _faultline().armed()):
        return None
    if status == 504:
        return "deadline"
    # a SET status wins over a propagating exception: the gRPC edge
    # calls complete(4xx) and then context.abort(), whose control-flow
    # exception unwinds through the timeline CM — a handled client
    # error must not count as a server error
    if (status >= 500) if status is not None else (exc is not None):
        return "error"
    if tl.degraded:
        return "degraded"
    if tl.fault:
        return "fault"
    threshold = slow_threshold_s(tl.operation)
    if 0 < threshold <= duration_s:
        return "slow"
    try:  # armed-only span scan (worker-thread injections)
        fl = _faultline()
        if fl.armed() and _trace_has_fault(tl.trace):
            return "fault"
    except Exception:  # pragma: no cover
        pass
    return None


# -- deferred fold ------------------------------------------------------------
#
# The request thread must pay for STAMPS, not aggregation: finishing a
# timeline pushes one small record into a lock-free ring and returns.
# Folding those records into the phase histograms and the SLO windows
# happens amortized (every _FOLD_EVERY-th request folds the backlog
# inline, ~30us per 512 requests) and at every read point (metrics
# scrape, /v1/debug/slo, /v1/debug/flight call flush()), so readers
# always see current state. Each record carries its own SLO bucket
# stamp — deferral shifts WHEN the math runs, never which window an
# observation lands in.

_FOLD_EVERY = 512
_PENDING_SIZE = 4096

_fold_lock = threading.Lock()


class _PendingRing:
    """Lock-free ring of finished records awaiting the fold. Writers
    claim a seq (``next`` on a count: atomic under the GIL) and store
    ``(seq, *fields)``; the fold, under ``_fold_lock``, takes every
    record past the last folded seq.

    Loss bound: a writer preempted between claiming its seq and storing
    the record can have that ONE record skipped (a fold that ran in
    between advances past its seq) — the same drop-one-rather-than-block
    tradeoff as :class:`FlightRing`, and it costs one observation, never
    a tail-ring entry (those are kept synchronously at completion)."""

    __slots__ = ("buf", "seq", "folded")

    def __init__(self):
        self.buf: list = [None] * _PENDING_SIZE
        self.seq = itertools.count(1)
        self.folded = 0  # last folded seq (guarded by _fold_lock)

    def push(self, *fields) -> int:
        seq = next(self.seq)
        self.buf[seq % _PENDING_SIZE] = (seq,) + fields
        return seq

    def take(self) -> list:
        """Caller holds ``_fold_lock``."""
        found = [r for r in list(self.buf)
                 if r is not None and r[0] > self.folded]
        if found:
            found.sort()
            self.folded = found[-1][0]
        return found


_pending = _PendingRing()           # finished requests
_pending_dispatch = _PendingRing()  # finished dispatch sides


def _finish_timeline(tl: Timeline, exc: BaseException | None) -> None:
    now = time.perf_counter()
    duration = now - tl._t0
    st = tl.stages
    if st is not None:
        # the handler's tail since the last mark (trailers, status) is
        # the reply's; the CPU this thread really had sits beside it
        st["reply"] = st.get("reply", 0.0) + (now - tl._mark)
        st["handler_cpu"] = time.thread_time() - tl._cpu0
        st["pool_wait"] = max(0.0, tl._entry - tl._arrival)
        tl._return = now
    reason = _tail_reason(tl, duration, exc)
    trace_id = (tl.trace or {}).get("trace_id")
    if reason is not None:  # rare path: keep the full trace NOW
        attributed = sum(tl.phases.values())
        phases_ms = {p: round(v * 1000.0, 3)
                     for p, v in tl.phases.items()}
        phases_ms["host"] = round(
            max(duration - attributed, 0.0) * 1000.0, 3)
        entry = {
            "reason": reason,
            "operation": tl.operation,
            "method": tl.method,
            "status": tl.status,
            "collection": tl.collection,
            "tenant": tl.tenant,
            "duration_ms": round(duration * 1000.0, 3),
            "phases_ms": phases_ms,
            "kept_at": time.time(),
            "trace": tl.trace,
        }
        if st is not None:  # what is known at handler return (no send)
            entry["stages_ms"] = {k: round(v * 1000.0, 3)
                                  for k, v in st.items()}
        _keep_tail(entry)
    # record fields: (operation, phases, duration_s, errored, collection,
    # tenant, trace_id, bucket, stages) — a tuple, not a dict: this build
    # runs on every request's thread
    # same status-wins rule as _tail_reason (abort control flow is not
    # an availability failure when the edge already mapped a 4xx)
    errored = ((tl.status >= 500) if tl.status is not None
               else (exc is not None))
    record = (tl.operation, tl.phases, duration, errored,
              tl.collection, tl.tenant,
              trace_id if reason is not None else None,
              int(_mono() // _BUCKET_S), st)
    if tl._sides is None:
        if st is not None:  # no termination hook: the handler's return
            st["send"] = 0.0  # is all the edge can see
            st["server_residency"] = now - tl._arrival
        seq = _pending.push(*record)
    else:
        tl._record = record
        seq = next(_finish_seq)
        if next(tl._sides) == 1:  # the RPC terminated before the return
            _push_staged(tl)
    if seq % _FOLD_EVERY == 0:
        flush()


# handler returns of deferred (staged) timelines: the amortized fold
# trigger stays on request threads, never on gRPC's serving thread
_finish_seq = itertools.count(1)


def _push_staged(tl: Timeline) -> None:
    """Second of (handler return, RPC termination): the staged record is
    whole. ``send`` is 0 where the RPC ended before the handler did
    (cancelled, deadline)."""
    st = tl.stages
    st["send"] = max(0.0, tl._term - tl._return)
    st["server_residency"] = max(tl._term, tl._return) - tl._arrival
    _pending.push(*tl._record)


_stage_child_cache: dict[tuple, object] = {}


def _stage_child(metric_name: str, *labels):
    """Cached histogram child of one of the two stage families (closed
    label sets: operations x stage names, index kinds x stage names)."""
    key = (metric_name,) + labels
    child = _stage_child_cache.get(key)
    if child is None:
        from weaviate_tpu.runtime import metrics

        child = getattr(metrics, metric_name).labels(*labels)
        if len(_stage_child_cache) < 4096:
            _stage_child_cache[key] = child
    return child


def _stage_values(phases: dict, stages: dict) -> tuple:
    """One staged request -> a value per REQUEST_STAGES + REQUEST_EXTRAS
    entry, in their order, zero included, so the stages' means sum to
    the residency's. ``search_other`` is the collection call's wall time
    less the stages and phases stamped inside it, a fan-out's two
    included (tests/test_tailboard.py holds every stage to its stated
    stamps)."""
    get, phase = stages.get, phases.get
    queue_wait, device, transfer = (phase("queue_wait", 0.0),
                                    phase("device", 0.0),
                                    phase("transfer", 0.0))
    filt, wake, fetch = get("filter", 0.0), get("wake", 0.0), \
        get("fetch", 0.0)
    other = max(0.0, get("search", 0.0) - (
        filt + wake + fetch + queue_wait + device + transfer
        + get("fanout_wait", 0.0) + get("merge", 0.0)))
    pool_wait, send, cpu, residency = (
        get("pool_wait", 0.0), get("send", 0.0), get("handler_cpu", 0.0),
        get("server_residency", 0.0))
    off_cpu = max(0.0, residency - pool_wait - send
                  - (queue_wait + device + transfer) - cpu)
    return (pool_wait, get("parse", 0.0), filt, queue_wait,
            device, transfer, wake, fetch, other, get("reply", 0.0),
            send, cpu, residency, off_cpu)


def _observe_columns(metric_name: str, columns: dict) -> None:
    """``{label tuple: [values]}`` into a stage family, one lock
    acquisition a series."""
    for labels, values in columns.items():
        _stage_child(metric_name, *labels).observe_many(values)


def flush() -> None:
    """Fold every pending completion record into the phase and stage
    histograms and the SLO windows, and every finished dispatch side
    into the dispatch-stage histogram. Called by read points and the
    amortized inline trigger; idempotent and cheap when there is no
    backlog. SLO window increments batch per (objective, bucket) so a
    512-record fold takes a handful of lock acquisitions, not
    thousands."""
    with _fold_lock:
        columns: dict[tuple, list] = {}
        cpu_columns: dict[tuple, list] = {}
        try:
            for (_seq, side) in _pending_dispatch.take():
                _fold_side(side, columns, cpu_columns)
            _observe_columns("dispatch_stage_seconds", columns)
            _observe_columns("dispatch_stage_cpu_seconds", cpu_columns)
        except Exception:  # pragma: no cover — never fail a reader
            pass
        found = _pending.take()
        if not found:
            return
        eng = slo_engine()
        horizon = eng.horizon_buckets()
        tenant_guard, coll_guard = _guards()
        slo_acc: dict[tuple, list[float]] = {}  # (obj, bucket) -> [g, b]
        staged: dict[str, list] = {}  # operation -> [[a value a stage]]
        fanned: dict[str, list] = {}  # the same, FANOUT_STAGES alone
        for (_seq, operation, phases, duration_s, errored, collection,
             tenant, trace_id, bucket, stages) in found:
            host = duration_s - sum(phases.values())
            collection = coll_guard.clamp(collection)
            tenant = tenant_guard.clamp(tenant)
            # exemplars only for tail-retained traces, so a bucket's
            # exemplar always RESOLVES through /v1/debug/traces?tail=true
            exemplar = {"trace_id": trace_id} if trace_id else None
            try:
                for p, v in phases.items():
                    _phase_child(operation, p, collection,
                                 tenant).observe(v, exemplar=exemplar)
                _phase_child(operation, "host", collection,
                             tenant).observe(max(host, 0.0),
                                             exemplar=exemplar)
                if stages is not None:
                    staged.setdefault(operation, []).append(
                        _stage_values(phases, stages))
                    if "fanout_wait" in stages:
                        fanned.setdefault(operation, []).append(
                            (stages["fanout_wait"], stages["merge"]))
            except Exception:  # pragma: no cover — never fail a reader
                pass
            for o in eng.objectives_for(operation):
                verdict = o.verdict(500 if errored else 200,
                                    duration_s, None)
                if verdict is not None:
                    cell = slo_acc.setdefault((o, bucket), [0.0, 0.0])
                    cell[0 if verdict else 1] += 1.0
        try:
            for names, by_op in ((REQUEST_STAGES + REQUEST_EXTRAS, staged),
                                 (FANOUT_STAGES, fanned)):
                for operation, rows in by_op.items():
                    _observe_columns("request_stage_seconds", {
                        (operation, s): col
                        for s, col in zip(names, zip(*rows))})
        except Exception:  # pragma: no cover — never fail a reader
            pass
        for (o, bucket), (good, bad) in slo_acc.items():
            o.record_bulk(bucket, good, bad, horizon)
    eng.maybe_sweep()


def on_trace_complete(trace_dict: dict, root_name: str,
                      duration_ms: float) -> None:
    """tracing._finalize hook, called for EVERY finished root trace.

    Inside a timeline (edge requests): just attach the trace — the
    timeline exit, which also knows the response status, makes the
    keep/drop decision. Outside one (direct ``tracing.trace`` users,
    worker roots): a standalone slow/fault decision so those traces can
    still be tail-kept."""
    tl = _timeline.get()
    if tl is not None:
        tl.trace = trace_dict
        return
    if not enabled():
        return
    reason = None
    duration_s = duration_ms / 1000.0
    threshold = slow_threshold_s(root_name)
    if 0 < threshold <= duration_s:
        reason = "slow"
    else:
        try:
            if _faultline().armed() and _trace_has_fault(trace_dict):
                reason = "fault"
        except Exception:  # pragma: no cover
            pass
    if reason is not None:
        _keep_tail({
            "reason": reason, "operation": root_name, "method": "",
            "status": None, "collection": None, "tenant": None,
            "duration_ms": round(duration_ms, 3),
            "phases_ms": {}, "kept_at": time.time(),
            "trace": trace_dict,
        })


# -- SLO engine ---------------------------------------------------------------

_BUCKET_S = 5.0  # sliding-window granularity

_DEFAULT_SLOS = (
    {"slo": "availability", "operation": "*", "kind": "availability",
     "objective": 0.999},
    {"slo": "latency", "operation": "*", "kind": "latency",
     "objective": 0.99, "threshold_ms": 500.0},
)


class _Objective:
    __slots__ = ("name", "operation", "kind", "objective", "threshold_s",
                 "counts", "lock")

    def __init__(self, spec: dict):
        self.name = str(spec["slo"])
        self.operation = str(spec.get("operation", "*"))
        self.kind = str(spec.get("kind", "availability"))
        if self.kind not in ("availability", "latency"):
            raise ValueError(f"SLO {self.name!r}: unknown kind "
                             f"{self.kind!r}")
        self.objective = float(spec.get("objective", 0.999))
        if not 0.0 < self.objective < 1.0:
            raise ValueError(f"SLO {self.name!r}: objective must be in "
                             f"(0, 1), got {self.objective}")
        self.threshold_s = float(spec.get("threshold_ms", 500.0)) / 1000.0
        # bucket index -> [good, bad]; pruned past the longest window
        self.counts: dict[int, list[float]] = {}
        self.lock = threading.Lock()

    def matches(self, operation: str) -> bool:
        return fnmatch.fnmatchcase(operation, self.operation)

    def verdict(self, status: int | None, duration_s: float,
                exc: BaseException | None) -> bool | None:
        """True = good, False = bad, None = excluded from this SLO."""
        errored = exc is not None or (status is not None and status >= 500)
        if self.kind == "availability":
            return not errored
        if errored:  # latency SLOs judge only requests that succeeded
            return None
        return duration_s <= self.threshold_s

    def record(self, bucket: int, good: bool, horizon: int) -> None:
        self.record_bulk(bucket, 1.0 if good else 0.0,
                         0.0 if good else 1.0, horizon)

    def record_bulk(self, bucket: int, good: float, bad: float,
                    horizon: int) -> None:
        with self.lock:
            cell = self.counts.get(bucket)
            if cell is None:
                cell = self.counts[bucket] = [0.0, 0.0]
                # prune on new-bucket creation: O(1) amortized
                dead = [b for b in self.counts if b < bucket - horizon]
                for b in dead:
                    del self.counts[b]
            cell[0] += good
            cell[1] += bad

    def window_counts(self, now_bucket: int, window_s: float) -> tuple:
        lo = now_bucket - int(window_s // _BUCKET_S)
        good = bad = 0.0
        with self.lock:
            for b, (g, x) in self.counts.items():
                if lo < b <= now_bucket:
                    good += g
                    bad += x
        return good, bad

    def burn_rate(self, now_bucket: int, window_s: float) -> float:
        """bad-fraction over the window divided by the error budget
        (1 - objective): 1.0 = burning exactly the budget, >>1 = paging
        territory. 0 when the window saw no traffic."""
        good, bad = self.window_counts(now_bucket, window_s)
        total = good + bad
        if total <= 0:
            return 0.0
        return (bad / total) / (1.0 - self.objective)


class SloEngine:
    """All objectives + the incident loop. One process-wide instance."""

    def __init__(self):
        self._lock = threading.Lock()
        self._objectives: list[_Objective] | None = None
        self._windows: tuple[float, ...] | None = None
        self._match_cache: dict[str, tuple[_Objective, ...]] = {}
        self._last_check = 0.0
        self._burning: set[str] = set()

    # -- configuration --------------------------------------------------------

    def _load(self) -> list[_Objective]:
        with self._lock:
            if self._objectives is None:
                raw = os.environ.get("WEAVIATE_TPU_SLO", "").strip()
                specs = _DEFAULT_SLOS
                if raw:
                    try:
                        parsed = json.loads(raw)
                        if isinstance(parsed, list) and parsed:
                            specs = parsed
                        else:
                            logger.warning("WEAVIATE_TPU_SLO must be a "
                                           "non-empty JSON list; using "
                                           "defaults")
                    except ValueError:
                        logger.warning("WEAVIATE_TPU_SLO is not valid "
                                       "JSON; using defaults")
                self._objectives = [_Objective(dict(s)) for s in specs]
                self._match_cache.clear()
            return self._objectives

    def configure_json(self, raw: str) -> None:
        """Explicit (re)configuration — ServerConfig wiring and tests."""
        specs = json.loads(raw)
        with self._lock:
            self._objectives = [_Objective(dict(s)) for s in specs]
            self._match_cache.clear()
            self._burning.clear()

    def windows(self) -> tuple[float, ...]:
        with self._lock:
            if self._windows is None:
                raw = os.environ.get("WEAVIATE_TPU_SLO_WINDOWS",
                                     "60,300,3600")
                try:
                    ws = tuple(sorted(float(w) for w in raw.split(",")
                                      if w.strip()))
                except ValueError:
                    ws = (60.0, 300.0, 3600.0)
                self._windows = ws or (60.0, 300.0, 3600.0)
            return self._windows

    def burn_threshold(self) -> float:
        return _env_float("WEAVIATE_TPU_SLO_BURN_THRESHOLD", 14.4)

    def horizon_buckets(self) -> int:
        return int(max(self.windows()) // _BUCKET_S) + 1

    def objectives_for(self, operation: str) -> tuple[_Objective, ...]:
        hit = self._match_cache.get(operation)
        if hit is None:
            objs = self._load()
            hit = tuple(o for o in objs if o.matches(operation))
            # the op set is bounded (route classes + rpc names), so the
            # cache is too
            if len(self._match_cache) < 256:
                self._match_cache[operation] = hit
        return hit

    def maybe_sweep(self) -> None:
        """Rate-limited incident sweep — at most once a second, however
        often the fold runs."""
        now = _mono()
        with self._lock:
            due = now - self._last_check >= 1.0
            if due:
                self._last_check = now
        if due:
            try:
                self.check_incidents(now=now)
            except Exception:  # pragma: no cover
                logger.exception("SLO incident sweep failed")

    # -- evaluation -----------------------------------------------------------

    def check_incidents(self, now: float | None = None) -> None:
        """Fast-window burn over threshold => flip the component-health
        registry (``slo:<name>``) and snapshot the flight recorder;
        recovery flips it back."""
        from weaviate_tpu.runtime import degrade

        now = _mono() if now is None else now
        bucket = int(now // _BUCKET_S)
        fast = self.windows()[0]
        threshold = self.burn_threshold()
        for o in self._load():
            burn = o.burn_rate(bucket, fast)
            component = f"slo:{o.name}"
            if burn >= threshold:
                if o.name not in self._burning:
                    self._burning.add(o.name)
                    reason = (f"burn rate {burn:.1f}x over the "
                              f"{int(fast)}s window (threshold "
                              f"{threshold:.1f}x, objective "
                              f"{o.objective})")
                    degrade.mark_unhealthy(component, reason)
                    snapshot_to_disk(f"slo:{o.name}")
            elif o.name in self._burning:
                self._burning.discard(o.name)
                degrade.mark_healthy(component)

    def refresh(self, now: float | None = None) -> None:
        """Republish the burn-rate gauges + run the incident sweep —
        called at scrape time and from /v1/debug/slo."""
        now = _mono() if now is None else now
        bucket = int(now // _BUCKET_S)
        try:
            from weaviate_tpu.runtime.metrics import slo_burn_rate

            for o in self._load():
                for w in self.windows():
                    slo_burn_rate.labels(o.name, f"{int(w)}s").set(
                        o.burn_rate(bucket, w))
        except Exception:  # pragma: no cover
            pass
        self.check_incidents(now=now)

    def snapshot(self, now: float | None = None) -> dict:
        """The /v1/debug/slo payload."""
        now = _mono() if now is None else now
        bucket = int(now // _BUCKET_S)
        out = []
        for o in self._load():
            windows = {}
            for w in self.windows():
                good, bad = o.window_counts(bucket, w)
                windows[f"{int(w)}s"] = {
                    "good": good, "bad": bad,
                    "burnRate": round(o.burn_rate(bucket, w), 4),
                }
            spec = {
                "slo": o.name, "operation": o.operation, "kind": o.kind,
                "objective": o.objective, "windows": windows,
                "burning": o.name in self._burning,
            }
            if o.kind == "latency":
                spec["thresholdMs"] = round(o.threshold_s * 1000.0, 3)
            out.append(spec)
        return {"slos": out,
                "burnThreshold": self.burn_threshold(),
                "fastWindowSeconds": self.windows()[0]}


_slo_engine: SloEngine | None = None
_slo_lock = threading.Lock()


def slo_engine() -> SloEngine:
    global _slo_engine
    if _slo_engine is None:
        with _slo_lock:
            if _slo_engine is None:
                _slo_engine = SloEngine()
    return _slo_engine


# -- flight recorder ----------------------------------------------------------


class FlightRing:
    """Fixed-size lock-free ring. Writers claim a slot via
    ``next(itertools.count())`` (atomic under the GIL) and store; readers
    copy the buffer. Under wrap-around a reader can see a record from
    either generation for a given slot — acceptable for a flight
    recorder, and the price of never blocking a dispatch loop."""

    __slots__ = ("_size", "_buf", "_seq")

    def __init__(self, size: int):
        self._size = max(8, int(size))
        self._buf: list[dict | None] = [None] * self._size
        self._seq = itertools.count()

    def append(self, record: dict) -> None:
        i = next(self._seq)
        record["seq"] = i
        self._buf[i % self._size] = record

    def snapshot(self) -> list[dict]:
        """Oldest-first records (sorted by claim sequence)."""
        items = [r for r in list(self._buf) if r is not None]
        items.sort(key=lambda r: r.get("seq", 0))
        return items


_flight_ring: FlightRing | None = None
_slowlog_ring: FlightRing | None = None


def _flight() -> FlightRing:
    global _flight_ring
    if _flight_ring is None:
        _flight_ring = FlightRing(_env_int("WEAVIATE_TPU_FLIGHT_RING", 256))
    return _flight_ring


def _slowlog() -> FlightRing:
    global _slowlog_ring
    if _slowlog_ring is None:
        _slowlog_ring = FlightRing(64)
    return _slowlog_ring


def new_dispatch(plane: str, kind: str = "", device: str = "") -> dict:
    """A dispatch record not yet in the ring: the worker opens one
    before it waits for work, so the wait that precedes a dispatch is
    stamped into that dispatch's sheet. :func:`record_dispatch` files
    it. ``device``: the chip the dispatch's programs run on
    (``placement.label``; "" on a mesh or outside any shard), which the
    record of every ``wtpu.*`` stage of that dispatch then carries."""
    return {"plane": plane, "kind": kind, "device": device}


def record_dispatch(plane: str, rec: dict | None = None, **fields) -> dict:
    """One dispatch record from the query batcher or the native plane.
    Lock-free, allocation-light — safe on the dispatch hot loop. Returns
    the live record: it is the dispatch's stamp sheet, which both of its
    threads stamp into (:func:`bind_dispatch`) and every consumer reads
    (the waiters' phases and spans, kernelscope's residency, the stage
    fold), and which late-arriving fields are patched into (the batcher
    learns its epoch fanout only after the async launch). ``rec``: a
    record opened earlier by :func:`new_dispatch`."""
    if rec is None:
        rec = {"plane": plane}
    rec["t"] = time.time()
    rec.update(fields)
    _flight().append(rec)
    return rec


# -- dispatch sides: leaf-level stages on the profiler's clock ----------------

_annotation_cls = None  # jax.profiler.TraceAnnotation, or False (no jax)
_ANNOTATION_NAMES = {s: "wtpu." + s for s in DISPATCH_STAGES
                     if s not in _UNANNOTATED}


def _resolve_annotation_cls():
    global _annotation_cls
    try:
        from jax.profiler import TraceAnnotation as cls
    except Exception:  # noqa: BLE001 — observability never requires jax
        cls = False
    _annotation_cls = cls
    return cls


_PAUSED = object()  # a side's mark while a nested side runs


class _Side:
    """One thread's side of one dispatch record: RAW STAMPS ONLY. The
    dispatch threads sit between a notify and a block with the
    interpreter lock in hand, so they compute nothing here: ``marks`` is
    the flat list ``[t0, stage0, t1, stage1, ..., t_end]`` (``stage_i``
    ran from ``t_i`` to ``t_i+1``; None: no stage), and the fold
    (:func:`_fold_side`, off the dispatch loop) turns it into per-stage
    seconds. One stage runs at a time (:meth:`mark` names the one that
    runs from now on and hands back the one that ran until now, which a
    nested stage restores when it ends), so a side's stages never
    overlap and cover its wall time.

    While a profiler session runs, and only then
    (``TraceAnnotation.is_enabled()``: one flag read), the running stage
    holds a ``TraceAnnotation("wtpu.<stage>")``, so its event lands in
    the host plane of the same ``.xplane.pb`` as the device's ops. A
    stage already running when the session starts is not in the trace,
    as a TraceMe built before the session would not be either."""

    __slots__ = ("rec", "name", "marks", "cpus", "cur", "ann", "outer")

    def __init__(self, rec: dict, name: str, now: float, stage, outer,
                 cpu: bool = True):
        self.rec = rec
        self.name = name
        self.marks: list = []
        # this thread's CPU clock, one stamp beside each wall stamp
        # (None on the sides in between, and with the tailboard off):
        # stage by stage, wall less CPU is what the thread spent not
        # running
        self.cpus: list | None = [] if cpu else None
        self.cur = self.ann = None
        self.outer = outer  # (the side this one paused, its stage) or None
        self.mark(stage, now)

    def mark(self, stage, now: float | None = None):
        """``stage`` runs from ``now`` on; -> the stage it takes over
        from."""
        cpus = self.cpus
        if cpus is not None:
            cpus.append(time.thread_time())
        if now is None:
            now = time.perf_counter()
        ann = self.ann
        if ann is not None:
            ann.__exit__(None, None, None)
            self.ann = None
        prev, self.cur = self.cur, stage
        self.marks += (now, stage)
        cls = _annotation_cls
        if cls and cls.is_enabled():
            label = _ANNOTATION_NAMES.get(stage)
            if label is not None:
                self.ann = ann = cls(label)
                ann.__enter__()
        return prev


_bound = threading.local()

#: a thread stamps its CPU clock on one dispatch side in so many. The
#: clock is a system call: 0.3 us a read on a plain kernel, 6 us on the
#: chip's hosts (a sandbox kernel), where ~12 reads on every dispatch
#: cost ``sift-flat-l2.c1`` 2 % of its qps (PERF.md section 6, PR 39).
#: The CPU histogram's own count says how many sides were stamped, so a
#: reader scales a series' sum by the wall series' count over it
CPU_STAMP_EVERY = 4


def bind_dispatch(rec: dict, side: str, stage: str | None = None,
                  now: float | None = None) -> _Side:
    """Make ``rec`` this thread's dispatch record until
    :func:`unbind_dispatch`: ``side`` is ``worker`` or ``drain``,
    ``stage`` the side's own work, which runs wherever no other stage is
    marked (``assemble``, ``finish``). Called by the batcher's worker and
    the transfer pipeline's drain thread only. A bind inside a bind (a
    solo dispatch inside a drain) pauses the outer side and resumes it
    at the unbind. -> the side, for the thread's own
    ``side.mark(stage)`` calls."""
    if _annotation_cls is None:
        _resolve_annotation_cls()
    if now is None:
        now = time.perf_counter()
    outer = getattr(_bound, "side", None)
    if outer is not None:
        outer = (outer, outer.mark(_PAUSED, now))
    # one count a label of the stage family (the dispatch's kind; a
    # nested side is a solo dispatch's): one count over sides that
    # alternate would stamp one sort only, and a reader scales a
    # series' CPU sum by that series' own counts
    counts = getattr(_bound, "sides", None)
    if counts is None:
        counts = _bound.sides = {}
    key = (rec.get("kind") or rec.get("plane"), outer is not None)
    n = counts.get(key, 0)
    counts[key] = n + 1
    new = _bound.side = _Side(rec, side, now, stage, outer,
                              n % CPU_STAMP_EVERY == 0 and enabled())
    return new


def unbind_dispatch(keep: bool = True) -> None:
    """Close this thread's side: one last stamp, and the side goes to
    the fold ring as it is. ``keep=False`` drops it (a worker that woke
    only to stop)."""
    side = getattr(_bound, "side", None)
    if side is None:
        return
    if side.cpus is not None:
        side.cpus.append(time.thread_time())
    now = time.perf_counter()
    if side.ann is not None:
        side.ann.__exit__(None, None, None)
        side.ann = None
    side.marks.append(now)
    if side.outer is None:
        _bound.side = None
    else:
        _bound.side, stage = side.outer
        _bound.side.mark(stage, now)
    if keep:
        _pending_dispatch.push(side)


def _fold_side(side: _Side, columns: dict, cpu_columns: dict) -> None:
    """One closed side -> its stages' seconds (into ``columns`` for the
    stage family and, in ms, into the record as ``<side>_ms``) and,
    where the side took CPU stamps, the CPU seconds its thread had
    inside each (``cpu_columns``, ``<side>_cpu_ms``). A stage's CPU is
    NOT cut to its wall time: where the kernel moves a thread's CPU
    clock in ticks (the chip's hosts: PERF.md section 6, PR 39) one
    stage reads 0 or a whole tick, and only the uncut readings add up
    to what the thread used. The
    worker's side also gives ``worker_wall``, its wall time less what a
    nested side took: the stages cover it, and ``dispatch_busy_pct``
    divides by it. Runs under ``_fold_lock``, never on a dispatch
    loop."""
    marks, cpus = side.marks, side.cpus
    stages: dict[str, float] = {}
    cpu: dict[str, float] = {}
    paused = paused_cpu = 0.0
    for n, i in enumerate(range(1, len(marks) - 1, 2)):
        stage, seconds = marks[i], marks[i + 1] - marks[i - 1]
        had = max(cpus[n + 1] - cpus[n], 0.0) if cpus else 0.0
        if stage is _PAUSED:
            paused += seconds
            paused_cpu += had
        elif stage is not None:
            stages[stage] = stages.get(stage, 0.0) + seconds
            cpu[stage] = cpu.get(stage, 0.0) + had
    if side.name == "worker":
        stages["worker_wall"] = marks[-1] - marks[0] - paused
        if cpus:
            cpu["worker_wall"] = max(cpus[-1] - cpus[0] - paused_cpu, 0.0)
    rec = side.rec
    rec[side.name + "_ms"] = {k: v * 1000.0 for k, v in stages.items()}
    kind = rec.get("kind") or rec["plane"]
    if rec.get("path") == "solo":
        kind += ".solo"
    for name, v in stages.items():
        columns.setdefault((kind, name), []).append(v)
    if cpus:
        rec[side.name + "_cpu_ms"] = {k: cpu[k] * 1000.0 for k in stages}
        for name in stages:
            cpu_columns.setdefault((kind, name), []).append(cpu[name])


class dispatch_stage:
    """``with dispatch_stage(name):`` times ``name`` (one of
    :data:`DISPATCH_STAGES`) into the dispatch record bound to this
    thread, and gives the time back to the stage it interrupted; a
    no-op on every other thread."""

    __slots__ = ("_name", "_side", "_prev")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._side = side = getattr(_bound, "side", None)
        if side is not None:
            self._prev = side.mark(self._name)
        return side

    def __exit__(self, *exc):
        if self._side is not None:
            self._side.mark(self._prev)
        return False


def open_stage(name: str, t0: float):
    """``tracing.span(..., stage=name)``'s hand-over: the span took its
    stamp once and gives it to the record too. A dispatch stage goes to
    the side bound to this thread, any other name to the live staged
    timeline. -> a token for :func:`close_stage`, None where neither
    record is live here."""
    if name in _DISPATCH_STAGE_SET:
        side = getattr(_bound, "side", None)
        return None if side is None else (side, side.mark(name, t0))
    tl = _timeline.get()
    if tl is None or tl.stages is None:
        return None
    return (tl, name, t0)


def close_stage(token, t1: float) -> None:
    if len(token) == 2:  # (side, the stage this one interrupted)
        token[0].mark(token[1], t1)
    else:
        tl, name, t0 = token
        st = tl.stages
        st[name] = st.get(name, 0.0) + t1 - t0


def slow_root(record: dict) -> None:
    """Structured slow-query entry (tracing's slow-root path lands here
    instead of free-text-only logging)."""
    _slowlog().append(dict(record))


def debug_flight() -> dict:
    """The /v1/debug/flight payload."""
    flush()
    return {
        "dispatches": _flight().snapshot(),
        "slowlog": _slowlog().snapshot(),
        "snapshots": _snapshot_files(),
    }


# -- incident snapshots -------------------------------------------------------

_SNAPSHOT_KEEP = 8
_snapshot_lock = threading.Lock()
_last_snapshot: float | None = None


def _snapshot_dir() -> str | None:
    return os.path.join(_data_dir, "flightrecorder") if _data_dir else None


def _snapshot_files() -> list[str]:
    d = _snapshot_dir()
    if not d or not os.path.isdir(d):
        return []
    try:
        return sorted(f for f in os.listdir(d) if f.endswith(".json"))
    except OSError:
        return []


def snapshot_cooldown_s() -> float:
    return _env_float("WEAVIATE_TPU_FLIGHT_SNAPSHOT_COOLDOWN_S", 30.0)


def snapshot_to_disk(reason: str, force: bool = False) -> str | None:
    """Persist the flight recorder + SLO state on incident (SLO burn,
    component-health flip). Cooldown-limited so a flapping incident
    cannot spam the data dir; keeps the newest ``_SNAPSHOT_KEEP`` files.
    Returns the written path, or None (no data dir / cooldown)."""
    global _last_snapshot
    d = _snapshot_dir()
    if d is None:
        return None
    now = _mono()
    with _snapshot_lock:
        if (not force and _last_snapshot is not None
                and now - _last_snapshot < snapshot_cooldown_s()):
            return None
        _last_snapshot = now
    try:
        from weaviate_tpu.runtime import degrade

        payload = {
            "written_at": time.time(),
            "reason": reason,
            "dispatches": _flight().snapshot(),
            "slowlog": _slowlog().snapshot(),
            "slo": slo_engine().snapshot(),
            "componentHealth": degrade.health(),
            "tail": tail_traces(16),
        }
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"flight-{int(time.time() * 1000)}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        files = _snapshot_files()
        for stale in files[:-_SNAPSHOT_KEEP]:
            try:
                os.unlink(os.path.join(d, stale))
            except OSError:
                pass
        try:
            from weaviate_tpu.runtime.metrics import flight_snapshots_total

            flight_snapshots_total.labels(reason).inc()
        except Exception:  # pragma: no cover
            pass
        logger.warning("flight-recorder snapshot written: %s (%s)",
                       path, reason)
        return path
    except Exception:  # incident capture must never crash serving
        logger.exception("flight-recorder snapshot failed")
        return None


def on_component_unhealthy(component: str, reason: str) -> None:
    """degrade.mark_unhealthy hook: a component flipping unhealthy is an
    incident — capture the dispatch history that led to it. SLO flips
    come through here too (mark_unhealthy call order), deduped by the
    snapshot cooldown."""
    if component.startswith("slo:"):
        return  # check_incidents already snapshotted with the burn reason
    snapshot_to_disk(f"component:{component}")


# -- the interpreter's account: threads by role, the lock probe ---------------

#: the ``role`` label's values, all of them. Python threads by name,
#: native threads by ``comm``; the canary's work runs on the
#: cyclemanager's thread and the probe itself is ``python_other``.
#: ``exited`` is what the process has used beyond its live threads and
#: beyond what threads that have gone were charged while they lived:
#: threads that began and ended between two walks (a REST handler's, a
#: compile pool's) and a seen thread's last stretch, so that the roles
#: add up to the process
THREAD_ROLES = ("grpc_serve", "grpc_pool", "batcher_worker", "batcher_drain",
                "cyclemanager", "rest", "python_other", "grpc_core",
                "device_runtime", "native_other", "exited")
_PYTHON_ROLES = (("grpc-pool", "grpc_pool"),
                 ("query-batcher", "batcher_worker"),
                 ("dp-dispatch", "batcher_worker"),
                 ("cyclemanager", "cyclemanager"), ("rest-", "rest"))
#: native threads by the ``comm`` prefixes a serving process showed on
#: the chip's host (PERF.md section 6, PR 39): grpc's ``event_engine``,
#: ``grpc_global_tim`` and ``lifeguard``; the TPU runtime's and XLA's
#: ``pjrt-tpu-*``, ``tfrt-*``, ``tf_XLAEigen``, ``llvm-worker-*`` and
#: the two a process on the CPU backend (same grpc, same jax) does not
#: have, ``EventFDAsyncWor`` and ``futex-default-S``
_NATIVE_ROLES = (
    (("event_engine", "grpc", "lifeguard"), "grpc_core"),
    (("pjrt", "tfrt", "tf_", "llvm", "EventFDAsyncWor", "futex-default-S"),
     "device_runtime"))


def thread_role(name: str | None, comm: str = "") -> str:
    """A thread's role: ``name`` is a Python thread's
    (``threading.enumerate()``), None for a native one, which is told by
    its ``comm``. grpc starts its serving thread as
    ``Thread(target=_serve)``, which Python names ``Thread-N (_serve)``;
    the pool's and every long-lived thread of this package carry a name
    of their own, so ``python_other`` stays small."""
    if name is not None:
        if name.endswith("(_serve)"):
            return "grpc_serve"
        if name.endswith("-transfer"):
            return "batcher_drain"
        if name.endswith("(process_request_thread)"):
            return "rest"
        for prefix, role in _PYTHON_ROLES:
            if name.startswith(prefix):
                return role
        return "python_other"
    for prefixes, role in _NATIVE_ROLES:
        if comm.startswith(prefixes):
            return role
    return "native_other"


def _procfs_reader():
    """-> ``read(path) -> bytes`` for small procfs files. Called through
    ``ctypes.PyDLL`` the three libc calls KEEP the interpreter lock: a
    plain ``open().read()`` gives it up round every system call, and on
    a loaded server a thread queues milliseconds to take it back, a few
    hundred times a walk (the readings of one walk would then lie
    seconds apart). The walk still runs bytecode between two reads, so
    like any busy Python thread it hands the lock over when a waiter's
    switch interval (5 ms) has run out: no thread waits for a scrape
    longer than for any other thread that computes."""
    libc = ctypes.PyDLL(None)
    c_open, c_read, c_close = libc.open, libc.read, libc.close
    c_open.argtypes = (ctypes.c_char_p, ctypes.c_int)
    c_read.argtypes = (ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t)
    c_read.restype = ctypes.c_ssize_t
    buf = ctypes.create_string_buffer(512)

    def read(path: str) -> bytes:
        fd = c_open(path.encode(), 0)
        if fd < 0:
            return b""
        n = c_read(fd, buf, 512)
        c_close(fd)
        return buf.raw[:n] if n > 0 else b""

    return read


class ThreadAccount:
    """What the kernel has charged each thread of this process, by role:
    ``walk`` reads ``<root>/<tid>/schedstat`` (ns on a core, ns runnable
    and waiting for one; ``stat``'s utime + stime is the first in 10-ms
    ticks and stands in where the kernel keeps no schedstat) for every
    thread, and ``comm`` for the native ones, and gives back what each
    role has used SINCE THE LAST WALK. A thread seen for the first time
    brings all it has used so far; one that exited has its readings up
    to the last walk in its role's total, which therefore never falls,
    and what it used after that walk comes in under ``exited``.

    A walk is ONE read a thread once it knows them: a native thread's
    role is kept from its second sighting on (its first may come before
    the thread has named itself), and a kernel that showed no schedstat
    is not asked again. ``walk_seconds`` is what the last walk took: on
    the chip's hosts ~55 us a read, 13-17 ms at 280 threads on an idle
    server and 29-36 ms under load, its own waits for the interpreter
    included (PERF.md section 6, PR 39). Nothing here runs anywhere but
    at a scrape."""

    def __init__(self, proc: str = "/proc/self", read=None):
        self.proc = proc
        self.root = proc + "/task"
        self._read = read or _procfs_reader()
        self._last: dict[int, tuple[float, float]] = {}
        self._native_role: dict[int, str] = {}
        self._schedstat: bool | None = None
        self._vanished = 0.0  # last readings of seen threads that went
        self._exited = 0.0
        self._tick = 1.0 / os.sysconf("SC_CLK_TCK")
        self.walk_seconds = 0.0

    def _stat_cpu(self, path: str) -> float | None:
        """utime + stime of a ``stat`` file, in seconds."""
        fields = self._read(path).rpartition(b")")[2].split()
        if len(fields) < 13:
            return None
        return (int(fields[11]) + int(fields[12])) * self._tick

    def _usage(self, tid: str) -> tuple[float, float] | None:
        """(CPU s, run-queue wait s) of one thread; None: it has gone."""
        base = f"{self.root}/{tid}/"
        if self._schedstat is not False:
            fields = self._read(base + "schedstat").split()
            if len(fields) >= 2:
                self._schedstat = True
                return int(fields[0]) * 1e-9, int(fields[1]) * 1e-9
        cpu = self._stat_cpu(base + "stat")
        if cpu is None:
            return None
        if self._schedstat is None:
            # the first thread read has no such file: this kernel keeps
            # none, and is not asked again
            self._schedstat = False
        return cpu, 0.0

    def _role(self, tid: int, name: str | None, known: bool) -> str:
        if name is not None:
            return thread_role(name)
        role = self._native_role.get(tid)
        if role is None:
            role = thread_role(None, self._read(
                f"{self.root}/{tid}/comm").decode(errors="replace").strip())
            if known:
                self._native_role[tid] = role
        return role

    def walk(self, python_threads: dict[int, str]) -> tuple[dict, dict, dict]:
        """``python_threads``: native id -> name of every Python thread.
        -> ({role: CPU s}, {role: run-queue wait s}) since the last
        walk, {role: live threads}; every role of :data:`THREAD_ROLES`
        is a key of each."""
        t0 = time.perf_counter()
        cpu = dict.fromkeys(THREAD_ROLES, 0.0)
        wait = dict.fromkeys(THREAD_ROLES, 0.0)
        live = dict.fromkeys(THREAD_ROLES, 0)
        last, seen = self._last, {}
        try:
            tids = os.listdir(self.root)
        except OSError:
            tids = []
        for tid in tids:
            usage = self._usage(tid)
            if usage is None:
                continue
            tid_n = int(tid)
            was = last.pop(tid_n, None)
            if was is not None and usage[0] < was[0]:
                # the id, under another thread: the old one has gone
                self._vanished += was[0]
                self._native_role.pop(tid_n, None)
                was = None
            role = self._role(tid_n, python_threads.get(tid_n),
                              was is not None)
            if was is None:
                was = (0.0, 0.0)
            cpu[role] += usage[0] - was[0]
            wait[role] += max(0.0, usage[1] - was[1])
            live[role] += 1
            seen[tid_n] = usage
        # what is left of the last walk's threads has gone: their
        # readings stay in their roles, so they come off the remainder
        for tid_n, was in last.items():
            self._vanished += was[0]
            self._native_role.pop(tid_n, None)
        self._last = seen
        # the process's own total holds the threads that are no more
        total = self._stat_cpu(self.proc + "/stat")
        if total is not None:
            gone = (total - sum(u[0] for u in seen.values())
                    - self._vanished)
            if gone > self._exited:
                cpu["exited"] = gone - self._exited
                self._exited = gone
        self.walk_seconds = time.perf_counter() - t0
        return cpu, wait, live


class LockProbe:
    """The ``lock-probe`` thread: sleep ``PERIOD_S``, note how much
    later than that the interpreter was back in hand. CPython gives the
    lock to any waiter, so the lateness is a sample of what every thread
    of the process pays to re-enter the interpreter after a blocking
    call (plus the timer's slack, which is all it reads on an idle
    server). 50 samples a second into a bounded deque, and nothing else
    on any thread: :func:`scrape_refresh` folds them."""

    PERIOD_S = 0.02

    def __init__(self):
        self.samples: deque = deque(maxlen=1 << 16)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="lock-probe",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        clock, sleep, period = time.perf_counter, time.sleep, self.PERIOD_S
        put, stopped = self.samples.append, self._stop.is_set
        while not stopped():
            t0 = clock()
            sleep(period)
            put(clock() - t0 - period)

    def alive(self) -> bool:
        return self._thread.is_alive()

    def stop(self) -> None:
        self._stop.set()

    def take(self) -> list[float]:
        out, pop = [], self.samples.popleft
        try:
            while True:
                out.append(max(0.0, pop()))
        except IndexError:
            return out


_account_lock = threading.Lock()
_account: ThreadAccount | None = None
_probe: LockProbe | None = None


def start_probe() -> None:
    """Server start (:func:`configure`): the probe runs while the
    tailboard is on, and only in a process that serves."""
    global _probe
    with _account_lock:
        if enabled() and (_probe is None or not _probe.alive()):
            _probe = LockProbe()


def stop_probe() -> None:
    global _probe
    with _account_lock:
        if _probe is not None:
            _probe.stop()
            _probe = None


def _account_refresh() -> None:
    """The scrape's reading of the interpreter's account: the probe's
    samples into their histogram, the threads' CPU by role into the
    counters. The clock gauge is taken beside the walk."""
    global _account
    from weaviate_tpu.runtime import metrics

    with _account_lock:
        if _probe is not None:
            samples = _probe.take()
            if samples:
                metrics.interpreter_wait_seconds.labels().observe_many(
                    samples)
        if _account is None:
            _account = ThreadAccount()
        names = {t.native_id: t.name for t in threading.enumerate()
                 if t.native_id is not None}
        now = _mono()
        cpu, wait, live = _account.walk(names)
        metrics.scrape_clock_seconds.set(now)
        metrics.thread_account_walk_seconds.set(_account.walk_seconds)
        for role in THREAD_ROLES:
            metrics.thread_cpu_seconds_total.labels(role).inc(cpu[role])
            metrics.thread_runqueue_wait_seconds_total.labels(role).inc(
                wait[role])
            metrics.threads_by_role.labels(role).set(live[role])


# -- debug payloads -----------------------------------------------------------


def debug_slo() -> dict:
    flush()
    eng = slo_engine()
    eng.refresh()
    return eng.snapshot()


def scrape_refresh() -> None:
    """Read-point hook for the /v1/metrics scrape paths: fold the
    pending completion records, republish the burn gauges (and run the
    incident sweep), then read the interpreter's account."""
    flush()
    slo_engine().refresh()
    if enabled():
        _account_refresh()


# -- test isolation -----------------------------------------------------------


def reset_for_tests() -> None:
    """Drop every cached policy/registry so the next use re-reads env —
    the conftest autouse fixture calls this between tests."""
    global _enabled_cached, _forced, _slow_map, _data_dir
    global _tail_ring, _flight_ring, _slowlog_ring, _slo_engine
    global _tenant_guard, _collection_guard, _last_snapshot
    global _pending, _pending_dispatch, _finish_seq, _account
    stop_probe()
    _account = None
    _enabled_cached = None
    _forced = None
    _slow_map = None
    _slow_cache.clear()
    _data_dir = None
    _tail_ring = None
    _flight_ring = None
    _slowlog_ring = None
    _slo_engine = None
    _tenant_guard = None
    _collection_guard = None
    _phase_child_cache.clear()
    _stage_child_cache.clear()
    with _fold_lock:
        _pending = _PendingRing()
        _pending_dispatch = _PendingRing()
        _finish_seq = itertools.count(1)
    with _snapshot_lock:
        _last_snapshot = None
