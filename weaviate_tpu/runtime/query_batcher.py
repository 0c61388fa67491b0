"""Server-side dynamic query batching.

Round-1 gap (VERDICT item 6): each concurrent Search dispatched its own
device program, so N clients paid N host->device round trips while the
scan kernel itself amortizes perfectly over a query batch
(`FlatIndex.search_by_vector_batch` runs one matmul for B queries).

Design (continuous batching, not a fixed window): a request that finds
the device idle dispatches IMMEDIATELY — zero added latency for a lone
client. Requests that arrive while a dispatch is in flight queue up; the
worker drains the whole queue into ONE batched dispatch as soon as the
device frees up. Under load the batch size self-tunes to the arrival
rate, exactly like continuous batching in model serving.

One wait is added to that (PR 32, ``_await_company``): where the batcher
already holds two or more requests (queued, or in the transfer window)
and has lately held more at once than it does now, the worker waits
while requests keep arriving, for at most half a recent dispatch's
launch-to-delivery time between two of them. A request that finds the
batcher empty still leaves at once. Without it 32 closed-loop clients
can settle into dispatches of one to four requests, each paying the
whole per-dispatch cost on the host.

Filtered requests coalesce too (ISSUE 3): when the index advertises
``supports_batched_filters`` the drain ships each request's allow list
alongside its query row and the engine folds them into per-query packed
bitmasks consumed INSIDE the scan kernels — one device program serves a
mixed filtered/unfiltered drain (unfiltered rows ride an all-ones mask;
a drain with no filters skips mask handling entirely). Two escape
hatches stay on the solo path: index types without batched-filter
support, and HIGHLY SELECTIVE filters, which the per-dispatch heuristic
routes to the store's gathered cutover (engine/store.py: scanning a
dense gather of the few allowed rows beats a full masked scan below
~capacity/8; the batcher uses a stricter /64 cut because a solo dispatch
also forfeits batching).

Drained batches are padded to power-of-two B buckets and k is bucketed
the same way, so the number of compiled program variants is bounded by
log2(max_batch) * log2(max k) instead of one executable per observed
(batch, k) combination. Mixed k's batch together at the k bucket and
slice.

Zero-sync pipeline (ISSUE 7): with an ``async_batch_fn`` (an index
``search_by_vector_batch_async`` returning a device-resident
``DeviceResultHandle``), the worker becomes a pure DISPATCH loop — it
launches batch N's program and hands the handle to a dedicated transfer
thread (runtime/transfer.py, double-buffered), then immediately drains
and dispatches batch N+1 while N's results cross D2H. The device never
idles on a host sync, and the host-side result routing (row slicing,
waiter wakeup) for batch N overlaps batch N+1's device time. The
transfer window (depth 2) is backpressure: at most two batches are in
flight past dispatch, so staged host memory stays bounded. Results are
bit-identical to the sync path — same program, same padding, same
slicing; only WHERE the transfer happens moves.

One batcher a collection (ISSUE 42): a plain request over several local
shards is ONE item on the collection's drain (db/drain.py), a
``QueryBatcher`` whose dispatch launches every member shard's scan over
the same padded block and hands ONE gathered handle to its one transfer
thread. The per-shard batchers stay for everything that targets one
shard or carries a filter.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from weaviate_tpu.runtime import (degrade, faultline, kernelscope, placement,
                                  retry, tailboard, tracing)
from weaviate_tpu.runtime.transfer import TransferPipeline

#: bounded intake: past this queue depth the batcher sheds load with a
#: typed retriable OverloadedError (REST surfaces it as 503 +
#: Retry-After) instead of accepting latency it can never serve
DEFAULT_MAX_QUEUE = int(os.environ.get("WEAVIATE_TPU_BATCHER_MAX_QUEUE",
                                       "4096"))


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class DeviceHybridUnavailable(RuntimeError):
    """The drain carried hybrid (sparse+dense) requests but the index
    could not run the fused device program for this dispatch shape —
    the shard layer catches this and serves the query through the host
    hybrid path instead."""


class BatcherStopped(RuntimeError):
    """The batcher was stopped with this request still queued, or before
    it could be: a shard that closed, or a collection's drain retired
    because the set of local shards changed under the request (the
    collection then answers through its shards' own batchers)."""


class _Pending:
    __slots__ = ("query", "k", "allow", "sparse", "event", "ids", "dists",
                 "error", "ctx", "t_enqueue", "t_deliver", "rec",
                 "explain_on", "explain")

    def __init__(self, query, k, allow, sparse=None):
        self.query = query
        self.k = k
        self.allow = allow
        # hybrid requests carry their packed sparse operand
        # (ops/bm25.SparseOperand) the way filtered ones carry ``allow``
        self.sparse = sparse
        self.event = threading.Event()
        # enqueue stamp: the flight recorder's wait_ms and the tailboard
        # queue_wait phase both derive from it
        self.t_enqueue = 0.0
        # stamped by the delivering thread just before ``event.set()``:
        # the request's ``wake`` stage runs from it
        self.t_deliver: float | None = None
        self.ids = None
        self.dists = None
        self.error: Exception | None = None
        # trace context of the submitting request: the worker dispatches
        # under ONE waiter's context (device spans land in that trace)
        self.ctx = tracing.capture()
        # the record of the dispatch this request rode in (tailboard's
        # flight record = the dispatch's stamp sheet): its stamps, batch
        # size, epoch fanout and kernelscope attribution are read back
        # on the request thread — every waiter of one dispatch reads the
        # same record, so they agree by construction
        self.rec: dict | None = None
        # per-query EXPLAIN: captured on the request thread at enqueue
        # (the worker has no request context); the dispatch plan is
        # merged back into the request sink after the waiter wakes
        self.explain_on = kernelscope.explain_enabled()
        self.explain: dict | None = None


class QueryBatcher:
    """Wraps one vector index's batched search entry point.

    ``batch_fn(queries [B,d], k, allow) -> (ids [B,k], dists [B,k])``
    where ``allow`` is None, one shared allow list, or — only when
    ``supports_filter_batching`` — a list of per-request allow lists
    (None entries = unfiltered). ``supports_filter_batching`` may be a
    bool or a zero-arg callable re-read at every dispatch: index
    capabilities change at runtime (DynamicIndex's flat->IVF upgrade,
    ``compress()`` swapping the backing store), and a stale snapshot
    would keep routing filtered requests solo after the index learned
    to coalesce them. ``capacity_fn`` (optional, returns the
    backing store's row capacity) powers the per-dispatch selectivity
    heuristic that routes tiny filters to the solo/gathered path — wire
    it ONLY when the store has a gathered cutover; otherwise solo is a
    full masked scan and strictly worse than batching. ``count_fn``
    (optional, ``allow -> int``) is where that heuristic reads an allow
    list's size: an index that keeps its filters' operands knows the
    count of a mask it has seen, and the batcher counts where no such
    function is wired or it returns None. ``pad_pow2``
    pads drains to pow2 B/k buckets — right for jitted device programs
    (bounds compiled variants), wasted work for per-row host indexes
    like HNSW (padded rows run real graph searches), so those opt out.

    Two units drain (ISSUE 42): a shard's index (``Shard._query_batcher``:
    every request that targets one shard, and a filtered one over
    several) and a collection's set of local shards (db/drain.py: a plain
    request over several). The second is this class with a ``batch_fn``
    / ``async_batch_fn`` that launch one scan a member shard over the one
    block and answer ``[B, S, k]``: ``program_devices`` names the chip of
    each program a dispatch launches (the dispatch counter moves once an
    entry), and ``_deliver`` hands a waiter ``[S, k]``.
    """

    #: ``_await_company``: the queue length at which a drain stops waiting
    #: for company; the wait for the next arrival as a share of a recent
    #: dispatch's launch-to-delivery time, and its cap (s); what a
    #: dispatch leaves of the remembered peak of requests held at once
    COALESCE_MIN = 16
    COALESCE_FLIGHT_SHARE = 0.5
    COALESCE_GAP_MAX_S = 0.010
    COALESCE_PEAK_DECAY = 0.98

    def __init__(self, batch_fn, max_batch: int = 256,
                 supports_filter_batching: bool = False,
                 capacity_fn=None, count_fn=None, pad_pow2: bool = True,
                 owner: dict | None = None, async_batch_fn=None,
                 transfer_depth: int = 2,
                 max_queue: int | None = None, kind: str = "index",
                 hybrid_batch_fn=None, program_devices=None):
        from weaviate_tpu.runtime import hbm_ledger

        self._batch_fn = batch_fn
        # hybrid dataplane: ``hybrid_batch_fn(queries, k, allows,
        # sparses) -> DeviceResultHandle | None`` runs the fused
        # sparse+dense program for drains carrying sparse operands
        # (None = unavailable for this dispatch shape -> the hybrid
        # waiters get a typed DeviceHybridUnavailable and the host path
        # takes over at the shard layer)
        self._hybrid_fn = hybrid_batch_fn
        # index kind label for kernelscope's per-compiled-variant
        # residency EWMA (the shard passes the index's ``index_type``)
        self.kind = str(kind)
        # zero-sync pipeline: ``async_batch_fn(queries, k, allow) ->
        # DeviceResultHandle | None`` (None = this dispatch can't run
        # async, fall back to batch_fn). When set, coalesced drains
        # dispatch-and-go: D2H runs on the transfer thread while the
        # worker drains the next batch.
        self._async_fn = async_batch_fn
        self._transfer: TransferPipeline | None = None
        self._transfer_depth = transfer_depth
        self.max_batch = max_batch
        self.max_queue = DEFAULT_MAX_QUEUE if max_queue is None \
            else max_queue
        self.filter_batching = supports_filter_batching  # bool | callable
        self._capacity_fn = capacity_fn
        self._count_fn = count_fn
        self.pad_pow2 = pad_pow2
        # HBM-ledger labels for the padded dispatch buffer (the shard
        # layer passes its collection/shard; standalone batchers fall
        # back to the ambient owner scope)
        self._hbm_owner = owner or hbm_ledger.current_owner()
        # the chip this batcher's index lies on (runtime/placement.py):
        # the ``device`` label of its dispatch counter and records
        self._device_label = placement.label(self._hbm_owner.get("device"))
        # one entry a PROGRAM a coalesced dispatch launches, the label
        # of the chip it runs on: the dispatch counter moves once an
        # entry. One, this batcher's own, for a shard's batcher; a
        # collection's drain (db/drain.py) launches one scan a member
        # shard over the one query block and names each member's chip
        self._program_devices = (self._device_label,) \
            if program_devices is None else tuple(program_devices)
        # metering labels: one batcher serves one (shard, vector), so
        # every request a dispatch coalesces shares these
        self._meter_labels = (
            str(self._hbm_owner.get("collection") or "-"),
            str(self._hbm_owner.get("tenant") or "-"))
        # health key scoped to THIS batcher's owner: batchers are
        # per-shard/per-vector, and a healthy shard's batch must not
        # clear the unhealthy flag a persistently-broken shard set
        scope = "/".join(str(v) for v in (
            self._hbm_owner.get("collection"), self._hbm_owner.get("shard"))
            if v and v not in ("-", "_unowned"))
        self._component = f"query_batcher:{scope}" if scope \
            else "query_batcher"
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: list[_Pending] = []
        self._worker: threading.Thread | None = None
        self._stopped = False
        self._queue_depth_at_drain = 0
        # what ``_await_company`` goes by: the most requests held at once
        # of late (queued + in the transfer window; decays a dispatch),
        # those in the window now, a running mean of launch -> delivered (s)
        self._peak = 0.0
        self._in_window = 0
        self._flight_s = 0.0
        # observability (tests/test_concurrency.py asserts coalescing;
        # tests/test_query_batcher.py asserts the pipeline overlaps)
        self.dispatches = 0
        self.batched_queries = 0
        self.filtered_batched = 0
        self.hybrid_batched = 0
        self.async_dispatches = 0
        # dispatches launched while a previous batch was still in the
        # transfer window — the overlap the double-buffering exists for
        self.overlapped_dispatches = 0

    def _ensure_worker(self):
        """Caller holds ``_cv`` (enqueue() appends under it)."""
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._run, name="query-batcher", daemon=True)
            self._worker.start()

    def stop(self):
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
            tp = self._transfer
        if tp is not None:
            # drains in-flight handles: every waiter gets its result (or
            # the fetch error), never a hang on shutdown
            tp.stop()

    def _ensure_transfer(self) -> TransferPipeline:
        with self._cv:
            if self._stopped:
                # stop() only stops the pipeline it can SEE — creating
                # one after it looked would leak a never-stopped drain
                # thread and let post-stop dispatches succeed. Raising
                # here routes the in-flight drain to its waiters as an
                # error (via _run's handler / the submit RuntimeError
                # path below).
                raise BatcherStopped("query batcher stopped")
            if self._transfer is None:
                self._transfer = TransferPipeline(
                    depth=self._transfer_depth, name="qb-transfer")
            return self._transfer

    def search(self, query: np.ndarray, k: int,
               allow: np.ndarray | None = None, sparse=None):
        """Blocking per-request entry; coalesces under concurrency:
        ``enqueue``, ``wait`` and ``finish``, one after the other. A
        caller that searches several batchers for one request (a
        collection's fan-out over its shards) calls the three itself."""
        return self.finish(self.wait(self.enqueue(query, k, allow, sparse)))

    def enqueue(self, query: np.ndarray, k: int,
                allow: np.ndarray | None = None, sparse=None) -> _Pending:
        """First half of ``search``: queue the request and return at
        once.

        ``sparse`` (a packed ``ops/bm25.SparseOperand``) marks a hybrid
        request: it rides the coalesced dispatch the way allow lists do
        and the drain runs the fused sparse+dense device program.

        Deadline-aware: a request that arrives with its budget spent
        fails typed BEFORE enqueueing. Overload-aware: a full queue
        sheds with a retriable OverloadedError instead of queueing
        latency the budget can't absorb."""
        retry.check("batcher")
        item = _Pending(np.asarray(query, dtype=np.float32), k, allow,
                        sparse)
        item.t_enqueue = time.perf_counter()
        with self._cv:
            if len(self._queue) >= self.max_queue:
                raise retry.OverloadedError(
                    f"query batcher queue full "
                    f"({len(self._queue)}/{self.max_queue})",
                    retry_after_s=0.1)
            self._queue.append(item)
            self._peak = max(self._peak,
                             len(self._queue) + self._in_window)
            self._ensure_worker()
            self._cv.notify()
        return item

    def wait(self, item: _Pending) -> _Pending:
        """Block until ``item`` is delivered, at most for what is left
        of the request's budget: a client can never hang past its
        deadline on a wedged dispatch. A budget that runs out drops the
        item from the queue if it still waits there (a dispatch that
        already carries it completes, its results discarded) and gives
        THIS client the typed timeout now."""
        rem = retry.remaining()
        if rem is None:
            item.event.wait()
        elif not item.event.wait(timeout=min(rem, threading.TIMEOUT_MAX)):
            from weaviate_tpu.runtime.metrics import deadline_exceeded_total

            self.discard(item)
            deadline_exceeded_total.labels("batcher").inc()
            raise retry.DeadlineExceeded("batcher")
        return item

    def discard(self, item: _Pending) -> None:
        """Take an abandoned request out of the queue, if it is still
        there: nobody will read its answer."""
        with self._cv:
            try:
                self._queue.remove(item)
            except ValueError:
                pass

    @staticmethod
    def phases(item: _Pending) -> tuple[float, float, float]:
        """(queue_wait, device, transfer) seconds of a delivered request,
        from the record of the dispatch it rode in. "device" is
        kernelscope's attributed residency: the drain-thread stamp
        window minus the sampled-memcpy EWMA (source=drain,
        block_until_ready-free) or the dispatch wall window on
        sync/null-device paths (source=wall); "transfer" is the memcpy
        share. The plain wall split stays as the fallback for dispatches
        that died before attribution."""
        rec = item.rec
        if rec is None:
            return 0.0, 0.0, 0.0
        st = rec["stamps"]
        t_exec = st["exec"]
        device_ms = rec.get("device_ms")
        if device_ms is not None:
            return (t_exec - item.t_enqueue, device_ms / 1000.0,
                    rec["transfer_ms"] / 1000.0)
        if "fetch0" in st:
            return (t_exec - item.t_enqueue, st["fetch0"] - t_exec,
                    st["fetch1"] - st["fetch0"])
        return (t_exec - item.t_enqueue,
                st["done"] - t_exec if "done" in st else 0.0, 0.0)

    def finish(self, item: _Pending, charge: bool = True):
        """Second half of ``search``, for a delivered ``item``: -> (ids,
        dists), or the dispatch's error. Everything here is DERIVED from
        the record of the dispatch this request rode in (the worker and
        the drain thread stamped it; nothing is timed a second time):
        the trace's spans and, with ``charge``, the always-on phases and
        the wake stage. A fan-out charges one of its searches, the one
        on its critical path (``Collection.near_vector``); the spans are
        recorded for every one."""
        rec = item.rec
        if rec is not None:
            t_wake = time.perf_counter()
            st = rec["stamps"]
            t_exec = st["exec"]
            t_done = item.t_deliver or st.get("done") or t_wake
            tracing.record_span("batcher.wait", item.t_enqueue, t_exec)
            if rec.get("filtered") and "assemble0" in st:
                # a filtered dispatch's copy of its query rows and allow
                # lists into the padded block — NOT the mask pack, which
                # happens inside batch_fn under ``store.mask_pack``
                tracing.record_span("batcher.assemble", st["assemble0"],
                                    t_exec)
            tracing.record_span("batcher.execute", t_exec, t_done,
                                batch=rec.get("batch") or 1,
                                **({"epochs": rec["epochs"]}
                                   if rec.get("epochs") else {}))
            if "fetch0" in st:
                # the pipelined D2H drain for this request's batch (the
                # transfer thread's handle.result() window)
                tracing.record_span("batcher.transfer", st["fetch0"],
                                    st["fetch1"])
            if charge:
                # always-on phase attribution (tailboard), folded into
                # this request's live timeline on the request thread
                queue_wait, device, transfer = self.phases(item)
                tailboard.phase("queue_wait", queue_wait)
                tailboard.phase("device", device)
                tailboard.phase("transfer", transfer)
                if item.t_deliver is not None:
                    # event set -> this thread running again: with 32
                    # request threads on one interpreter lock this is
                    # where a woken waiter queues for it
                    tailboard.request_stage("wake",
                                            t_wake - item.t_deliver)
        if item.explain is not None:
            # fold the dispatch's plan into the request-level explain
            # sink (installed by the REST/gRPC edge on THIS thread)
            kernelscope.merge_into_request(item.explain)
        if item.error is not None:
            raise item.error
        return item.ids, item.dists

    # -- worker ---------------------------------------------------------------

    def _run(self):
        while True:
            # the dispatch record is opened BEFORE the wait for work, so
            # the wait that precedes a dispatch is stamped into that
            # dispatch's sheet; this thread is its ``worker`` side.
            # ``assemble`` is the worker's own work on a dispatch: it
            # runs wherever no other stage is marked (the two waits,
            # launch, mask_pack, ...), so the side's stages never
            # overlap and cover its wall time
            rec = tailboard.new_dispatch("batcher", self.kind,
                                         self._device_label)
            side = tailboard.bind_dispatch(rec, "worker", "assemble")
            drained = self._await_drain(side)
            if drained is not None:
                try:
                    self._dispatch(drained, rec)
                except Exception as e:  # noqa: BLE001 — to every waiter
                    for it in drained:
                        if not it.event.is_set():
                            it.error = e
                            it.event.set()
            # a worker that woke only to stop leaves no record
            tailboard.unbind_dispatch(keep=drained is not None)
            if drained is None:
                return

    def _await_drain(self, side) -> list[_Pending] | None:
        """Block until there is work; -> the drained requests (None:
        stopped). The two waits are ``side``'s ``slot_wait`` and
        ``idle`` stages, marked only where the worker really waits."""
        # pipeline pacing: with the transfer window full (one batch
        # computing, one draining), DON'T drain yet — arriving
        # requests keep coalescing into the next batch, so the
        # pipeline keeps the sync path's batch sizes AND the overlap
        tp = self._transfer
        if tp is not None and tp.inflight >= tp.depth:
            side.mark("slot_wait")
            tp.wait_slot()
            side.mark("assemble")
        with self._cv:
            if not self._queue and not self._stopped:
                side.mark("idle")
                while not self._queue and not self._stopped:
                    self._cv.wait(timeout=1.0)
                side.mark("assemble")
            if tp is not None:
                self._await_company(side)
            if self._stopped:
                for it in self._queue:
                    it.error = BatcherStopped("query batcher stopped")
                    it.event.set()
                self._queue.clear()
                return None
            drained = self._queue[: self.max_batch]
            del self._queue[: len(drained)]
            self._peak *= self.COALESCE_PEAK_DECAY
            # queue depth AFTER the drain (what the next batch
            # inherits) — the flight recorder's congestion signal
            self._queue_depth_at_drain = len(self._queue)
        return drained

    def _await_company(self, side) -> None:
        """Caller holds ``_cv``. Every dispatch costs the interpreter the
        same launch, fetch and delivery whether it carries one request or
        sixteen, and under many closed-loop clients that cost is what
        bounds the server: once replies leave one at a time, requests
        come back one at a time, dispatches of one to four trickle
        through at two thirds of the rate, and nothing brings the large
        batches back (PERF.md section 5, bottleneck 6). So the worker
        waits (its ``slot_wait`` stage) for the company it can expect:
        ``_peak`` is how many requests this batcher has recently held at
        once, queued and in the transfer window, so ``_peak`` less those
        in the window now are the most that can still arrive, and
        ``COALESCE_MIN`` is enough. It waits while requests keep
        arriving: until that many are queued, until none has arrived for
        ``gap`` (half of a recent dispatch's launch-to-delivery time, at
        most ``COALESCE_GAP_MAX_S``: never long beside what the dispatch
        itself will take), or until the oldest has waited four gaps. A
        request that finds the batcher empty leaves at once whatever the
        past held, and two clients that alternate find nobody to wait
        for."""
        if len(self._queue) + self._in_window < 2:
            return
        gap = min(self.COALESCE_FLIGHT_SHARE * self._flight_s,
                  self.COALESCE_GAP_MAX_S)
        waited = False
        while self._queue and not self._stopped:
            if len(self._queue) >= min(self.COALESCE_MIN,
                                       int(self._peak) - self._in_window):
                break
            left = min(self._queue[-1].t_enqueue + gap,
                       self._queue[0].t_enqueue + 4.0 * gap
                       ) - time.perf_counter()
            if left <= 0:
                break
            if not waited:
                side.mark("slot_wait")
                waited = True
            self._cv.wait(timeout=left)
        if waited:
            side.mark("assemble")

    def _landed(self, b: int) -> None:
        """A dispatch of ``b`` requests has left the transfer window
        (drain thread, or the worker where the submit failed): they may
        come back now, so the worker's expectation changes."""
        with self._cv:
            self._in_window -= b
            self._cv.notify_all()

    def _allowed_count(self, allow) -> int:
        """Selectivity of an allow list (bool mask over doc-id space or
        array of allowed ids)."""
        if self._count_fn is not None:
            n = self._count_fn(allow)
            if n is not None:
                return n
        a = np.asarray(allow)
        return int(np.count_nonzero(a)) if a.dtype == np.bool_ else a.size

    def _prefer_solo(self, it: _Pending) -> bool:
        """Per-dispatch selectivity heuristic: a HIGHLY selective filter
        beats the batched masked scan by taking the store's gathered
        cutover, which only exists on the solo (shared-mask) path. The
        /64 cut is stricter than the store's /8 crossover because going
        solo also gives up dispatch coalescing."""
        if self._capacity_fn is None:
            return False
        try:
            cap = int(self._capacity_fn())
        except Exception:  # noqa: BLE001 — heuristic only, never fail a query
            return False
        if cap <= 0:
            return False
        return self._allowed_count(it.allow) <= cap // 64

    def _dispatch(self, drained: list[_Pending], rec: dict | None = None):
        """One drain -> its dispatches. ``rec`` is the record the worker
        opened before it waited (its side is bound to this thread); a
        direct caller gets a fresh one."""
        if rec is None:
            rec = tailboard.new_dispatch("batcher", self.kind,
                                         self._device_label)
        # split the drain: filtered requests coalesce with the plain ones
        # into ONE bitmask-batched device program; only index types
        # without batched-filter support and highly selective filters
        # (gathered cutover) dispatch solo
        solo, coal = [], []
        fb = self.filter_batching
        filter_batching = bool(fb() if callable(fb) else fb)
        for it in drained:
            # hybrid requests never go solo: their sparse operand only
            # dispatches through the fused batched program
            if it.sparse is None and it.allow is not None and (
                    not filter_batching or self._prefer_solo(it)):
                solo.append(it)
            else:
                coal.append(it)
        for it in solo:
            plan = {} if it.explain_on else None
            # a solo dispatch is a dispatch of its own: its own record
            # (path=solo: the stage family labels it ``<kind>.solo``),
            # bound over the drain's while it runs; not filed in the
            # flight ring, which keeps one entry per drain
            srec = it.rec = tailboard.new_dispatch(
                "batcher", self.kind, self._device_label)
            t_exec = time.perf_counter()
            srec.update(path="solo", batch=1, b_pad=1, k=it.k,
                        stamps={"exec": t_exec})
            tailboard.bind_dispatch(srec, "worker", "assemble", t_exec)
            try:
                with tailboard.dispatch_stage("launch"):
                    if plan is None:
                        ids, dists = tracing.run_in(
                            it.ctx, self._batch_fn, it.query[None, :],
                            it.k, it.allow)
                    else:
                        with kernelscope.explain_scope(plan):
                            ids, dists = tracing.run_in(
                                it.ctx, self._batch_fn, it.query[None, :],
                                it.k, it.allow)
                it.ids, it.dists = ids[0], dists[0]
            except Exception as e:  # noqa: BLE001
                it.error = e
            srec["stamps"]["done"] = time.perf_counter()
            # no drain stamps on the solo path (sync device call):
            # wall-window attribution, metered against this batcher's
            # owner like any other dispatch
            wall, _ = kernelscope.fold_dispatch(srec, "wall")
            kernelscope.meter(*self._meter_labels, wall)
            if plan is not None:
                plan["batcher"] = {
                    "batch": 1, "b_pad": 1, "k_bucket": it.k,
                    "queue_depth": self._queue_depth_at_drain,
                    "filtered": int(it.allow is not None), "solo": True,
                    "async": False, "kind": self.kind}
                it.explain = plan
            with tailboard.dispatch_stage("deliver"):
                it.t_deliver = time.perf_counter()
                it.event.set()
            tailboard.unbind_dispatch()
        if not coal:
            # a purely-solo drain still leaves a flight-recorder record
            # (batch=0): the solo/gathered path is exactly the regression
            # surface an r05-style post-hoc investigation digs through
            if solo:
                tailboard.record_dispatch(
                    "batcher", rec, batch=0, b_pad=0, k=0,
                    queue_depth=self._queue_depth_at_drain,
                    wait_ms=round(max(
                        (it.rec["stamps"]["exec"] - it.t_enqueue)
                        * 1000.0 for it in solo), 3),
                    filtered=len(solo), solo=len(solo),
                    window_inflight=0, epochs=0)
            return
        b = len(coal)
        # pow2 B/k buckets bound the number of compiled variants (one
        # executable per bucket, not per observed batch size); padded
        # query rows are zero vectors whose results are discarded
        if self.pad_pow2:
            b_pad = min(_next_pow2(b), max(self.max_batch, b))
            k_bucket = _next_pow2(max(it.k for it in coal))
        else:
            b_pad = b
            k_bucket = max(it.k for it in coal)
        filtered = [it for it in coal if it.allow is not None]
        hybrid = [it for it in coal if it.sparse is not None]
        t_assemble0 = time.perf_counter()
        allows = None
        if filtered:
            # per-request allow lists ride along row-aligned; unfiltered
            # and padded rows are None (all-ones downstream)
            allows = [it.allow for it in coal] + [None] * (b_pad - b)
        sparses = None
        if hybrid:
            # sparse operands ride row-aligned exactly like allow lists;
            # pure-vector and padded rows are None (dense-only downstream)
            sparses = [it.sparse for it in coal] + [None] * (b_pad - b)
        queries = np.zeros((b_pad,) + coal[0].query.shape, dtype=np.float32)
        for row, it in enumerate(coal):
            queries[row] = it.query
        self.dispatches += 1
        self.batched_queries += b
        self.filtered_batched += len(filtered)
        from weaviate_tpu.runtime.metrics import (
            batcher_compile_bucket, batcher_filtered_batched)

        for device in self._program_devices:
            batcher_compile_bucket.labels(b=str(b_pad), k=str(k_bucket),
                                          device=device).inc()
        if filtered:
            batcher_filtered_batched.inc(len(filtered))
        # the shared dispatch runs under ONE waiter's trace context (the
        # first traced one) so device-level spans attribute somewhere
        # real; every waiter still records its own wait/execute split
        # from the stamps below
        ctx = next((it.ctx for it in coal if it.ctx is not None), None)
        # per-query EXPLAIN: if any coalesced waiter asked, the engine's
        # host-side plan notes emitted during THIS dispatch (the program
        # build on the worker thread) land in one shared sink; explain
        # never changes WHAT is dispatched — sync and async answers stay
        # bit-identical
        plan = {} if any(it.explain_on for it in coal) else None
        t0 = time.perf_counter()
        # the dispatch's stamp sheet: perf_counter stamps every consumer
        # derives from — ``assemble0`` (query block copy begins), ``exec``
        # (launch begins: the end of every waiter's queue_wait), then
        # ``fetch0``/``fetch1`` (drain thread) and ``done`` (results
        # routed, or the failure)
        stamps = {"assemble0": t_assemble0, "exec": t0}
        # flight-recorder dispatch record (lock-free ring): the dispatch
        # history a post-hoc regression investigation replays. epochs is
        # patched in below once the async handle reports its fanout.
        tp0 = self._transfer
        flight_rec = tailboard.record_dispatch(
            "batcher", rec, batch=b, b_pad=b_pad, k=k_bucket,
            queue_depth=self._queue_depth_at_drain,
            wait_ms=round(max(
                (t0 - it.t_enqueue) * 1000.0 for it in coal), 3),
            filtered=len(filtered), solo=len(solo),
            window_inflight=tp0.inflight if tp0 is not None else 0,
            epochs=0, stamps=stamps)
        for it in coal:
            it.rec = flight_rec

        def _attribute(source: str, nbytes: int = 0):
            """Kernelscope fold for this dispatch, from the record's
            stamps: the attribution lands in the record (each waiter
            reads it back on its own request thread), feeds the
            per-compiled-variant residency EWMA + histogram, and is
            metered per tenant."""
            device_s, _ = kernelscope.fold_dispatch(flight_rec, source,
                                                    nbytes)
            # apportion across the coalesced requests, weighted by rows
            # scanned — one batcher serves one (shard, vector), so rows
            # and owner labels are uniform per dispatch: the weights
            # degenerate to an even split and the tenant meter sees the
            # full dispatch residency exactly once
            for share in kernelscope.apportion(device_s,
                                               [1.0] * len(coal)):
                kernelscope.meter(*self._meter_labels, share)

        # the pow2-padded query block becomes a device upload inside
        # batch_fn — ledger-registered until the results leave the
        # device (sync: end of this call; async: transfer completion) so
        # peak watermarks see concurrent drains
        from weaviate_tpu.runtime.hbm_ledger import ledger as _hbm

        pad_key = _hbm.register("dispatch_pad", queries.nbytes,
                                dtype="float32", **self._hbm_owner)

        def _fail(err: BaseException) -> None:
            """Single exit path for every failure mode: release the pad
            exactly once and set EVERY not-yet-delivered waiter's event
            — an unset event hangs its client forever (the transfer
            thread swallows callback exceptions by design)."""
            _hbm.release(pad_key)
            t1 = stamps.setdefault("done", time.perf_counter())
            for it in coal:
                if not it.event.is_set():
                    it.error = err
                    it.t_deliver = t1
                    it.event.set()

        def _launch(fn, *args):
            """The ``batch_fn`` / ``async_fn`` call: cache lookup, H2D of
            the query block, ``Execute`` (and, on the sync path, the
            stages nested in it: mask_pack, d2h_wait, rescore)."""
            with tailboard.dispatch_stage("launch"):
                if plan is None:
                    return tracing.run_in(ctx, fn, *args)
                # engine plan notes are emitted while the program is
                # built/launched here (host side); an async handle's
                # finish step runs later on the transfer thread and
                # stays outside the sink by design
                with kernelscope.explain_scope(plan):
                    return tracing.run_in(ctx, fn, *args)

        def _sync_batch():
            # faultline point: one coalesced device dispatch (the
            # deterministic schedule sees retries as separate calls)
            faultline.fire("batcher.dispatch", batch=b, k=k_bucket)
            return _launch(self._batch_fn, queries, k_bucket, allows)

        def _retry_once(first_err: BaseException):
            """Faulted device batch: ONE sync retry. A second failure
            errors only THIS batch's waiters — with the ORIGINAL error,
            the root cause — and flips the batcher's unhealthy flag
            (visible in /v1/nodes); later batches keep serving and
            clear it on success. Returns the (ids, dists) tuple or None
            after failing the waiters."""
            from weaviate_tpu.runtime.metrics import batcher_dispatch_retries

            batcher_dispatch_retries.inc()
            try:
                res2 = _sync_batch()
                # a sync fn that can't actually serve (null-device
                # stubs return None) is a failed retry, not a result
                if not (isinstance(res2, tuple) and len(res2) == 2):
                    raise TypeError(
                        f"batch_fn returned {type(res2).__name__}, "
                        "expected (ids, dists)")
                return res2
            except Exception as e2:  # noqa: BLE001
                degrade.mark_unhealthy(
                    self._component,
                    f"dispatch failed twice: {first_err}; retry: {e2}")
                _fail(first_err)
                return None

        def _mark_served():
            if degrade.is_unhealthy(self._component):
                degrade.mark_healthy(self._component)

        handle = None
        ids = dists = None
        try:
            if hybrid:
                # fused sparse+dense program: there is NO sync fallback
                # for hybrid drains (batch_fn has no sparse-operand
                # slot) — unavailability is a typed error the shard
                # layer converts into the host hybrid path, and the
                # pure-vector remainder re-dispatches normally
                hf = self._hybrid_fn
                if hf is not None:
                    faultline.fire("batcher.dispatch", batch=b,
                                   k=k_bucket)
                    handle = _launch(hf, queries, k_bucket, allows,
                                     sparses)
                if handle is None:
                    _hbm.release(pad_key)
                    err = DeviceHybridUnavailable(
                        "index cannot run the fused hybrid program for "
                        "this dispatch")
                    t1 = stamps["done"] = time.perf_counter()
                    for it in hybrid:
                        it.error = err
                        it.t_deliver = t1
                        it.event.set()
                    rest = [it for it in coal if it.sparse is None]
                    if rest:
                        self._dispatch(rest)
                    return
                self.hybrid_batched += len(hybrid)
                from weaviate_tpu.runtime.metrics import \
                    batcher_hybrid_batched

                batcher_hybrid_batched.inc(len(hybrid))
            elif self._async_fn is not None:
                # dispatch-and-go: launch the program, hand the
                # device-resident handle to the transfer thread, return
                # to drain the NEXT batch while this one crosses D2H
                faultline.fire("batcher.dispatch", batch=b, k=k_bucket)
                handle = _launch(self._async_fn, queries, k_bucket,
                                 allows)
            if handle is not None:
                n_ep = int(handle.attrs.get("epochs", 0) or 0)
                if n_ep:
                    flight_rec["epochs"] = n_ep
            if handle is None:
                ids, dists = _sync_batch()
        except Exception as e:  # noqa: BLE001
            if hybrid:
                # no sparse-aware sync retry exists — surface the fault
                _fail(e)
                return
            result = _retry_once(e)
            if result is None:
                return
            ids, dists = result
            handle = None
        if plan is not None:
            plan["batcher"] = {
                "batch": b, "b_pad": b_pad, "k_bucket": k_bucket,
                "queue_depth": self._queue_depth_at_drain,
                "filtered": len(filtered), "hybrid": len(hybrid),
                "solo": False,
                "async": handle is not None, "kind": self.kind}
            for it in coal:
                if it.explain_on:
                    it.explain = plan
        if handle is None:
            _hbm.release(pad_key)
            t1 = stamps["done"] = time.perf_counter()
            # sync path: no drain stamps exist — wall-window attribution
            # with an explicit source label (the null-device deflake
            # guard: degrade, don't crash or report zeros)
            _attribute("wall")
            self._deliver(coal, ids, dists, t1)
            _mark_served()
            return
        self.async_dispatches += 1
        from weaviate_tpu.runtime.metrics import batcher_overlapped

        def _finish(res):
            try:
                t1 = stamps["done"] = time.perf_counter()
                self._flight_s += 0.125 * (t1 - t0 - self._flight_s)
                self._deliver(coal, res[0], res[1], t1)
                _hbm.release(pad_key)
                _mark_served()
            except Exception as e:  # noqa: BLE001 — an out-of-contract
                # result shape must surface to the waiters (the sync
                # path raises it through _run's handler)
                _fail(e)

        def _complete(res, err, t_fetch0, t_fetch1):
            stamps["fetch0"], stamps["fetch1"] = t_fetch0, t_fetch1
            if err is None or hybrid:
                # served or failed here and now: from the worker's side
                # these requests can come back from this moment on
                self._landed(b)
            if err is not None and hybrid:
                # the sync retry path can't re-run a hybrid program
                # (no sparse-operand slot) — deliver the fault
                _fail(err)
                return
            if err is None:
                # drain-thread stamps: dispatch-submit (t0) .. transfer-
                # complete (t_fetch1), minus the sampled-memcpy EWMA for
                # this result size = attributed device residency with
                # ZERO added syncs — the drain blocked on this handle's
                # D2H anyway
                _attribute("drain", kernelscope.result_nbytes(res))
                _finish(res)
                return
            # the device batch (or its D2H drain) faulted on the
            # transfer thread: retry ONCE through the sync path — the
            # queries are still host-resident, so a transient device
            # fault costs one re-dispatch, not client errors. The retry
            # is a FULL device dispatch, so it runs on its own
            # short-lived thread: blocking here would stall every other
            # in-flight batch's D2H behind one faulted batch.

            def _retry_path():
                try:
                    res2 = _retry_once(err)
                    if res2 is not None:
                        # the retry served through the sync path: wall
                        # attribution (the drain stamps belong to the
                        # faulted attempt, not this result)
                        stamps["done"] = time.perf_counter()
                        _attribute("wall")
                        _finish(res2)
                finally:
                    self._landed(b)

            threading.Thread(target=_retry_path, daemon=True,
                             name="batcher-fault-retry").start()

        counted = False
        try:
            tp = self._ensure_transfer()
            if tp.inflight > 0:
                self.overlapped_dispatches += 1
                batcher_overlapped.inc()
            with self._cv:
                self._in_window += b
            counted = True
            tp.submit(handle, _complete, ctx=ctx, rec=flight_rec)
        except Exception as e:  # noqa: BLE001 — stopped mid-shutdown
            _fail(e)
            if counted:
                self._landed(b)

    @staticmethod
    def _deliver(coal: list[_Pending], ids, dists, t1: float):
        """Route one batch's host results to their waiters (identical
        slicing for the sync and pipelined paths — parity by
        construction). ``t1`` is the record's ``done`` stamp, which the
        caller has already written: kept in the signature for callers
        that wrap this (the benchmark's fault injection); each waiter's
        ``t_deliver`` is taken here, just before its event is set.
        ``ids`` and ``dists`` are ``[B, k]``, or ``[B, S, k]`` from a
        collection's drain over S member shards: a waiter then gets
        ``[S, k]``, member by member."""
        with tailboard.dispatch_stage("deliver"):
            for row, it in enumerate(coal):
                kk = min(it.k, ids.shape[-1])
                it.ids = ids[row, ..., :kk]
                it.dists = dists[row, ..., :kk]
                it.t_deliver = time.perf_counter()
                it.event.set()
