"""Zero-sync serving pipeline (ISSUE 7): the query batcher's
double-buffered async path.

Covers the tentpole's contract points:

1. overlap actually occurs — dispatch N+1 starts while batch N's D2H
   fetch is still in flight (the device-idle gap the pipeline removes);
2. results match the sync path BIT-EXACTLY for identical drains across
   filtered/unfiltered mixes (same program, same padding, same slicing —
   only WHERE the transfer happens moves);
3. an error raised on the transfer thread propagates to exactly the
   failing batch's waiters, and the batcher keeps serving afterwards;
4. clean shutdown with in-flight handles — waiters get results, not
   hangs, and post-stop submissions fail loudly;

plus the engine-level handle parity (store/quantized/flat async twins,
gathered-path finish, shard-level queued-tail merge).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.runtime.query_batcher import QueryBatcher, _Pending
from weaviate_tpu.runtime.transfer import (DeviceResultHandle,
                                           TransferPipeline)


def _corpus_index(n=512, dim=16, seed=0, **kw):
    rng = np.random.default_rng(seed)
    idx = FlatIndex(dim=dim, capacity=max(n, 64), **kw)
    idx.add_batch(np.arange(n),
                  rng.standard_normal((n, dim)).astype(np.float32))
    return idx, rng


# -- 1. overlap ---------------------------------------------------------------


def test_dispatch_overlaps_inflight_fetch():
    """Batch N+1's dispatch must start BEFORE batch N's fetch completes:
    the first batch's handle blocks in the transfer thread while the
    worker launches the second."""
    dispatched = []
    release_first = threading.Event()

    def async_fn(queries, k, allow):
        b = len(queries)
        seq = len(dispatched)
        dispatched.append(time.perf_counter())

        def fin():
            if seq == 0:
                assert release_first.wait(timeout=10.0)
            return (np.arange(b * k, dtype=np.int64).reshape(b, k),
                    np.zeros((b, k), np.float32))

        return DeviceResultHandle((), finish=fin)

    def sync_fn(queries, k, allow):  # pragma: no cover — must not run
        raise AssertionError("sync path used")

    qb = QueryBatcher(sync_fn, async_batch_fn=async_fn)
    try:
        out = [None, None]

        def client(j):
            out[j] = qb.search(np.zeros(4, np.float32), 3)

        t0 = threading.Thread(target=client, args=(0,))
        t0.start()
        deadline = time.time() + 5.0
        while len(dispatched) < 1 and time.time() < deadline:
            time.sleep(0.005)
        assert len(dispatched) == 1
        # first batch is now stuck in its D2H window; a second request
        # must still dispatch (double buffering)
        t1 = threading.Thread(target=client, args=(1,))
        t1.start()
        while len(dispatched) < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert len(dispatched) == 2, \
            "second dispatch did not start while the first fetch was " \
            "in flight"
        assert not t0.is_alive() or out[0] is None  # first still waiting
        release_first.set()
        t0.join(timeout=5.0)
        t1.join(timeout=5.0)
        assert out[0] is not None and out[1] is not None
        assert qb.async_dispatches == 2
        assert qb.overlapped_dispatches >= 1
    finally:
        release_first.set()
        qb.stop()


def test_pipeline_pacing_keeps_coalescing():
    """With the transfer window full, the worker must WAIT (requests
    keep coalescing) instead of racing ahead with single-query
    dispatches — the pacing that keeps the batching win alongside the
    overlap win."""
    release = threading.Event()
    batches = []

    def async_fn(queries, k, allow):
        batches.append(len(queries))

        def fin(b=len(queries)):
            assert release.wait(timeout=10.0)
            return (np.zeros((b, k), np.int64),
                    np.zeros((b, k), np.float32))

        return DeviceResultHandle((), finish=fin)

    # pad_pow2 off so ``batches`` records REAL coalesced sizes (the
    # padded block would count pad rows and break the sum below)
    qb = QueryBatcher(lambda *a: (_ for _ in ()).throw(AssertionError()),
                      async_batch_fn=async_fn, transfer_depth=2,
                      pad_pow2=False)
    try:
        threads = [threading.Thread(
            target=lambda: qb.search(np.zeros(4, np.float32), 3))
            for _ in range(12)]
        threads[0].start()
        deadline = time.time() + 5.0
        while len(batches) < 1 and time.time() < deadline:
            time.sleep(0.005)
        for t in threads[1:]:
            t.start()
        # give the stragglers time to enqueue; the window (depth 2)
        # fills after at most two more dispatches, then the rest MUST
        # coalesce into one final drain once released
        time.sleep(0.3)
        assert len(batches) <= 3, batches
        release.set()
        for t in threads:
            t.join(timeout=5.0)
        assert sum(batches) == 12  # every request served, none lost
        # some drain carried a real coalesced backlog (vs 12 x b=1)
        assert max(batches) >= 4, batches
    finally:
        release.set()
        qb.stop()


# -- 2. sync/async parity -----------------------------------------------------


def _drain_through(qb, reqs):
    """Push one fixed drain through ``_dispatch`` — identical batch
    composition for both modes, so results must be bit-exact."""
    items = [_Pending(np.asarray(q, np.float32), k, allow)
             for q, k, allow in reqs]
    qb._dispatch(items)
    for it in items:
        assert it.event.wait(timeout=10.0)
        assert it.error is None, it.error
    return [(np.asarray(it.ids), np.asarray(it.dists)) for it in items]


@pytest.mark.parametrize("quantization", [None, "bq"])
def test_async_results_bit_exact_vs_sync_mixed_drains(quantization):
    kw = {"quantization": quantization} if quantization else {}
    idx, rng = _corpus_index(**kw)
    qs = rng.standard_normal((8, 16)).astype(np.float32)
    # mixed drain: unfiltered rows + per-request allow lists of very
    # different selectivity, mixed k
    reqs = [
        (qs[0], 5, None),
        (qs[1], 5, np.arange(0, 400, 3, dtype=np.int64)),
        (qs[2], 3, None),
        (qs[3], 7, np.arange(100, 140, dtype=np.int64)),
        (qs[4], 5, np.array([7, 9, 11, 13, 400], dtype=np.int64)),
        (qs[5], 5, None),
    ]
    qb_sync = QueryBatcher(idx.search_by_vector_batch,
                           supports_filter_batching=True)
    qb_async = QueryBatcher(idx.search_by_vector_batch,
                            supports_filter_batching=True,
                            async_batch_fn=idx.search_by_vector_batch_async)
    try:
        a = _drain_through(qb_sync, reqs)
        b = _drain_through(qb_async, reqs)
        for (ia, da), (ib, db) in zip(a, b):
            np.testing.assert_array_equal(ia, ib)
            np.testing.assert_array_equal(da, db)
        assert qb_async.async_dispatches == 1
        assert qb_sync.async_dispatches == 0
    finally:
        qb_sync.stop()
        qb_async.stop()


def test_unbatchable_async_falls_back_to_sync_path():
    """An async_batch_fn returning None (index can't serve this drain
    async) must fall back to batch_fn transparently."""
    idx, rng = _corpus_index()
    calls = {"sync": 0}

    def sync_fn(queries, k, allow):
        calls["sync"] += 1
        return idx.search_by_vector_batch(queries, k, allow)

    qb = QueryBatcher(sync_fn, async_batch_fn=lambda *a: None)
    try:
        q = rng.standard_normal(16).astype(np.float32)
        ids, dists = qb.search(q, 5)
        assert len(ids) == 5 and calls["sync"] == 1
        assert qb.async_dispatches == 0
    finally:
        qb.stop()


# -- 3. transfer-thread error propagation -------------------------------------


def test_transfer_error_reaches_only_its_batch_waiters():
    boom = RuntimeError("device fell over mid-transfer")
    gate = threading.Event()
    n_dispatch = [0]

    def async_fn(queries, k, allow):
        b = len(queries)
        seq = n_dispatch[0]
        n_dispatch[0] += 1

        def fin():
            if seq == 0:
                assert gate.wait(timeout=10.0)
                raise boom
            return (np.zeros((b, k), np.int64),
                    np.zeros((b, k), np.float32))

        return DeviceResultHandle((), finish=fin)

    qb = QueryBatcher(lambda *a: None, async_batch_fn=async_fn)
    try:
        errs = [None, None]

        def client(j):
            try:
                qb.search(np.zeros(4, np.float32), 3)
            except Exception as e:  # noqa: BLE001
                errs[j] = e

        t0 = threading.Thread(target=client, args=(0,))
        t0.start()
        deadline = time.time() + 5.0
        while n_dispatch[0] < 1 and time.time() < deadline:
            time.sleep(0.005)
        t1 = threading.Thread(target=client, args=(1,))
        t1.start()
        while n_dispatch[0] < 2 and time.time() < deadline:
            time.sleep(0.005)
        gate.set()
        t0.join(timeout=5.0)
        t1.join(timeout=5.0)
        assert errs[0] is boom, errs[0]   # failing batch's waiter
        assert errs[1] is None            # later batch unaffected
    finally:
        gate.set()
        qb.stop()


# -- 4. clean shutdown --------------------------------------------------------


def test_stop_drains_inflight_handles_then_rejects_new_work():
    release = threading.Event()

    def async_fn(queries, k, allow):
        b = len(queries)

        def fin():
            assert release.wait(timeout=10.0)
            return (np.zeros((b, k), np.int64),
                    np.zeros((b, k), np.float32))

        return DeviceResultHandle((), finish=fin)

    qb = QueryBatcher(lambda *a: None, async_batch_fn=async_fn)
    got = []

    def client():
        got.append(qb.search(np.zeros(4, np.float32), 3))

    t = threading.Thread(target=client)
    t.start()
    deadline = time.time() + 5.0
    while qb.async_dispatches < 1 and time.time() < deadline:
        time.sleep(0.005)
    stopper = threading.Thread(target=qb.stop)
    stopper.start()
    time.sleep(0.05)
    release.set()  # in-flight transfer completes during shutdown
    t.join(timeout=5.0)
    stopper.join(timeout=5.0)
    assert not t.is_alive() and got, "in-flight waiter hung on stop()"
    with pytest.raises(RuntimeError):
        qb.search(np.zeros(4, np.float32), 3)


def test_malformed_async_result_errors_waiters_instead_of_hanging():
    """An async_batch_fn whose handle resolves to an out-of-contract
    shape must surface the routing failure to the batch's waiters — the
    transfer thread swallows callback exceptions to protect later
    batches, so without the _deliver guard every client would block
    forever on an event that is never set."""
    def async_fn(queries, k, allow):
        # 1-D ids: _deliver's ids.shape[1] slicing raises
        return DeviceResultHandle((), finish=lambda: (
            np.zeros(len(queries), np.int64),
            np.zeros(len(queries), np.float32)))

    qb = QueryBatcher(lambda *a: None, async_batch_fn=async_fn)
    try:
        with pytest.raises(Exception):
            qb.search(np.zeros(4, np.float32), 3)
    finally:
        qb.stop()


def test_dispatch_after_stop_cannot_create_a_transfer_pipeline():
    """stop() only stops the pipeline it can see — a dispatch racing
    shutdown must NOT lazily create one afterwards (leaked drain
    thread, post-stop submissions silently succeeding); it errors its
    waiters instead."""
    qb = QueryBatcher(
        lambda *a: None,
        async_batch_fn=lambda q, k, a: DeviceResultHandle(
            (), finish=lambda: (np.zeros((len(q), k), np.int64),
                                np.zeros((len(q), k), np.float32))))
    qb.stop()
    it = _Pending(np.zeros(4, np.float32), 3, None)
    qb._dispatch([it])  # the racing worker's drain, post-stop
    assert it.event.wait(timeout=5.0)
    assert isinstance(it.error, RuntimeError)
    assert qb._transfer is None, "stop() race created a drain pipeline"


def test_transfer_pipeline_stop_without_thread_is_clean():
    tp = TransferPipeline()
    tp.stop()  # never started a thread — must not raise
    with pytest.raises(RuntimeError):
        tp.submit(DeviceResultHandle.ready(1), lambda *a: None)


# -- engine-level handle parity ----------------------------------------------


def test_store_search_async_matches_sync_incl_gathered():
    idx, rng = _corpus_index()
    store = idx.store
    qs = rng.standard_normal((4, 16)).astype(np.float32)
    d1, i1 = store.search(qs, 6)
    d2, i2 = store.search_async(qs, 6).result()
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(d1, d2)
    # gathered path (highly selective shared mask) rides the finish step
    mask = np.zeros(store.capacity, bool)
    mask[:9] = True
    d3, i3 = store.search(qs, 4, mask)
    d4, i4 = store.search_async(qs, 4, mask).result()
    np.testing.assert_array_equal(i3, i4)
    np.testing.assert_array_equal(d3, d4)
    assert set(i4.ravel().tolist()) <= set(range(9)) | {-1}


def test_quantized_async_rescore_pins_dispatch_time_layout():
    """A compact() landing while the handle sits in the transfer window
    must NOT change what the finish step's host rescore resolves: the
    candidates were scanned against the dispatch-time row layout, so the
    rescore reads the dispatch-time capacity + full-precision tier (the
    pipelined drain widens the old microsecond race to a whole
    overlapped batch)."""
    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    rng = np.random.default_rng(11)
    x = rng.standard_normal((600, 32)).astype(np.float32)
    store = QuantizedVectorStore(dim=32, quantization="bq", capacity=1024,
                                 rescore="host")
    store.train(x)
    store.add(x)
    qs = rng.standard_normal((3, 32)).astype(np.float32)
    d_sync, i_sync = store.search(qs, 5)
    handle = store.search_async(qs, 5)
    # shrink + remap the store while the handle is "in flight"
    store.delete(np.arange(0, 600, 2))
    store.compact()
    d_async, i_async = handle.result()
    np.testing.assert_array_equal(i_sync, i_async)
    np.testing.assert_array_equal(d_sync, d_async)


def test_handle_result_is_idempotent_and_caches_errors():
    h = DeviceResultHandle((), finish=lambda: [1, 2, 3])
    assert h.result() == [1, 2, 3]
    assert h.result() is h.result()

    calls = [0]

    def bad():
        calls[0] += 1
        raise ValueError("once")

    h2 = DeviceResultHandle((), finish=bad)
    with pytest.raises(ValueError):
        h2.result()
    with pytest.raises(ValueError):
        h2.result()
    assert calls[0] == 1  # cached, not re-raised from a re-run


def test_shard_batch_async_merges_queued_tail(tmp_path):
    """ASYNC_INDEXING queued vectors must merge into pipelined batch
    results exactly like the sync path (snapshot-before-dispatch)."""
    from weaviate_tpu.db.database import Database
    from weaviate_tpu.schema.config import CollectionConfig

    db = Database(str(tmp_path))
    try:
        col = db.create_collection(CollectionConfig(name="QBA"))
        rng = np.random.default_rng(1)
        vecs = rng.standard_normal((60, 8)).astype(np.float32)
        for i in range(60):
            col.put_object({"i": i}, vector=vecs[i])
        shard = next(iter(col.shards.values()))
        qs = vecs[:5]
        h = shard.vector_search_batch_async(qs, 4)
        assert h is not None
        ids_a, dists_a, counts_a = h.result()
        ids_s, dists_s, counts_s = shard.vector_search_batch(qs, 4)
        np.testing.assert_array_equal(ids_a, ids_s)
        np.testing.assert_array_equal(counts_a, counts_s)
        # self-hit first
        assert [int(ids_a[r, 0]) for r in range(5)] == list(range(5))
    finally:
        db.close()


# -- the dispatch record: one per dispatch, read by every consumer (ISSUE 25) --


def _async_batcher(fin_sleep=0.0, kind="flat", gate=None):
    """Async batcher over a stub program: ``gate`` (an Event) holds the
    FIRST launch so that what arrives meanwhile coalesces."""
    launches = []

    def async_fn(queries, k, allow):
        b = len(queries)
        launches.append(b)
        if gate is not None and len(launches) == 1:
            assert gate.wait(timeout=10.0)

        def fin():
            time.sleep(fin_sleep)
            return (np.arange(b * k, dtype=np.int64).reshape(b, k),
                    np.zeros((b, k), np.float32))

        return DeviceResultHandle((), finish=fin)

    def sync_fn(queries, k, allow):  # pragma: no cover — must not run
        raise AssertionError("sync path used")

    return QueryBatcher(sync_fn, async_batch_fn=async_fn, kind=kind), \
        launches


def test_coalesced_dispatch_is_one_record_read_by_n_requests():
    """n waiters of one coalesced dispatch: ONE dispatch record, n
    request records, and the requests' ``device`` and ``queue_wait`` are
    read from that record (they agree because there is nothing else to
    read), one ``wake`` each."""
    from weaviate_tpu.runtime import tailboard

    n = 5
    gate = threading.Event()
    qb, launches = _async_batcher(fin_sleep=0.01, gate=gate)
    timelines = {}

    def client(i):
        t = time.perf_counter()
        with tailboard.request("grpc.search", t_entry=t,
                               t_arrival=t) as tl:
            qb.search(np.full(4, float(i), np.float32), 3)
            timelines[i] = tl

    try:
        first = threading.Thread(target=client, args=(0,))
        first.start()
        deadline = time.time() + 10.0
        while not launches and time.time() < deadline:
            time.sleep(0.002)          # the first dispatch is in its launch
        rest = [threading.Thread(target=client, args=(i,))
                for i in range(1, n + 1)]
        for t in rest:
            t.start()
        while len(qb._queue) < n and time.time() < deadline:
            time.sleep(0.002)          # n requests queued behind it
        gate.set()
        for t in [first] + rest:
            t.join(timeout=10.0)
    finally:
        qb.stop()
    assert launches == [1, 8]          # one solo-sized, one coalesced (pad 8)
    records = [r for r in tailboard.debug_flight()["dispatches"]
               if r.get("batch") == n]
    assert len(records) == 1
    rec = records[0]
    assert rec["t_source"] == "drain" and rec["kind"] == "flat"
    rode = [timelines[i] for i in range(1, n + 1)]
    assert {tl.phases["device"] for tl in rode} == \
        {rec["device_ms"] / 1000.0}
    waits = [tl.phases["queue_wait"] for tl in rode]
    assert round(max(waits) * 1000.0, 3) == rec["wait_ms"]
    assert all(0 < w <= max(waits) for w in waits)
    assert all(tl.stages["wake"] > 0 for tl in rode)
    # both sides stamped into the one record, and their stages cover
    # their wall time
    worker = dict(rec["worker_ms"])
    wall = worker.pop("worker_wall")
    assert set(worker) <= set(tailboard.DISPATCH_STAGES)
    assert sum(worker.values()) == pytest.approx(wall, rel=0.05)
    # the drain's side runs from the fetch's start past the ``done``
    # stamp (the delivery follows it), with no gap between its stages
    drain = rec["drain_ms"]
    assert set(drain) <= set(tailboard.DISPATCH_STAGES)
    st = rec["stamps"]
    assert sum(drain.values()) >= (st["done"] - st["fetch0"]) * 1000.0
    assert rec["drain_ms"]["finish"] >= 10.0      # the stub's finish step
    assert "launch" in rec["worker_ms"] and "deliver" in rec["drain_ms"]


def _dispatch_stage_reader(kind):
    """(total, count) of the kind's dispatch stages SINCE this call: the
    registry's series live as long as the process."""
    from weaviate_tpu.runtime import tailboard
    from weaviate_tpu.runtime.metrics import dispatch_stage_seconds

    def now(stage):
        child = dispatch_stage_seconds.labels(kind, stage)
        return child.total, child.count

    tailboard.flush()
    names = tailboard.DISPATCH_STAGES + ("worker_wall",)
    base = {s: now(s) for s in names}

    def since(stage):
        tailboard.flush()
        total, count = now(stage)
        return total - base[stage][0], count - base[stage][1]

    return since


def test_worker_stages_cover_the_workers_wall_time():
    """``idle`` + ``slot_wait`` + the worker's other stages cover the
    worker thread's wall time: nothing it does between two dispatches is
    unaccounted."""
    from weaviate_tpu.runtime import tailboard

    since = _dispatch_stage_reader("flat")
    qb, launches = _async_batcher(fin_sleep=0.002)
    t0 = time.perf_counter()
    try:
        for i in range(6):
            qb.search(np.zeros(4, np.float32), 3)
            time.sleep(0.01)           # the worker idles between requests
        t1 = time.perf_counter()
    finally:
        qb.stop()

    wall = since("worker_wall")[0]
    # async path: d2h_wait, rescore, deliver and finish are the drain's
    worker = sum(since(s)[0] for s in ("idle", "slot_wait", "assemble",
                                       "mask_pack", "launch"))
    assert since("launch")[1] == 6 and since("worker_wall")[1] == 6
    assert worker == pytest.approx(wall, rel=0.01)
    assert since("idle")[0] > 0.04     # five 10-ms pauses
    # and the records' walls cover the time the worker was alive (its
    # last wait, which the stop ended, belongs to no dispatch)
    assert 0.75 * (t1 - t0) < wall <= (t1 - t0) + 0.01
    # the drain's side: one ``finish`` a dispatch, each at least the
    # stub's 2-ms finish step
    assert since("finish")[1] == 6 and since("finish")[0] >= 6 * 0.002


def test_annotations_only_on_the_worker_and_drain_threads(monkeypatch):
    """A profiler annotation is entered for the dispatch stages on the
    batcher's worker and the drain thread, and on NO request thread: the
    benchmark's gap-namer sums an event name's cover over threads."""
    from weaviate_tpu.runtime import tailboard, tracing

    seen = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append((self.name, threading.current_thread().name))

        def __exit__(self, *exc):
            return False

        @staticmethod
        def is_enabled():              # a profiler session is running
            return True

    monkeypatch.setattr(tailboard, "_annotation_cls", Recorder)
    qb, _ = _async_batcher(fin_sleep=0.001)

    def client():
        t = time.perf_counter()
        with tailboard.request("grpc.search", t_entry=t, t_arrival=t), \
                tracing.trace("grpc.Search"):
            # request-thread stages of both kinds of name: neither may
            # reach the profiler from here
            with tracing.span("shard.allow_mask", stage="filter"):
                pass
            with tracing.span("store.mask_pack", stage="mask_pack"):
                pass
            with tailboard.dispatch_stage("launch"):
                pass
            qb.search(np.zeros(4, np.float32), 3)

    try:
        threads = [threading.Thread(target=client, name=f"request-{i}")
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
    finally:
        qb.stop()
    assert {thread for _, thread in seen} == {"query-batcher",
                                              "qb-transfer"}
    by_thread = {}
    for name, thread in seen:
        by_thread.setdefault(thread, set()).add(name)
    assert {"wtpu.idle", "wtpu.assemble", "wtpu.launch"} <= \
        by_thread["query-batcher"]
    assert {"wtpu.finish", "wtpu.d2h_wait", "wtpu.deliver"} <= \
        by_thread["qb-transfer"]
    # the slot wait is the drain's busy time: never annotated
    assert all(name != "wtpu.slot_wait" for name, _ in seen)
    assert all(name.startswith("wtpu.") and name[5:] in
               tailboard.DISPATCH_STAGES for name, _ in seen)


def test_solo_dispatch_is_its_own_record_under_its_own_kind():
    """A highly selective filter goes solo: a dispatch record of its own
    (``<kind>.solo`` in the stage family), read by its one waiter."""
    from weaviate_tpu.runtime import tailboard

    solo = _dispatch_stage_reader("flat.solo")
    coalesced = _dispatch_stage_reader("flat")
    idx, rng = _corpus_index(n=512, dim=16)
    qb = QueryBatcher(idx.search_by_vector_batch,
                      supports_filter_batching=True,
                      capacity_fn=lambda: 512, kind="flat")
    try:
        allow = np.zeros(512, bool)
        allow[:4] = True                         # 4 <= 512 / 64: solo
        t = time.perf_counter()
        with tailboard.request("grpc.search", t_entry=t,
                               t_arrival=t) as tl:
            ids, _ = qb.search(rng.standard_normal(16).astype(np.float32),
                               3, allow=allow)
    finally:
        qb.stop()
    assert set(ids.tolist()) <= {0, 1, 2, 3}
    assert solo("launch")[1] == 1 and solo("launch")[0] > 0
    assert solo("d2h_wait")[1] == 1
    assert coalesced("launch")[1] == 0 and coalesced("assemble")[1] == 1
    assert tl.phases["device"] > 0 and tl.stages["wake"] > 0


def test_gathered_solo_scan_and_full_scan_compile_under_different_names(
        monkeypatch):
    """Programs that differ in role differ in module name: the scan over
    a dense gather of the few allowed rows is
    ``jit_gathered_topk_distances`` on its own and, as the solo path
    runs it since PR 40 (the gathers and the scan in ONE program),
    ``jit_shared_candidates_topk``; the full scan keeps
    ``jit_chunked_topk_distances`` (the benchmark's ``scan_programs``
    pattern), the device-side fold of a shared allow list is
    ``jit_apply_allow_mask``. Naming only: same body, same answers."""
    import jax.numpy as jnp

    from weaviate_tpu.engine.store import apply_allow_mask
    from weaviate_tpu.ops import candidates
    from weaviate_tpu.ops.topk import (chunked_topk_distances,
                                       gathered_topk_distances)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((128, 16)), jnp.float32)
    kw = dict(k=4, chunk_size=128, metric="l2-squared")

    def module(fn, *args, **kwargs):
        return fn.lower(*args, **kwargs).as_text().split("module @")[1] \
            .split()[0]

    assert module(chunked_topk_distances, q, x, **kw) == \
        "jit_chunked_topk_distances"
    assert module(gathered_topk_distances, q, x, **kw) == \
        "jit_gathered_topk_distances"
    assert module(apply_allow_mask, jnp.ones(8, bool),
                  jnp.ones(8, bool)) == "jit_apply_allow_mask"
    full = chunked_topk_distances(q, x, **kw)
    gathered = gathered_topk_distances(q, x, **kw)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(full, gathered))
    # and the store's gathered cutover really runs under the new name:
    # one program, which holds the gathered scan
    calls = []

    def spy(q, slots, rows, k, metric, **kwargs):
        calls.append((candidates.shared_candidates_topk.lower(
            q, slots, rows, k, metric, **kwargs).as_text().split(
                "module @")[1].split()[0], slots.shape))
        return candidates.shared_candidates_topk(q, slots, rows, k, metric,
                                                 **kwargs)

    from weaviate_tpu.engine import store as store_mod

    monkeypatch.setattr(store_mod, "shared_candidates_topk", spy)
    idx, rng = _corpus_index(n=512, dim=16)
    allow = np.zeros(512, bool)
    allow[:4] = True
    idx.store.search(rng.standard_normal((1, 16)).astype(np.float32), 3,
                     allow_mask=allow)
    # the 4 allowed rows' pow2 bucket
    assert calls == [("jit_shared_candidates_topk", (128,))]


# -- the wait for company (PR 32) --------------------------------------------


class _Held:
    """A batcher whose dispatches stay in the transfer window until they
    are released, one event each; ``log`` holds (batch size, launch time)."""

    def __init__(self, **attrs):
        self.log = []
        self.release = []

        def async_fn(queries, k, allow):
            b = len(queries)
            ev = threading.Event()
            self.release.append(ev)
            self.log.append((b, time.perf_counter()))

            def fin():
                assert ev.wait(timeout=10.0)
                return (np.zeros((b, k), np.int64),
                        np.zeros((b, k), np.float32))

            return DeviceResultHandle((), finish=fin)

        self.qb = QueryBatcher(
            lambda *a: (_ for _ in ()).throw(AssertionError("sync path")),
            async_batch_fn=async_fn, pad_pow2=False)
        for name, value in attrs.items():
            setattr(self.qb, name, value)
        self.threads = []
        self.sent = []

    def send(self):
        self.sent.append(time.perf_counter())
        t = threading.Thread(
            target=lambda: self.qb.search(np.zeros(4, np.float32), 3))
        t.start()
        self.threads.append(t)

    def wait_for(self, n_dispatches, timeout=5.0):
        deadline = time.time() + timeout
        while len(self.log) < n_dispatches and time.time() < deadline:
            time.sleep(0.002)
        assert len(self.log) >= n_dispatches, self.log

    def close(self):
        for ev in self.release:
            ev.set()
        deadline = time.time() + 5.0
        while time.time() < deadline and any(
                t.is_alive() for t in self.threads):
            for ev in list(self.release):
                ev.set()
            time.sleep(0.005)
        self.qb.stop()
        for t in self.threads:
            t.join(timeout=5.0)


def _primed(peak, gap_s, **attrs):
    """A held batcher with one request in the window, that remembers
    ``peak`` requests held at once and dispatches that took 2 x gap_s."""
    h = _Held(COALESCE_GAP_MAX_S=gap_s, **attrs)
    h.send()
    h.wait_for(1)
    with h.qb._cv:
        h.qb._peak = float(peak)
    h.qb._flight_s = 2.0 * gap_s
    return h


def test_company_that_can_still_arrive_is_waited_for_and_no_longer():
    """Four held at once of late, one in the window: three can still
    come. The first two wait; the third completes the company and the
    drain leaves at once, one dispatch of three."""
    h = _primed(peak=4, gap_s=0.5)
    try:
        h.send()
        time.sleep(0.1)
        h.send()
        time.sleep(0.1)
        assert len(h.log) == 1, "left without the company it could expect"
        h.send()
        h.wait_for(2)
        assert h.log[1][0] == 3
        assert h.log[1][1] - h.sent[3] < 0.25, "waited past a full company"
    finally:
        h.close()
    assert h.qb._in_window == 0


def test_a_request_nobody_follows_leaves_after_one_gap():
    h = _primed(peak=8, gap_s=0.15)
    try:
        h.send()
        h.wait_for(2)
        waited = h.log[1][1] - h.sent[1]
        assert h.log[1][0] == 1 and 0.12 <= waited < 0.45, waited
    finally:
        h.close()


def test_arrivals_that_never_stop_are_cut_off_after_four_gaps():
    h = _primed(peak=64, gap_s=0.1)
    try:
        t_first = time.perf_counter()
        while len(h.log) < 2 and time.perf_counter() - t_first < 2.0:
            h.send()
            time.sleep(0.05)
        assert len(h.log) == 2
        waited = h.log[1][1] - h.sent[1]
        assert 0.35 <= waited < 0.7 and 5 <= h.log[1][0] <= 12, (
            waited, h.log)
    finally:
        h.close()


def test_enough_company_ends_the_wait():
    h = _primed(peak=64, gap_s=0.5, COALESCE_MIN=4)
    try:
        for _ in range(4):
            h.send()
        h.wait_for(2)
        assert h.log[1][0] == 4 and h.log[1][1] - h.sent[-1] < 0.25
    finally:
        h.close()


@pytest.mark.parametrize("case", ["alone_after_a_crowd", "two_alternate",
                                  "rule_off"])
def test_who_finds_nobody_to_wait_for_leaves_at_once(case):
    """A request that finds the batcher empty goes whatever the past
    held; with two clients that take turns the other one is in the
    window, so nobody can arrive; COALESCE_MIN = 1 is the old rule."""
    if case == "alone_after_a_crowd":
        h = _Held(COALESCE_GAP_MAX_S=0.5)
        with h.qb._cv:
            h.qb._peak = 32.0
        h.qb._flight_s = 1.0
        first = 0
    else:
        h = _primed(peak=2 if case == "two_alternate" else 32, gap_s=0.5,
                    **({"COALESCE_MIN": 1} if case == "rule_off" else {}))
        first = 1
    try:
        h.send()
        h.wait_for(first + 1)
        assert h.log[first][0] == 1
        assert h.log[first][1] - h.sent[first] < 0.2
    finally:
        h.close()


def test_the_window_count_comes_back_to_zero_after_a_fault_and_a_retry():
    """``_in_window`` is what the expectation is reckoned from: a
    dispatch that faults on the transfer thread and is served by the sync
    retry has to leave the window like any other."""
    calls = []

    def async_fn(queries, k, allow):
        b = len(queries)

        def fin():
            raise RuntimeError("device fault")

        return DeviceResultHandle((), finish=fin)

    def sync_fn(queries, k, allow):
        calls.append(len(queries))
        b = len(queries)
        return (np.zeros((b, k), np.int64), np.zeros((b, k), np.float32))

    qb = QueryBatcher(sync_fn, async_batch_fn=async_fn)
    try:
        ids, _ = qb.search(np.zeros(4, np.float32), 3)
        assert ids.shape == (3,) and calls == [1]
        deadline = time.time() + 5.0
        while qb._in_window and time.time() < deadline:
            time.sleep(0.005)
        assert qb._in_window == 0
    finally:
        qb.stop()
