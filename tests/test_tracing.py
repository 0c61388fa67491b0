"""Request-scoped tracing (ISSUE 2 tentpole): span nesting, sampling,
cross-thread propagation through the query batcher, traceparent
stitching over the in-proc cluster transport, and the REST surface
(/v1/debug/traces, ?trace=true, per-query _debug.timing)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from weaviate_tpu.runtime import tracing


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.clear_traces()
    yield
    tracing.clear_traces()


def _spans(trace_dict, name):
    return [s for s in trace_dict["spans"] if s["name"] == name]


# -- core ---------------------------------------------------------------------

def test_span_is_noop_outside_trace():
    with tracing.span("anything", x=1) as sp:
        assert sp is tracing.NULL_SPAN
        sp.set(y=2)  # must not raise
    assert tracing.recent_traces() == []
    assert not tracing.is_active()


def test_nesting_and_parent_chain():
    with tracing.trace("root", force=True):
        with tracing.span("a", k=10):
            with tracing.span("b"):
                pass
        with tracing.span("c"):
            pass
    t = tracing.recent_traces(1)[0]
    by_name = {s["name"]: s for s in t["spans"]}
    assert set(by_name) == {"root", "a", "b", "c"}
    assert by_name["root"]["parent_id"] is None
    assert by_name["a"]["parent_id"] == by_name["root"]["span_id"]
    assert by_name["b"]["parent_id"] == by_name["a"]["span_id"]
    assert by_name["c"]["parent_id"] == by_name["root"]["span_id"]
    assert by_name["a"]["attrs"]["k"] == 10
    # spans feed the /metrics histogram
    from weaviate_tpu.runtime.metrics import span_duration

    assert span_duration.labels("a").count >= 1


def test_nested_trace_degrades_to_span():
    with tracing.trace("outer", force=True):
        with tracing.trace("inner"):
            pass
    traces = tracing.recent_traces()
    assert len(traces) == 1
    assert {s["name"] for s in traces[0]["spans"]} == {"outer", "inner"}


def test_sampling_gates_device_sync(monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0")
    tracing.reset_policy_for_tests()
    calls = []

    import jax

    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: calls.append(1) or real(v))
    import jax.numpy as jnp

    x = jnp.arange(4)
    with tracing.trace("unsampled") as root:
        tracing.device_sync(root, x)
    assert not calls  # no device synchronization off-sample
    with tracing.trace("forced", force=True) as root:
        tracing.device_sync(root, x)
    assert calls
    t = tracing.recent_traces(1)[0]
    assert "device_ms" in _spans(t, "forced")[0]["attrs"]
    tracing.reset_policy_for_tests()


def test_sample_rate_one_in_n(monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0.5")
    tracing.reset_policy_for_tests()
    decisions = [tracing.should_sample() for _ in range(10)]
    assert decisions.count(True) == 5
    tracing.reset_policy_for_tests()


def test_propagate_into_worker_threads():
    out = {}

    def work():
        with tracing.span("worker.side"):
            out["active"] = tracing.is_active()

    with tracing.trace("root", force=True):
        t = threading.Thread(target=tracing.propagate(work))
        t.start()
        t.join()
    assert out["active"]
    tr = tracing.recent_traces(1)[0]
    assert _spans(tr, "worker.side")


def test_record_span_and_slow_query_log(monkeypatch, caplog):
    monkeypatch.setenv("QUERY_SLOW_LOG_ENABLED", "true")
    monkeypatch.setenv("QUERY_SLOW_LOG_THRESHOLD", "1ms")
    tracing.reset_policy_for_tests()
    import logging

    with caplog.at_level(logging.WARNING, "weaviate_tpu.slow_query"):
        with tracing.trace("slow.root"):
            t0 = time.perf_counter()
            time.sleep(0.01)
            tracing.record_span("external.bit", t0, time.perf_counter(),
                                batch=3)
    tr = tracing.recent_traces(1)[0]
    assert _spans(tr, "external.bit")[0]["attrs"]["batch"] == 3
    # the slow-root log is STRUCTURED (ISSUE 15 satellite): one line,
    # machine-parseable, same record that lands in the flight recorder's
    # slowlog ring
    slow = [r.message for r in caplog.records
            if r.message.startswith("slow_query ")]
    assert slow, [r.message for r in caplog.records]
    import json

    rec = json.loads(slow[0].split(" ", 1)[1])
    assert rec["root"] == "slow.root"
    assert rec["trace_id"] == tr["trace_id"]
    assert rec["duration_ms"] >= rec["threshold_ms"] == 1.0
    assert any(s["name"] == "external.bit" for s in rec["spans"])
    from weaviate_tpu.runtime import tailboard

    entries = tailboard.debug_flight()["slowlog"]
    assert any(e["trace_id"] == tr["trace_id"] for e in entries)
    tracing.reset_policy_for_tests()


# -- query batcher cross-thread split ----------------------------------------

def test_batcher_wait_execute_split_lands_in_trace():
    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    def batch_fn(queries, k, allow):
        time.sleep(0.002)
        b = len(queries)
        return (np.zeros((b, k), np.int64),
                np.zeros((b, k), np.float32))

    qb = QueryBatcher(batch_fn)
    try:
        with tracing.trace("req", force=True):
            qb.search(np.zeros(4, np.float32), k=3)
        tr = tracing.recent_traces(1)[0]
        waits = _spans(tr, "batcher.wait")
        execs = _spans(tr, "batcher.execute")
        assert waits and execs
        assert execs[0]["attrs"]["batch"] >= 1
        assert execs[0]["duration_ms"] >= 1.0
    finally:
        qb.stop()


def test_batcher_coalesced_waiters_each_record_their_split():
    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    release = threading.Event()
    calls = []

    def batch_fn(queries, k, allow):
        calls.append(len(queries))
        if len(calls) == 1:
            release.wait(5)  # hold the device so followers coalesce
        b = len(queries)
        return (np.zeros((b, k), np.int64),
                np.zeros((b, k), np.float32))

    qb = QueryBatcher(batch_fn)
    results = []

    def one():
        with tracing.trace("req", force=False):
            qb.search(np.zeros(4, np.float32), k=2)
        results.append(1)

    try:
        threads = [threading.Thread(target=one) for _ in range(4)]
        threads[0].start()
        time.sleep(0.05)
        for t in threads[1:]:
            t.start()
        time.sleep(0.05)
        release.set()
        for t in threads:
            t.join(5)
        assert len(results) == 4
        traces = tracing.recent_traces(10)
        batches = [_spans(t, "batcher.execute")[0]["attrs"]["batch"]
                   for t in traces if _spans(t, "batcher.execute")]
        assert len(batches) == 4
        assert max(batches) >= 2  # followers coalesced into one dispatch
    finally:
        release.set()
        qb.stop()


def test_coalesced_dispatch_spans_are_derived_from_its_record():
    """Every waiter's spans come from the ONE dispatch record: the
    followers' waits end at the same stamp, and a filtered dispatch
    carries ``batcher.assemble`` where ``batcher.mask_pack`` used to
    misname the copy of the query block."""
    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    release = threading.Event()
    calls = []

    def batch_fn(queries, k, allow):
        calls.append(len(queries))
        if len(calls) == 1:
            release.wait(5)
        b = len(queries)
        return (np.zeros((b, k), np.int64), np.zeros((b, k), np.float32))

    qb = QueryBatcher(batch_fn, supports_filter_batching=True)
    t_ref = time.perf_counter()
    ends = {}

    def one(i):
        with tracing.trace("req"):
            qb.search(np.zeros(4, np.float32), k=2,
                      allow=np.ones(8, bool) if i else None)
            tr = tracing.capture()[0]
        # absolute end of this waiter's queue wait, on one clock
        wait = _spans(tr.to_dict(), "batcher.wait")[0]
        ends[i] = (tr._t0 - t_ref) * 1000.0 + wait["start_ms"] \
            + wait["duration_ms"]

    try:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(4)]
        threads[0].start()
        time.sleep(0.05)
        for t in threads[1:]:
            t.start()
        time.sleep(0.05)
        release.set()
        for t in threads:
            t.join(5)
        traces = tracing.recent_traces(10)
        assert all(not _spans(t, "batcher.mask_pack") for t in traces)
        # the three filtered followers rode one dispatch; the unfiltered
        # first request's dispatch assembled no allow lists
        assert sum(1 for t in traces
                   if _spans(t, "batcher.assemble")) == 3
        # their waits end at the same stamp (the record's ``exec``), to
        # the rounding of a span's start and duration
        followers = sorted(ends.values())[1:]
        assert followers[-1] - followers[0] < 0.01
    finally:
        release.set()
        qb.stop()


def test_staged_span_stamps_once_for_trace_and_timeline():
    """A span site that is also a stage hands ONE pair of stamps to the
    trace and to the request's timeline: the two durations are the same
    number, and the stage is recorded with or without a trace."""
    from weaviate_tpu.runtime import tailboard

    t = time.perf_counter()
    with tailboard.request("grpc.search", t_entry=t, t_arrival=t) as tl:
        with tracing.trace("req"):
            with tracing.span("shard.allow_mask", stage="filter",
                              shard="s"):
                time.sleep(0.003)
        traced = tl.stages["filter"]
        with tracing.span("objects.fetch", stage="fetch") as sp:
            time.sleep(0.001)             # no trace: the stage alone
        assert sp is tracing.NULL_SPAN
    span = _spans(tracing.recent_traces(1)[0], "shard.allow_mask")[0]
    assert span["attrs"] == {"shard": "s"}     # ``stage`` is no attribute
    assert span["duration_ms"] == round(traced * 1000.0, 3) >= 3.0
    assert tl.stages["fetch"] >= 0.001
    # outside a staged timeline the site is a plain span, or nothing
    with tracing.span("shard.allow_mask", stage="filter") as sp:
        pass
    assert sp is tracing.NULL_SPAN
    with tailboard.request("rest.search") as plain:
        with tracing.span("shard.allow_mask", stage="filter"):
            pass
    assert plain.stages is None


# -- traceparent over the in-proc transport -----------------------------------

def test_traceparent_round_trip():
    header = tracing.current_traceparent()
    assert header is None
    with tracing.trace("root", force=True):
        header = tracing.current_traceparent()
    tid, parent, sampled = tracing.parse_traceparent(header)
    assert sampled and len(tid) == 32 and len(parent) == 16
    assert tracing.parse_traceparent("garbage") is None
    assert tracing.parse_traceparent(None) is None


def test_remote_segment_stitches_over_transport():
    from weaviate_tpu.cluster.transport import InternalServer, rpc

    srv = InternalServer()

    def handler(payload):
        with tracing.span("remote.work"):
            pass
        return {"ok": True}

    srv.route("/t", handler)
    srv.start()
    try:
        with tracing.trace("root", force=True):
            assert rpc(srv.address, "/t", {})["ok"]
            tid = tracing.current_trace_id()
        tr = tracing.recent_traces(1)[0]
        assert tr["trace_id"] == tid
        remote = [s for s in tr["spans"] if s["attrs"].get("remote")]
        assert {"rpc.server", "remote.work"} <= {s["name"]
                                                for s in remote}
        # the adopted segment chains into the caller's rpc.client span
        by_id = {s["span_id"]: s for s in tr["spans"]}
        server_span = [s for s in remote if s["name"] == "rpc.server"][0]
        assert by_id[server_span["parent_id"]]["name"] == "rpc.client"
    finally:
        srv.stop()


def test_remote_segment_without_header_is_plain_span():
    from weaviate_tpu.cluster.transport import InternalServer, rpc

    srv = InternalServer()
    srv.route("/t", lambda payload: {"ok": True})
    srv.start()
    try:
        # no active trace on the caller: no traceparent sent, handler
        # records nothing, nothing breaks
        assert rpc(srv.address, "/t", {})["ok"]
        assert tracing.recent_traces() == []
    finally:
        srv.stop()


# -- REST surface -------------------------------------------------------------

@pytest.fixture
def rest(tmp_path):
    from weaviate_tpu.api.rest import RestServer, config_from_json
    from weaviate_tpu.db.database import Database

    db = Database(str(tmp_path))
    db.create_collection(config_from_json({
        "class": "Doc",
        "properties": [{"name": "t", "dataType": ["text"]}]}))
    col = db.get_collection("Doc")
    for i in range(40):
        col.put_object({"t": f"doc {i}"},
                       vector=[float(i), 1.0, 2.0, 3.0])
    srv = RestServer(db)
    srv.start()
    yield f"http://{srv.address}"
    srv.stop()
    db.close()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req).read())


GQL = {"query": '{ Get { Doc(nearVector: {vector: [1.0,1.0,2.0,3.0]}, '
                'limit: 3) { t _additional { id distance } } } }'}


def test_rest_trace_true_yields_full_trace(rest):
    out = _post(rest + "/v1/graphql?trace=true", GQL)
    assert out["data"]["Get"]["Doc"]
    dbg = out["_debug"]
    assert dbg["traceId"] and dbg["timing"]

    traces = json.loads(urllib.request.urlopen(
        rest + "/v1/debug/traces?limit=10").read())["traces"]
    mine = [t for t in traces if t["trace_id"] == dbg["traceId"]]
    assert len(mine) == 1
    t = mine[0]
    assert t["sampled"]
    names = [s["name"] for s in t["spans"]]
    # acceptance: >= 6 nested spans across the layers
    assert len(names) >= 6, names
    for expected in ("query.vector", "shard.vector_search", "store.scan",
                     "objects.fetch"):
        assert expected in names, names
    # device time measured (block_until_ready) on the sampled request
    assert any("device_ms" in s["attrs"] for s in t["spans"]), t["spans"]


def test_probe_routes_do_not_flood_the_ring(rest):
    from weaviate_tpu.api.rest import _route_class

    # route-class canonicalization: scanned URLs can't mint new
    # span_duration label values
    assert _route_class("/v1/objects/Doc/abc") == "objects"
    assert _route_class("/v1/%2e%2e/etc/passwd") == "unmatched"
    assert _route_class("/secret/paths") == "unmatched"
    assert _route_class("/.well-known/ready") == ".well-known"

    tracing.clear_traces()
    for _ in range(3):  # health probes + meta + metrics scrapes
        urllib.request.urlopen(rest + "/v1/.well-known/ready")
        urllib.request.urlopen(rest + "/v1/meta")
        urllib.request.urlopen(rest + "/v1/metrics")
        urllib.request.urlopen(rest + "/v1/debug/traces")
    traces = json.loads(urllib.request.urlopen(
        rest + "/v1/debug/traces?limit=50").read())["traces"]
    assert traces == []  # none of the probe traffic entered the ring
    # but a real query still does
    _post(rest + "/v1/graphql", GQL)
    traces = json.loads(urllib.request.urlopen(
        rest + "/v1/debug/traces?limit=50").read())["traces"]
    assert len(traces) == 1
    assert traces[0]["spans"][0]["name"] == "rest.POST /graphql"


def test_rest_unsampled_has_no_debug_and_no_device_sync(rest):
    out = _post(rest + "/v1/graphql", GQL)
    assert "_debug" not in out
    traces = json.loads(urllib.request.urlopen(
        rest + "/v1/debug/traces?limit=1").read())["traces"]
    t = traces[0]
    assert not t["sampled"]
    assert not any("device_ms" in s["attrs"] for s in t["spans"])
