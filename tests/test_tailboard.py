"""Tailboard (ISSUE 15): always-on phase attribution, tail-based trace
retention, the SLO burn engine, and the flight recorder.

The acceptance scenarios run BLACK-BOX over a real RestServer with
``TRACE_SAMPLE_RATE=1000`` (so background sampling effectively never
fires): the requests an operator needs — errored, deadline-exceeded,
fault-slowed — must be retrievable from the tail ring with phase
timings, and a phase-histogram bucket exemplar must resolve to a
retained trace id through the strict exposition parser."""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from test_metrics_exposition import parse_openmetrics  # noqa: E402
from weaviate_tpu.api.client import Client, RestError
from weaviate_tpu.api.rest import DEBUG_ENDPOINTS, RestServer
from weaviate_tpu.db.database import Database
from weaviate_tpu.runtime import degrade, faultline, tailboard, tracing


@pytest.fixture
def served(tmp_path, monkeypatch):
    """Real server, 1-in-1000 sampling (so device sampling effectively
    never fires), tail slow threshold 30ms for graphql."""
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0.001")
    monkeypatch.setenv("WEAVIATE_TPU_TAIL_SLOW_MS",
                       json.dumps({"graphql": 30, "*": 250}))
    tracing.reset_policy_for_tests()
    # the ring of finished traces is the process's: a forced trace of a
    # test that ran earlier in this worker is not this server's
    tracing.clear_traces()
    tailboard.reset_for_tests()
    db = Database(str(tmp_path))
    srv = RestServer(db)
    srv.start()
    client = Client(srv.address)
    client.create_class({"name": "Tail"})
    rng = np.random.default_rng(3)
    for i in range(16):
        client.create_object(
            "Tail", {}, vector=[float(x) for x in
                                rng.standard_normal(8)])
    yield client, srv, db
    srv.stop()
    db.close()
    tracing.reset_policy_for_tests()


def _graphql_search(client, timeout_s: float | None = None):
    q = ('{ Get { Tail(limit: 3, nearVector: {vector: '
         '[0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8]}) '
         '{ _additional { id } } } }')
    path = "/v1/graphql"
    if timeout_s is not None:
        path += f"?timeout={timeout_s}"
    return client.request("POST", path,
                          body={"query": q, "variables": {}})


def _tail_entries(client, reason=None):
    out = client.request("GET", "/v1/debug/traces?tail=true")["traces"]
    return [e for e in out if reason is None or e["reason"] == reason]


def test_tail_retention_under_hostile_sampling(served):
    """An errored, a deadline-exceeded, and a fault-slowed request are
    each kept in the tail ring with phase timings — at 1-in-1000
    sampling — and a bucket exemplar resolves to a retained trace."""
    client, srv, db = served

    # warm the compiled path first (the first search carries XLA compile
    # time and is legitimately tail-kept as slow), then prove a FAST
    # clean request is NOT tail-kept
    for _ in range(2):
        _graphql_search(client)
    tailboard.clear_tail()
    _graphql_search(client)
    assert _tail_entries(client) == []

    # 1. errored: every batcher dispatch faults (the retry too) -> the
    #    search surfaces a 500 through the graphql edge
    faultline.arm("batcher.dispatch", "error", every=1)
    with pytest.raises(RestError) as err:
        _graphql_search(client)
    faultline.disarm()
    assert err.value.status == 500
    errored = _tail_entries(client, "error")
    assert errored, _tail_entries(client)
    assert errored[0]["operation"] == "graphql"
    assert errored[0]["status"] == 500

    # 2. deadline-exceeded: injected dispatch latency far past a tiny
    #    request budget -> typed 504 -> reason "deadline"
    faultline.arm("batcher.dispatch", "latency", latency_s=0.30, every=1)
    with pytest.raises(RestError) as e:
        _graphql_search(client, timeout_s=0.05)
    faultline.disarm()
    assert e.value.status == 504
    deadline = _tail_entries(client, "deadline")
    assert deadline and deadline[0]["operation"] == "graphql"
    assert deadline[0]["status"] == 504

    # 3. fault-injected latency slow request: 60ms injected latency vs
    #    the 30ms graphql threshold -> completes fine, kept as slow,
    #    with the batcher phase split present
    faultline.arm("batcher.dispatch", "latency", latency_s=0.06, every=1)
    resp = _graphql_search(client)
    faultline.disarm()
    assert "errors" not in resp or not resp["errors"]
    slow = _tail_entries(client, "slow")
    assert slow, _tail_entries(client)
    entry = slow[0]
    assert entry["duration_ms"] >= 30
    phases = entry["phases_ms"]
    # the injected latency fired inside the dispatch window -> the
    # always-on "device" phase (dispatch wall) absorbed it, no sync
    assert phases["device"] >= 50, phases
    assert "queue_wait" in phases and "host" in phases
    # the retained entry carries the full trace, trace_id included
    assert entry["trace"] and entry["trace"]["trace_id"]
    assert entry["trace"]["sampled"] is False  # retention beat sampling

    # 4. exemplar resolution: a request_phase_seconds bucket exemplar
    #    names a trace id that IS retrievable from the tail ring
    req = urllib.request.Request(
        f"http://{srv.address}/v1/metrics",
        headers={"Accept": "application/openmetrics-text"})
    parsed = parse_openmetrics(urllib.request.urlopen(req).read().decode())
    exemplar_ids = {
        s["exemplar"]["labels"]["trace_id"]
        for s in parsed["samples"]
        if s["name"] == "weaviate_tpu_request_phase_seconds_bucket"
        and s["exemplar"] is not None}
    assert exemplar_ids
    retained_ids = {e["trace"]["trace_id"] for e in _tail_entries(client)
                    if e.get("trace")}
    assert exemplar_ids & retained_ids

    # 5. the same traces NEVER depended on the sampled ring: with
    #    TRACE_SAMPLE_RATE=1000 none of these were device-sampled
    all_traces = client.request("GET", "/v1/debug/traces")["traces"]
    assert all(not t["sampled"] for t in all_traces)


def test_degraded_request_is_tail_kept(served):
    client, srv, db = served
    # a degraded marker reported during handling flags the timeline
    from weaviate_tpu.api import rest as rest_mod

    orig = srv.dispatch

    def degraded_dispatch(method, path, params, body):
        if path == "/v1/graphql":
            degrade.report("replica_skipped", collection="Tail",
                           detail="test")
        return orig(method, path, params, body)

    srv.dispatch = degraded_dispatch
    try:
        resp = _graphql_search(client)
    finally:
        srv.dispatch = orig
    assert resp.get("degraded")
    entries = _tail_entries(client, "degraded")
    assert entries and entries[0]["operation"] == "graphql"


def test_phase_histogram_always_on(served):
    """Every request lands phase observations — queue_wait/device from
    the batcher stamps, host as the remainder — with collection/tenant
    labels passing the top-K guard."""
    client, srv, db = served
    from weaviate_tpu.runtime.metrics import request_phase_seconds

    _graphql_search(client)
    tailboard.flush()  # a scrape would do this; tests read directly
    child = request_phase_seconds.labels("graphql", "device", "Tail", "-")
    assert child.count >= 1
    host = request_phase_seconds.labels("graphql", "host", "Tail", "-")
    assert host.count >= 1
    wait = request_phase_seconds.labels("graphql", "queue_wait", "Tail",
                                        "-")
    assert wait.count >= 1


def test_debug_index_lists_every_endpoint(served):
    """GET /v1/debug enumerates the debug surface; every listed endpoint
    serves 200; every registered endpoint is listed (the dict drives
    both, and this test pins the round trip)."""
    client, srv, db = served
    index = client.request("GET", "/v1/debug")
    listed = {e["path"] for e in index["endpoints"]}
    assert listed == {f"/v1/debug/{n}" for n in DEBUG_ENDPOINTS}
    for name in DEBUG_ENDPOINTS:
        payload = client.request("GET", f"/v1/debug/{name}")
        assert isinstance(payload, dict), name
    for e in index["endpoints"]:
        assert e["description"].strip()
    # unknown debug routes still 404
    with pytest.raises(RestError) as err:
        client.request("GET", "/v1/debug/nonsense")
    assert err.value.status == 404


def test_flight_recorder_dispatch_records(served):
    client, srv, db = served
    for _ in range(3):
        _graphql_search(client)
    flight = client.request("GET", "/v1/debug/flight")
    recs = [r for r in flight["dispatches"] if r["plane"] == "batcher"]
    assert recs
    r = recs[-1]
    for field in ("batch", "k", "queue_depth", "wait_ms",
                  "window_inflight", "epochs", "seq", "t"):
        assert field in r, r
    assert r["batch"] >= 1 and r["wait_ms"] >= 0
    assert "slowlog" in flight and "snapshots" in flight


def test_slo_engine_end_to_end(tmp_path, monkeypatch):
    """Acceptance: injected latency drives the burn rate over threshold,
    flips the component-health registry, and writes a flight-recorder
    snapshot into the data dir."""
    monkeypatch.setenv("WEAVIATE_TPU_SLO", json.dumps([
        {"slo": "search-latency", "operation": "graphql",
         "kind": "latency", "objective": 0.99, "threshold_ms": 5},
        {"slo": "availability", "operation": "*",
         "kind": "availability", "objective": 0.999},
    ]))
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0.001")
    tracing.reset_policy_for_tests()
    tailboard.reset_for_tests()
    db = Database(str(tmp_path))  # wires the tailboard data dir
    srv = RestServer(db)
    srv.start()
    client = Client(srv.address)
    client.create_class({"name": "Tail"})
    client.create_object("Tail", {},
                         vector=[1.0, 0.0, 0.0, 0.0,
                                 0.0, 0.0, 0.0, 0.0])
    try:
        _graphql_search(client)  # compile warm-up, un-injected
        faultline.arm("batcher.dispatch", "latency", latency_s=0.02,
                      every=1)
        for _ in range(6):
            _graphql_search(client)
        faultline.disarm()
        # the debug endpoint refreshes gauges AND runs the incident sweep
        slo = client.request("GET", "/v1/debug/slo")
        lat = next(s for s in slo["slos"] if s["slo"] == "search-latency")
        fast = f"{int(slo['fastWindowSeconds'])}s"
        assert lat["windows"][fast]["bad"] >= 6
        assert lat["windows"][fast]["burnRate"] >= slo["burnThreshold"]
        assert lat["burning"] is True
        # component-health registry flipped (PR 8 wiring): visible to
        # /v1/nodes consumers through degrade.health()
        health = degrade.health()
        assert "slo:search-latency" in health["unhealthy"]
        assert "burn rate" in \
            health["unhealthy"]["slo:search-latency"]["reason"]
        # burn gauge republished over threshold
        from weaviate_tpu.runtime.metrics import slo_burn_rate

        g = slo_burn_rate.labels("search-latency", fast)
        assert g.value >= slo["burnThreshold"]
        # flight-recorder snapshot written into the data dir
        snapdir = os.path.join(str(tmp_path), "flightrecorder")
        assert os.path.isdir(snapdir)
        snaps = [f for f in os.listdir(snapdir) if f.endswith(".json")]
        assert snaps
        with open(os.path.join(snapdir, sorted(snaps)[-1])) as f:
            snap = json.load(f)
        assert snap["reason"] == "slo:search-latency"
        assert any(r["plane"] == "batcher" for r in snap["dispatches"])
        assert snap["componentHealth"]["unhealthy"]
        # availability SLO stayed clean: injected latency, not errors
        avail = next(s for s in slo["slos"] if s["slo"] == "availability")
        assert avail["burning"] is False
        # recovery: fast traffic drains the bad fraction -> healthy again
        eng = tailboard.slo_engine()
        obj = next(o for o in eng._load()
                   if o.name == "search-latency")
        bucket = int(time.monotonic() // tailboard._BUCKET_S)
        for _ in range(4000):
            obj.record(bucket, True, eng.horizon_buckets())
        eng.refresh()
        assert "slo:search-latency" not in degrade.health()["unhealthy"]
    finally:
        faultline.disarm()
        srv.stop()
        db.close()
        tracing.reset_policy_for_tests()


def test_component_flip_writes_snapshot(tmp_path):
    tailboard.reset_for_tests()
    tailboard.set_data_dir(str(tmp_path))
    tailboard.record_dispatch("batcher", batch=4, k=16, queue_depth=0,
                              wait_ms=0.1, window_inflight=0, epochs=0)
    degrade.mark_unhealthy("query_batcher:test", "dispatch failed twice")
    try:
        snapdir = os.path.join(str(tmp_path), "flightrecorder")
        snaps = os.listdir(snapdir)
        assert snaps
        with open(os.path.join(snapdir, snaps[0])) as f:
            snap = json.load(f)
        assert snap["reason"] == "component:query_batcher:test"
        assert snap["dispatches"][0]["batch"] == 4
        # the cooldown suppresses a flapping component's snapshot spam
        degrade.mark_healthy("query_batcher:test")
        degrade.mark_unhealthy("query_batcher:test", "again")
        assert len(os.listdir(snapdir)) == len(snaps)
    finally:
        degrade.mark_healthy("query_batcher:test")


def test_mapped_client_error_is_not_an_availability_failure():
    """The gRPC edge maps 4xx then context.abort() raises through the
    timeline CM — a handled client error must neither count against the
    availability SLO nor be tail-kept as 'error'."""
    tailboard.reset_for_tests()
    with pytest.raises(RuntimeError):
        with tailboard.request("grpc.search"):
            tailboard.complete(404)
            raise RuntimeError("abort control flow")
    assert tailboard.tail_traces() == []
    tailboard.flush()
    eng = tailboard.slo_engine()
    avail = next(o for o in eng._load() if o.kind == "availability")
    bucket = int(time.monotonic() // tailboard._BUCKET_S)
    good, bad = avail.window_counts(bucket, 60)
    assert (good, bad) == (1.0, 0.0)
    # an UNMAPPED exception (no complete()) still counts as an error
    with pytest.raises(RuntimeError):
        with tailboard.request("grpc.search"):
            raise RuntimeError("unhandled")
    assert tailboard.tail_traces()[0]["reason"] == "error"
    tailboard.flush()
    good, bad = avail.window_counts(bucket, 60)
    assert bad == 1.0


# -- unit-level pieces --------------------------------------------------------


def test_label_guard_top_k():
    g = tailboard.LabelGuard(2)
    assert g.clamp("a") == "a"
    assert g.clamp("b") == "b"
    assert g.clamp("c") == "other"
    assert g.clamp("a") == "a"  # established values keep their series
    assert g.clamp(None) == "-"
    assert g.clamp("") == "-"


def test_slow_threshold_per_operation(monkeypatch):
    monkeypatch.setenv("WEAVIATE_TPU_TAIL_SLOW_MS",
                       json.dumps({"grpc.*": 40, "objects": 10}))
    tailboard.reset_for_tests()
    assert tailboard.slow_threshold_s("objects") == pytest.approx(0.010)
    assert tailboard.slow_threshold_s("grpc.search") == pytest.approx(0.040)
    assert tailboard.slow_threshold_s("schema") == pytest.approx(0.250)
    monkeypatch.setenv("WEAVIATE_TPU_TAIL_SLOW_MS", "75")
    tailboard.reset_for_tests()
    assert tailboard.slow_threshold_s("anything") == pytest.approx(0.075)


def test_timeline_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("WEAVIATE_TPU_TAILBOARD", "0")
    tailboard.reset_for_tests()
    with tailboard.request("objects") as tl:
        assert tl is None
        tailboard.phase("device", 1.0)  # no live timeline: dropped
        tailboard.complete(500)
    assert tailboard.tail_traces() == []


def test_standalone_trace_slow_is_tail_kept(monkeypatch):
    """Direct tracing users (no edge timeline) still get tail-kept when
    slow — on_trace_complete's standalone path."""
    monkeypatch.setenv("WEAVIATE_TPU_TAIL_SLOW_MS", "1")
    tailboard.reset_for_tests()
    with tracing.trace("bulk.rebuild"):
        time.sleep(0.01)
    kept = tailboard.tail_traces()
    assert kept and kept[0]["reason"] == "slow"
    assert kept[0]["operation"] == "bulk.rebuild"


def test_flight_ring_wraps_and_orders():
    ring = tailboard.FlightRing(8)
    for i in range(20):
        ring.append({"i": i})
    snap = ring.snapshot()
    assert len(snap) == 8
    assert [r["i"] for r in snap] == list(range(12, 20))


# -- stages: one clock from the wire to the reply (ISSUE 25) ------------------


@pytest.fixture
def grpc_search(tmp_path, request):
    """A real GrpcServer over a socket and a unary Search callable; the
    collection has one shard, or as many as the test's parameter says."""
    import grpc

    from weaviate_tpu.api.grpc import v1_pb2 as pb
    from weaviate_tpu.api.grpc.server import GrpcServer
    from weaviate_tpu.schema.config import (CollectionConfig, Property,
                                            ShardingConfig)

    db = Database(str(tmp_path))
    db.create_collection(CollectionConfig(name="Stage", properties=[
        Property(name="bucket", data_type="int")],
        sharding=ShardingConfig(desired_count=getattr(request, "param", 1))))
    col = db.get_collection("Stage")
    rng = np.random.default_rng(5)
    for i in range(64):
        col.put_object({"bucket": i % 10},
                       vector=rng.standard_normal(8).astype(np.float32))
    server = GrpcServer(db).start()
    channel = grpc.insecure_channel(f"127.0.0.1:{server.port}")
    call = channel.unary_unary(
        "/weaviate.v1.Weaviate/Search",
        request_serializer=pb.SearchRequest.SerializeToString,
        response_deserializer=pb.SearchReply.FromString)

    def search(filtered: bool):
        req = pb.SearchRequest(collection="Stage", limit=3,
                               uses_123_api=True)
        req.near_vector.vector_bytes = rng.standard_normal(8).astype(
            "<f4").tobytes()
        req.metadata.uuid = True
        if filtered:
            req.filters.operator = pb.Filters.OPERATOR_LESS_THAN
            req.filters.target.property = "bucket"
            req.filters.value_int = 5
        assert len(call(req).results) == 3

    yield search
    channel.close()
    server.stop()
    db.close()


def _stage(operation, stage):
    from weaviate_tpu.runtime.metrics import request_stage_seconds

    return request_stage_seconds.labels(operation, stage)


def test_every_stage_of_a_grpc_search_is_observed_once_and_sums(grpc_search):
    """Over a real socket: every stage is observed once per Search (zero
    included), the additive stages sum to ``server_residency``, and the
    four phases sum to the timeline's duration exactly as before the
    stages existed."""
    from weaviate_tpu.runtime.metrics import request_phase_seconds

    every = tailboard.REQUEST_STAGES + tailboard.REQUEST_EXTRAS

    def read():
        """(count, total) by stage, and the four phases' total: the
        registry's series live as long as the process, so deltas."""
        tailboard.flush()
        stages = {s: (_stage("grpc.search", s).count,
                      _stage("grpc.search", s).total) for s in every}
        host = request_phase_seconds.labels("grpc.search", "host",
                                            "Stage", "-")
        return stages, host.count, sum(request_phase_seconds.labels(
            "grpc.search", p, "Stage", "-").total
            for p in tailboard.PHASES)

    base, base_n, base_phases = read()
    fanout_base = [_stage("grpc.search", s).count
                   for s in tailboard.FANOUT_STAGES]
    n = 12
    for i in range(n):
        grpc_search(filtered=i % 3 == 0)
    # the record is whole at the RPC's termination, which the server
    # sees a moment after the client has its reply
    deadline = time.time() + 10.0
    while time.time() < deadline:
        now, now_n, now_phases = read()
        if now["server_residency"][0] - base["server_residency"][0] == n:
            break
        time.sleep(0.02)
    assert {s: now[s][0] - base[s][0] for s in every} == \
        {s: n for s in every}
    total = {s: now[s][1] - base[s][1] for s in every}
    assert sum(total[s] for s in tailboard.REQUEST_STAGES) == \
        pytest.approx(total["server_residency"], rel=1e-9)
    # a one-shard request observes neither stage of a fan-out
    assert fanout_base == [_stage("grpc.search", s).count
                           for s in tailboard.FANOUT_STAGES]
    assert total["filter"] > 0 and total["fetch"] > 0
    assert total["queue_wait"] > 0 and total["device"] > 0
    assert total["pool_wait"] > 0 and total["send"] > 0
    assert 0 < total["handler_cpu"] < total["server_residency"]
    # the phases' clock starts where it did before the stages existed,
    # below the handler's metadata and deadline preamble: what the
    # handler's wall time holds beyond them is that preamble, the first
    # part of ``parse``
    handler = total["server_residency"] - total["pool_wait"] - total["send"]
    phases = now_phases - base_phases
    assert 0.0 <= handler - phases <= total["parse"]
    # the phase series, with its collection label, saw the same requests
    assert now_n - base_n == n


@pytest.mark.parametrize("grpc_search", [4], indirect=True)
def test_a_fanned_out_search_stays_additive(grpc_search):
    """Four local shards: the request is charged ONE queue_wait, device
    and transfer (its critical path's), observes ``fanout_wait`` and
    ``merge`` once each, and the stages, those two included, sum to
    ``server_residency`` as a one-shard request's do without them."""
    from weaviate_tpu.runtime.metrics import (fanout_shards_total,
                                              fanout_width,
                                              request_phase_seconds)

    every = tailboard.REQUEST_STAGES + tailboard.FANOUT_STAGES \
        + tailboard.REQUEST_EXTRAS

    def read():
        tailboard.flush()
        return ({s: (_stage("grpc.search", s).count,
                     _stage("grpc.search", s).total) for s in every},
                {p: request_phase_seconds.labels(
                    "grpc.search", p, "Stage", "-").count
                 for p in ("queue_wait", "device", "host")})

    base, base_phases = read()
    shards = fanout_shards_total.labels("Stage").value
    widths = fanout_width.labels().count
    n = 10
    for i in range(n):
        grpc_search(filtered=i % 2 == 0)
    deadline = time.time() + 10.0
    while time.time() < deadline:
        now, now_phases = read()
        if now["server_residency"][0] - base["server_residency"][0] == n:
            break
        time.sleep(0.02)
    assert {s: now[s][0] - base[s][0] for s in every} == \
        {s: n for s in every}
    assert {p: now_phases[p] - base_phases[p] for p in now_phases} == \
        {p: n for p in now_phases}      # one a request, not one a shard
    total = {s: now[s][1] - base[s][1] for s in every}
    assert sum(total[s] for s in tailboard.REQUEST_STAGES
               + tailboard.FANOUT_STAGES) == \
        pytest.approx(total["server_residency"], rel=1e-9)
    assert total["fanout_wait"] > 0 and total["merge"] > 0
    assert total["queue_wait"] > 0 and total["device"] > 0
    assert total["search_other"] > 0    # the glue: not swallowed, not negative
    assert fanout_shards_total.labels("Stage").value - shards == 4 * n
    assert fanout_width.labels().count - widths == n


class _Clock:
    """A stated clock in tailboard's place of ``time``."""

    def __init__(self):
        self.now = 100.0
        self.cpu = 7.0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        return self.cpu

    monotonic = staticmethod(time.monotonic)
    time = staticmethod(time.time)


class _Ctx:
    def add_callback(self, cb):
        self.cb = cb
        return True


def _phase_totals(operation):
    from weaviate_tpu.runtime.metrics import request_phase_seconds

    tailboard.flush()
    return {p: request_phase_seconds.labels(operation, p, "-", "-").total
            for p in tailboard.PHASES}


def test_stages_from_stated_stamps_and_phases_unchanged(monkeypatch):
    """The same stamps through a staged and an unstaged timeline: the
    phases are the same to the digit (``host`` stays the handler's
    remainder), and each stage is what its stamps say."""
    clock = _Clock()
    monkeypatch.setattr(tailboard, "time", clock)

    def drive(operation, staged, fanned=False):
        clock.now, clock.cpu = 100.002, 7.0     # handler entry
        ctx = _Ctx()
        with tailboard.request(
                operation, t_entry=clock.now if staged else None,
                t_arrival=100.000 if staged else None) as tl:
            if staged:
                tl.defer_to(ctx)
            clock.now = 100.003
            tailboard.mark("parse")
            tailboard.request_stage("filter", 0.0005)
            tailboard.phase("queue_wait", 0.001)
            tailboard.phase("device", 0.004)
            tailboard.request_stage("wake", 0.0005)
            tailboard.request_stage("fetch", 0.001)
            if fanned:
                tailboard.fanout(0.0012, 0.0003)
            clock.now = 100.012
            tailboard.mark("search")
            tailboard.complete(200)
            clock.now, clock.cpu = 100.013, 7.0015  # handler return
        clock.now = 100.015                          # RPC termination
        if staged:
            ctx.cb()

    drive("op.staged", True)
    drive("op.plain", False)
    staged, plain = _phase_totals("op.staged"), _phase_totals("op.plain")
    assert staged == plain
    assert plain["host"] == pytest.approx(0.011 - 0.005)
    got = {s: _stage("op.staged", s).total
           for s in tailboard.REQUEST_STAGES + tailboard.REQUEST_EXTRAS}
    want = {"pool_wait": 0.002, "parse": 0.001, "filter": 0.0005,
            "queue_wait": 0.001, "device": 0.004, "transfer": 0.0,
            "wake": 0.0005, "fetch": 0.001,
            "search_other": 0.009 - 0.007, "reply": 0.001, "send": 0.002,
            "handler_cpu": 0.0015, "server_residency": 0.015,
            # the handler's wall (0.015 less pool_wait and send) less the
            # waits it was meant to make (0.005) less its CPU
            "off_cpu": 0.011 - 0.005 - 0.0015}
    assert got == pytest.approx(want, abs=1e-9)
    assert _stage("op.plain", "parse").count == 0   # unstaged: no stages
    assert _stage("op.staged", "fanout_wait").count == 0
    # the same stamps with a fan-out's two stages: they come out of
    # ``search_other`` and nothing else moves
    drive("op.fanned", True, fanned=True)
    assert _phase_totals("op.fanned") == plain
    got = {s: _stage("op.fanned", s).total
           for s in tailboard.REQUEST_STAGES + tailboard.FANOUT_STAGES
           + tailboard.REQUEST_EXTRAS}
    assert got == pytest.approx(dict(
        want, fanout_wait=0.0012, merge=0.0003,
        search_other=0.009 - 0.007 - 0.0015), abs=1e-9)
    assert _stage("op.fanned", "merge").count == 1


def test_termination_before_the_handler_returns_gives_send_zero(
        monkeypatch):
    """A cancelled RPC terminates while its handler still runs: the
    record is pushed by the handler's return, ``send`` is 0 and the
    residency ends at the return."""
    clock = _Clock()
    monkeypatch.setattr(tailboard, "time", clock)
    ctx = _Ctx()
    with tailboard.request("op.cancelled", t_entry=100.0,
                           t_arrival=100.0) as tl:
        tl.defer_to(ctx)
        clock.now = 100.004
        ctx.cb()                    # termination first
        tailboard.flush()
        assert _stage("op.cancelled", "send").count == 0  # not yet whole
        clock.now = 100.010
        tailboard.complete(499)
    tailboard.flush()
    assert _stage("op.cancelled", "send").count == 1
    assert _stage("op.cancelled", "send").total == 0.0
    assert _stage("op.cancelled", "server_residency").total == \
        pytest.approx(0.010)
