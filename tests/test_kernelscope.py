"""Kernelscope (ISSUE 17): zero-sync device-time truth.

Four faces, each pinned:

1. per-dispatch chip timing WITHOUT host sync — the drain-thread window
   minus the sampled-memcpy EWMA populates the device phase on every
   request (source "drain"), and degrades to the dispatch wall window
   (source "wall") on sync/null-device paths instead of crashing or
   reporting zeros;
2. per-query EXPLAIN — ``?explain=true`` (REST) / ``x-explain`` (gRPC)
   threads a sink through batcher -> engine and returns a structured
   plan; emission sites pass host scalars only, so the G1 baseline for
   the dispatch path stays EMPTY (pinned below);
3. per-tenant device metering — apportioned dispatch residency summed
   over tenants reproduces the total within 5%;
4. on-demand kernel profiles — ``/v1/debug/profile?ms=N`` ranks trace
   events through the kernel registry and persists/prunes captures.

Plus the PROFILING_PORT satellite: port 0 (the default) must NEVER
start the jax profiler server.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from weaviate_tpu.api.client import Client, RestError
from weaviate_tpu.api.rest import DEBUG_ENDPOINTS, RestServer
from weaviate_tpu.config import ServerConfig
from weaviate_tpu.db.database import Database
from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.ivf import IVFIndex
from weaviate_tpu.runtime import kernelscope
from weaviate_tpu.runtime.query_batcher import QueryBatcher, _Pending
from weaviate_tpu.runtime.transfer import DeviceResultHandle

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


# -- face 1 units: estimator / attribution / apportionment --------------------


def test_memcpy_estimator_fallback_chain():
    """bucket EWMA -> global EWMA -> 0.0; no sampled trace yet means the
    full window attributes to device (the pre-kernelscope behavior)."""
    assert kernelscope.memcpy_estimate(4096) == 0.0
    assert kernelscope.attribute(0.01, 4096) == (0.01, 0.0)

    kernelscope.observe_memcpy(0.002, 4096)
    # same pow2 bucket (bit_length 13) hits the bucket EWMA
    assert kernelscope.memcpy_estimate(4096) == pytest.approx(0.002)
    assert kernelscope.memcpy_estimate(5000) == pytest.approx(0.002)
    # unseen bucket falls back to the global EWMA, not zero
    assert kernelscope.memcpy_estimate(1) == pytest.approx(0.002)
    # EWMA, not last-sample: alpha 0.2
    kernelscope.observe_memcpy(0.004, 4096)
    assert kernelscope.memcpy_estimate(4096) == pytest.approx(
        0.2 * 0.004 + 0.8 * 0.002)
    # negative inputs are ignored, not folded in
    kernelscope.observe_memcpy(-1.0, 4096)
    assert kernelscope.memcpy_estimate(4096) == pytest.approx(0.0024)


def test_attribute_clamps_into_window():
    """A memcpy estimate larger than the window must clamp: both parts
    non-negative, summing exactly to the window."""
    kernelscope.observe_memcpy(0.05, 1024)
    dev, mem = kernelscope.attribute(0.01, 1024)
    assert dev == 0.0 and mem == pytest.approx(0.01)
    dev, mem = kernelscope.attribute(-5.0, 1024)
    assert (dev, mem) == (0.0, 0.0)
    dev, mem = kernelscope.attribute(0.2, 1024)
    assert dev + mem == pytest.approx(0.2)
    assert mem == pytest.approx(0.05)


def test_result_nbytes_walks_pytrees():
    ids = np.zeros((4, 8), np.int64)
    dists = np.zeros((4, 8), np.float32)
    assert kernelscope.result_nbytes((ids, dists)) == \
        ids.nbytes + dists.nbytes
    assert kernelscope.result_nbytes([(ids,), [dists, None], 7]) == \
        ids.nbytes + dists.nbytes
    assert kernelscope.result_nbytes(None) == 0


def test_apportion_shares_sum_exactly():
    shares = kernelscope.apportion(0.9, [1.0, 2.0, 3.0])
    assert sum(shares) == pytest.approx(0.9)
    assert shares[2] == pytest.approx(0.45)
    # degenerate weights: even split, never a crash or a dropped share
    assert kernelscope.apportion(0.6, [0.0, 0.0, -1.0]) == \
        pytest.approx([0.2, 0.2, 0.2])
    assert kernelscope.apportion(1.0, []) == []


def test_record_dispatch_and_meter_roll_up_in_snapshot():
    kernelscope.record_dispatch("flat", 8, 16, 0.010, "drain")
    kernelscope.record_dispatch("flat", 8, 16, 0.020, "drain")
    kernelscope.meter("c0", "t0", 0.030)
    kernelscope.meter("c0", "t0", -1.0)  # non-positive: ignored
    snap = kernelscope.snapshot()
    v = snap["variants"]["flat/b8/k16"]
    assert v["n"] == 2 and v["source"] == "drain"
    assert v["last_ms"] == pytest.approx(20.0)
    assert v["ewma_ms"] == pytest.approx(0.2 * 20.0 + 0.8 * 10.0)
    assert snap["total_device_seconds"] == pytest.approx(0.030)
    assert snap["dispatches"]["drain"] == 2
    assert snap["meters"]["c0/t0"] == pytest.approx(0.030)


# -- face 2 units: the explain sink -------------------------------------------


def test_explain_sink_merges_sections():
    assert not kernelscope.explain_enabled()
    kernelscope.explain_note("ivf", nprobe=4)  # no sink: must be a no-op
    token = kernelscope.explain_begin()
    assert kernelscope.explain_enabled()
    kernelscope.explain_note("ivf", nprobe=4, nlist=64)
    kernelscope.explain_note("ivf", candidates=128)  # merges, not replaces
    kernelscope.explain_note("store", path="full_scan")
    plan = kernelscope.explain_end(token)
    assert not kernelscope.explain_enabled()
    assert plan["ivf"] == {"nprobe": 4, "nlist": 64, "candidates": 128}
    assert plan["store"]["path"] == "full_scan"


def test_explain_scope_restores_previous_sink():
    token = kernelscope.explain_begin()
    inner = {}
    with kernelscope.explain_scope(inner):
        kernelscope.explain_note("a", x=1)
    kernelscope.explain_note("b", y=2)
    plan = kernelscope.explain_end(token)
    assert inner == {"a": {"x": 1}}
    assert plan == {"b": {"y": 2}}


# -- face 1 integration: drain-source attribution -----------------------------


def _drain_batcher(window_s=0.05, kind="flat"):
    """Batcher whose async handle sleeps ``window_s`` in its finish step
    — the drain window the transfer thread stamps."""
    def async_fn(queries, k, allow):
        b = len(queries)

        def fin():
            time.sleep(window_s)
            return (np.arange(b * k, dtype=np.int64).reshape(b, k),
                    np.zeros((b, k), np.float32))

        return DeviceResultHandle((), finish=fin)

    def sync_fn(queries, k, allow):  # pragma: no cover — must not run
        raise AssertionError("sync path used")

    return QueryBatcher(sync_fn, async_batch_fn=async_fn, kind=kind)


def test_drain_attribution_populates_device_phase():
    """THE acceptance pin: an UNSAMPLED request served through the async
    pipeline gets an attributed device time from the drain-thread stamps
    minus the memcpy EWMA — no tracing sample, no host sync."""
    # sampled transfer.d2h traces previously fed the estimator: the
    # result pytree is (1x4 int64, 1x4 f32) = 48 bytes
    for _ in range(4):
        kernelscope.observe_memcpy(0.004, 48)
    qb = _drain_batcher(window_s=0.05)
    try:
        p = _Pending(np.zeros(4, np.float32), 3, None)
        p.t_enqueue = time.perf_counter()
        qb._dispatch([p])
        assert p.event.wait(timeout=10.0)
        assert p.error is None
        # per-request attribution rode the dispatch's record back to
        # the waiter (every waiter of one dispatch reads the same record)
        assert p.rec["t_source"] == "drain"
        assert p.rec["device_ms"] >= 30.0
        assert p.rec["transfer_ms"] == pytest.approx(4.0)
    finally:
        qb.stop()
    snap = kernelscope.snapshot()
    assert snap["dispatches"]["drain"] >= 1
    # pow2 buckets: b=1 -> b1, k=3 -> k4; one compiled-variant EWMA
    v = snap["variants"]["flat/b1/k4"]
    assert v["source"] == "drain" and v["last_ms"] >= 30.0
    assert snap["total_device_seconds"] >= 0.03
    # the dispatch was metered (ambient owner -> "-/-")
    assert sum(kernelscope.meters_snapshot().values()) == pytest.approx(
        kernelscope.total_device_seconds(), rel=1e-6)


def test_null_device_degrades_to_wall_source():
    """Deflake guard: on a rig whose async path yields no handle (null
    device / bench stubs) attribution degrades to the dispatch wall
    window with source "wall" — never a crash, never zeros."""
    def batch_fn(queries, k, allow):
        time.sleep(0.01)
        b = len(queries)
        return (np.arange(b * k, dtype=np.int64).reshape(b, k),
                np.zeros((b, k), np.float32))

    qb = QueryBatcher(batch_fn, async_batch_fn=lambda *a: None, kind="flat")
    try:
        ids, dists = qb.search(np.zeros(4, np.float32), 3)
        assert ids.shape == (3,)
    finally:
        qb.stop()
    snap = kernelscope.snapshot()
    assert snap["dispatches"]["wall"] >= 1
    assert snap["dispatches"].get("drain", 0) == 0
    v = snap["variants"]["flat/b1/k4"]
    assert v["source"] == "wall" and v["last_ms"] > 0.0
    assert snap["total_device_seconds"] > 0.0


def test_solo_filtered_path_attributes_wall():
    """The solo path (filtered request, no filter batching) is a sync
    device call: wall-window attribution under the UNPADDED k."""
    def batch_fn(queries, k, allow):
        b = len(queries)
        return (np.zeros((b, k), np.int64), np.zeros((b, k), np.float32))

    qb = QueryBatcher(batch_fn, supports_filter_batching=False, kind="hnsw")
    try:
        qb.search(np.zeros(4, np.float32), 3, [1, 2, 3])
    finally:
        qb.stop()
    snap = kernelscope.snapshot()
    v = snap["variants"]["hnsw/b1/k3"]
    assert v["source"] == "wall" and v["n"] == 1


# -- face 3: per-tenant metering ----------------------------------------------


def test_two_tenant_metering_sums_to_total():
    """Acceptance: two tenants served through their own batchers — the
    per-tenant meters must sum to kernelscope's total attributed
    residency within 5% (the apportioned shares sum exactly)."""
    def batch_fn(queries, k, allow):
        b = len(queries)
        return (np.zeros((b, k), np.int64), np.zeros((b, k), np.float32))

    batchers = {
        t: QueryBatcher(batch_fn, max_batch=16,
                        owner={"collection": "Ks", "tenant": t})
        for t in ("t0", "t1")}
    try:
        for _ in range(40):
            for t, qb in batchers.items():
                qb.search(np.zeros(4, np.float32), 4)
    finally:
        for qb in batchers.values():
            qb.stop()
    meters = kernelscope.meters_snapshot()
    assert meters[("Ks", "t0")] > 0 and meters[("Ks", "t1")] > 0
    total = kernelscope.total_device_seconds()
    assert total > 0
    assert abs(sum(meters.values()) - total) / total < 0.05


# -- face 2 integration: EXPLAIN through the engine ---------------------------


def test_explain_ivf_filtered_plan_and_sync_async_parity():
    """A filtered IVF search under an explain sink reports the probe
    plan — lists_frac, candidates, rescored, the filter bit, the merge
    legs — and sync/async return identical results."""
    from weaviate_tpu.engine.ivf import IVFStore

    rng = np.random.default_rng(7)
    # (flatSearchCutoff off: 256 allowed rows are under its default, and
    # the exact route that would answer them has no probe plan to report)
    st = IVFStore(dim=16, nlist=8, nprobe=2, train_threshold=256,
                  delta_threshold=64, quantization="pq",
                  flat_search_cutoff=0)
    st.add(rng.standard_normal((512, 16)).astype(np.float32))
    assert st.trained
    qs = rng.standard_normal((3, 16)).astype(np.float32)
    allow = np.zeros(st.capacity, dtype=bool)
    allow[:256] = True

    token = kernelscope.explain_begin()
    dists, ids = st.search(qs, 10, allow)
    plan = kernelscope.explain_end(token)

    ivf = plan["ivf"]
    assert ivf["nprobe"] == 2 and ivf["nlist"] == 8
    assert ivf["lists_frac"] == pytest.approx(2 / 8)
    assert ivf["candidates"] > 0
    assert ivf["rescored"] > 0 and ivf["quantized"] is True
    assert ivf["filtered"] is True
    assert ivf["queries"] == 3 and ivf["k"] == 10
    assert "merge_legs" in ivf and "delta_leg" in ivf
    assert ivf["gather"] == "codes"    # "slab" where the lists hold rows

    # sync IS async.result() — pin the bit-identical contract, and pin
    # that running WITHOUT a sink changes nothing about the results
    dists2, ids2 = st.search_async(qs, 10, allow).result()
    np.testing.assert_array_equal(ids, ids2)
    np.testing.assert_array_equal(dists, dists2)
    assert set(ids.ravel().tolist()) <= set(range(256)) | {-1}


@pytest.fixture
def served(tmp_path, monkeypatch):
    """Real server, sampling effectively off — explain and attribution
    must work on unsampled requests."""
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0.001")
    from weaviate_tpu.runtime import tracing
    tracing.reset_policy_for_tests()
    db = Database(str(tmp_path))
    srv = RestServer(db)
    srv.start()
    client = Client(srv.address)
    client.create_class({"name": "Ks", "properties": [
        {"name": "n", "data_type": "int"}]})
    rng = np.random.default_rng(11)
    for i in range(24):
        client.create_object("Ks", {"n": i},
                             vector=[float(x)
                                     for x in rng.standard_normal(8)])
    yield client, srv, db
    srv.stop()
    db.close()
    tracing.reset_policy_for_tests()


def _gql(client, explain=False):
    q = ('{ Get { Ks(limit: 3, '
         'where: {path: ["n"], operator: GreaterThanEqual, valueInt: 8}, '
         'nearVector: {vector: '
         '[0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8]}) '
         '{ _additional { id distance } } } }')
    path = "/v1/graphql" + ("?explain=true" if explain else "")
    return client.request("POST", path, body={"query": q, "variables": {}})


def test_rest_explain_black_box(served):
    """Acceptance: ``?explain=true`` on a filtered search returns the
    structured plan (batcher coalescing + engine sections) and the SAME
    result set as the unexplained request; without the flag no plan
    rides the response."""
    client, srv, db = served
    plain = _gql(client)
    assert "_explain" not in plain

    resp = _gql(client, explain=True)
    plan = resp["_explain"]
    b = plan["batcher"]
    assert b["batch"] >= 1 and b["k_bucket"] >= 3
    assert b["filtered"] >= 1
    assert b["kind"]
    # at least one engine layer noted its path (flat "index" note or the
    # store's filter-cutover note, depending on routing)
    assert "index" in plan or "store" in plan
    if "store" in plan:
        assert plan["store"]["path"] in (
            "bitmask_batched", "gathered", "shared_mask", "full_scan")
    # explain is observational: identical result set
    assert resp["data"] == plain["data"]

    # repeated explained requests must not leak sinks across requests
    again = _gql(client, explain=True)
    assert again["data"] == plain["data"]


def test_debug_kernelscope_endpoint_reports_attribution(served):
    """The ``/v1/debug/kernelscope`` face: after served searches the
    snapshot carries variants + meters + dispatch counts."""
    client, srv, db = served
    for _ in range(3):
        _gql(client)
    out = client.request("GET", "/v1/debug/kernelscope")
    assert out["dispatches"]["drain"] + out["dispatches"]["wall"] >= 1
    assert out["total_device_seconds"] > 0
    assert out["variants"], out
    assert "kernelscope" in DEBUG_ENDPOINTS and "profile" in DEBUG_ENDPOINTS


def test_grpc_x_explain_rides_trailing_metadata(tmp_path):
    """gRPC analog: ``x-explain: true`` metadata returns the plan as
    the ``x-explain`` trailing-metadata entry."""
    grpc = pytest.importorskip("grpc")
    from weaviate_tpu.api.grpc import v1_pb2 as pb
    from weaviate_tpu.api.grpc.server import GrpcServer
    from weaviate_tpu.schema.config import CollectionConfig

    db = Database(str(tmp_path))
    server = GrpcServer(db).start()
    channel = grpc.insecure_channel(f"127.0.0.1:{server.port}")
    try:
        db.create_collection(CollectionConfig(name="Doc"))
        col = db.get_collection("Doc")
        rng = np.random.default_rng(5)
        for i in range(8):
            col.put_object({},
                           vector=rng.standard_normal(8).astype(np.float32))
        search = channel.unary_unary(
            "/weaviate.v1.Weaviate/Search",
            request_serializer=pb.SearchRequest.SerializeToString,
            response_deserializer=pb.SearchReply.FromString)
        req = pb.SearchRequest(collection="Doc", limit=3)
        req.near_vector.vector_bytes = \
            rng.standard_normal(8).astype("<f4").tobytes()
        reply, call = search.with_call(
            req, metadata=(("x-explain", "true"),))
        assert len(reply.results) == 3
        trailers = dict(call.trailing_metadata() or ())
        plan = json.loads(trailers["x-explain"])
        assert plan["batcher"]["batch"] >= 1
        # without the metadata flag: no explain trailer
        _, call2 = search.with_call(req)
        assert "x-explain" not in dict(call2.trailing_metadata() or ())
    finally:
        channel.close()
        server.stop()
        db.close()


# -- the zero-new-host-syncs pin ----------------------------------------------


def test_g1_baseline_stays_empty_for_dispatch_path():
    """Explain emission + attribution added code to every engine layer;
    NONE of it may read device values on the host. The G1 checker over
    the whole dispatch path must report zero raw violations (the repo
    baseline has no G1 entries to hide behind)."""
    from tools.graftlint.core import run
    from tools.graftlint.g1_host_sync import HostSyncChecker

    res = run(["weaviate_tpu/engine", "weaviate_tpu/ops",
               "weaviate_tpu/parallel",
               "weaviate_tpu/runtime/query_batcher.py"],
              REPO_ROOT, use_cache=False, checkers=[HostSyncChecker()])
    assert res.violations == [], [
        (v.path, v.line, v.message) for v in res.violations]


# -- face 4: on-demand kernel profiles ----------------------------------------


def _meta(kind, pid, name, tid=None):
    ev = {"ph": "M", "name": kind, "pid": pid, "args": {"name": name}}
    if tid is not None:
        ev["tid"] = tid
    return ev


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur}


# one device plane (its op line and its module line) and the host's
# threads in ONE trace, as jax.profiler writes them (ts/dur in us)
_FAKE_EVENTS = [
    _meta("process_name", 1, "/device:TPU:0"),
    _meta("thread_name", 1, "XLA Ops", tid=1),
    _meta("thread_name", 1, "XLA Modules", tid=2),
    _meta("process_name", 7, "/host:CPU"),
    _x(1, 1, "jit__bq_scan_tiled.3", 0.0, 1500.0),
    _x(1, 1, "pq4_lut_matmul", 5000.0, 800.0),
    _x(1, 1, "fusion.42_misc", 5800.0, 100.0),
    # the module line repeats the ops' time: never counted twice
    _x(1, 2, "jit_bq_topk(123)", 0.0, 1500.0),
    # host threads: a lookalike name, and the program's stage annotations
    _x(7, 30, "jit__bq_scan_tiled.3", 0.0, 6000.0),
    _x(7, 31, "wtpu.rescore", 1500.0, 3000.0),
    _x(7, 32, "wtpu.idle", 1400.0, 400.0),
]
_FAKE_N = len(_FAKE_EVENTS)


def test_capture_ranks_kernels_and_prunes(tmp_path):
    calls = []

    def fake(ms):
        calls.append(ms)
        return list(_FAKE_EVENTS)

    kernelscope.configure(data_dir=str(tmp_path), keep=2, capturer=fake)
    rec = kernelscope.capture_profile(7)
    assert calls == [7]
    assert rec["ms"] == 7 and rec["raw_events"] == _FAKE_N
    ranked = [(k["kernel"], k["device_ms"]) for k in rec["kernels"]]
    assert ranked == [("bq_scan_reduce", 1.5), ("pq4_scan_reduce", 0.8),
                      ("other", 0.1)]
    assert rec["total_device_ms"] == pytest.approx(2.4)
    assert rec["kernels"][0]["top_events"][0]["name"] == \
        "jit__bq_scan_tiled.3"

    # persisted, listed newest-first, pruned past keep=2
    kernelscope.capture_profile(8)
    rec3 = kernelscope.capture_profile(9)
    caps = kernelscope.list_captures()
    assert len(caps) == 2
    assert caps[0]["id"] == rec3["id"]
    loaded = kernelscope.load_capture(rec3["id"])
    assert loaded["kernels"][0]["kernel"] == "bq_scan_reduce"
    # path traversal is sanitized to a basename; junk ids load nothing
    assert kernelscope.load_capture("../../etc/passwd") is None


def test_profile_rest_endpoint(served, tmp_path):
    """``GET /v1/debug/profile``: paramless lists (never captures),
    ``?ms=N`` captures through the injected capturer, ``?id=`` loads,
    bad params are typed 4xx."""
    client, srv, db = served
    calls = []

    def fake(ms):
        calls.append(ms)
        return list(_FAKE_EVENTS)

    kernelscope.configure(data_dir=str(tmp_path / "caps"), capturer=fake)
    out = client.request("GET", "/v1/debug/profile")
    assert out == {"captures": []} and calls == []

    rec = client.request("GET", "/v1/debug/profile?ms=5")
    assert calls == [5]
    assert rec["kernels"][0]["kernel"] == "bq_scan_reduce"
    assert client.request("GET", "/v1/debug/profile")["captures"][0][
        "id"] == rec["id"]
    full = client.request("GET", f"/v1/debug/profile?id={rec['id']}")
    assert full["total_device_ms"] == rec["total_device_ms"]

    for bad in ("ms=abc", "ms=0", "ms=999999"):
        with pytest.raises(RestError) as e:
            client.request("GET", f"/v1/debug/profile?{bad}")
        assert e.value.status == 422, bad
    with pytest.raises(RestError) as e:
        client.request("GET", "/v1/debug/profile?id=cap-0-0")
    assert e.value.status == 404


def test_summarize_counts_device_planes_only_and_names_gaps_by_stage():
    """What the benchmark reads from the .xplane.pb, read from the
    server: host threads are never device time, busy is the union of the
    device's op line, the idle share is over the captured window, and
    the longest gaps carry the ``wtpu.<stage>`` that covers most of
    each."""
    out = kernelscope.summarize_trace_events(_FAKE_EVENTS)
    assert out["device_planes"] == ["/device:TPU:0"]
    assert out["total_device_ms"] == pytest.approx(2.4)   # no host 6 ms
    assert out["window_ms"] == pytest.approx(6.0)
    assert out["busy_ms"] == pytest.approx(2.4)
    assert out["device_idle_pct"] == pytest.approx(60.0)
    gaps = {g["stage"]: g["gap_ms"] for g in out["idle_gaps"]}
    # 1.5..5.0 ms lies under wtpu.rescore (3.0 of 3.5 ms; wtpu.idle only
    # 0.3), 5.9..6.0 under no stage at all
    assert gaps == {"wtpu.rescore": pytest.approx(3.5),
                    "no wtpu stage": pytest.approx(0.1)}
    assert sum(gaps.values()) == pytest.approx(
        out["window_ms"] - out["busy_ms"])
    # nested ops count once in busy (a loop and its body)
    nested = _FAKE_EVENTS + [_x(1, 1, "while.body", 100.0, 500.0)]
    assert kernelscope.summarize_trace_events(nested)["busy_ms"] == \
        pytest.approx(2.4)


def test_summarize_a_trace_without_a_device_plane_has_no_device_time():
    host_only = [e for e in _FAKE_EVENTS if e.get("pid") == 7]
    out = kernelscope.summarize_trace_events(host_only)
    assert out["kernels"] == [] and out["total_device_ms"] == 0
    assert out["device_planes"] == [] and "idle_gaps" not in out


def test_summarize_trace_events_tolerates_junk():
    assert kernelscope.summarize_trace_events(None) == \
        {"kernels": [], "total_device_ms": 0}
    out = kernelscope.summarize_trace_events(
        [{"ph": "X"}, {"ph": "X", "name": "x", "dur": 0}, "junk", 3])
    assert out["kernels"] == []


# -- satellite: PROFILING_PORT gate -------------------------------------------


def test_profiling_port_defaults_off():
    cfg = ServerConfig.from_env({})
    assert cfg.profiling_port == 0
    assert cfg.profile_keep == 8
    cfg = ServerConfig.from_env({"PROFILING_PORT": "9431",
                                 "PROFILING_KEEP": "3"})
    assert cfg.profiling_port == 9431 and cfg.profile_keep == 3


def test_profiler_server_never_starts_on_port_zero(monkeypatch):
    """PROFILING_PORT=0 (the default) must NEVER start the jax profiler
    server — not even a call that fails."""
    import jax

    from weaviate_tpu.server import Server

    calls = []
    monkeypatch.setattr(jax.profiler, "start_server",
                        lambda port: calls.append(port))
    srv = Server.__new__(Server)
    assert srv._start_profiler(0) is False
    assert calls == []
    assert srv._start_profiler(9431) is True
    assert calls == [9431]

    # a port that fails to bind degrades to a warning, not a crash
    def boom(port):
        raise OSError("address in use")

    monkeypatch.setattr(jax.profiler, "start_server", boom)
    assert srv._start_profiler(9431) is False
