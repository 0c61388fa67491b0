"""HBM ledger + capacity-aware admission (ISSUE 4).

Acceptance-criteria coverage: /v1/debug/memory per-collection totals
agree with the sum of ledger registrations EXACTLY on a CPU mesh, and
check_device_alloc rejects an over-budget import with allocator stats
unavailable (CPU backend exposes none) — plus the watermark
reject -> release -> accept hysteresis cycle and the memwatch stats-TTL
fix.
"""

import gc
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from weaviate_tpu.runtime import hbm_ledger
from weaviate_tpu.runtime.hbm_ledger import HBMLedger
from weaviate_tpu.runtime.memwatch import (InsufficientMemoryError,
                                           MemoryMonitor)


# -- ledger core ---------------------------------------------------------------


def test_register_update_release_totals_and_peak():
    led = HBMLedger()
    k1 = led.register("corpus", 1000, collection="A", shard="s0")
    k2 = led.register("codes", 500, collection="B", shard="s1")
    assert led.total_bytes() == 1500
    assert led.collection_bytes("A") == 1000
    assert led.shard_bytes("B", "s1") == 500
    led.update(k1, 4000)  # capacity grow
    assert led.total_bytes() == 4500
    assert led.peak_bytes() == 4500
    led.release(k2)
    assert led.total_bytes() == 4000
    assert led.peak_bytes() == 4500  # peak is a high-water mark
    led.release(k1)
    assert led.total_bytes() == 0
    assert led.collection_bytes("A") == 0


def test_owner_context_labels_registrations():
    led = HBMLedger()
    with hbm_ledger.owner("Col", "shard-3", tenant="acme"):
        led.register("corpus", 64)
    top = led.top(1)[0]
    assert (top["collection"], top["shard"], top["tenant"]) == \
        ("Col", "shard-3", "acme")
    # outside any scope -> the _unowned placeholder, never a crash
    led.register("corpus", 8)
    assert any(t["collection"] == "_unowned" for t in led.top(5))


def test_host_placement_excluded_from_device_totals():
    led = HBMLedger()
    led.register("graph", 1 << 20, collection="H", placement="host")
    assert led.total_bytes() == 0  # admission gates device bytes only
    bd = led.breakdown()
    assert bd["H"]["hostBytes"] == 1 << 20
    assert bd["H"]["bytes"] == 0


def test_track_releases_with_array_lifetime():
    import jax.numpy as jnp

    led = HBMLedger()
    arr = jnp.zeros((128,), jnp.uint32)
    led.track("allow_bitmask", arr, collection="T")
    assert led.collection_bytes("T") == int(arr.nbytes)
    del arr
    gc.collect()
    assert led.collection_bytes("T") == 0


@pytest.mark.parametrize("entry", ["release_many", "track"])
def test_a_finalizer_takes_no_lock(entry):
    """A weakref finalizer can fire from a cyclic GC inside any locked
    section of its thread, ``hbm_bytes.labels()`` under ``register``
    included (a tier-1 run hung there: the batcher's worker took the
    gauge's plain Lock a second time). So what a finalizer runs returns
    while the ledger's lock and the gauge's are both held elsewhere, and
    the ledger's next call releases the entry."""
    import threading

    from weaviate_tpu.runtime.metrics import hbm_bytes

    class Buffer:
        nbytes = 4096

    led = HBMLedger()
    owner = [Buffer()]
    if entry == "track":
        led.track("corpus", owner[0], collection="Fin", shard="f0")
    else:
        import weakref

        keys = [led.register("corpus", 4096, collection="Fin", shard="f0")]
        weakref.finalize(owner[0], led.release_many, keys)
    assert led.shard_bytes("Fin", "f0") == 4096
    dropped = threading.Thread(target=owner.clear, daemon=True)
    with led._lock, hbm_bytes._lock:
        dropped.start()
        dropped.join(5.0)
        assert not dropped.is_alive()
    assert led.shard_bytes("Fin", "f0") == 0
    assert led.total_bytes() == 0


def test_gauges_follow_ledger_and_drop_on_release():
    from weaviate_tpu.runtime.metrics import registry

    led = hbm_ledger.ledger  # gauges only export from the global ledger
    key = led.register("corpus", 12345, collection="GaugeCol", shard="g0")
    text = registry.expose()
    assert ('weaviate_tpu_hbm_bytes{collection="GaugeCol",shard="g0",'
            'component="corpus"} 12345.0') in text
    led.release(key)
    assert "GaugeCol" not in registry.expose()  # child removed, not 0


# -- store instrumentation -----------------------------------------------------


def test_device_store_registers_and_grows():
    from weaviate_tpu.engine.store import DeviceVectorStore

    led = hbm_ledger.ledger
    with hbm_ledger.owner("StoreCol", "s0"):
        store = DeviceVectorStore(dim=16, capacity=32)
    expected = sum(int(a.nbytes) for a in
                   (store.vectors, store.valid, store.sq_norms))
    assert led.collection_bytes("StoreCol") == expected
    # grow past capacity -> the SAME entry updates to the new footprint
    store.add(np.random.randn(100, 16).astype(np.float32))
    store.flush_staged()
    expected = sum(int(a.nbytes) for a in
                   (store.vectors, store.valid, store.sq_norms))
    assert led.collection_bytes("StoreCol") == expected
    del store
    gc.collect()
    assert led.collection_bytes("StoreCol") == 0


def test_compress_swaps_attribution_without_leaking():
    from weaviate_tpu.engine.flat import FlatIndex

    led = hbm_ledger.ledger
    with hbm_ledger.owner("CompressCol", "s0"):
        idx = FlatIndex(dim=8, capacity=64)
    idx.add_batch(np.arange(64), np.random.randn(64, 8).astype(np.float32))
    before = led.collection_bytes("CompressCol")
    assert before > 0
    idx.compress(quantization="bq")
    gc.collect()  # old store's finalizer releases its corpus entry
    after = led.collection_bytes("CompressCol")
    # quantized codes and the float32 rescore rows (resident where they
    # fit) replace the f32 corpus: attribution stays on the collection,
    # the old corpus bytes are gone
    assert after > 0
    expected = (int(idx.store.codes.nbytes) + int(idx.store.valid.nbytes)
                + int(idx.store.rescore_rows.nbytes))
    assert after == expected
    del idx
    gc.collect()
    assert led.collection_bytes("CompressCol") == 0


def test_quantized_store_components():
    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    led = hbm_ledger.ledger
    with hbm_ledger.owner("QCol", "s0"):
        st = QuantizedVectorStore(dim=32, quantization="bq", capacity=64,
                                  rescore="device")
    st.add(np.random.randn(32, 32).astype(np.float32))
    bd = led.breakdown()["QCol"]
    assert bd["components"]["codes"] == \
        int(st.codes.nbytes) + int(st.valid.nbytes)
    assert bd["components"]["rescore_rows"] == int(st.rescore_rows.nbytes)
    del st
    gc.collect()
    assert led.collection_bytes("QCol") == 0


# -- admission control (allocator stats ABSENT on the CPU backend) -------------


def test_budget_enforced_from_ledger_projection():
    led = HBMLedger()
    mon = MemoryMonitor(device_limit_bytes=10_000, ledger=led,
                        high_watermark=0.9, low_watermark=0.8)
    led.register("corpus", 8500, collection="X")
    with pytest.raises(InsufficientMemoryError) as e:
        mon.check_device_alloc(1000)  # 9500 > 9000
    assert e.value.status == 507
    assert e.value.source == "ledger"
    assert mon.under_pressure


def test_watermark_reject_release_accept_cycle():
    """High trips, low clears: 8500+1000 rejects; releasing down to 7000
    (< low 8000) clears pressure and the same request is admitted."""
    led = HBMLedger()
    mon = MemoryMonitor(device_limit_bytes=10_000, ledger=led,
                        high_watermark=0.9, low_watermark=0.8)
    k = led.register("corpus", 8500, collection="X")
    with pytest.raises(InsufficientMemoryError):
        mon.check_device_alloc(1000)
    # hysteresis: still above low watermark -> a small alloc that fits
    # under high is STILL refused while pressure latched
    with pytest.raises(InsufficientMemoryError):
        mon.check_device_alloc(100)  # 8600 > low 8000, pressure on
    led.update(k, 7000)  # tenant offload / delete frees capacity
    mon.check_device_alloc(1000)  # 8000 <= low? 7000 usage clears latch
    assert not mon.under_pressure


def test_memory_pressure_counter_and_span():
    from weaviate_tpu.runtime.metrics import memory_pressure_total

    led = HBMLedger()
    mon = MemoryMonitor(device_limit_bytes=1000, ledger=led)
    child = memory_pressure_total.labels("device", "rejected")
    before = child.value
    with pytest.raises(InsufficientMemoryError):
        mon.check_device_alloc(5000)
    assert memory_pressure_total.labels("device", "rejected").value \
        == before + 1


def test_no_budget_means_no_gate():
    mon = MemoryMonitor(ledger=HBMLedger())
    mon.check_device_alloc(1 << 40)  # no explicit/env/allocator budget


# -- memwatch stats TTL (satellite: sticky-unavailable fix) --------------------


def test_device_stats_unavailable_retries_after_ttl(monkeypatch):
    from weaviate_tpu.runtime import memwatch

    calls = {"n": 0}

    def flaky_probe():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("backend still initializing")
        return {"tpu:0": {"bytesInUse": 7, "bytesLimit": 100,
                          "peakBytesInUse": 9}}

    monkeypatch.setattr(memwatch, "_probe_device_stats", flaky_probe)
    monkeypatch.setattr(memwatch, "_stats_failed_at", None)
    monkeypatch.setattr(memwatch, "STATS_RETRY_S", 1e6)
    assert memwatch.device_memory_stats() == {}  # transient failure
    # within the TTL the negative verdict is cached (no re-probe)
    assert memwatch.device_memory_stats() == {}
    assert calls["n"] == 1
    # TTL elapsed -> re-probe succeeds and clears the verdict
    monkeypatch.setattr(memwatch, "STATS_RETRY_S", 0.0)
    assert memwatch.device_memory_stats()["tpu:0"]["bytesInUse"] == 7
    assert calls["n"] == 2
    monkeypatch.setattr(memwatch, "STATS_RETRY_S", 1e6)
    assert memwatch.device_memory_stats()["tpu:0"]["bytesInUse"] == 7


# -- REST surface --------------------------------------------------------------


@pytest.fixture
def rest_server(tmp_path):
    from weaviate_tpu.api.rest import RestServer
    from weaviate_tpu.db.database import Database

    mon = MemoryMonitor()  # no budget yet; tests tighten it
    db = Database(str(tmp_path), memory_monitor=mon)
    srv = RestServer(db)
    srv.start()
    yield srv, db, mon
    srv.stop()
    db.close()


def _req(srv, method, path, body=None):
    r = urllib.request.Request(
        f"http://{srv.address}/v1{path}", method=method,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(r) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


def test_debug_memory_totals_match_ledger_exactly(rest_server):
    srv, db, _mon = rest_server
    status, _ = _req(srv, "POST", "/schema", {
        "class": "MemCol",
        "properties": [{"name": "t", "dataType": ["text"]}]})
    assert status == 200
    for i in range(3):
        status, _ = _req(srv, "POST", "/objects", {
            "class": "MemCol", "properties": {"t": "x"},
            "vector": [float(i)] * 32})
        assert status == 200
    status, mem = _req(srv, "GET", "/debug/memory")
    assert status == 200
    led = hbm_ledger.ledger
    col = mem["ledger"]["collections"]["MemCol"]
    # endpoint rollup == sum of live registrations, exactly
    assert col["bytes"] == led.collection_bytes("MemCol")
    assert sum(col["shards"].values()) == col["bytes"]
    assert mem["ledger"]["totalBytes"] == led.total_bytes()
    assert mem["ledger"]["peakBytes"] == led.peak_bytes()
    # CPU backend: no allocator stats, hence no delta section
    assert mem["allocator"] == {}
    assert "allocatorDelta" not in mem
    # the shard-level rollup shows up in verbose /v1/nodes too
    status, nodes = _req(srv, "GET", "/nodes?output=verbose")
    assert status == 200
    shards = [s for s in nodes["nodes"][0]["shards"]
              if s["class"] == "MemCol"]
    assert shards and sum(s["hbmBytes"] for s in shards) == col["bytes"]


def test_over_budget_import_rejected_with_507(rest_server):
    srv, db, mon = rest_server
    status, _ = _req(srv, "POST", "/schema", {
        "class": "TightCol",
        "properties": [{"name": "t", "dataType": ["text"]}]})
    assert status == 200
    mon.device_limit = 1  # everything rejects from here on
    status, err = _req(srv, "POST", "/objects", {
        "class": "TightCol", "properties": {"t": "y"},
        "vector": [0.5] * 16})
    assert status == 507
    detail = err["error"][0]
    assert detail["code"] == "INSUFFICIENT_MEMORY"
    assert detail["usageSource"] == "ledger"  # allocator stats absent
    # nothing was admitted: the object is not visible
    status, listing = _req(srv, "GET", "/objects?class=TightCol")
    assert status == 200 and listing["objects"] == []
    # release the clamp -> the same import is accepted (full cycle)
    mon.device_limit = None
    status, _ = _req(srv, "POST", "/objects", {
        "class": "TightCol", "properties": {"t": "y"},
        "vector": [0.5] * 16})
    assert status == 200


def test_over_budget_batch_import_rejected_with_507(rest_server):
    """Bulk import (/v1/batch/objects) is THE path capacity gating
    exists for — the admission rejection must surface as a typed 507,
    not dissolve into per-object FAILED entries under HTTP 200."""
    srv, db, mon = rest_server
    status, _ = _req(srv, "POST", "/schema", {
        "class": "BatchCol",
        "properties": [{"name": "t", "dataType": ["text"]}]})
    assert status == 200
    mon.device_limit = 1
    status, err = _req(srv, "POST", "/batch/objects", {"objects": [
        {"class": "BatchCol", "properties": {"t": "a"},
         "vector": [0.1] * 16},
        {"class": "BatchCol", "properties": {"t": "b"},
         "vector": [0.2] * 16},
    ]})
    assert status == 507
    assert err["error"][0]["code"] == "INSUFFICIENT_MEMORY"
