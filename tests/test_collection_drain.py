"""A request over a collection's local shards is ONE item in ONE drain
(ISSUE 42, ``db/drain.py``): one batcher a collection, every member
shard's scan launched over one query block, two dispatch threads whatever
the number of shards. The answers are the host reference's
(``tests/multishard_reference.py``: numpy, float64, no shards) and, id for
id and distance for distance, those of the per-shard route the same
collection takes for a filtered request. Runs on the CPU's eight forced
devices (``tests/conftest.py``); a host of one or four chips is shown by
patching ``placement.local_devices``, as ``tests/test_shard_placement.py``
does."""

from __future__ import annotations

import threading
import time
import uuid as uuid_mod

import jax
import numpy as np
import pytest

import multishard_reference as ref
from weaviate_tpu.api.rest import config_from_json
from weaviate_tpu.db import drain as drain_mod
from weaviate_tpu.db.database import Database
from weaviate_tpu.filters.filters import Filter, Operator
from weaviate_tpu.runtime import metrics, placement, retry
from weaviate_tpu.runtime.query_batcher import BatcherStopped

ROWS, DIM, SHARDS = 4000, 32, 8
METRICS = ("l2-squared", "cosine", "dot")
DISPATCH_THREADS = ("query-batcher", "qb-transfer")


def _uuid(i: int) -> str:
    return str(uuid_mod.UUID(int=i + 1))


def klass(name: str, metric: str = "cosine", shards: int = SHARDS, **index):
    return {"class": name, "vectorIndexType": "flat",
            "vectorIndexConfig": dict({"distance": metric}, **index),
            "shardingConfig": {"desiredCount": shards},
            "properties": [{"name": "bucket", "dataType": ["int"]},
                           {"name": "text", "dataType": ["text"]}]}


def fill(col, rows, first: int = 0):
    done = col.batch_put([
        {"uuid": _uuid(first + i), "vector": rows[i],
         "properties": {"bucket": (first + i) % 100,
                        "text": f"word{(first + i) % 7} row"}}
        for i in range(len(rows))])
    assert all(r["status"] == "SUCCESS" for r in done)


def top_k(rows, query, k, metric, allowed=None):
    """The reference's top k; ``dot`` (the negative inner product, as the
    program reports it) is computed here the way the reference computes
    the other two: float64, one scan, a stable sort."""
    if metric != "dot":
        return ref.top_k(rows, query, k, metric, allowed)
    d = -(np.asarray(rows, np.float64) @ np.asarray(query, np.float64))
    where = np.arange(len(d)) if allowed is None else np.flatnonzero(allowed)
    order = where[np.argsort(d[where], kind="stable")[:k]]
    return order.astype(np.int64), d[order]


def ids_of(results) -> list[int]:
    return [uuid_mod.UUID(r.uuid).int - 1 for r in results]


def pairs(results) -> list:
    return [(r.uuid, r.distance, r.shard) for r in results]


def route(col, name: str) -> float:
    return metrics.fanout_route_total.labels(col.config.name, name).value


class by_shards:
    """The same collection with no drain to take: every request rides
    the shards' own batchers (what a filtered request does)."""

    def __init__(self, col):
        self.col = col

    def __enter__(self):
        self.col._drain_for = lambda *_a: None

    def __exit__(self, *_exc):
        del self.col._drain_for


def dispatch_threads() -> list:
    return [t for t in threading.enumerate()
            if t.name in DISPATCH_THREADS and t.is_alive()]


class World:
    def __init__(self, path: str):
        rng = np.random.default_rng(42)
        self.rows = rng.standard_normal((ROWS, DIM)).astype(np.float32)
        self.queries = rng.standard_normal((48, DIM)).astype(np.float32)
        self.db = Database(path)
        self.cols = {m: self.db.create_collection(config_from_json(
            klass("Drain" + m[:2].title(), m))) for m in METRICS}
        sharding = self.cols["cosine"].sharding
        self.home = np.array([
            int(sharding.shard_for(_uuid(i)).rsplit("-", 1)[1])
            for i in range(ROWS)])
        self.twins = []
        for a in (5, 6, 7):
            b = int(np.flatnonzero(self.home != self.home[a])[100 * a])
            self.rows[b] = self.rows[a]
            self.twins.append((a, b))
        for col in self.cols.values():
            fill(col, self.rows)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    w = World(str(tmp_path_factory.mktemp("drain")))
    yield w
    w.db.close()


@pytest.fixture
def fresh_placement(monkeypatch):
    monkeypatch.setattr(placement, "_held", {})


def host_of(n: int, monkeypatch) -> list:
    devices = jax.local_devices()[:n]
    monkeypatch.setattr(placement, "local_devices", lambda: devices)
    return devices


def block_uploads(monkeypatch) -> list:
    """-> the devices a batcher's worker puts a [1, DIM] numpy query
    block on from now on (``placement.put``, the one helper that
    uploads; a twin's warm-up on its own thread is not a dispatch)."""
    puts = []
    real_put = placement.put

    def put(arr, device=None):
        if isinstance(arr, np.ndarray) and arr.shape == (1, DIM) and \
                threading.current_thread().name == "query-batcher":
            puts.append(device)
        return real_put(arr, device)

    monkeypatch.setattr(placement, "put", put)
    return puts


def assert_is_the_top_k(world, results, query, k, metric):
    want, want_d = top_k(world.rows, query, k + 8, metric)
    got = ids_of(results)
    n = min(k, len(want))
    assert len(got) == n and len(set(got)) == n
    np.testing.assert_allclose([r.distance for r in results], want_d[:n],
                               rtol=1e-5, atol=2e-5)
    # a float32 near-tie may swap neighbours: compare as sets wherever
    # the reference's distances are closer than a rounding
    i = 0
    while i < n:
        j = i + 1
        while j < len(want) and want_d[j] - want_d[j - 1] <= \
                4e-6 * max(1.0, abs(want_d[j])):
            j += 1
        assert set(got[i:min(j, n)]) <= set(want[i:j].tolist())
        i = j


# -- the answers ------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 10, 100, 600])
@pytest.mark.parametrize("metric", METRICS)
def test_answers_are_the_references_and_the_shard_routes(world, metric, k):
    """k = 600 is above a shard's ~500 rows: the members answer with all
    they have, in blocks of different widths, and the merge still returns
    the corpus's top k."""
    col = world.cols[metric]
    assert max(s.object_count() for s in col.shards.values()) < 600
    drained = route(col, "drain")
    for q in world.queries[:6]:
        found = col.near_vector(q, k=k, include_objects=False)
        assert_is_the_top_k(world, found, q, k, metric)
        with by_shards(col):
            assert pairs(col.near_vector(q, k=k, include_objects=False)) \
                == pairs(found)
    assert route(col, "drain") - drained == 6


@pytest.mark.parametrize("metric", METRICS)
def test_equal_vectors_on_two_shards_are_both_returned(world, metric):
    for a, b in world.twins:
        found = world.cols[metric].near_vector(world.rows[a], k=10,
                                               include_objects=False)
        got = ids_of(found)
        assert {a, b} <= set(got[:3]) and len(set(got)) == 10
        assert found[got.index(a)].distance == pytest.approx(
            found[got.index(b)].distance, abs=1e-5)
        assert found[got.index(a)].shard != found[got.index(b)].shard


def test_members_of_different_widths_stack_to_one_block():
    a = (np.array([[1, 2, 3]]), np.array([[.1, .2, .3]], np.float32))
    b = (np.array([[7]]), np.array([[.5]], np.float32))
    ids, dists = drain_mod._stack([a, b])
    assert ids.shape == dists.shape == (1, 2, 3)
    assert ids.tolist() == [[[1, 2, 3], [7, -1, -1]]]
    assert dists[0, 1].tolist() == [.5, np.inf, np.inf]


def test_a_gathered_handle_resolves_its_members_and_fails_as_the_member():
    """``DeviceResultHandle.gather``: one handle over several programs'
    handles; every member's copy is started before the first is waited
    for, each resolves through its own finish chain, and a member's
    error is the whole's."""
    import jax.numpy as jnp

    from weaviate_tpu.runtime.transfer import DeviceResultHandle

    started = []

    class Lazy:
        """Stands for a device array: notes when its copy is asked for."""

        def __init__(self, name):
            self.name = name

        def copy_to_host_async(self):
            started.append(self.name)

        def __array__(self, dtype=None, copy=None):
            started.append("fetch " + self.name)
            return np.arange(3)

    a = DeviceResultHandle((Lazy("a"),), finish=lambda x: x + 1).map(
        lambda x: x * 2)
    b = DeviceResultHandle((Lazy("b"),), finish=lambda x: x)
    c = DeviceResultHandle.ready("host")
    whole = DeviceResultHandle.gather([a, b, c], finish=tuple)
    out = whole.result()
    assert started == ["a", "b", "fetch a", "fetch b"]
    assert out[0].tolist() == [2, 4, 6] and out[1].tolist() == [0, 1, 2]
    assert out[2] == "host" and whole.result() is out

    def boom(_x):
        raise KeyError("member")

    bad = DeviceResultHandle((jnp.arange(3),), finish=boom)
    with pytest.raises(KeyError):
        DeviceResultHandle.gather([c, bad]).result()


# -- one item, one drain, two threads ----------------------------------------------


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
def test_32_threads_give_the_serial_answers_and_queue_one_item_a_request(
        world, metric):
    col = world.cols[metric]
    serial = [ids_of(col.near_vector(q, k=10, include_objects=False))
              for q in world.queries[:32]]
    b = col._drains[""].batcher
    gate, held = threading.Event(), []
    dispatch = b._dispatch

    def gated(drained, rec=None):
        held.append(len(drained))
        gate.wait()
        return dispatch(drained, rec)

    b._dispatch = gated
    got = {}
    fanned = metrics.fanout_shards_total.labels(col.config.name).value
    try:
        threads = [threading.Thread(
            target=lambda c=c: got.__setitem__(c, ids_of(col.near_vector(
                world.queries[c], k=10, include_objects=False))))
            for c in range(32)]
        for t in threads:
            t.start()
        deadline = time.time() + 20.0
        while time.time() < deadline and sum(held) + len(b._queue) < 32:
            time.sleep(0.01)
        # ONE item a request on the drain; nothing on any shard's own
        assert sum(held) + len(b._queue) == 32
        assert not any(len(sb._queue) for s in col.shards.values()
                       for sb in s._query_batchers.values())
        assert not col._pool._threads and not got
    finally:
        gate.set()
        del b._dispatch
    for t in threads:
        t.join()
    assert [got[c] for c in range(32)] == serial
    assert b.batched_queries > b.dispatches     # they coalesced
    assert metrics.fanout_shards_total.labels(col.config.name).value \
        - fanned == 32 * SHARDS


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_two_dispatch_threads_whatever_the_shard_count(tmp_path, shards):
    rows = np.random.default_rng(shards).standard_normal(
        (256, DIM)).astype(np.float32)
    before = set(dispatch_threads())
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(
            klass(f"Two{shards}", shards=shards)))
        fill(col, rows)
        for q in rows[:8]:
            col.near_vector(q, k=5, include_objects=False)
        mine = [t.name for t in set(dispatch_threads()) - before]
        assert sorted(mine) == sorted(DISPATCH_THREADS)
        assert not any(s._query_batchers for s in col.shards.values())
        # a filtered request rides the shards' own batchers: two a shard
        col.near_vector(rows[0], k=5, include_objects=False,
                        where=Filter.where("bucket", Operator.LESS_THAN, 50))
        mine = [t.name for t in set(dispatch_threads()) - before]
        assert mine.count("query-batcher") == 1 + shards
    finally:
        db.close()
    deadline = time.time() + 15.0
    while time.time() < deadline and set(dispatch_threads()) - before:
        time.sleep(0.05)
    assert not set(dispatch_threads()) - before   # close() leaves no thread


def test_shards_on_four_devices_drain_in_one_dispatch(
        tmp_path, monkeypatch, fresh_placement):
    """Eight shards, two a device: ONE dispatch of the drain launches
    eight programs, the counter moves once a member under the member's
    device, and the block is uploaded once a device."""
    host = host_of(4, monkeypatch)
    rows = np.random.default_rng(4).standard_normal(
        (512, DIM)).astype(np.float32)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(klass("FourChips")))
        fill(col, rows)
        assert sorted(placement.label(s.device)
                      for s in col.shards.values()) == sorted(
            [placement.label(d) for d in host] * 2)
        col.near_vector(rows[0], k=10, include_objects=False)   # builds it
        b = col._drains[""].batcher
        bucket = metrics.batcher_compile_bucket

        def counted():
            return {placement.label(d): bucket.labels(
                b="1", k="16", device=placement.label(d)).value
                for d in host}

        puts = block_uploads(monkeypatch)
        was, dispatches = counted(), b.dispatches
        found = col.near_vector(rows[1], k=10, include_objects=False)
        assert ids_of(found)[0] == 1
        assert b.dispatches - dispatches == 1
        assert {d: n - was[d] for d, n in counted().items()} == \
            {placement.label(d): 2 for d in host}
        assert sorted(puts, key=lambda d: d.id) == list(host)
        # the dispatch record names no one chip, the members span four
        assert b._device_label == ""
    finally:
        db.close()


def test_on_one_visible_device_the_block_is_uploaded_once(
        tmp_path, monkeypatch, fresh_placement):
    (only,) = host_of(1, monkeypatch)
    rows = np.random.default_rng(1).standard_normal(
        (512, DIM)).astype(np.float32)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(klass("OneChipDrain")))
        fill(col, rows)
        col.near_vector(rows[0], k=10, include_objects=False)
        puts = block_uploads(monkeypatch)
        was = metrics.batcher_compile_bucket.labels(
            b="1", k="16", device=placement.label(only)).value
        assert ids_of(col.near_vector(rows[2], k=10,
                                      include_objects=False))[0] == 2
        assert puts == [only]
        assert metrics.batcher_compile_bucket.labels(
            b="1", k="16", device=placement.label(only)).value - was == SHARDS
        assert col._drains[""].batcher._device_label == placement.label(only)
    finally:
        db.close()


# -- what keeps the shards' own batchers ---------------------------------------------


def test_filtered_allow_listed_and_hybrid_requests_take_the_shards(world):
    col = world.cols["cosine"]
    q = world.queries[40]
    where = Filter.where("bucket", Operator.LESS_THAN, 50)
    allowed = (np.arange(ROWS) % 100) < 50
    allow_by_shard = {n: s.allow_mask(where) for n, s in col.shards.items()}

    def moved(fn):
        was = route(col, "drain"), route(col, "shards")
        out = fn()
        return out, (route(col, "drain") - was[0],
                     route(col, "shards") - was[1])

    found, by = moved(lambda: col.near_vector(
        q, k=10, include_objects=False, where=where))
    assert by == (0, 1)
    want, _ = top_k(world.rows, q, 10, "cosine", allowed)
    assert ids_of(found) == want.tolist()
    listed, by = moved(lambda: col.near_vector(
        q, k=10, include_objects=False, allow_list_by_shard=allow_by_shard))
    assert by == (0, 1) and pairs(listed) == pairs(found)
    # hybrid under a filter: its dense leg carries the shards' allow lists
    fused, by = moved(lambda: col.hybrid(
        "word3", vector=q, alpha=0.5, k=10, where=where,
        include_objects=False))
    assert by == (0, 1) and len(fused) == 10
    with by_shards(col):
        again = col.hybrid("word3", vector=q, alpha=0.5, k=10, where=where,
                           include_objects=False)
    assert [(r.uuid, r.score) for r in again] == \
        [(r.uuid, r.score) for r in fused]
    # without one its dense leg is a plain near_vector: the same answer
    # from the drain as from the shards
    plain, by = moved(lambda: col.hybrid(
        "word3", vector=q, alpha=0.5, k=10, include_objects=False))
    assert by == (1, 0)
    with by_shards(col):
        again = col.hybrid("word3", vector=q, alpha=0.5, k=10,
                           include_objects=False)
    assert [(r.uuid, r.score) for r in again] == \
        [(r.uuid, r.score) for r in plain]


def test_one_shard_and_unbatched_collections_build_no_drain(tmp_path):
    rows = np.random.default_rng(9).standard_normal(
        (256, DIM)).astype(np.float32)
    db = Database(str(tmp_path))
    try:
        one = db.create_collection(config_from_json(
            klass("OneShard", shards=1)))
        fill(one, rows)
        assert ids_of(one.near_vector(rows[3], k=5,
                                      include_objects=False))[0] == 3
        assert not one._drains
        assert route(one, "drain") == route(one, "shards") == 0
        # QUERY_DYNAMIC_BATCHING=false, as a shard reads it at its start
        off = db.create_collection(config_from_json(klass("Unbatched")))
        for shard in off.shards.values():
            shard.dynamic_batching = False
        fill(off, rows)
        assert ids_of(off.near_vector(rows[3], k=5,
                                      include_objects=False))[0] == 3
        assert not off._drains
        assert (route(off, "drain"), route(off, "shards")) == (0, 1)
    finally:
        db.close()


# -- read-your-writes, compress, shards that come and go ------------------------------


def test_queued_vectors_are_found(tmp_path):
    """Async indexing with the workers taken away: what was acknowledged
    and not yet indexed is in each shard's snapshot, taken before the
    enqueue on the drain, and is merged into that shard's answer."""
    from weaviate_tpu.runtime.index_queue import IndexQueue

    rng = np.random.default_rng(7)
    rows = rng.standard_normal((400, DIM)).astype(np.float32)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(klass("Queued", "l2-squared")))
        fill(col, rows[:200])
        pinned = {}
        for name, shard in col.shards.items():
            shard.async_indexing = True
            idx = shard.vector_indexes[""]
            old = shard._index_queues.get("")
            if old is not None:
                old.stop()
            pinned[name] = shard._index_queues[""] = IndexQueue(
                idx, start_worker=False)
        fill(col, rows[200:], first=200)
        assert sum(q.size() for q in pinned.values()) == 200
        drained = route(col, "drain")
        for i in (0, 150, 250, 399):
            found = col.near_vector(rows[i], k=5, include_objects=False)
            assert ids_of(found)[0] == i
            want, _ = ref.top_k(rows, rows[i], 5, "l2-squared")
            assert ids_of(found) == want.tolist()
        assert route(col, "drain") - drained == 4
        col.delete_object(_uuid(250))       # queued, then deleted
        assert 250 not in ids_of(col.near_vector(rows[250], k=5,
                                                 include_objects=False))
        for q in pinned.values():
            q.drain()
        assert ids_of(col.near_vector(rows[399], k=1,
                                      include_objects=False)) == [399]
    finally:
        db.close()


def _hammer(col, queries, stop, errors, answers):
    n = 0
    while not stop.is_set():
        q = n % len(queries)
        try:
            answers.append((q, ids_of(col.near_vector(
                queries[q], k=10, include_objects=False))))
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        n += 1


def test_compress_of_one_member_under_traffic(tmp_path):
    """A member's store is swapped for a compressed one under the drain:
    the entry points are resolved a dispatch, the member then takes the
    block from the host (``takes_device_queries`` is false), and every
    answer before, during and after has the true neighbour first."""
    rng = np.random.default_rng(11)
    centres = rng.standard_normal((32, DIM)).astype(np.float32)
    rows = (centres[rng.integers(0, 32, 2048)] + 0.3 * rng.standard_normal(
        (2048, DIM))).astype(np.float32)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(klass("Squeeze")))
        fill(col, rows)
        stop, errors, got = threading.Event(), [], []
        threads = [threading.Thread(target=_hammer, args=(
            col, rows[:64], stop, errors, got)) for _ in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        member = list(col.shards.values())[3].vector_indexes[""]
        assert member.takes_device_queries
        member.compress("bq")
        assert not member.takes_device_queries
        after, deadline = len(got), time.time() + 60.0
        while time.time() < deadline and len(got) < after + 16:
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join()
        assert not errors and len(got) >= after + 16
        assert all(ids[0] == q for q, ids in got)
        assert len(col._drains) == 1    # the same drain all along
    finally:
        db.close()


def test_a_shard_dropped_and_loaded_again_under_traffic(tmp_path):
    """The set of local shards changes under the drain: it is rebuilt
    over the new set, a request that was queued on the retired one is
    answered by the shards' own batchers, and nobody sees an error."""
    rows = np.random.default_rng(13).standard_normal(
        (1024, DIM)).astype(np.float32)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(klass("Comes")))
        fill(col, rows)
        col.near_vector(rows[0], k=10, include_objects=False)
        first = col._drains[""]
        stop, errors, got = threading.Event(), [], []
        threads = [threading.Thread(target=_hammer, args=(
            col, rows[:64], stop, errors, got)) for _ in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        for name in ("shard-2", "shard-5"):
            # as a node that gives a shard up and takes it back: the
            # Shard object is closed and a new one opened from its files
            with col._lock:
                gone = col.shards.pop(name)
            time.sleep(0.05)
            gone.close()
        deadline = time.time() + 60.0
        while time.time() < deadline and col._drains[""] is first:
            time.sleep(0.05)    # the next request rebuilds it
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join()
        assert not [e for e in errors
                    if not isinstance(e, (RuntimeError, OSError))]
        assert col._drains[""] is not first
        first.batcher._worker.join(timeout=10.0)    # it was stopped
        assert not first.batcher._worker.is_alive()
        tail = [ids for _q, ids in got[-8:]]
        assert tail and all(len(ids) == 10 for ids in tail)
        for i in (1, 2, 3):
            assert ids_of(col.near_vector(rows[i], k=10,
                                          include_objects=False))[0] == i
    finally:
        db.close()


def test_a_drain_retired_under_a_request_answers_through_the_shards(world):
    """The race of a rebuild: the request holds a drain that is stopped
    before its item is served. The refusal is typed, and the request
    takes the shards' own batchers."""
    col = world.cols["cosine"]
    q = world.queries[41]
    want = pairs(col.near_vector(q, k=10, include_objects=False))
    retired = col._drains[""]
    retired.stop()
    with pytest.raises(BatcherStopped):
        retired.batcher.search(q, 10)
    col._drain_for = lambda *_a: retired
    was = route(col, "drain"), route(col, "shards")
    try:
        assert pairs(col.near_vector(q, k=10, include_objects=False)) == want
    finally:
        del col._drain_for
    assert (route(col, "drain") - was[0], route(col, "shards") - was[1]) \
        == (0, 1)
    # the next request finds the retired one stopped and builds another
    with col._lock:
        col._drains.pop("")
    assert pairs(col.near_vector(q, k=10, include_objects=False)) == want
    assert col._drains[""] is not retired


def test_a_spent_deadline_leaves_nothing_on_the_drain(world):
    col = world.cols["l2-squared"]
    col.near_vector(world.queries[0], k=10, include_objects=False)
    b = col._drains[""].batcher
    gate = threading.Event()
    dispatch = b._dispatch
    b._dispatch = lambda drained, rec=None: (gate.wait(),
                                             dispatch(drained, rec))[1]
    try:
        first = threading.Thread(target=col.near_vector, args=(
            world.queries[1],), kwargs={"k": 10, "include_objects": False})
        first.start()
        deadline = time.time() + 20.0
        while time.time() < deadline and b._queue:
            time.sleep(0.01)
        with retry.deadline(0.2), pytest.raises(retry.DeadlineExceeded):
            col.near_vector(world.queries[2], k=10, include_objects=False)
        assert not b._queue
    finally:
        gate.set()
        del b._dispatch
    first.join()
