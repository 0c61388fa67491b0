"""A filter's device operands, built once a clause and kept (PR 40).

``FlatIndex`` keeps, on the device, the packed bitmap row and the
gathered slot list of every allow mask that cannot change
(engine/filter_operands.py). Held here: the operands are bit-equal to
what the host path packs; a write (add, delete, grow, compress, reload)
makes the next search a miss with the right answer; an acknowledged
write is in the next answer of the served path, also under threads; a
writeable array is never kept; the byte bound and the HBM ledger; the
gathered path's one program against its eager ops; the counter and the
``mask_pack`` stage.
"""

import gc
import os
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import filter_reference  # noqa: E402 — the evaluation before the memo, tests/

from weaviate_tpu.db.database import Database  # noqa: E402
from weaviate_tpu.engine import filter_operands  # noqa: E402
from weaviate_tpu.engine.flat import FlatIndex  # noqa: E402
from weaviate_tpu.engine.store import AllowBits, AllowSlots  # noqa: E402
from weaviate_tpu.filters import Filter, Operator  # noqa: E402
from weaviate_tpu.ops.candidates import shared_candidates_topk  # noqa: E402
from weaviate_tpu.ops.pallas_kernels import (  # noqa: E402
    mask_pad_cols, pack_allow_bitmask)
from weaviate_tpu.runtime import hbm_ledger, tailboard  # noqa: E402
from weaviate_tpu.runtime.metrics import (  # noqa: E402
    dispatch_stage_seconds, filter_operand_total)
from weaviate_tpu.runtime.query_batcher import QueryBatcher  # noqa: E402
from weaviate_tpu.schema.config import (  # noqa: E402
    CollectionConfig, DataType, Property, VectorConfig)

DIM, ROWS, CAPACITY = 32, 700, 1024
KINDS = {
    "plain": {},
    "bq": dict(quantization="bq"),
    "pq": dict(quantization="pq", pq_centroids=256, pq_segments=16),
    "sq": dict(quantization="sq"),
}
RESULTS = ("hit", "miss", "shared", "uncached")


def counted():
    """-> since(path) -> {result: increments since this call}."""
    def now(path):
        return {r: filter_operand_total.labels(path, r).value
                for r in RESULTS}

    base = {p: now(p) for p in ("bitmask", "gathered")}

    def since(path):
        return {r: int(v - base[path][r]) for r, v in now(path).items()
                if v != base[path][r]}

    return since


def frozen(mask: np.ndarray) -> np.ndarray:
    """A mask as the filter memo hands it out: read-only."""
    mask = np.array(mask, dtype=bool)
    mask.flags.writeable = False
    return mask


def corpus(seed=5, rows=ROWS, dim=DIM):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((rows, dim)).astype(np.float32), rng


def make_index(kind="plain", rows=ROWS, capacity=CAPACITY, **kw):
    """Doc ids are not slots (id = 3 * slot + 7) and every 11th row is
    deleted, so the slot table is neither dense nor the identity."""
    x, rng = corpus(rows=rows)
    idx = FlatIndex(dim=DIM, capacity=capacity, chunk_size=256,
                    **KINDS[kind], **kw)
    if kind in ("pq", "sq"):
        idx.store.train(x[:512])
    ids = 3 * np.arange(rows) + 7
    idx.add_batch(ids, x)
    idx.delete(*ids[::11].tolist())
    return idx, x, ids, rng


def doc_masks(rng, ids, shares=(0.02, 0.3, 0.6, 0.97)):
    """Read-only masks over the doc-id space, one a share."""
    size = int(ids.max()) + 1
    return [frozen(rng.random(size) < s) for s in shares]


def host_block(idx, lists):
    """The [B, capacity] bool block the host path packs."""
    block = np.ones((len(lists), idx.store.capacity), dtype=bool)
    for r, a in enumerate(lists):
        if a is not None:
            block[r] = idx._allow_mask(a)
    return block


def operand(idx, lists):
    with idx._lock:
        op = idx._bitmask_operand(lists)
    assert isinstance(op, AllowBits)
    return np.asarray(op.bits)


def exact_reference(x, ids, live, q, allow, k):
    """Unfiltered, then masked: exact distances over every live doc, the
    allowed ones' k nearest. -> doc ids."""
    d = ((x - q[None, :]) ** 2).sum(-1)
    ok = live & allow[ids]
    order = np.argsort(np.where(ok, d, np.inf), kind="stable")
    return ids[order[: min(k, int(ok.sum()))]]


# -- (a) bit-equal operands ---------------------------------------------------

@pytest.fixture(scope="module")
def indexes():
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = make_index(kind)
        return made[kind]

    return get


@pytest.mark.parametrize("b_pad", [1, 2, 4, 8, 16, 32])
@pytest.mark.parametrize("kind", list(KINDS))
def test_operands_equal_the_packed_translated_block(indexes, kind, b_pad):
    """Through the cache (miss, then hit) and through the in-dispatch
    dedupe, a dispatch's ``allow_bits`` are ``pack_allow_bitmask`` of the
    translated block: filtered, unfiltered and padded rows mixed, one
    mask on several rows, a writeable array among them."""
    idx, _x, ids, _ = indexes(kind)
    rng = np.random.default_rng(b_pad)
    pool = doc_masks(rng, ids) + [rng.random(int(ids.max()) + 1) < 0.5]
    b = max(1, b_pad - b_pad // 4)           # the rest is padding
    lists = [pool[j] if j < len(pool) else None
             for j in rng.integers(0, len(pool) + 2, b)]
    lists += [None] * (b_pad - b)
    want = pack_allow_bitmask(host_block(idx, lists),
                              mask_pad_cols(idx.store.capacity))
    since = counted()
    first = operand(idx, lists)
    again = operand(idx, lists)
    assert first.dtype == np.uint32 and first.shape == want.shape
    assert np.array_equal(first, want) and np.array_equal(again, want)
    filtered = sum(a is not None for a in lists)
    distinct = len({id(a) for a in lists if a is not None})
    writeable = sum(a is pool[-1] for a in lists) > 0
    got = since("bitmask")
    assert sum(got.values()) == 2 * filtered
    assert got.get("shared", 0) == 2 * (filtered - distinct)
    assert got.get("uncached", 0) == 2 * writeable
    assert got.get("hit", 0) >= distinct - writeable   # the second pass


@pytest.mark.parametrize("kind", list(KINDS))
def test_answers_equal_the_host_paths(indexes, kind):
    """Same coalescing, same program, same answers: the index's search
    over per-query lists returns what the store returns for the host
    path's bool block, bit for bit."""
    idx, _x, ids, rng = indexes(kind)
    masks = doc_masks(rng, ids)
    lists = [masks[1], None, masks[2], masks[1], masks[3]]
    q = rng.standard_normal((len(lists), DIM)).astype(np.float32)
    for _ in range(2):                       # a miss, then a hit
        got_ids, got_d = idx.search_by_vector_batch(q, 7, lists)
        want_d, want_slots = idx.store.search(q, 7, host_block(idx, lists))
        want_ids = np.where(want_slots >= 0,
                            idx._slot_to_id_safe(want_slots), -1)
        assert np.array_equal(got_ids, want_ids)
        assert np.array_equal(got_d, want_d)


# -- (b) invalidation ---------------------------------------------------------

def _add(idx, x, ids, live):
    more = corpus(seed=9, rows=40)[0]
    new_ids = 3 * np.arange(ROWS, ROWS + 40) + 7
    idx.add_batch(new_ids, more)
    return (idx, np.concatenate([x, more]), np.concatenate([ids, new_ids]),
            np.concatenate([live, np.ones(40, bool)]))


def _delete(idx, x, ids, live):
    gone = np.flatnonzero(live)[:25]
    idx.delete(*ids[gone].tolist())
    live = live.copy()
    live[gone] = False
    return idx, x, ids, live


def _grow(idx, x, ids, live):
    n = CAPACITY                              # past the store's capacity
    more = corpus(seed=10, rows=n)[0]
    new_ids = 3 * np.arange(ROWS, ROWS + n) + 7
    idx.add_batch(new_ids, more)
    assert idx.store.capacity > CAPACITY
    return (idx, np.concatenate([x, more]), np.concatenate([ids, new_ids]),
            np.concatenate([live, np.ones(n, bool)]))


def _compress(idx, x, ids, live):
    idx.compress("sq")
    return idx, x, ids, live


def _reload(idx, x, ids, live):
    return FlatIndex.restore(idx.snapshot()), x, ids, live


MUTATIONS = {"add": _add, "delete": _delete, "grow": _grow,
             "compress": _compress, "reload": _reload}


@pytest.mark.parametrize("mutation", list(MUTATIONS))
def test_a_write_makes_the_next_search_a_miss(mutation):
    """After anything that moves a slot, the store's capacity or the
    store itself, no kept operand is served: the next filtered search
    builds its operands again (``miss``) and answers as the exact
    unfiltered-then-masked reference does."""
    idx, x, ids, rng = make_index()
    live = np.ones(ROWS, bool)
    live[::11] = False
    size = 3 * (ROWS + CAPACITY) + 8          # covers every id ever added
    broad, narrow = frozen(rng.random(size) < 0.5), \
        frozen(rng.random(size) < 0.03)
    q = x[:3] + 0.01
    k = 5

    def search(index, x, ids, live):
        got, _ = index.search_by_vector_batch(q, k, [broad, broad, broad])
        solo, _ = index.search_by_vector_batch(q[:1], k, narrow)
        for r in range(3):
            want = exact_reference(x, ids, live, q[r], broad, k)
            assert set(got[r][got[r] >= 0].tolist()) == set(want.tolist())
        want = exact_reference(x, ids, live, q[0], narrow, k)
        assert set(solo[0][solo[0] >= 0].tolist()) == set(want.tolist())

    search(idx, x, ids, live)
    since = counted()
    search(idx, x, ids, live)
    assert since("bitmask") == {"hit": 1, "shared": 2}
    assert since("gathered") == {"hit": 1}
    gen = idx._slot_gen
    idx, x, ids, live = MUTATIONS[mutation](idx, x, ids, live)
    if mutation != "reload":
        assert idx._slot_gen > gen
        assert idx._operands.resident == (0, 0)
    since = counted()
    search(idx, x, ids, live)
    assert since("bitmask") == {"miss": 1, "shared": 2}
    # a compressed store has no gathered cutover: the host path, as ever
    assert since("gathered") == (
        {"uncached": 1} if mutation == "compress" else {"miss": 1})


# -- (c) read your writes, through the served path ----------------------------

N = 400  # doc i has bucket i % 100


def _uuid(i: int) -> str:
    return f"00000000-0000-0000-0000-{i:012d}"


def _lt(p):
    return Filter.where("bucket", Operator.LESS_THAN, p)


@pytest.fixture
def items(tmp_path):
    db = Database(str(tmp_path))
    col = db.create_collection(CollectionConfig(
        name="Item", vectors=[VectorConfig()],
        properties=[Property(name="bucket", data_type=DataType.INT)]))
    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((N, 8)).astype(np.float32)
    col.batch_put([{"uuid": _uuid(i), "properties": {"bucket": i % 100},
                    "vector": vecs[i]} for i in range(N)])
    yield col, list(col.shards.values())[0], vecs
    db.close()


def _served(col, shard, vecs, q, where, k=5):
    """The served answer, held to the reference: the filter as it was
    evaluated before the memo, then the exact nearest among the allowed
    objects that exist."""
    got = [r.uuid for r in col.near_vector(q, k=k, where=where)]
    with shard._lock:
        allow = filter_reference.reference_mask(where, shard._inverted,
                                                shard.doc_id_space)
    idx = shard.vector_indexes[""]
    doc_ids = np.flatnonzero(allow)
    doc_ids = doc_ids[[idx.contains(d) for d in doc_ids]]
    rows = idx.store.get(idx.slots_for_doc_ids(doc_ids))
    order = np.argsort(((rows - q[None, :]) ** 2).sum(-1), kind="stable")
    want = [shard.object_by_doc_id(int(d)).uuid
            for d in doc_ids[order[:k]]]
    assert got == want
    return got


# p = 50 allows 200 of 400 rows: a row of a coalesced dispatch's bitmask;
# p = 1 allows 4 <= capacity / 64: solo, the gathered slot list
@pytest.mark.parametrize("p, path", [(50, "bitmask"), (1, "gathered")])
def test_read_your_writes_single_thread(items, p, path):
    col, shard, vecs = items
    q = vecs[7] + 0.5
    _served(col, shard, vecs, q, _lt(p))
    since = counted()
    _served(col, shard, vecs, q, _lt(p))
    assert since(path) == {"hit": 1}          # the clause's operand is kept
    new = _uuid(N + 1)
    col.put_object({"bucket": 0}, vector=q, uuid=new)      # matches, at 0
    since = counted()
    assert _served(col, shard, vecs, q, _lt(p))[0] == new
    assert since(path) == {"miss": 1}
    assert col.delete_object(new)
    since = counted()
    assert new not in _served(col, shard, vecs, q, _lt(p))
    assert since(path) == {"miss": 1}
    since = counted()
    _served(col, shard, vecs, q, _lt(p))
    assert since(path) == {"hit": 1}


def test_read_your_writes_under_threads(items):
    """Eight threads search one clause while a writer puts and deletes an
    object that matches it, at distance 0 of the query. A search that no
    write began or ended during (the writer's state read before and after
    it is the same) holds the object if and only if the last acknowledged
    write was the put."""
    col, shard, vecs = items
    q = (vecs[11] + 0.25).astype(np.float32)
    new = _uuid(N + 2)
    state = ["absent", 0]          # what the last ACKNOWLEDGED write left
    stop = threading.Event()
    checked = {"present": 0, "absent": 0}
    wrong = []
    lock = threading.Lock()

    def writer():
        seq = 0
        while not stop.is_set():
            seq += 1
            state[:] = ["flux", seq]
            col.put_object({"bucket": 3}, vector=q, uuid=new)
            state[:] = ["present", seq]
            time.sleep(0.02)
            state[:] = ["flux", seq]
            col.delete_object(new)
            state[:] = ["absent", seq]
            time.sleep(0.02)

    def searcher(p):
        while not stop.is_set():
            before = tuple(state)
            got = [r.uuid for r in col.near_vector(q, k=3, where=_lt(p))]
            if before != tuple(state) or before[0] == "flux":
                continue
            with lock:
                checked[before[0]] += 1
                if (new in got) != (before[0] == "present"):
                    wrong.append((before, got))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=searcher, args=(p,))
                   for p in (50, 50, 50, 50, 5, 5, 99, 99)]
        threads.append(threading.Thread(target=writer))
        for t in threads:
            t.start()
        deadline = time.time() + 20.0
        while time.time() < deadline and min(checked.values()) < 10:
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=20.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not wrong, wrong[:3]
    assert min(checked.values()) >= 10, checked


# -- (d) a writeable array is never kept --------------------------------------

@pytest.mark.parametrize("shared", [False, True])
def test_a_writeable_mask_is_never_kept(shared):
    idx, x, ids, rng = make_index()
    mask = np.zeros(int(ids.max()) + 1, dtype=bool)
    near, far = ids[1], ids[2]                # neither is a deleted row
    mask[[near, far]] = True
    q = x[1:2]
    lists = mask if shared else [mask]
    since = counted()
    first, _ = idx.search_by_vector_batch(q, 1, lists)
    mask[near] = False                        # the caller's array moves on
    second, _ = idx.search_by_vector_batch(q, 1, lists)
    assert first[0, 0] == near and second[0, 0] == far
    assert since("gathered" if shared else "bitmask") == {"uncached": 2}
    assert idx._operands is None or idx._operands.resident[0] == 0
    # and a read-only VIEW of an array somebody can still write to
    view = mask[:]
    view.flags.writeable = False
    assert not filter_operands.stable_mask(view)
    assert filter_operands.stable_mask(frozen(mask))


# -- (e) the byte bound and the ledger ----------------------------------------

def test_the_lru_holds_its_byte_bound_and_the_ledger_follows(monkeypatch):
    owner = dict(collection="OperandBound", shard="s0", tenant="")
    with hbm_ledger.owner(**owner):
        idx, x, ids, rng = make_index()
    row = mask_pad_cols(idx.store.capacity) // 8      # bytes a packed row
    monkeypatch.setattr(filter_operands, "OPERAND_CACHE_MAX_BYTES", 4 * row)

    def booked():
        return hbm_ledger.ledger.shard_component_bytes(
            "OperandBound", "s0").get("allow_bitmask", 0)

    masks = doc_masks(rng, ids, shares=[0.3 + 0.05 * j for j in range(8)])
    q = x[:1]
    for m in masks:
        idx.search_by_vector_batch(q, 3, [m])
        gc.collect()                  # a dispatch's transient bits go
        entries, nbytes = idx._operands.resident
        assert nbytes <= 4 * row and booked() == nbytes
    assert entries == 4               # four rows fit; no unfiltered row
    since = counted()
    idx.search_by_vector_batch(q, 3, [masks[-1]])     # most recent: kept
    idx.search_by_vector_batch(q, 3, [masks[0]])      # least: evicted
    assert since("bitmask") == {"hit": 1, "miss": 1}
    idx.delete(int(ids[1]))                           # a write drops all
    assert idx._operands.resident == (0, 0) and booked() == 0
    idx.search_by_vector_batch(q, 3, [masks[0]])
    assert booked() == row
    del idx
    gc.collect()
    assert booked() == 0              # the index went, its bytes with it


# -- (f) the gathered path's one program --------------------------------------

@pytest.mark.parametrize("metric", ["l2-squared", "cosine", "dot"])
def test_gathered_program_equals_its_eager_ops(metric):
    """``jit_shared_candidates_topk`` returns the ids and distances of
    the ops it fused, run one by one (the path as it stood), bit for
    bit: dead slots, slots past the store and -1 padding included."""
    rng = np.random.default_rng(2)
    n, bucket, k = 512, 128, 6
    rows = jnp.asarray(rng.standard_normal((n, DIM)), jnp.float32)
    norms = jnp.sum(rows * rows, axis=-1)
    valid = jnp.asarray(rng.random(n) < 0.8)
    slots = np.full(bucket, -1, np.int32)
    slots[:40] = np.sort(rng.choice(n, 40, replace=False))
    slots[40] = n + 5                          # past the store: dead
    q = jnp.asarray(rng.standard_normal((3, DIM)), jnp.float32)
    kw = dict(row_norms=norms, valid=valid, selection="exact")
    got = shared_candidates_topk(q, jnp.asarray(slots), rows, k, metric, **kw)
    want = shared_candidates_topk.__wrapped__(
        q, jnp.asarray(slots), rows, k, metric, **kw)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    ids = np.asarray(got[1])
    assert set(ids[ids >= 0].tolist()) <= set(
        slots[:40][np.asarray(valid)[slots[:40]]].tolist())
    module = shared_candidates_topk.lower(
        q, jnp.asarray(slots), rows, k, metric, **kw
    ).as_text().split("module @")[1].split()[0]
    assert module == "jit_shared_candidates_topk"


def test_a_kept_slot_list_is_what_the_store_would_list():
    idx, x, ids, rng = make_index()
    narrow = doc_masks(rng, ids, shares=[0.05])[0]
    with idx._lock:
        op = idx._shared_operand(narrow)
        again = idx._shared_operand(narrow)
    assert isinstance(op, AllowSlots) and again.slots is op.slots
    allowed = np.flatnonzero(idx._allow_mask(narrow))
    slots = np.asarray(op.slots)
    assert op.count == len(allowed) and len(slots) == 128
    assert np.array_equal(slots[:op.count], allowed)
    assert np.all(slots[op.count:] == -1)
    # too broad for the cut (capacity / 8): a slot mask, nothing kept
    broad = doc_masks(rng, ids, shares=[0.6])[0]
    with idx._lock:
        assert isinstance(idx._shared_operand(broad), np.ndarray)
    assert idx._operands.resident[0] == 1


# -- (g) the counter and the stage --------------------------------------------

def test_counter_results_and_mask_pack_on_a_dispatch_that_only_hits():
    """hit, miss, shared and uncached each occur, one increment a
    filtered row; and a coalesced dispatch whose rows all hit still
    stamps its ``mask_pack`` stage (the metric must not fall silent)."""
    idx, x, ids, rng = make_index()
    kept, other = doc_masks(rng, ids, shares=[0.4, 0.7])
    loose = np.array(other)                    # writeable
    q = x[:4]

    def stage_count():
        tailboard.flush()
        return dispatch_stage_seconds.labels("flat", "mask_pack").count

    qb = QueryBatcher(idx.search_by_vector_batch,
                      supports_filter_batching=True,
                      capacity_fn=lambda: idx.store.capacity,
                      count_fn=idx.allowed_count, kind="flat")
    try:
        since = counted()
        idx.search_by_vector_batch(q, 3, [kept, kept, loose, None])
        assert since("bitmask") == {"miss": 1, "shared": 1, "uncached": 1}
        since = counted()
        idx.search_by_vector_batch(q, 3, [kept, None, None, None])
        assert since("bitmask") == {"hit": 1}
        before = stage_count()
        since = counted()
        got_ids, _ = qb.search(q[0], 3, allow=kept)     # through a worker
        assert since("bitmask") == {"hit": 1}
        assert stage_count() == before + 1
    finally:
        qb.stop()
    want, _ = idx.search_by_vector_batch(q[:1], 3, [kept])
    assert np.array_equal(got_ids, want[0])
    # the count the batcher's solo cut reads is the entry's
    assert idx.allowed_count(kept) == int(np.count_nonzero(kept))
    assert idx._operands.get(kept, (idx._slot_gen, idx.store.capacity)
                             ).doc_count == int(np.count_nonzero(kept))
    assert idx.allowed_count(ids[:9]) == 9
