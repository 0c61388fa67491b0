"""Batched filtered search: per-query allow bitmasks inside the scan.

Parity contract (ISSUE 3): bitmask-batched filtered top-k must match a
NumPy masked-argsort reference exactly across metrics / storage dtypes /
selectivities — including empty allow lists and k > allowed-count — and
the QueryBatcher must serve a mixed filtered/unfiltered drain as ONE
device dispatch padded to pow2 buckets.
"""

import threading
import time

import numpy as np
import pytest

from weaviate_tpu.ops.pallas_kernels import (
    MASK_BLOCK,
    mask_pad_cols,
    pack_allow_bitmask,
    pack_allow_bitmask_jnp,
    unpack_allow_bitmask,
)

DEAD = 1e37  # distances >= this are masked/dead slots


def masked_ref(q, corpus, mask, k, metric="l2-squared"):
    """NumPy masked-argsort reference: (ids, dists) of the <=k allowed
    rows, ascending, ties by lower index (lax.top_k convention)."""
    if metric == "l2-squared":
        d = ((q[None, :] - corpus) ** 2).sum(-1)
    elif metric == "dot":
        d = -(corpus @ q)
    else:  # cosine: both sides normalized
        qn = q / max(np.linalg.norm(q), 1e-30)
        cn = corpus / np.maximum(
            np.linalg.norm(corpus, axis=1, keepdims=True), 1e-30)
        d = 1.0 - cn @ qn
    d = np.where(mask, d.astype(np.float32), np.inf)
    order = np.argsort(d, kind="stable")[:k]
    live = np.isfinite(d[order])
    return order[live], d[order][live]


def test_pack_unpack_roundtrip(rng):
    for cols in (1, 31, 32, 500, 512, 513, 1300):
        allow = rng.random((3, cols)) < 0.4
        bits = pack_allow_bitmask(allow)
        assert bits.dtype == np.uint32
        assert bits.shape == (3, mask_pad_cols(cols) // 32)
        back = np.asarray(unpack_allow_bitmask(bits, cols))
        assert np.array_equal(back, allow), cols
        # traceable packer agrees with the host packer
        import jax.numpy as jnp

        bits_dev = np.asarray(pack_allow_bitmask_jnp(jnp.asarray(allow)))
        assert np.array_equal(bits_dev, bits), cols


@pytest.mark.parametrize("metric", ["l2-squared", "dot", "cosine"])
def test_served_scan_masked_parity(rng, metric):
    """Per-query allow bitmasks through the scan every flat store serves
    (``"approx"``, the XLA unpack and where), against numpy."""
    import jax.numpy as jnp

    from weaviate_tpu.ops.topk import chunked_topk

    b, n, d, k = 6, 1100, 48, 9
    q = rng.standard_normal((b, d)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    allow = rng.random((b, n)) < 0.25
    allow[0, :] = True          # unfiltered row
    allow[1, :] = False         # empty allow list
    allow[2, :3] = True
    allow[2, 3:] = False        # k > allowed-count
    bits = jnp.asarray(pack_allow_bitmask(allow))
    xin = x
    if metric == "cosine":
        xin = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True),
                             1e-30)
    fd, fi = chunked_topk(jnp.asarray(q), jnp.asarray(xin), k=k,
                          chunk_size=512, metric=metric,
                          selection="approx", allow_bits=bits)
    fd, fi = np.asarray(fd), np.asarray(fi)
    for r in range(b):
        ri, rd = masked_ref(q[r], x, allow[r], k, metric)
        assert np.array_equal(fi[r, :len(ri)], ri), (r, fi[r], ri)
        assert np.all(fd[r, len(ri):] >= DEAD)
        assert np.allclose(fd[r, :len(ri)], rd, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
def test_store_batched_mask_parity(rng, dtype_name):
    import jax.numpy as jnp

    from weaviate_tpu.engine.store import DeviceVectorStore

    b, n, d, k = 5, 700, 32, 7
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    st = DeviceVectorStore(dim=d, capacity=1024, chunk_size=256,
                           dtype=jnp.dtype(dtype_name))
    st.add(corpus)
    allow = rng.random((b, n)) < 0.3
    allow[1, :] = False
    allow[2, :2] = True
    allow[2, 2:] = False
    full = np.zeros((b, st.capacity), dtype=bool)
    full[:, :n] = allow
    dists, slots = st.search(q, k, allow_mask=full)
    # the reference scans what the store scans: rows rounded to the
    # storage dtype
    stored = np.asarray(jnp.asarray(corpus).astype(st.dtype),
                        dtype=np.float32)
    for r in range(b):
        ri, rd = masked_ref(q[r], stored, allow[r], k)
        live = dists[r] < DEAD
        assert live.sum() == len(ri), (r, slots[r])
        assert np.array_equal(slots[r][live], ri), r
        assert np.allclose(dists[r][live], rd, rtol=1e-3, atol=1e-3)


def test_store_shared_mask_broadcast(rng):
    """[1, capacity] and [capacity] masks are the same API; a [B, C] mask
    of identical rows returns the same results as the shared form."""
    from weaviate_tpu.engine.store import DeviceVectorStore

    b, n, d, k = 4, 400, 16, 6
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    st = DeviceVectorStore(dim=d, capacity=512)
    st.add(corpus)
    shared = np.zeros(st.capacity, dtype=bool)
    shared[:n] = rng.random(n) < 0.4
    d1, i1 = st.search(q, k, allow_mask=shared)
    d2, i2 = st.search(q, k, allow_mask=shared[None, :])
    d3, i3 = st.search(q, k, allow_mask=np.broadcast_to(
        shared, (b, st.capacity)))
    assert np.array_equal(i1, i2) and np.array_equal(i1, i3)
    assert np.allclose(d1, d2) and np.allclose(d1, d3)


@pytest.mark.parametrize("quant,centroids", [("bq", 16), ("pq", 16),
                                             ("pq", 256)])
def test_quantized_batched_mask_parity(rng, quant, centroids):
    """Per-query masks through the compressed scan kernels. With
    rescore_limit covering the whole corpus the exact host rescore makes
    results independent of scan approximations, so parity vs the NumPy
    masked reference is exact — and disallowed rows must never even
    appear as candidates."""
    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    b, n, d, k = 4, 450, 32, 6
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    st = QuantizedVectorStore(dim=d, capacity=512, quantization=quant,
                              pq_centroids=centroids, rescore_limit=100)
    if quant == "pq":
        st.train(corpus)
    st.add(corpus)
    allow = rng.random((b, n)) < 0.3
    allow[1, :] = False
    allow[2, :2] = True
    allow[2, 2:] = False
    full = np.zeros((b, st.capacity), dtype=bool)
    full[:, :n] = allow
    dists, slots = st.search(q, k, allow_mask=full)
    for r in range(b):
        ri, rd = masked_ref(q[r], corpus, allow[r], k)
        live = slots[r] >= 0
        assert live.sum() == len(ri), (quant, centroids, r)
        assert np.array_equal(slots[r][live], ri), (quant, centroids, r)
        assert np.allclose(dists[r][live], rd, rtol=1e-4, atol=1e-4)


def test_sharded_store_batched_mask(rng):
    """Mesh path: per-query masks shard column-wise, row-aligned with the
    corpus; each device packs its slice locally; the ICI merge is
    unchanged."""
    from weaviate_tpu.engine.store import DeviceVectorStore
    from weaviate_tpu.parallel.mesh import default_mesh

    mesh = default_mesh()
    if mesh is None:
        pytest.skip("needs the multi-device virtual mesh")
    b, n, d, k = 4, 600, 16, 5
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    st = DeviceVectorStore(dim=d, capacity=1024, chunk_size=64, mesh=mesh)
    st.add(corpus)
    allow = rng.random((b, n)) < 0.25
    allow[0, :] = False
    full = np.zeros((b, st.capacity), dtype=bool)
    full[:, :n] = allow
    dists, slots = st.search(q, k, allow_mask=full)
    for r in range(b):
        ri, _rd = masked_ref(q[r], corpus, allow[r], k)
        live = dists[r] < DEAD
        assert live.sum() == len(ri), r
        assert np.array_equal(slots[r][live], ri), r


def _make_batcher(idx):
    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    calls = []
    real = idx.search_by_vector_batch

    def counting(qs, k, allow=None):
        calls.append({"rows": len(qs), "k": k,
                      "filtered": allow is not None,
                      "per_query": isinstance(allow, (list, tuple))})
        return real(qs, k, allow)

    qb = QueryBatcher(counting, supports_filter_batching=True,
                      capacity_fn=lambda: idx.store.capacity)
    return qb, calls


def test_batcher_mixed_drain_one_dispatch(rng):
    """Mixed filtered + unfiltered requests drain into ONE device
    dispatch, padded to pow2 B and k buckets; every request still gets
    its own exact (per-filter) result."""
    from weaviate_tpu.engine.flat import FlatIndex

    n, d, k = 300, 16, 5
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    idx = FlatIndex(dim=d, capacity=512)
    idx.add_batch(np.arange(n), corpus)
    qb, calls = _make_batcher(idx)
    nreq = 11
    queries = rng.standard_normal((nreq, d)).astype(np.float32)
    allows = [None if j % 3 == 0 else
              np.flatnonzero(rng.random(n) < 0.3).astype(np.int64)
              for j in range(nreq)]

    # block the first dispatch so the rest reliably coalesce behind it
    gate = threading.Event()
    first = threading.Event()
    inner = qb._batch_fn

    def slow_first(qs, kk, allow=None):
        if not first.is_set():
            first.set()
            gate.wait(5.0)
        return inner(qs, kk, allow)

    qb._batch_fn = slow_first
    results = [None] * nreq

    def worker(j):
        results[j] = qb.search(queries[j], k, allows[j])

    threads = [threading.Thread(target=worker, args=(j,))
               for j in range(nreq)]
    threads[0].start()
    time.sleep(0.1)
    for t in threads[1:]:
        t.start()
    time.sleep(0.3)
    gate.set()
    for t in threads:
        t.join()
    qb.stop()

    # the queued-up 10 requests (mixed filtered/unfiltered) shared ONE
    # dispatch...
    coalesced = [c for c in calls if c["rows"] > 1]
    assert len(coalesced) == 1, calls
    assert coalesced[0]["filtered"] and coalesced[0]["per_query"]
    # ...padded to pow2 buckets (B and k)
    assert coalesced[0]["rows"] == 16, calls  # next_pow2(10)
    assert coalesced[0]["k"] == 8, calls      # next_pow2(5)
    assert qb.filtered_batched > 0

    # exact per-request results vs the direct path
    for j in range(nreq):
        ids, dists = results[j]
        al = None if allows[j] is None else [allows[j]]
        ref_i, _ = idx.search_by_vector_batch(
            queries[j][None, :], k,
            al if al is not None else None)
        got = np.asarray(ids)
        want = ref_i[0]
        assert np.array_equal(got[got >= 0], want[want >= 0]), j
        if allows[j] is not None:
            live = got[got >= 0]
            assert np.isin(live, allows[j]).all(), j


def test_batcher_selective_filter_goes_solo(rng):
    """The per-dispatch selectivity heuristic routes a highly selective
    filter (<= capacity/64 allowed) to a solo dispatch where the store's
    gathered cutover applies; broad filters stay batched."""
    from weaviate_tpu.engine.flat import FlatIndex

    n, d, k = 300, 16, 4
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    idx = FlatIndex(dim=d, capacity=512)
    idx.add_batch(np.arange(n), corpus)
    qb, calls = _make_batcher(idx)

    tiny = np.array([3, 7], dtype=np.int64)       # 2 <= 512 // 64
    broad = np.flatnonzero(rng.random(n) < 0.5).astype(np.int64)
    # drive _dispatch directly — no threads needed to pin the drain
    from weaviate_tpu.runtime.query_batcher import _Pending

    pend = [
        _Pending(rng.standard_normal(d).astype(np.float32), k, tiny),
        _Pending(rng.standard_normal(d).astype(np.float32), k, broad),
        _Pending(rng.standard_normal(d).astype(np.float32), k, None),
    ]
    qb._dispatch(pend)
    assert all(p.event.is_set() and p.error is None for p in pend)
    solo = [c for c in calls if c["rows"] == 1]
    coal = [c for c in calls if c["rows"] > 1]
    assert len(solo) == 1 and not solo[0]["per_query"]  # tiny went solo
    assert len(coal) == 1 and coal[0]["per_query"]      # broad batched
    # solo result respects its filter (-1 padding when k > allowed count)
    got = np.asarray(pend[0].ids)
    assert np.isin(got[got >= 0], tiny).all()
    assert (got >= 0).sum() == len(tiny)


# -- allow-list intake: doc-id space -> slot mask (FlatIndex._allow_mask) ----


def _translations():
    """(mask, ids) children of weaviate_tpu_allow_translate_total; the
    registry lives as long as the process, so tests read deltas."""
    from weaviate_tpu.runtime.metrics import allow_translate_total

    return (allow_translate_total.labels("mask"),
            allow_translate_total.labels("ids"))


def slot_mask_by_definition(idx, allow):
    """What a bool mask over doc ids has always meant as a slot mask:
    list its ids, sort them, binary-search every slot's doc id in them.
    The gather through the slot table is held to this, bit for bit."""
    from weaviate_tpu import native

    table = idx._slot_to_id[: idx.store.capacity]
    return native.membership(table, np.unique(np.nonzero(allow)[0]))


def _intake_index(rng, layout, n=300, d=8):
    """A small index in one of the states the slot table can be in, and
    the size of the doc-id space a mask over it would have."""
    from weaviate_tpu.engine.flat import FlatIndex

    corpus = rng.standard_normal((n, d)).astype(np.float32)
    idx = FlatIndex(dim=d, capacity=512)
    space = n
    if layout == "shuffled":
        # slots not in doc-id order: the import arrives out of order, a
        # third of it is imported again (set_at: same slots, new rows)
        # and some ids leave and come back in fresh slots
        order = rng.permutation(n)
        idx.add_batch(order, corpus[order])
        again = order[: n // 3]
        corpus[again] += 1.0
        idx.add_batch(again, corpus[again])
        back = order[-20:]
        idx.delete(*back.tolist())
        idx.add_batch(back, corpus[back])
        assert not np.all(np.diff(idx._slot_to_id[:n]) > 0)
    else:
        idx.add_batch(np.arange(n), corpus)
    if layout in ("deleted", "compacted"):
        idx.delete(*rng.choice(n, 70, replace=False).tolist())
    if layout == "compacted":
        idx.compact()
        assert len(idx) == n - 70
    if layout == "short_mask":
        # rows added after the mask was built: their doc ids lie past it
        space = n - 40
    if layout == "long_mask":
        # the shard's doc-id counter runs ahead of what this index holds
        space = n + 333
    return idx, corpus, space


@pytest.mark.parametrize("pct", [0, 1, 10, 50, 99, 100])
@pytest.mark.parametrize("layout", ["in_order", "deleted", "short_mask",
                                    "long_mask", "compacted", "shuffled"])
def test_mask_form_equals_definition(rng, layout, pct):
    """A bool mask becomes a slot mask by one gather; the result is the
    old nonzero -> unique -> membership form bit for bit, and the same
    set given as doc ids (which still goes through native.membership)
    finds the same neighbours."""
    idx, _corpus, space = _intake_index(rng, layout)
    allow = rng.random(space) < pct / 100.0   # 0: none, 100: every id
    want = slot_mask_by_definition(idx, allow)
    as_mask, as_ids = _translations()
    m0, i0 = as_mask.value, as_ids.value
    got = idx._allow_mask(allow)
    assert (as_mask.value, as_ids.value) == (m0 + 1, i0)
    assert got.dtype == np.bool_ and got.shape == want.shape
    assert len(got) == len(idx._slot_to_id[: idx.store.capacity])
    assert np.array_equal(got, want)
    # a dead slot reads False whatever the mask says
    assert not got[idx._slot_to_id[: len(got)] < 0].any()

    ids = np.flatnonzero(allow).astype(np.int64)
    from_ids = idx._allow_mask(ids)
    assert (as_mask.value, as_ids.value) == (m0 + 1, i0 + 1)
    assert np.array_equal(from_ids, want)

    q = rng.standard_normal((3, idx.dim)).astype(np.float32)
    by_mask = idx.search_by_vector_batch(q, 5, [allow] * 3)
    by_ids = idx.search_by_vector_batch(q, 5, [ids] * 3)
    assert np.array_equal(by_mask[0], by_ids[0])
    assert np.array_equal(by_mask[1], by_ids[1])
    live = by_mask[0][by_mask[0] >= 0]
    assert np.isin(live, ids).all()
    one_m = idx.search_by_vector(q[0], 5, allow)
    one_i = idx.search_by_vector(q[0], 5, ids)
    assert np.array_equal(one_m[0], one_i[0])


@pytest.mark.parametrize("quant", [None, "bq", "epoch"])
def test_mask_form_on_every_flat_store(rng, quant):
    """Plain, compressed and epoch-stacked stores share the intake: the
    gather reads the index's slot table, never the store."""
    from weaviate_tpu.engine.flat import FlatIndex

    n, d = 200, 32
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    kw = {"epoch": {"epoch_rows": 64}, "bq": {"quantization": "bq"},
          None: {}}[quant]
    idx = FlatIndex(dim=d, capacity=256, **kw)
    order = rng.permutation(n)
    idx.add_batch(order, corpus[order])
    idx.delete(*order[:25].tolist())
    for space in (n - 30, n, n + 100):
        allow = rng.random(space) < 0.4
        assert np.array_equal(idx._allow_mask(allow),
                              slot_mask_by_definition(idx, allow)), space


def test_empty_mask_allows_nothing(rng):
    """A mask over an empty doc-id space (a filter built before the
    first import) has nothing to gather from: every slot reads False."""
    idx, _, _ = _intake_index(rng, "in_order", n=50)
    empty = np.zeros(0, dtype=bool)
    got = idx._allow_mask(empty)
    assert got.dtype == np.bool_ and not got.any()
    assert np.array_equal(got, slot_mask_by_definition(idx, empty))
    assert idx._allow_mask(None) is None


@pytest.mark.parametrize("form", ["mask", "ids", "ids+mask"])
def test_search_batch_span_names_the_form(rng, form):
    from weaviate_tpu.runtime import tracing

    idx, _, space = _intake_index(rng, "in_order", n=100)
    allow = rng.random(space) < 0.5
    given = {"mask": [allow, None], "ids": [np.flatnonzero(allow), None],
             "ids+mask": [allow, np.flatnonzero(allow)]}[form]
    q = rng.standard_normal((2, idx.dim)).astype(np.float32)
    with tracing.trace("test.form", force=True):
        idx.search_by_vector_batch(q, 3, given)
        idx.search_by_vector_batch(q, 3)
    spans = [s for s in tracing.recent_traces(1)[0]["spans"]
             if s["name"] == "flat.search_batch"]
    assert [s["attrs"].get("form") for s in spans] == [form, None]


def test_coalesced_dispatch_counts_one_translation_a_request(rng):
    """A coalesced filtered dispatch of B requests translates B masks on
    the worker, padded rows none; an unfiltered dispatch reaches the
    intake's first line and no further."""
    from weaviate_tpu.engine.flat import FlatIndex
    from weaviate_tpu.runtime.query_batcher import _Pending

    n, d, k = 300, 16, 4
    idx = FlatIndex(dim=d, capacity=512)
    idx.add_batch(np.arange(n),
                  rng.standard_normal((n, d)).astype(np.float32))
    qb, calls = _make_batcher(idx)
    as_mask, as_ids = _translations()

    def pending(allow):
        return _Pending(rng.standard_normal(d).astype(np.float32), k,
                        allow)

    try:
        m0, i0 = as_mask.value, as_ids.value
        plain = [pending(None) for _ in range(5)]
        qb._dispatch(plain)
        assert all(p.error is None for p in plain)
        assert (as_mask.value, as_ids.value) == (m0, i0)

        b = 5                                   # padded to 8 rows
        masked = [pending(rng.random(n) < 0.5) for _ in range(b)]
        qb._dispatch(masked)
        assert all(p.error is None for p in masked)
        assert [c["rows"] for c in calls] == [8, 8]
        assert calls[1]["per_query"]
        assert (as_mask.value, as_ids.value) == (m0 + b, i0)
        for p in masked:
            got = np.asarray(p.ids)
            assert p.allow[got[got >= 0]].all()
    finally:
        qb.stop()


@pytest.mark.parametrize("frozen", [True, False])
@pytest.mark.parametrize("quant", [None, "bq"])
def test_coalesced_dispatch_translates_a_mask_object_once(rng, quant,
                                                          frozen):
    """(PR 40) Rows of one dispatch that carry the SAME mask object are
    translated once, whatever the array; a read-only one (what the
    filter memo hands out) is not translated by the next dispatch
    either, its packed row stayed on the device; a writeable one is
    translated again, and a write to the index makes both a miss. The
    answers are the masked reference's throughout."""
    from weaviate_tpu.engine.flat import FlatIndex
    from weaviate_tpu.runtime.query_batcher import _Pending

    n, d, k = 300, 16, 4
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    idx = FlatIndex(dim=d, capacity=512,
                    **({"quantization": quant, "rescore_limit": 512}
                       if quant else {}))
    idx.add_batch(np.arange(n), corpus)
    qb, calls = _make_batcher(idx)
    as_mask, _ = _translations()
    allow = rng.random(n) < 0.5
    other = rng.random(n) < 0.5
    allow.flags.writeable = other.flags.writeable = not frozen

    def dispatch(live):
        batch = [_Pending(rng.standard_normal(d).astype(np.float32), k, a)
                 for a in (allow, other, allow, None, allow)]
        before = as_mask.value
        qb._dispatch(batch)
        for p in batch:
            assert p.error is None
            want, _ = masked_ref(
                p.query, corpus,
                live if p.allow is None else live & p.allow, k)
            assert np.array_equal(np.asarray(p.ids)[:len(want)], want)
        return as_mask.value - before

    try:
        live = np.ones(n, dtype=bool)
        assert dispatch(live) == 2              # two objects, five rows
        assert dispatch(live) == (0 if frozen else 2)
        idx.delete(int(np.flatnonzero(allow)[0]))
        live[np.flatnonzero(allow)[0]] = False
        assert dispatch(live) == 2              # the slot table moved
        assert all(c["per_query"] and c["rows"] == 8 for c in calls)
    finally:
        qb.stop()


def test_mask_block_constant():
    # every masked kernel unpacks whole 512-column blocks; the packers
    # and kernels must agree on the constant
    assert MASK_BLOCK == 512 and MASK_BLOCK % 32 == 0
