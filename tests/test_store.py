"""Tests for the HBM vector store: add/delete/grow/search/compact."""

import numpy as np
import pytest

from weaviate_tpu.engine.store import DeviceVectorStore


def test_add_and_search(rng):
    store = DeviceVectorStore(dim=32, capacity=64, chunk_size=64)
    vecs = rng.standard_normal((20, 32)).astype(np.float32)
    slots = store.add(vecs)
    assert list(slots) == list(range(20))
    q = vecs[7]
    d, i = store.search(q, k=3)
    assert i[0] == 7
    assert d[0] < 1e-3


def test_growth(rng):
    store = DeviceVectorStore(dim=16, capacity=8, chunk_size=8)
    vecs = rng.standard_normal((100, 16)).astype(np.float32)
    store.add(vecs)
    assert store.capacity >= 100
    d, i = store.search(vecs[55], k=1)
    assert i[0] == 55


def test_delete_tombstones(rng):
    store = DeviceVectorStore(dim=8, capacity=32, chunk_size=32)
    vecs = rng.standard_normal((10, 8)).astype(np.float32)
    store.add(vecs)
    d, i = store.search(vecs[3], k=1)
    assert i[0] == 3
    store.delete([3])
    d, i = store.search(vecs[3], k=1)
    assert i[0] != 3
    assert store.live_count() == 9


def test_cosine_normalizes_on_add(rng):
    store = DeviceVectorStore(dim=16, metric="cosine", capacity=32, chunk_size=32)
    v = rng.standard_normal((5, 16)).astype(np.float32)
    store.add(v * 100.0)  # scale must not matter for cosine
    d, i = store.search(v[2], k=1)
    assert i[0] == 2
    assert d[0] < 1e-3  # cosine distance of parallel vectors ~ 0


def test_allow_mask(rng):
    store = DeviceVectorStore(dim=8, capacity=32, chunk_size=32)
    vecs = rng.standard_normal((10, 8)).astype(np.float32)
    store.add(vecs)
    mask = np.zeros(32, dtype=bool)
    mask[[1, 4]] = True
    d, i = store.search(vecs[0], k=5, allow_mask=mask)
    live = i[i >= 0]
    assert set(live.tolist()).issubset({1, 4})


def test_update_in_place(rng):
    store = DeviceVectorStore(dim=8, capacity=32, chunk_size=32)
    vecs = rng.standard_normal((4, 8)).astype(np.float32)
    store.add(vecs)
    newv = rng.standard_normal(8).astype(np.float32)
    store.set_at([2], newv[None, :])
    d, i = store.search(newv, k=1)
    assert i[0] == 2 and d[0] < 1e-3


def test_search_by_distance(rng):
    store = DeviceVectorStore(dim=4, capacity=32, chunk_size=32)
    base = np.zeros((1, 4), dtype=np.float32)
    near = np.full((3, 4), 0.1, dtype=np.float32)
    far = np.full((3, 4), 10.0, dtype=np.float32)
    store.add(np.concatenate([base, near, far]))
    d, i = store.search_by_distance(np.zeros(4, dtype=np.float32), max_distance=1.0)
    assert set(i.tolist()) == {0, 1, 2, 3}


def test_compact(rng):
    store = DeviceVectorStore(dim=8, capacity=64, chunk_size=64)
    vecs = rng.standard_normal((20, 8)).astype(np.float32)
    store.add(vecs)
    store.delete(list(range(0, 20, 2)))  # drop evens
    mapping = store.compact()
    assert store.live_count() == 10
    # odd original slots survive, remapped contiguously
    assert (mapping[1::2][:10] >= 0).all()
    d, i = store.search(vecs[5], k=1)
    assert i[0] == mapping[5]


def test_snapshot_restore(rng):
    store = DeviceVectorStore(dim=8, capacity=32, chunk_size=32)
    vecs = rng.standard_normal((10, 8)).astype(np.float32)
    store.add(vecs)
    store.delete([4])
    snap = store.snapshot()
    restored = DeviceVectorStore.restore(snap)
    assert restored.live_count() == 9
    d, i = restored.search(vecs[6], k=1)
    assert i[0] == 6


def test_dim_mismatch_raises(rng):
    store = DeviceVectorStore(dim=8)
    with pytest.raises(ValueError):
        store.add(rng.standard_normal((2, 16)).astype(np.float32))


def test_staged_adds_visible_to_every_read_path(rng):
    """add() stages rows host-side; each public read path must flush first
    so visibility matches the old inline-scatter behavior exactly."""
    store = DeviceVectorStore(dim=8)
    vecs = rng.standard_normal((50, 8)).astype(np.float32)
    slots = store.add(vecs)
    assert store._staged_rows == 50  # below the flush threshold
    d, i = store.search(vecs[7], k=1)
    assert i[0] == slots[7]
    assert store._staged_rows == 0
    # get() on a still-staged row
    s2 = store.add(vecs[:3] + 10.0)
    got = store.get(s2[1])
    assert np.allclose(got[0], vecs[1] + 10.0, atol=1e-4)
    # delete of a staged row flushes first, then tombstones
    s3 = store.add(vecs[:2] - 5.0)
    store.delete(s3[0])
    d, i = store.search(vecs[0] - 5.0, k=1)
    assert i[0] != s3[0]
    # live_count sees staged rows
    store.add(vecs[:4] + 20.0)
    assert store.live_count() == 50 + 3 + 2 - 1 + 4


def test_staged_flush_threshold(rng):
    store = DeviceVectorStore(dim=8)
    limit = store._stage_limit
    n = limit + 10
    for s in range(0, n, 1000):
        store.add(rng.standard_normal((min(1000, n - s), 8))
                  .astype(np.float32))
    # at least one threshold flush happened without any read
    assert store._staged_rows < limit


def test_failed_flush_keeps_staged_rows(rng, monkeypatch):
    """A flush-time failure (OOM, compile error) must not drop rows whose
    add() already returned success — they stay staged and re-flushable."""
    import weaviate_tpu.engine.store as store_mod

    store = DeviceVectorStore(dim=8)
    vecs = rng.standard_normal((20, 8)).astype(np.float32)
    slots = store.add(vecs)

    calls = {"n": 0}
    orig = store_mod._scatter_rows

    def boom(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected flush failure")
        return orig(*a, **k)

    monkeypatch.setattr(store_mod, "_scatter_rows", boom)
    with pytest.raises(RuntimeError):
        store.flush_staged()
    assert store._staged_rows == 20  # retained
    d, i = store.search(vecs[4], k=1)  # retry succeeds
    assert i[0] == slots[4]


def test_failed_flush_async_surfaced_keeps_staged_rows(rng, monkeypatch):
    """Dispatch is async: _scatter_rows can return fine and the runtime
    fail later (device OOM, preemption). The flush PROBES the scatter
    result before dropping the staging buffers, so an async-surfaced
    failure also leaves the rows re-flushable."""
    import weaviate_tpu.engine.store as store_mod

    store = DeviceVectorStore(dim=8)
    vecs = rng.standard_normal((20, 8)).astype(np.float32)
    slots = store.add(vecs)

    calls = {"n": 0}
    orig = store_mod._probe_scatter

    def async_boom(valid, slot):
        calls["n"] += 1
        if calls["n"] == 1:
            # what a poisoned result array raises at materialization time
            raise RuntimeError("injected async runtime failure")
        return orig(valid, slot)

    monkeypatch.setattr(store_mod, "_probe_scatter", async_boom)
    with pytest.raises(RuntimeError):
        store.flush_staged()
    assert store._staged_rows == 20  # NOT silently dropped
    d, i = store.search(vecs[4], k=1)  # retry flush + search succeeds
    assert i[0] == slots[4]
    assert calls["n"] >= 2
