"""``Bucket.get_many`` against ``Bucket.get``, key for key.

A batched read walks the segments a SEGMENT at a time (one vectorised
binary search over a fixed-width segment's key array) where ``get``
walks them a key at a time; ``_replace_segment_lookup`` is the one
definition of "newest layer wins, a tombstone shadows", and every case
here holds the batched walk to it."""

from __future__ import annotations

import itertools
import sys
import threading

import numpy as np
import pytest

from weaviate_tpu.storage import kv
from weaviate_tpu.storage.kv import Bucket, _replace_segment_lookup


def _key(i: int) -> bytes:
    """36 bytes, like a uuid key; order is not the order of ``i``."""
    return f"{(i * 2654435761) % (1 << 32):08x}-{i:027d}".encode()


def _val(i: int, layer: int) -> bytes:
    return f"v{i}@{layer}".encode() * 3


def _agree(b: Bucket, keys: list[bytes], routes: dict | None = None):
    got = b.get_many(keys, routes)
    assert got == [b.get(k) for k in keys]
    with b._lock:
        mems = [m.data for m in [*b._sealed, b._mem][::-1]]
        segments = list(b._segments)[::-1]
    for k, v in zip(keys, got):
        if not any(k in m for m in mems):
            assert v == _replace_segment_lookup(segments, k)
    return got


def _layered(tmp_path, n_segments: int, *, sealed: bool = True,
             active: bool = True, per_layer: int = 240) -> Bucket:
    """``n_segments`` segments, then a sealed and an active memtable.
    Layer L holds keys [L*120, L*120+per_layer): half of every layer is
    overwritten by the next; every layer also tombstones a few keys of
    the layer below and re-puts one the layer below tombstoned."""
    b = Bucket(str(tmp_path), "b", memtable_limit=1 << 30)
    layers = n_segments + int(sealed) + int(active)
    for layer in range(layers):
        lo = layer * 120
        b.put_many([(_key(i), _val(i, layer))
                    for i in range(lo, lo + per_layer)])
        if layer:
            b.delete_many([_key(i) for i in range(lo - 120, lo - 110)])
            if layer > 1:  # re-put above the tombstone
                b.put(_key(lo - 240), _val(lo - 240, layer))
        if layer < n_segments:
            b.flush()
        elif layer == n_segments and sealed and active:
            with b._lock:
                b._seal()
    assert b.segment_count == n_segments
    assert len(b._sealed) == int(sealed and active)
    return b


def _batch(rng, n: int, span: int) -> list[bytes]:
    """``n`` keys: present ones, tombstoned ones, and absent ones below,
    between and above every layer's range."""
    ids = rng.integers(-50, span + 50, n)
    return [_key(int(i)) if i >= 0 else b"\x00" * 36 for i in ids]


@pytest.mark.parametrize("n_segments", range(6))
@pytest.mark.parametrize("n", [1, 2, 10, 100, 1000])
def test_get_many_equals_get(tmp_path, n_segments, n):
    b = _layered(tmp_path, n_segments)
    try:
        rng = np.random.default_rng(n_segments * 1000 + n)
        span = (n_segments + 2) * 120 + 240
        routes: dict = {}
        got = _agree(b, _batch(rng, n, span), routes)
        assert len(got) == n
        assert sum(routes.values()) <= n
        # every segment is fixed-width: only the LAST key still missing
        # is searched as get searches it
        assert routes.get("scalar", 0) < kv._ARRAY_MIN_BATCH
    finally:
        b.close()


@pytest.mark.parametrize("sealed,active", [(False, False), (False, True),
                                           (True, True)])
def test_memtable_layers_shadow_segments(tmp_path, sealed, active):
    b = _layered(tmp_path, 2, sealed=sealed, active=active)
    try:
        keys = [_key(i) for i in range(0, 2 * 120 + 480)]
        got = _agree(b, keys)
        assert any(v is None for v in got) and any(v for v in got)
    finally:
        b.close()


def test_overwrite_tombstone_and_reput_across_layers(tmp_path):
    b = _layered(tmp_path, 4)
    try:
        # key 0: put @0, tombstoned @1, re-put @2
        assert b.get(_key(0)) == _val(0, 2)
        # key 1: put @0, tombstoned @1, never again
        assert b.get(_key(1)) is None
        # key 130: put @0 and overwritten @1; tombstoned @2 (120..129 is
        # the range layer 2 deletes, 130 is not in it)
        assert b.get(_key(130)) == _val(130, 1)
        assert b.get(_key(121)) is None
        _agree(b, [_key(i) for i in (0, 1, 130, 121, 0, 1)])
    finally:
        b.close()


@pytest.mark.parametrize("where", ["below", "between", "above"])
def test_absent_keys_around_a_segments_range(tmp_path, where):
    b = Bucket(str(tmp_path), "b")
    try:
        present = [bytes([0x40 + i]) * 8 for i in range(0, 20, 2)]
        b.put_many([(k, k * 2) for k in present])
        b.flush()
        absent = {"below": [b"\x00" * 8, b"\x3f" * 8],
                  "between": [bytes([0x41 + i]) * 8 for i in range(0, 18, 2)],
                  "above": [b"\x7f" * 8, b"\xff" * 8]}[where]
        routes: dict = {}
        got = _agree(b, absent + present, routes)
        assert got[:len(absent)] == [None] * len(absent)
        assert routes == {"array": len(absent) + len(present)}
    finally:
        b.close()


def test_duplicate_keys_and_empty_batch(tmp_path):
    b = _layered(tmp_path, 3)
    try:
        assert b.get_many([]) == []
        keys = [_key(5), _key(5), _key(10 ** 6), _key(5), _key(10 ** 6),
                _key(250), _key(250)]
        got = _agree(b, keys)
        assert got[0] == got[1] == got[3] and got[2] is None is got[4]
    finally:
        b.close()


_ALPHABET = (0x00, 0x01, 0x61, 0x7F, 0x80, 0xFF)


@pytest.mark.parametrize("width", [1, 2, 3])
def test_byte_order_with_nul_and_high_bytes(tmp_path, width):
    """The array route must order keys as ``bytes`` does: embedded and
    TRAILING NUL bytes and bytes >= 0x80 (numpy's ``S`` strips trailing
    NULs on conversion; the search never converts)."""
    b = Bucket(str(tmp_path), "b")
    try:
        every = [bytes(t) for t in
                 itertools.product(_ALPHABET, repeat=width)]
        stored = every[::2]
        b.put_many([(k, b"=" + k) for k in stored])
        b.flush()
        seg = b._segments[0]
        assert seg._keys is not None and seg._keys.dtype.itemsize == width
        # the same prefixes at other widths: NUL-padded look-alikes
        others = [k + b"\x00" for k in every] + [k[:-1] for k in every
                                                 if width > 1]
        routes: dict = {}
        got = _agree(b, every + others, routes)
        assert got[:len(every):2] == [b"=" + k for k in stored]
        assert all(v is None for v in got[1:len(every):2])
        assert all(v is None for v in got[len(every):])
        assert routes == {"array": len(every) + len(others)}
    finally:
        b.close()


def test_mixed_length_segment_beside_fixed_width(tmp_path):
    """A segment whose keys have several lengths has no key array and is
    searched key by key, inside the same walk as its fixed-width
    neighbours."""
    b = Bucket(str(tmp_path), "b")
    try:
        b.put_many([(_key(i), _val(i, 0)) for i in range(100)])
        b.flush()  # fixed width, oldest
        b.put_many([(_key(i), _val(i, 1)) for i in range(50, 80)]
                   + [(b"short", b"s"), (b"a much longer key" * 3, b"l")])
        b.delete(_key(9))
        b.flush()  # several lengths
        b.put_many([(_key(i), _val(i, 2)) for i in range(70, 120)])
        b.flush()  # fixed width, newest
        assert [s._keys is None for s in b._segments] == [False, True, False]
        keys = [_key(i) for i in range(0, 130)] + [
            b"short", b"a much longer key" * 3, b"absent"]
        routes: dict = {}
        got = _agree(b, keys, routes)
        assert got[9] is None and got[-3:] == [b"s", b"l", None]
        # the middle segment answers keys 50..69 (70..79 are shadowed by
        # the newest), the tombstone and its two odd keys; everything
        # else ends on an array segment, the absent keys on the oldest
        assert routes == {"scalar": 23, "array": len(keys) - 23}
        # only variable-length segments: the whole batch goes key by key
        only = Bucket(str(tmp_path), "only")
        only.put_many([(b"k" * (1 + i % 3) + bytes([i]), bytes([i]))
                       for i in range(40)])
        only.flush()
        routes = {}
        _agree(only, [b"k" * (1 + i % 3) + bytes([i]) for i in range(60)],
               routes)
        assert routes == {"scalar": 60}
        only.close()
    finally:
        b.close()


def test_single_key_batch_takes_gets_walk(tmp_path):
    b = _layered(tmp_path, 2, sealed=False, active=False)
    try:
        routes: dict = {}
        assert b.get_many([_key(3)], routes) == [b.get(_key(3))]
        assert routes == {"scalar": 1}
        routes = {}
        b.get_many([_key(3), _key(4)], routes)
        assert routes == {"array": 2}
    finally:
        b.close()


def test_routes_reach_the_counter_and_the_span(tmp_path):
    from weaviate_tpu.runtime import tracing
    from weaviate_tpu.runtime.metrics import kv_batched_keys

    b = _layered(tmp_path, 2)
    try:
        before = {p: kv_batched_keys.labels(p).value
                  for p in ("memtable", "array", "scalar")}
        keys = [_key(i) for i in range(0, 720, 3)]
        routes: dict = {}
        with tracing.trace("t", force=True):
            b.get_many(keys, routes)
        after = {p: kv_batched_keys.labels(p).value - before[p]
                 for p in before}
        assert after == {"memtable": routes["memtable"],
                         "array": routes["array"], "scalar": 0}
        assert routes["memtable"] and routes["array"]
        (sp,) = [s for s in tracing.recent_traces(1)[0]["spans"]
                 if s["name"] == "kv.get_many"]
        assert sp["attrs"]["memtable"] == routes["memtable"]
        assert sp["attrs"]["array"] == routes["array"]
        assert sp["attrs"]["scalar"] == 0
    finally:
        b.close()


@pytest.mark.parametrize("upkeep", ["flush", "compact"])
def test_read_races_segment_list_swap(tmp_path, upkeep):
    """Readers snapshot the layer lists under the lock; a flush or a
    compaction that swaps them meanwhile must cost a reader nothing: a
    key that is never rewritten reads the same in every batch."""
    b = Bucket(str(tmp_path), "b", memtable_limit=16 * 1024)
    stable = [_key(i) for i in range(400)]
    want = [_val(i, 0) for i in range(400)]
    b.put_many(list(zip(stable, want)))
    b.flush()
    stop = threading.Event()
    failures: list = []
    reads = [0]

    def reader():
        rng = np.random.default_rng(threading.get_ident() % 2 ** 32)
        while not stop.is_set():
            pick = rng.integers(0, 400, 64).tolist()
            got = b.get_many([stable[i] for i in pick])
            if got != [want[i] for i in pick]:
                failures.append((pick, got))
                return
            reads[0] += 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        for round_ in range(12):
            b.put_many([(_key(1000 + round_ * 50 + i), _val(i, round_))
                        for i in range(50)])
            b.delete(_key(1000 + round_ * 50))
            b.flush()
            if upkeep == "compact" and round_ % 3 == 2:
                b.compact()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures[:1]
    assert reads[0] > 0
    if upkeep == "compact":
        assert b.segment_count < 12
    _agree(b, stable + [_key(1000 + i) for i in range(0, 600, 7)])
    b.close()
