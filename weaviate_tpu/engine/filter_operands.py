"""A filter's device operands, derived once from its mask and kept.

What the scan takes of a filter is a pure function of two things: the
mask over doc ids that the filter evaluated to, and the index's slot
table. Since PR 33 a clause's mask is ONE memoised read-only array
(``InvertedIndex.leaf_mask``), handed to every request that names the
clause, yet every dispatch translated it through the slot table, packed
it and uploaded it again, a row a request (PERF.md, PR 40: 39.5 ms a
dispatch of 13 requests on the batcher's one worker). ``FlatIndex``
keeps the results here instead, on the device:

- a mask's packed bitmap row ``[capacity_pad / 32]`` uint32 (what a
  coalesced dispatch stacks into its ``allow_bits``, a row a query);
- where the mask is selective enough for the store's gathered cutover,
  its pow2-padded slot list ``[bucket]`` int32 (what a solo dispatch
  scans);
- the mask's allowed count (what the batcher's solo cut goes by).

**Key and invalidation.** An entry is keyed by the mask OBJECT (``is``)
and valid for one stamp, the index's slot-table generation and the
store's capacity. Only a mask that cannot change is kept
(``stable_mask``: not writeable, nor a view of an array that is), and
the entry holds a reference to it, so its identity cannot be handed to
another array meanwhile. A write that changes the filter's answer drops
the memo, so the next request brings a NEW object (a miss); a write
that moves a slot moves the generation, which drops every entry. No
entry is served across either: an acknowledged write is in the next
request's answer (``Shard.allow_mask`` I1-I3 hold as they did).

**Bound.** Least recently used out past ``OPERAND_CACHE_MAX_BYTES`` of
device memory, booked in the HBM ledger under ``allow_bitmask`` (the
component a dispatch's transient bitmask has); an entry pins its host
mask too, eight times its packed row.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict

import numpy as np

from weaviate_tpu.runtime import hbm_ledger
from weaviate_tpu.runtime.metrics import filter_operand_resident

#: device bytes an index keeps of its filters' operands: 256 packed rows
#: at 262,144 slots, 64 at a million (``InvertedIndex.LEAF_MEMO_MAX_BYTES``
#: holds as many masks on the host)
OPERAND_CACHE_MAX_BYTES = 8 << 20


def stable_mask(allow) -> bool:
    """True for a bool mask nobody can write to: a stable key while a
    reference to it is held. What the filter memo hands out qualifies
    (``Shard.allow_mask`` I3); a fresh ``a & b`` of a compound filter, an
    id list, or a read-only view of a writeable array does not."""
    if (not isinstance(allow, np.ndarray) or allow.dtype != np.bool_
            or allow.ndim != 1 or allow.flags.writeable):
        return False
    base = allow.base
    return base is None or (isinstance(base, np.ndarray)
                            and not base.flags.writeable)


class _Entry:
    """One mask's operands at the cache's stamp; ``bits`` or ``slots``
    (or both) is on the device. It is filed under ``id(mask)`` and holds
    ``mask``, so no other array can have that id while it is filed."""

    __slots__ = ("mask", "doc_count", "bits", "slots", "slot_count")

    def __init__(self, mask):
        self.mask = mask
        self.doc_count: int | None = None
        self.bits = None
        self.slots = None
        self.slot_count = 0


class FilterOperandCache:
    """The LRU one index keeps. ``get`` / ``attach`` / ``ones`` are
    called with the index's lock held (they compare the stamp);
    ``doc_count`` from any thread. ``owner``: the HBM ledger's labels
    (collection, shard, tenant) of what it holds."""

    def __init__(self, owner: dict | None = None):
        self.owner = dict(owner or hbm_ledger.current_owner())
        self._lock = threading.Lock()
        self._entries: OrderedDict[int, _Entry] = OrderedDict()
        self._stamp = None
        self._ones = None
        self._bytes = 0
        self._hbm_keys: dict[str, int] = {}
        self._labels = (str(self.owner.get("collection") or "-"),
                        str(self.owner.get("shard") or "-"))
        weakref.finalize(self, hbm_ledger.ledger.release_many,
                         self._hbm_keys.values())

    # -- look-ups -------------------------------------------------------------

    def get(self, mask, stamp) -> _Entry | None:
        """The entry of ``mask`` at ``stamp``, or None. A stamp other
        than the cache's drops everything first."""
        with self._lock:
            self._restamp(stamp)
            e = self._entries.get(id(mask))
            if e is not None:
                self._entries.move_to_end(id(mask))
            return e

    def doc_count(self, mask) -> int:
        """Doc ids ``mask`` allows: read from its entry where it has one
        (the count does not depend on the stamp), counted where not."""
        with self._lock:
            e = self._entries.get(id(mask))
        if e is None:
            return int(np.count_nonzero(mask))
        if e.doc_count is None:
            e.doc_count = int(np.count_nonzero(mask))
        return e.doc_count

    def ones(self, stamp, build):
        """The unfiltered query's row at ``stamp`` (``build()`` -> the
        device array), kept until the stamp moves."""
        with self._lock:
            self._restamp(stamp)
            if self._ones is None:
                self._ones = build()
                self._bytes += int(self._ones.nbytes)
                self._publish()
            return self._ones

    # -- fills ----------------------------------------------------------------

    def attach(self, mask, stamp, *, bits=None, slots=None,
               slot_count: int = 0) -> None:
        """Keep a device operand of ``mask`` (a ``stable_mask``) built at
        ``stamp``; least recently used entries go to make room. One
        operand larger than the whole budget is not kept."""
        new = bits if bits is not None else slots
        nbytes = int(new.nbytes)
        if nbytes > OPERAND_CACHE_MAX_BYTES:
            return
        with self._lock:
            self._restamp(stamp)
            e = self._entries.get(id(mask))
            if e is None:
                e = self._entries[id(mask)] = _Entry(mask)
            self._entries.move_to_end(id(mask))
            if bits is not None:
                self._bytes += nbytes - (0 if e.bits is None
                                         else int(e.bits.nbytes))
                e.bits = bits
            else:
                self._bytes += nbytes - (0 if e.slots is None
                                         else int(e.slots.nbytes))
                e.slots, e.slot_count = slots, slot_count
            while (self._bytes > OPERAND_CACHE_MAX_BYTES
                   and len(self._entries) > 1):
                _k, old = self._entries.popitem(last=False)
                self._bytes -= self._nbytes(old)
            self._publish()

    def clear(self) -> None:
        """Drop every entry and release its buffers (the index's slot
        table moved, or the index is going)."""
        with self._lock:
            self._drop()

    # -- what it holds --------------------------------------------------------

    @property
    def resident(self) -> tuple[int, int]:
        """(entries, device bytes)."""
        with self._lock:
            return len(self._entries), self._bytes

    # -- internals (caller holds ``_lock``) -----------------------------------

    @staticmethod
    def _nbytes(e: _Entry) -> int:
        return sum(int(a.nbytes) for a in (e.bits, e.slots)
                   if a is not None)

    def _restamp(self, stamp) -> None:
        """Caller holds ``_lock``: another stamp drops what was kept."""
        if stamp != self._stamp:
            self._drop()
            self._stamp = stamp

    def _drop(self) -> None:
        """Caller holds ``_lock``."""
        if self._entries or self._ones is not None:
            self._entries.clear()
            self._ones = None
            self._bytes = 0
            self._publish()

    def _publish(self) -> None:
        """Caller holds ``_lock``: the ledger and the gauge read what the
        cache holds now."""
        hbm_ledger.ledger.set_keyed(
            self._hbm_keys, "allow_bitmask", self._bytes,
            owner=self.owner, dtype="uint32")
        filter_operand_resident.labels(*self._labels, "entries").set(
            len(self._entries))
        filter_operand_resident.labels(*self._labels, "bytes").set(
            self._bytes)
