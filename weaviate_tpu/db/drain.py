"""A collection's drain: ONE ``QueryBatcher`` over the collection's set
of LOCAL shards (ISSUE 42, ROADMAP S23).

A shard has a batcher of its own (``Shard._query_batcher``): one worker,
one transfer thread, and it serves every request that targets that shard
alone. A plain ``near_vector`` over SEVERAL local shards used to enqueue
on each of them, so eight shards meant eight locks, eight condition
variables, eight events and eight ``finish`` a request, sixteen dispatch
threads on one interpreter, and eight coalescing decisions that each saw
an eighth of what was in flight. Here the unit that drains is the
collection's set of local shards: such a request is ONE item in ONE
queue, and a drain launches every member's scan over the same coalesced,
padded query block, back to back from the one worker, each through the
member index's own ``search_by_vector_batch_async`` (the per-shard
program as it was: same function, same shapes, one execution a member a
drain; no fused program, no merge on the device). The block is uploaded
once a chip and handed to every member there that takes a block on its
device (``FlatIndex.takes_device_queries``). ONE gathered handle goes to
the ONE transfer thread, which fetches all members' results, runs each
member's own finish (slot -> doc id against the table captured at
dispatch) and stacks them ``[B, S, k]``; a waiter gets ``[S, k]``.

Nothing here chooses a route by an option: ``Collection._fan_out`` takes
the drain for a request over more than one local shard that carries no
filter, allow list or sparse operand, and the shards' own batchers for
everything else. The members span whatever chips their shards lie on
(one process, one host: the worker only launches, and a program follows
its committed operands, runtime/placement.py).
"""

from __future__ import annotations

import numpy as np

from weaviate_tpu.runtime import placement
from weaviate_tpu.runtime.query_batcher import QueryBatcher
from weaviate_tpu.runtime.transfer import DeviceResultHandle


def _stack(results: list) -> tuple[np.ndarray, np.ndarray]:
    """The members' ``(ids [B, k_s], dists [B, k_s])`` as ``[B, S, k]``.
    A member answers with ``min(k, its capacity)`` columns, so widths may
    differ: the narrower are padded with what a scan itself pads with
    (id -1, distance inf), which the shard's ``ids >= 0`` filter drops."""
    b = len(results[0][0])
    width = max(ids.shape[1] for ids, _ in results)
    ids = np.full((b, len(results), width), -1, np.int64)
    dists = np.full((b, len(results), width), np.inf, np.float32)
    for s, (m_ids, m_dists) in enumerate(results):
        ids[:, s, :m_ids.shape[1]] = m_ids
        dists[:, s, :m_dists.shape[1]] = m_dists
    return ids, dists


class CollectionDrain:
    """The batcher of one (collection, vector space) over ``members``:
    ``(shard name, shard, index)`` of every local shard, in the order a
    request names them. Built lazily by ``Collection._drain_for``,
    replaced when the set it was built over changes (``serves``),
    stopped in ``Collection.close``."""

    def __init__(self, collection: str, members: list):
        self.names = tuple(name for name, _, _ in members)
        self._members = tuple(members)
        indexes = [idx for _, _, idx in members]
        devices = [getattr(idx, "device", None) for idx in indexes]
        self.batcher = QueryBatcher(
            self._search_members,
            pad_pow2=any(getattr(idx, "compiled_batch_shapes", True)
                         for idx in indexes),
            async_batch_fn=(self._launch_members if all(
                shard.async_pipeline for _, shard, _ in members) else None),
            # the padded block's ledger entry and the dispatch record
            # name a chip where every member lies on the same one
            owner={"collection": collection, "shard": "*", "tenant": "-",
                   "device": devices[0] if len(set(devices)) == 1
                   else None},
            kind=str(getattr(indexes[0], "index_type", "index")) + ".drain",
            # the dispatch counter moves once a PROGRAM: one a member a
            # drain, under the member's chip
            program_devices=[placement.label(d) for d in devices])

    def serves(self, members: list) -> bool:
        """Whether this drain was built over exactly these shard and
        index objects (a shard dropped and loaded again is another)."""
        return len(members) == len(self._members) and all(
            a[1] is b[1] and a[2] is b[2]
            for a, b in zip(members, self._members))

    def stop(self) -> None:
        self.batcher.stop()

    # -- what the batcher's worker calls --------------------------------------

    def _launch_members(self, queries: np.ndarray, k: int, allow=None):
        """Every member's scan over the one block, launched back to
        back; -> one handle over all of them. The entry points are
        resolved per dispatch (``compress()`` or an upgrade under the
        drain degrades, and never pins a stale method): a member
        without an async one, or whose index declines this dispatch,
        answers through its sync call, here, on the worker."""
        blocks: dict = {}   # device -> the block as it lies there
        handles = []
        for _name, _shard, idx in self._members:
            handle = None
            launch = getattr(idx, "search_by_vector_batch_async", None)
            if launch is not None:
                block = queries
                if getattr(idx, "takes_device_queries", False):
                    block = blocks.get(idx.device)
                    if block is None:
                        block = blocks[idx.device] = placement.put(
                            queries, idx.device)
                handle = launch(block, k)
            if handle is None:
                handle = DeviceResultHandle.ready(
                    idx.search_by_vector_batch(queries, k))
            handles.append(handle)
        return DeviceResultHandle.gather(handles, finish=_stack)

    def _search_members(self, queries: np.ndarray, k: int, allow=None):
        """The sync path (no pipeline, or the one retry of a dispatch
        that faulted): every member's blocking batch search."""
        return _stack([idx.search_by_vector_batch(queries, k)
                       for _, _, idx in self._members])
