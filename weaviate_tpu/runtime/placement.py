"""Which chip of the host a shard lives on, and the one way its arrays
get there.

A process on a host of several chips used to serve from the default
device alone (``jnp.asarray`` is the default device). Here a shard is
given ONE local device when it is built or reopened, and every array its
vector indexes make afterwards is *committed* to that device
(``jax.device_put(arr, device)``); programs follow their committed
operands, so the scan, the scatter, the rescore and the filter's
operands all run where the rows lie, by the same code whatever the
device. Nothing is replicated and no collective is added: a request over
a collection's shards is the fan-out's programs on up to as many chips,
merged once on the host (db/collection.py ``_fan_out``).

The rule (``acquire``): the local device that holds the fewest shards of
this process, the lowest index first among equals. So a collection's
shards are spread evenly in the order they are made (8 over 4 chips: two
a chip; 3 over 4: three chips), a second collection goes on where the
first stopped instead of starting again at chip 0, and on a host with
one visible device every shard gets that device and nothing changes:
its arrays are not even committed there (``commits``).
There is no option, environment variable or class key: the rule reads
``jax.local_devices()`` and what this process has placed.

The device travels from the shard to the stores in the HBM ledger's
owner scope (``hbm_ledger.owner(..., device=)``): every site that builds
a store later (a grown epoch, a compressed twin) already re-enters that
scope, and the ledger's entries carry their device for the same price. A
mesh-sharded shard takes no device (``None``: the mesh places its rows),
and neither does a store built outside any shard (tests, tools): ``put``
with ``None`` is the default device, uncommitted, as ``jnp.asarray`` was.

A single-device executable belongs to its device, so a chip that holds a
store of the same shapes as another has to build (or load) its own copy
of every program size the other dispatched: ``Twins`` is the registry
that does so off the dispatch path, so that no window first meets a size
on chip 3 that the warm-up only showed chip 0.
"""

from __future__ import annotations

import logging
import queue
import threading
import weakref

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_held: dict[int, int] = {}   # device id -> shards this process placed there


def local_devices() -> list:
    """The chips this process may place shards on (module-level so that
    a test can show the rule a host of one)."""
    import jax

    return jax.local_devices()


def acquire():
    """A device for a new shard: the least-held local device, lowest
    index first. Pair with ``release`` when the shard closes."""
    devices = local_devices()
    with _lock:
        device = min(devices, key=lambda d: (_held.get(d.id, 0), d.id))
        _held[device.id] = _held.get(device.id, 0) + 1
    return device


def release(device) -> None:
    if device is None:
        return
    with _lock:
        n = _held.get(device.id, 0) - 1
        if n > 0:
            _held[device.id] = n
        else:
            _held.pop(device.id, None)


def held() -> dict[int, int]:
    """Device id -> shards placed there (tests)."""
    with _lock:
        return dict(_held)


def label(device) -> str:
    """``tpu:2``: the ``device`` label of counters, gauges, spans and
    ledger entries, and the key ``memwatch.device_memory_stats`` gives a
    device's allocator stats under. ``""`` for no device (a mesh, or a
    store outside any shard)."""
    return "" if device is None else f"{device.platform}:{device.id}"


def commits(device) -> bool:
    """Whether an array for ``device`` is committed there: wherever the
    host shows more than one chip. On a host of one chip the default
    device IS that chip, and an array stays uncommitted on it, as every
    array of a one-chip server always was: call for call the JAX calls
    such a server made before (``jnp.asarray``, ``jnp.zeros``), so the
    same programs under the same persistent-cache keys (a program on
    committed operands has another key) and the same timing a dispatch.
    That timing is not free to move: ``cohere-bq-cosine.c32`` lives
    between two states of the batcher, and with committed operands on
    its one chip it fell into the slow one within five seconds of the
    window in six runs of seven (PERF.md section 6, PR 41)."""
    return device is not None and len(local_devices()) > 1


def put(arr, device=None):
    """THE placement helper: ``arr`` (numpy or jax) on ``device``,
    committed (``commits``); on the default device, uncommitted, where
    ``device`` is None or the host's one chip."""
    import jax
    import jax.numpy as jnp

    if commits(device):
        return jax.device_put(arr, device)
    return jnp.asarray(arr)


def zeros(shape, dtype, device=None):
    """A zero array made ON ``device`` (no copy through the default
    one), committed as ``put`` commits."""
    import jax.numpy as jnp

    return jnp.zeros(shape, dtype,
                     device=device if commits(device) else None)


class Twins:
    """Indexes that hold stores of the same shapes on different chips
    build each other's program sizes (of unfiltered dispatches: a size
    is (padded batch, k); a filtered dispatch's programs are built where
    they are first met).

    An index on a device registers once. On its dispatch path it calls
    ``dispatched(index, size)``: one tuple and one set look-up where the
    size is known for the index's shapes (``index.twin_shapes()``: what
    decides a program besides the batch) on its device. Where it is new
    there, every OTHER device that holds an index of the same shapes and
    has not run the size is asked to, once, on a daemon thread:
    ``warm_twin(size)`` of one such index, a search of zero queries
    through the index's own entry point, so the program built is the one
    a request would build. A warm-up that watches the compile counters
    therefore keeps going until every chip is quiet, and a size first
    met on another chip inside a window finds its executable built."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seen: set = set()       # (device id, shapes, size)
        self._indexes = weakref.WeakSet()
        self._work: queue.SimpleQueue = queue.SimpleQueue()
        self._pending = 0             # queued or running
        self._thread: threading.Thread | None = None

    def register(self, index) -> None:
        with self._lock:
            self._indexes.add(index)

    def dispatched(self, index, size) -> None:
        device, shapes = index.device, index.twin_shapes()
        if shapes is None or (device.id, shapes, size) in self._seen:
            return
        todo = {}
        with self._lock:
            self._seen.add((device.id, shapes, size))
            for other in list(self._indexes):
                key = (other.device.id, shapes, size)
                if key not in self._seen and other.twin_shapes() == shapes:
                    self._seen.add(key)
                    todo[other.device.id] = other
            self._pending += len(todo)
            # queued under the lock: the thread gives itself up under it
            for other in todo.values():
                self._work.put((weakref.ref(other), shapes, size))
            if todo and self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="twin-warm", daemon=True)
                self._thread.start()

    def _run(self) -> None:
        while True:
            try:
                ref, shapes, size = self._work.get(timeout=5.0)
            except queue.Empty:
                with self._lock:
                    if self._work.empty():
                        self._thread = None
                        return
                continue
            twin = ref()
            try:
                # a store that grew meanwhile builds its sizes itself
                if twin is not None and twin.twin_shapes() == shapes:
                    twin.warm_twin(size)
            except Exception as e:  # noqa: BLE001 — a warm-up only
                logger.debug("twin warm of %s failed: %s", size, e)
            finally:
                del twin
                with self._lock:
                    self._pending -= 1

    def idle(self) -> bool:
        """Nothing queued and nothing running (tests)."""
        with self._lock:
            return self._pending == 0


twins = Twins()
