"""Memory watchdog: gate allocations against host and device budgets.

Reference: usecases/memwatch/monitor.go:49 — CheckAlloc(:99) compares the
projected live heap against GOMEMLIMIT and rejects imports/cache growth
when it would overshoot. The TPU analog adds the HBM budget: device
arrays (vector stores, posting lists) are tracked against per-device HBM
capacity read from jax device memory_stats when available — and, where
the backend exposes no allocator stats (CPU meshes),
against the HBM ledger's projection of registered device bytes
(runtime/hbm_ledger.py), so admission control keeps working exactly
where the allocator goes blind.

Watermark semantics (config: HBM_HIGH_WATERMARK / HBM_LOW_WATERMARK,
defaults 0.9 / 0.8): an import that would push projected usage past
``budget * high`` is refused with a typed 507-style error BEFORE the
transfer is dispatched (no mid-import OOM). Once tripped, the monitor
stays in pressure mode — still refusing — until usage falls back under
``budget * low`` (hysteresis: a budget hovering at the high mark must
not flap accept/reject per request). Every transition and rejection
emits a ``memory.pressure`` trace span and bumps
``weaviate_tpu_memory_pressure_total`` so degradation is visible.
"""

from __future__ import annotations

import os
import threading
import time

from weaviate_tpu.runtime.placement import label as _label

#: seconds before an "allocator stats unavailable" verdict is re-probed.
#: One transient failure (backend still initializing) must not disable
#: device stats forever; re-probing every request would re-pay backend
#: init on platforms that genuinely lack stats.
STATS_RETRY_S = 60.0


class InsufficientMemoryError(MemoryError):
    """Typed admission rejection (HTTP maps it to 507 Insufficient
    Storage). ``projected``/``budget``/``source`` describe the refusal."""

    status = 507

    def __init__(self, message: str, *, projected: int = 0,
                 budget: int = 0, source: str = ""):
        super().__init__(message)
        self.projected = projected
        self.budget = budget
        self.source = source  # "allocator" | "ledger" | "tracked"


def _env_fraction(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        v = float(raw)
    except ValueError:
        return default
    return v if 0.0 < v <= 1.0 else default


class MemoryMonitor:
    def __init__(self, host_limit_bytes: int | None = None,
                 device_limit_bytes: int | None = None,
                 max_utilization: float = 0.9,
                 ledger=None,
                 high_watermark: float | None = None,
                 low_watermark: float | None = None):
        self.host_limit = host_limit_bytes
        self.device_limit = device_limit_bytes
        self.max_utilization = max_utilization
        # watermark precedence: explicit arg > env > max_utilization/0.8
        self.high_watermark = (
            high_watermark if high_watermark is not None
            else _env_fraction("HBM_HIGH_WATERMARK", max_utilization))
        self.low_watermark = (
            low_watermark if low_watermark is not None
            else _env_fraction("HBM_LOW_WATERMARK", 0.8))
        self.low_watermark = min(self.low_watermark, self.high_watermark)
        if ledger is None:
            from weaviate_tpu.runtime.hbm_ledger import ledger as _default

            ledger = _default
        self.ledger = ledger
        self._lock = threading.Lock()
        self._pressure = False  # hysteresis latch (high trips, low clears)
        self._last_source = "ledger"  # which tier answered device_in_use
        # host-side tracked allocations (we can't read the Python live
        # heap cheaply; callers register their big buffers)
        self._tracked_host = 0

    # -- device -----------------------------------------------------------

    def device_budget(self, stats: dict | None = None,
                      device=None) -> int | None:
        """HBM budget in bytes; explicit limit wins, else read from the
        backend (a TPU exposes memory_stats: ``device``'s own limit
        where one is named, else the first device's), else the
        HBM_DEVICE_LIMIT_BYTES env override (the only option on backends
        with no allocator stats)."""
        budget = self._device_budget_raw(stats, device)
        try:
            from weaviate_tpu.runtime.metrics import hbm_budget_bytes

            hbm_budget_bytes.set(float(budget or 0))
        except Exception:  # noqa: BLE001 — metrics are best-effort
            pass
        return budget

    def _device_budget_raw(self, stats: dict | None = None,
                           device=None) -> int | None:
        if self.device_limit is not None:
            return self.device_limit
        stats = device_memory_stats() if stats is None else stats
        own = stats.get(_label(device))
        for dev in ([own] if own else []) + list(stats.values()):
            if dev.get("bytesLimit"):
                return int(dev["bytesLimit"])
        raw = os.environ.get("HBM_DEVICE_LIMIT_BYTES")
        if raw:
            try:
                return int(raw)
            except ValueError:
                pass
        return None

    def device_in_use(self, stats: dict | None = None, device=None) -> int:
        """Current device usage: allocator stats when the backend has
        them, else the ledger's registered device bytes. ``device`` (the
        chip the bytes are bound for: a shard's own,
        runtime/placement.py) is asked alone: its allocator's bytes in
        use, or the ledger's bytes of owners placed there, so a shard
        bound for an empty chip is not refused because another is full.
        Without one: the FULLEST device, and the ledger's total. The
        ledger projection is the LOGICAL global footprint (on a mesh,
        summed over shards) — conservative against a per-device
        allocator budget, exact against an operator-granted
        HBM_DEVICE_LIMIT_BYTES. Records which source answered in
        ``_last_source`` (the admission path probes ONCE and threads
        the dict through)."""
        stats = device_memory_stats() if stats is None else stats
        own = stats.get(_label(device))
        in_use = [d["bytesInUse"] for d in ([own] if own else stats.values())
                  if d.get("bytesInUse") is not None]
        # _last_source is read by the rejection path on other threads —
        # publish it under the monitor lock (callers never hold it here)
        if in_use:
            with self._lock:
                self._last_source = "allocator"
            return max(in_use)
        with self._lock:
            self._last_source = "ledger"
        if device is not None:
            return self.ledger.device_bytes().get(_label(device), 0)
        return self.ledger.total_bytes()

    def check_device_alloc(self, nbytes: int, what: str = "",
                           device=None) -> None:
        """Raise InsufficientMemoryError if landing ``nbytes`` more on the
        device would cross the high watermark (reference CheckAlloc
        semantics: refuse BEFORE allocating, don't OOM mid-import).
        Hysteresis: once tripped, keeps refusing until usage falls under
        the low watermark. ``device``: the chip the bytes are bound for
        (``device_in_use``)."""
        budget, in_use = self._device_budget_and_use(device)
        if budget is None:
            return
        source = getattr(self, "_last_source", "ledger")
        projected = in_use + int(nbytes)
        high = budget * self.high_watermark
        low = budget * self.low_watermark
        with self._lock:
            if self._pressure and in_use <= low:
                self._pressure = False
                self._pressure_event("cleared", projected, budget, source)
            reject = projected > high or (self._pressure and projected > low)
            if reject and not self._pressure:
                self._pressure = True
                self._pressure_event("entered", projected, budget, source)
        if reject:
            self._pressure_event("rejected", projected, budget, source,
                                 what=what)
            raise InsufficientMemoryError(
                f"device allocation of {nbytes} bytes"
                f"{f' ({what})' if what else ''} would exceed "
                f"{self.high_watermark:.0%} of HBM budget {budget} "
                f"({source} usage {in_use})",
                projected=projected, budget=budget, source=source)

    def device_fits(self, nbytes: int, device=None) -> bool:
        """Would ``nbytes`` more on the device stay under the high
        watermark? ``check_device_alloc``'s rule as a question: nothing
        is raised, latched or counted. For a caller that has somewhere
        else to put the bytes (a compressed store's float32 rescore
        rows: the host), where a refused import has not. No budget (a
        backend without allocator stats and no configured limit): yes."""
        budget, in_use = self._device_budget_and_use(device)
        return budget is None or (
            not self.under_pressure
            and in_use + int(nbytes) <= budget * self.high_watermark)

    def _device_budget_and_use(self, device=None
                               ) -> tuple[int | None, int]:
        """(budget, bytes in use) of ``device`` (None: see
        ``device_in_use``) from ONE stats probe; the explicit-limit fast
        path skips the probe for the budget. No budget: ``(None, 0)``."""
        stats = None if self.device_limit is not None \
            else device_memory_stats()
        budget = self.device_budget(stats, device)
        if budget is None:
            return None, 0
        return budget, self.device_in_use(stats, device)

    @staticmethod
    def _pressure_event(action: str, projected: int, budget: int,
                        source: str, what: str = "") -> None:
        try:
            from weaviate_tpu.runtime import tracing
            from weaviate_tpu.runtime.metrics import memory_pressure_total

            memory_pressure_total.labels("device", action).inc()
            now = time.perf_counter()
            tracing.record_span("memory.pressure", now, now,
                                action=action, projected=projected,
                                budget=budget, source=source,
                                **({"what": what} if what else {}))
        except Exception:  # noqa: BLE001 — observability must not gate
            pass

    @property
    def under_pressure(self) -> bool:
        with self._lock:
            return self._pressure

    # -- host -------------------------------------------------------------

    def track_host(self, nbytes: int) -> None:
        with self._lock:
            self._tracked_host += nbytes

    def release_host(self, nbytes: int) -> None:
        with self._lock:
            self._tracked_host = max(0, self._tracked_host - nbytes)

    def check_host_alloc(self, nbytes: int) -> None:
        if self.host_limit is None:
            return
        with self._lock:
            projected = self._tracked_host + nbytes
        if projected > self.host_limit * self.max_utilization:
            raise InsufficientMemoryError(
                f"host allocation of {nbytes} bytes would exceed "
                f"{self.max_utilization:.0%} of limit {self.host_limit}",
                projected=projected, budget=self.host_limit,
                source="tracked")

    @property
    def tracked_host(self) -> int:
        return self._tracked_host


# "unavailable" verdict with an expiry: a transient probe failure (e.g.
# backend still initializing) re-probes after STATS_RETRY_S instead of
# disabling device stats for the life of the process; a succeeding probe
# clears it. The positive path is NOT cached — allocator stats are a
# cheap attribute read once the backend is up.
_stats_lock = threading.Lock()
_stats_failed_at: float | None = None


def _probe_device_stats() -> dict:
    """One raw probe (module-level so tests can monkeypatch failures)."""
    import jax

    out = {}
    for dev in jax.devices():
        stats = dev.memory_stats()
        if stats:
            out[_label(dev)] = {
                "bytesInUse": stats.get("bytes_in_use"),
                "bytesLimit": stats.get("bytes_limit"),
                "peakBytesInUse": stats.get("peak_bytes_in_use"),
            }
    return out


def device_memory_stats() -> dict:
    """Per-device HBM usage (the GOMEMLIMIT analog for device memory).

    Returns {} when the backend does not expose allocator stats (e.g.
    CPU mesh). Unavailability is cached with
    a TTL (STATS_RETRY_S) so a polled status endpoint doesn't re-pay
    backend init every few seconds, yet one transient failure can't
    permanently blind the monitor."""
    global _stats_failed_at
    with _stats_lock:
        if (_stats_failed_at is not None
                and time.monotonic() - _stats_failed_at < STATS_RETRY_S):
            return {}
    try:
        out = _probe_device_stats()
    except Exception:
        out = {}
    with _stats_lock:
        _stats_failed_at = None if out else time.monotonic()
    return out
