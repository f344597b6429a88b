"""HBM budget accounting and the OOM exception contract.

The reference hooks RMM's allocation-failure callback
(DeviceMemoryEventHandler.scala:36) and drives a per-thread retry state
machine from native code (RmmSpark; RmmRapidsRetryIterator.scala:27).
XLA's allocator is not user-hookable the same way (SURVEY §7 hard-part
#3), so the TPU design inverts the control flow: batches are *accounted*
against a logical HBM budget at registration time, and crossing the
budget raises ``RetryOOM``/``SplitAndRetryOOM`` **before** the device
allocator would fail. The spill catalog (spill.py) frees accounted bytes
by moving cold batches to host/disk, exactly like the reference's
device→host→disk store chain.

OOM *injection* for tests lives here too: the analogue of
``RmmSpark.forceRetryOOM`` (RmmSparkRetrySuiteBase.scala:48) — tests arm
a countdown and the Nth allocation attempt throws.
"""

from __future__ import annotations

import threading
from typing import Optional

from ..robustness.faults import fault_point


class OutOfDeviceMemory(RuntimeError):
    """Base for device-memory pressure errors (GpuOOM in the JNI)."""


class RetryOOM(OutOfDeviceMemory):
    """Roll back to the last checkpoint and try again at the same size."""


class SplitAndRetryOOM(OutOfDeviceMemory):
    """Roll back, split the input, retry the halves (SplitAndRetryOOM)."""


class TaskContext:
    """Per-task bookkeeping (thread association + retry counters).

    The reference associates JVM threads with Spark task ids inside
    RmmSpark so the native state machine knows which task to interrupt;
    here the context is a thread-local carrying injection state and
    metrics.
    """

    def __init__(self, task_id: int):
        self.task_id = task_id
        self.retry_count = 0
        self.split_count = 0
        self.spilled_bytes = 0
        self.alloc_attempts = 0
        # GpuTaskMetrics.scala:81-146 accumulators (the semaphore's wait
        # is the query's: QueryContext.semaphore_wait_ns)
        self.spill_time_ns = 0
        # test-only injection counters (None = disarmed)
        self._inject_retry_after: Optional[int] = None
        self._inject_split_after: Optional[int] = None

    def metrics(self) -> dict:
        """Snapshot (surfaced per task, like GpuTaskMetrics in the UI)."""
        return {"retryCount": self.retry_count,
                "splitAndRetryCount": self.split_count,
                "spilledBytes": self.spilled_bytes,
                "spillTimeNs": self.spill_time_ns}

    # --- fault injection (RmmSpark.forceRetryOOM analogue) ---
    def force_retry_oom(self, num_allocs_before: int = 0) -> None:
        self._inject_retry_after = num_allocs_before

    def force_split_and_retry_oom(self, num_allocs_before: int = 0) -> None:
        self._inject_split_after = num_allocs_before

    def on_alloc_attempt(self) -> None:
        self.alloc_attempts += 1
        if self._inject_retry_after is not None:
            if self._inject_retry_after == 0:
                self._inject_retry_after = None
                raise RetryOOM("injected RetryOOM")
            self._inject_retry_after -= 1
        if self._inject_split_after is not None:
            if self._inject_split_after == 0:
                self._inject_split_after = None
                raise SplitAndRetryOOM("injected SplitAndRetryOOM")
            self._inject_split_after -= 1


_TL = threading.local()


def task_context() -> TaskContext:
    ctx = getattr(_TL, "ctx", None)
    if ctx is None:
        ctx = TaskContext(task_id=threading.get_ident())
        _TL.ctx = ctx
    return ctx


def reset_task_context() -> TaskContext:
    _TL.ctx = TaskContext(task_id=threading.get_ident())
    return _TL.ctx


#: sentinel: "resolve the owner from the thread's current query" —
#: distinct from None, which means an explicitly untagged reservation
_RESOLVE_OWNER = object()


class _QuerySlice:
    """Per-query partition of the device budget: equal ``share`` of
    the pool, plus whatever idle-slot capacity the query borrows."""

    __slots__ = ("query_id", "share", "used")

    def __init__(self, query_id: str, share: int):
        self.query_id = query_id
        self.share = share
        self.used = 0


class MemoryBudget:
    """Logical byte budget over device HBM.

    ``reserve`` is called before building device arrays for a batch;
    if the budget would overflow it first asks the spill catalog to
    release bytes (synchronousSpill, RapidsBufferCatalog.scala:589) and
    only then raises RetryOOM. Thread-safe; shared across tasks like a
    single device pool.

    Multi-tenant isolation (ROADMAP item 1): while queries are
    registered (``register_query``), the pool is carved into
    ``slots`` equal slices — the admission semaphore's permit count —
    and a query's reservations are checked against its own slice.
    Capacity not claimed by a registered query (empty slots + the
    integer-division remainder) forms an idle pool a query may borrow
    from; it may never eat into another *registered* query's share.
    Spill pressure is scoped the same way: ``reserve`` hands the
    requesting query's id and the live-owner set to the spill
    callback, which then refuses to evict batches belonging to other
    live queries. With no queries registered (single-query sessions,
    unit tests, worker processes) every check degrades to the plain
    global budget — bit-identical to the pre-partition behavior.
    """

    def __init__(self, limit_bytes: int):
        self.limit = limit_bytes
        self.used = 0
        self._lock = threading.Lock()
        self._spill_fn = None  # wired by the spill catalog
        self._slices: dict = {}  # query_id -> _QuerySlice
        self._nslots = 1

    def set_spill_callback(self, fn) -> None:
        self._spill_fn = fn

    # --- per-query slices -------------------------------------------------
    def register_query(self, query_id: str,
                       slots: Optional[int] = None) -> None:
        """Claim a budget slice for an admitted query. ``slots`` is the
        admission concurrency (slice count); sticky across calls so
        per-call callers only pass it once per process lifetime."""
        with self._lock:
            if slots is not None:
                self._nslots = max(int(slots), 1)
            share = self.limit // self._nslots
            self._slices[query_id] = _QuerySlice(query_id, share)

    def unregister_query(self, query_id: str) -> None:
        """Release a finished query's slice. Bytes it still holds
        (e.g. shuffle map outputs pending fetch) stay accounted
        globally and become fair spill victims for everyone."""
        with self._lock:
            self._slices.pop(query_id, None)

    def active_owners(self) -> set:
        with self._lock:
            return set(self._slices)

    def query_used(self, query_id: str) -> int:
        with self._lock:
            sl = self._slices.get(query_id)
            return sl.used if sl is not None else 0

    def _slice_cap_locked(self, sl: "_QuerySlice") -> int:
        """Effective byte cap for one slice: its own share plus the
        idle pool (capacity not reserved to any live query), minus
        what other queries already borrowed from that pool."""
        idle_pool = self.limit - sum(
            s.share for s in self._slices.values())
        borrowed_others = sum(
            max(0, s.used - s.share)
            for s in self._slices.values() if s is not sl)
        return sl.share + max(0, idle_pool - borrowed_others)

    def _try_reserve_locked(self, nbytes: int, owner) -> int:
        """Commit the reservation if it fits; else return the byte
        deficit the spill pass must free (>= 1)."""
        sl = self._slices.get(owner) if owner else None
        if self.used + nbytes > self.limit:
            deficit = self.used + nbytes - self.limit
        elif sl is not None and len(self._slices) > 1:
            cap = self._slice_cap_locked(sl)
            deficit = max(0, sl.used + nbytes - cap)
        else:
            # unpartitioned, untagged, or sole tenant: whole pool
            deficit = 0
        if deficit:
            return deficit
        self.used += nbytes
        if sl is not None:
            sl.used += nbytes
        return 0

    def reserve(self, nbytes: int, owner=_RESOLVE_OWNER) -> None:
        task_context().on_alloc_attempt()
        # seeded fault-site: forced RetryOOM/SplitAndRetryOOM at
        # operator granularity (detail defaults to the armed op_scope)
        fault_point("memory.reserve")
        if owner is _RESOLVE_OWNER:
            # un-plumbed call sites charge the thread's current query;
            # spill.py passes the batch's recorded owner explicitly so
            # reserve/release pair up on the same slice regardless of
            # which thread re-materializes
            from ..robustness.admission import current_query
            q = current_query()
            owner = q.query_id if q is not None else None
        with self._lock:
            needed = self._try_reserve_locked(nbytes, owner)
            if not needed:
                return
        # Out of budget: spill-then-recheck in a loop (outside the lock —
        # spilling calls back into release()). A single spill pass can
        # free less than asked — other tasks reserve concurrently, and
        # the catalog frees whole batches — so keep asking until the
        # reservation fits or the catalog frees nothing more. The
        # requester's identity scopes victim selection: other live
        # queries' batches are off the table.
        while self._spill_fn is not None:
            try:
                freed = self._spill_fn(needed, owner,
                                       self.active_owners())
            except TypeError:
                freed = self._spill_fn(needed)  # legacy 1-arg callback
            with self._lock:
                needed = self._try_reserve_locked(nbytes, owner)
                if not needed:
                    return
            if freed <= 0:
                break
        with self._lock:
            sl = self._slices.get(owner) if owner else None
            slice_info = (f" slice[{owner}]={sl.used}/"
                          f"{self._slice_cap_locked(sl)}"
                          if sl is not None else "")
        raise RetryOOM(
            f"device budget exhausted: used={self.used} request={nbytes} "
            f"limit={self.limit}{slice_info}")

    def release(self, nbytes: int, owner: Optional[str] = None) -> None:
        with self._lock:
            self.used = max(0, self.used - nbytes)
            if owner:
                sl = self._slices.get(owner)
                if sl is not None:
                    sl.used = max(0, sl.used - nbytes)


_DEVICE_BUDGET: Optional[MemoryBudget] = None
_BUDGET_LOCK = threading.Lock()


# The CPU backend reports no memory_stats(); tests and virtual-mesh
# rehearsals run there against this explicit stand-in for one chip's HBM.
CPU_TEST_HBM_BYTES = 16 << 30


def device_hbm_bytes(dev) -> int:
    """``bytes_limit`` of ``dev`` as the backend reports it. The CPU
    backend gets the documented test budget; any other device that
    cannot report its limit is an error, not a guess."""
    stats = dev.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if dev.platform == "cpu":
        return CPU_TEST_HBM_BYTES
    raise RuntimeError(
        f"device {dev} ({dev.platform}/{dev.device_kind}) reports no "
        f"memory_stats()['bytes_limit']; set srt.memory.tpu.poolSize "
        f"explicitly to size the device budget")


def device_budget() -> MemoryBudget:
    """Process-wide device budget, sized from config on first use
    (GpuDeviceManager.initializeRmm analogue)."""
    global _DEVICE_BUDGET
    with _BUDGET_LOCK:
        if _DEVICE_BUDGET is None:
            from ..conf import (DEVICE_MEMORY_FRACTION, DEVICE_MEMORY_LIMIT,
                                active_conf)
            conf = active_conf()
            limit = conf.get(DEVICE_MEMORY_LIMIT)
            if limit <= 0:
                import jax
                hbm = device_hbm_bytes(jax.devices()[0])
                limit = int(hbm * conf.get(DEVICE_MEMORY_FRACTION))
            _DEVICE_BUDGET = MemoryBudget(limit)
        return _DEVICE_BUDGET


def reset_device_budget(limit_bytes: Optional[int] = None) -> MemoryBudget:
    """Test hook: replace the global budget."""
    global _DEVICE_BUDGET
    with _BUDGET_LOCK:
        if limit_bytes is None:
            _DEVICE_BUDGET = None
            return None  # re-derived lazily
        _DEVICE_BUDGET = MemoryBudget(limit_bytes)
        return _DEVICE_BUDGET
