"""Tiered spill: device (HBM) → host (native pool) → disk.

Rebuild of the reference's spill framework (SURVEY §2.3):
RapidsBufferCatalog.scala (handle-based registry, synchronousSpill:589,
acquire:461), RapidsDeviceMemoryStore / RapidsHostMemoryStore /
RapidsDiskStore, SpillableColumnarBatch.scala, SpillPriorities.scala.

TPU mapping: a "device buffer" is the set of jax.Arrays inside a
ColumnarBatch; spilling to host copies the leaves into slabs of the
native C++ HostMemoryPool (native/tputable.cpp — the pinned-host-pool
role of RapidsHostMemoryStore), so host spill space is a real bounded
allocation: pool exhaustion cascades older host entries to disk, and if
space still cannot be found the entry bypasses the pool (plain numpy)
under the same byte-limit accounting. Disk tier is an .npz file.
Re-materialization is ``jnp.asarray`` back into HBM. All device bytes
are accounted against the shared MemoryBudget so spilling actually
relieves device pressure.
"""

from __future__ import annotations

import atexit
import os
import re
import shutil
import tempfile
import threading
from enum import IntEnum
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.vector import ColumnarBatch
from ..jit_registry import read_int
from ..obs import events as _events
from ..robustness import faults as _faults
from ..robustness.integrity import DataCorruption, array_checksum
from .budget import MemoryBudget, device_budget


class SpillPriority(IntEnum):
    """Lower spills first (SpillPriorities.scala ordering)."""

    SHUFFLE_OUTPUT = 0       # regeneratable / long-lived, cold
    CACHED = 10
    ACTIVE_ON_DECK = 50      # input batches queued behind an operator
    ACTIVE_WORKING = 100     # spills last


def batch_nbytes(batch: ColumnarBatch) -> int:
    leaves = jax.tree_util.tree_leaves(batch)
    return sum(x.size * x.dtype.itemsize for x in leaves
               if hasattr(x, "dtype"))


def _tree_to_host(batch: ColumnarBatch):
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    host = [np.asarray(x) if hasattr(x, "dtype") else x for x in leaves]
    return host, treedef


class _PooledLeaves:
    """Array leaves packed into one native-pool slab."""

    __slots__ = ("pool", "ptr", "total", "metas", "scalars", "nleaves")

    def __init__(self, pool, ptr: int, total: int, metas, scalars,
                 nleaves: int):
        self.pool = pool
        self.ptr = ptr
        self.total = total
        self.metas = metas      # [(leaf_idx, offset, shape, dtype)]
        self.scalars = scalars  # {leaf_idx: value}
        self.nleaves = nleaves

    @classmethod
    def pack(cls, pool, host_leaves) -> Optional["_PooledLeaves"]:
        import ctypes
        arrays = [(i, x) for i, x in enumerate(host_leaves)
                  if isinstance(x, np.ndarray)]
        scalars = {i: x for i, x in enumerate(host_leaves)
                   if not isinstance(x, np.ndarray)}
        total = sum(int(a.nbytes) for _, a in arrays)
        ptr = pool.alloc(max(total, 1))
        if ptr is None:
            return None
        buf = (ctypes.c_char * max(total, 1)).from_address(ptr)
        metas = []
        off = 0
        for i, a in arrays:
            n = int(a.nbytes)
            if n:
                view = np.frombuffer(buf, dtype=np.uint8, count=n,
                                     offset=off)
                view[:] = np.ascontiguousarray(a).view(np.uint8).ravel()
            metas.append((i, off, a.shape, a.dtype))
            off += n
        return cls(pool, ptr, total, metas, scalars, len(host_leaves))

    def unpack(self):
        import ctypes
        buf = (ctypes.c_char * max(self.total, 1)).from_address(self.ptr)
        leaves = [None] * self.nleaves
        for i, v in self.scalars.items():
            leaves[i] = v
        for i, off, shape, dtype in self.metas:
            count = int(np.prod(shape)) if shape else 1
            nbytes = count * dtype.itemsize
            if nbytes:
                # COPY out of the pooled slab: jax.device_put on the CPU
                # backend can be zero-copy, so a view here would alias
                # pool memory that free() hands to the NEXT spill —
                # silent corruption of any batch still referencing it
                arr = np.frombuffer(buf, dtype=dtype, count=count,
                                    offset=off).reshape(shape).copy()
            else:
                arr = np.zeros(shape, dtype)
            leaves[i] = arr
        return leaves

    def free(self) -> None:
        if self.ptr:
            self.pool.free(self.ptr)
            self.ptr = 0


def _tree_to_device(host_leaves, treedef) -> ColumnarBatch:
    dev = [jnp.asarray(x) if isinstance(x, np.ndarray) else x
           for x in host_leaves]
    return jax.tree_util.tree_unflatten(treedef, dev)


class SpillableBatch:
    """A columnar batch registered for spill (SpillableColumnarBatch).

    States: DEVICE (accounted against the HBM budget), HOST (numpy),
    DISK (.npz file). ``get()`` re-materializes on device;
    ``close()`` releases whatever tier holds it.
    """

    __slots__ = ("_batch", "_host", "_pooled", "_treedef", "_path",
                 "_nbytes", "priority", "_lock", "_catalog", "handle",
                 "closed", "_scalars", "_nleaves", "_num_rows",
                 "creation_stack", "_slab", "_crcs", "owner")

    def __init__(self, batch: ColumnarBatch,
                 priority: SpillPriority = SpillPriority.ACTIVE_ON_DECK,
                 catalog: Optional["SpillCatalog"] = None):
        self._nbytes = batch_nbytes(batch)
        self._catalog = catalog or spill_catalog()
        # budget-slice owner: the query whose thread registered this
        # batch. Reserve/release always pair on this tag so slice
        # accounting stays consistent no matter which thread spills or
        # re-materializes; victim selection uses it to keep one
        # tenant's pressure off another's batches.
        from ..robustness.admission import current_query
        q = current_query()
        self.owner: Optional[str] = q.query_id if q is not None else None
        self._catalog.budget.reserve(self._nbytes, owner=self.owner)
        self._batch: Optional[ColumnarBatch] = batch
        self._num_rows = read_int(batch.num_rows)
        self._host = None
        self._pooled: Optional[_PooledLeaves] = None
        self._treedef = None
        self._path: Optional[str] = None
        self._slab = None  # (metas, scalars, nleaves, total) for .slab
        self._crcs = None  # per-leaf checksums taken at spill time
        self.priority = priority
        self._lock = threading.Lock()
        self.closed = False
        self.creation_stack: Optional[str] = None
        if self._catalog.leak_detection:
            import traceback
            self.creation_stack = "".join(traceback.format_stack(limit=12))
        self.handle = self._catalog.register(self)

    @property
    def nbytes(self) -> int:
        return self._nbytes

    @property
    def tier(self) -> str:
        if self._batch is not None:
            return "device"
        if self._host is not None or self._pooled is not None:
            return "host"
        if self._path is not None:
            return "disk"
        return "closed"

    def num_rows(self) -> int:
        return self._num_rows

    def spill_to_host(self) -> int:
        """Device → host. Returns device bytes freed."""
        import time as _time
        with self._lock:
            if self._batch is None or self.closed:
                return 0
            t0 = _time.perf_counter_ns()
            host, self._treedef = _tree_to_host(self._batch)
            # checksum every leaf the moment it leaves the device: the
            # host and disk tiers both verify against these at
            # re-materialization (device->host->disk chain integrity)
            if self._catalog.verify_checksums:
                self._crcs = tuple(
                    array_checksum(x) if isinstance(x, np.ndarray)
                    else None for x in host)
            # host tier backing: native pool slab when space can be
            # found (cascading older host entries to disk), else plain
            # numpy under the same byte accounting
            self._pooled = self._catalog.try_pool_pack(host)
            if self._pooled is None:
                self._host = host
            self._batch = None
            self._catalog.budget.release(self._nbytes, owner=self.owner)
            from .budget import task_context
            from ..robustness.admission import current_query
            ctx = task_context()
            ctx.spilled_bytes += self._nbytes
            ctx.spill_time_ns += _time.perf_counter_ns() - t0
            rq = current_query()
            _events.emit("SpillToHost", bytes=self._nbytes,
                         time_ns=_time.perf_counter_ns() - t0,
                         priority=int(self.priority),
                         owner=self.owner,
                         requested_by=rq.query_id
                         if rq is not None else None)
            return self._nbytes

    def spill_to_disk(self) -> int:
        """Host → disk. Returns host bytes freed.

        Pool-slab entries stream RAW via the native O_DIRECT writer
        (GDS-spill role: bulk spills bypass the page cache and need no
        npz re-serialization); numpy-fallback entries keep the .npz
        path."""
        with self._lock:
            if (self._host is None and self._pooled is None) or \
                    self.closed:
                return 0
            if self._pooled is not None:
                from ..native import direct_write
                fd, path = tempfile.mkstemp(
                    suffix=".slab", dir=self._catalog.spill_dir)
                os.close(fd)
                if direct_write(path, self._pooled.ptr,
                                max(self._pooled.total, 1)):
                    self._path = path
                    self._slab = (self._pooled.metas,
                                  self._pooled.scalars,
                                  self._pooled.nleaves,
                                  self._pooled.total)
                    self._pooled.free()
                    self._pooled = None
                    _events.emit("SpillToDisk", bytes=self._nbytes,
                                 tier="slab")
                    return self._nbytes
                os.unlink(path)  # direct write failed: npz fallback
            host = self._host if self._host is not None \
                else self._pooled.unpack()
            fd, path = tempfile.mkstemp(suffix=".npz",
                                        dir=self._catalog.spill_dir)
            os.close(fd)
            arrays = {f"a{i}": x for i, x in enumerate(host)
                      if isinstance(x, np.ndarray)}
            scalars = {i: x for i, x in enumerate(host)
                       if not isinstance(x, np.ndarray)}
            np.savez(path, **arrays)
            self._path = path
            self._scalars = scalars
            self._nleaves = len(host)
            self._host = None
            if self._pooled is not None:
                self._pooled.free()
                self._pooled = None
            _events.emit("SpillToDisk", bytes=self._nbytes, tier="npz")
            return self._nbytes

    def get(self) -> ColumnarBatch:
        """Re-materialize on device (unspillBufferToDeviceStore,
        RapidsBufferCatalog.scala:633).

        budget.reserve runs OUTSIDE self._lock: its spill callback may
        call back into this object's spill_to_disk (or another thread's
        get may spill us) — holding the lock across it deadlocks.
        """
        with self._lock:
            if self.closed:
                raise ValueError("SpillableBatch used after close")
            if self._batch is not None:
                return self._batch
        self._catalog.budget.reserve(self._nbytes, owner=self.owner)
        try:
            with self._lock:
                if self.closed:
                    self._catalog.budget.release(self._nbytes,
                                                 owner=self.owner)
                    raise ValueError("SpillableBatch used after close")
                if self._batch is not None:  # raced with another get()
                    self._catalog.budget.release(self._nbytes,
                                                 owner=self.owner)
                    return self._batch
                if self._host is None and self._pooled is None and \
                        self._path is not None:
                    # a corrupt spill file may fail to even PARSE
                    # (flipped npz metadata, short read): any decode
                    # error here is at-rest corruption, same as a
                    # checksum mismatch
                    try:
                        if self._slab is not None:
                            self._host = self._load_slab()
                        else:
                            data = np.load(self._path)
                            leaves = []
                            for i in range(self._nleaves):
                                if i in self._scalars:
                                    leaves.append(self._scalars[i])
                                else:
                                    leaves.append(data[f"a{i}"])
                            self._host = leaves
                    except Exception as e:
                        raise DataCorruption(
                            f"spill entry handle={self.handle} "
                            f"unreadable at re-materialization: "
                            f"{type(e).__name__}: {e}",
                            detail="entry dropped; recompute the "
                                   "batch") from e
                    os.unlink(self._path)
                    self._path = None
                if self._pooled is not None:
                    host = self._pooled.unpack()  # copies out of the slab
                    self._pooled.free()
                    self._pooled = None
                else:
                    host = self._host
                self._host = None
                # every tier funnels through one verification point
                # before touching the device
                host = self._verify_host(host)
                self._batch = _tree_to_device(host, self._treedef)
                return self._batch
        except DataCorruption:
            # the entry's bytes are gone for good — drop it so retries
            # cannot re-read garbage; the caller (retry framework /
            # stage rerun) recomputes the batch from its lineage
            with self._lock:
                self.closed = True
                self._host = None
                if self._pooled is not None:
                    self._pooled.free()
                    self._pooled = None
                if self._path is not None:
                    try:
                        os.unlink(self._path)
                    except OSError:
                        pass
                    self._path = None
            self._catalog.budget.release(self._nbytes, owner=self.owner)
            self._catalog.unregister(self.handle)
            raise

    def _verify_host(self, host):
        """Seeded corruption site plus checksum verification at
        re-materialization — host- and disk-tier entries both pass
        through here on their way back to the device."""
        if _faults.armed():
            host = list(host)
            for idx, leaf in enumerate(host):
                if isinstance(leaf, np.ndarray) and leaf.size:
                    # adopt the return value: read-only leaves are
                    # corrupted on a copy, not in place
                    host[idx] = _faults.corrupt_point(
                        "spill.materialize", leaf,
                        f"handle={self.handle};leaf={idx};")
        if self._crcs is None:
            return host
        for idx, (leaf, crc) in enumerate(zip(host, self._crcs)):
            if crc is None or not isinstance(leaf, np.ndarray):
                continue
            actual = array_checksum(leaf)
            if actual != crc:
                raise DataCorruption(
                    f"spill entry handle={self.handle} leaf={idx} "
                    f"failed verification at re-materialization",
                    expected=crc, actual=actual,
                    detail="entry dropped; recompute the batch")
        return host

    def _load_slab(self):
        """Read a raw .slab spill back (O_DIRECT when the 4K-aligned
        buffer qualifies, buffered otherwise) and rebuild leaves."""
        from ..native import direct_read
        metas, scalars, nleaves, total = self._slab
        # 4096-aligned destination so O_DIRECT reads qualify
        raw = np.empty(max(total, 1) + 4096, np.uint8)
        off = (-raw.ctypes.data) % 4096
        buf = raw[off:off + max(total, 1)]
        if not direct_read(self._path, buf.ctypes.data, max(total, 1)):
            buf = np.fromfile(self._path, np.uint8, count=total)
        leaves = [None] * nleaves
        for i, v in scalars.items():
            leaves[i] = v
        for i, offset, shape, dtype in metas:
            count = int(np.prod(shape)) if shape else 1
            nbytes = count * dtype.itemsize
            if nbytes:
                arr = np.frombuffer(buf.data, dtype=dtype, count=count,
                                    offset=offset).reshape(shape)
            else:
                arr = np.zeros(shape, dtype)
            leaves[i] = arr
        self._slab = None
        return leaves

    def close(self) -> None:
        with self._lock:
            if self.closed:
                return
            self.closed = True
            if self._batch is not None:
                self._catalog.budget.release(self._nbytes,
                                             owner=self.owner)
                self._batch = None
            self._host = None
            if self._pooled is not None:
                self._pooled.free()
                self._pooled = None
            if self._path is not None:
                try:
                    os.unlink(self._path)
                except OSError:
                    pass
                self._path = None
        self._catalog.unregister(self.handle)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class SpillCatalog:
    """Handle registry + spill policy (RapidsBufferCatalog.scala:62).

    ``synchronous_spill(n)`` frees at least n device bytes by spilling
    registered batches in priority order, then pushes host-tier overflow
    to disk when the host limit is exceeded.
    """

    def __init__(self, budget: Optional[MemoryBudget] = None,
                 host_limit: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        from ..conf import (HOST_SPILL_LIMIT, INTEGRITY_CHECKSUM,
                            SPILL_DIR, active_conf)
        conf = active_conf()
        self.budget = budget or device_budget()
        self.budget.set_spill_callback(self.synchronous_spill)
        self.host_limit = host_limit or conf.get(HOST_SPILL_LIMIT)
        self.verify_checksums = conf.get(INTEGRITY_CHECKSUM)
        # disk-tier entries live in a PER-SESSION directory under the
        # configured root: a process killed mid-query cannot leak
        # orphaned mkstemp files forever — this process removes its own
        # dir at exit, and any dir whose owning pid is gone is swept
        # here on the next catalog init
        base = spill_dir or conf.get(SPILL_DIR)
        os.makedirs(base, exist_ok=True)
        sweep_stale_spill_dirs(base)
        self.spill_root = base
        self.spill_dir = tempfile.mkdtemp(
            prefix=f"session-{os.getpid()}-", dir=base)
        atexit.register(_remove_session_dir, self.spill_dir)
        self._entries: Dict[int, SpillableBatch] = {}
        self._next = 0
        self._lock = threading.Lock()
        self.host_pool = None
        from ..conf import LEAK_DETECTION
        self.leak_detection = conf.get(LEAK_DETECTION)
        from ..native import native_available
        if native_available():
            from ..native import HostMemoryPool
            self.host_pool = HostMemoryPool(self.host_limit)

    def try_pool_pack(self, host_leaves) -> Optional[_PooledLeaves]:
        """Pack spilled leaves into the native host pool; exhaustion
        cascades existing host-tier entries to disk
        (RapidsHostMemoryStore's spill-on-alloc-failure contract).
        None = caller keeps a plain numpy fallback."""
        if self.host_pool is None:
            return None
        pooled = _PooledLeaves.pack(self.host_pool, host_leaves)
        if pooled is not None:
            return pooled
        with self._lock:
            victims = sorted(
                (e for e in self._entries.values() if e.tier == "host"),
                key=lambda e: (e.priority, -e.nbytes))
        for v in victims:
            v.spill_to_disk()
            pooled = _PooledLeaves.pack(self.host_pool, host_leaves)
            if pooled is not None:
                return pooled
        return None

    def register(self, sb: SpillableBatch) -> int:
        with self._lock:
            h = self._next
            self._next += 1
            self._entries[h] = sb
            return h

    def unregister(self, handle: int) -> None:
        with self._lock:
            self._entries.pop(handle, None)

    def device_candidates(self) -> List[SpillableBatch]:
        with self._lock:
            return sorted(
                (e for e in self._entries.values() if e.tier == "device"),
                key=lambda e: (e.priority, -e.nbytes))

    def synchronous_spill(self, target_bytes: int,
                          requester: Optional[str] = None,
                          active_owners=None) -> int:
        """Free >= target_bytes of device memory if possible
        (RapidsBufferCatalog.synchronousSpill:589).

        Budget-slice isolation: when the budget passes the requesting
        query and the live-owner set, candidates belonging to OTHER
        live queries are skipped — a tenant's pressure spills only its
        own batches, untagged ones, and leftovers of finished queries
        (idle slices). Legacy single-tenant callers pass neither and
        see the original all-candidates behavior. A cancel/deadline on
        the requesting query aborts mid-spill (the reservation that
        triggered this pass is moot)."""
        from ..robustness.admission import current_query
        qc = current_query()
        freed = 0
        for e in self.device_candidates():
            if freed >= target_bytes:
                break
            if qc is not None:
                qc.check()  # teardown point: mid-spill cancellation
            owner = e.owner
            if (active_owners and owner is not None
                    and owner != requester and owner in active_owners):
                continue  # another live query's slice: not evictable
            n = e.spill_to_host()
            if n and owner is not None and owner != requester:
                # observable proof of the isolation contract: only
                # finished queries' leftovers cross tenant lines
                _events.emit("CrossQuerySpill", bytes=n, owner=owner,
                             requested_by=requester,
                             owner_active=bool(
                                 active_owners
                                 and owner in active_owners))
            freed += n
        self._enforce_host_limit()
        return freed

    def _host_used(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values()
                       if e.tier == "host")

    def _enforce_host_limit(self) -> None:
        used = self._host_used()
        if used <= self.host_limit:
            return
        with self._lock:
            host = sorted((e for e in self._entries.values()
                           if e.tier == "host"),
                          key=lambda e: (e.priority, -e.nbytes))
        for e in host:
            if used <= self.host_limit:
                break
            used -= e.spill_to_disk()

    def leak_report(self) -> List[dict]:
        """Entries still registered — each is a leaked (never-closed)
        spillable. With srt.memory.leakDetection.enabled the creation
        stack pinpoints the owner (MemoryCleaner.scala role: the
        reference dumps leaked RapidsBuffers at executor shutdown)."""
        with self._lock:
            entries = list(self._entries.values())
        return [{"handle": e.handle, "tier": e.tier,
                 "nbytes": e.nbytes, "priority": int(e.priority),
                 "creation_stack": e.creation_stack}
                for e in entries if not e.closed]

    def log_leaks(self) -> int:
        import logging
        leaks = self.leak_report()
        log = logging.getLogger("spark_rapids_tpu.memory")
        for lk in leaks:
            log.warning(
                "LEAKED SpillableBatch handle=%s tier=%s bytes=%d%s",
                lk["handle"], lk["tier"], lk["nbytes"],
                ("\n" + lk["creation_stack"])
                if lk["creation_stack"] else "")
        return len(leaks)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            tiers = {"device": 0, "host": 0, "disk": 0}
            for e in self._entries.values():
                t = e.tier
                if t in tiers:
                    tiers[t] += e.nbytes
        tiers["budget_used"] = self.budget.used
        tiers["budget_limit"] = self.budget.limit
        return tiers


_SESSION_DIR_RE = re.compile(r"^session-(\d+)-")


def _remove_session_dir(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        return True  # exists but not ours (EPERM etc.)
    return True


def sweep_stale_spill_dirs(base: str) -> int:
    """Remove session spill directories whose owning process is gone
    (killed mid-query before its atexit cleanup could run). Returns the
    number of directories swept."""
    try:
        names = os.listdir(base)
    except OSError:
        return 0
    swept = 0
    for name in names:
        m = _SESSION_DIR_RE.match(name)
        if m is None:
            continue
        pid = int(m.group(1))
        if pid == os.getpid() or _pid_alive(pid):
            continue
        shutil.rmtree(os.path.join(base, name), ignore_errors=True)
        swept += 1
    return swept


_CATALOG: Optional[SpillCatalog] = None
_CAT_LOCK = threading.Lock()


def spill_catalog() -> SpillCatalog:
    global _CATALOG
    with _CAT_LOCK:
        if _CATALOG is None:
            _CATALOG = SpillCatalog()
        return _CATALOG


def reset_spill_catalog(**kwargs) -> SpillCatalog:
    """Test hook: fresh catalog (optionally with a fresh budget)."""
    global _CATALOG
    with _CAT_LOCK:
        _CATALOG = SpillCatalog(**kwargs)
        return _CATALOG
