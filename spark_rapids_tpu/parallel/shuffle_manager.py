"""Shuffle manager: pluggable block-based shuffle with three modes.

Rebuild of RapidsShuffleInternalManagerBase.scala (:1075, SURVEY §2.7)
and its catalogs (ShuffleBufferCatalog / ShuffleReceivedBufferCatalog),
re-architected for TPU:

- CACHE_ONLY:     blocks stay device-resident as SpillableBatches in a
                  ShuffleBlockCatalog (RapidsCachingWriter path); spill
                  tiering applies automatically under memory pressure.
- MULTITHREADED:  blocks serialize on a writer thread pool to host
                  memory (optionally zstd-compressed, the nvcomp-LZ4
                  role) and deserialize on a reader pool — the
                  reference's threaded file shuffle with host RAM
                  standing in for shuffle files.
- NATIVE:         the SPMD path: shuffle IS a mesh all-to-all inside
                  the compiled program (shuffle.py shuffle_exchange) —
                  this manager only records metadata for it, because
                  ICI collectives live inside jit, not behind an RPC
                  (SURVEY §2.7 "TPU equivalent" row).

A driver-side heartbeat registry (RapidsShuffleHeartbeatManager role)
tracks executor liveness for multi-host deployments.
"""

from __future__ import annotations

import concurrent.futures as cf
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from ..columnar.vector import ColumnarBatch, choose_capacity
from ..conf import (SHUFFLE_COMPRESS, SHUFFLE_MODE, SHUFFLE_PARTITIONS,
                    SrtConf, active_conf)
from ..jit_registry import read_int
from ..memory.spill import SpillPriority, SpillableBatch
from ..robustness import integrity
from ..robustness.faults import corrupt_point, fault_point
from .serializer import deserialize_batch, serialize_batch

BlockId = Tuple[int, int, int]  # (shuffle_id, map_id, reduce_id)

#: reduce id of a map batch held whole (``ShuffleManager.hold_map_output``):
#: it sorts before every real partition, and no reduce partition reads it
WHOLE = -1


class ShuffleBlockCatalog:
    """Device-resident shuffle blocks as spillables
    (ShuffleBufferCatalog.scala role)."""

    def __init__(self):
        self._blocks: Dict[BlockId, List[SpillableBatch]] = {}
        self._lock = threading.Lock()

    def add(self, block: BlockId, batch: ColumnarBatch) -> None:
        sb = SpillableBatch(batch, SpillPriority.SHUFFLE_OUTPUT)
        with self._lock:
            old = self._blocks.get(block)
            # a replayed map task (OOM retry) OVERWRITES its block —
            # appending would duplicate the partition's rows
            self._blocks[block] = [sb]
        if old:
            for prev in old:
                prev.close()

    def get(self, block: BlockId) -> List[ColumnarBatch]:
        from ..memory.retry import with_retry_no_split
        with self._lock:
            sbs = list(self._blocks.get(block, []))
        # rematerializing a spilled block reserves device budget; OOM
        # here spills other blocks and retries (pure re-read)
        return with_retry_no_split(lambda: [sb.get() for sb in sbs])

    def blocks_for_reduce(self, shuffle_id: int,
                          reduce_id: int) -> List[BlockId]:
        with self._lock:
            return sorted(b for b in self._blocks
                          if b[0] == shuffle_id and b[2] == reduce_id)

    def remove(self, block: BlockId) -> None:
        with self._lock:
            gone = self._blocks.pop(block, [])
        for sb in gone:
            sb.close()

    def remove_shuffle(self, shuffle_id: int) -> int:
        with self._lock:
            gone = [b for b in self._blocks if b[0] == shuffle_id]
            n = 0
            for b in gone:
                for sb in self._blocks.pop(b):
                    sb.close()
                    n += 1
        return n


class HostBlockStore:
    """Serialized host-memory blocks (the MULTITHREADED mode's 'shuffle
    files'). Blocks are stored inside the integrity layer's framed
    checksum envelope — the checksum is computed once at
    write/registration (SPARK-35275 role) and verified at every
    consumption point (server serve, remote fetch, local read)."""

    def __init__(self):
        self._blocks: Dict[BlockId, bytes] = {}
        self._lock = threading.Lock()
        self.bytes_written = 0

    def put(self, block: BlockId, data: bytes) -> None:
        framed = integrity.wrap(data)
        # seeded at-rest corruption (chaos/tests): flips a byte of the
        # stored frame so every later verification path must catch it
        framed = corrupt_point(
            "shuffle.block.store", framed,
            f"sid={block[0]};map={block[1]};reduce={block[2]};")
        with self._lock:
            self._blocks[block] = framed
            self.bytes_written += len(framed)

    def get(self, block: BlockId) -> Optional[bytes]:
        """The raw FRAMED bytes (header + payload) — what the transport
        serves; consumers unwrap/verify."""
        with self._lock:
            return self._blocks.get(block)

    def remove_block(self, block: BlockId) -> bool:
        with self._lock:
            data = self._blocks.pop(block, None)
            if data is not None:
                self.bytes_written -= len(data)
            return data is not None

    def blocks_for_reduce(self, shuffle_id: int,
                          reduce_id: int) -> List[BlockId]:
        with self._lock:
            return sorted(b for b in self._blocks
                          if b[0] == shuffle_id and b[2] == reduce_id)

    def blocks_for_map(self, shuffle_id: int,
                       map_id: int) -> List[BlockId]:
        with self._lock:
            return sorted(b for b in self._blocks
                          if b[0] == shuffle_id and b[1] == map_id)

    def remove_shuffle(self, shuffle_id: int) -> int:
        with self._lock:
            gone = [b for b in self._blocks if b[0] == shuffle_id]
            for b in gone:
                self.bytes_written -= len(self._blocks.pop(b))
            return len(gone)

    def rename_shuffle(self, old_id: int, new_id: int) -> int:
        with self._lock:
            gone = [b for b in self._blocks if b[0] == old_id]
            for b in gone:
                self._blocks[(new_id, b[1], b[2])] = self._blocks.pop(b)
            return len(gone)


class _Segment:
    """One reduce partition's append-only consolidated bytes: the
    framed envelopes of every pushed block, back to back, plus an index
    of where each (origin, map_id) entry sits."""

    __slots__ = ("buf", "index")

    def __init__(self):
        self.buf = bytearray()
        #: (origin_endpoint, map_id) -> (offset, length, rows); a
        #: re-pushed entry (map replay) re-points the index at its new
        #: bytes — the old range becomes dead space, never re-read
        self.index: Dict[Tuple[str, int], Tuple[int, int, int]] = {}


class SegmentStore:
    """Receive-side consolidation of PUSHED shuffle blocks into
    per-reducer segments (the push-based shuffle's 'merged shuffle
    file' role, Spark's magnet push-merge). Each pushed block is
    appended — still inside its integrity frame — to the segment for
    its (shuffle_id, reduce_id), so a reducer's read is ONE sequential
    scan over local memory instead of maps-many socket round trips.

    Integrity granularity is the ENTRY: every frame verifies on scan,
    and a corrupt entry is quarantined alone (dropped from the index)
    — the reader re-pulls just that (origin, map_id) from its origin,
    never losing the rest of the segment. Entries carry exact
    (rows, bytes), so the index doubles as the receive-side
    MapOutputStatistics source (no second accounting pass)."""

    def __init__(self):
        self._segments: Dict[Tuple[int, int], _Segment] = {}
        self._lock = threading.Lock()
        self.bytes_appended = 0
        self.entries_appended = 0
        self.entries_quarantined = 0

    def append(self, shuffle_id: int, reduce_id: int, origin: str,
               map_id: int, rows: int, framed: bytes) -> None:
        # seeded corrupt-at-rest-in-segment (chaos/tests): flips a byte
        # of the entry as stored, so the per-entry verification on scan
        # must quarantine exactly this entry
        framed = corrupt_point(
            "shuffle.segment.store", framed,
            f"sid={shuffle_id};reduce={reduce_id};m={map_id};"
            f"origin={origin};")
        with self._lock:
            seg = self._segments.setdefault((shuffle_id, reduce_id),
                                            _Segment())
            off = len(seg.buf)
            seg.buf += framed
            seg.index[(origin, map_id)] = (off, len(framed), int(rows))
            self.bytes_appended += len(framed)
            self.entries_appended += 1

    def entries(self, shuffle_id: int, reduce_id: int
                ) -> List[Tuple[str, int, int, int]]:
        """Sorted (origin, map_id, length, rows) index view."""
        with self._lock:
            seg = self._segments.get((shuffle_id, reduce_id))
            if seg is None:
                return []
            return sorted((o, m, ln, rows)
                          for (o, m), (_off, ln, rows) in
                          seg.index.items())

    def map_ids_from(self, shuffle_id: int,
                     reduce_id: int) -> Dict[str, set]:
        """origin endpoint -> map ids present — the pull path's
        per-peer exclude sets."""
        out: Dict[str, set] = {}
        with self._lock:
            seg = self._segments.get((shuffle_id, reduce_id))
            if seg is None:
                return out
            for (o, m) in seg.index:
                out.setdefault(o, set()).add(m)
        return out

    def scan(self, shuffle_id: int, reduce_id: int, keep=None,
             verify: bool = True):
        """One sequential pass over the segment: yields
        ``(origin, map_id, payload)`` for every live index entry that
        passes ``keep(origin, map_id)``, verifying each frame. A frame
        that fails verification quarantines ONLY its own entry (the
        index forgets it; the dead bytes stay) — the caller's pull
        fallback refetches that (origin, map_id) from its origin."""
        with self._lock:
            seg = self._segments.get((shuffle_id, reduce_id))
            if seg is None:
                return
            # snapshot in OFFSET order (the sequential scan); appends
            # during iteration only extend past the snapshot
            items = sorted(((off, ln, rows, o, m)
                            for (o, m), (off, ln, rows) in
                            seg.index.items()))
            buf = seg.buf
        for off, ln, _rows, origin, map_id in items:
            if keep is not None and not keep(origin, map_id):
                continue
            framed = bytes(buf[off:off + ln])
            if not verify:
                yield origin, map_id, integrity.strip(framed)
                continue
            try:
                payload = integrity.unwrap(
                    framed, what=f"segment entry sid={shuffle_id} "
                                 f"reduce={reduce_id} m={map_id} "
                                 f"from {origin}")
            except integrity.DataCorruption as e:
                self.quarantine_entry(shuffle_id, reduce_id, origin,
                                      map_id, reason=str(e))
                continue
            yield origin, map_id, payload

    def quarantine_entry(self, shuffle_id: int, reduce_id: int,
                         origin: str, map_id: int,
                         reason: str = "") -> bool:
        """Drop ONE corrupt entry from the index — unlike block-store
        quarantine this never poisons the shuffle: the origin still
        holds the authoritative block, so recovery is a point refetch
        (recompute of one entry), not a whole-segment loss."""
        import logging
        with self._lock:
            seg = self._segments.get((shuffle_id, reduce_id))
            dropped = (seg is not None
                       and seg.index.pop((origin, map_id), None)
                       is not None)
            if dropped:
                self.entries_quarantined += 1
        if dropped:
            logging.getLogger("spark_rapids_tpu.shuffle").warning(
                "quarantined corrupt segment entry sid=%s reduce=%s "
                "map=%s origin=%s%s", shuffle_id, reduce_id, map_id,
                origin, f": {reason}" if reason else "")
        return dropped

    def statistics(self, shuffle_id: int,
                   num_partitions: int) -> MapOutputStatistics:
        """Exact per-(map, reduce) (rows, bytes) straight from the
        segment index — what pushed entries declared at write time, no
        re-walk of any block store. Bytes are the framed payload sizes
        (frame header excluded) to match the write-side accounting."""
        detail: Dict[Tuple[int, int], Tuple[int, int]] = {}
        with self._lock:
            for (sid, rid), seg in self._segments.items():
                if sid != shuffle_id or rid >= num_partitions:
                    continue
                for (_o, m), (_off, ln, rows) in seg.index.items():
                    pr, pb = detail.get((m, rid), (0, 0))
                    detail[(m, rid)] = (
                        pr + rows,
                        pb + max(ln - integrity.HEADER_SIZE, 0))
        rows_by = [0] * num_partitions
        bytes_by = [0] * num_partitions
        for (_m, rid), (rows, nbytes) in detail.items():
            rows_by[rid] += rows
            bytes_by[rid] += nbytes
        return MapOutputStatistics(shuffle_id, num_partitions, rows_by,
                                   bytes_by, detail)

    def remove_shuffle(self, shuffle_id: int) -> int:
        with self._lock:
            gone = [k for k in self._segments if k[0] == shuffle_id]
            n = 0
            for k in gone:
                seg = self._segments.pop(k)
                n += len(seg.index)
                self.bytes_appended -= len(seg.buf)
            return n

    def rename_shuffle(self, old_id: int, new_id: int) -> int:
        """Stage-level retry: received segments re-key alongside the
        origin blocks, so surviving pushed entries keep serving reads
        under the re-planned exchange's fresh shuffle id."""
        with self._lock:
            gone = [k for k in self._segments if k[0] == old_id]
            n = 0
            for k in gone:
                self._segments[(new_id, k[1])] = self._segments.pop(k)
                n += 1
            return n


@dataclass
class ShuffleWriteMetrics:
    blocks_written: int = 0
    rows_written: int = 0
    bytes_written: int = 0
    write_time_ns: int = 0


@dataclass
class MapOutputStatistics:
    """Exact per-(map, reduce) shuffle sizes for one shuffle — what the
    reference's AQE reads from Spark's MapOutputStatistics, here with
    both rows and bytes so adaptive rules can reason in either unit.
    ``detail`` maps (map_id, reduce_id) -> (rows, bytes); the
    ``*_by_reduce`` lists are its per-reduce sums."""

    shuffle_id: int
    num_partitions: int
    rows_by_reduce: List[int]
    bytes_by_reduce: List[int]
    detail: Dict[Tuple[int, int], Tuple[int, int]]

    @property
    def total_rows(self) -> int:
        return sum(self.rows_by_reduce)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_reduce)

    @classmethod
    def from_events(cls, events: Sequence[dict],
                    shuffle_id: int) -> "MapOutputStatistics":
        """Rebuild the statistics offline from ShuffleWrite event-log
        records (tools/history_report.py's path; the in-engine path
        reads ShuffleManager.map_output_statistics instead). JSON
        round-trips dict keys as strings, hence the int() parses."""
        detail: Dict[Tuple[int, int], Tuple[int, int]] = {}
        nparts = 0
        for rec in events:
            if (rec.get("event") != "ShuffleWrite"
                    or rec.get("shuffle_id") != shuffle_id):
                continue
            mid = int(rec.get("map_id", 0))
            rrows = rec.get("reduce_rows") or {}
            rbytes = rec.get("reduce_bytes") or {}
            for rid_s, rows in rrows.items():
                rid = int(rid_s)
                nparts = max(nparts, rid + 1)
                detail[(mid, rid)] = (int(rows),
                                      int(rbytes.get(rid_s, 0) or 0))
        rows_by = [0] * nparts
        bytes_by = [0] * nparts
        for (_mid, rid), (rows, nbytes) in detail.items():
            rows_by[rid] += rows
            bytes_by[rid] += nbytes
        return cls(shuffle_id, nparts, rows_by, bytes_by, detail)


class ReplicaStore:
    """Buddy copies of OTHER workers' completed map output, keyed
    ``(origin_endpoint, shuffle_id, map_id, reduce_id)`` — origin is
    part of the key because map ids are only unique per worker (every
    worker numbers maps from the same ``attempt << 20`` base), so
    merging replicas into the host store would silently collide.
    Replicas never feed normal fetches, statistics, or local reads;
    they serve only origin-addressed replica fetches (transport
    MAGIC_FETCH_REPL) issued by a reader whose pull from the origin
    failed terminally. Entries keep their integrity framing so the
    checksum travels with the bytes."""

    def __init__(self):
        self._lock = threading.Lock()
        self._blocks: Dict[Tuple[str, int, int, int], bytes] = {}
        #: (origin, shuffle_id) -> {reduce_id: (map ids...)} — what a
        #: COMPLETE replica set contains, published by the origin only
        #: AFTER its replica pushes drained. Replica pushes are
        #: best-effort (a dead buddy or timeout silently drops one), so
        #: without the manifest a buddy fetch could serve a partial
        #: partition as if it were whole. No manifest, or a manifest
        #: block missing from the store -> no coverage -> the reader
        #: falls back to stage retry.
        self._manifests: Dict[Tuple[str, int],
                              Dict[int, Tuple[int, ...]]] = {}
        self.bytes_stored = 0
        self.blocks_stored = 0

    def put(self, origin: str, shuffle_id: int, map_id: int,
            reduce_id: int, framed: bytes) -> None:
        with self._lock:
            key = (origin, shuffle_id, map_id, reduce_id)
            prev = self._blocks.get(key)
            self._blocks[key] = framed
            self.bytes_stored += len(framed) - (len(prev) if prev else 0)
            if prev is None:
                self.blocks_stored += 1

    def put_manifest(self, origin: str, shuffle_id: int,
                     manifest: Dict[int, Tuple[int, ...]]) -> None:
        with self._lock:
            self._manifests[(origin, shuffle_id)] = {
                int(r): tuple(sorted(ms))
                for r, ms in manifest.items()}

    def coverage(self, origin: str, shuffle_id: int, reduce_id: int
                 ) -> Optional[List[Tuple[int, bytes]]]:
        """The COMPLETE replica set for one (origin, reduce) — (map_id,
        framed) in map order — or None when this store cannot vouch for
        completeness (no manifest from the origin, or a manifest block
        that never arrived). An empty list is a real answer: the origin
        produced no blocks for this partition."""
        with self._lock:
            man = self._manifests.get((origin, shuffle_id))
            if man is None:
                return None
            out: List[Tuple[int, bytes]] = []
            for map_id in man.get(reduce_id, ()):
                framed = self._blocks.get(
                    (origin, shuffle_id, map_id, reduce_id))
                if framed is None:
                    return None
                out.append((map_id, framed))
            return out

    def drop(self, origin: str, shuffle_id: int, map_id: int,
             reduce_id: int) -> None:
        with self._lock:
            prev = self._blocks.pop(
                (origin, shuffle_id, map_id, reduce_id), None)
            if prev is not None:
                self.bytes_stored -= len(prev)
                self.blocks_stored -= 1

    def remove_shuffle(self, shuffle_id: int) -> None:
        with self._lock:
            for k in [k for k in self._blocks if k[1] == shuffle_id]:
                self.bytes_stored -= len(self._blocks[k])
                self.blocks_stored -= 1
                del self._blocks[k]
            for k in [k for k in self._manifests if k[1] == shuffle_id]:
                del self._manifests[k]

    def rename_shuffle(self, old_id: int, new_id: int) -> None:
        with self._lock:
            for k in [k for k in self._blocks if k[1] == old_id]:
                self._blocks[(k[0], new_id, k[2], k[3])] = \
                    self._blocks.pop(k)
            for k in [k for k in self._manifests if k[1] == old_id]:
                self._manifests[(k[0], new_id)] = \
                    self._manifests.pop(k)

    def clear(self) -> None:
        """Drop everything — replicas have no cross-job value (shuffle
        ids are fresh per attempt), and a rejoined worker process
        restarts its shuffle-id counter, so stale entries from an
        earlier incarnation could otherwise collide with new sids."""
        with self._lock:
            self._blocks.clear()
            self._manifests.clear()
            self.bytes_stored = 0
            self.blocks_stored = 0


class ShuffleManager:
    """getWriter/getReader surface over the mode-selected store."""

    def __init__(self, conf: Optional[SrtConf] = None,
                 num_threads: int = 4):
        self.conf = conf or active_conf()
        self.mode = self.conf.get(SHUFFLE_MODE).upper()  # MESH|MULTITHREADED|CACHE_ONLY
        self.codec = self.conf.get(SHUFFLE_COMPRESS).lower()
        self.compress = self.codec != "none"
        from ..conf import INTEGRITY_CHECKSUM
        self.verify_checksums = self.conf.get(INTEGRITY_CHECKSUM)
        from ..conf import (SHUFFLE_PUSH_ENABLED, SHUFFLE_PUSH_LOCAL_BYPASS)
        #: push-based shuffle only applies to the serialized-block mode;
        #: CACHE_ONLY never leaves the process and MESH shuffles inside
        #: the compiled program
        self.push_enabled = (self.conf.get(SHUFFLE_PUSH_ENABLED)
                             and self.mode == "MULTITHREADED")
        self.local_bypass = self.conf.get(SHUFFLE_PUSH_LOCAL_BYPASS)
        self.catalog = ShuffleBlockCatalog()
        self.host_store = HostBlockStore()
        self.segments = SegmentStore()
        self.replicas = ReplicaStore()
        #: this process's shuffle-server endpoint ("host:port"), set by
        #: ShuffleBlockServer — the ORIGIN stamped on every pushed block
        #: (map ids are only unique per peer, so segment entries key on
        #: (origin, map_id))
        self.local_endpoint: Optional[str] = None
        self._pusher = None
        #: bytes handed through the zero-copy local channel instead of
        #: serializer+socket+deserializer (shuffleBytesBypassed)
        self.bypassed_bytes = 0
        #: shuffles with a corrupt-at-rest block: their outputs must
        #: never be served or reused (stage-level reuse of a poisoned
        #: sid fails over to a whole-job retry that regenerates them)
        self._poisoned_sids: set = set()
        self.integrity_failures = 0
        self._pool = cf.ThreadPoolExecutor(max_workers=num_threads)
        self._registered: Dict[int, int] = {}  # shuffle_id -> num_parts
        #: (shuffle_id, reduce_id) -> rows written (AQE statistics — the
        #: MapOutputStatistics the reference's AQE reads from Spark)
        #: rows per (shuffle, map, reduce): replays overwrite their
        #: own map's contribution instead of double-counting
        self._part_rows: Dict[Tuple[int, int, int], int] = {}
        #: exact serialized bytes per (shuffle, map, reduce) — recorded
        #: at write time (CACHE_ONLY estimates from device buffers);
        #: the byte half of MapOutputStatistics
        self._part_bytes: Dict[Tuple[int, int, int], int] = {}
        #: running per-(shuffle, reduce) sums maintained at write time —
        #: partition_row_counts/partition_byte_counts read these in O(n)
        #: instead of scanning every (map, reduce) entry per call
        self._reduce_rows: Dict[Tuple[int, int], int] = {}
        self._reduce_bytes: Dict[Tuple[int, int], int] = {}
        self.write_metrics = ShuffleWriteMetrics()
        self._lock = threading.Lock()

    # --- lifecycle ---
    def register_shuffle(self, shuffle_id: int, num_partitions: int) -> None:
        with self._lock:
            self._registered[shuffle_id] = num_partitions

    def unregister_shuffle(self, shuffle_id: int) -> None:
        self.catalog.remove_shuffle(shuffle_id)
        self.host_store.remove_shuffle(shuffle_id)
        self.segments.remove_shuffle(shuffle_id)
        self.replicas.remove_shuffle(shuffle_id)
        with self._lock:
            self._registered.pop(shuffle_id, None)
            self._poisoned_sids.discard(shuffle_id)
            for k in [k for k in self._part_rows if k[0] == shuffle_id]:
                del self._part_rows[k]
            for k in [k for k in self._part_bytes if k[0] == shuffle_id]:
                del self._part_bytes[k]
            for d in (self._reduce_rows, self._reduce_bytes):
                for k in [k for k in d if k[0] == shuffle_id]:
                    del d[k]

    # --- integrity ---
    def is_poisoned(self, shuffle_id: int) -> bool:
        with self._lock:
            return shuffle_id in self._poisoned_sids

    def quarantine_block(self, block: BlockId, reason: str = "") -> None:
        """A stored block failed verification: drop it and poison its
        shuffle so no consumer can ever read a partial partition — the
        ONLY safe recoveries are stage rerun / whole-job retry, both of
        which refuse poisoned state and regenerate from scratch."""
        import logging
        self.host_store.remove_block(block)
        with self._lock:
            self._poisoned_sids.add(block[0])
            self.integrity_failures += 1
        logging.getLogger("spark_rapids_tpu.shuffle").warning(
            "quarantined corrupt shuffle block %s%s", block,
            f": {reason}" if reason else "")

    def rename_shuffle(self, old_id: int, new_id: int) -> int:
        """Re-key every surviving block (and its AQE row stats) from
        ``old_id`` to ``new_id`` — stage-level recovery reuses a prior
        attempt's completed map outputs under the re-planned exchange's
        fresh shuffle id instead of recomputing them."""
        moved = self.host_store.rename_shuffle(old_id, new_id)
        self.segments.rename_shuffle(old_id, new_id)
        self.replicas.rename_shuffle(old_id, new_id)
        with self._lock:
            if old_id in self._poisoned_sids:  # defensive: reuse of a
                self._poisoned_sids.discard(old_id)  # poisoned sid is
                self._poisoned_sids.add(new_id)      # refused upstream
            if old_id in self._registered:
                self._registered[new_id] = self._registered.pop(old_id)
            for k in [k for k in self._part_rows if k[0] == old_id]:
                self._part_rows[(new_id, k[1], k[2])] = \
                    self._part_rows.pop(k)
            for k in [k for k in self._part_bytes if k[0] == old_id]:
                self._part_bytes[(new_id, k[1], k[2])] = \
                    self._part_bytes.pop(k)
            for d in (self._reduce_rows, self._reduce_bytes):
                for k in [k for k in d if k[0] == old_id]:
                    d[(new_id, k[1])] = d.pop(k)
        return moved

    def partition_row_counts(self, shuffle_id: int) -> List[int]:
        """Rows per reduce partition (valid once the map side wrote).
        Reads the running per-reduce sums maintained at write time —
        O(partitions), not O(all blocks ever written)."""
        n = self.num_partitions(shuffle_id)
        with self._lock:
            return [self._reduce_rows.get((shuffle_id, r), 0)
                    for r in range(n)]

    def partition_byte_counts(self, shuffle_id: int) -> List[int]:
        """Serialized bytes per reduce partition (CACHE_ONLY: device
        buffer estimate)."""
        n = self.num_partitions(shuffle_id)
        with self._lock:
            return [self._reduce_bytes.get((shuffle_id, r), 0)
                    for r in range(n)]

    def map_output_statistics(self, shuffle_id: int,
                              map_ids: Optional[set] = None
                              ) -> MapOutputStatistics:
        """Exact per-(map, reduce) rows/bytes for this process's map
        outputs of ``shuffle_id``. ``map_ids`` restricts the view to a
        subset of maps — speculation reports only the maps a worker WON
        so losing duplicates never reach the global statistics."""
        n = self.num_partitions(shuffle_id)
        detail: Dict[Tuple[int, int], Tuple[int, int]] = {}
        with self._lock:
            for (sid, mid, rid), rows in self._part_rows.items():
                if sid != shuffle_id or rid >= n:
                    continue
                if map_ids is not None and mid not in map_ids:
                    continue
                detail[(mid, rid)] = (
                    rows, self._part_bytes.get((sid, mid, rid), 0))
        rows_by = [0] * n
        bytes_by = [0] * n
        for (_mid, rid), (rows, nbytes) in detail.items():
            rows_by[rid] += rows
            bytes_by[rid] += nbytes
        return MapOutputStatistics(shuffle_id, n, rows_by, bytes_by,
                                   detail)

    def num_partitions(self, shuffle_id: int) -> int:
        return self._registered[shuffle_id]

    def received_statistics(self, shuffle_id: int) -> MapOutputStatistics:
        """Receive-side view: exact per-(map, reduce) sizes of every
        pushed entry, read straight from the segment index."""
        return self.segments.statistics(shuffle_id,
                                        self.num_partitions(shuffle_id))

    # --- push path ---
    def _get_pusher(self):
        if self._pusher is None:
            from .transport import BlockPusher
            with self._lock:
                if self._pusher is None:
                    self._pusher = BlockPusher()
        return self._pusher

    def push_map_output(self, shuffle_id: int, map_id: int,
                        route: Dict[int, str], who: str = "") -> int:
        """Eagerly replicate this map's freshly serialized blocks to
        the endpoints that own their reduce partitions (``route``:
        reduce_id -> endpoint), so the reduce-side fetch overlaps the
        remaining map work. Push is REPLICATION — the origin keeps its
        blocks, a failed push silently degrades to the pull path, and
        self-owned partitions are skipped (they read through the local
        short-circuit, no copy needed). Returns blocks enqueued."""
        if not self.push_enabled or self.mode != "MULTITHREADED":
            return 0
        origin = self.local_endpoint
        if not origin:
            return 0  # no server running: nothing can address us back
        pusher = self._get_pusher()
        pushed = 0
        for reduce_id, endpoint in route.items():
            if not endpoint or endpoint == origin:
                continue
            block = (shuffle_id, map_id, reduce_id)
            framed = self.host_store.get(block)
            if framed is None:
                continue  # empty partition for this map
            with self._lock:
                rows = self._part_rows.get(block, 0)
            pusher.push(endpoint, shuffle_id, reduce_id, map_id, rows,
                        framed, origin, who=who)
            pushed += 1
        return pushed

    def replicate_map_output(self, shuffle_id: int, map_id: int,
                             buddy: str, who: str = "") -> int:
        """Conf-gated k=2 durability (srt.shuffle.replication.factor):
        push EVERY block of this completed map — all reduce partitions,
        including the ones this worker owns — to ``buddy``'s replica
        store, so a hard kill of this worker degrades to a buddy fetch
        instead of a stage re-execution. Reuses the eager-push framing
        and integrity checksums; a failed replica push silently leaves
        that block at k=1 (stage retry still covers it). Returns blocks
        enqueued; the caller's drain covers them."""
        if self.mode != "MULTITHREADED":
            return 0
        origin = self.local_endpoint
        if not origin or not buddy or buddy == origin:
            return 0
        pusher = self._get_pusher()
        pushed = 0
        for block in sorted(self.host_store.blocks_for_map(shuffle_id,
                                                           map_id)):
            framed = self.host_store.get(block)
            if framed is None:
                continue
            with self._lock:
                rows = self._part_rows.get(block, 0)
            pusher.push(buddy, shuffle_id, block[2], map_id, rows,
                        framed, origin, who=who, replica=True)
            pushed += 1
        return pushed

    def publish_replica_manifest(self, shuffle_id: int, buddy: str,
                                 timeout_s: float = 30.0) -> bool:
        """After this shuffle's replica pushes drained: tell ``buddy``
        exactly which blocks a COMPLETE replica set of this origin
        contains ({reduce: (map ids...)}, read from the host store).
        The buddy only answers replica fetches for partitions where it
        holds every manifest block — so a silently dropped best-effort
        push degrades coverage to none (stage retry) instead of to a
        partial partition (wrong rows). Synchronous single attempt;
        False means the buddy never learned of these replicas."""
        if self.mode != "MULTITHREADED":
            return False
        origin = self.local_endpoint
        if not origin or not buddy or buddy == origin:
            return False
        with self._lock:
            nparts = self._registered.get(shuffle_id)
        if nparts is None or self.is_poisoned(shuffle_id):
            return False
        manifest = {
            rid: tuple(b[1] for b in self.host_store.blocks_for_reduce(
                shuffle_id, rid))
            for rid in range(nparts)}
        import pickle
        framed = integrity.wrap(pickle.dumps(manifest))
        from .transport import _MANIFEST_MAP_ID, _push_once
        try:
            return _push_once(buddy, shuffle_id, 0, _MANIFEST_MAP_ID,
                              0, framed, origin, timeout_s,
                              replica=True)
        except OSError:
            return False

    def migrate_blocks(self, target: str, deadline: float) -> List[int]:
        """Graceful-decommission block migration: replica-push every
        registered, non-poisoned shuffle's host-store blocks (this
        worker's own completed map output — received push segments need
        no migration, their origins stay authoritative) to ``target``,
        stopping at ``deadline`` (time.monotonic). Returns the shuffle
        ids migrated and emits one BlockMigrated event per shuffle; the
        caller must drain the pusher and then publish_replica_manifest
        for each returned sid — without the manifest the buddy will
        never vouch for (or serve) these replicas."""
        origin = self.local_endpoint
        if (self.mode != "MULTITHREADED" or not origin or not target
                or target == origin):
            return []
        from ..obs import events as _events
        pusher = self._get_pusher()
        migrated: List[int] = []
        with self._lock:
            registered = dict(self._registered)
        for sid, nparts in sorted(registered.items()):
            if self.is_poisoned(sid):
                continue
            moved = 0
            for rid in range(nparts):
                if time.monotonic() >= deadline:
                    break
                for block in self.host_store.blocks_for_reduce(sid, rid):
                    framed = self.host_store.get(block)
                    if framed is None:
                        continue
                    with self._lock:
                        rows = self._part_rows.get(block, 0)
                    pusher.push(target, sid, rid, block[1], rows,
                                framed, origin, who="decommission",
                                replica=True)
                    moved += 1
            if time.monotonic() >= deadline:
                break
            if moved:
                _events.emit("BlockMigrated", shuffle_id=sid,
                             blocks=moved, target=target, origin=origin)
            migrated.append(sid)
        return migrated

    def drain_pushes(self, timeout_s: float = 30.0) -> bool:
        """Block until every enqueued push acked, failed, or timed out
        — called before the stage barrier so a released reducer sees
        all successful pushes in its segment. False = timed out with
        pushes still in flight (harmless: readers snapshot + exclude,
        so a late push is simply ignored and its block pulls)."""
        if self._pusher is None:
            return True
        return self._pusher.drain(timeout_s)

    # --- write path ---
    def write_map_output(self, shuffle_id: int, map_id: int,
                         partitions: Sequence[ColumnarBatch],
                         local_ok: bool = False) -> int:
        """One map task's output: partitions[i] goes to reduce i.
        Returns serialized bytes written (0 in CACHE_ONLY mode).

        ``local_ok=True`` asserts every consumer of this shuffle runs in
        THIS process (driver-local session) — with the push locality
        bypass on, MULTITHREADED writes then hand the live batch through
        the device catalog (zero-copy local channel) instead of
        serializer+socket+deserializer, counted as bypassed bytes."""
        t0, bytes_before = self._begin_write(shuffle_id, map_id)
        bypass = self._bypass(local_ok)
        bypassed_nb = 0
        futures = []
        local_rows: Dict[int, int] = {}
        local_bytes: Dict[int, int] = {}
        for reduce_id, batch in enumerate(partitions):
            n = 0 if batch is None else read_int(batch.num_rows)
            if n == 0:
                continue
            local_rows[reduce_id] = n
            block = (shuffle_id, map_id, reduce_id)
            if self.mode == "CACHE_ONLY" or bypass:
                from ..memory.spill import batch_nbytes
                nb = batch_nbytes(batch)
                local_bytes[reduce_id] = nb
                self.catalog.add(block, batch)
                self.write_metrics.rows_written += n
                self.write_metrics.blocks_written += 1
                if bypass:
                    bypassed_nb += nb
            else:  # MULTITHREADED (MESH writes never reach here)
                futures.append((reduce_id, self._pool.submit(
                    self._serialize_one, block, batch)))
        for reduce_id, f in futures:
            local_bytes[reduce_id] = f.result()
        return self._end_write(shuffle_id, map_id, local_rows, local_bytes,
                               bypassed_nb, t0, bytes_before)

    def holds_on_device(self, local_ok: bool) -> bool:
        """Whether this manager keeps a single-process map output as live
        device batches in its catalog (CACHE_ONLY, or the push locality
        bypass) rather than serialized blocks: what
        ``hold_map_output`` needs."""
        return self.mode == "CACHE_ONLY" or self._bypass(local_ok)

    def hold_map_output(self, shuffle_id: int, map_id: int,
                        batch: ColumnarBatch, reduce_rows: Sequence[int],
                        reduce_bytes: Sequence[int],
                        local_ok: bool = False) -> int:
        """One map task's output held WHOLE in the device catalog,
        under ``(shuffle_id, map_id, WHOLE)``, with the rows and bytes
        each reduce partition would receive from it: the statistics,
        the ``ShuffleWrite`` event, the fault point, the cancel check
        and the spill priority are ``write_map_output``'s. A reader
        that takes every partition at once streams the held batches
        (``read_partition(shuffle_id, WHOLE)``); any other read first
        replaces each by its slices (``split_held``). Needs
        ``holds_on_device``."""
        t0, bytes_before = self._begin_write(shuffle_id, map_id)
        self.catalog.add((shuffle_id, map_id, WHOLE), batch)
        local_rows = {r: n for r, n in enumerate(reduce_rows) if n > 0}
        local_bytes = {r: reduce_bytes[r] for r in local_rows}
        bypassed_nb = (sum(local_bytes.values())
                       if self._bypass(local_ok) else 0)
        return self._end_write(shuffle_id, map_id, local_rows, local_bytes,
                               bypassed_nb, t0, bytes_before)

    def split_held(self, shuffle_id: int, map_id: int, cut) -> None:
        """Replace a held map batch by its slices: ``cut(batch)[i]``
        (None where empty) becomes the block of reduce partition i. The
        statistics were recorded when the batch was held."""
        held, = self.catalog.get((shuffle_id, map_id, WHOLE))
        for reduce_id, batch in enumerate(cut(held)):
            if batch is None:
                continue
            self.catalog.add((shuffle_id, map_id, reduce_id), batch)
            with self._lock:
                self.write_metrics.rows_written += self._part_rows.get(
                    (shuffle_id, map_id, reduce_id), 0)
            self.write_metrics.blocks_written += 1
        self.catalog.remove((shuffle_id, map_id, WHOLE))

    def _bypass(self, local_ok: bool) -> bool:
        return (local_ok and self.mode == "MULTITHREADED"
                and self.push_enabled and self.local_bypass)

    def _begin_write(self, shuffle_id: int, map_id: int) -> Tuple[int, int]:
        fault_point("shuffle.write", f"sid={shuffle_id};map={map_id};")
        from ..robustness.admission import check_current_query
        check_current_query()  # cancelled query: skip the whole write
        return time.perf_counter_ns(), self.write_metrics.bytes_written

    def _end_write(self, shuffle_id: int, map_id: int,
                   local_rows: Dict[int, int], local_bytes: Dict[int, int],
                   bypassed_nb: int, t0: int, bytes_before: int) -> int:
        """Record one map output's rows and bytes per reduce partition
        (the AQE statistics) and emit its ``ShuffleWrite`` event;
        returns the serialized bytes it wrote."""
        with self._lock:
            self.bypassed_bytes += bypassed_nb
            for reduce_id, rows in local_rows.items():
                key = (shuffle_id, map_id, reduce_id)
                tot = (shuffle_id, reduce_id)
                nb = local_bytes.get(reduce_id, 0)
                # running per-reduce sums: a replayed map replaces its
                # own prior contribution instead of double-counting
                self._reduce_rows[tot] = (self._reduce_rows.get(tot, 0)
                                          + rows
                                          - self._part_rows.get(key, 0))
                self._reduce_bytes[tot] = (
                    self._reduce_bytes.get(tot, 0) + nb
                    - self._part_bytes.get(key, 0))
                self._part_rows[key] = rows
                self._part_bytes[key] = nb
        dt_ns = time.perf_counter_ns() - t0
        self.write_metrics.write_time_ns += dt_ns
        wrote = self.write_metrics.bytes_written - bytes_before
        from ..obs import events as _events
        _events.emit("ShuffleWrite", shuffle_id=shuffle_id,
                     map_id=map_id, blocks=len(local_rows),
                     rows=sum(local_rows.values()), bytes=wrote,
                     write_time_ns=dt_ns,
                     reduce_rows={str(r): v
                                  for r, v in sorted(local_rows.items())},
                     reduce_bytes={str(r): v
                                   for r, v in sorted(local_bytes.items())})
        return wrote

    def _serialize_one(self, block: BlockId, batch: ColumnarBatch) -> int:
        data = serialize_batch(batch, compress=self.compress,
                               codec=self.codec)
        self.host_store.put(block, data)
        from ..obs import registry as _registry
        _registry.observe("shuffle_block_bytes", len(data), "bytes")
        with self._lock:  # writer pool threads race on the counters
            self.write_metrics.rows_written += int(batch.num_rows)
            self.write_metrics.blocks_written += 1
            self.write_metrics.bytes_written += len(data)
        return len(data)

    # --- read path ---
    def read_partition(self, shuffle_id: int, reduce_id: int,
                       map_mod=None) -> Iterator[ColumnarBatch]:
        """All map outputs for one reduce partition, in map order.
        ``map_mod=(s, S)`` keeps only blocks with map_id % S == s — a
        skewed reduce partition splits into S disjoint map slices."""
        fault_point("shuffle.read", f"sid={shuffle_id};reduce={reduce_id};")
        if self.is_poisoned(shuffle_id):
            raise integrity.DataCorruption(
                f"shuffle {shuffle_id} quarantined after a corrupt "
                f"block; partition {reduce_id} is incomplete")
        def keep(map_id: int) -> bool:
            return map_mod is None or map_id % map_mod[1] == map_mod[0]
        if self.mode == "CACHE_ONLY":
            for block in self.catalog.blocks_for_reduce(shuffle_id,
                                                        reduce_id):
                if keep(block[1]):
                    yield from self.catalog.get(block)
            return
        # zero-copy locality bypass: blocks the writer handed through
        # the device catalog (never serialized) serve directly
        for block in self.catalog.blocks_for_reduce(shuffle_id,
                                                    reduce_id):
            if keep(block[1]):
                yield from self.catalog.get(block)
        blocks = [b for b in self.host_store.blocks_for_reduce(
            shuffle_id, reduce_id) if keep(b[1])]
        futures = [self._pool.submit(self._deserialize_one, b)
                   for b in blocks]
        from ..robustness.admission import check_current_query
        for f in futures:
            # abort the fan-in between blocks when the consuming
            # query was cancelled or blew its deadline
            check_current_query()
            batch = f.result()
            if batch is not None:
                yield batch

    def _deserialize_one(self, block: BlockId) -> Optional[ColumnarBatch]:
        framed = self.host_store.get(block)
        if framed is None:
            return None
        if not self.verify_checksums:
            return deserialize_batch(integrity.strip(framed))
        try:
            data = integrity.unwrap(
                framed, what=f"shuffle block sid={block[0]} "
                             f"map={block[1]} reduce={block[2]}")
        except integrity.DataCorruption:
            # local read of a corrupt-at-rest block: quarantine and
            # surface — returning garbage rows is never an option
            self.quarantine_block(block, reason="local read")
            raise
        return deserialize_batch(data)

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)


_MANAGER: Optional[ShuffleManager] = None
_MANAGER_LOCK = threading.Lock()


def shuffle_manager() -> ShuffleManager:
    global _MANAGER
    with _MANAGER_LOCK:
        if _MANAGER is None:
            _MANAGER = ShuffleManager()
        return _MANAGER


def reset_shuffle_manager(conf: Optional[SrtConf] = None) -> ShuffleManager:
    global _MANAGER
    with _MANAGER_LOCK:
        if _MANAGER is not None:
            _MANAGER.shutdown()
        _MANAGER = ShuffleManager(conf)
        return _MANAGER


# ---------------------------------------------------------------------------
# heartbeat registry (RapidsShuffleHeartbeatManager role)
# ---------------------------------------------------------------------------

@dataclass
class ExecutorInfo:
    executor_id: str
    endpoint: str
    last_heartbeat: float = field(default_factory=time.monotonic)


class ShuffleHeartbeatManager:
    """Driver-side registry of live shuffle peers. In the reference this
    bootstraps UCX endpoint exchange (Plugin.scala:292-303); here it
    carries host:port endpoints for the DCN block-fetch path and lets
    the planner exclude dead peers."""

    def __init__(self, timeout_s: Optional[float] = None):
        if timeout_s is None:
            # standalone default from conf; the cluster driver passes
            # its own srt.cluster.heartbeatTimeoutSec through instead.
            # srt.shuffle.heartbeat.timeoutSec is a deprecated alias
            # that forwards to the same key.
            from ..conf import HEARTBEAT_TIMEOUT_S, active_conf
            timeout_s = active_conf().get(HEARTBEAT_TIMEOUT_S)
        self.timeout_s = timeout_s
        self._executors: Dict[str, ExecutorInfo] = {}
        #: every endpoint an executor EVER served from -> executor_id;
        #: a peer holding a stale endpoint resolves the executor's
        #: current one through this (fetch failover)
        self._aliases: Dict[str, str] = {}
        self._lock = threading.Lock()

    def register(self, executor_id: str, endpoint: str,
                 prior_endpoint: Optional[str] = None
                 ) -> List[ExecutorInfo]:
        """Returns the current peer list (what a new executor needs to
        open connections). ``prior_endpoint`` declares this executor
        the REPLACEMENT of whichever executor last served that
        endpoint (worker rejoin): the predecessor is dropped and every
        alias it ever held re-points at the replacement, so
        ``resolve(old_endpoint)`` reroutes in-flight fetches to the
        new incarnation."""
        with self._lock:
            if prior_endpoint is not None:
                old_eid = self._aliases.get(prior_endpoint)
                if old_eid is not None and old_eid != executor_id:
                    self._executors.pop(old_eid, None)
                    for ep, eid in list(self._aliases.items()):
                        if eid == old_eid:
                            self._aliases[ep] = executor_id
            self._executors[executor_id] = ExecutorInfo(executor_id,
                                                        endpoint)
            self._aliases[endpoint] = executor_id
            return [e for e in self._executors.values()
                    if e.executor_id != executor_id]

    def deregister(self, executor_id: str) -> None:
        """Forget a gracefully-decommissioned executor. Its aliases are
        kept (resolving them returns None until a replacement
        re-registers over one of them)."""
        with self._lock:
            self._executors.pop(executor_id, None)

    def owner_of(self, endpoint: str) -> Optional[str]:
        """Executor id that ever served ``endpoint`` (live or not) —
        lets the driver fence a rejoining worker's predecessor."""
        with self._lock:
            return self._aliases.get(endpoint)

    def heartbeat(self, executor_id: str,
                  endpoint: Optional[str] = None) -> bool:
        with self._lock:
            info = self._executors.get(executor_id)
            if info is None:
                return False  # unknown: executor must re-register
            info.last_heartbeat = time.monotonic()
            if endpoint and endpoint != info.endpoint:
                # shuffle server moved (restart on a new port): keep the
                # old endpoint as an alias so in-flight fetches fail over
                info.endpoint = endpoint
                self._aliases[endpoint] = executor_id
            return True

    def resolve(self, endpoint: str) -> Optional[str]:
        """Current endpoint of the live executor that served
        ``endpoint`` at any point — None when that executor is unknown
        or has gone silent past the timeout."""
        now = time.monotonic()
        with self._lock:
            eid = self._aliases.get(endpoint)
            info = self._executors.get(eid) if eid else None
            if info is None or now - info.last_heartbeat > self.timeout_s:
                return None
            return info.endpoint

    def live_executors(self) -> List[str]:
        now = time.monotonic()
        with self._lock:
            return [e.executor_id for e in self._executors.values()
                    if now - e.last_heartbeat <= self.timeout_s]

    def is_alive(self, executor_id: str) -> bool:
        """Heartbeat-based liveness — the slow-vs-dead discriminator
        speculation needs: a straggler still heartbeats (speculate), a
        dead worker does not (evict + stage retry instead)."""
        with self._lock:
            info = self._executors.get(executor_id)
            if info is None:
                return False
            return time.monotonic() - info.last_heartbeat <= self.timeout_s

    def expire_dead(self) -> List[str]:
        now = time.monotonic()
        with self._lock:
            dead = [eid for eid, e in self._executors.items()
                    if now - e.last_heartbeat > self.timeout_s]
            for eid in dead:
                del self._executors[eid]
            return dead


# ---------------------------------------------------------------------------
# map-output registry (MapOutputTracker role, stage-level recovery)
# ---------------------------------------------------------------------------

class MapOutputRegistry:
    """Driver-side record of which shuffles' map phases COMPLETED in
    the current job attempt (Spark's MapOutputTracker role, reduced to
    what stage-level recovery needs). Shuffles are keyed by their
    traversal POSITION in the physical plan — shuffle ids are fresh per
    attempt, positions are stable across re-plans of the same job —
    and a position is complete once its barrier released (every
    worker's map side wrote before any barrier reply goes out)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._complete: Dict[int, int] = {}  # pos -> shuffle_id
        #: exact per-(map, reduce) sizes reported by workers at barrier
        #: time: (shuffle_id, worker) -> {(map_id, reduce_id):
        #: (rows, bytes)} — the registry half of MapOutputStatistics
        self._map_stats: Dict[Tuple[int, int],
                              Dict[Tuple[int, int],
                                   Tuple[int, int]]] = {}
        #: first-result-wins commits under speculation:
        #: shuffle_id -> {logical_shard: (worker, (map_ids...))}
        self._commits: Dict[int, Dict[int, Tuple[int,
                                                 Tuple[int, ...]]]] = {}

    def start_attempt(self) -> None:
        with self._lock:
            self._complete.clear()
            self._map_stats.clear()
            self._commits.clear()

    # --- map-output statistics (exact sizes, reported at barriers) ---
    def record_map_stats(self, shuffle_id: int, worker: int,
                         detail: Dict[Tuple[int, int],
                                      Tuple[int, int]]) -> None:
        with self._lock:
            self._map_stats[(shuffle_id, worker)] = dict(detail or {})

    def map_output_statistics(self, shuffle_id: int,
                              num_partitions: int) -> MapOutputStatistics:
        """Driver-side merged view across every reporting worker,
        restricted to COMMITTED maps when speculation produced
        duplicates (first result wins; losers never count)."""
        with self._lock:
            commits = self._commits.get(shuffle_id)
            won: Optional[set] = None
            if commits:
                won = {(worker, mid)
                       for worker, mids in commits.values()
                       for mid in mids}
            detail: Dict[Tuple[int, int], Tuple[int, int]] = {}
            for (sid, worker), d in self._map_stats.items():
                if sid != shuffle_id:
                    continue
                for (mid, rid), v in d.items():
                    if won is not None and (worker, mid) not in won:
                        continue
                    detail[(mid, rid)] = v
        rows_by = [0] * num_partitions
        bytes_by = [0] * num_partitions
        for (_mid, rid), (rows, nbytes) in detail.items():
            if rid < num_partitions:
                rows_by[rid] += rows
                bytes_by[rid] += nbytes
        return MapOutputStatistics(shuffle_id, num_partitions, rows_by,
                                   bytes_by, detail)

    # --- first-result-wins commits (speculative execution dedup) ---
    def try_commit_maps(self, shuffle_id: int, logical_shard: int,
                        worker: int,
                        map_ids: Sequence[int]) -> Tuple[int,
                                                         Tuple[int, ...]]:
        """Commit ``worker`` as the producer of ``logical_shard``'s map
        outputs unless another worker already committed — the
        first-result-wins rule. Returns the WINNING (worker, map_ids),
        which is the caller's when it won the race."""
        with self._lock:
            by_shard = self._commits.setdefault(shuffle_id, {})
            cur = by_shard.get(logical_shard)
            if cur is None:
                cur = by_shard[logical_shard] = (worker, tuple(map_ids))
            return cur

    def committed_maps(self, shuffle_id: int) -> Dict[int,
                                                      Tuple[int,
                                                            Tuple[int,
                                                                  ...]]]:
        with self._lock:
            return dict(self._commits.get(shuffle_id, {}))

    def mark_complete(self, pos: int, shuffle_id: int) -> None:
        if pos < 0:
            return
        with self._lock:
            fresh = self._complete.get(pos) != shuffle_id
            self._complete[pos] = shuffle_id
        if fresh:
            # once per barrier release, not once per worker reply
            from ..obs import events as _events
            _events.emit("StageCompleted", position=pos,
                         shuffle_id=shuffle_id)

    def complete_positions(self) -> List[int]:
        with self._lock:
            return sorted(self._complete)
