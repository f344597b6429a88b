"""Configuration system — the RapidsConf equivalent.

TPU-native analogue of the reference's config layer
(sql-plugin/.../RapidsConf.scala: ConfBuilder/TypedConfBuilder DSL at
lines 200-310, ~300 ``spark.rapids.*`` entries, doc generation via
``RapidsConf.main`` at :2214). Same shape here: a typed builder DSL that
registers every config with type, default, validation, and doc string
under the ``srt.`` prefix (``spark_rapids_tpu``), plus markdown doc-gen
so docs never drift from code.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional


class ConfEntry:
    """One registered configuration key."""

    def __init__(self, key: str, conv: Callable[[str], Any], default: Any,
                 doc: str, is_internal: bool, is_startup_only: bool,
                 commonly_used: bool,
                 checker: Optional[Callable[[Any], Optional[str]]] = None):
        self.key = key
        self.conv = conv
        self.default = default
        self.doc = doc
        self.is_internal = is_internal
        self.is_startup_only = is_startup_only
        self.commonly_used = commonly_used
        self.checker = checker

    def get(self, settings: Dict[str, str]) -> Any:
        raw = settings.get(self.key)
        if raw is None:
            raw = os.environ.get(self.key.replace(".", "_").upper())
        if raw is None:
            return self.default
        value = self.conv(raw) if isinstance(raw, str) else raw
        if self.checker is not None:
            err = self.checker(value)
            if err:
                raise ValueError(f"{self.key}={value!r}: {err}")
        return value


_REGISTRY: Dict[str, ConfEntry] = {}


class ConfBuilder:
    """Typed builder DSL (TypedConfBuilder in the reference)."""

    def __init__(self, key: str):
        assert key.startswith("srt."), key
        self.key = key
        self._doc = ""
        self._internal = False
        self._startup_only = False
        self._commonly_used = False
        self._checker: Optional[Callable[[Any], Optional[str]]] = None

    def doc(self, text: str) -> "ConfBuilder":
        self._doc = text
        return self

    def internal(self) -> "ConfBuilder":
        self._internal = True
        return self

    def startup_only(self) -> "ConfBuilder":
        self._startup_only = True
        return self

    def commonly_used(self) -> "ConfBuilder":
        self._commonly_used = True
        return self

    def check(self, fn: Callable[[Any], Optional[str]]) -> "ConfBuilder":
        self._checker = fn
        return self

    def check_values(self, allowed: List[Any]) -> "ConfBuilder":
        return self.check(
            lambda v: None if v in allowed else f"must be one of {allowed}")

    def _register(self, conv, default) -> ConfEntry:
        entry = ConfEntry(self.key, conv, default, self._doc, self._internal,
                          self._startup_only, self._commonly_used, self._checker)
        _REGISTRY[self.key] = entry
        return entry

    def boolean(self, default: bool) -> ConfEntry:
        return self._register(
            lambda s: s.strip().lower() in ("true", "1", "yes"), default)

    def integer(self, default: int) -> ConfEntry:
        return self._register(int, default)

    def double(self, default: float) -> ConfEntry:
        return self._register(float, default)

    def string(self, default: Optional[str]) -> ConfEntry:
        return self._register(str, default)

    def bytes_(self, default: int) -> ConfEntry:
        return self._register(parse_bytes, default)


def conf(key: str) -> ConfBuilder:
    return ConfBuilder(key)


def parse_bytes(s: str) -> int:
    """'512m', '2g', '1024' -> bytes."""
    s = s.strip().lower()
    units = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40, "b": 1}
    if s and s[-1] in units:
        return int(float(s[:-1]) * units[s[-1]])
    return int(s)


def _positive(v) -> Optional[str]:
    return None if v > 0 else "must be positive"


def _non_negative(v) -> Optional[str]:
    return None if v >= 0 else "must be non-negative"


def _fraction(v) -> Optional[str]:
    return None if 0.0 < v <= 1.0 else "must be in (0, 1]"


# ---------------------------------------------------------------------------
# Registered configs. Reference counterparts cited per entry.
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("srt.sql.enabled") \
    .doc("Enable TPU acceleration of SQL operators. When false every plan "
         "runs on the CPU oracle path. (spark.rapids.sql.enabled)") \
    .commonly_used().boolean(True)

EXPLAIN = conf("srt.sql.explain") \
    .doc("Explain mode: NONE, NOT_ON_TPU (log only operators that could not "
         "be placed on TPU and why), ALL. (spark.rapids.sql.explain, "
         "RapidsConf.scala:1807)") \
    .check_values(["NONE", "NOT_ON_TPU", "ALL"]).string("NONE")

BATCH_SIZE_ROWS = conf("srt.sql.batchSizeRows") \
    .doc("Target rows per columnar batch; capacities are bucketed to powers "
         "of two at or below this. (spark.rapids.sql.batchSizeBytes, "
         "RapidsConf.scala:550 — rows not bytes because XLA buffers are "
         "statically shaped per column)") \
    .check(_positive).commonly_used().integer(1 << 20)

BATCH_SIZE_BYTES = conf("srt.sql.batchSizeBytes") \
    .doc("Soft cap on bytes per batch used by the coalesce planner. "
         "(spark.rapids.sql.batchSizeBytes)") \
    .check(_positive).bytes_(1 << 30)

CACHE_HOST_LIMIT_BYTES = conf("srt.cache.hostLimitBytes") \
    .doc("Host-memory budget for df.cache() compressed blocks; overflow "
         "tiers to an append-only disk file read back per block. "
         "(ParquetCachedBatchSerializer host blob management)") \
    .check(_positive).bytes_(256 << 20)

CONCURRENT_TASKS = conf("srt.sql.concurrentTpuTasks") \
    .doc("Number of host threads allowed to submit device work "
         "concurrently. (spark.rapids.sql.concurrentGpuTasks, "
         "RapidsConf.scala:535)") \
    .check(_positive).commonly_used().integer(2)

DEVICE_MEMORY_LIMIT = conf("srt.memory.tpu.poolSize") \
    .doc("HBM budget in bytes for columnar batches; 0 means derive from the "
         "device. Exceeding it triggers spill-and-retry. "
         "(spark.rapids.memory.gpu.allocFraction / pool init, "
         "GpuDeviceManager.scala:275)") \
    .startup_only().bytes_(0)

DEVICE_MEMORY_FRACTION = conf("srt.memory.tpu.allocFraction") \
    .doc("Fraction of device HBM usable for batches when poolSize=0. "
         "(spark.rapids.memory.gpu.allocFraction)") \
    .check(_fraction).double(0.75)

HOST_SPILL_LIMIT = conf("srt.memory.host.spillStorageSize") \
    .doc("Host memory for spilled buffers before overflowing to disk. "
         "(spark.rapids.memory.host.spillStorageSize)") \
    .bytes_(4 << 30)

SPILL_DIR = conf("srt.memory.spill.dir") \
    .doc("Directory for disk-tier spill files. (Spark local dirs in the "
         "reference, RapidsDiskStore.scala)") \
    .string("/tmp/srt_spill")

RETRY_MAX_SPLITS = conf("srt.memory.retry.maxSplits") \
    .doc("Max recursive halvings of an input batch under "
         "split-and-retry before giving up. (RmmRapidsRetryIterator "
         "semantics)") \
    .check(_positive).integer(8)

OOM_INJECTION_MODE = conf("srt.test.oomInjection.mode") \
    .doc("Test-only: inject synthetic OOM on the Nth allocation "
         "(RmmSpark.forceRetryOOM analogue). NONE|RETRY|SPLIT") \
    .internal().check_values(["NONE", "RETRY", "SPLIT"]).string("NONE")

READER_TYPE = conf("srt.sql.format.parquet.reader.type") \
    .doc("Parquet reader strategy: PERFILE, COALESCING, or MULTITHREADED "
         "(cloud). (spark.rapids.sql.format.parquet.reader.type, "
         "GpuParquetScan.scala:1862,2057)") \
    .check_values(["PERFILE", "COALESCING", "MULTITHREADED"]) \
    .string("COALESCING")

READER_THREADS = conf("srt.sql.multiThreadedRead.numThreads") \
    .doc("Most host threads a scan of several files decodes on, ahead of "
         "the thread that assembles and uploads its batches (fewer where "
         "the host has fewer cores or the scan fewer files). Decoding "
         "local files is bound by the host's memory bandwidth: four keep "
         "ahead of the scan's own thread, eight slow its copies down; "
         "raise it where the pool hides storage latency. "
         "(spark.rapids.sql.multiThreadedRead.numThreads)") \
    .check(_positive).integer(4)

MAX_READER_BATCH_SIZE_ROWS = conf("srt.sql.reader.batchSizeRows") \
    .doc("Soft cap on rows per scan batch. "
         "(spark.rapids.sql.reader.batchSizeRows)") \
    .check(_positive).integer(1 << 20)

SHUFFLE_MODE = conf("srt.shuffle.mode") \
    .doc("Shuffle transport: MESH (XLA all-to-all over ICI/DCN), "
         "MULTITHREADED (host partition exchange), CACHE_ONLY (single "
         "process). (spark.rapids.shuffle.mode, RapidsConf.scala:1495)") \
    .check_values(["MESH", "MULTITHREADED", "CACHE_ONLY"]).string("CACHE_ONLY")

SHUFFLE_PARTITIONS = conf("srt.shuffle.partitions") \
    .doc("Default shuffle partition count (spark.sql.shuffle.partitions)") \
    .check(_positive).integer(8)

EXCHANGE_ENABLED = conf("srt.shuffle.exchange.enabled") \
    .doc("Plan shuffle/broadcast exchanges between pipeline stages "
         "(EnsureRequirements): hash exchange before aggregate merge and "
         "shuffled joins, range exchange before global sort, broadcast "
         "exchange for small build sides. When false the staged "
         "operators run single-stream. "
         "(GpuShuffleExchangeExecBase.scala:167)") \
    .commonly_used().boolean(True)

BROADCAST_THRESHOLD_ROWS = conf("srt.sql.broadcastRowThreshold") \
    .doc("Estimated build-side row count at or below which a join uses a "
         "broadcast exchange instead of shuffling both sides. "
         "(spark.sql.autoBroadcastJoinThreshold, bytes there — rows here "
         "because batch capacities are row-bucketed)") \
    .check(_positive).integer(100_000)

JOIN_SUB_PARTITION_ROWS = conf("srt.sql.join.subPartitionRows") \
    .doc("Join build sides above this many rows are hash-split into "
         "sub-partitions and joined pair-wise so the build working set "
         "stays bounded instead of requiring the whole side in one "
         "device batch. (spark.rapids.sql.test.subPartitioning / "
         "GpuSubPartitionHashJoin.scala)") \
    .check(_positive).integer(1 << 22)

AGG_MERGE_PARTITION_ROWS = conf("srt.sql.agg.mergePartitionRows") \
    .doc("Aggregate merge passes whose concatenated partial rows exceed "
         "this are hash-re-partitioned by group key and merged bucket "
         "by bucket (the reference's re-partition merge fallback, "
         "GpuAggregateExec.scala:711,792).") \
    .check(_positive).integer(1 << 22)

SORT_OOC_ROWS = conf("srt.sql.sort.oocRowBudget") \
    .doc("Sort partitions whose total rows exceed this merge their "
         "spilled sorted runs with a bounded-memory k-way chunk merge "
         "instead of one full-size concat+sort — device residency "
         "stays O(budget) regardless of partition size (the "
         "out-of-core iterator of GpuSortExec.scala:242).") \
    .check(_positive).integer(1 << 22)

_SHUFFLE_CODECS = ("NONE", "LZ4", "ZSTD")

SHUFFLE_COMPRESS = conf("srt.shuffle.compression.codec") \
    .doc("Codec for serialized shuffle buffers: NONE, LZ4 (native "
         "codec), or ZSTD. "
         "(spark.rapids.shuffle.compression.codec, nvcomp LZ4 in the "
         "reference)") \
    .check(lambda v: None if str(v).upper() in _SHUFFLE_CODECS
           else f"unknown codec {v!r}; allowed (case-insensitive): "
                f"{list(_SHUFFLE_CODECS)}").string("NONE")

SHUFFLE_PUSH_ENABLED = conf("srt.shuffle.push.enabled") \
    .doc("Push-based shuffle: map tasks eagerly push their compressed "
         "blocks to the owning reducer's endpoint at map completion, "
         "and the receiving side consolidates them into per-reducer "
         "segments so a reduce read is one sequential scan plus a "
         "pull of whatever was never pushed (the pull path is the "
         "always-correct fallback). (Spark's push-based shuffle / "
         "magnet role)") \
    .commonly_used().boolean(True)

SHUFFLE_PUSH_IN_FLIGHT_BYTES = conf("srt.shuffle.push.maxInFlightBytes") \
    .doc("Per-endpoint cap on un-acknowledged pushed bytes; map tasks "
         "block pushing to a slow reducer past this window so push "
         "memory stays bounded regardless of fan-out "
         "(BounceBufferManager role on the push side).") \
    .check(_positive).bytes_(32 << 20)

SHUFFLE_PUSH_LOCAL_BYPASS = conf("srt.shuffle.push.localBypass") \
    .doc("Locality bypass: when producer and consumer share a process "
         "(driver-local session; mesh-co-located partitions in MESH "
         "mode) the live ColumnarBatch is handed through a zero-copy "
         "local channel, skipping serializer+socket+deserializer. "
         "Bypassed bytes are reported as shuffleBytesBypassed.") \
    .boolean(True)

ADAPTIVE_ENABLED = conf("srt.sql.adaptive.enabled") \
    .doc("Adaptive query execution: re-plan stages on runtime shuffle "
         "statistics — coalesce small reduce partitions and switch "
         "shuffled joins to broadcast when the materialized build side "
         "is small. (spark.sql.adaptive.enabled; "
         "GpuQueryStagePrepOverrides / GpuCustomShuffleReaderExec)") \
    .commonly_used().boolean(True)

ADAPTIVE_MIN_PARTITION_ROWS = conf(
    "srt.sql.adaptive.coalescePartitions.minPartitionRows") \
    .doc("AQE merges adjacent reduce partitions until each group holds "
         "at least this many rows "
         "(spark.sql.adaptive.coalescePartitions.minPartitionSize, rows "
         "here because batch capacities are row-bucketed).") \
    .check(_positive).integer(1 << 16)

ADAPTIVE_BROADCAST_ROWS = conf("srt.sql.adaptive.autoBroadcastJoinRows") \
    .doc("A shuffled join whose materialized build side has at most "
         "this many rows switches to broadcast at runtime, skipping "
         "the probe-side shuffle (spark.sql.adaptive."
         "autoBroadcastJoinThreshold). 0 falls back to "
         "srt.sql.broadcastRowThreshold.") \
    .integer(0)

ADAPTIVE_SKEW_ROWS = conf("srt.sql.adaptive.skewJoin.partitionRows") \
    .doc("A reduce partition whose PROBE side exceeds this many rows "
         "in a shuffled join splits into map-slices joined separately "
         "against the full build partition (spark.sql.adaptive."
         "skewJoin.skewedPartitionThreshold; the "
         "GpuCustomShuffleReaderExec skewed-partition-spec role).") \
    .check(_positive).integer(1 << 20)

ADAPTIVE_COALESCE_ENABLED = conf(
    "srt.sql.adaptive.coalescePartitions.enabled") \
    .doc("AQE rule 1: merge adjacent small reduce partitions after the "
         "map side materializes, using measured per-partition rows and "
         "bytes (spark.sql.adaptive.coalescePartitions.enabled).") \
    .boolean(True)

ADAPTIVE_TARGET_BYTES = conf(
    "srt.sql.adaptive.coalescePartitions.targetBytes") \
    .doc("Coalesced partition groups close once they reach this many "
         "measured shuffle bytes, even below minPartitionRows — the "
         "byte-size generalization of the row floor "
         "(spark.sql.adaptive.advisoryPartitionSizeInBytes). 0 keeps "
         "the rows-only behavior.") \
    .check(_non_negative).bytes_(8 << 20)

ADAPTIVE_SKEW_ENABLED = conf("srt.sql.adaptive.skewJoin.enabled") \
    .doc("AQE rule 2: split skewed reduce partitions of a shuffled "
         "join into map-slices replicated against the other side "
         "(spark.sql.adaptive.skewJoin.enabled).") \
    .boolean(True)

ADAPTIVE_SKEW_BYTES = conf("srt.sql.adaptive.skewJoin.partitionBytes") \
    .doc("A reduce partition whose PROBE side exceeds this many "
         "measured shuffle bytes is skew-split, independent of the row "
         "threshold (spark.sql.adaptive.skewJoin."
         "skewedPartitionThresholdInBytes). 0 disables the byte "
         "trigger.") \
    .check(_non_negative).bytes_(64 << 20)

ADAPTIVE_JOIN_ENABLED = conf("srt.sql.adaptive.join.enabled") \
    .doc("AQE rule 3: demote a shuffled join to broadcast (or cap an "
         "oversized broadcast build via sub-partitioning) when the "
         "MEASURED build side contradicts the plan-time estimate "
         "(DynamicJoinSelection / spark.sql.adaptive."
         "autoBroadcastJoinThreshold direction flips).") \
    .boolean(True)

ADAPTIVE_BROADCAST_BYTES = conf("srt.sql.adaptive.autoBroadcastJoinBytes") \
    .doc("A shuffled join whose materialized build side has at most "
         "this many measured shuffle bytes switches to broadcast at "
         "runtime, in addition to the autoBroadcastJoinRows row "
         "trigger. 0 disables the byte trigger.") \
    .check(_non_negative).bytes_(0)

ADAPTIVE_MAX_BROADCAST_BYTES = conf(
    "srt.sql.adaptive.maxBroadcastBuildBytes") \
    .doc("A plan-time broadcast join whose MATERIALIZED build side "
         "exceeds this many bytes is forced onto the bounded "
         "sub-partition join path (the broadcast->shuffle 'promote' "
         "mitigation: the exchange topology is fixed per attempt, so "
         "the memory-safety half of promotion is what AQE can still "
         "deliver mid-flight). 0 disables.") \
    .check(_non_negative).bytes_(0)

ADAPTIVE_SPECULATION_ENABLED = conf("srt.sql.adaptive.speculation.enabled") \
    .doc("AQE rule 4: when a heartbeat-alive worker lags the map side "
         "of a shuffle stage, the driver re-executes its map shards on "
         "an idle worker; first result wins in the map-output registry "
         "and losing blocks are never fetched "
         "(spark.speculation; default off, matching Spark).") \
    .boolean(False)

ADAPTIVE_SPECULATION_FACTOR = conf(
    "srt.sql.adaptive.speculation.slowWorkerFactor") \
    .doc("A worker is a straggler once its barrier arrival lags the "
         "median arrived worker by this multiple "
         "(spark.speculation.multiplier).") \
    .check(_positive).double(3.0)

ADAPTIVE_SPECULATION_MIN_WAIT_S = conf(
    "srt.sql.adaptive.speculation.minWaitSec") \
    .doc("Never speculate before the first arrival has waited this "
         "many seconds — bounds wasted duplicate work on naturally "
         "short stages.") \
    .check(_non_negative).double(1.0)

LEGACY_ADAPTIVE_BROADCAST_ROWS = conf("srt.sql.adaptiveBroadcastRows") \
    .doc("DEPRECATED alias for srt.sql.adaptive.autoBroadcastJoinRows "
         "(pre-AQE-subsystem name). Setting it forwards to the new key "
         "and warns once per process.") \
    .integer(0)

SESSION_TIMEZONE = conf("srt.sql.session.timeZone") \
    .doc("Session timezone id used by timezone-aware SQL functions "
         "(spark.sql.session.timeZone). Conversions run on device "
         "against materialized transition tables (GpuTimeZoneDB "
         "analogue, expr/timezone.py).") \
    .string("UTC")

PARQUET_REBASE_READ = conf("srt.sql.parquet.datetimeRebaseModeInRead") \
    .doc("How to treat pre-1582-10-15 dates/timestamps in parquet "
         "reads: CORRECTED (as written, proleptic Gregorian), LEGACY "
         "(rebase from the hybrid Julian calendar), EXCEPTION (fail). "
         "(spark.sql.parquet.datetimeRebaseModeInRead, "
         "datetimeRebaseUtils.scala)") \
    .check_values(["CORRECTED", "LEGACY", "EXCEPTION"]) \
    .string("CORRECTED")

PARQUET_REBASE_WRITE = conf("srt.sql.parquet.datetimeRebaseModeInWrite") \
    .doc("Calendar for pre-1582-10-15 dates/timestamps in parquet "
         "writes: CORRECTED, LEGACY (rebase to hybrid Julian), or "
         "EXCEPTION. (spark.sql.parquet.datetimeRebaseModeInWrite)") \
    .check_values(["CORRECTED", "LEGACY", "EXCEPTION"]) \
    .string("CORRECTED")

METRICS_LEVEL = conf("srt.metrics.level") \
    .doc("Operator metric detail kept in per-query summaries and the "
         "metrics registry: ESSENTIAL, MODERATE, DEBUG. "
         "(spark.rapids.sql.metrics.level, GpuExec.scala:36-49)") \
    .check_values(["ESSENTIAL", "MODERATE", "DEBUG"]).string("MODERATE")

EVENT_LOG_ENABLED = conf("srt.eventLog.enabled") \
    .doc("Write a structured JSONL event log (QueryStart/End, "
         "StageSubmitted/Completed, TaskEnd, SpillToHost/Disk, "
         "FetchFailed, RetryAttempt, FaultInjected, "
         "CorruptionDetected...) to srt.eventLog.dir — one "
         "events-<pid>.jsonl per process, Spark history-server role. "
         "Off by default: when disabled no event sink is instantiated "
         "and every emit site is a single None check "
         "(obs/events.py).") \
    .boolean(False)

EVENT_LOG_DIR = conf("srt.eventLog.dir") \
    .doc("Directory for event-log files (and per-query Chrome traces "
         "when srt.eventLog.trace.enabled). Created on first emit; "
         "defaults to ./srt-events when enabled without a dir. Feed "
         "it to tools/profile_report.py for an offline per-query "
         "report (spark.eventLog.dir role).") \
    .string("")

TRACE_ENABLED = conf("srt.eventLog.trace.enabled") \
    .doc("Record per-query spans (query -> stage -> task -> operator) "
         "and write a Chrome-trace (catapult) JSON file "
         "trace-<query_id>.json next to the event log. Requires "
         "srt.eventLog.enabled for the file to land; spans add one "
         "object per operator pull, so leave off for benchmarking "
         "(NvtxWithMetrics.scala role). On a cluster the driver ships "
         "its trace context with each job so worker spans parent "
         "under the driver's; tools/history_report.py clock-aligns "
         "and merges the per-process trace-*.json files.") \
    .boolean(False)

EVENT_LOG_MAX_BYTES = conf("srt.eventLog.maxBytes") \
    .doc("Rotate events-<pid>.jsonl when it exceeds this many bytes: "
         "the live file rolls to .1 (and .1 to .2, which is dropped "
         "on the next roll), bounding a long-running process to about "
         "three segments of this size. 0 disables rotation. Readers "
         "(tools/profile_report.py, tools/history_report.py) stitch "
         "rolled segments back in order (spark.eventLog.rolling role).") \
    .check(_non_negative).bytes_(0)

RESOURCE_SAMPLE_INTERVAL_MS = conf("srt.obs.resource.intervalMs") \
    .doc("Period of the background resource sampler, which records "
         "ResourceSample events (RSS, device memory in use, spill-pool "
         "occupancy, fetch-pool queue depth, prefetch buffer bytes) to "
         "the event log so stalls can be correlated with memory "
         "pressure. Requires srt.eventLog.enabled. 0 (default) "
         "disables sampling: no thread is started and the hot path "
         "stays a module-global None check.") \
    .check(_non_negative).integer(0)

CPU_ORACLE_STRICT = conf("srt.test.cpuOracle.strict") \
    .doc("Test-only: fail instead of falling back when an operator cannot "
         "run on TPU (assert_tpu_fallback analogue).") \
    .internal().boolean(False)

ALLOW_INCOMPAT = conf("srt.sql.incompatibleOps.enabled") \
    .doc("Enable operators whose semantics differ from Spark in corner "
         "cases. (spark.rapids.sql.incompatibleOps.enabled)") \
    .boolean(True)

ANSI_ENABLED = conf("srt.sql.ansi.enabled") \
    .doc("ANSI mode: arithmetic overflow and invalid casts raise instead "
         "of returning null/wrapping (spark.sql.ansi.enabled semantics; "
         "GpuCast.scala AnsiCast paths).") \
    .boolean(False)

IGNORE_CORRUPT_FILES = conf("srt.sql.ignoreCorruptFiles") \
    .doc("Skip-and-warn instead of failing when a file is corrupt "
         "(unreadable, truncated, bad checksum) during a scan — "
         "Spark's spark.sql.files.ignoreCorruptFiles semantics: rows "
         "already decoded from the broken file are kept, the rest of "
         "the file is dropped with a warning. Default FAILFAST "
         "(raise).") \
    .boolean(False)

IGNORE_MISSING_FILES = conf("srt.sql.ignoreMissingFiles") \
    .doc("Skip-and-warn instead of failing when a scan file has been "
         "deleted between planning and execution — Spark's "
         "spark.sql.files.ignoreMissingFiles semantics.") \
    .boolean(False)

DELTA_DURABLE_COMMITS = conf("srt.delta.durableCommits") \
    .doc("Crash-durable Delta commits: every transaction-log commit "
         "fsyncs the commit file and its parent directory (and every "
         "staged data file before its rename promotes it), so a "
         "machine crash immediately after commit() returns can never "
         "lose or tear the version. Disable only to A/B the fsync "
         "overhead (the ingest_rows_per_s bench lane measures it).") \
    .boolean(True)

DELTA_COMMIT_MAX_RETRIES = conf("srt.delta.commit.maxRetries") \
    .doc("How many times an optimistic Delta committer re-validates "
         "and retries after losing the O_EXCL race for its target "
         "version before surfacing CommitConflict.") \
    .check(lambda v: None if v >= 0 else "must be >= 0").integer(10)

DELTA_COMMIT_BACKOFF_MS = conf("srt.delta.commit.backoffMs") \
    .doc("Base backoff in milliseconds between Delta commit-conflict "
         "retries; grows exponentially per attempt with +-50% jitter, "
         "capped at 32x the base. 0 retries immediately.") \
    .check(lambda v: None if v >= 0 else "must be >= 0").integer(15)

DELTA_CHECKPOINT_INTERVAL = conf("srt.delta.checkpointInterval") \
    .doc("Write a compacted log checkpoint (NNN.checkpoint.json + "
         "_last_checkpoint pointer) every this many commits, bounding "
         "snapshot replay to the commits after the checkpoint. The "
         "checkpoint carries a crc32 — a torn/corrupt checkpoint is "
         "detected and replay falls back to the full JSON log. "
         "0 disables checkpointing.") \
    .check(lambda v: None if v >= 0 else "must be >= 0").integer(10)

DELTA_VACUUM_RETENTION_SEC = conf("srt.delta.vacuum.retentionSec") \
    .doc("VACUUM's retention guard for files the log has never "
         "referenced (crash orphans: staged .tmp files and promoted-"
         "but-uncommitted data files): younger files survive the "
         "sweep because they may belong to a commit in flight. "
         "Staging files whose owning pid is provably dead are swept "
         "regardless of age. Files tombstoned by a committed remove "
         "action are always reclaimable.") \
    .check(lambda v: None if v >= 0 else "must be >= 0").double(600.0)

INTEGRITY_CHECKSUM = conf("srt.integrity.checksum.enabled") \
    .doc("Verify crc32c-style checksums on every off-device byte path "
         "(shuffle blocks at serve/fetch/local read, host+disk spill "
         "entries at re-materialization, file-cache entries on hit). "
         "Corruption converts to a retryable fetch failure on the "
         "transport and raises DataCorruption from storage tiers — "
         "no silent wrong answers. Disable only to A/B the (noise-"
         "level) checksum overhead.") \
    .boolean(True)

MESH_DATA_AXIS = conf("srt.mesh.dataAxis") \
    .doc("Name of the mesh axis partitions are sharded over.") \
    .internal().string("data")

MESH_STAGE_PROGRAMS = conf("srt.mesh.stagePrograms.enabled") \
    .doc("Compile one SPMD program per query stage (everything between "
         "shuffle boundaries, as cut by plan/adaptive.py) instead of "
         "one monolithic program for the whole plan. Stage outputs "
         "stay device-resident between programs, exchange collectives "
         "run at the consumer stage's head (or vanish entirely under "
         "the residency rule), and a join-overflow retry re-runs ONLY "
         "the overflowing stage at doubled growth from its retained "
         "inputs — the whole-plan retry ladder that re-executed every "
         "leaf (and aborted q19 at scale) is gone. Off = legacy "
         "whole-plan lowering, kept as the fallback boundary.") \
    .boolean(True)

MESH_RESIDENCY = conf("srt.mesh.residency.enabled") \
    .doc("Planner residency rule for mesh exchanges: an exchange whose "
         "child already satisfies the target placement (hash on the "
         "same key exprs, range on the same orders, single partition "
         "over single partition) lowers to a device-resident identity "
         "hand-through pinned by with_sharding_constraint instead of "
         "an in-program all_to_all — the generalized "
         "MeshColocationBypass. Also respects "
         "srt.shuffle.push.localBypass (the single-box face of the "
         "same locality contract).") \
    .boolean(True)

MESH_DONATION = conf("srt.mesh.donation.enabled") \
    .doc("Donate consumed stage inputs to the stage program "
         "(jit donate_argnums) so XLA reuses their buffers in place. "
         "Only applied when the stage cannot retry (no join-overflow "
         "check) and the input has exactly one consumer.") \
    .boolean(True)

MESH_BROADCAST_REPLICATED = conf("srt.mesh.broadcastReplicated") \
    .doc("Place shuffle-free broadcast build subtrees host-executed "
         "and replicated (PartitionSpec()) on every device instead of "
         "lowering them per-shard and all_gathering inside the "
         "program — the partition-rule table's "
         "BroadcastExchangeExec -> replicated row.") \
    .boolean(True)

MESH_PARTITION_RULES = conf("srt.mesh.partitionRules") \
    .doc("Extra partition rules prepended to the built-in table: "
         "';'-separated 'regex=data|replicated' clauses matched "
         "against each stage input's rule path (class names joined "
         "with '/', stage root first). First match wins; the built-in "
         "table replicates broadcast subtrees and shards everything "
         "else over the data axis.") \
    .string("")

MESH_MAX_JOIN_GROWTH = conf("srt.mesh.maxJoinGrowth") \
    .doc("Upper bound on the per-stage join output growth factor the "
         "overflow retry may reach before the query fails (each retry "
         "doubles the factor for the overflowing stage only).") \
    .check(lambda v: None if v >= 1 else "must be >= 1").integer(64)

URI_REWRITE_RULES = conf("srt.io.uriRewrite") \
    .doc("Ordered 'FROM->TO;FROM2->TO2' prefix rewrite rules applied to "
         "scan paths before file resolution — mount-style remote-store "
         "acceleration (spark.rapids.alluxio.pathsToReplace role).") \
    .string("")

FILECACHE_ENABLED = conf("srt.filecache.enabled") \
    .doc("Cache scanned input files on local disk with LRU eviction "
         "(spark.rapids.filecache.enabled role).") \
    .boolean(False)

FILECACHE_DIR = conf("srt.filecache.dir") \
    .doc("Directory for the scan file cache.") \
    .string("/tmp/srt_filecache")

FILECACHE_MAX_SIZE = conf("srt.filecache.maxSize") \
    .doc("File-cache capacity in bytes; least-recently-used files are "
         "evicted past this size.") \
    .bytes_(1 << 30)

FILECACHE_LOCAL_FS = conf("srt.filecache.useForLocalFiles") \
    .doc("Also cache local-filesystem files (the reference caches only "
         "remote filesystems by default; this knob exists for tests and "
         "for slow network mounts that look local).") \
    .boolean(False)

DEBUG_DUMP_PATH = conf("srt.debug.dumpPath") \
    .doc("When set, each operator keeps its most recent output batch "
         "and an execution failure dumps them all (plus the plan tree "
         "and error) under this directory as parquet for offline "
         "replay (DumpUtils.scala crash-dump role). Debug tool: holds "
         "one extra batch per operator alive.") \
    .string("")

EXTRA_PLUGINS = conf("srt.plugins") \
    .doc("Comma-separated 'pkg.module:attr' entries loaded at "
         "initialize: each attr is called with the active conf "
         "(spark.rapids.sql.plugins / RapidsPluginUtils "
         "loadExtraPlugins role).") \
    .string("")

LEAK_DETECTION = conf("srt.memory.leakDetection.enabled") \
    .doc("Track the creation stack of every SpillableBatch and report "
         "entries still registered at shutdown/reset "
         "(MemoryCleaner/RapidsBufferCatalog leak-detection role). "
         "Adds per-allocation traceback capture cost; test/debug "
         "tool.") \
    .boolean(False)

WINDOW_BATCHED_RUNNING = conf("srt.sql.window.batchedRunning.enabled") \
    .doc("Stream running-frame window functions (rank family, ROWS "
         "unbounded-preceding..current-row aggregates) batch-at-a-time "
         "over a sorted child with carried state instead of "
         "materializing whole partitions "
         "(GpuRunningWindowExec/BatchedRunningWindowFixer role).") \
    .boolean(True)

JOIN_BLOOM_BITS_PER_KEY = conf("srt.sql.join.bloomFilter.bitsPerKey") \
    .doc("Bloom filter sizing: bits per build-side key (rounded up to a "
         "power of two, clamped to [2^10, 2^24] bits).") \
    .check(_positive).integer(10)

JOIN_GROWTH_STEPS = conf("srt.sql.join.outputGrowthSteps") \
    .doc("Max output-capacity doublings for a join whose true match "
         "count overflows the estimate before the probe batch splits "
         "(SplitAndRetryOOM contract).") \
    .check(_positive).integer(4)

RANGE_SAMPLE_SIZE = conf("srt.shuffle.sample.sizePerPartition") \
    .doc("Range-partitioner sketch size: sample rows per output "
         "partition used to derive bounds "
         "(spark.sql.execution.rangeExchange.sampleSizePerPartition).") \
    .check(_positive).integer(40)

CLUSTER_BARRIER_TIMEOUT = conf("srt.cluster.barrierTimeoutSec") \
    .doc("Seconds a cluster worker waits on a driver shuffle barrier / "
         "gather before treating the attempt as failed.") \
    .check(_positive).integer(120)

PALLAS_TILE_ROWS = conf("srt.sql.pallas.tileRows") \
    .doc("Row-tile size for fused pallas reductions (one HBM->VMEM DMA "
         "per tile; must be a multiple of 4096 — a tile is laid out "
         "(rows/128, 128) and 8-bit masks pack 32 sublanes to a "
         "register).") \
    .check(lambda v: None if v % 4096 == 0 and v > 0
           else "must be a positive multiple of 4096") \
    .integer(8192)

JOIN_BLOOM_ENABLED = conf("srt.sql.join.bloomFilter.enabled") \
    .doc("Build a bloom filter over the materialized build side of "
         "inner/semi hash joins and pre-filter probe batches with it "
         "(GpuBloomFilterAggregate/MightContain runtime-filter role). "
         "Pays one hash pass per side; wins when most probe rows have "
         "no match.") \
    .boolean(True)

JOIN_BLOOM_MIN_PROBE_ROWS = conf("srt.sql.join.bloomFilter.minProbeRows") \
    .doc("Skip the bloom pre-filter when a probe batch is smaller than "
         "this (filter overhead would exceed the join saving).") \
    .check(_positive).integer(4096)

PYTHON_WORKERS_MAX = conf("srt.python.workers.max") \
    .doc("Maximum pooled Python worker processes for vectorized pandas "
         "UDFs (ArrowEvalPython). Workers are reused across batches and "
         "queries. (python/rapids/daemon.py worker pool role)") \
    .check(_positive).integer(4)

PARQUET_NATIVE_DECODE = conf("srt.sql.format.parquet.nativeDecode.enabled") \
    .doc("Decode eligible parquet column chunks (fixed-width types, "
         "Snappy/uncompressed, PLAIN/RLE_DICTIONARY, v1 pages) in the "
         "native C++ runtime without the GIL; ineligible columns and "
         "files fall back to pyarrow per column/file. "
         "(GpuParquetScan.scala:2624 device-decode role, host-native "
         "stage.)") \
    .boolean(True)

ORC_NATIVE_DECODE = conf("srt.sql.format.orc.nativeDecode.enabled") \
    .doc("Decode eligible ORC files (flat numeric schemas, "
         "DIRECT_V2/RLEv2 with PRESENT streams, "
         "NONE/ZLIB/SNAPPY/ZSTD) in the native C++ runtime; anything "
         "outside the envelope falls back to pyarrow per file. "
         "(GpuOrcScan.scala device-decode role, host-native stage.)") \
    .boolean(True)

SHUFFLE_FETCH_MAX_CONCURRENT = conf("srt.shuffle.fetch.maxConcurrent") \
    .doc("Peers fetched in parallel per reduce partition over the TCP "
         "shuffle transport (RapidsShuffleClient maxInFlight role).") \
    .check(_positive).integer(4)

SHUFFLE_FETCH_IN_FLIGHT_BYTES = conf("srt.shuffle.fetch.inFlightBytes") \
    .doc("Byte budget for fetched-but-not-yet-consumed shuffle blocks "
         "per reduce partition (BounceBufferManager window role): "
         "producers stall when the window is full, bounding reduce "
         "fan-in host memory.") \
    .check(_positive).integer(128 * 1024 * 1024)

SHUFFLE_FETCH_POOL_SIZE = conf("srt.shuffle.fetch.poolSize") \
    .doc("Worker threads in the process-wide shuffle fetch pool shared "
         "by every reduce partition (replaces per-endpoint one-shot "
         "thread churn; RapidsShuffleClient exec pool role). Per-reduce "
         "concurrency is still capped by "
         "srt.shuffle.fetch.maxConcurrent.") \
    .check(_positive).integer(8)

PIPELINE_ENABLED = conf("srt.exec.pipeline.enabled") \
    .doc("Run blocking plan edges (scan decode, shuffle fetch/"
         "deserialize, broadcast materialization) on background "
         "producer threads behind a bounded prefetch queue so host I/O "
         "overlaps device compute (exec/pipeline.py; multithreaded "
         "reader + RapidsShuffleIterator fetch-ahead role). Queued "
         "batches register as on-deck spillable; producer-side "
         "failures re-raise on the consuming thread at the same plan "
         "node as synchronous mode.") \
    .commonly_used().boolean(True)

PIPELINE_DEPTH = conf("srt.exec.pipeline.depth") \
    .doc("Max batches queued per pipelined edge. 2 double-buffers: the "
         "producer stages batch N+1 while the consumer computes on "
         "batch N; higher values smooth bursty sources at the cost of "
         "more on-deck memory (bounded by "
         "srt.exec.pipeline.maxBytesInFlight).") \
    .check(_positive).integer(2)

PIPELINE_MAX_BYTES = conf("srt.exec.pipeline.maxBytesInFlight") \
    .doc("Byte budget for batches queued per pipelined edge; the "
         "producer stalls while the queue holds this much. A single "
         "batch over the budget is admitted alone into an empty queue "
         "(progress guarantee). Accepts k/m/g suffixes.") \
    .check(_positive).bytes_(256 * 1024 * 1024)

FETCH_MAX_RETRIES = conf("srt.shuffle.fetch.maxRetries") \
    .doc("Reconnect attempts per peer when a shuffle block fetch fails "
         "mid-stream (connection refused/reset, timeout). Already-"
         "received blocks are skipped on the retried stream, so a "
         "retry never duplicates a block "
         "(RapidsShuffleClient retry discipline).") \
    .check(lambda v: None if v >= 0 else "must be >= 0").integer(3)

FETCH_BACKOFF_BASE_S = conf("srt.shuffle.fetch.backoffBaseSec") \
    .doc("Base delay for exponential backoff between shuffle fetch "
         "retries; attempt n sleeps base * 2^(n-1) * (1 + jitter), "
         "jitter in [0, 0.25).") \
    .check(_positive).double(0.05)

FETCH_TIMEOUT_S = conf("srt.shuffle.fetch.timeoutSec") \
    .doc("Per-ATTEMPT socket timeout for shuffle block fetches (connect "
         "and each read); a stalled peer costs one attempt, not the "
         "whole fetch.") \
    .check(_positive).double(30.0)

HEARTBEAT_INTERVAL_S = conf("srt.cluster.heartbeatIntervalSec") \
    .doc("Seconds between a cluster worker's liveness heartbeats to the "
         "driver's ShuffleHeartbeatManager "
         "(RapidsShuffleHeartbeatManager executorHeartbeatInterval).") \
    .check(_positive).double(2.0)

HEARTBEAT_TIMEOUT_S = conf("srt.cluster.heartbeatTimeoutSec") \
    .doc("Seconds of heartbeat silence before the driver declares a "
         "worker dead, evicts it, and breaks its barriers (failure "
         "detection instead of waiting out barrierTimeoutSec). Keep "
         "comfortably above the longest GIL-bound stall (XLA compiles "
         "block the heartbeat thread).") \
    .check(_positive).double(30.0)

DECOMMISSION_ENABLED = conf("srt.cluster.decommission.enabled") \
    .doc("Workers install a SIGTERM handler for graceful decommission "
         "(Spark's spark.decommission.enabled role): on SIGTERM or a "
         "driver 'decommission' frame the worker finishes its in-flight "
         "job, drains pending pushes, migrates its completed map-output "
         "blocks to a live buddy peer as replicas, and deregisters — so "
         "a planned shutdown costs zero stage re-executions.") \
    .boolean(True)

DECOMMISSION_TIMEOUT_S = conf("srt.cluster.decommission.timeoutSec") \
    .doc("Wall-clock budget in seconds for a decommissioning worker's "
         "drain + block-migration phase; on expiry the remaining blocks "
         "are abandoned to normal recovery (buddy replicas if "
         "replicated, else stage re-execution).") \
    .check(_positive).double(30.0)

SHUFFLE_REPLICATION_FACTOR = conf("srt.shuffle.replication.factor") \
    .doc("Copies of each completed map-output block across the cluster: "
         "1 keeps the origin worker authoritative (classic); 2 also "
         "pushes every block to a deterministic buddy worker over the "
         "eager-push framing, so a hard worker kill degrades to a "
         "buddy replica fetch instead of a stage re-execution. Replicas "
         "are addressed by (origin, shuffle, map, reduce) and never "
         "serve normal fetches, so map-id collisions across workers "
         "are impossible.") \
    .check(lambda v: None if v >= 1 else "must be >= 1").integer(1)

FAULT_PLAN_SPEC = conf("srt.test.faultPlan") \
    .doc("Fault-injection plan spec (robustness/faults.py grammar), "
         "armed in every process that executes with this conf — cluster "
         "workers arm it from the job conf. Empty disables injection.") \
    .internal().string("")

DPP_ENABLED = conf("srt.sql.dpp.enabled") \
    .doc("Runtime dynamic partition pruning: when a broadcast join's "
         "probe side scans a partitioned table on a partition column, "
         "the materialized build side's distinct keys prune the scan's "
         "file list before any probe file opens "
         "(GpuSubqueryBroadcastExec / DynamicPruningExpression role).") \
    .boolean(True)

PYTHON_UDF_TIMEOUT = conf("srt.python.udf.timeoutSec") \
    .doc("Seconds a single pandas-UDF batch may run in a worker before "
         "the worker is killed and the job fails (guards against hung "
         "UDFs wedging the engine; 0 disables).") \
    .check(lambda v: v >= 0).integer(600)

PALLAS_ENABLED = conf("srt.sql.pallas.enabled") \
    .doc("Execute eligible global (no grouping keys) aggregates through "
         "the pallas tile_reduce kernel, one device program per batch. A "
         "Filter under the aggregate runs inside that program, so no "
         "filtered intermediate is built: in the kernel when the kernel "
         "can evaluate the predicate exactly, otherwise (a FLOAT64 "
         "comparison on TPU, Divide, numeric IN) as a mask XLA computes "
         "in front of the kernel at the columns' own types. "
         "On TPU the fused kernel computes float sums in float32 with "
         "float64 cross-tile combination — the same corner-case "
         "deviation class as spark.rapids.sql.variableFloatAgg.enabled; "
         "on CPU (interpret mode) arithmetic stays float64-exact.") \
    .boolean(True)

PALLAS_GROUPED_ENABLED = conf("srt.sql.pallas.groupedAgg.enabled") \
    .doc("Execute eligible grouped aggregations (sum/avg over floats, "
         "count) through the one-hot MXU pallas kernel "
         "(ops/pallas_kernels.tile_group_reduce) when a batch resolves "
         "to <= 1024 groups via the hash-claim prelude; larger key "
         "domains and non-sum-decomposable aggregates keep the XLA "
         "scatter path inside the same traced program. Active on TPU "
         "(or with SRT_PALLAS_GROUPED_FORCE=1, the CPU interpret-mode "
         "test lane). Float sums share srt.sql.pallas.enabled's "
         "variableFloatAgg-class deviation on TPU.") \
    .boolean(True)

PALLAS_GROUP_MAX_CAPACITY = conf("srt.exec.pallas.groupAgg.maxCapacity") \
    .doc("Batch-capacity ceiling for the grouped pallas MXU lane. "
         "Per-bucket counts accumulate in float32 lanes on the MXU and "
         "float32 represents integers exactly only below 2^24, so "
         "batches at or above this capacity take the stock integer "
         "scatter/sort path (Count/CountStar would otherwise drift). "
         "Raising it past 2^24 trades count exactness for MXU "
         "coverage; a forced fallback logs one PallasCapacityFallback "
         "event per process.") \
    .check(_positive).integer(1 << 24)

FUSION_ENABLED = conf("srt.exec.fusion.enabled") \
    .doc("Operator-fusion pass (plan/overrides.py -> exec/fused.py): "
         "collapse scan -> filter -> project -> partial-aggregate "
         "chains into one jitted program per chain so intermediate "
         "batches never round-trip through HBM (cuDF fused "
         "filter/project + GpuHashAggregateExec partial-on-scan role). "
         "Chains holding eager or partition-context expressions "
         "(input_file_name, spark_partition_id, ...) always stay "
         "unfused.") \
    .commonly_used().boolean(True)

FUSION_EXCLUDE_EXECS = conf("srt.exec.fusion.excludeExecs") \
    .doc("Comma-separated exec class names (FilterExec, ProjectExec, "
         "HashAggregateExec) the fusion matcher must not absorb into a "
         "FusedPipelineExec — an opt-out list for isolating a "
         "suspected fusion miscompare without turning the whole pass "
         "off. An excluded class breaks the chain at that node.") \
    .string("")

FUSION_DONATE = conf("srt.exec.fusion.donateInputs") \
    .doc("Donate the input batch's device buffers to the fused program "
         "(jax.jit donate_argnums) so XLA reuses them for the output "
         "instead of allocating fresh HBM. Applied only on non-CPU "
         "backends and only when the chain's source produces "
         "single-use buffers (file scans, not in-memory tables whose "
         "batches are re-executed). For fused joins the probe batch is "
         "donated only on capacity-measured relaunches, where the "
         "launch is provably final and the batch provably dead.") \
    .boolean(True)

FUSION_JOINS = conf("srt.exec.fusion.joins") \
    .doc("Hash-join fusion (fusion v2): compile build+probe plus the "
         "filter/project/partial-aggregate suffix above the join into "
         "one jitted program per probe batch, so the joined batch "
         "never materializes in HBM between operators. The join node "
         "keeps all of its own orchestration — broadcast demotion, "
         "skew splits, sub-partitioning, bloom prefilter, DPP and "
         "capacity-growth retries (plan/adaptive.py decisions apply "
         "unchanged; only the per-pair program is swapped). Joins "
         "with eager key expressions or a post-join condition stay "
         "unfused.") \
    .commonly_used().boolean(True)

FUSION_FINAL_AGG = conf("srt.exec.fusion.finalAgg") \
    .doc("FINAL-mode HashAggregate fusion (fusion v2): compile the "
         "post-shuffle merge pass together with its upstream "
         "coalesce/project — partial batches concatenate, project and "
         "merge+finalize inside one jitted program instead of an "
         "eager concat followed by a separate merge launch. Falls "
         "back to an eager pre-concat above "
         "srt.exec.fusion.finalAgg.maxMergeInputs batches.") \
    .commonly_used().boolean(True)

FUSION_MERGE_MAX_INPUTS = conf("srt.exec.fusion.finalAgg.maxMergeInputs") \
    .doc("Largest number of partial batches handed to the fused "
         "FINAL-merge program as separate arguments (each distinct "
         "count is its own cached program signature). Above this the "
         "batches are eagerly concatenated first and the single-input "
         "fused program runs — correctness is unchanged, one extra "
         "HBM materialization is paid.") \
    .check(_positive).integer(8)

FUSION_SORT = conf("srt.exec.fusion.sort") \
    .doc("Sort-prefix fusion (fusion v2) for the out-of-core sorter "
         "(exec/sort.py): chunk slicing + head-row extraction, "
         "carry+chunk concat + key-extraction + local sort, and the "
         "bound-row safe-prefix count each run as one jitted program "
         "instead of eager kernel calls between separate launches.") \
    .boolean(True)

OPTIMIZER_ENABLED = conf("srt.sql.optimizer.enabled") \
    .doc("Cost-based optimizer: keep plans below the row threshold on "
         "the CPU engine where device compile/transfer overhead "
         "dominates. (spark.rapids.sql.optimizer.enabled, "
         "CostBasedOptimizer.scala:54)") \
    .boolean(False)

OPTIMIZER_ROW_THRESHOLD = conf("srt.sql.optimizer.rowThreshold") \
    .doc("Weighted row-volume below which the cost model keeps a plan "
         "on CPU (only with srt.sql.optimizer.enabled).") \
    .check(_positive).integer(10_000)

CONCURRENT_QUERY_TASKS = conf("srt.sql.concurrentQueryTasks") \
    .doc("Number of queries admitted to execute concurrently against "
         "the device pool; further queries wait in a bounded admission "
         "queue. Also sets the number of per-query memory-budget "
         "slices. (spark.rapids.sql.concurrentGpuTasks / "
         "GpuSemaphore.scala, lifted from task to query granularity)") \
    .check(_positive).commonly_used().integer(4)

ADMISSION_MAX_QUEUE_DEPTH = conf("srt.sql.admission.maxQueueDepth") \
    .doc("Maximum queries allowed to WAIT for admission on top of the "
         "running set; arrivals beyond this are load-shed with a "
         "retryable AdmissionRejected instead of queueing unboundedly.") \
    .check(_non_negative).integer(16)

ADMISSION_BACKOFF_BASE_S = conf("srt.sql.admission.backoffBaseSec") \
    .doc("Base seconds for the exponential backoff (with jitter) a "
         "queued query sleeps between admission re-checks; doubles per "
         "attempt up to a small cap. Bounds cancellation/deadline "
         "latency while queued.") \
    .check(_positive).double(0.05)

QUERY_TIMEOUT_S = conf("srt.sql.queryTimeout") \
    .doc("Per-query deadline in seconds, measured from admission "
         "request to last batch; 0 disables. On expiry the query tears "
         "down through every pipeline/fetch thread and raises "
         "DeadlineExceeded. df.collect(timeout=...) overrides per "
         "call.") \
    .check(_non_negative).commonly_used().double(0.0)

SERVE_HOST = conf("srt.serve.host") \
    .doc("Interface the SQL serving front door (serve/server.py) binds "
         "its listening socket to.") \
    .string("127.0.0.1")

SERVE_PORT = conf("srt.serve.port") \
    .doc("TCP port for the SQL serving front door; 0 picks an "
         "ephemeral port (the bound port is on SqlServer.endpoint).") \
    .check(_non_negative).integer(0)

SERVE_AUTH_TOKEN = conf("srt.serve.authToken") \
    .doc("Shared-secret token clients must present in their HELLO "
         "frame; empty disables authentication. A mismatch closes the "
         "connection with a non-retryable error before any session "
         "state is created.") \
    .string("")

SERVE_MAX_SESSIONS = conf("srt.serve.maxSessions") \
    .doc("Maximum concurrently open client sessions; connections "
         "beyond this are refused at HELLO with a retryable error "
         "(session-level load shed, upstream of query admission).") \
    .check(_positive).integer(64)

SERVE_STREAM_CHUNK_ROWS = conf("srt.serve.streamChunkRows") \
    .doc("Maximum rows per result-batch frame streamed back to a "
         "client; larger results split into multiple frames in the "
         "serializer's columnar wire format.") \
    .check(_positive).integer(1 << 16)

RESULT_CACHE_ENABLED = conf("srt.sql.resultCache.enabled") \
    .doc("Cross-tenant result reuse in the serving tier: completed "
         "result sets are cached under a canonicalized-plan "
         "fingerprint (plan_cache.py structural key: file snapshots "
         "fold in mtime/size, Delta scans their commit version) and "
         "replayed for identical resubmissions without re-executing "
         "or re-passing admission. Entries are crc-framed "
         "(robustness/integrity.py) and invalidated by Delta commits "
         "to any scanned table. Bit-identical on/off.") \
    .commonly_used().boolean(False)

RESULT_CACHE_MAX_BYTES = conf("srt.sql.resultCache.maxBytes") \
    .doc("Byte budget for the serving result cache; inserting past "
         "the cap evicts least-recently-used entries first. 0 "
         "disables caching even when enabled.") \
    .check(_non_negative).bytes_(64 << 20)

SHUFFLE_HEARTBEAT_TIMEOUT_S = conf("srt.shuffle.heartbeat.timeoutSec") \
    .doc("DEPRECATED alias for srt.cluster.heartbeatTimeoutSec (the "
         "standalone shuffle service and the cluster driver once read "
         "different keys). Setting it forwards to the new key and warns "
         "once per process.") \
    .check(_positive).double(30.0)


# (key, replacement) pairs resolved in SrtConf.__init__: the old key's
# value forwards to the new key when the new key is unset, with a
# once-per-process deprecation warning.
_DEPRECATED_ALIASES = {
    "srt.sql.adaptiveBroadcastRows": "srt.sql.adaptive.autoBroadcastJoinRows",
    "srt.shuffle.heartbeat.timeoutSec": "srt.cluster.heartbeatTimeoutSec",
}
_ALIAS_WARNED: set = set()


class SrtConf:
    """Immutable snapshot of settings, one per session (RapidsConf)."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings = dict(settings or {})
        for k in self._settings:
            if k.startswith("srt.") and k not in _REGISTRY:
                raise KeyError(f"unknown config {k!r}; registered: "
                               f"{sorted(_REGISTRY)}")
            if k in _REGISTRY and _REGISTRY[k].checker is not None:
                # fail fast AT SET TIME, not at first read deep inside a
                # query: run the entry's converter+checker now so e.g. an
                # unknown srt.shuffle.compression.codec raises here with
                # the allowed set in the message
                _REGISTRY[k].get({k: self._settings[k]})
        for old, new in _DEPRECATED_ALIASES.items():
            if old not in self._settings:
                continue
            if old not in _ALIAS_WARNED:
                _ALIAS_WARNED.add(old)
                import warnings
                warnings.warn(f"config {old!r} is deprecated; use {new!r}",
                              DeprecationWarning, stacklevel=2)
            self._settings.setdefault(new, self._settings[old])

    def get(self, entry: ConfEntry):
        return entry.get(self._settings)

    def with_settings(self, **kv) -> "SrtConf":
        s = dict(self._settings)
        s.update({k.replace("_", "."): v for k, v in kv.items()})
        return SrtConf(s)

    def set(self, key: str, value) -> "SrtConf":
        s = dict(self._settings)
        s[key] = value
        return SrtConf(s)

    # Property shorthands used across the codebase
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return self.get(EXPLAIN)

    @property
    def batch_size_rows(self) -> int:
        return self.get(BATCH_SIZE_ROWS)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def ansi(self) -> bool:
        return self.get(ANSI_ENABLED)


_ACTIVE = threading.local()


def active_conf() -> SrtConf:
    c = getattr(_ACTIVE, "conf", None)
    if c is None:
        c = SrtConf()
        _ACTIVE.conf = c
    return c


def set_active_conf(c: SrtConf) -> None:
    _ACTIVE.conf = c


def generate_docs() -> str:
    """Markdown table of all public configs (RapidsConf.main doc-gen,
    RapidsConf.scala:2214 -> docs/configs.md)."""
    lines = ["# spark_rapids_tpu configuration", "",
             "Generated from `spark_rapids_tpu/conf.py` — do not edit.", "",
             "| Name | Default | Description |", "|---|---|---|"]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.is_internal:
            continue
        doc = e.doc.replace("\n", " ")
        lines.append(f"| {e.key} | {e.default!r} | {doc} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    import sys
    out = sys.argv[1] if len(sys.argv) > 1 else "docs/configs.md"
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        f.write(generate_docs())
    print(f"wrote {out}")
