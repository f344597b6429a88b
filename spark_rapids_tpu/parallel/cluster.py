"""Multi-host runtime: a driver coordinating worker processes that
execute staged plans with a cross-process shuffle.

Rebuild of the reference's distributed runtime seam (SURVEY §5
distributed comm backend; RapidsShuffleHeartbeatManager +
RapidsShuffleServer/Client): Spark provides the driver/executor
process model there, so the plugin only ships the shuffle; HERE the
framework is the engine, so this module provides the missing runtime:

- ``ClusterWorker``: one engine process. Serves its shuffle blocks over
  the TCP transport (parallel/transport.py), executes its share of a
  staged physical plan, and coordinates through the driver's control
  channel (register / shuffle barrier / result).
- ``ClusterDriver``: accepts worker registrations, ships each job as
  (cloudpickled logical plan, conf overrides), releases shuffle
  barriers once every worker's map side is written, and merges ordered
  worker results.

Execution model (one plan, W workers):
- every worker builds the IDENTICAL physical plan from the logical plan
  (apply_overrides is deterministic; workers are fresh processes so
  shuffle ids match),
- non-broadcast file-scan leaves are sharded round-robin by file index;
  leaves under a BroadcastExchange replicate (every worker materializes
  the same build side, the reference's broadcast contract),
- ShuffleExchange map sides write LOCAL blocks, a driver barrier makes
  map outputs visible, and reduce partitions are assigned to workers in
  CONTIGUOUS blocks (so concatenating worker results in id order
  preserves range-partitioned global sort order); reads fetch each
  partition from every peer over the transport,
- final output rows stream back to the driver as pickled pydicts.

Fault tolerance (docs/ROBUSTNESS.md has the full contract):
- workers heartbeat the driver's ShuffleHeartbeatManager; silence past
  ``srt.cluster.heartbeatTimeoutSec`` evicts the worker and breaks any
  barrier it would have joined (failure DETECTION, instead of waiting
  out the barrier timeout),
- sharding is by LOGICAL worker id over a fixed modulus: each physical
  worker carries a contiguous ascending segment of logical ids, so a
  dead worker's shard can be re-attached to a survivor without
  reshuffling anyone else's data or breaking global partition order,
- recovery is STAGE-level first: shuffles whose barrier released in the
  failed attempt keep their map outputs — survivors rename the blocks
  under the re-planned exchange's fresh shuffle id and only the dead
  worker's shards re-execute; whole-job retry is the outer last resort.

Workers run on any reachable host; tests drive the full stack with
subprocess workers on localhost (the reference's own test strategy —
SURVEY §4: no real multi-node cluster anywhere in CI).
"""

from __future__ import annotations

import os
import pickle
import select
import socket
import socketserver
import struct
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

from ..robustness.faults import FaultDrop, fault_point

_FRAME = struct.Struct(">I")


def _send_msg(sock: socket.socket, obj) -> None:
    payload = pickle.dumps(obj)
    sock.sendall(_FRAME.pack(len(payload)) + payload)


def _recv_msg(sock: socket.socket):
    head = _recv_exact(sock, 4)
    if head is None:
        return None
    (n,) = _FRAME.unpack(head)
    data = _recv_exact(sock, n)
    return None if data is None else pickle.loads(data)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


class WorkerLost(RuntimeError):
    """A worker process died mid-dialogue (connection closed)."""

    def __init__(self, worker_id: int):
        super().__init__(f"worker {worker_id} lost")
        self.worker_id = worker_id


class _DecommissionRequested(BaseException):
    """Raised by the worker's SIGTERM handler to interrupt the IDLE
    control-socket recv (BaseException: must not be swallowed by a
    generic except). Mid-job, the handler only sets the flag — the job
    finishes and replies first."""


class RecoveryTimer:
    """Failure-detection → first-post-recovery-result span. Stamped at
    the moment the driver classifies a failure; ``finish`` observes the
    ``recovery_time_ns`` histogram and emits a RecoveryTimed event —
    the chaos legs' recovery-budget assertion hook."""

    def __init__(self, kind: str):
        self.kind = kind
        self.t0 = time.perf_counter_ns()

    def finish(self, **attrs) -> int:
        dt = time.perf_counter_ns() - self.t0
        from ..obs import events as _events
        from ..obs import registry as _registry
        _registry.observe("recovery_time_ns", dt, "ns")
        _events.emit("RecoveryTimed", kind=self.kind,
                     recovery_time_ns=dt, **attrs)
        return dt


class StageRetryFailed(RuntimeError):
    """A survivor could not satisfy a stage-level retry (its recorded
    job state is gone or from another job) — fall back to whole-job."""

    def __init__(self, worker_id: int, detail: str):
        super().__init__(f"stage retry failed at worker {worker_id}: "
                         f"{detail}")
        self.worker_id = worker_id


class ClusterTaskContext:
    """Per-worker execution context handed to the exec layer via
    ExecContext.cluster.

    ``worker_id``/``num_workers`` are PHYSICAL (this attempt's worker
    list); sharding is by LOGICAL ids over ``shard_mod`` — the worker
    count of the job's first attempt — so a retry can hand a dead
    worker's logical shards to a survivor without moving anyone else's
    data. ``fresh_ids`` are the logical ids this worker newly adopted
    in this attempt: stages feeding a REUSED exchange re-execute only
    those (the survivors' own map outputs were renamed into the new
    shuffle id), while stages feeding a non-reused exchange run all of
    ``logical_ids``.
    """

    def __init__(self, worker_id: int, num_workers: int,
                 peers: List[str], driver_addr: Tuple[str, int],
                 logical_ids: Optional[List[int]] = None,
                 fresh_ids: Optional[List[int]] = None,
                 shard_mod: Optional[int] = None,
                 map_id_base: int = 0, attempt: int = 0,
                 assign: Optional[List[List[int]]] = None,
                 epoch: int = 0):
        self.worker_id = worker_id
        #: incarnation epoch assigned at registration; rides every
        #: barrier/gather frame so the driver can fence a zombie
        #: predecessor after eviction/decommission/rejoin
        self.epoch = epoch
        self.num_workers = num_workers
        self.peers = peers  # shuffle endpoints "host:port", worker order
        self.driver_addr = driver_addr
        self.logical_ids = (sorted(logical_ids) if logical_ids is not None
                            else [worker_id])
        self.fresh_ids = (sorted(fresh_ids) if fresh_ids is not None
                          else list(self.logical_ids))
        self.shard_mod = shard_mod if shard_mod is not None else num_workers
        #: the FULL logical-id assignment of this attempt (one list per
        #: physical worker, same order as ``peers``) — lets the map side
        #: predict which endpoint will read each reduce partition (the
        #: push-based shuffle's routing table)
        self.assign = ([list(a) for a in assign] if assign is not None
                       else [[w] for w in range(num_workers)])
        self.map_id_base = map_id_base
        self.attempt = attempt
        #: shuffle ids (THIS attempt's) whose map outputs were reused
        #: from the previous attempt — gates stage_ids()
        self.reusable_sids: Set[int] = set()
        self.sid_to_pos: Dict[int, int] = {}
        #: range-partition bounds carried over from the previous attempt
        #: (sid -> rows); a reused range exchange must keep its original
        #: bounds or the renamed blocks would disagree with fresh ones
        self._prefilled_bounds: Dict[int, list] = {}
        #: bounds recorded DURING this attempt (aliased into the
        #: worker's _last_job so the next retry can prefill)
        self.bounds_out: Dict[int, list] = {}
        #: speculation callback installed by _run_job:
        #: (pos, unit_lids, map_id_base, live_sid) -> (map_ids, detail)
        #: — builds a re-sharded clone of the stage subtree at plan
        #: position ``pos`` and runs its map phase for the straggler's
        #: logical ids under a disjoint map-id namespace
        self.spec_factory = None

    def lids_csv(self) -> str:
        return ",".join(str(l) for l in self.logical_ids)

    def stage_ids(self, downstream_sid: Optional[int] = None) -> List[int]:
        """Logical shards this worker runs for the plan segment feeding
        ``downstream_sid`` (None/unknown → the full logical set)."""
        if downstream_sid is not None and \
                downstream_sid in self.reusable_sids:
            return self.fresh_ids
        return self.logical_ids

    def assigned(self, num_partitions: int,
                 downstream_sid: Optional[int] = None) -> List[int]:
        """Contiguous reduce partitions for this worker: the union of
        each owned logical id's block. Logical ids are contiguous per
        worker, so the union is one contiguous range and concatenating
        worker results in physical order preserves partition order."""
        out: Set[int] = set()
        for lid in self.stage_ids(downstream_sid):
            lo = (num_partitions * lid) // self.shard_mod
            hi = (num_partitions * (lid + 1)) // self.shard_mod
            out.update(range(lo, hi))
        return sorted(out)

    def partition_owners(self, num_partitions: int) -> Dict[int, str]:
        """reduce partition -> the endpoint expected to READ it, from
        the attempt's full logical-id assignment (same contiguous-range
        arithmetic as ``assigned``). Best-effort by construction: AQE
        may coalesce or skew-split partitions afterwards, so push
        consumers treat a miss as 'pull it instead', never an error."""
        owners: Dict[int, str] = {}
        for w, lids in enumerate(self.assign):
            if w >= len(self.peers):
                break
            for lid in lids:
                lo = (num_partitions * lid) // self.shard_mod
                hi = (num_partitions * (lid + 1)) // self.shard_mod
                for r in range(lo, hi):
                    owners[r] = self.peers[w]
        return owners

    def owns_first(self) -> bool:
        return self.worker_id == 0

    # --- recorded range-partition bounds (stage-retry determinism) ---
    def prefill_bounds(self, shuffle_id: int, rows: list) -> None:
        self._prefilled_bounds[shuffle_id] = rows

    def bounds_for(self, shuffle_id: int) -> Optional[list]:
        return self._prefilled_bounds.get(shuffle_id)

    def record_bounds(self, shuffle_id: int, rows: list) -> None:
        self.bounds_out[shuffle_id] = [tuple(r) for r in rows]

    def _timeout(self) -> int:
        from ..conf import CLUSTER_BARRIER_TIMEOUT, active_conf
        return active_conf().get(CLUSTER_BARRIER_TIMEOUT)

    def barrier(self, shuffle_id: int, pos: int = -1,
                detail: Optional[dict] = None,
                spec_ok: bool = False) -> Optional[dict]:
        """Block until every worker's map side for shuffle_id is
        written (driver-released). ``pos`` is the exchange's stable
        traversal position — the driver's map-output registry records
        completion by position, not by (attempt-fresh) shuffle id.

        ``detail`` is this worker's exact per-(map, reduce)
        (rows, bytes) report, recorded into the driver's map-output
        registry. With speculation enabled the driver may answer
        ``speculate`` instead of ``release``: this worker then runs a
        straggler's shard through ``spec_factory`` under a disjoint
        map-id namespace and re-arrives with the speculative report.
        Returns the driver's winners verdict ({"allowed": {worker:
        (map_ids...)}}) under speculation, else None (no filtering)."""
        fault_point("cluster.barrier",
                    f"attempt={self.attempt};workers={self.lids_csv()};"
                    f"pos={pos};")
        if os.environ.get("SRT_CLUSTER_DEBUG"):
            print(f"[w{self.worker_id}] barrier {shuffle_id} pos={pos}",
                  file=sys.stderr, flush=True)
        spec_on = False
        try:
            from ..conf import ADAPTIVE_SPECULATION_ENABLED, active_conf
            spec_on = bool(active_conf().get(ADAPTIVE_SPECULATION_ENABLED))
        except Exception:
            spec_on = False
        msg: dict = {"type": "barrier", "shuffle_id": shuffle_id,
                     "worker": self.worker_id, "pos": pos,
                     "epoch": self.epoch}
        if detail is not None:
            msg["detail"] = dict(detail)
            msg["map_ids"] = sorted({m for (m, _r) in detail})
        if spec_on:
            msg["speculation"] = True
            msg["spec_ok"] = bool(spec_ok
                                  and self.spec_factory is not None)
            msg["unit"] = list(self.logical_ids)
        while True:
            with socket.create_connection(self.driver_addr,
                                          timeout=self._timeout()) as s:
                _send_msg(s, msg)
                reply = _recv_msg(s)
            if reply and reply.get("type") == "release":
                return reply.get("winners")
            if reply and reply.get("type") == "speculate":
                unit = list(reply.get("unit") or ())
                # disjoint namespace: high bit within this attempt's
                # map-id space, sub-ranged by speculator worker, so a
                # spec map can never collide with a normal map id or
                # another speculator's
                base = self.map_id_base + (1 << 19) + (self.worker_id << 14)
                spec_ids: List[int] = []
                spec_detail: dict = {}
                failed = False
                try:
                    if self.spec_factory is None:
                        raise RuntimeError("no spec_factory installed")
                    spec_ids, spec_detail = self.spec_factory(
                        pos, unit, base, shuffle_id)
                except Exception:
                    # report the failure; the driver must NOT commit an
                    # empty result for the straggler's unit
                    failed = True
                    spec_ids, spec_detail = [], {}
                msg = {"type": "barrier", "shuffle_id": shuffle_id,
                       "worker": self.worker_id, "pos": pos,
                       "epoch": self.epoch,
                       "speculation": True, "spec_report": True,
                       "spec_failed": failed, "unit": unit,
                       "detail": spec_detail,
                       "map_ids": sorted(spec_ids)}
                continue
            raise RuntimeError(
                f"barrier {shuffle_id} failed: {reply!r}")

    def gather(self, key, payload) -> List:
        """All-gather a picklable payload across workers through the
        driver (GpuRangePartitioner.sketch-to-driver role); returns the
        payloads in worker order."""
        if os.environ.get("SRT_CLUSTER_DEBUG"):
            print(f"[w{self.worker_id}] gather {key}",
                  file=sys.stderr, flush=True)
        with socket.create_connection(self.driver_addr,
                                      timeout=self._timeout()) as s:
            _send_msg(s, {"type": "gather", "key": key,
                          "worker": self.worker_id, "payload": payload,
                          "epoch": self.epoch})
            reply = _recv_msg(s)
        if not reply or reply.get("type") != "gathered":
            raise RuntimeError(f"gather {key} failed: {reply!r}")
        return reply["payloads"]

    def resolve_endpoint(self, endpoint: str) -> Optional[str]:
        """Ask the driver's heartbeat registry for the CURRENT endpoint
        of the (live) executor that ever served ``endpoint`` — the
        shuffle fetch failover hook (transport.fetch_all_partitions
        endpoint_resolver). None when that executor is gone."""
        try:
            with socket.create_connection(self.driver_addr,
                                          timeout=10) as s:
                _send_msg(s, {"type": "resolve", "endpoint": endpoint})
                reply = _recv_msg(s)
            if not reply or reply.get("type") != "resolved":
                return None
            return reply.get("endpoint")
        except OSError:
            return None


# ---------------------------------------------------------------------------
# plan annotation (stage positions + downstream-exchange links)
# ---------------------------------------------------------------------------

_MISSING = object()


def _annotate_plan(physical) -> Tuple[Dict[int, int], Set[int]]:
    """Walk the physical plan pre-order, assigning each shuffle
    exchange a stable traversal POSITION (``_cluster_pos``) and
    recording, on every exchange and file scan, the shuffle id of the
    exchange its output feeds (``_downstream_sid`` /
    ``_shard_downstream``; None for the final result segment and under
    broadcasts, which rebuild every attempt).

    Returns ``(sid_to_pos, tainted_sids)``. Pure function of the plan:
    every worker and every attempt derives identical positions, which
    is what lets the driver name stages by position while shuffle ids
    stay fresh per attempt. A subtree SHARED by two different consumer
    exchanges taints both consumers: a fresh-shard-only re-run cannot
    split its output between them, so neither is eligible for reuse.
    """
    from ..exec.exchange import BroadcastExchangeExec, ShuffleExchangeExec
    from ..io.scan import FileSourceScanExec

    sid_to_pos: Dict[int, int] = {}
    tainted: Set[int] = set()
    seen_under: Dict[int, object] = {}  # id(node) -> first downstream sid
    counter = [0]

    def walk(node, downstream: Optional[int]) -> None:
        nid = id(node)
        prev = seen_under.get(nid, _MISSING)
        if prev is not _MISSING:
            if prev != downstream:
                for d in (prev, downstream):
                    if d is not None:
                        tainted.add(d)
            return
        seen_under[nid] = downstream
        if isinstance(node, ShuffleExchangeExec):
            node._cluster_pos = counter[0]
            node._downstream_sid = downstream
            sid_to_pos[node.shuffle_id] = counter[0]
            counter[0] += 1
            for c in node.children:
                walk(c, node.shuffle_id)
            return
        if isinstance(node, BroadcastExchangeExec):
            for c in node.children:
                walk(c, None)
            return
        if isinstance(node, FileSourceScanExec):
            node._shard_downstream = downstream
        for c in node.children:
            walk(c, downstream)

    walk(physical, None)
    return sid_to_pos, tainted


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def _shard_scans(physical, worker_id: int, num_workers: int,
                 cluster: Optional[ClusterTaskContext] = None) -> None:
    """Shard file-scan leaves by file index over the logical id set,
    EXCEPT under broadcast exchanges (replicated build sides). With a
    ``cluster`` context the shard set is per-scan: scans feeding a
    REUSED exchange keep only the freshly adopted shards."""
    from ..exec.exchange import BroadcastExchangeExec
    from ..io.scan import FileScan

    done: Set[int] = set()  # shared subtrees: shard each scan once

    def walk(node, under_broadcast: bool) -> None:
        from ..io.scan import FileSourceScanExec
        if isinstance(node, FileSourceScanExec) and not under_broadcast:
            if id(node) in done:
                return
            done.add(id(node))
            if cluster is None:
                ids, mod = {worker_id}, num_workers
            else:
                dsid = getattr(node, "_shard_downstream", None)
                ids = set(cluster.stage_ids(dsid))
                mod = cluster.shard_mod
            scan = node.scan
            mine = [p for i, p in enumerate(scan.paths) if i % mod in ids]
            sharded = FileScan.__new__(FileScan)
            sharded.__dict__.update(scan.__dict__)
            sharded.paths = mine
            node.scan = sharded
            return
        ub = under_broadcast or isinstance(node, BroadcastExchangeExec)
        for c in node.children:
            walk(c, ub)

    walk(physical, False)


def _worker_has_local_relation(physical, num_workers: int) -> bool:
    """Non-broadcast local relations would duplicate rows W times."""
    from ..exec.exchange import BroadcastExchangeExec
    from ..plan.transitions import HostToDeviceExec

    def walk(node, under_broadcast: bool) -> bool:
        ub = under_broadcast or isinstance(node, BroadcastExchangeExec)
        if not node.children:
            from ..io.scan import FileSourceScanExec
            if not isinstance(node, FileSourceScanExec) and \
                    not ub and num_workers > 1:
                return True
        return any(walk(c, ub) for c in node.children)
    return walk(physical, False)


class ClusterWorker:
    """One engine process: shuffle server + job execution loop."""

    def __init__(self, driver_host: str, driver_port: int,
                 host: str = "127.0.0.1"):
        from ..conf import SrtConf, set_active_conf
        from .shuffle_manager import shuffle_manager
        from .transport import ShuffleBlockServer
        self.driver_addr = (driver_host, driver_port)
        # the transport serves HOST blocks: the process-wide manager
        # must be built in MULTITHREADED (serialize-to-host) mode
        # before anything else instantiates it
        set_active_conf(SrtConf({"srt.shuffle.mode": "MULTITHREADED"}))
        self.manager = shuffle_manager()
        assert self.manager.mode == "MULTITHREADED", self.manager.mode
        self.server = ShuffleBlockServer(self.manager, host=host)
        self.host = host
        #: state of the most recent job attempt, kept across failures so
        #: a stage-level retry can rename completed map outputs:
        #: {"token": job_token, "sids": [sid by position],
        #:  "bounds": {sid: bounds_rows}}
        self._last_job: Optional[dict] = None
        # --- graceful decommission state (SIGTERM or driver frame) ---
        self._decommission = threading.Event()
        #: True only while the control thread is blocked in the IDLE
        #: recv — the one place the SIGTERM handler may raise to
        #: interrupt (mid-job it just sets the event; the job replies
        #: first and the loop picks the flag up after)
        self._idle_wait = False
        self._executor_id: Optional[str] = None
        self._epoch = 0
        #: the last job's peer list + own index — the decommission path
        #: computes its ring buddy from these (replicas already live
        #: there under k=2 replication)
        self._last_peers: List[str] = []
        self._last_worker_id = 0

    def _heartbeat_loop(self, executor_id: str, interval: float,
                        stop: threading.Event) -> None:
        """Liveness beats on fresh connections (the control socket is
        owned by the job dialogue). A ``drop`` fault skips one beat; a
        ``delay`` fault models a slow peer; killing this thread (any
        other injected error) models a silently wedged worker."""
        import random
        # ±10% jitter: a fleet of workers started together must not
        # phase-lock their beats into synchronized driver load spikes
        while not stop.wait(interval * random.uniform(0.9, 1.1)):
            try:
                fault_point("cluster.heartbeat",
                            f"executor={executor_id};")
            except FaultDrop:
                continue
            try:
                with socket.create_connection(
                        self.driver_addr,
                        timeout=max(5.0, interval * 2)) as s:
                    _send_msg(s, {"type": "heartbeat",
                                  "executor_id": executor_id,
                                  "endpoint": self.server.endpoint})
                    _recv_msg(s)
            except OSError:
                pass  # driver unreachable; the main loop will notice

    def _on_sigterm(self, signum, frame) -> None:
        self._decommission.set()
        if self._idle_wait:
            raise _DecommissionRequested()

    def _recv_ctl(self, s: socket.socket):
        """Idle control-socket recv, interruptible by SIGTERM: the
        handler's raise (or an already-set flag) converts to a
        synthetic ``decommission`` frame."""
        if self._decommission.is_set():
            return {"type": "decommission", "reason": "sigterm"}
        self._idle_wait = True
        try:
            return _recv_msg(s)
        except _DecommissionRequested:
            return {"type": "decommission", "reason": "sigterm"}
        finally:
            self._idle_wait = False

    def run_forever(self) -> None:
        """Register, then serve job requests until shutdown."""
        from ..conf import DECOMMISSION_ENABLED, active_conf
        if active_conf().get(DECOMMISSION_ENABLED) and \
                threading.current_thread() is threading.main_thread():
            import signal
            try:
                signal.signal(signal.SIGTERM, self._on_sigterm)
            except (ValueError, OSError):
                pass  # exotic embedding: SIGTERM stays default
        stop_hb = threading.Event()
        try:
            with socket.create_connection(self.driver_addr,
                                          timeout=120) as s:
                reg: dict = {"type": "register",
                             "shuffle_endpoint": self.server.endpoint}
                # rejoin: declare which dead incarnation's endpoint
                # this process replaces — the driver re-points block
                # ownership and fences the predecessor's epoch
                prior = os.environ.get("SRT_REJOIN_ENDPOINT")
                if prior:
                    reg["prior_endpoint"] = prior
                _send_msg(s, reg)
                msg = _recv_msg(s)
                if isinstance(msg, dict) and \
                        msg.get("type") == "registered":
                    self._executor_id = msg["executor_id"]
                    self._epoch = int(msg.get("epoch", 0))
                    hb = threading.Thread(
                        target=self._heartbeat_loop,
                        args=(msg["executor_id"],
                              float(msg.get("heartbeat_interval", 2.0)),
                              stop_hb),
                        daemon=True)
                    hb.start()
                    msg = self._recv_ctl(s)
                #: control frames the mid-job cancel listener consumed
                #: early — replayed in order once the job has replied,
                #: preserving the pre-listener queue-in-socket semantics
                pending: List[dict] = []
                while True:
                    if msg is None or msg["type"] == "shutdown":
                        return
                    if msg["type"] == "reset":
                        # failed-attempt / post-job cleanup: drop every
                        # shuffle's blocks (stale state must not leak
                        # into the re-run) and forget the job record
                        for sid in list(self.manager._registered):
                            self.manager.unregister_shuffle(sid)
                        # held replicas too: a fresh run's shuffle ids
                        # restart from the same counter, so a stale
                        # replica under a recycled sid must not survive
                        self.manager.replicas.clear()
                        self._last_job = None
                        _send_msg(s, {"type": "reset_done"})
                    elif msg["type"] == "prepare_retry":
                        # stage-level retry probe: report which job's
                        # map outputs this worker still holds — NO
                        # blocks are dropped (that is the whole point)
                        token = (self._last_job or {}).get("token")
                        _send_msg(s, {"type": "retry_ready",
                                      "token": token})
                    elif msg["type"] == "cancel":
                        # stale cancel: the job it targeted already
                        # replied (the broadcast raced our result) —
                        # nothing to do, stay in protocol sync
                        pass
                    elif msg["type"] == "decommission":
                        self._decommission_now(
                            s, msg.get("reason") or "driver request")
                        return
                    elif msg["type"] == "job":
                        alive = self._serve_job(s, msg, pending)
                        if not alive:
                            return
                    msg = (pending.pop(0) if pending
                           else self._recv_ctl(s))
        finally:
            stop_hb.set()

    def _decommission_now(self, s: socket.socket, reason: str) -> None:
        """Graceful exit: stop taking work, drain in-flight pushes,
        migrate this worker's hot shuffle blocks to a live peer (as
        manifest-covered replicas — the same durability path k=2
        replication uses), then deregister. A worker SIGTERM'd mid-job
        lands here only AFTER the job replied, so the driver never
        loses a result to decommission."""
        from ..conf import DECOMMISSION_TIMEOUT_S, active_conf
        deadline = time.monotonic() + active_conf().get(
            DECOMMISSION_TIMEOUT_S)
        # Briefly drain queued control frames: the post-job reset must
        # apply BEFORE migration, or we would ship a finished job's
        # (already-freed-on-the-driver's-books) blocks to the buddy.
        drain_until = time.monotonic() + 1.0
        while time.monotonic() < drain_until:
            readable, _w, _x = select.select([s], [], [], 0.1)
            if not readable:
                continue
            try:
                ctl = _recv_msg(s)
            except OSError:
                break
            if ctl is None:
                break
            if ctl.get("type") == "reset":
                for sid in list(self.manager._registered):
                    self.manager.unregister_shuffle(sid)
                self.manager.replicas.clear()
                self._last_job = None
                try:
                    _send_msg(s, {"type": "reset_done"})
                except OSError:
                    pass
            elif ctl.get("type") == "shutdown":
                return
        # announce: the driver stops assigning this worker jobs and
        # answers with the surviving peer list (migration targets)
        peers: List[str] = []
        try:
            with socket.create_connection(self.driver_addr,
                                          timeout=10) as c:
                _send_msg(c, {"type": "decommission_request",
                              "executor_id": self._executor_id,
                              "endpoint": self.server.endpoint})
                reply = _recv_msg(c)
            if isinstance(reply, dict):
                peers = list(reply.get("peers") or ())
        except OSError:
            pass  # driver gone: nothing to migrate FOR; exit anyway
        self.manager.drain_pushes()
        own = self.server.endpoint
        candidates = [p for p in peers if p != own]
        target: Optional[str] = None
        if self._last_peers and len(self._last_peers) > 1:
            ring = self._last_peers[(self._last_worker_id + 1)
                                    % len(self._last_peers)]
            if ring in candidates:
                target = ring  # replicas (if any) already live there
        if target is None and candidates:
            target = candidates[0]
        migrated: List[int] = []
        if target is not None:
            migrated = self.manager.migrate_blocks(target, deadline)
            self.manager.drain_pushes()
            for sid in migrated:
                self.manager.publish_replica_manifest(
                    sid, target,
                    timeout_s=max(1.0, deadline - time.monotonic()))
        try:
            with socket.create_connection(self.driver_addr,
                                          timeout=10) as c:
                _send_msg(c, {"type": "decommission_done",
                              "executor_id": self._executor_id,
                              "endpoint": own, "reason": reason,
                              "migrated_sids": migrated,
                              "target": target})
                _recv_msg(c)
        except OSError:
            pass

    def _serve_job(self, s: socket.socket, msg,
                   pending: List[dict]) -> bool:
        """Run one job on a side thread while THIS (control) thread
        keeps listening on the driver socket — the only way a cancel
        can reach a busy worker. Mid-job, a ``cancel`` frame (or a
        closed connection: driver gone) flips the job's cancel token
        and the executing thread surfaces QueryCancelled at its next
        check point; any OTHER frame (reset/prepare_retry of an aborted
        attempt) is appended to ``pending`` for the caller to replay
        after the reply, exactly as it would have queued in the socket
        buffer before this listener existed. Returns False when the
        dialogue is over (driver lost / shutdown mid-job)."""
        from ..robustness.admission import QueryContext
        qctx = QueryContext(
            query_id=f"{msg.get('job_token', 'job')}"
                     f"-w{msg.get('worker_id', 0)}")
        reply: List[Optional[dict]] = [None]

        def _job() -> None:
            try:
                rows, metrics = self._run_job(msg, qctx)
                reply[0] = {"type": "result", "rows": rows,
                            "metrics": metrics}
            except BaseException as e:  # surface to driver
                import traceback
                reply[0] = {"type": "error",
                            "error": f"{e}\n{traceback.format_exc()}"}

        jt = threading.Thread(target=_job, daemon=True,
                              name="srt-worker-job")
        jt.start()
        while jt.is_alive():
            readable, _w, _x = select.select([s], [], [], 0.25)
            if not readable:
                continue
            try:
                ctl = _recv_msg(s)
            except OSError:
                ctl = None
            if ctl is None:
                # driver connection lost: abandon the job (nobody is
                # left to receive the result)
                qctx.cancel("driver connection lost")
                jt.join(timeout=30.0)
                return False
            t = ctl.get("type")
            if t == "cancel":
                qctx.cancel(ctl.get("reason") or "driver cancel")
            elif t == "shutdown":
                qctx.cancel("worker shutdown")
                jt.join(timeout=30.0)
                return False
            else:
                # a reset/prepare_retry mid-job means the driver gave
                # up on this attempt: finish fast, reply (the driver
                # drains it), then let the caller replay the frame
                if t == "reset":
                    qctx.cancel("driver reset during job")
                pending.append(ctl)
        jt.join()
        _send_msg(s, reply[0])
        return True

    def _run_job(self, msg, qctx=None) -> Tuple[List[dict], dict]:
        from ..conf import SrtConf, set_active_conf
        from ..exec.base import ExecContext
        from ..plan import overrides
        from ..plan.host_table import batch_to_table, to_pydict
        from ..robustness import faults
        logical = pickle.loads(msg["plan"])
        settings = dict(msg["conf"])
        settings["srt.shuffle.mode"] = "MULTITHREADED"
        conf = SrtConf(settings)
        set_active_conf(conf)
        # cancellation/deadline token: explicit cancels arrive over the
        # control socket (see _serve_job); the DEADLINE propagates
        # through the job conf — srt.sql.queryTimeout ships with every
        # job, so each worker arms its own clock from job start (driver
        # queueing time is not counted against the worker's slice)
        from ..conf import QUERY_TIMEOUT_S
        from ..robustness.admission import QueryContext, set_current_query
        if qctx is None:
            qctx = QueryContext(
                query_id=f"{msg.get('job_token', 'job')}"
                         f"-w{msg.get('worker_id', 0)}")
        qctx.set_timeout(conf.get(QUERY_TIMEOUT_S))
        set_current_query(qctx)
        # arm (or keep, or disarm) this process's fault plan from the
        # job conf — the driver-side test's spec reaches every worker
        faults.arm_from_conf(conf)
        # same hand-off for the event log: srt.eventLog.* in the job
        # conf lights up (or tears down) this worker's JSONL sink,
        # and srt.obs.resource.intervalMs the resource sampler
        from ..obs import events as _events
        from ..obs import resource as _resource
        _events.configure_from_conf(conf)
        _resource.configure_from_conf(conf)
        # cross-process tracing: rebuild a child tracer from the
        # driver's shipped context so this worker's task/operator spans
        # share the driver's trace_id and parent under its job span
        from ..conf import TRACE_ENABLED
        from ..obs.trace import Tracer
        tracer = (Tracer.from_context(msg.get("trace_ctx"))
                  if conf.get(TRACE_ENABLED) else None)
        attempt = msg.get("attempt", 0)
        logical_ids = msg.get("logical_ids") or [msg["worker_id"]]
        fresh_ids = msg.get("fresh_ids")
        self._last_peers = list(msg["peers"])
        self._last_worker_id = msg["worker_id"]
        cluster = ClusterTaskContext(
            msg["worker_id"], msg["num_workers"], msg["peers"],
            self.driver_addr, logical_ids=logical_ids,
            fresh_ids=fresh_ids if fresh_ids is not None else logical_ids,
            shard_mod=msg.get("shard_mod") or msg["num_workers"],
            map_id_base=msg.get("map_id_base", 0), attempt=attempt,
            assign=msg.get("assign"),
            epoch=int(msg.get("epoch", self._epoch)))
        fault_point("cluster.job",
                    f"attempt={attempt};workers={cluster.lids_csv()};")
        # shuffle ids are allocated during the translation below, and
        # peers must agree on them: seed the counter from the driver's
        # per-attempt base so veterans and late (re)joiners — whose
        # process-lifetime counters have diverged — produce identical
        # ids for the same plan
        sid_base = msg.get("sid_base")
        if sid_base:
            from ..exec.exchange import seed_shuffle_ids
            seed_shuffle_ids(int(sid_base))
        physical = overrides.apply_overrides(logical, conf)
        if _worker_has_local_relation(physical, cluster.num_workers):
            raise RuntimeError(
                "cluster mode shards file scans; non-broadcast local "
                "relations would duplicate (write the input to files)")
        sid_to_pos, tainted = _annotate_plan(physical)
        sids_by_pos = [sid for sid, _pos in
                       sorted(sid_to_pos.items(), key=lambda kv: kv[1])]
        cluster.sid_to_pos = sid_to_pos
        reuse_token = msg.get("reuse_token")
        if reuse_token is not None:
            self._prepare_reuse(msg, cluster, sids_by_pos, tainted,
                                reuse_token)
        else:
            # fresh attempt: stale blocks (a failed attempt the driver
            # chose not to stage-retry) were dropped by "reset"
            self._last_job = None
        # record BEFORE executing: a crash mid-job must leave behind
        # the sid map + bounds that DID complete (bounds_out is aliased,
        # so _compute_bounds fills it in place as the job runs)
        self._last_job = {"token": msg.get("job_token"),
                          "sids": sids_by_pos,
                          "bounds": cluster.bounds_out}
        _shard_scans(physical, cluster.worker_id, cluster.num_workers,
                     cluster)
        cluster.spec_factory = self._make_spec_factory(msg, conf, qctx,
                                                       cluster)
        debug = os.environ.get("SRT_CLUSTER_DEBUG")
        if debug:
            print(f"[w{cluster.worker_id}] plan (lids="
                  f"{cluster.logical_ids} fresh={cluster.fresh_ids} "
                  f"reuse={sorted(cluster.reusable_sids)}):\n"
                  f"{physical.tree_string()}", file=sys.stderr, flush=True)
        ctx = ExecContext(conf, query=qctx)
        ctx.cluster = cluster
        ctx.tracer = tracer
        # distinct per-worker default so monotonically_increasing_id /
        # spark_partition_id stay unique when no exchange streams reduce
        # partitions (exchanges overwrite this with the global reduce id)
        ctx.partition_id = cluster.worker_id
        rows: List[dict] = []
        t0 = time.perf_counter_ns()
        # the task span opens on THIS thread (the one pulling the
        # operator chain), so operator spans parent under it through
        # the tracer's thread-local scope stack
        task_scope = (tracer.span(
            f"task-w{cluster.worker_id}-a{attempt}", kind="task",
            attrs={"worker_id": cluster.worker_id, "attempt": attempt,
                   "logical_ids": list(cluster.logical_ids),
                   "job_token": msg.get("job_token")})
            if tracer is not None else None)
        if task_scope is not None:
            task_scope.__enter__()
        try:
            from ..plan.adaptive import adaptive_execute
            for batch in adaptive_execute(physical, ctx):
                if int(batch.num_rows) == 0:
                    continue
                d = to_pydict(batch_to_table(batch))
                names = list(d)
                for i in range(len(d[names[0]]) if names else 0):
                    rows.append({k: d[k][i] for k in names})
        finally:
            set_current_query(None)
            if task_scope is not None:
                task_scope.__exit__(None, None, None)
            if tracer is not None:
                log_dir = _events.log_dir()
                if log_dir:
                    try:
                        tracer.write_chrome_trace(os.path.join(
                            log_dir,
                            f"trace-{tracer.trace_id}-"
                            f"w{cluster.worker_id}-a{attempt}-"
                            f"{os.getpid()}.json"))
                    except OSError:
                        pass
        wall_ns = time.perf_counter_ns() - t0
        if debug:
            print(f"[w{cluster.worker_id}] rows={len(rows)}",
                  file=sys.stderr, flush=True)
        metrics = {eid: {m.name: m.value for m in md.values()}
                   for eid, md in ctx.metrics.items()}
        from ..obs import registry as _registry
        _registry.observe("task_time_ns", wall_ns, "ns")
        _events.emit("TaskEnd", worker_id=cluster.worker_id,
                     logical_ids=list(cluster.logical_ids),
                     attempt=attempt, rows=len(rows), wall_ns=wall_ns,
                     job_token=msg.get("job_token"), metrics=metrics)
        return rows, metrics

    def _make_spec_factory(self, msg, conf, qctx,
                           cluster: ClusterTaskContext):
        """Speculation callback for ClusterTaskContext.barrier: build a
        FRESH clone of the plan, locate the exchange at the straggler's
        stage position, point it at the live shuffle id, re-shard its
        subtree's scans to the straggler's logical ids, and run the map
        phase under the given disjoint map-id namespace. Returns
        ``(map_ids, detail)`` — the speculative report the worker
        re-arrives at the barrier with."""
        def spec_factory(pos: int, unit_lids: List[int], base: int,
                         live_sid: int):
            from ..exec.base import ExecContext
            from ..exec.exchange import ShuffleExchangeExec
            from ..plan import overrides
            clone = overrides.apply_overrides(pickle.loads(msg["plan"]),
                                              conf)
            _annotate_plan(clone)
            target: List = [None]

            def find(node):
                if target[0] is not None:
                    return
                if isinstance(node, ShuffleExchangeExec) and \
                        getattr(node, "_cluster_pos", -1) == pos:
                    target[0] = node
                    return
                for c in node.children:
                    find(c)

            find(clone)
            ex = target[0]
            if ex is None:
                raise RuntimeError(
                    f"speculation: no exchange at position {pos}")

            def has_nested(node) -> bool:
                return any(isinstance(c, ShuffleExchangeExec)
                           or has_nested(c) for c in node.children)

            if has_nested(ex):
                # a non-leaf stage would need ANOTHER barrier from
                # inside this one — refuse (spec_ok should have gated)
                raise RuntimeError(
                    "speculation: stage has nested exchanges")
            ex.shuffle_id = live_sid
            spec_cluster = ClusterTaskContext(
                cluster.worker_id, cluster.num_workers, cluster.peers,
                cluster.driver_addr, logical_ids=list(unit_lids),
                shard_mod=cluster.shard_mod,
                map_id_base=base, attempt=cluster.attempt,
                assign=cluster.assign)
            _shard_scans(ex, cluster.worker_id, cluster.num_workers,
                         spec_cluster)
            sctx = ExecContext(conf, query=qctx)
            sctx.partition_id = cluster.worker_id
            spec_ids = ex.run_speculative_maps(sctx, base)
            detail = self.manager.map_output_statistics(
                live_sid, map_ids=set(spec_ids)).detail
            return spec_ids, detail
        return spec_factory

    def _prepare_reuse(self, msg, cluster: ClusterTaskContext,
                       sids_by_pos: List[int], tainted: Set[int],
                       reuse_token: str) -> None:
        """Stage-level retry: re-key the previous attempt's completed
        map outputs under this attempt's fresh shuffle ids; drop the
        rest. Raises when this worker's record cannot satisfy the
        driver's request (driver falls back to whole-job retry)."""
        last = self._last_job
        if last is None or last.get("token") != reuse_token or \
                len(last.get("sids") or []) != len(sids_by_pos):
            raise RuntimeError(
                "stage-reuse state unavailable: worker job record "
                f"{(last or {}).get('token')!r} cannot satisfy retry of "
                f"job {reuse_token!r}")
        reusable_positions = set(msg.get("reusable_positions") or [])
        reused: Set[int] = set()
        for pos, new_sid in enumerate(sids_by_pos):
            old_sid = last["sids"][pos]
            if pos in reusable_positions and new_sid not in tainted:
                if self.manager.is_poisoned(old_sid):
                    # a corrupt block was quarantined from this
                    # shuffle: its map outputs are incomplete and must
                    # NOT be reused — fail the stage retry so the
                    # driver's whole-job fallback regenerates them
                    raise RuntimeError(
                        "stage-reuse state unavailable: shuffle "
                        f"{old_sid} quarantined after DataCorruption")
                self.manager.rename_shuffle(old_sid, new_sid)
                reused.add(new_sid)
                old_bounds = last["bounds"].get(old_sid)
                if old_bounds is not None:
                    cluster.prefill_bounds(new_sid, old_bounds)
            else:
                self.manager.unregister_shuffle(old_sid)
        cluster.reusable_sids = reused

    def close(self) -> None:
        self.server.close()


def worker_main(argv=None) -> None:  # pragma: no cover - subprocess body
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--driver", required=True)  # host:port
    args = ap.parse_args(argv)
    host, port = args.driver.rsplit(":", 1)
    w = ClusterWorker(host, int(port))
    try:
        w.run_forever()
    finally:
        w.close()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class ClusterDriver:
    """Coordinates registration, heartbeats, shuffle barriers, and job
    execution across workers."""

    def __init__(self, num_workers: int, host: str = "127.0.0.1",
                 barrier_timeout: float = 120.0,
                 heartbeat_interval: Optional[float] = None,
                 heartbeat_timeout: Optional[float] = None):
        from ..conf import (HEARTBEAT_INTERVAL_S, HEARTBEAT_TIMEOUT_S,
                            active_conf)
        from .shuffle_manager import (MapOutputRegistry,
                                      ShuffleHeartbeatManager)
        conf = active_conf()
        self.num_workers = num_workers
        self.barrier_timeout = barrier_timeout
        self.heartbeat_interval = (
            heartbeat_interval if heartbeat_interval is not None
            else conf.get(HEARTBEAT_INTERVAL_S))
        self.heartbeat_timeout = (
            heartbeat_timeout if heartbeat_timeout is not None
            else conf.get(HEARTBEAT_TIMEOUT_S))
        self._workers: List[Tuple[socket.socket, str, str]] = []
        #: serializes frames on the worker control sockets — a cancel
        #: broadcast from another thread must not interleave with the
        #: job dialogue's own sends mid-frame
        self._ctl_send_lock = threading.Lock()
        self._registered = threading.Event()
        self._barriers: Dict = {}
        self._gathers: Dict = {}
        #: speculation-aware barrier states (condition-based; used only
        #: when the job conf enables srt.sql.adaptive.speculation) —
        #: shuffle_id -> state dict, see _spec_state
        self._spec_barriers: Dict = {}
        #: (slowWorkerFactor, minWaitSec) parsed from the job conf
        self._spec_conf: Tuple[float, float] = (3.0, 1.0)
        #: per-worker-index unit keys (tuple of logical ids) the
        #: current attempt expects at every speculative barrier
        self._expected_units: Optional[List[Tuple[int, ...]]] = None
        #: executor ids in worker-index order for the current attempt
        self._worker_eids: List[str] = []
        self._block = threading.Lock()
        self._exec_seq = 0
        #: executor_id -> incarnation epoch (assigned at registration);
        #: epochs of evicted/decommissioned/superseded incarnations
        #: move to the fence set — their barrier/gather frames are
        #: refused, so a zombie can never commit or serve blocks
        self._epochs: Dict[str, int] = {}
        self._fenced_epochs: Set[int] = set()
        #: executor_id -> Event set when its decommission completes
        self._decommissioned: Dict[str, threading.Event] = {}
        #: per-attempt shuffle-id base shipped with every job: workers
        #: re-seed their local allocator from it, keeping shuffle ids
        #: identical across veterans and late (re)joiners
        self._sid_attempts = 0
        self._heartbeats = ShuffleHeartbeatManager(
            timeout_s=self.heartbeat_timeout)
        self._registry = MapOutputRegistry()
        #: per-failed-attempt assignment record for stage retries:
        #: executor_id -> logical ids it carried in the last attempt
        self._last_assign: Optional[Dict[str, List[int]]] = None
        self._last_shard_mod: Optional[int] = None
        #: what recovery did, in order — tests and operators read this
        #: ({"type": "stage_retry"|"job_retry"|"heartbeat_eviction", ...})
        self.recovery_events: List[dict] = []
        self._stop = threading.Event()
        self._server = socketserver.ThreadingTCPServer(
            (host, 0), self._make_handler(), bind_and_activate=True)
        self._server.daemon_threads = True
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         daemon=True)
        self._monitor.start()

    @property
    def address(self) -> Tuple[str, int]:
        return self._server.server_address

    def _make_handler(driver_self):
        driver = driver_self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                msg = _recv_msg(self.request)
                if not msg:
                    return
                t = msg.get("type")
                if t == "register":
                    prior = msg.get("prior_endpoint")
                    with driver._block:
                        eid = f"exec-{driver._exec_seq}"
                        epoch = driver._exec_seq + 1
                        driver._exec_seq += 1
                        driver._epochs[eid] = epoch
                        if prior:
                            # rejoin: fence the incarnation that last
                            # served this endpoint and drop its stale
                            # control socket from the worker list
                            old = driver._heartbeats.owner_of(prior)
                            if old is not None and old != eid:
                                driver._fenced_epochs.add(
                                    driver._epochs.get(old, -1))
                            driver._workers = [
                                w for w in driver._workers
                                if w[1] != prior]
                        driver._workers.append(
                            (self.request, msg["shuffle_endpoint"], eid))
                        driver._heartbeats.register(
                            eid, msg["shuffle_endpoint"],
                            prior_endpoint=prior)
                        ready = (len(driver._workers)
                                 >= driver.num_workers)
                    _send_msg(self.request,
                              {"type": "registered", "executor_id": eid,
                               "epoch": epoch,
                               "heartbeat_interval":
                                   driver.heartbeat_interval})
                    if ready:
                        driver._registered.set()
                    # keep the connection open: job dialogue reuses it
                    threading.Event().wait()  # parked; driver drives
                elif t == "barrier":
                    if driver._is_fenced(msg):
                        self._refuse_fenced(msg)
                        return
                    try:
                        # exact map-output sizes ride every barrier
                        # message: the registry's MapOutputStatistics
                        # is fed here regardless of speculation
                        if msg.get("detail"):
                            driver._registry.record_map_stats(
                                msg["shuffle_id"], msg["worker"],
                                msg["detail"])
                        if msg.get("speculation"):
                            reply = driver._barrier_speculative(msg)
                        else:
                            driver._barrier(msg["shuffle_id"],
                                            msg.get("pos", -1))
                            reply = {"type": "release"}
                    except threading.BrokenBarrierError:
                        # aborted by the failure monitor: answer with a
                        # clean error instead of an EOF'd connection
                        _send_msg(self.request,
                                  {"type": "error",
                                   "error": "barrier aborted"})
                        return
                    _send_msg(self.request, reply)
                elif t == "gather":
                    if driver._is_fenced(msg):
                        self._refuse_fenced(msg)
                        return
                    try:
                        payloads = driver._gather(msg["key"],
                                                  msg["worker"],
                                                  msg["payload"])
                    except threading.BrokenBarrierError:
                        _send_msg(self.request,
                                  {"type": "error",
                                   "error": "gather aborted"})
                        return
                    _send_msg(self.request, {"type": "gathered",
                                             "payloads": payloads})
                elif t == "heartbeat":
                    driver._heartbeats.heartbeat(msg["executor_id"],
                                                 msg.get("endpoint"))
                    _send_msg(self.request, {"type": "ok"})
                elif t == "resolve":
                    _send_msg(self.request,
                              {"type": "resolved",
                               "endpoint": driver._heartbeats.resolve(
                                   msg["endpoint"])})
                elif t == "decommission_request":
                    # the worker stops being schedulable NOW; it keeps
                    # heartbeating (and serving fetches) through the
                    # migration window that follows
                    eid = msg.get("executor_id")
                    with driver._block:
                        driver._workers = [w for w in driver._workers
                                           if w[2] != eid]
                        driver.num_workers = len(driver._workers)
                        peers = [ep for _s, ep, _e in driver._workers]
                    _send_msg(self.request,
                              {"type": "ok", "peers": peers})
                elif t == "decommission_done":
                    eid = msg.get("executor_id")
                    with driver._block:
                        driver._fenced_epochs.add(
                            driver._epochs.get(eid, -1))
                    driver._heartbeats.deregister(eid)
                    migrated = list(msg.get("migrated_sids") or ())
                    driver.recovery_events.append(
                        {"type": "decommission", "executor": eid,
                         "migrated_sids": migrated,
                         "target": msg.get("target")})
                    from ..obs import events as _events
                    _events.emit("WorkerDecommissioned", executor=eid,
                                 endpoint=msg.get("endpoint"),
                                 reason=msg.get("reason"),
                                 migrated_sids=migrated,
                                 target=msg.get("target"))
                    driver._decommissioned.setdefault(
                        eid, threading.Event()).set()
                    _send_msg(self.request, {"type": "ok"})

            def _refuse_fenced(self, msg) -> None:
                from ..obs import events as _events
                _events.emit("ZombieFenced", epoch=msg.get("epoch"),
                             mtype=msg.get("type"),
                             worker=msg.get("worker"))
                try:
                    _send_msg(self.request,
                              {"type": "fenced",
                               "error": "fenced: stale incarnation "
                                        "epoch"})
                except OSError:
                    pass
        return Handler

    def _is_fenced(self, msg) -> bool:
        """True when the frame carries a fenced incarnation epoch —
        checked BEFORE any registry write, so a zombie predecessor can
        neither commit map output nor join a sync point. Frames with no
        epoch (older workers) are treated as live."""
        ep = msg.get("epoch")
        return ep is not None and ep in self._fenced_epochs

    def _barrier(self, shuffle_id, pos: int = -1) -> None:
        with self._block:
            b = self._barriers.get(shuffle_id)
            if b is None:
                b = self._barriers[shuffle_id] = threading.Barrier(
                    self.num_workers)
        b.wait(timeout=self.barrier_timeout)
        # barrier released == every worker's map side wrote: record the
        # stage as complete for stage-level retries (by stable position)
        self._registry.mark_complete(pos, shuffle_id)

    # --- speculation-aware barrier (condition-based, early release) ---
    def _spec_state(self, shuffle_id: int) -> dict:
        with self._block:
            st = self._spec_barriers.get(shuffle_id)
            if st is None:
                st = self._spec_barriers[shuffle_id] = {
                    "cond": threading.Condition(),
                    "arrived": {},      # worker -> monotonic arrival t
                    "spec_ok": {},      # worker -> bool
                    "speculating": set(),  # workers given a directive
                    "assigned_units": {},  # unit -> speculator worker
                    "pos": -1,
                    "released": False,
                    "winners": None,
                    "aborted": False,
                }
            return st

    def _expected_unit_list(self) -> List[Tuple[int, ...]]:
        if self._expected_units:
            return list(self._expected_units)
        return [(w,) for w in range(self.num_workers)]

    def _barrier_speculative(self, msg) -> dict:
        """Condition-based replacement for the all-or-nothing barrier,
        used when the job conf enables speculation. Every arrival
        commits its unit's map ids first-result-wins; release happens
        as soon as every expected unit has a committed producer — which
        may be BEFORE a straggler arrives, because a waiting worker can
        be handed a ``speculate`` directive to re-run the straggler's
        shard. The release reply carries the winners verdict that
        filters all reads."""
        sid = msg["shuffle_id"]
        w = msg["worker"]
        pos = msg.get("pos", -1)
        map_ids = list(msg.get("map_ids") or ())
        unit = tuple(msg.get("unit") or ())
        is_spec = bool(msg.get("spec_report"))
        st = self._spec_state(sid)
        cond = st["cond"]
        from ..obs import events as _events
        with cond:
            if st["aborted"]:
                raise threading.BrokenBarrierError()
            if pos >= 0:
                st["pos"] = pos
            if unit and not (is_spec and msg.get("spec_failed")):
                winner = self._registry.try_commit_maps(
                    sid, unit, w, map_ids)
                if is_spec:
                    _events.emit("SpeculativeTask", phase="result",
                                 shuffle_id=sid, unit=list(unit),
                                 speculator=w, won=winner[0] == w)
            if not is_spec:
                st["arrived"][w] = time.monotonic()
                st["spec_ok"][w] = bool(msg.get("spec_ok"))
            self._maybe_release_spec(sid, st)
            deadline = time.monotonic() + self.barrier_timeout
            while not st["released"]:
                if st["aborted"]:
                    raise threading.BrokenBarrierError()
                if not is_spec:
                    directive = self._maybe_speculate(sid, st, w)
                    if directive is not None:
                        return directive
                cond.wait(timeout=0.1)
                if time.monotonic() > deadline:
                    raise threading.BrokenBarrierError()
            winners = st["winners"]
        reply = {"type": "release"}
        if winners is not None:
            reply["winners"] = winners
        return reply

    def _maybe_release_spec(self, sid: int, st: dict) -> None:
        """cond held. Release once every expected unit committed a
        producer; build the winners verdict ({worker: map_ids}). A
        stage where any unit was won by a NON-owner is not marked
        reuse-complete: stage retry renames each worker's LOCAL blocks,
        and a suppressed straggler's store disagrees with the verdict."""
        if st["released"]:
            return
        committed = self._registry.committed_maps(sid)
        expected = self._expected_unit_list()
        if any(u not in committed for u in expected):
            return
        allowed: Dict[int, Tuple[int, ...]] = {
            wi: () for wi in range(self.num_workers)}
        suppressed = False
        for wi, u in enumerate(expected):
            ww, mids = committed[u]
            allowed[ww] = tuple(sorted(set(allowed[ww]) | set(mids)))
            if ww != wi:
                suppressed = True
        st["winners"] = {"allowed": allowed}
        st["released"] = True
        st["cond"].notify_all()
        if not suppressed:
            self._registry.mark_complete(st["pos"], sid)

    def _maybe_speculate(self, sid: int, st: dict,
                         w: int) -> Optional[dict]:
        """cond held; ``w`` is a non-spec arrival still waiting. Hand
        it a speculate directive when (a) it is the earliest-arrived
        eligible waiter, (b) some expected unit has neither arrived nor
        been assigned, (c) that unit's owner is heartbeat-ALIVE (a dead
        owner is the eviction monitor's job, not speculation's), and
        (d) the wait since the last arrival exceeds
        max(minWaitSec, slowWorkerFactor x arrival spread)."""
        if not st["spec_ok"].get(w) or w in st["speculating"]:
            return None
        candidates = [x for x in st["arrived"]
                      if st["spec_ok"].get(x)
                      and x not in st["speculating"]]
        if not candidates or w != min(
                candidates, key=lambda x: st["arrived"][x]):
            return None
        times = list(st["arrived"].values())
        factor, min_wait = self._spec_conf
        spread = (max(times) - min(times)) if len(times) > 1 else 0.0
        if time.monotonic() - max(times) <= max(min_wait,
                                                factor * spread):
            return None
        expected = self._expected_unit_list()
        for wi, unit in enumerate(expected):
            if wi in st["arrived"] or unit in st["assigned_units"]:
                continue
            eid = (self._worker_eids[wi]
                   if wi < len(self._worker_eids) else None)
            if eid is not None and not self._heartbeats.is_alive(eid):
                continue
            st["assigned_units"][unit] = w
            st["speculating"].add(w)
            from ..obs import events as _events
            _events.emit("SpeculativeTask", phase="launch",
                         shuffle_id=sid, unit=list(unit),
                         speculator=w, straggler=wi)
            return {"type": "speculate", "unit": list(unit)}
        return None

    def _gather(self, key, worker: int, payload) -> List:
        with self._block:
            g = self._gathers.get(key)
            if g is None:
                g = self._gathers[key] = {
                    "data": {},
                    "barrier": threading.Barrier(self.num_workers)}
        g["data"][worker] = payload
        g["barrier"].wait(timeout=self.barrier_timeout)
        return [g["data"].get(w) for w in range(self.num_workers)]

    def _abort_sync(self) -> None:
        """Break every waiting barrier/gather (failure path: blocked
        survivors must error out instead of waiting out the timeout)."""
        with self._block:
            barriers = list(self._barriers.values())
            gathers = list(self._gathers.values())
            spec_states = list(self._spec_barriers.values())
        for b in barriers:
            try:
                b.abort()
            except Exception:
                pass
        for g in gathers:
            try:
                g["barrier"].abort()
            except Exception:
                pass
        for st in spec_states:
            try:
                with st["cond"]:
                    st["aborted"] = True
                    st["cond"].notify_all()
            except Exception:
                pass

    def _monitor_loop(self) -> None:
        """Failure DETECTION: evict workers whose heartbeats went
        silent, break the barriers they would have joined, and shut
        their control sockets so the blocked job dialogue surfaces
        WorkerLost instead of waiting out the barrier timeout."""
        period = max(0.2, min(1.0, self.heartbeat_timeout / 4.0))
        while not self._stop.wait(period):
            try:
                dead = self._heartbeats.expire_dead()
            except Exception:
                continue
            if not dead:
                continue
            print(f"[driver] heartbeat loss: evicting {sorted(dead)}",
                  file=sys.stderr, flush=True)
            self.recovery_events.append({"type": "heartbeat_eviction",
                                         "executors": sorted(dead)})
            with self._block:
                for eid in dead:
                    # fence the evicted incarnation: if it was merely
                    # wedged (not dead) and wakes up, its frames must
                    # not corrupt the retry's registry state
                    self._fenced_epochs.add(self._epochs.get(eid, -1))
            from ..obs import events as _events
            _events.emit("WorkerEvicted", executors=sorted(dead))
            self._abort_sync()
            with self._block:
                targets = [s for s, _ep, eid in self._workers
                           if eid in set(dead)]
            for s in targets:
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

    def cancel_job(self, reason: str = "driver cancel") -> None:
        """Broadcast a cancel to every worker's control socket. Workers
        flip their in-flight job's cancel token (see _serve_job); a
        worker that already replied reads the frame as a stale no-op.
        Safe from any thread; best-effort per socket."""
        from ..obs import events as _events
        with self._block:
            targets = list(self._workers)
        _events.emit("ClusterCancelBroadcast", reason=reason,
                     num_workers=len(targets))
        for sock, _ep, _eid in targets:
            try:
                with self._ctl_send_lock:
                    _send_msg(sock, {"type": "cancel", "reason": reason})
            except OSError:
                pass

    def wait_for_workers(self, timeout: float = 60.0) -> None:
        if not self._registered.wait(timeout):
            raise TimeoutError(
                f"{len(self._workers)}/{self.num_workers} workers "
                "registered")

    def run(self, logical_plan, conf_settings: Optional[dict] = None,
            max_retries: int = 2) -> List[dict]:
        """Execute one plan across the cluster; returns merged rows in
        worker order (= partition order for sorted plans).

        Failure recovery (SURVEY §5 failure detection / shuffle retry),
        innermost first:
        1. transport-level: fetch retries with backoff, then endpoint
           failover through the heartbeat registry (transport.py);
        2. STAGE-level: on WorkerLost, shuffles whose barrier released
           keep their map outputs — survivors rename the blocks under
           the retry's fresh shuffle ids, the dead worker's logical
           shards re-execute on a survivor, everything downstream of
           the last completed exchange re-runs;
        3. whole-job: when no stage completed or a survivor lost its
           job record, reset everyone and re-run on the surviving set.
        Deterministic worker ERRORS do not retry — they reproduce."""
        self.wait_for_workers()
        # the driver process logs events too (workers configure
        # themselves from the same conf dict inside _run_job)
        from ..conf import SrtConf
        from ..obs import events as _events
        from ..obs import resource as _resource
        from ..obs.trace import maybe_tracer
        tracer = None
        try:
            dconf = SrtConf(dict(conf_settings or {}))
            _events.configure_from_conf(dconf)
            _resource.configure_from_conf(dconf)
            tracer = maybe_tracer(dconf)
        except Exception:
            pass  # an invalid test conf must not mask the real error
        job_token = os.urandom(8).hex()
        # the driver's job span roots the whole distributed trace; its
        # context ships with every job message so worker spans parent
        # under it across the process boundary
        job_span = (tracer.begin(f"job-{job_token}", kind="job",
                                 attrs={"job_token": job_token})
                    if tracer is not None else None)
        trace_ctx = (tracer.context(job_span)
                     if tracer is not None else None)
        try:
            last: Optional[BaseException] = None
            retry_spec: Optional[dict] = None
            rec_timer: Optional[RecoveryTimer] = None
            from ..robustness.admission import QueryInterrupted
            for attempt in range(max_retries + 1):
                try:
                    out = self._run_once(logical_plan, conf_settings,
                                         job_token, attempt, retry_spec,
                                         trace_ctx)
                    if rec_timer is not None:
                        # failure detection → first post-recovery
                        # result: the recovery span chaos legs budget
                        rec_timer.finish(job_token=job_token,
                                         attempt=attempt)
                    return out
                except QueryInterrupted:
                    # typed cancel/deadline — NOT a failure to retry:
                    # stop the rest of the fleet and drain the aborted
                    # dialogue so the next job starts in protocol sync
                    self.cancel_job("peer query interrupted")
                    self._recover()
                    raise
                except StageRetryFailed as e:
                    last = e
                    retry_spec = None
                    if rec_timer is None:
                        rec_timer = RecoveryTimer("job_retry")
                    self.recovery_events.append({"type": "job_retry",
                                                 "cause": str(e)})
                    _events.emit("RetryAttempt", scope="job",
                                 job_token=job_token, attempt=attempt,
                                 cause=str(e))
                    self._recover()
                except WorkerLost as e:
                    last = e
                    retry_spec = self._plan_stage_retry(job_token)
                    if rec_timer is None:
                        rec_timer = RecoveryTimer(
                            "stage_retry" if retry_spec is not None
                            else "job_retry")
                    if retry_spec is not None:
                        _events.emit("RetryAttempt", scope="stage",
                                     job_token=job_token, attempt=attempt,
                                     reused_positions=list(
                                         retry_spec["reusable_positions"]),
                                     cause=str(e))
                    else:
                        self.recovery_events.append({"type": "job_retry",
                                                     "cause": str(e)})
                        _events.emit("RetryAttempt", scope="job",
                                     job_token=job_token, attempt=attempt,
                                     cause=str(e))
                        self._recover()
                if not self._workers:
                    break
            raise RuntimeError(
                f"job failed after worker losses: {last}") from last
        finally:
            if tracer is not None:
                tracer.end(job_span)
                log_dir = _events.log_dir()
                if log_dir:
                    try:
                        tracer.write_chrome_trace(os.path.join(
                            log_dir,
                            f"trace-{tracer.trace_id}-driver-"
                            f"{os.getpid()}.json"))
                    except OSError:
                        pass

    def _run_once(self, logical_plan, conf_settings, job_token: str,
                  attempt: int, retry_spec: Optional[dict],
                  trace_ctx: Optional[dict] = None) -> List[dict]:
        import cloudpickle
        self._registry.start_attempt()
        with self._block:
            self._barriers.clear()
            self._gathers.clear()
            self._spec_barriers.clear()
            workers = list(self._workers)
        try:
            from ..conf import (ADAPTIVE_SPECULATION_FACTOR,
                                ADAPTIVE_SPECULATION_MIN_WAIT_S)
            from ..conf import SrtConf as _SC
            _c = _SC(dict(conf_settings or {}))
            self._spec_conf = (
                float(_c.get(ADAPTIVE_SPECULATION_FACTOR)),
                float(_c.get(ADAPTIVE_SPECULATION_MIN_WAIT_S)))
        except Exception:
            self._spec_conf = (3.0, 1.0)
        n = len(workers)
        self.num_workers = n
        peers = [ep for _s, ep, _e in workers]
        if retry_spec is not None:
            assign = retry_spec["assign"]
            fresh = retry_spec["fresh"]
            shard_mod = retry_spec["shard_mod"]
            reusable = list(retry_spec["reusable_positions"])
            reuse_token: Optional[str] = job_token
        else:
            assign = [[w] for w in range(n)]
            fresh = [list(a) for a in assign]
            shard_mod = n
            reusable = []
            reuse_token = None
        self._last_assign = {eid: list(a) for (_s, _ep, eid), a
                             in zip(workers, assign)}
        self._last_shard_mod = shard_mod
        # the speculative barrier names its per-worker units by the
        # attempt's logical-id assignment (a speculator re-runs a
        # straggler's WHOLE shard set: one worker's maps are one
        # inseparable unit, first full result wins)
        self._expected_units = [tuple(sorted(a)) for a in assign]
        self._worker_eids = [eid for (_s, _ep, eid) in workers]
        from ..obs import events as _events
        _events.emit("StageSubmitted", job_token=job_token,
                     attempt=attempt, num_workers=n, assign=assign,
                     reused_positions=reusable)
        blob = cloudpickle.dumps(logical_plan)
        # 4096 ids of headroom per attempt covers any plan's exchange
        # count plus AQE/speculative re-allocations within the job
        self._sid_attempts += 1
        sid_base = self._sid_attempts * 4096 + 1
        for w, (sock, _ep, _eid) in enumerate(workers):
            try:
                with self._ctl_send_lock:
                    _send_msg(sock, {"type": "job", "plan": blob,
                                     "epoch": self._epochs.get(_eid, 0),
                                     "sid_base": sid_base,
                                     "conf": dict(conf_settings or {}),
                                     "worker_id": w,
                                     "num_workers": n,
                                     "peers": peers,
                                     "job_token": job_token,
                                     "attempt": attempt,
                                     "logical_ids": assign[w],
                                     "fresh_ids": fresh[w],
                                     "assign": assign,
                                     "shard_mod": shard_mod,
                                     "map_id_base": attempt << 20,
                                     "reusable_positions": reusable,
                                     "reuse_token": reuse_token,
                                     "trace_ctx": trace_ctx})
            except OSError:
                raise WorkerLost(w)
        results: List[Optional[List[dict]]] = [None] * n
        #: per-worker {exec_id: {metric: value}} of the last successful
        #: job — AQE tests read skew/coalesce counters through this
        worker_metrics: List[dict] = [{} for _ in range(n)]
        # reply wait is cancel-aware: when the DRIVER thread runs under
        # a query token (session-driven runs), poll it between select
        # ticks — the first trip broadcasts cancel to every worker, then
        # we keep draining their (now typed-error) replies in order
        from ..robustness.admission import (DeadlineExceeded,
                                            QueryCancelled, current_query)
        qc = current_query()
        cancel_sent = False
        for w, (sock, _ep, _eid) in enumerate(workers):
            try:
                if qc is None:
                    reply = _recv_msg(sock)
                else:
                    while True:
                        if not cancel_sent and (qc.is_cancelled()
                                                or qc.expired()):
                            self.cancel_job(qc.cancel_reason
                                            or "deadline exceeded")
                            cancel_sent = True
                        readable, _w2, _x = select.select(
                            [sock], [], [], 0.25)
                        if readable:
                            reply = _recv_msg(sock)
                            break
            except OSError:
                reply = None
            if reply is None:
                raise WorkerLost(w)
            if reply["type"] == "error":
                err = reply["error"]
                if "QueryCancelled" in err or "DeadlineExceeded" in err:
                    # typed interrupt from the worker — NOT a worker
                    # loss, must NOT trigger stage/job retry (a rerun
                    # of a cancelled query is exactly what cancel
                    # forbids); surface the matching driver-side type
                    first = err.splitlines()[0] if err else err
                    cls = (DeadlineExceeded if "DeadlineExceeded" in err
                           else QueryCancelled)
                    raise cls(f"worker {w}: {first}")
                if "stage-reuse state unavailable" in err:
                    raise StageRetryFailed(w, err)
                if "barrier" in err or "gather" in err or \
                        "peer closed" in err or "refused" in err or \
                        "FetchFailed" in err or "DataCorruption" in err:
                    # collateral of a lost peer — or detected data
                    # corruption, which a rerun regenerates — not a
                    # plan error
                    raise WorkerLost(w)
                raise RuntimeError(
                    f"worker {w} failed:\n{err}")
            results[w] = reply["rows"]
            worker_metrics[w] = reply.get("metrics", {})
        # post-job cleanup: peers are done fetching once every worker
        # has returned, so drop all shuffle blocks now — without this a
        # long-lived worker accumulates every past job's map outputs
        # (only the failure path used to reset). Best-effort: the job
        # already succeeded, a worker dying here is the next run's
        # problem.
        for sock, _ep, _eid in workers:
            try:
                with self._ctl_send_lock:
                    _send_msg(sock, {"type": "reset"})
                _recv_msg(sock)  # reset_done (keeps protocol in sync)
            except OSError:
                pass
        self.last_metrics = worker_metrics
        out: List[dict] = []
        for rows in results:
            out.extend(rows or [])
        return out

    def _plan_stage_retry(self, job_token: str) -> Optional[dict]:
        """After WorkerLost: decide whether the next attempt can reuse
        completed stages. Probes every worker with ``prepare_retry``
        (which also drains the failed attempt's stale replies and
        prunes the dead), re-attaches dead logical ids to survivors
        keeping segments contiguous, and returns the retry spec — or
        None when nothing completed / no usable survivor record, in
        which case the caller falls back to whole-job recovery."""
        completed = self._registry.complete_positions()
        self._abort_sync()
        prev_assign = self._last_assign
        alive: List[Tuple[socket.socket, str, str]] = []
        reuse_refused = False
        for sock, ep, eid in self._workers:
            ok = False
            try:
                _send_msg(sock, {"type": "prepare_retry"})
                sock.settimeout(self.barrier_timeout * 2 + 60)
                try:
                    for _ in range(32):
                        reply = _recv_msg(sock)
                        if reply is None:
                            break
                        # a worker that refused the FAILED attempt's
                        # reuse request (quarantined/poisoned shuffle)
                        # may have its refusal sitting in the stale
                        # backlog while the driver classified a peer's
                        # collateral barrier error first — honor it
                        # here, or the driver would re-plan the same
                        # doomed stage retry until attempts run out
                        if reply.get("type") == "error" and \
                                "stage-reuse state unavailable" in \
                                reply.get("error", ""):
                            reuse_refused = True
                        if reply.get("type") == "retry_ready":
                            ok = reply.get("token") == job_token
                            break
                finally:
                    sock.settimeout(None)
            except OSError:
                ok = False
            if ok:
                alive.append((sock, ep, eid))
        self._fence_pruned(alive)
        if not alive:
            self._workers = []
            self.num_workers = 0
            return None
        self._workers = alive
        self.num_workers = len(alive)
        if reuse_refused:
            print("[driver] stage retry unusable: a worker refused map-"
                  "output reuse (quarantined shuffle); falling back to "
                  "whole-job retry", file=sys.stderr, flush=True)
            return None
        if not completed or not prev_assign or \
                any(eid not in prev_assign for _s, _ep, eid in alive):
            return None
        alive_eids = {eid for _s, _ep, eid in alive}
        dead_lids = sorted(l for eid, lids in prev_assign.items()
                           if eid not in alive_eids for l in lids)
        new_assign = {eid: sorted(prev_assign[eid])
                      for _s, _ep, eid in alive}
        for lid in dead_lids:
            # attach to the LAST survivor whose segment starts below the
            # dead id (else the first): all ids between adjacent
            # survivor segments are dead, so this keeps every survivor's
            # logical ids one contiguous ascending run — which is what
            # preserves global partition order on concat
            best = None
            for _s, _ep, eid in alive:
                if min(new_assign[eid]) < lid:
                    best = eid
            if best is None:
                best = alive[0][2]
            new_assign[best].append(lid)
            new_assign[best].sort()
        assign = [list(new_assign[eid]) for _s, _ep, eid in alive]
        fresh = [sorted(set(new_assign[eid]) - set(prev_assign[eid]))
                 for _s, _ep, eid in alive]
        spec = {"assign": assign, "fresh": fresh,
                "shard_mod": self._last_shard_mod,
                "reusable_positions": list(completed)}
        self.recovery_events.append(
            {"type": "stage_retry", "reused_positions": list(completed),
             "assign": assign, "fresh": fresh})
        print(f"[driver] stage-level retry: reusing map outputs at plan "
              f"positions {list(completed)}; re-executing logical shards "
              f"{sorted(dead_lids)} on {len(alive)} surviving workers",
              file=sys.stderr, flush=True)
        return spec

    def _recover(self) -> None:
        """Whole-job fallback: prune dead workers, unblock stuck
        barriers, reset survivors (drops ALL shuffle state)."""
        self._abort_sync()
        with self._block:
            self._barriers.clear()
            self._gathers.clear()
        alive = []
        for sock, ep, eid in self._workers:
            try:
                with self._ctl_send_lock:
                    _send_msg(sock, {"type": "reset"})
                # drain stale replies of the aborted attempt (a worker
                # stuck at a now-aborted barrier first reports its job
                # error, THEN processes the reset); budget covers a full
                # worker-side barrier timeout plus slack
                sock.settimeout(self.barrier_timeout * 2 + 60)
                try:
                    for _ in range(32):
                        reply = _recv_msg(sock)
                        if reply is None:
                            break
                        if reply.get("type") == "reset_done":
                            alive.append((sock, ep, eid))
                            break
                finally:
                    sock.settimeout(None)
            except OSError:
                pass
        self._fence_pruned(alive)
        self._workers = alive
        self.num_workers = len(alive)

    def _fence_pruned(self, alive: List[Tuple[socket.socket, str, str]]
                      ) -> None:
        """Fence every worker about to be dropped from the roster: a
        pruned-but-breathing process (hung, paused, partitioned) must
        not commit into the attempt that replaces it."""
        alive_eids = {eid for _s, _ep, eid in alive}
        pruned = []
        with self._block:
            for _s, _ep, eid in self._workers:
                if eid not in alive_eids:
                    self._fenced_epochs.add(self._epochs.get(eid, -1))
                    pruned.append(eid)
        if pruned:
            # socket-close detection beats the heartbeat monitor when
            # the death happens mid-dialogue; the eviction is just as
            # real, so it gets the same event
            from ..obs import events as _events
            _events.emit("WorkerEvicted", executors=sorted(pruned),
                         detection="socket")

    def decommission(self, executor_id: Optional[str] = None,
                     timeout: float = 60.0) -> bool:
        """Ask one worker (default: the last-registered) to gracefully
        decommission: it finishes any in-flight job, migrates its hot
        shuffle blocks to a live peer, deregisters, and exits. Returns
        True once the worker's ``decommission_done`` lands."""
        with self._block:
            targets = list(self._workers)
        if executor_id is not None:
            targets = [t for t in targets if t[2] == executor_id]
        if not targets:
            return False
        sock, _ep, eid = targets[-1]
        ev = self._decommissioned.setdefault(eid, threading.Event())
        try:
            with self._ctl_send_lock:
                _send_msg(sock, {"type": "decommission",
                                 "reason": "driver request"})
        except OSError:
            return False
        return ev.wait(timeout)

    def wait_for_n_workers(self, n: int, timeout: float = 60.0) -> None:
        """Block until ``n`` workers are registered — the rejoin/elastic
        counterpart of ``wait_for_workers`` (which waits for the
        roster's ORIGINAL size)."""
        deadline = time.monotonic() + timeout
        while True:
            with self._block:
                if len(self._workers) >= n:
                    self.num_workers = len(self._workers)
                    return
                have = len(self._workers)
            if time.monotonic() > deadline:
                raise TimeoutError(f"{have}/{n} workers registered")
            time.sleep(0.05)

    def shutdown(self) -> None:
        self._stop.set()
        for sock, _ep, _eid in self._workers:
            try:
                _send_msg(sock, {"type": "shutdown"})
            except OSError:
                pass
        self._server.shutdown()
        self._server.server_close()


def launch_local_workers(driver: ClusterDriver, n: int,
                         env: Optional[dict] = None
                         ) -> List[subprocess.Popen]:
    """Spawn n worker processes on this host (the test/SURVEY §4
    topology; production workers run the same module on their hosts)."""
    host, port = driver.address
    procs = []
    base_env = dict(os.environ)
    # local test workers always run the CPU backend: the one real TPU
    # chip cannot be shared by N processes (override via env for real
    # per-host-accelerator deployments)
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env.update(env or {})
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    base_env["PYTHONPATH"] = root + os.pathsep + \
        base_env.get("PYTHONPATH", "")
    import tempfile
    for i in range(n):
        # NEVER leave workers on an undrained PIPE: XLA's per-compile
        # cache warnings are large, and a full 64K pipe blocks the
        # worker mid-write (a deadlock that worsens as the compile
        # cache grows). Logs go to files for post-mortem instead.
        log_path = os.path.join(tempfile.gettempdir(),
                                f"srt_worker_{os.getpid()}_{i}.log")
        # append: elastic clusters launch replacements from the same
        # driver pid, and truncating would destroy the incumbent's log
        # (it still holds the old fd, so both would interleave into a
        # truncated file)
        log_f = open(log_path, "ab")
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "spark_rapids_tpu.parallel.cluster",
             "--driver", f"{host}:{port}"],
            env=base_env, stdout=log_f, stderr=subprocess.STDOUT))
        log_f.close()
    return procs


if __name__ == "__main__":  # pragma: no cover
    worker_main()
