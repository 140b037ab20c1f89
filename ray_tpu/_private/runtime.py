"""Driver-side runtime: ownership, object directory, task routing, actors.

This is the CoreWorker-equivalent for the driver process (reference:
src/ray/core_worker/core_worker.h:167) plus the pieces of the reference's
TaskManager / ReferenceCounter / ActorTaskSubmitter that round-1 centralizes
in the driver:

  * ObjectDirectory — per-object state + waiters (reference: memory store
    futures, store_provider/memory_store/).
  * submission routing — normal tasks go through the cluster scheduler
    (dependency stage + placement, reference: normal_task_submitter.h:86);
    actor tasks are sequenced per-actor and pushed to the actor's dedicated
    worker (reference: actor_task_submitter.h:68 SequentialActorSubmitQueue).
  * failure handling — task retries on worker crash (reference:
    task_manager.h:248 ResubmitTask), actor restart FSM driven off worker
    death (reference: gcs_actor_manager.h:94).
"""

from __future__ import annotations

import os
import socket
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import sanitizer
from . import serialization
from . import wire as _wire
from .config import Config
from .events import (FAILED, FINISHED, PENDING_ARGS, PLACED, READY, RUNNING,
                     SUBMITTED_TO_NODE, ProfileSpan, TaskEventBuffer)
from .controller import (ALIVE, DEAD, PENDING_CREATION, PG_PENDING,
                         PG_REMOVED, RESTARTING, ActorInfo, Controller,
                         JobInfo, NodeInfo, PlacementGroupInfo)
from .exceptions import (ActorError, GetTimeoutError, ObjectLostError,
                         OutOfMemoryError, TaskError, WorkerCrashedError)
from .ids import (ActorID, JobID, NodeID, ObjectID, PlacementGroupID, TaskID,
                  WorkerID)
from .node import NodeManager
from .object_store import RemoteObjectReader
from ..storeview import events as _store_events
from .protocol import (ActorStateMsg, GetReply, GetRequest, PutFromWorker,
                       RpcCall, RpcReply, TaskDone, TaskSpec, WaitReply,
                       WaitRequest)
from .resources import CPU, TPU, ResourceSet
from .scheduler import ClusterScheduler
from ..util import telemetry

_runtime_lock = threading.Lock()
_global_runtime: Optional["Runtime"] = None
_worker_runtime = None  # set in worker processes


def set_worker_runtime(rt) -> None:
    global _worker_runtime
    _worker_runtime = rt


def current_runtime():
    """The active runtime facade: WorkerRuntime inside workers, else driver."""
    if _worker_runtime is not None:
        return _worker_runtime
    return _global_runtime


def driver_runtime() -> Optional["Runtime"]:
    return _global_runtime


class ObjectState:
    """One object-directory entry.  The direct-call fast path creates
    tens of thousands per second, so construction must be
    allocation-light: the real threading.Event (whose Condition is the
    single most expensive allocation on the submit path) is created
    lazily, only when a consumer blocks before the result lands.
    ``ready`` is a plain bool flipped under the class-wide lock; readers
    may peek it unlocked (GIL write-once visibility — the same guarantee
    Event.is_set() gave).  The shared lock is fine: every critical
    section is O(1) and tiny."""

    __slots__ = ("ready", "desc", "callbacks", "_evt")
    _lock = threading.Lock()

    def __init__(self):
        self.ready = False
        self.desc = None
        self.callbacks: Optional[List[Callable[[], None]]] = None
        self._evt: Optional[threading.Event] = None

    def mark_ready(self, desc) -> None:
        with ObjectState._lock:
            if self.ready:
                return
            self.desc = desc
            self.ready = True
            evt = self._evt
            cbs, self.callbacks = self.callbacks, None
        if evt is not None:
            evt.set()
        for cb in cbs or ():
            cb()

    def reset(self) -> None:
        """Back to pending (object lost; reconstruction in flight) so
        consumers block until the re-executed task delivers."""
        with ObjectState._lock:
            self.desc = None
            self.ready = False
            if self._evt is not None:
                self._evt.clear()

    def wait(self, timeout: Optional[float] = None) -> bool:
        # Safe bare read: double-checked fast path — ready only flips
        # under the class lock, and we re-check under it below.
        if self.ready:  # ray-tpu: noqa[RT401]
            return True
        with ObjectState._lock:
            if self.ready:
                return True
            evt = self._evt
            if evt is None:
                evt = self._evt = threading.Event()
        return evt.wait(timeout)

    def add_callback(self, cb: Callable[[], None]) -> None:
        with ObjectState._lock:
            if not self.ready:
                if self.callbacks is None:
                    self.callbacks = []
                self.callbacks.append(cb)
                return
        cb()

    def discard_callback(self, cb: Callable[[], None]) -> None:
        with ObjectState._lock:
            if self.callbacks:
                try:
                    self.callbacks.remove(cb)
                except ValueError:
                    pass


def _has_remote_desc(args, kwargs) -> bool:
    return any(isinstance(d, tuple) and d and d[0] == "at"
               for d in list(args) + list(kwargs.values()))


class _DepsPending(Exception):
    """A dependency's descriptor vanished (object lost; reconstruction in
    flight) between scheduling and dispatch."""

    def __init__(self, oids):
        self.oids = oids
        super().__init__(f"{len(oids)} dependencies back to pending")


@dataclass
class _RunningTask:
    spec: TaskSpec
    node_id: NodeID
    worker_id: Optional[WorkerID] = None


@dataclass
class _ActorRuntimeState:
    worker_id: Optional[WorkerID] = None
    node_id: Optional[NodeID] = None
    next_seq: int = 0          # next sequence number to assign
    next_dispatch: int = 0     # next sequence number eligible to dispatch
    ready_buffer: Dict[int, Tuple[TaskSpec, list, dict]] = field(default_factory=dict)
    pending_bind: List[Tuple[TaskSpec, list, dict]] = field(default_factory=list)
    lock: threading.RLock = field(default_factory=threading.RLock)
    # Direct-call listener of the actor's worker (direct.py); set on the
    # worker's "alive" report, cleared on worker death.
    direct_addr: Optional[Tuple[str, int]] = None
    # Driver->actor direct channel (cluster mode).  driver_mode flips to
    # "direct" (sticky) the first time a fast-path call finds the actor
    # quiescent — no queued/unbound calls AND no classic dispatches still
    # in flight — so a channel frame can never overtake a classic one.
    driver_mode: Optional[str] = None
    driver_ch: Any = None
    classic_inflight: set = field(default_factory=set)


class _DriverChannelOwner:
    """DirectChannel owner shim for the driver Runtime: actor resolution
    goes straight to the controller; channel replies land in the driver's
    object directory (local_ready -> mark_ready).  Non-inline results
    arrive upstream as a normal TaskDone from the actor's node — which
    registers and marks them ready — so the channel's "upstream" signal
    is a no-op here."""

    def __init__(self, rt):
        self.rt = rt
        self.direct_token = rt.node.direct_token

    def control(self, method: str, *args):
        return getattr(self.rt, "ctl_" + method)(*args)

    def local_ready(self, oid_bytes: bytes, desc) -> None:
        if desc and desc[0] == "upstream":
            return
        self.rt.mark_ready(ObjectID(oid_bytes), desc)


class Runtime:
    """Driver-process runtime (controller + scheduler + local node plane)."""

    def __init__(self, num_cpus: Optional[float] = None,
                 num_tpus: Optional[int] = None,
                 resources: Optional[Dict[str, float]] = None,
                 namespace: str = "default",
                 head_port: Optional[int] = None,
                 cluster_token: Optional[bytes] = None,
                 advertise_host: Optional[str] = None,
                 state_dir: Optional[str] = None):
        Config.initialize()
        self.controller = Controller()
        self.state_store = None
        if state_dir:
            # Head fault tolerance: replay persisted controller tables
            # before anything registers (reference: gcs_server.cc loading
            # GcsInitData on boot), then attach the WAL for new mutations.
            from .persist import StateStore
            store = StateStore(state_dir,
                               fsync=bool(Config.get("head_wal_fsync")))
            self.controller.restore(store.load())
            self.controller.persist = store
            store.on_compact = lambda: store.compact(
                self.controller.snapshot_records())
            self.state_store = store
            # Job counter must advance past replayed jobs or the new
            # driver job collides with a restored one.
            import struct as _struct
            with JobID._lock:
                for j in self.controller.jobs:
                    (val,) = _struct.unpack("<I", j.binary())
                    JobID._counter = max(JobID._counter, val)
        self.job_id = JobID.next()
        self.namespace = namespace
        self.driver_task_id = TaskID.for_driver(self.job_id)
        self.controller.register_job(JobInfo(self.job_id))

        if num_tpus is None:
            from ..accelerators.tpu import TPUAcceleratorManager
            num_tpus = TPUAcceleratorManager.detect_num_chips()
        node_resources: Dict[str, float] = {
            CPU: float(num_cpus if num_cpus is not None else (os.cpu_count() or 1)),
            "memory": float(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))
            if hasattr(os, "sysconf") else 64e9,
        }
        if num_tpus:
            node_resources[TPU] = float(num_tpus)
            from ..accelerators.tpu import TPUAcceleratorManager
            marker = TPUAcceleratorManager.slice_head_resource_name()
            if marker:
                node_resources[marker] = 1.0
        # Other registered accelerator plugins advertise their chips too
        # (reference: the per-vendor manager loop in
        # _private/accelerators/__init__.py).
        from ..accelerators.accelerator import all_accelerators
        for mgr in all_accelerators():
            if mgr.resource_name in node_resources:
                continue
            try:
                n = mgr.detect_num_chips()
            except Exception:
                n = 0
            if n:
                node_resources[mgr.resource_name] = float(n)
        if resources:
            node_resources.update(resources)

        self.node_id = NodeID.from_random()
        node_info = NodeInfo(self.node_id, socket.gethostname(),
                             ResourceSet(node_resources), is_head=True)
        self.controller.register_node(node_info)

        self.directory: Dict[ObjectID, ObjectState] = {}
        self._dir_lock = threading.RLock()
        self._mapped_segments: Dict[ObjectID, Any] = {}
        # Arena objects pinned on behalf of driver-held zero-copy views;
        # released at free() (plasma client-pin semantics).
        self._arena_pins: set = set()

        # -- ownership / GC (reference: reference_counter.h:44) ----------- #
        # Driver-process ObjectRef counts; objects with zero refs, zero
        # in-flight dependent tasks and no escaped (pickled-away) copies
        # are freed from the directory + store.
        self._gc_enabled = bool(Config.get("enable_object_gc"))
        self._ref_lock = threading.Lock()
        # Zero-copy view tracking: materialized values alias shm/arena
        # memory, so a GC-triggered free must wait for the views to die
        # (plasma buffer-retention semantics).  Values that can't carry a
        # weakref keep their object pinned for the session (leak-safe).
        self._view_counts: Dict[ObjectID, int] = {}
        self._view_immortal: set = set()
        self._pending_free: set = set()
        # __del__ may fire at arbitrary GC points (possibly while this very
        # process holds _ref_lock), so ref drops are queued lock-free and
        # drained by a dedicated thread (reference: the Cython ObjectRef
        # dealloc defers to the io service for the same reason).
        import queue as _q
        self._ref_drop_q: Any = _q.SimpleQueue()
        if self._gc_enabled:
            sanitizer.spawn(self._ref_drop_loop, name="ref-gc")
        self._local_refs: Dict[ObjectID, int] = {}
        self._escaped: set = set()
        self._dropped: set = set()
        self._dep_counts: Dict[ObjectID, int] = {}
        self._deps_retained: Dict[TaskID, List[ObjectID]] = {}
        # outer object -> ObjectIDs serialized inside its value: the
        # inner objects are retained (via _dep_counts) for exactly the
        # outer's lifetime (reference: reference_counter.h:44 nested-ref
        # containment).
        self._contained: Dict[ObjectID, List[ObjectID]] = {}

        # -- lineage + reconstruction (reference: task_manager.h:248
        # ResubmitTask, object_recovery_manager.h:41) ---------------------- #
        from collections import OrderedDict
        self._lineage: "OrderedDict[TaskID, TaskSpec]" = OrderedDict()
        self._lineage_lock = threading.Lock()
        self._lineage_cap = int(Config.get("lineage_max_entries"))
        self._recovering: Dict[TaskID, threading.Event] = {}
        self._recover_attempts: Dict[TaskID, int] = {}

        # Session directory + worker-log tailing + export events
        # (reference: /tmp/ray/session_* with log_monitor.py:116 and
        # RayEventRecorder export events).  Created before the NodeManager
        # so the first spawned worker already redirects into it.
        from .log_monitor import (ExportEventWriter, LogMonitor,
                                  create_session_dir)
        self.session_dir = create_session_dir()
        self.session_logs_dir = os.path.join(self.session_dir, "logs")
        self.log_monitor = LogMonitor(self.session_logs_dir)
        self.log_monitor.start()
        self.export_events = ExportEventWriter(
            os.path.join(self.session_logs_dir, "events.jsonl"))
        self.controller.event_sink = self.export_events.write

        self.scheduler = ClusterScheduler(self.controller, self._object_ready)
        self.scheduler.on_dispatch_error = self._fail_task
        self.scheduler.try_pipeline = self._try_pipeline
        # Tasks queued ahead on a busy worker (pipelined submission):
        # they hold no resource booking, so TaskDone skips release.
        self._pipelined: set = set()
        # Per-node credit accounting for REMOTE pipelining (reference: the
        # C++ submitter's per-lease in-flight cap,
        # normal_task_submitter.cc:516): at most _pipeline_cap(node)
        # lease-less tasks ride ahead to each remote node; a credit
        # returns on TaskDone/failure/UpPipelineReject.
        self._pipeline_credits: Dict[NodeID, int] = {}
        self._pipelined_node: Dict[TaskID, NodeID] = {}
        self._pipeline_lock = threading.Lock()
        # node_id -> monotonic deadline: a node that just rejected a
        # pipelined dispatch is skipped until the deadline, so a full
        # pool doesn't ping-pong tasks head<->node (localizing args each
        # round trip) while nothing has changed.
        self._pipeline_cooldown: Dict[NodeID, float] = {}
        self.node = NodeManager(node_info, self, num_tpu_chips=int(num_tpus or 0))
        self.scheduler.add_node(node_info)
        self.nodes: Dict[NodeID, NodeManager] = {self.node_id: self.node}

        self._running: Dict[TaskID, _RunningTask] = {}
        self._running_lock = threading.Lock()
        # fn_id -> pickled function (reference: GCS function table).
        self._fn_table: Dict[bytes, bytes] = {}
        # Syncer receiver state: node -> (version, view, recv_time).
        self._node_views: Dict[NodeID, tuple] = {}
        self._node_views_lock = threading.Lock()
        self._actors: Dict[ActorID, _ActorRuntimeState] = {}
        self._actors_lock = threading.Lock()
        # Direct actor calls in flight (fast path, see submit_actor_direct):
        # task_id bytes -> (actor_id, return_ids, call_name).  These tasks
        # bypass the running table / events / scheduler entirely.
        self._direct_lock = threading.Lock()
        self._direct_inflight: Dict[
            bytes, Tuple[ActorID, List[ObjectID], str]] = {}
        self._put_index = 0
        self._put_lock = threading.Lock()
        self._shutdown = False

        self.events = TaskEventBuffer(
            Config.get("task_events_max_num_task_in_gcs"))
        # Control-plane telescope: the scheduler folds READY/PLACED
        # lifecycle stamps into the TaskEvent ring (stage-wait
        # histograms derive from the per-transition monotonic stamps).
        self.scheduler.on_stage = self.events.record
        # worker_id hex -> latest user-metrics snapshot pushed from that
        # process (see ray_tpu.util.metrics).
        self.metrics_snapshots: Dict[str, list] = {}
        # Metrics time-series backplane: bounded history + windowed
        # queries + SLO burn-rate alerts, fed from the metrics_push
        # verb (no reporting loop of its own; see ray_tpu.metricsview).
        from ray_tpu.metricsview import MetricsView
        self.metricsview = MetricsView(event_sink=self._export_event)

        # -- live diagnostics (reference: `ray stack` + the debug-state
        # dump; see diagnostics.py) ------------------------------------- #
        # dump_id -> {"replies": {worker_hex: record}, "event", "want"}
        self._stack_lock = threading.Lock()
        self._stack_dump_seq = 0
        self._stack_dumps: Dict[int, Dict[str, Any]] = {}
        # profile_id -> same collection-entry shape as _stack_dumps
        # (cluster profiler shares the stack-capture fan-out/settle
        # machinery; see ctl_profile).
        self._profile_seq = 0
        self._profiles: Dict[int, Dict[str, Any]] = {}
        # One release per worker that answered the shutdown-time request
        # for its buffered spans and final metrics.
        self._flush_acks = threading.Semaphore(0)
        # Rate limiter for the worker-death flight recorder.
        # None = no bundle written yet (0.0 would suppress the first
        # bundle on a freshly booted host: monotonic ~= uptime).
        self._last_death_bundle: Optional[float] = None

        # -- multi-node cluster plane (reference: gcs_node_manager.h node
        # registration + object_manager pull/push; see cluster.py) -------- #
        self.head_server = None
        self.data_server = None
        self._data_client = None
        self._puller = None
        self._xfer_q = None
        if head_port is not None:
            import queue as _queue

            from .cluster import (DataClient, DataServer, HeadServer,
                                  ObjectPuller)
            # No silent well-known default: the control port unpickles peer
            # messages, so an unauthenticated join would be code execution.
            token = cluster_token or os.urandom(16)
            self.cluster_token = token
            # Direct channels must work across nodes: all workers in the
            # cluster share the cluster token and bind on routable hosts.
            self.node.direct_token = token
            self.node.direct_host = advertise_host or os.environ.get(
                "RAY_TPU_ADVERTISE_HOST", "127.0.0.1")
            advertise = advertise_host or os.environ.get(
                "RAY_TPU_ADVERTISE_HOST", "127.0.0.1")
            self.data_server = DataServer(self.node.store, token,
                                          advertise_host=advertise)
            self._data_client = DataClient(token)
            self.head_server = HeadServer(self, port=head_port, token=token,
                                          advertise_host=advertise)
            self._puller = ObjectPuller(
                self.node.store, self._data_client, self.node_id.binary(),
                self.head_server.node_data_address)
            # Cross-node pulls block; never run them on the scheduler loop
            # or a node reader thread (see _offload).  Ordered work (actor
            # dispatch) gets its own queue thread; everything else shares a
            # pool so one stalled peer can't freeze the data plane.
            from concurrent.futures import ThreadPoolExecutor
            self._xfer_q = _queue.Queue()
            self._xfer_pool = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="head-xfer")
            sanitizer.spawn(self._xfer_loop, name="head-xfer-ordered")

        if self.state_store is not None:
            self._revive_persisted_state()

    def _revive_persisted_state(self) -> None:
        """After a head restart: re-plan replayed placement groups on the
        fresh node set and restart replayed actors from their creation
        specs (their workers died with the old head; restarting does NOT
        consume the user's restart budget — reference: GCS failover
        reconstructing actors from GcsInitData)."""
        for pg in list(self.controller.placement_groups.values()):
            if pg.state == PG_REMOVED:
                continue
            for b in pg.bundles:
                b.node_id = None
            pg.state = PG_PENDING
            self.scheduler.create_placement_group(pg)
        for info in list(self.controller.actors.values()):
            if info.state == DEAD or info.creation_spec is None:
                continue
            with self._actors_lock:
                self._actors[info.actor_id] = _ActorRuntimeState()
            self.controller.set_actor_state(info.actor_id, RESTARTING)
            self._submit_actor_creation(
                self._restart_creation_spec(info.actor_id,
                                            info.creation_spec))

    @staticmethod
    def _restart_creation_spec(actor_id: ActorID, spec: TaskSpec) -> TaskSpec:
        """Fresh creation TaskSpec for restarting an actor from its
        original creation spec (new task id; returns already delivered)."""
        return TaskSpec(
            task_id=TaskID.of(actor_id), name=spec.name,
            fn_blob=spec.fn_blob, method_name=None,
            arg_descs=spec.arg_descs, kwarg_descs=spec.kwarg_descs,
            return_ids=[], resources=spec.resources,
            create_actor_id=actor_id, max_retries=0,
            placement_group=spec.placement_group,
            bundle_index=spec.bundle_index,
            scheduling_strategy=spec.scheduling_strategy,
            runtime_env=spec.runtime_env,
            max_concurrency=spec.max_concurrency)

    # ------------------------------------------------------------------ #
    # object directory
    # ------------------------------------------------------------------ #

    def _state(self, object_id: ObjectID) -> ObjectState:
        with self._dir_lock:
            st = self.directory.get(object_id)
            if st is None:
                st = ObjectState()
                self.directory[object_id] = st
            return st

    def _object_ready(self, object_id: ObjectID) -> bool:
        with self._dir_lock:
            st = self.directory.get(object_id)
        return st is not None and st.ready

    def mark_ready(self, object_id: ObjectID, desc) -> None:
        self._state(object_id).mark_ready(desc)
        self.scheduler.notify_object_ready(object_id)
        if self._gc_enabled:
            # The ref was dropped while the producing task was in flight:
            # collect the result now that it has landed.  (The lock is
            # required for the check: an unlocked emptiness pre-check races
            # with the drop path's insert — drop reads event-unset, we set
            # it and see _dropped still empty, drop inserts -> leak.)
            with self._ref_lock:
                collect = object_id in self._dropped and \
                    self._collectable_locked(object_id)
                if collect:
                    self._dropped.discard(object_id)
            if collect:
                self.free([object_id])

    def _materialize(self, object_id: ObjectID, desc) -> Any:
        if desc[0] == "at":
            # Remote-node object: pull it into the head's local store first
            # (owner lookup + transfer, reference: pull_manager.h:50).
            if self._puller is None:
                raise ObjectLostError(
                    f"object {object_id} lives on a remote node but this "
                    "runtime has no cluster data plane")
            desc = self._puller.localize(desc)
        kind = desc[0]
        if kind == "inline":
            return serialization.unpack_payload(desc[1])
        if kind == "shm":
            shm = self._mapped_segments.get(object_id)
            if shm is None:
                try:
                    value, shm = RemoteObjectReader.read(desc[1], desc[2])
                    # The mapping read bypasses the store, so the lifecycle
                    # ring would count this object as never-read (and flag
                    # it as a leak candidate).  Record the read here; the
                    # restore fallback below goes through get_buffer, which
                    # records it itself.
                    ring = getattr(self.node.store, "view", None)
                    if ring is not None and _store_events.enabled():
                        ring.push(_store_events.E_GET,
                                  object_id.binary(), desc[2])
                except FileNotFoundError:
                    # The local store spilled this object: its segment
                    # was unlinked when the payload moved to disk.  A
                    # store read restores the segment under the same
                    # name, after which the mapping works again.
                    try:
                        buf, _keep = self.node.store.get_buffer(object_id)
                    except (KeyError, ValueError) as e:
                        raise ObjectLostError(
                            f"object {object_id} segment is gone and the "
                            f"local store cannot restore it: {e}",
                            object_id_bytes=object_id.binary()) from None
                    buf.release()
                    value, shm = RemoteObjectReader.read(desc[1], desc[2])
                self._mapped_segments[object_id] = shm
            else:
                value = serialization.read_payload_from(shm.buf[: desc[2]])
            self._track_view(object_id, value)
            return value
        if kind == "shma":
            # Pin once per driver-held object so the arena offset stays valid
            # for any zero-copy views the caller retains; released at free().
            pin = object_id not in self._arena_pins
            value = self.node.store.read_by_key(desc[4], pin=pin)
            if value is None:
                raise ObjectLostError(
                    f"object {object_id} was evicted or freed",
                    object_id_bytes=object_id.binary())
            if pin:
                self._arena_pins.add(object_id)
            self._track_view(object_id, value)
            return value
        if kind == "err":
            raise serialization.unpack_payload(desc[1])
        raise ValueError(f"bad descriptor {desc!r}")

    # ------------------------------------------------------------------ #
    # public API surface (driver side)
    # ------------------------------------------------------------------ #

    def put(self, value: Any) -> ObjectID:
        with self._put_lock:
            self._put_index += 1
            idx = (1 << 20) + self._put_index
        object_id = ObjectID.of(self.driver_task_id, idx)
        # Refs inside the value become containment-retained (released
        # when this object frees), not escaped-forever pins.
        from .api import _nested_collector
        inner: list = []
        token = _nested_collector.set(inner)
        try:
            meta, buffers = serialization.serialize_payload(value)
        finally:
            _nested_collector.reset(token)
        if inner:
            self.note_contained(object_id, inner)
        nbytes = serialization.payload_nbytes(meta, buffers)
        if nbytes <= Config.get("max_inline_object_size"):
            buf = bytearray(nbytes)
            serialization.write_payload_into(memoryview(buf), meta, buffers)
            self.mark_ready(object_id, ("inline", bytes(buf)))
        else:
            self.node.store.put_serialized(object_id, meta, buffers)
            self.mark_ready(object_id, self.node.store.descriptor(object_id))
        return object_id

    def _states(self, object_ids: List[ObjectID]) -> List[ObjectState]:
        """Bulk _state(): one directory-lock round for the whole list."""
        with self._dir_lock:
            directory = self.directory
            states = []
            for o in object_ids:
                st = directory.get(o)
                if st is None:
                    st = ObjectState()
                    directory[o] = st
                states.append(st)
            return states

    def get(self, object_ids: List[ObjectID],
            timeout: Optional[float] = None) -> List[Any]:
        deadline = None if timeout is None else time.monotonic() + timeout
        states = self._states(object_ids)
        for st in states:
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                raise GetTimeoutError("get timed out")
            if not st.wait(remaining):
                raise GetTimeoutError("get timed out")
        values = []
        max_attempts = int(Config.get("object_reconstruction_max_attempts"))
        for o, st in zip(object_ids, states):
            last: Optional[BaseException] = None
            for _attempt in range(max_attempts + 1):
                try:
                    values.append(self._materialize(o, st.desc))
                    last = None
                    break
                except ObjectLostError as e:
                    # Lost from the cluster: try lineage re-execution
                    # (reference: object_recovery_manager.h:92).
                    last = e
                    if self._recover_object(o) is None:
                        raise
                    remaining = None if deadline is None else \
                        deadline - time.monotonic()
                    if not st.wait(remaining):
                        raise GetTimeoutError(
                            "get timed out during object reconstruction")
            if last is not None:
                raise last
        return values

    def wait(self, object_ids: List[ObjectID], num_returns: int,
             timeout: Optional[float], fetch_local: bool = True):
        """Event-driven wait: readiness callbacks signal a condition — no
        poll loop (the round-1 1ms spin showed up directly in the wait_1k
        microbenchmark; reference: WaitManager wait_manager.h)."""
        if num_returns > len(object_ids):
            raise ValueError(
                f"num_returns={num_returns} exceeds the {len(object_ids)} "
                "refs passed to wait()")
        deadline = None if timeout is None else time.monotonic() + timeout
        cond = threading.Condition()
        states = self._states(object_ids)
        # Count already-ready objects up front and register callbacks only
        # on pending ones; the callback wakes the waiter ONCE, when the
        # count crosses num_returns — a 1k-ref wait must not pay 1k
        # wakeups (reference: WaitManager's single completion signal).
        pending_states = [st for st in states if not st.ready]
        n_ready = [len(states) - len(pending_states)]

        def on_ready():
            with cond:
                n_ready[0] += 1
                if n_ready[0] >= num_returns:
                    cond.notify()

        if n_ready[0] < num_returns:
            for st in pending_states:
                st.add_callback(on_ready)
            try:
                with cond:
                    while n_ready[0] < num_returns:
                        remaining = None if deadline is None else \
                            deadline - time.monotonic()
                        if remaining is not None and remaining <= 0:
                            break
                        cond.wait(remaining)
            finally:
                # Unregister from still-pending states: polling wait()
                # loops must not accumulate dead closures on never-ready
                # objects.
                for st in pending_states:
                    st.discard_callback(on_ready)
        ready = [o for o, st in zip(object_ids, states) if st.ready]
        ready = ready[:max(num_returns, 0)] if len(ready) > num_returns \
            else ready
        ready_set = set(ready)
        pending = [o for o in object_ids if o not in ready_set]
        return ready, pending

    def _track_view(self, oid: ObjectID, value: Any) -> None:
        """The returned value aliases shared memory: freeing the object
        must wait for the value's death (or never happen if the value
        can't carry a weakref)."""
        if not self._gc_enabled:
            # Without GC there is no deferred-free machinery (no drain
            # thread): frees behave exactly as before view tracking.
            return
        import weakref
        with self._ref_lock:
            if oid in self._view_immortal:
                return
            try:
                weakref.finalize(value, self._on_view_dead, oid)
            except TypeError:
                self._view_immortal.add(oid)
                self._pending_free.discard(oid)
                return
            self._view_counts[oid] = self._view_counts.get(oid, 0) + 1

    def _on_view_dead(self, oid: ObjectID) -> None:
        # weakref.finalize callback: may fire at arbitrary GC points
        # (possibly with _ref_lock held on this thread) — lock-free
        # enqueue only, like ObjectRef.__del__.
        if self._gc_enabled and not self._shutdown:
            self._ref_drop_q.put(("view", oid))

    def _view_dead(self, oid: ObjectID) -> None:
        with self._ref_lock:
            n = self._view_counts.get(oid, 0) - 1
            if n > 0:
                self._view_counts[oid] = n
                return
            self._view_counts.pop(oid, None)
            run_free = oid in self._pending_free
            self._pending_free.discard(oid)
        if run_free:
            self.free([oid])

    def free(self, object_ids: List[ObjectID]) -> None:
        # Objects with live zero-copy views defer their free to view death.
        deferred = []
        with self._ref_lock:
            for oid in object_ids:
                if self._view_counts.get(oid, 0) > 0 or \
                        oid in self._view_immortal:
                    if oid not in self._view_immortal:
                        self._pending_free.add(oid)
                    deferred.append(oid)
        if deferred:
            object_ids = [o for o in object_ids if o not in set(deferred)]
        contained_freed: List[ObjectID] = []
        for oid in object_ids:
            with self._ref_lock:
                self._local_refs.pop(oid, None)
                self._escaped.discard(oid)
                self._dropped.discard(oid)
            # Refs serialized inside this object's value lose their
            # container: release the retention (frees cascade below).
            contained_freed.extend(self._release_contained(oid))
            with self._dir_lock:
                st = self.directory.pop(oid, None)
            if st is not None and st.desc and st.desc[0] == "at":
                # Remote-owned object: route the delete to the owner node.
                proxy = self.nodes.get(NodeID(st.desc[1]))
                if proxy is not None and getattr(proxy, "is_remote", False):
                    from .cluster import FreeObject
                    proxy.send(FreeObject(st.desc[2]))
                # A pulled copy may be cached in the head store too.
                try:
                    self.node.store.delete(oid)
                except Exception as e:
                    telemetry.note_swallowed("runtime.free_object", e)
            shm = self._mapped_segments.pop(oid, None)
            if shm is not None:
                try:
                    shm.close()
                except Exception as e:
                    telemetry.note_swallowed("runtime.free_object", e)
            if st is not None and st.desc and st.desc[0] == "shma":
                if oid in self._arena_pins:
                    self._arena_pins.discard(oid)
                    self.node.store.unpin_key(st.desc[4])
                try:
                    self.node.store.delete(oid)
                except KeyError:
                    pass
                continue
            if st is not None and st.desc and st.desc[0] == "shm":
                try:
                    self.node.store.delete(oid)
                except KeyError:
                    from .object_store import _open_untracked
                    try:
                        seg = _open_untracked(st.desc[1], create=False)
                        seg.close()
                        seg.unlink()
                    except FileNotFoundError:
                        pass
        if contained_freed:
            self.free(contained_freed)

    # ------------------------------------------------------------------ #
    # ownership GC (reference: reference_counter.h local refs + borrows)
    # ------------------------------------------------------------------ #

    def add_local_ref(self, oid: ObjectID) -> None:
        if not self._gc_enabled:
            return
        with self._ref_lock:
            self._local_refs[oid] = self._local_refs.get(oid, 0) + 1

    def enqueue_ref_drop(self, oid: ObjectID) -> None:
        """GC-safe entry point for ObjectRef.__del__ (lock-free put)."""
        if self._gc_enabled and not self._shutdown:
            self._ref_drop_q.put(("drop", oid))

    def _ref_drop_loop(self) -> None:
        import queue as _q
        while True:
            item = self._ref_drop_q.get()
            if item is None or self._shutdown:
                return
            # Batch everything already queued: one _ref_lock acquisition
            # per batch instead of per dropped ref (a 1000-ref get()
            # releases 1000 refs nearly at once).
            batch = [item]
            while len(batch) < 512:
                try:
                    batch.append(self._ref_drop_q.get_nowait())
                except _q.Empty:
                    break
            done = False
            drops: List[ObjectID] = []
            for it in batch:
                if it is None:
                    done = True
                elif it[0] == "drop":
                    drops.append(it[1])
                else:
                    try:
                        self._view_dead(it[1])
                    except Exception as e:
                        telemetry.note_swallowed("runtime.ref_gc", e)
            if drops:
                try:
                    self._apply_ref_drops(drops)
                except Exception as e:
                    telemetry.note_swallowed("runtime.ref_gc", e)
            if done or self._shutdown:
                return

    def _apply_ref_drops(self, oids: List[ObjectID]) -> None:
        """Batched remove_local_ref: same semantics, one lock round."""
        to_free: List[ObjectID] = []
        with self._ref_lock:
            for oid in oids:
                n = self._local_refs.get(oid, 0) - 1
                if n > 0:
                    self._local_refs[oid] = n
                    continue
                self._local_refs.pop(oid, None)
                if not self._collectable_locked(oid):
                    continue
                with self._dir_lock:
                    st = self.directory.get(oid)
                if st is not None and not st.ready:
                    self._dropped.add(oid)
                else:
                    to_free.append(oid)
        if to_free:
            self.free(to_free)

    def remove_local_ref(self, oid: ObjectID) -> None:
        if not self._gc_enabled or self._shutdown:
            return
        free = False
        with self._ref_lock:
            n = self._local_refs.get(oid, 0) - 1
            if n > 0:
                self._local_refs[oid] = n
            else:
                self._local_refs.pop(oid, None)
                if self._collectable_locked(oid):
                    with self._dir_lock:
                        st = self.directory.get(oid)
                    if st is not None and not st.ready:
                        # Producing task still in flight: collect at
                        # mark_ready instead.
                        self._dropped.add(oid)
                    else:
                        free = True
        if free:
            self.free([oid])

    def mark_escaped(self, oid: ObjectID) -> None:
        """An ObjectRef was pickled into user data: copies may now live
        anywhere (borrowed, reference: reference_counter borrows), so the
        object is never auto-collected."""
        if self._gc_enabled:
            with self._ref_lock:
                self._escaped.add(oid)

    def note_contained(self, outer: ObjectID,
                       inner: List[ObjectID]) -> None:
        """``inner`` refs were serialized inside ``outer``'s value: retain
        them for the outer object's lifetime (released by free(outer)),
        NOT forever (reference: reference_counter.h:44 containment)."""
        if not self._gc_enabled or not inner:
            return
        with self._ref_lock:
            self._contained.setdefault(outer, []).extend(inner)
            for oid in inner:
                self._dep_counts[oid] = self._dep_counts.get(oid, 0) + 1

    def _release_contained(self, outer: ObjectID) -> List[ObjectID]:
        """Drop the outer->inner retention; returns inner objects that
        became collectable (caller frees them outside the lock).  A
        still-pending inner (producer in flight) defers to the _dropped
        set like _apply_ref_drops does — freeing now would let the late
        mark_ready resurrect a zero-reference directory entry and pin
        its payload forever."""
        to_free: List[ObjectID] = []
        with self._ref_lock:
            inner = self._contained.pop(outer, None)
            for oid in inner or ():
                n = self._dep_counts.get(oid, 0) - 1
                if n > 0:
                    self._dep_counts[oid] = n
                    continue
                self._dep_counts.pop(oid, None)
                if not self._collectable_locked(oid):
                    continue
                with self._dir_lock:
                    st = self.directory.get(oid)
                if st is not None and not st.ready:
                    self._dropped.add(oid)
                else:
                    self._dropped.discard(oid)
                    to_free.append(oid)
        return to_free

    def _collectable_locked(self, oid: ObjectID) -> bool:
        return (oid not in self._escaped
                and self._local_refs.get(oid, 0) == 0
                and self._dep_counts.get(oid, 0) == 0)

    def _retain_deps(self, spec: TaskSpec) -> None:
        if not self._gc_enabled:
            return
        deps = [a[1] for a in spec.arg_descs if a[0] == "ref"]
        deps += [d[1] for d in spec.kwarg_descs.values() if d[0] == "ref"]
        # Nested refs (pickled inside arg values) are borrows: retained
        # for the task's lifetime like positional ref args; the worker
        # escalates to escaped via BorrowRetained if it keeps them
        # (reference: reference_counter.h:44).
        deps += list(getattr(spec, "nested_refs", ()) or ())
        if not deps:
            return
        with self._ref_lock:
            if spec.task_id in self._deps_retained:
                return  # already retained (idempotent across resubmits)
            self._deps_retained[spec.task_id] = deps
            for d in deps:
                self._dep_counts[d] = self._dep_counts.get(d, 0) + 1

    def _release_deps(self, task_id: TaskID) -> None:
        if not self._gc_enabled:
            return
        to_free: List[ObjectID] = []
        with self._ref_lock:
            deps = self._deps_retained.pop(task_id, None)
            for d in deps or ():
                n = self._dep_counts.get(d, 0) - 1
                if n > 0:
                    self._dep_counts[d] = n
                else:
                    self._dep_counts.pop(d, None)
                    if self._collectable_locked(d):
                        to_free.append(d)
        if to_free:
            self.free(to_free)

    # ------------------------------------------------------------------ #
    # lineage + reconstruction
    # ------------------------------------------------------------------ #

    def _record_lineage(self, spec: TaskSpec) -> None:
        # Only stateless task outputs are reconstructable by re-execution
        # (actor method results depend on actor state; reference semantics).
        # Streaming tasks are excluded: partial streams can't re-execute
        # idempotently (matches the reference's streaming-generator caveat).
        if spec.actor_id is not None or spec.create_actor_id is not None \
                or not spec.return_ids or getattr(spec, "streaming", False):
            return
        with self._lineage_lock:
            self._lineage[spec.task_id] = spec
            self._lineage.move_to_end(spec.task_id)
            while len(self._lineage) > self._lineage_cap:
                self._lineage.popitem(last=False)

    def _recover_object(self, oid: ObjectID) -> Optional[threading.Event]:
        """Kick lineage re-execution of the task that produced ``oid``.
        Returns an event set when recovery delivers (None if the object is
        not reconstructable)."""
        task_id = oid.task_id()
        with self._lineage_lock:
            spec = self._lineage.get(task_id)
            if spec is None:
                return None
            attempts = self._recover_attempts.get(task_id, 0)
            if attempts >= int(Config.get(
                    "object_reconstruction_max_attempts")):
                return None
            inflight = self._recovering.get(task_id)
            if inflight is not None:
                return inflight
            self._recover_attempts[task_id] = attempts + 1
            done = threading.Event()
            self._recovering[task_id] = done
        # Drop stale driver-side state for the lost returns so the
        # re-produced values land cleanly.  Healthy sibling returns that the
        # driver still holds zero-copy views into (multi-return tasks) are
        # left untouched: deleting their arena slot would corrupt live user
        # arrays, and mark_ready no-ops on their still-set states.
        for rid in spec.return_ids:
            if rid != oid and rid in self._arena_pins:
                continue
            shm = self._mapped_segments.pop(rid, None)
            if shm is not None:
                try:
                    shm.close()
                except Exception as e:
                    telemetry.note_swallowed("runtime.reconstruct_cleanup", e)
            if rid in self._arena_pins:
                self._arena_pins.discard(rid)
                try:
                    self.node.store.unpin_key(rid.binary())
                except Exception as e:
                    telemetry.note_swallowed("runtime.reconstruct_cleanup", e)
            try:
                self.node.store.delete(rid)
            except Exception as e:
                telemetry.note_swallowed("runtime.reconstruct_cleanup", e)
            self._state(rid).reset()
        with self._ref_lock:
            self._escaped.add(oid)  # recovered objects stay pinned
        # Recursively rebuild dependencies that are gone (GC'd after their
        # refs dropped, or lost and never re-produced): a resubmitted task
        # parks in the dependency stage, so unready deps must have their
        # own recovery kicked here or it waits forever.  An unrecoverable
        # dep (no lineage — e.g. a freed ray.put — or attempts exhausted)
        # fails the whole recovery NOW: waiters get ObjectLostError instead
        # of hanging on a task that can never run.
        deps = [a[1] for a in spec.arg_descs if a[0] == "ref"]
        deps += [d[1] for d in spec.kwarg_descs.values() if d[0] == "ref"]
        for dep in deps:
            with self._dir_lock:
                st = self.directory.get(dep)
            if st is None or not st.ready:
                if self._recover_object(dep) is None:
                    err = ("err", serialization.pack_payload(ObjectLostError(
                        f"object {oid} is unrecoverable: its input {dep} "
                        "has no lineage (freed put or evicted spec)",
                        object_id_bytes=oid.binary())))
                    for rid in spec.return_ids:
                        self._state(rid).mark_ready(err)
                    self._finish_recovery(task_id)
                    return None
        self.events.record(task_id.hex(), PENDING_ARGS, name=spec.name,
                           error_message="lineage reconstruction")
        self.submit_spec(spec)
        return done

    def _finish_recovery(self, task_id: TaskID) -> None:
        with self._lineage_lock:
            done = self._recovering.pop(task_id, None)
        if done is not None:
            done.set()

    def _lost_object_in_error(self, error_desc) -> Optional[ObjectID]:
        """If a task failed because an input object was lost, name it."""
        if not error_desc or error_desc[0] != "err":
            return None
        try:
            exc = serialization.unpack_payload(error_desc[1])
        except Exception:
            return None
        inner = getattr(exc, "cause", exc)
        oid_bytes = getattr(inner, "object_id_bytes", None)
        if isinstance(inner, ObjectLostError) and oid_bytes:
            try:
                return ObjectID(oid_bytes)
            except ValueError:
                return None
        return None

    # ------------------------------------------------------------------ #
    # task submission
    # ------------------------------------------------------------------ #

    def submit_spec(self, spec: TaskSpec) -> None:
        if spec.fn_id is not None and spec.fn_blob is not None and \
                spec.fn_id not in self._fn_table:
            # Function table (reference: GCS function_manager): workers
            # fetch by id when a stripped spec misses their local cache.
            self._fn_table[spec.fn_id] = spec.fn_blob
        if self._gc_enabled:
            # Pre-create return states so a ref dropped while the task is
            # in flight is distinguishable from a never-existed object:
            # remove_local_ref defers those frees to mark_ready via
            # _dropped, which needs the pending state to exist.
            for oid in spec.return_ids:
                self._state(oid)
        self._retain_deps(spec)
        self._record_lineage(spec)
        if spec.actor_id is not None:
            self.events.record(
                spec.task_id.hex(), PENDING_ARGS, name=spec.name,
                task_type="ACTOR_TASK", actor_id=spec.actor_id.hex())
            self._submit_actor_task(spec)
        elif spec.create_actor_id is not None:
            self.events.record(
                spec.task_id.hex(), PENDING_ARGS, name=spec.name,
                task_type="ACTOR_CREATION_TASK",
                actor_id=spec.create_actor_id.hex())
            self._submit_actor_creation(spec)
        else:
            self.events.record(spec.task_id.hex(), PENDING_ARGS,
                               name=spec.name)
            self.scheduler.submit(spec, self._dispatch_normal)

    def _resolve(self, spec: TaskSpec):
        """Resolve ref args to descriptors; raises _DepsPending if any dep
        went back to pending (lost + reconstruction in flight) between the
        scheduler's readiness check and now."""
        pending: List[ObjectID] = []

        def desc_of(oid):
            st = self._state(oid)
            d = st.desc
            if d is None:
                pending.append(oid)
            return d

        args = []
        for kind, payload in spec.arg_descs:
            if kind == "ref":
                args.append(desc_of(payload))
            else:
                args.append(("inline", payload))
        kwargs = {}
        for k, (kind, payload) in spec.kwarg_descs.items():
            if kind == "ref":
                kwargs[k] = desc_of(payload)
            else:
                kwargs[k] = ("inline", payload)
        if pending:
            raise _DepsPending(pending)
        return args, kwargs

    def _after_deps(self, oids: List[ObjectID], fn: Callable[[], None]) -> None:
        """Run fn once every oid is (re-)ready."""
        remaining = {"n": len(oids)}
        lock = threading.Lock()

        def one_ready():
            with lock:
                remaining["n"] -= 1
                done = remaining["n"] == 0
            if done:
                fn()

        for oid in oids:
            self._state(oid).add_callback(one_ready)

    def _xfer_loop(self) -> None:
        while True:
            fn = self._xfer_q.get()
            if fn is None:
                return
            try:
                fn()
            except Exception:
                import traceback
                traceback.print_exc()

    def _offload(self, fn, ordered: bool = False) -> None:
        """Run `fn` off the caller's thread in cluster mode (it may block on
        cross-node object pulls), inline otherwise.  ``ordered`` work shares
        one queue thread (per-actor dispatch ordering); the rest runs on a
        small pool."""
        if self._xfer_q is None:
            fn()
        elif ordered:
            self._xfer_q.put(fn)
        else:
            self._xfer_pool.submit(self._safely, fn)

    @staticmethod
    def _safely(fn) -> None:
        try:
            fn()
        except Exception:
            import traceback
            traceback.print_exc()

    def _requeue_or_fail(self, spec: TaskSpec, reason: str) -> None:
        if spec.actor_id is None and spec.create_actor_id is None and \
                spec.retry_count < spec.max_retries:
            spec.retry_count += 1
            self.submit_spec(spec)
        elif spec.create_actor_id is not None:
            # Creation never completed; re-place it (no restart consumed).
            self._submit_actor_creation(spec)
        elif spec.actor_id is not None:
            self._fail_task(spec, ActorError(spec.actor_id, reason))
        else:
            self._fail_task(spec, WorkerCrashedError(reason))

    def _pipeline_topup(self, budget: int = 2) -> None:
        """Move up to ``budget`` queued tasks into worker pipeline slots
        (bounded so one TaskDone never monopolizes the poller thread)."""
        for _ in range(budget):
            nxt = self.scheduler.take_pipelineable()
            if nxt is None:
                return
            if not self._try_pipeline(nxt.spec):
                # No pipeline room: route back through normal (booked)
                # submission.
                self.scheduler.submit(nxt.spec, nxt.dispatch)
                return

    def _pipeline_cap(self, node_id: NodeID) -> int:
        """In-flight pipelined-task cap for a remote node: ~2 queued-ahead
        tasks per pooled worker (reference: the per-worker in-flight cap of
        the C++ submitter's pipelining)."""
        info = self.controller.nodes.get(node_id)
        cpus = info.total_resources.get("CPU") if info is not None else 1.0
        return max(2, min(32, int(2 * (cpus or 1.0))))

    def _try_pipeline(self, spec: TaskSpec) -> bool:
        """Scheduler callback when the cluster is full: queue the task
        ahead on a busy worker (no booking) to hide the done->dispatch
        round trip.  Local workers take it synchronously; remote nodes
        take it under per-node credit accounting, answering
        UpPipelineReject when their pools have no queue room."""
        if len(self.nodes) == 1 and self._puller is None \
                and not self.node.has_pipeline_room():
            # Cheap precheck: a full pool means resolve/queue/requeue
            # below is guaranteed wasted work (the topup loop runs on
            # every TaskDone).
            return False
        try:
            args, kwargs = self._resolve(spec)
        except _DepsPending:
            return False
        if len(self.nodes) == 1 and self._puller is None:
            with self._running_lock:
                self._running[spec.task_id] = _RunningTask(spec,
                                                           self.node_id)
            self._pipelined.add(spec.task_id)
            if self.node.dispatch_pipelined(spec, args, kwargs):
                self.events.record(spec.task_id.hex(), SUBMITTED_TO_NODE,
                                   node_id=self.node_id.hex())
                return True
            self._pipelined.discard(spec.task_id)
            with self._running_lock:
                self._running.pop(spec.task_id, None)
            return False
        # Cluster: pick the remote node with the most spare credit (the
        # local node is excluded — its dispatches ride the ordered
        # transfer queue, where queue-ahead wins nothing).  Credit
        # mutations happen under _pipeline_lock: submit threads and the
        # completion (poller) thread race here, and a lost decrement
        # would leak credits until pipelining silently turned off.
        now = time.monotonic()
        with self._pipeline_lock:
            best, best_spare = None, 0
            for nid, node in self.nodes.items():
                if not getattr(node, "is_remote", False):
                    continue
                if self._pipeline_cooldown.get(nid, 0.0) > now:
                    continue
                spare = self._pipeline_cap(nid) - \
                    self._pipeline_credits.get(nid, 0)
                if spare > best_spare:
                    best, best_spare = nid, spare
            if best is None:
                return False
            node = self.nodes.get(best)
            if node is None:
                return False
            self._pipelined_node[spec.task_id] = best
            self._pipeline_credits[best] = \
                self._pipeline_credits.get(best, 0) + 1
        with self._running_lock:
            self._running[spec.task_id] = _RunningTask(spec, best)
        self._pipelined.add(spec.task_id)
        node.dispatch_task(spec, args, kwargs, pipelined=True)
        self.events.record(spec.task_id.hex(), SUBMITTED_TO_NODE,
                           node_id=best.hex())
        return True

    def _return_pipeline_credit(self, task_id: TaskID) -> None:
        with self._pipeline_lock:
            nid = self._pipelined_node.pop(task_id, None)
            if nid is not None and nid in self._pipeline_credits:
                self._pipeline_credits[nid] = max(
                    0, self._pipeline_credits[nid] - 1)

    def on_pipeline_reject(self, spec: TaskSpec, node_id: NodeID) -> None:
        """A remote node had no pipeline room: return the credit, put the
        node on a short pipelining cooldown (otherwise the empty-queue
        fast path would bounce the task straight back, re-localizing its
        args each round trip), and run the task through normal (booked)
        scheduling."""
        with self._running_lock:
            self._running.pop(spec.task_id, None)
        self._pipelined.discard(spec.task_id)
        self._return_pipeline_credit(spec.task_id)
        with self._pipeline_lock:
            self._pipeline_cooldown[node_id] = time.monotonic() + 0.5
        self.scheduler.submit(spec, self._dispatch_normal)

    def _dispatch_normal(self, spec: TaskSpec, node_id: NodeID) -> None:
        try:
            args, kwargs = self._resolve(spec)
        except _DepsPending:
            # A dep went back to pending (reconstruction): give back the
            # booked resources and let the dependency stage re-hold it.
            if not spec.resources.is_empty() or spec.placement_group is not None:
                self.scheduler.release(node_id, spec.resources,
                                       spec.placement_group,
                                       spec.bundle_index)
            self.scheduler.submit(spec, self._dispatch_normal)
            return
        node = self.nodes.get(node_id)
        if node is None:
            # Node died between placement and dispatch.
            self._requeue_or_fail(spec, f"node {node_id} died before "
                                  f"dispatch of {spec.name}")
            return
        if not getattr(node, "is_remote", False) and self._puller is not None \
                and _has_remote_desc(args, kwargs):
            # Local dispatch with remote args: pull them home on the
            # transfer thread — pulls must not block the scheduler loop.
            self._track(spec, node_id)

            def run():
                a, k = self._puller.localize_all(args, kwargs)
                node.dispatch_task(spec, a, k)
            self._offload(run)
            return
        self._track(spec, node_id)
        node.dispatch_task(spec, args, kwargs)

    # -- actors ---------------------------------------------------------- #

    def register_actor(self, info: ActorInfo) -> None:
        self.controller.register_actor(info)
        with self._actors_lock:
            self._actors[info.actor_id] = _ActorRuntimeState()

    def _submit_actor_creation(self, spec: TaskSpec) -> None:
        self.controller.set_actor_state(spec.create_actor_id, PENDING_CREATION)
        self.scheduler.submit(spec, self._dispatch_normal)

    def _actor_state(self, actor_id: ActorID) -> _ActorRuntimeState:
        # Lock-free read first: dict.get is GIL-atomic and entries are
        # never replaced once inserted, so the hot path (one lookup per
        # direct call) skips the lock.
        st = self._actors.get(actor_id)  # ray-tpu: noqa[RT401]
        if st is not None:
            return st
        with self._actors_lock:
            st = self._actors.get(actor_id)
            if st is None:
                st = _ActorRuntimeState()
                self._actors[actor_id] = st
            return st

    def _submit_actor_task(self, spec: TaskSpec) -> None:
        ast = self._actor_state(spec.actor_id)
        info = self.controller.get_actor(spec.actor_id)
        if info is not None and info.state == DEAD:
            self._fail_task(spec, ActorError(spec.actor_id, info.death_cause))
            return
        with ast.lock:
            seq = ast.next_seq
            ast.next_seq += 1
        deps = [a[1] for a in spec.arg_descs if a[0] == "ref"]
        deps += [d[1] for d in spec.kwarg_descs.values() if d[0] == "ref"]
        if not deps:
            # Fast path: no ref args — resolution is a pure re-tag of the
            # inline payloads, nothing can go back to pending.
            self._enqueue_actor_dispatch(
                ast, spec, seq,
                [("inline", p) for _k, p in spec.arg_descs],
                {k: ("inline", p) for k, (_kind, p)
                 in spec.kwarg_descs.items()})
            return
        unresolved = [d for d in deps if not self._object_ready(d)]

        def on_deps_ready():
            try:
                args, kwargs = self._resolve(spec)
            except _DepsPending as dp:
                # Dep reset under us (lost + reconstructing): wait again.
                self._after_deps(dp.oids, on_deps_ready)
                return
            self._enqueue_actor_dispatch(ast, spec, seq, args, kwargs)

        if not unresolved:
            on_deps_ready()
        else:
            self._after_deps(list(unresolved), on_deps_ready)

    def _enqueue_actor_dispatch(self, ast: _ActorRuntimeState, spec: TaskSpec,
                                seq: int, args, kwargs) -> None:
        """Strict per-actor ordering: dispatch seq k only after k-1
        (reference: sequential_actor_submit_queue.h)."""
        to_send = []
        with ast.lock:
            ast.ready_buffer[seq] = (spec, args, kwargs)
            while ast.next_dispatch in ast.ready_buffer:
                item = ast.ready_buffer.pop(ast.next_dispatch)
                ast.next_dispatch += 1
                to_send.append(item)
        for item in to_send:
            self._dispatch_to_actor_worker(ast, *item)

    def _dispatch_to_actor_worker(self, ast: _ActorRuntimeState,
                                  spec: TaskSpec, args, kwargs) -> None:
        with ast.lock:
            if ast.worker_id is None:
                ast.pending_bind.append((spec, args, kwargs))
                return
            node_id, worker_id = ast.node_id, ast.worker_id
            # Classic dispatches in flight block the driver channel from
            # activating (frames on two transports must never reorder).
            ast.classic_inflight.add(spec.task_id)
        node = self.nodes.get(node_id)
        if node is None:
            self._fail_task(spec, ActorError(
                spec.actor_id, "actor's node left the cluster"))
            return
        if not getattr(node, "is_remote", False) and self._xfer_q is not None:
            # All local actor dispatches ride the transfer queue in cluster
            # mode: localization may block, and a faster no-pull task must
            # not overtake an earlier pulling one (per-actor ordering).
            self._track(spec, node_id)

            def run():
                a, k = self._puller.localize_all(args, kwargs)
                node.dispatch_task(spec, a, k, target_worker=worker_id)
            self._offload(run, ordered=True)
            return
        if getattr(node, "is_remote", False):
            self._track(spec, node_id)
            node.dispatch_task(spec, args, kwargs, target_worker=worker_id)
        else:
            # Local fast path: insert into running without the
            # SUBMITTED_TO_WORKER event — dispatch_actor_task records
            # RUNNING immediately after anyway.
            with self._running_lock:
                self._running[spec.task_id] = _RunningTask(spec, node_id)
            node.dispatch_actor_task(spec, args, kwargs, worker_id)

    def submit_actor_direct(self, actor_id: ActorID, task_id: TaskID,
                            name: str, method_name: str,
                            return_ids: List[ObjectID], args: list,
                            kwargs: dict, max_concurrency: int) -> bool:
        """Fast-path actor method call (reference: the direct caller->actor
        submission stream, actor_task_submitter.h:68 — the driver pushes
        the call straight onto the actor worker's connection).

        Skips TaskSpec construction, task events, the running table and
        on_task_done: the call frame goes directly to the bound worker and
        the reply is routed by ``on_direct_task_done`` via
        ``_direct_inflight``.  Falls back (returns False) whenever ordering
        needs the full path: worker unbound/restarting, or queued calls
        ahead (per-caller submission order must hold).

        Cluster mode: the driver opens its own caller->actor channel
        (direct.py DirectChannel over TCP) to actors on remote nodes — and
        to local actors whose classic dispatches ride the ordered transfer
        queue — activating it (sticky) only at quiescence: no queued or
        in-flight classic dispatches, so a channel frame can never
        overtake a classic one.  Channel calls record no task events
        (mirrors worker->worker direct calls); calls with ref args still
        take the classic path, which is unordered relative to the channel
        — the same documented trade the worker-side channels make."""
        ast = self._actor_state(actor_id)
        tb = task_id.binary()
        if ast.driver_mode == "direct":
            return self._submit_via_channel(
                ast, actor_id, tb, name, method_name, return_ids, args,
                kwargs, max_concurrency)
        with ast.lock:
            if (ast.worker_id is None or ast.pending_bind
                    or ast.next_dispatch != ast.next_seq):
                return False
            node = self.nodes.get(ast.node_id)
            if node is None:
                return False
            if getattr(node, "is_remote", False) or \
                    self._xfer_q is not None:
                if ast.classic_inflight or ast.direct_addr is None:
                    return False  # not quiescent yet: classic this call
                ast.driver_mode = "direct"
            if ast.driver_mode == "direct":
                pass  # channel submission happens outside ast.lock
            else:
                return self._submit_direct_local(
                    ast, node, actor_id, tb, name, method_name,
                    return_ids, args, kwargs, max_concurrency)
        return self._submit_via_channel(
            ast, actor_id, tb, name, method_name, return_ids, args,
            kwargs, max_concurrency)

    def _submit_direct_local(self, ast, node, actor_id: ActorID,
                             tb: bytes, name: str, method_name: str,
                             return_ids: List[ObjectID], args: list,
                             kwargs: dict, max_concurrency: int) -> bool:
        """The in-process fast path (caller holds ast.lock)."""
        # Claim the sequence slot and ship while still holding
        # ast.lock so a concurrently submitted call claiming seq N+1
        # cannot reach the worker pipe before this frame (seq N).
        ast.next_seq += 1
        ast.next_dispatch += 1
        if self._gc_enabled:
            # Pending states must exist before a ref drop can arrive
            # (see submit_spec's pre-create note).  The oids are freshly
            # minted — no concurrent creator exists — so GIL-atomic
            # setitem is enough (skips the directory lock).
            directory = self.directory  # ray-tpu: noqa[RT401]
            for oid in return_ids:
                if oid not in directory:
                    directory[oid] = ObjectState()
        with self._direct_lock:
            self._direct_inflight[tb] = (actor_id, return_ids, name)
        frame = (_wire.RUN_TASK, tb, name, None, None, method_name,
                 tuple(r.binary() for r in return_ids),
                 actor_id.binary(), False, max_concurrency, None,
                 args, kwargs, None)
        if not node.send_direct(ast.worker_id, frame):
            with self._direct_lock:
                self._direct_inflight.pop(tb, None)
            desc = ("err", serialization.pack_payload(ActorError(
                actor_id, "actor worker died before the call was sent")))
            for oid in return_ids:
                self.mark_ready(oid, desc)
        return True

    def _submit_via_channel(self, ast, actor_id: ActorID, tb: bytes,
                            name: str, method_name: str,
                            return_ids: List[ObjectID], args: list,
                            kwargs: dict, max_concurrency: int) -> bool:
        """Driver->actor direct channel (cluster mode): the frame rides
        the driver's own TCP connection to the actor's worker — the
        head's control plane sees neither the call nor its inline reply
        (reference: caller->executor pushes as the cluster default,
        normal_task_submitter.cc:516, actor_task_submitter.h:68)."""
        ch = ast.driver_ch
        if ch is None:
            with ast.lock:
                ch = ast.driver_ch
                if ch is None:
                    from .direct import DirectChannel
                    ch = DirectChannel(_DriverChannelOwner(self), actor_id)
                    ast.driver_ch = ch
                    with ch.lock:
                        ch._ensure_resolver_locked()
        # Object states must exist before the frame ships: the inline
        # reply can land on the channel's recv thread immediately.
        self._states(return_ids)
        frame = (_wire.RUN_TASK, tb, name, None, None, method_name,
                 tuple(r.binary() for r in return_ids),
                 actor_id.binary(), False, max_concurrency, None,
                 args, kwargs, None)
        ch.submit(frame, return_ids)
        return True

    def on_direct_task_done(self, t: tuple) -> bool:
        """Route a wire TaskDone for a direct call (pre-decode): mark the
        caller-held return refs ready.  Returns False for non-direct tasks
        so the node runs the full TaskDone path."""
        with self._direct_lock:
            entry = self._direct_inflight.pop(t[1], None)
        if entry is None:
            return False
        aid, return_ids, name = entry
        error = t[4]
        # One terminal event per direct call keeps the state API's task
        # view complete; the intermediate states are intentionally skipped
        # on this path.
        if error is not None:
            err_repr = None
            try:
                err_repr = repr(serialization.unpack_payload(error[1]))
            except Exception as e:
                telemetry.note_swallowed("runtime.error_repr", e)
            self.events.record(TaskID(t[1]).hex(), FAILED, name=name,
                               task_type="ACTOR_TASK", actor_id=aid.hex(),
                               error_message=err_repr)
            for oid in return_ids:
                self.mark_ready(oid, error)
            return True
        self.events.record(TaskID(t[1]).hex(), FINISHED, name=name,
                           task_type="ACTOR_TASK", actor_id=aid.hex())
        for ob, desc in t[3]:
            self.mark_ready(ObjectID(ob), desc)
        return True

    def _fail_direct_inflight(self, actor_id: ActorID, reason: str) -> None:
        with self._direct_lock:
            failed = [(tb, rids) for tb, (aid, rids, _name)
                      in self._direct_inflight.items() if aid == actor_id]
            for tb, _ in failed:
                self._direct_inflight.pop(tb, None)
        if not failed:
            return
        desc = ("err", serialization.pack_payload(
            ActorError(actor_id, reason)))
        for _tb, rids in failed:
            for oid in rids:
                self.mark_ready(oid, desc)

    def bind_actor_worker(self, actor_id: ActorID, node_id: NodeID,
                          worker_id: WorkerID) -> None:
        ast = self._actor_state(actor_id)
        with ast.lock:
            ast.worker_id = worker_id
            ast.node_id = node_id
            pending, ast.pending_bind = ast.pending_bind, []
        for item in pending:
            self._dispatch_to_actor_worker(ast, *item)

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        ast = self._actor_state(actor_id)
        info = self.controller.get_actor(actor_id)
        if info is not None and no_restart:
            info.max_restarts = info.num_restarts  # no further restarts
        if ast.worker_id is not None and ast.node_id is not None:
            self.nodes[ast.node_id].kill_actor_worker(ast.worker_id)

    # ------------------------------------------------------------------ #
    # events from the node plane
    # ------------------------------------------------------------------ #

    def note_task_running(self, task_id: TaskID, node_id: NodeID,
                          worker_id: WorkerID) -> None:
        with self._running_lock:
            rt = self._running.get(task_id)
            if rt is not None:
                rt.worker_id = worker_id
        self.events.record(task_id.hex(), RUNNING, node_id=node_id.hex(),
                           worker_id=worker_id.hex())

    def _track(self, spec: TaskSpec, node_id: NodeID) -> None:
        with self._running_lock:
            self._running[spec.task_id] = _RunningTask(spec, node_id)
        self.events.record(spec.task_id.hex(), SUBMITTED_TO_NODE,
                           node_id=node_id.hex())

    def on_task_done(self, msg: TaskDone, node_id: NodeID) -> None:
        with self._running_lock:
            running = self._running.pop(msg.task_id, None)
        spec = running.spec if running else None
        if spec is not None and spec.actor_id is not None:
            with self._actors_lock:
                ast = self._actors.get(spec.actor_id)
            if ast is not None:
                ast.classic_inflight.discard(spec.task_id)
        resubmit = False
        if msg.error is not None:
            # A task that failed because an *input* object was lost gets
            # resubmitted once the input's lineage re-execution is kicked
            # off — the scheduler's dependency stage holds it until the
            # rebuilt value lands (reference: task resubmission on
            # ObjectLostError, object_recovery_manager.h).
            lost = self._lost_object_in_error(msg.error)
            if lost is not None and spec is not None \
                    and spec.actor_id is None \
                    and spec.create_actor_id is None \
                    and self._recover_object(lost) is not None:
                resubmit = True
                self.events.record(
                    msg.task_id.hex(), PENDING_ARGS, name=spec.name,
                    error_message="input lost; awaiting reconstruction")
            else:
                err = None
                try:
                    err = repr(serialization.unpack_payload(msg.error[1]))
                except Exception as e:
                    telemetry.note_swallowed("runtime.error_repr", e)
                self.events.record(msg.task_id.hex(), FAILED,
                                   error_message=err)
                self._export_event("EXPORT_TASK", {
                    "task_id": msg.task_id.hex(), "state": FAILED,
                    "name": spec.name if spec else None,
                    "error_message": err})
                for oid in (spec.return_ids if spec
                            else [r[0] for r in msg.results]):
                    self.mark_ready(oid, msg.error)
                if spec is not None and getattr(spec, "streaming", False):
                    self._fail_stream(msg.task_id, msg.error)
                self._finish_recovery(msg.task_id)
        else:
            self.events.record(msg.task_id.hex(), FINISHED)
            for oid, desc in msg.results:
                self.mark_ready(oid, desc)
            # Safe bare read: empty-dict fast path; a stale non-empty
            # view just takes the locked _finish_recovery slow path.
            if self._recovering:  # ray-tpu: noqa[RT401]
                self._finish_recovery(msg.task_id)
        if spec is not None and spec.task_id in self._pipelined:
            # Pipelined task: never booked resources — nothing to release
            # or exchange, but the freed worker-queue slot can take the
            # next queued task.
            self._pipelined.discard(spec.task_id)
            self._return_pipeline_credit(spec.task_id)
            self._pipeline_topup()
        elif spec is not None and spec.create_actor_id is None:
            # Actor creation keeps its resources for the actor's lifetime.
            if not spec.resources.is_empty() or spec.placement_group is not None:
                from .resources import TPU as _TPU
                if msg.error is None and spec.actor_id is None \
                        and spec.placement_group is None \
                        and spec.runtime_env is None \
                        and spec.scheduling_strategy is None \
                        and spec.resources.get(_TPU) == 0:
                    # Lease reuse: hand the booking straight to the next
                    # queued task of this class and dispatch it onto the
                    # just-freed worker — no release/re-book round trip
                    # through the scheduler loop.
                    nxt = self.scheduler.exchange_finished(node_id, spec)
                    if nxt is not None:
                        self.scheduler._dispatch_safely(
                            nxt.spec, nxt.dispatch, node_id)
                        # Keep worker queues non-empty: a backlogged class
                        # also tops up the pipeline window so workers never
                        # idle through the done->dispatch round trip.
                        self._pipeline_topup()
                else:
                    self.scheduler.release(node_id, spec.resources,
                                           spec.placement_group,
                                           spec.bundle_index)
        if resubmit:
            # Deps stay retained across the resubmit (releasing first could
            # let GC free a sibling input that nothing would re-produce).
            self.submit_spec(spec)
        # Safe bare read: empty-dict fast path; _release_deps re-checks
        # membership under its own lock.
        elif self._deps_retained:  # ray-tpu: noqa[RT401]
            self._release_deps(msg.task_id)

    def on_dispatch_failed(self, spec: TaskSpec, reason: str,
                           lost_object_bytes: Optional[bytes] = None) -> None:
        with self._running_lock:
            self._running.pop(spec.task_id, None)
        if lost_object_bytes is not None and spec.actor_id is None \
                and spec.create_actor_id is None:
            # A dependency vanished between resolve and dispatch: rebuild it
            # via lineage and resubmit (the dependency stage holds the task
            # until the rebuilt value lands).
            try:
                lost = ObjectID(lost_object_bytes)
            except ValueError:
                lost = None
            if lost is not None and self._recover_object(lost) is not None:
                # Deps stay retained across the resubmit (see on_task_done).
                self.submit_spec(spec)
                return
        self._fail_task(spec, WorkerCrashedError(reason))

    def fail_task_bytes(self, task_id_bytes: bytes, return_id_bytes,
                        reason: str) -> None:
        """Fail a task known only by its wire-frame ids (sender-side
        serialization failure).  The tracked running spec — if still there
        — provides the resource booking to release; without it, fall back
        to erroring the raw return ids."""
        try:
            task_id = TaskID(task_id_bytes)
        except ValueError:
            return
        # A direct call whose frame never serialized: clear its in-flight
        # entry so long-lived actors don't accumulate dead records.
        with self._direct_lock:
            self._direct_inflight.pop(task_id_bytes, None)
        with self._running_lock:
            running = self._running.pop(task_id, None)
        if running is not None:
            spec = running.spec
            if spec.task_id in self._pipelined:
                self._pipelined.discard(spec.task_id)
                self._return_pipeline_credit(spec.task_id)
            elif spec.create_actor_id is None and (
                    not spec.resources.is_empty()
                    or spec.placement_group is not None):
                self.scheduler.release(running.node_id, spec.resources,
                                       spec.placement_group,
                                       spec.bundle_index)
            self._fail_task(spec, WorkerCrashedError(reason))
            return
        self.events.record(task_id.hex(), FAILED, error_message=reason)
        desc = ("err", serialization.pack_payload(WorkerCrashedError(reason)))
        for rb in return_id_bytes:
            try:
                self.mark_ready(ObjectID(rb), desc)
            except ValueError:
                pass
        self._release_deps(task_id)
        self._finish_recovery(task_id)

    def _fail_task(self, spec: TaskSpec, exc: Exception) -> None:
        if spec.actor_id is not None:
            with self._actors_lock:
                ast = self._actors.get(spec.actor_id)
            if ast is not None:
                ast.classic_inflight.discard(spec.task_id)
        self.events.record(spec.task_id.hex(), FAILED, name=spec.name,
                           error_message=repr(exc))
        self._export_event("EXPORT_TASK", {
            "task_id": spec.task_id.hex(), "state": FAILED,
            "name": spec.name, "error_message": repr(exc)})
        self._release_deps(spec.task_id)
        desc = ("err", serialization.pack_payload(exc))
        for oid in spec.return_ids:
            self.mark_ready(oid, desc)
        if getattr(spec, "streaming", False):
            self._fail_stream(spec.task_id, desc)
        self._finish_recovery(spec.task_id)

    def _fail_stream(self, task_id: TaskID, err_desc) -> None:
        """Publish an error at the first unpublished stream index so a
        blocked ObjectRefGenerator raises instead of hanging forever."""
        i = 0
        while True:
            st = self._state(ObjectID.of(task_id, i))
            if not st.ready:
                st.mark_ready(err_desc)
                self.scheduler.notify_object_ready(ObjectID.of(task_id, i))
                return
            i += 1
            if i > 1 << 20:
                return

    def on_worker_died(self, worker_id: WorkerID, node_id: NodeID,
                       running_tasks: List[TaskID],
                       actor_id: Optional[ActorID],
                       reason: str = "") -> None:
        if self._shutdown:
            return
        specs: List[TaskSpec] = []
        with self._running_lock:
            for tid in running_tasks:
                rt = self._running.pop(tid, None)
                if rt is not None:
                    specs.append(rt.spec)
        oom = reason.startswith("OOM-killed")
        # Direct actor calls bypass the running table (submit_actor_direct):
        # count them so a busy actor's death still registers as unexpected.
        n_direct = 0
        if actor_id is not None:
            with self._direct_lock:
                n_direct = sum(1 for (aid, _r, _n)
                               in self._direct_inflight.values()
                               if aid == actor_id)
        self._export_event("EXPORT_WORKER", {
            "worker_id": worker_id.hex(), "node_id": node_id.hex(),
            "state": "DEAD", "reason": reason or None,
            "actor_id": actor_id.hex() if actor_id is not None else None,
            "num_running_tasks": len(specs) + n_direct})
        if specs or n_direct:
            # Dying WHILE running tasks is the unexpected case worth
            # forensics (clean pool reaping and idle actor kills are not).
            # A death on a draining node is the EXPECTED half of a
            # preemption: tag the bundle so the postmortem reads
            # "preempted", not "mystery crash".
            node = self.controller.nodes.get(node_id)
            draining = bool(node is not None and node.draining)
            self._maybe_death_bundle(
                f"worker_death_{'preempted_' if draining else ''}"
                f"{worker_id.hex()[:8]}",
                {"worker_id": worker_id.hex(),
                 "reason": "preempted" if draining else reason,
                 "worker_reason": reason,
                 "node_draining": draining,
                 "running_tasks": [t.hex() for t in running_tasks],
                 "direct_calls_inflight": n_direct})
        for spec in specs:
            if spec.task_id in self._pipelined:
                # Pipelined task: no booking to release; the resubmit
                # below goes through normal (booked) submission.
                self._pipelined.discard(spec.task_id)
                self._return_pipeline_credit(spec.task_id)
            elif spec.create_actor_id is None and (
                    not spec.resources.is_empty()
                    or spec.placement_group is not None):
                self.scheduler.release(node_id, spec.resources,
                                       spec.placement_group, spec.bundle_index)
            if spec.actor_id is None and spec.create_actor_id is None and \
                    spec.retry_count < spec.max_retries:
                spec.retry_count += 1
                self.submit_spec(spec)
            elif spec.actor_id is not None:
                self._fail_task(spec, ActorError(
                    spec.actor_id,
                    f"worker died while running {spec.name}"
                    + (f" ({reason})" if reason else "")))
            elif spec.create_actor_id is None:
                err_cls = OutOfMemoryError if oom else WorkerCrashedError
                self._fail_task(spec, err_cls(
                    f"worker {worker_id} died while running {spec.name}"
                    + (f" ({reason})" if reason else "")))
        if actor_id is not None:
            self._fail_direct_inflight(
                actor_id, "worker died while running a direct actor call"
                + (f" ({reason})" if reason else ""))
            self._on_actor_worker_death(actor_id, node_id)

    def _on_actor_worker_death(self, actor_id: ActorID, node_id: NodeID) -> None:
        info = self.controller.get_actor(actor_id)
        if info is None or info.state == DEAD:
            return
        ast = self._actor_state(actor_id)
        with ast.lock:
            ast.worker_id = None
            ast.node_id = None
            ast.direct_addr = None
            # Classic frames to the dead worker can't be in flight anymore;
            # a stale entry would wedge driver-channel activation forever.
            ast.classic_inflight.clear()
        # Release the actor's held creation resources.
        if info.creation_spec is not None:
            cs = info.creation_spec
            if not cs.resources.is_empty() or cs.placement_group is not None:
                self.scheduler.release(node_id, cs.resources,
                                       cs.placement_group, cs.bundle_index)
        if info.num_restarts < info.max_restarts:
            info.num_restarts += 1
            self.controller.set_actor_state(actor_id, RESTARTING)
            self._submit_actor_creation(
                self._restart_creation_spec(actor_id, info.creation_spec))
        else:
            self.controller.set_actor_state(actor_id, DEAD,
                                            death_cause="worker died")
            with ast.lock:
                pending = ast.pending_bind + list(ast.ready_buffer.values())
                ast.pending_bind = []
                ast.ready_buffer.clear()
            for spec, _a, _k in pending:
                self._fail_task(spec, ActorError(actor_id, "actor died"))

    def on_node_died(self, node_id: NodeID) -> None:
        """A joined node's control connection dropped: fail/retry its tasks,
        restart its actors elsewhere, re-plan its PG bundles (reference:
        gcs_node_manager.cc node death fan-out + gcs_actor_manager restart;
        gcs_placement_group_manager bundle rescheduling)."""
        if self._shutdown:
            return
        self.nodes.pop(node_id, None)
        with self._node_views_lock:
            self._node_views.pop(node_id, None)
        self.controller.mark_node_dead(node_id, "connection lost")
        # Death fan-out reruns/fails its work: a later same-identity
        # re-attach (even across a head restart) must be refused.
        self.controller.drop_revivable(node_id.binary())
        self.scheduler.remove_node(node_id)
        telemetry.set_gauge("ray_tpu_node_draining",
                            len(self.controller.draining_nodes()))

        specs: List[TaskSpec] = []
        with self._running_lock:
            for tid, rt in list(self._running.items()):
                if rt.node_id == node_id:
                    self._running.pop(tid, None)
                    specs.append(rt.spec)
        with self._pipeline_lock:
            self._pipeline_credits.pop(node_id, None)
            self._pipeline_cooldown.pop(node_id, None)
        for spec in specs:
            # Pipelined entries must clear BEFORE the resubmit: the retried
            # task reuses its task_id, and a stale _pipelined entry would
            # make its eventual TaskDone skip the booked-resource release.
            if spec.task_id in self._pipelined:
                self._pipelined.discard(spec.task_id)
                with self._pipeline_lock:
                    self._pipelined_node.pop(spec.task_id, None)
            # Creation tasks are re-placed (the actor never came up, so no
            # restart is consumed); retryable tasks resubmit; others fail.
            self._requeue_or_fail(
                spec, f"node {node_id} died while running {spec.name}")

        # Actors that lived there: restart elsewhere via the FSM.
        with self._actors_lock:
            lost = [aid for aid, ast in self._actors.items()
                    if ast.node_id == node_id]
        for aid in lost:
            self._on_actor_worker_death(aid, node_id)

        # PG bundles committed to the dead node: re-plan just those bundles
        # on the surviving nodes.
        for pg in list(self.controller.placement_groups.values()):
            if any(b.node_id == node_id for b in pg.bundles):
                self.scheduler.reschedule_lost_bundles(pg, node_id)

    def ctl_node_data_address(self, node_id_bytes: bytes):
        """Data-plane address lookup for peer pulls (the location oracle)."""
        if self.head_server is None:
            return None
        return self.head_server.node_data_address(node_id_bytes)

    def on_actor_state(self, msg: ActorStateMsg, node_id: NodeID,
                       worker_id: WorkerID) -> None:
        if msg.state == "alive":
            addr = getattr(msg, "direct_addr", None)
            if addr is not None:
                ast = self._actor_state(msg.actor_id)
                with ast.lock:
                    ast.direct_addr = tuple(addr)
            self.controller.set_actor_state(msg.actor_id, ALIVE, node_id)
        else:
            cause = "creation failed"
            if msg.error is not None and msg.error[0] == "err":
                try:
                    exc = serialization.unpack_payload(msg.error[1])
                    inner = getattr(exc, "cause", exc)
                    cause = f"creation failed: {type(inner).__name__}: {inner}"
                except Exception as e:
                    telemetry.note_swallowed("runtime.error_repr", e)
            self.controller.set_actor_state(msg.actor_id, DEAD,
                                            death_cause=cause)
            ast = self._actor_state(msg.actor_id)
            with ast.lock:
                pending = ast.pending_bind + list(ast.ready_buffer.values())
                ast.pending_bind = []
                ast.ready_buffer.clear()
            err = msg.error or ("err", serialization.pack_payload(
                ActorError(msg.actor_id, cause)))
            for spec, _a, _k in pending:
                for oid in spec.return_ids:
                    self.mark_ready(oid, err)

    # -- worker-initiated requests -------------------------------------- #

    def on_get_request(self, node, msg: GetRequest) -> None:
        states = self._states(msg.object_ids)
        remaining = {"n": len(states)}
        lock = threading.Lock()
        replied = {"done": False}
        timer_box: Dict[str, Any] = {}
        is_remote = getattr(node, "is_remote", False)
        is_client = getattr(node, "is_client", False)

        def finish(timed_out: bool):
            with lock:
                if replied["done"]:
                    return
                replied["done"] = True
            # The timeout Timer must die WITH the request: un-cancelled
            # it idles out the full user timeout per get() — thousands of
            # zombie timer threads under load (leak found by the
            # sanitizer).
            t = timer_box.get("t")
            if t is not None:
                t.cancel()
            if not is_remote and any(
                    isinstance(st.desc, tuple) and st.desc
                    and st.desc[0] == "at" for st in states
                    if st.ready):
                # Local reader needs remote objects: the pull blocks, so
                # run the reply construction on the transfer thread.
                self._offload(lambda: _build_reply(timed_out))
            else:
                _build_reply(timed_out)

        def _build_reply(timed_out: bool):
            values = []
            pinned_keys = []
            for oid, st in zip(msg.object_ids, states):
                if not st.ready:
                    values.append(("err", b""))
                    continue
                d = st.desc
                if is_remote:
                    # Consumer is on another node: it pulls payloads over
                    # the data plane by key, so ship location-tagged
                    # descriptors instead of pinning here (the fetch pins
                    # on the owner for the duration of the copy).
                    if isinstance(d, tuple) and d and d[0] in ("shm", "shma"):
                        from .cluster import tag_desc
                        d = tag_desc(d, self.node_id.binary())
                    values.append(d)
                    continue
                if isinstance(d, tuple) and d and d[0] == "at":
                    # Remote object requested by a head-local worker: pull
                    # it into the head store, then hand out a local pin.
                    d = self._puller.localize(d) if self._puller else (
                        "err", serialization.pack_payload(ObjectLostError(
                            "remote object without a cluster data plane",
                            object_id_bytes=oid.binary())))
                if is_client:
                    # Store-less remote driver: materialize to a raw inline
                    # payload (shm offsets mean nothing across the wire).
                    if isinstance(d, tuple) and d and d[0] in ("shm", "shma"):
                        from .cluster import read_raw_payload
                        raw = read_raw_payload(node.store, d)
                        d = ("inline", raw) if raw is not None else (
                            "err", serialization.pack_payload(ObjectLostError(
                                "object was evicted or freed",
                                object_id_bytes=oid.binary())))
                    values.append(d)
                    continue
                if isinstance(d, tuple) and d and d[0] == "shma":
                    # Refresh + pin so the offset stays valid until the
                    # worker's ReadDone (plasma client-pin semantics).
                    nd = node.store.pin_desc_by_key(d[4])
                    if nd is None:
                        d = ("err", serialization.pack_payload(
                            ObjectLostError("object was evicted or freed",
                                            object_id_bytes=oid.binary())))
                    else:
                        d = nd
                        pinned_keys.append(nd[4])
                values.append(d)
            if pinned_keys:
                node.track_get_pins(msg.worker_id, msg.request_id,
                                    pinned_keys)
            node.send_to_worker(msg.worker_id,
                                GetReply(msg.request_id, values, timed_out))

        def one_ready():
            with lock:
                remaining["n"] -= 1
                done = remaining["n"] == 0
            if done:
                finish(False)

        if msg.timeout_s is not None:
            timer = threading.Timer(msg.timeout_s, lambda: finish(True))
            timer.daemon = True
            timer_box["t"] = timer
            timer.start()
        if not states:
            finish(False)
        for st in states:
            st.add_callback(one_ready)

    def on_wait_request(self, node: NodeManager, msg: WaitRequest) -> None:
        def run():
            try:
                ready, _ = self.wait(msg.object_ids, msg.num_returns,
                                     msg.timeout_s)
            except Exception:  # noqa: BLE001 — a lost reply hangs the caller
                ready = []
            node.send_to_worker(msg.worker_id,
                                WaitReply(msg.request_id, ready))
        sanitizer.spawn(run, name="wait-reply")

    def on_put_from_worker(self, msg: PutFromWorker) -> None:
        self.mark_ready(msg.object_id, msg.desc)

    # ctl_* methods that may block (long-poll style): handled off the
    # reader thread so one waiting worker can't stall its node connection.
    # stack_dump/debug_dump wait for StackDumpReplies that arrive ON the
    # poller thread — running them there would deadlock the collection.
    _BLOCKING_CTL = frozenset({"kv_wait", "pubsub_poll", "stack_dump",
                               "debug_dump", "profile"})

    def on_rpc_call(self, node, msg: RpcCall) -> None:
        def run():
            try:
                fn = getattr(self, "ctl_" + msg.method)
                value = fn(*msg.args, **msg.kwargs)
                node.send_to_worker(msg.worker_id,
                                    RpcReply(msg.request_id, value))
            except Exception as e:  # noqa: BLE001
                node.send_to_worker(msg.worker_id,
                                    RpcReply(msg.request_id, None, repr(e)))
        if msg.method in self._BLOCKING_CTL:
            sanitizer.spawn(run, name=f"ctl-{msg.method}")
        else:
            run()

    # control-plane methods callable from workers (and used by the driver
    # API directly). All arguments/returns must be plain picklable data.

    def ctl_pin_object(self, oid_bytes: bytes) -> bool:
        """Pin an object against eviction AND reference-count collection
        (ray_tpu.checkpoint emergency replicas: the newest snapshot must
        survive object-store pressure and the producer dropping its ref).
        Returns whether the head store held a pinnable copy; either way
        the escape-mark keeps the directory entry alive."""
        oid = ObjectID(oid_bytes)
        self.mark_escaped(oid)
        sanitizer.note_pin(oid.hex())
        store_pin = getattr(self.node.store, "try_pin", None)
        if store_pin is None:
            return False
        return bool(store_pin(oid, pinner="ckpt_pin"))

    def ctl_unpin_object(self, oid_bytes: bytes) -> bool:
        oid = ObjectID(oid_bytes)
        with self._ref_lock:
            self._escaped.discard(oid)
        sanitizer.note_unpin(oid.hex())
        store_unpin = getattr(self.node.store, "try_unpin", None)
        if store_unpin is None:
            return False
        return bool(store_unpin(oid, pinner="ckpt_pin"))

    def ctl_kv_put(self, key, value, namespace="default", overwrite=True):
        return self.controller.kv_put(key, value, namespace, overwrite)

    def ctl_kv_get(self, key, namespace="default"):
        return self.controller.kv_get(key, namespace)

    def ctl_kv_del(self, key, namespace="default"):
        return self.controller.kv_del(key, namespace)

    def ctl_kv_keys(self, prefix="", namespace="default"):
        return self.controller.kv_keys(prefix, namespace)

    def ctl_kv_wait(self, key, namespace="default", timeout=None):
        return self.controller.kv_wait(key, namespace, timeout)

    def ctl_get_named_actor(self, name, namespace=None):
        info = self.controller.get_named_actor(name,
                                               namespace or self.namespace)
        if info is None or info.state == DEAD:
            return None
        return (info.actor_id.binary(), info.max_restarts, info.class_name)

    def ctl_register_actor(self, actor_id_bytes, name, namespace, max_restarts,
                           class_name):
        info = ActorInfo(ActorID(actor_id_bytes), name or None,
                         "DEPENDENCIES_UNREADY", None, max_restarts,
                         namespace=namespace or self.namespace,
                         class_name=class_name)
        self.register_actor(info)
        if name:
            sanitizer.note_named_actor(name, namespace or self.namespace,
                                       class_name)
        return True

    def ctl_actor_creation_spec(self, actor_id_bytes, spec: TaskSpec):
        info = self.controller.get_actor(ActorID(actor_id_bytes))
        if info is not None:
            info.creation_spec = spec
            # Re-persist: the creation spec is what a restarted head
            # rebuilds the actor from.
            self.controller._p(("actor", info))
        return True

    def ctl_kill_actor(self, actor_id_bytes, no_restart=True):
        self.kill_actor(ActorID(actor_id_bytes), no_restart)
        return True

    def ctl_actor_state(self, actor_id_bytes):
        info = self.controller.get_actor(ActorID(actor_id_bytes))
        return info.state if info else None

    def ctl_create_pg(self, bundles: List[Dict[str, float]], strategy: str,
                      name: Optional[str] = None):
        from .controller import BundleInfo
        pg_id = PlacementGroupID.of(self.job_id)
        info = PlacementGroupInfo(
            pg_id, name, strategy,
            [BundleInfo(i, ResourceSet(b)) for i, b in enumerate(bundles)])
        self.controller.register_placement_group(info)
        self.scheduler.create_placement_group(info)
        return pg_id.binary()

    def ctl_pg_state(self, pg_id_bytes):
        info = self.controller.get_placement_group(PlacementGroupID(pg_id_bytes))
        return info.state if info else None

    def ctl_pg_bundle_locations(self, pg_id_bytes):
        info = self.controller.get_placement_group(PlacementGroupID(pg_id_bytes))
        if info is None:
            return None
        return [b.node_id.binary() if b.node_id else None for b in info.bundles]

    def ctl_remove_pg(self, pg_id_bytes):
        info = self.controller.get_placement_group(PlacementGroupID(pg_id_bytes))
        if info is not None:
            self.scheduler.remove_placement_group(info)
        return True

    def ctl_cluster_resources(self):
        return self.scheduler.total_resources()

    def ctl_available_resources(self):
        return self.scheduler.available_resources()

    def ctl_nodes(self):
        now = time.monotonic()
        return [{"node_id": n.node_id.hex(), "alive": n.alive,
                 "hostname": n.hostname,
                 "resources": n.total_resources.to_dict(),
                 "is_head": n.is_head,
                 "draining": n.draining,
                 "drain_reason": n.drain_reason,
                 # Relative, so cross-process readers never difference a
                 # foreign monotonic stamp (RT203 territory).
                 "drain_remaining_s": max(0.0, n.drain_deadline_mono - now)
                 if n.draining else 0.0}
                for n in self.controller.nodes.values()]

    def ctl_drain_node(self, node_id_hex: str, deadline_s: float = 30.0,
                       reason: str = "preemption") -> bool:
        """Drain protocol entry point: mark the node unschedulable for
        new leases and advertise the kill deadline.  Train/serve
        controllers poll the node table and evacuate their work; the
        autoscaler's provider hook and `ray-tpu drain` both land here."""
        try:
            node_id = NodeID.from_hex(node_id_hex)
        except ValueError:
            return False
        if not self.controller.drain_node(node_id, deadline_s, reason):
            return False
        self.scheduler.set_draining(node_id, True)
        telemetry.set_gauge("ray_tpu_node_draining",
                            len(self.controller.draining_nodes()))
        return True

    def ctl_undrain_node(self, node_id_hex: str) -> bool:
        try:
            node_id = NodeID.from_hex(node_id_hex)
        except ValueError:
            return False
        if not self.controller.undrain_node(node_id):
            return False
        self.scheduler.set_draining(node_id, False)
        telemetry.set_gauge("ray_tpu_node_draining",
                            len(self.controller.draining_nodes()))
        return True

    # -- syncer (reference: src/ray/ray_syncer/ray_syncer.h:91) -------------

    def on_node_view(self, node_id: NodeID, version: int, view: dict) -> None:
        """Receive a versioned resource view; stale versions are dropped
        (reference: ray_syncer receiver version check)."""
        with self._node_views_lock:
            cur = self._node_views.get(node_id)
            if cur is not None and cur[0] >= version:
                return
            self._node_views[node_id] = (version, view, time.time())

    def ctl_node_views(self):
        """Latest per-node load views; the head's own node is sampled live
        (it needs no sync channel)."""
        out = {}
        with self._node_views_lock:
            for nid, (version, view, ts) in self._node_views.items():
                out[nid.hex()] = dict(view, _version=version, _ts=ts)
        local = self.nodes.get(self.node_id)
        if local is not None and not getattr(local, "is_remote", False):
            out[self.node_id.hex()] = dict(local.local_view(),
                                           _version=-1, _ts=time.time())
        return out

    def ctl_list_actors(self, filters=None, limit=10000):
        """Actor table view; ``filters`` is an equality dict applied
        server-side so point lookups (state.get_actor) don't ship the
        whole table (mirrors ctl_list_tasks' filter pushdown)."""
        out = []
        for a in self.controller.actors.values():
            rec = {"actor_id": a.actor_id.hex(), "state": a.state,
                   "name": a.name, "class_name": a.class_name,
                   "num_restarts": a.num_restarts,
                   # Placement: lets drain-aware owners (train/serve
                   # controllers) find which of their actors sit on a
                   # draining node.
                   "node_id": a.node_id.hex() if a.node_id else None}
            if filters and any(rec.get(k) != v for k, v in filters.items()):
                continue
            out.append(rec)
            if len(out) >= limit:
                break
        return out

    # -- state API feeds (reference: dashboard/modules/state/state_head.py
    #    backed by GcsTaskManager; here the buffers live in-process) ----- #

    def ctl_resolve_actor_direct(self, actor_id_bytes: bytes):
        """Resolve an actor's direct-call address for a caller worker
        (reference: the GCS actor-table lookup the core worker does before
        opening its caller->actor stream).  Returns (state, addr, cause):
        state in {"alive", "pending", "restarting", "dead"}; addr is the
        worker's direct listener when alive (None if the worker predates
        direct serving or runs without a token)."""
        try:
            actor_id = ActorID(actor_id_bytes)
        except ValueError:
            return ("dead", None, "invalid actor id")
        info = self.controller.get_actor(actor_id)
        if info is None:
            return ("dead", None, "unknown actor")
        if info.state == DEAD:
            return ("dead", None, info.death_cause or "actor died")
        if info.state in (PENDING_CREATION, RESTARTING):
            return ("pending" if info.state == PENDING_CREATION
                    else "restarting", None, None)
        ast = self._actor_state(actor_id)
        with ast.lock:
            return ("alive", ast.direct_addr, None)

    def ctl_list_tasks(self, filters=None, limit=10000, stage=None,
                       min_stage_wait_s=None):
        """Task-event records with server-side pushdown: equality
        ``filters``, ``limit`` (newest-first early exit), and lifecycle
        stage-latency selection (``stage`` + ``min_stage_wait_s``) — a
        point lookup must stay cheap when the ring holds the 10k-node
        bench's task table."""
        return self.events.snapshot(filters, limit, stage,
                                    min_stage_wait_s)

    def ctl_summarize_tasks(self, states=None, limit=None):
        return self.events.summary(states, limit)

    # -- control-plane telescope (ray_tpu.schedview; reference analog:
    #    `ray status -v` demand debug strings, here first-class) -------- #

    def ctl_sched_stats(self):
        """Live scheduler view for `ray-tpu sched` / GET /api/sched:
        queue depths, decision totals + trailing rates, task-event
        buffer health (ring saturation), node counts."""
        self.scheduler._maybe_publish_metrics(force=True)
        ring = self.scheduler.ring
        return {
            "queues": self.scheduler.queue_depths(),
            "decisions": ring.stats(),
            "rates": {"decisions_per_s_5s": round(ring.rate(5.0), 2),
                      "decisions_per_s_60s": round(ring.rate(60.0), 2)},
            "events": self.events.stats(),
            "nodes": {"total": len(self.controller.nodes),
                      "draining": len(self.controller.draining_nodes())},
        }

    def ctl_sched_decisions(self, task_id=None, limit=200):
        """Recent scheduler decision records (bounded ring snapshot);
        ``task_id`` filters, prefix ok."""
        return self.scheduler.ring.snapshot(task_id, limit)

    def ctl_explain_task(self, task_id_hex: str):
        """Answer `ray-tpu task why <id>`: why is this task still
        pending (unresolved deps / closest-fit gap / drain fence /
        missing PG bundle), or why did it land where it did (the
        recorded placement decision).  Accepts id prefixes."""
        matches = {t.hex() for t in self.scheduler.pending_task_ids()
                   if t.hex().startswith(task_id_hex)}
        matches.update(self.events.find_ids(task_id_hex))
        if not matches:
            return {"task_id": task_id_hex, "status": "unknown",
                    "reasons": [],
                    "detail": "no task with this id (or prefix) in the "
                              "scheduler queues or the task-event ring"}
        if len(matches) > 1 and task_id_hex not in matches:
            return {"task_id": task_id_hex, "status": "ambiguous",
                    "reasons": [], "matches": sorted(matches)[:8]}
        tid_hex = task_id_hex if task_id_hex in matches \
            else next(iter(matches))
        out: Dict[str, Any] = {"task_id": tid_hex}
        ev = (self.events.snapshot({"task_id": tid_hex}, 1)
              or [None])[0]
        if ev is not None:
            out["state"] = ev["state"]
            out["name"] = ev["name"]
            out["stage_waits"] = ev["stage_waits"]
            out["node_id"] = ev["node_id"]
            if ev["error_message"]:
                out["error_message"] = ev["error_message"]
        decision = self.scheduler.ring.latest_for(tid_hex)
        if decision is not None:
            out["last_decision"] = decision
        pending = None
        try:
            pending = self.scheduler.explain_task(TaskID.from_hex(tid_hex))
        except ValueError:
            pending = None
        if pending is not None:
            out.update(pending)
            return out
        # Not held by the scheduler: it placed (or never queued).
        state = out.get("state")
        out["status"] = {
            PENDING_ARGS: "submitted", READY: "ready", PLACED: "placed",
            SUBMITTED_TO_NODE: "dispatched", RUNNING: "running",
            FINISHED: "finished", FAILED: "failed",
        }.get(state, "unknown")
        out.setdefault("reasons", [])
        return out

    @staticmethod
    def _desc_location(desc, local_hex):
        """(node_hex, inner_desc, nbytes) for a directory descriptor; a
        bare descriptor lives on the head, an "at" tag names its owner."""
        if not desc:
            return None, None, None
        node_hex, inner = local_hex, desc
        if desc[0] == "at":
            node_hex, inner = desc[1].hex(), desc[2]
        nbytes = None
        if inner[0] == "inline":
            nbytes = len(inner[1])
        elif inner[0] == "shm":
            nbytes = inner[2]
        elif inner[0] == "shma":
            nbytes = inner[3]
        return node_hex, inner, nbytes

    def ctl_list_objects(self, limit=10000):
        ring = getattr(self.node.store, "view", None)
        latest = {}
        if ring is not None:
            for rec in ring.latest_index():
                latest[rec["object_id"]] = rec
        out = []
        with self._dir_lock:
            items = list(self.directory.items())[:limit]
        local_hex = self.node_id.hex()
        for oid, st in items:
            desc = st.desc
            kind = desc[0] if desc else "pending"
            node_hex, _inner, nbytes = self._desc_location(desc, local_hex)
            rec = {"object_id": oid.hex(), "status": kind,
                   "size_bytes": nbytes, "node_id": node_hex,
                   "task_id": oid.task_id().hex()}
            seen = latest.get(oid.hex())
            if seen is not None:
                rec["store_state"] = seen["state"]
                rec["pins"] = seen["pins"]
            out.append(rec)
        return out

    # -- data-plane telescope (storeview): memory summary, per-object
    #    explain, store event ring — reference: `ray memory`, the
    #    memory_summary state API ---------------------------------------- #

    def ctl_memory_summary(self, top_n: int = 10):
        """Cluster-wide object-store occupancy: per-node stats (the head
        sampled live, remote nodes via their synced views), directory-
        attributed top objects by size, and leak candidates.  Backs
        `ray-tpu memory` and state.memory_summary()."""
        self._publish_store_metrics(force=True)
        nodes = {}
        for nhex, view in self.ctl_node_views().items():
            sub = view.get("store")
            if isinstance(sub, dict):
                nodes[nhex] = dict(sub)
        totals = {}
        for key in ("used_bytes", "capacity_bytes", "pinned_bytes",
                    "spilled_bytes", "num_objects", "num_pinned",
                    "num_spilled"):
            totals[key] = sum(int(sub.get(key, 0))
                              for sub in nodes.values())
        objects = self.ctl_list_objects()
        sized = [o for o in objects if o.get("size_bytes")]
        sized.sort(key=lambda o: o["size_bytes"], reverse=True)
        leaks = []
        for nhex, sub in nodes.items():
            for rec in sub.get("leak_candidates") or ():
                leaks.append(dict(rec, node_id=nhex))
        leaks.sort(key=lambda r: int(r.get("nbytes", 0)), reverse=True)
        return {"nodes": nodes, "totals": totals,
                "top_objects": sized[:top_n],
                "leak_candidates": leaks,
                "num_directory_objects": len(objects)}

    def ctl_explain_object(self, object_id_hex: str):
        """Answer `ray-tpu obj why <id>`: where an object lives (directory
        descriptor + owner node), what produced it (owner task id from the
        id itself), and what the store event ring saw it do (spill/restore
        and pull history, pins and pinners).  Accepts id prefixes."""
        prefix = (object_id_hex or "").lower()
        with self._dir_lock:
            matches = [oid for oid in self.directory
                       if oid.hex().startswith(prefix)]
        ring = getattr(self.node.store, "view", None)
        if not matches:
            # Deleted objects leave the directory but linger in the
            # ring's latest-state index: still explainable.
            if ring is not None:
                rec = ring.explain(prefix)
                if rec.get("status") in ("ok", "ambiguous"):
                    rec.setdefault("directory", None)
                    return rec
            return {"object_id": prefix, "status": "unknown",
                    "detail": "no object with this id (or prefix) in the "
                              "directory or the store event ring"}
        hexes = sorted(o.hex() for o in matches)
        if len(matches) > 1 and prefix not in hexes:
            return {"object_id": prefix, "status": "ambiguous",
                    "matches": hexes[:8]}
        oid = matches[0] if len(matches) == 1 \
            else next(o for o in matches if o.hex() == prefix)
        with self._dir_lock:
            st = self.directory.get(oid)
        desc = st.desc if st is not None else None
        node_hex, inner, nbytes = self._desc_location(desc,
                                                      self.node_id.hex())
        out: Dict[str, Any] = {
            "object_id": oid.hex(), "status": "ok",
            "owner_task_id": oid.task_id().hex(),
            "directory": {"state": desc[0] if desc else "pending",
                          "node_id": node_hex, "size_bytes": nbytes,
                          "error": bool(inner) and inner[0] == "err"}}
        if ring is not None:
            rec = ring.explain(oid.hex())
            out["local"] = rec if rec.get("status") == "ok" else None
        if node_hex and node_hex != self.node_id.hex():
            # Remote object: its lifecycle lives in the owner's ring; the
            # synced store view carries that node's top objects, so
            # surface a match when one exists.
            view = self.ctl_node_views().get(node_hex) or {}
            sub = view.get("store") or {}
            for ent in sub.get("top_objects") or ():
                if ent.get("object_id") == oid.hex():
                    out["owner_view"] = ent
                    break
        return out

    def ctl_store_events(self, object_id=None, limit=200):
        """Head store event-ring snapshot (newest-last); feeds the
        flight-recorder bundle and tests."""
        ring = getattr(self.node.store, "view", None)
        if ring is None:
            return {"events": [], "stats": {}}
        return {"events": ring.snapshot(object_id, limit),
                "stats": ring.stats()}

    def ctl_list_placement_groups(self):
        return [{"placement_group_id": pg.pg_id.hex(), "state": pg.state,
                 "name": pg.name, "strategy": pg.strategy,
                 "bundle_count": len(pg.bundles)}
                for pg in self.controller.placement_groups.values()]

    def ctl_list_jobs(self):
        return [{"job_id": j.job_id.hex(), "start_time": j.start_time,
                 "end_time": j.end_time, "entrypoint": j.entrypoint}
                for j in self.controller.jobs.values()]

    def ctl_get_fn_blob(self, fn_id: bytes):
        return self._fn_table.get(fn_id)

    # -- live diagnostics (reference: `ray stack`, scripts.py; the debug
    #    state dump a postmortem attaches) ------------------------------- #

    def on_stack_reply(self, msg, node_id: Optional[NodeID] = None) -> None:
        """A worker's StackDumpReply landed (local poller thread or a
        remote node's UpStackReply): file it under its dump id."""
        with self._stack_lock:
            entry = self._stack_dumps.get(msg.dump_id)
            if entry is None:
                return  # collector already timed out and left
            record = dict(msg.record)
            record["node_id"] = node_id.hex() if node_id is not None else None
            entry["replies"][msg.worker_id.hex()] = record
            evt = entry["event"]
        evt.set()

    def on_stack_expect(self, dump_id: int, worker_ids: List) -> None:
        """A remote node answered StackDumpAll with the worker set it
        fanned out to: widen the expected-reply set so a wedged remote
        worker surfaces as 'unresponsive' instead of silently missing."""
        with self._stack_lock:
            entry = self._stack_dumps.get(dump_id)
            if entry is None:
                return
            entry["want"].update(w.hex() for w in worker_ids)
            entry["expects_pending"] -= 1
            evt = entry["event"]
        evt.set()

    def ctl_stack_dump(self,
                       timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Snapshot every live worker's thread stacks plus the driver's
        own (cluster-wide ``ray stack``).  Returns ``{"time", "stacks",
        "unresponsive"}``; a worker that cannot answer within the timeout
        is itself a diagnostic signal and is listed by id.

        Blocking: listed in _BLOCKING_CTL so a worker-originated call
        never runs on the node poller thread that must route the replies.
        """
        from .diagnostics import capture_process_stacks
        if timeout_s is None:
            timeout_s = Config.get("stack_dump_timeout_s")
        nodes = list(self.nodes.values())
        remote_nodes = [n for n in nodes if getattr(n, "is_remote", False)]
        with self._stack_lock:
            self._stack_dump_seq += 1
            dump_id = self._stack_dump_seq
            # Each remote node answers the broadcast with an UpStackExpect
            # naming its worker set; until every expect has landed the
            # collection can't know it has seen all wanted replies.
            entry: Dict[str, Any] = {"replies": {}, "want": set(),
                                     "expects_pending": len(remote_nodes),
                                     "event": threading.Event()}
            self._stack_dumps[dump_id] = entry
        expected: List[WorkerID] = []
        for node in nodes:
            try:
                ids = node.broadcast_stack_dump(dump_id)
                if not getattr(node, "is_remote", False):
                    expected.extend(ids)
            except Exception:  # noqa: BLE001 — a dead node can't stop a dump
                with self._stack_lock:
                    if getattr(node, "is_remote", False):
                        entry["expects_pending"] -= 1
        with self._stack_lock:
            entry["want"].update(w.hex() for w in expected)
        self._settle_collect(entry, timeout_s)
        with self._stack_lock:
            self._stack_dumps.pop(dump_id, None)
            replies = dict(entry["replies"])
            want = set(entry["want"])
        driver = capture_process_stacks("driver", is_driver=True)
        driver["node_id"] = self.node_id.hex()
        stacks = [driver] + [replies[k] for k in sorted(replies)]
        return {"time": time.time(), "stacks": stacks,
                "unresponsive": sorted(want - set(replies))}

    def _settle_collect(self, entry: Dict[str, Any], timeout_s: float,
                        settle_s: float = 0.5) -> None:
        """Wait for a broadcast collection (stack dump / profile) to
        complete: every wanted reply present AND every remote node's
        expect set landed — or replies stopped arriving for
        ``settle_s`` (a node server that dies before answering with its
        expect set must not hold the collection to the full timeout)."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        last_change = time.monotonic()
        prev_progress = -1
        while time.monotonic() < deadline:
            with self._stack_lock:
                have = set(entry["replies"])
                want = set(entry["want"])
                expects_pending = entry["expects_pending"]
            progress = len(have) + len(want)
            if progress != prev_progress:
                prev_progress = progress
                last_change = time.monotonic()
            if want <= have and (
                    expects_pending <= 0
                    or time.monotonic() - last_change >= settle_s):
                break
            entry["event"].clear()
            entry["event"].wait(min(0.05, max(
                0.0, deadline - time.monotonic())))

    # -- cluster profiler (see ray_tpu/profiler/) ------------------------ #

    def on_profile_reply(self, msg, node_id: Optional[NodeID] = None
                         ) -> None:
        """A worker's ProfileReply landed (local node or a remote's
        UpProfileReply): file it under its profile id."""
        with self._stack_lock:
            entry = self._profiles.get(msg.profile_id)
            if entry is None:
                return  # collector already timed out and left
            record = dict(msg.record)
            record["node_id"] = node_id.hex() if node_id is not None \
                else None
            entry["replies"][msg.worker_id.hex()] = record
            evt = entry["event"]
        evt.set()

    def on_profile_expect(self, profile_id: int, worker_ids: List) -> None:
        """A remote node answered ProfileAll with its worker set (see
        on_stack_expect — wedged remote workers must surface as
        unresponsive)."""
        with self._stack_lock:
            entry = self._profiles.get(profile_id)
            if entry is None:
                return
            entry["want"].update(w.hex() for w in worker_ids)
            entry["expects_pending"] -= 1
            evt = entry["event"]
        evt.set()

    def ctl_profile(self, duration_s: float = 2.0, hz: float = 67.0,
                    jax_profile: bool = False,
                    timeout_s: Optional[float] = None,
                    save: bool = True) -> Dict[str, Any]:
        """Cluster-wide on-demand profile: every live worker (plus the
        driver) samples its threads for ``duration_s``; the records are
        merged into ONE clock-aligned Chrome-trace JSON written under
        ``<session>/profiles/`` and returned inline.

        Blocking for duration + collection timeout: listed in
        _BLOCKING_CTL so a worker-originated call never runs on the
        node poller thread that must route the replies."""
        from ray_tpu.profiler.capture import capture_profile
        from ray_tpu.profiler.merge import (merge_records, write_jax_artifacts,
                                            write_trace)
        from .protocol import ProfileRequest
        if timeout_s is None:
            timeout_s = Config.get("stack_dump_timeout_s")
        duration_s = max(0.1, float(duration_s))
        nodes = list(self.nodes.values())
        remote_nodes = [n for n in nodes if getattr(n, "is_remote", False)]
        t0_wall = time.time()
        with self._stack_lock:
            self._profile_seq += 1
            profile_id = self._profile_seq
            entry: Dict[str, Any] = {"replies": {}, "want": set(),
                                     "expects_pending": len(remote_nodes),
                                     "event": threading.Event()}
            self._profiles[profile_id] = entry
        req = ProfileRequest(profile_id, duration_s, hz=hz,
                             jax_profile=jax_profile,
                             driver_wall_s=t0_wall)
        expected: List[WorkerID] = []
        for node in nodes:
            try:
                ids = node.broadcast_profile(req)
                if not getattr(node, "is_remote", False):
                    expected.extend(ids)
            except Exception:  # noqa: BLE001 — a dead node can't stop it
                with self._stack_lock:
                    if getattr(node, "is_remote", False):
                        entry["expects_pending"] -= 1
        with self._stack_lock:
            entry["want"].update(w.hex() for w in expected)
        # The driver samples itself on THIS thread (ctl_profile is
        # blocking-listed) while the workers capture in parallel.
        driver_record = capture_profile(
            "driver", duration_s, hz=hz, jax_profile=jax_profile,
            driver_wall_s=t0_wall, is_driver=True)
        self._settle_collect(entry, timeout_s)
        with self._stack_lock:
            self._profiles.pop(profile_id, None)
            replies = dict(entry["replies"])
            want = set(entry["want"])
        t1_wall = time.time()
        records = [driver_record] + [replies[k] for k in sorted(replies)]
        doc = merge_records(
            records,
            timeline_events=self.events.chrome_trace(),
            # Wall clock on purpose: the window selects timeline events
            # by their wall-anchored positions, not a duration.
            window=(t0_wall - 1.0, t1_wall + 1.0),  # ray-tpu: noqa[RT203]
            meta={"profile_id": profile_id, "duration_s": duration_s,
                  "hz": hz, "driver_t0_wall_s": t0_wall,
                  "unresponsive": sorted(want - set(replies))})
        path = None
        if save:
            pdir = os.path.join(self.session_dir, "profiles",
                                f"{time.strftime('%Y%m%d-%H%M%S')}-"
                                f"{profile_id:04d}")
            path = write_trace(os.path.join(pdir, "trace.json"), doc)
            write_jax_artifacts(pdir, records)
        telemetry.inc("ray_tpu_profiler_captures_total")
        return {
            "path": path,
            "trace": doc,
            "num_events": len(doc["traceEvents"]),
            "workers": sorted(replies),
            "unresponsive": sorted(want - set(replies)),
        }

    def ctl_debug_dump(self, reason: str = "manual",
                       capture_stacks: bool = True,
                       extra: Optional[Dict[str, Any]] = None,
                       profile_s: Optional[float] = None) -> str:
        """Write a postmortem bundle under <session>/debug/; returns its
        path (flight recorder, `ray-tpu debug dump`).  ``profile_s`` > 0
        attaches an on-demand cluster profile of that duration (None =
        the debug_bundle_profile_s config default)."""
        from .diagnostics import write_debug_bundle
        return write_debug_bundle(self, reason,
                                  capture_stacks=capture_stacks,
                                  extra=extra, profile_s=profile_s)

    def ctl_export_event(self, source_type: str, event: Dict[str, Any]):
        """Append a structured record to <session>/logs/events.jsonl on
        behalf of any process (train watchdog, user tooling)."""
        self._export_event(source_type, dict(event))
        return True

    def _export_event(self, source_type: str, event: Dict[str, Any]) -> None:
        try:
            self.export_events.write(source_type, event)
        except Exception as e:  # forensics never fail the caller
            telemetry.note_swallowed("runtime.export_event", e)

    def _maybe_death_bundle(self, reason: str,
                            extra: Dict[str, Any]) -> None:
        """Rate-limited flight-recorder capture on unexpected worker death
        (no stack broadcast: the dead worker can't answer, and the bundle
        must stay cheap on the failure path)."""
        if self._shutdown or not Config.get("debug_bundle_on_worker_death"):
            return
        now = time.monotonic()
        if self._last_death_bundle is not None and \
                now - self._last_death_bundle < Config.get(
                "debug_bundle_min_interval_s"):
            return
        self._last_death_bundle = now

        def run():
            try:
                from .diagnostics import write_debug_bundle
                write_debug_bundle(self, reason, capture_stacks=False,
                                   extra=extra)
            except Exception as e:
                telemetry.note_swallowed("runtime.death_bundle", e)
        sanitizer.spawn(run, name="death-bundle")

    # -- pubsub (reference: src/ray/pubsub/ long-poll publisher) ----------

    def ctl_publish(self, channel: str, message) -> None:
        self.controller.publish(channel, message)

    def ctl_pubsub_poll(self, channel: str, after_seq: int = 0,
                        timeout=None):
        return self.controller.pubsub_poll(channel, after_seq, timeout)

    def ctl_log_files(self):
        """Session log files + sizes (reference: state API list_logs)."""
        return self.log_monitor.list_files()

    def ctl_log_tail(self, filename: str, n: int = 100):
        """Last n lines of a session log file (reference: state API
        get_log)."""
        return self.log_monitor.tail(filename, n)

    def ctl_session_dir(self):
        return self.session_dir

    def ctl_timeline(self):
        return self.events.chrome_trace()

    def ctl_add_profile_span(self, spans):
        """A batch of finished spans, each the tuple that
        ``telemetry._emit_span`` makes: the head's own one at a time, a
        worker's with its metrics flush."""
        self.events.add_spans([ProfileSpan(*s) for s in spans])
        return True

    def ctl_telemetry_flushed(self):
        """A worker answered ``FlushTelemetry``: its spans and metrics
        frames came ahead of this one on the same connection."""
        self._flush_acks.release()
        return True

    def _collect_terminal_flush(self, timeout_s: float = 2.0) -> None:
        """Ask every live worker of this host for its buffered spans and
        final metrics, and wait (bounded) for the answers: the last
        seconds of a run are otherwise lost with the connection."""
        try:
            deadline = time.monotonic() + timeout_s
            for _ in self.node.broadcast_flush():
                if not self._flush_acks.acquire(
                        timeout=max(0.0, deadline - time.monotonic())):
                    break
        except Exception as e:  # noqa: BLE001 — shutdown goes on
            telemetry.note_swallowed("runtime.terminal_flush", e)

    def write_trace_files(self) -> Optional[str]:
        """``<session>/trace/spans.jsonl`` (every span the head holds,
        of every process, one JSON object a line), ``stalls.json`` (what
        ``telemetry.stalls`` reads out of them: the step periods that ran
        long and what each coincided with) and ``counters.json`` (the
        cluster-merged metric samples): what a finished run leaves for
        whoever reads it afterwards."""
        import json
        from ray_tpu.util import metrics as _metrics
        trace_dir = os.path.join(self.session_dir, "trace")
        try:
            os.makedirs(trace_dir, exist_ok=True)
            spans = [sp.to_dict() for sp in self.events.spans()]
            with open(os.path.join(trace_dir, "spans.jsonl"), "w") as f:
                for span in spans:
                    f.write(json.dumps(span, default=str) + "\n")
            with open(os.path.join(trace_dir, "stalls.json"), "w") as f:
                json.dump(telemetry.stalls(spans), f, default=str)
            by_name, acc = _metrics._aggregate_snapshots()
            counters = {
                "types": {n: m["type"] for n, m in by_name.items()},
                "samples": {
                    name: [{"tags": tags, "value": value}
                           for _k, (tags, value) in sorted(bucket.items())]
                    for name, bucket in acc.items()}}
            with open(os.path.join(trace_dir, "counters.json"), "w") as f:
                json.dump(counters, f)
        except Exception as e:  # noqa: BLE001 — shutdown goes on
            telemetry.note_swallowed("runtime.write_trace_files", e)
            return None
        return trace_dir

    _STORE_OP_KINDS = ("create", "seal", "get", "pin", "unpin", "delete")
    _STORE_SPILL_KEYS = (("spill", "num_spilled"),
                         ("restore", "num_restored"),
                         ("evict", "num_evictions"))

    def _store_metrics_state(self):
        state = getattr(self, "_store_pub", None)
        if state is None:
            state = self._store_pub = {"lock": threading.Lock(),
                                       "last": 0.0, "counts": {}}
        return state

    def _publish_store_metrics(self, force: bool = False) -> None:
        """Data-plane half of the telemetry flush: fold per-node object
        store occupancy into head-registry gauges and turn event-ring /
        stats tallies into counter deltas.  Piggybacks on the existing
        metrics flush (no second reporting loop) and is rate-limited so a
        busy cluster's flush storms don't rescan the views every push.
        Counter deltas are clamped at zero: a node that restarts resets
        its tallies, and a negative delta must not decrement a counter."""
        pub = self._store_metrics_state()
        now = time.monotonic()
        with pub["lock"]:
            if not force and now - pub["last"] < 1.0:
                return
            pub["last"] = now
        try:
            head = dict(self.node.store.stats())
            ring = getattr(self.node.store, "view", None)
            if ring is not None:
                head["counts"] = dict(ring.counts)
            per_node = {self.node_id.hex(): head}
            with self._node_views_lock:
                views = [(nid.hex(), view) for nid, (_v, view, _ts)
                         in self._node_views.items()]
            for nhex, view in views:
                sub = view.get("store")
                if isinstance(sub, dict):
                    per_node[nhex] = sub
            for nhex, sub in per_node.items():
                tags = {"node": nhex}
                telemetry.set_gauge("ray_tpu_store_used_bytes",
                                    int(sub.get("used_bytes", 0)), tags=tags)
                telemetry.set_gauge("ray_tpu_store_capacity_bytes",
                                    int(sub.get("capacity_bytes", 0)),
                                    tags=tags)
                telemetry.set_gauge("ray_tpu_store_pinned_bytes",
                                    int(sub.get("pinned_bytes", 0)),
                                    tags=tags)
                telemetry.set_gauge("ray_tpu_store_spilled_bytes",
                                    int(sub.get("spilled_bytes", 0)),
                                    tags=tags)
                telemetry.set_gauge("ray_tpu_store_objects",
                                    int(sub.get("num_objects", 0)),
                                    tags=tags)
                prev = pub["counts"].setdefault(nhex, {})
                counts = sub.get("counts") or {}
                for kind in self._STORE_OP_KINDS:
                    cur = int(counts.get(kind, 0))
                    delta = cur - prev.get(kind, 0)
                    if delta > 0:
                        telemetry.inc("ray_tpu_store_ops_total", delta,
                                      tags={"op": kind})
                    prev[kind] = cur
                for op, key in self._STORE_SPILL_KEYS:
                    cur = int(sub.get(key, 0))
                    delta = cur - prev.get("_" + op, 0)
                    if delta > 0:
                        telemetry.inc("ray_tpu_store_spill_ops_total",
                                      delta, tags={"op": op})
                    prev["_" + op] = cur
                # Remote nodes' transfers happen in THEIR processes:
                # _record_transfer incs a registry the merged scrape
                # never sees, so the bytes ride the synced ring tallies
                # instead.  The head's own entry is skipped — its
                # transfers already inc'd in-process (double count).
                if nhex == self.node_id.hex():
                    continue
                tb = sub.get("transfer_bytes") or {}
                for direction in ("push", "pull"):
                    cur = int(tb.get(direction, 0))
                    delta = cur - prev.get("_tb_" + direction, 0)
                    if delta > 0:
                        telemetry.inc(
                            "ray_tpu_store_transfer_bytes_total",
                            delta, tags={"direction": direction})
                    prev["_tb_" + direction] = cur
        except Exception as e:  # noqa: BLE001
            telemetry.note_swallowed("runtime.store_metrics", e)

    def ctl_metrics_push(self, source_id: str, snapshot):
        """One batched per-process metrics flush (util/metrics.py flush
        paths).  Stores the latest snapshot for the merged scrape AND
        gives the time-series backplane its ingest tick — piggybacked
        here so history needs no second reporting loop."""
        self.metrics_snapshots[source_id] = snapshot
        self._publish_store_metrics()
        self.metricsview.on_push()
        return True

    # Back-compat verb name (pre-metricsview workers).
    ctl_push_metrics = ctl_metrics_push

    def ctl_metrics_query(self, name: str, window_s: float = 60.0,
                          agg: str = "avg", tags=None):
        # Give the store gauges a flush chance first: a driver-only
        # session has no worker pushes to piggyback on.
        self._publish_store_metrics()
        return self.metricsview.query(name, window_s, agg, tags=tags)

    def ctl_metrics_history(self, name: str, window_s: float = 300.0,
                            tags=None, max_points: int = 240):
        return self.metricsview.history(name, window_s, tags=tags,
                                        max_points=max_points)

    def ctl_metrics_series(self):
        return self.metricsview.store.series_names()

    def ctl_alerts(self, recent: int = 50):
        return self.metricsview.alerts(recent=recent)

    def ctl_slo_set(self, objectives):
        return self.metricsview.set_objectives(objectives)

    def ctl_slo_list(self):
        return self.metricsview.slo.objectives()

    # -- tracing (reference: util/tracing/tracing_helper.py spans routed
    #    to a collector; here an in-memory bounded span table) ----------- #

    def ctl_add_trace_span(self, span: dict):
        buf = getattr(self, "_trace_spans", None)
        if buf is None:
            from collections import deque
            buf = self._trace_spans = deque(maxlen=50_000)
        buf.append(span)
        return True

    def ctl_get_trace_spans(self, trace_id=None):
        buf = getattr(self, "_trace_spans", None) or ()
        return [s for s in buf
                if trace_id is None or s.get("trace_id") == trace_id]

    def ctl_list_trace_ids(self):
        buf = getattr(self, "_trace_spans", None) or ()
        seen = dict.fromkeys(s.get("trace_id") for s in buf)
        return list(seen)

    # ------------------------------------------------------------------ #

    def shutdown(self) -> None:
        # While the workers' connections still stand: their last spans
        # and counters, then the two files a finished run leaves.
        self._collect_terminal_flush()
        self.write_trace_files()
        self._shutdown = True
        self.scheduler.stop()
        with self._actors_lock:
            asts = list(self._actors.values())
        for ast in asts:
            if ast.driver_ch is not None:
                ast.driver_ch.close()
        if self._gc_enabled:
            self._ref_drop_q.put(None)
        if self._xfer_q is not None:
            self._xfer_q.put(None)
            self._xfer_pool.shutdown(wait=False)
        if self.head_server is not None:
            self.head_server.shutdown()
        if self.data_server is not None:
            self.data_server.shutdown()
        if self._data_client is not None:
            self._data_client.shutdown()
        self.node.shutdown()
        if self.state_store is not None:
            # Clean shutdown: actors die with the cluster — only a CRASHED
            # head revives actors on restart.  Without this, a later
            # unrelated `start --head` on the same state dir would re-run
            # stale user actor code from the snapshot.
            try:
                for info in list(self.controller.actors.values()):
                    if info.state != DEAD:
                        self.controller.set_actor_state(
                            info.actor_id, DEAD,
                            death_cause="cluster shutdown")
                # Compact so the next start replays a snapshot instead of
                # the whole WAL.
                self.state_store.compact(self.controller.snapshot_records())
            except Exception as e:
                telemetry.note_swallowed("runtime.shutdown_compact", e)
            self.state_store.close()
        self.log_monitor.stop()
        self.log_monitor.poll_once()  # flush buffered worker output
        self.export_events.close()
        for shm in self._mapped_segments.values():
            try:
                shm.close()
            except Exception:  # ray-tpu: noqa[RT202] — best-effort teardown
                pass
        self._mapped_segments.clear()
        self.controller.finish_job(self.job_id)
        global _global_runtime
        with _runtime_lock:
            if _global_runtime is self:
                _global_runtime = None


def init_runtime(**kwargs) -> Runtime:
    global _global_runtime
    with _runtime_lock:
        if _global_runtime is not None:
            return _global_runtime
        # Leak-sanitizer baseline BEFORE the Runtime boots: the
        # runtime's own long-lived threads (ref-gc, head-accept,
        # node-dispatch, ...) must be inside the gate — a regression
        # that leaves one running after shutdown() is exactly what the
        # ratchet exists to catch (RAY_TPU_SANITIZE=1).
        sanitizer.snapshot()
        rt = Runtime(**kwargs)
        _global_runtime = rt
    return rt
