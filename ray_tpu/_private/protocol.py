"""Wire-level structures exchanged between driver, node manager and workers.

The reference expresses these as protobufs (reference: src/ray/protobuf/
common.proto TaskSpec, node_manager.proto, core_worker.proto) carried over
gRPC; here they are small dataclasses carried over multiprocessing pipes
(pickle).  The shape is kept close to ``TaskSpecification`` (reference:
src/ray/common/task/task_spec.h:82) so a later native transport can swap in
underneath without touching the scheduler or API layers.

Value descriptors (how an argument/return travels):
    ("inline", payload_bytes)            — packed payload, small objects
    ("shm", name, nbytes)                — dedicated shared-memory segment
    ("shma", segment, offset, nbytes, id_bytes)
                                         — slot in the node's C++ arena store;
                                           offset valid only while pinned
    ("err", payload_bytes)               — serialized exception
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from .ids import ActorID, ObjectID, PlacementGroupID, TaskID, WorkerID
from .resources import ResourceSet

ValueDesc = Tuple  # ("inline", bytes) | ("shm", str, int) | ("err", bytes)


@dataclass
class TaskSpec:
    task_id: TaskID
    name: str
    # One of: serialized function (normal task / actor ctor) or method name.
    fn_blob: Optional[bytes]
    method_name: Optional[str]
    # Args are ObjectIDs (dependencies) or already-serialized inline values.
    arg_descs: List[Tuple[str, Any]]  # ("ref", ObjectID) | ("val", bytes)
    kwarg_descs: Dict[str, Tuple[str, Any]]
    return_ids: List[ObjectID]
    resources: ResourceSet
    actor_id: Optional[ActorID] = None        # actor method target
    create_actor_id: Optional[ActorID] = None  # actor construction
    max_retries: int = 0
    retry_count: int = 0
    placement_group: Optional[PlacementGroupID] = None
    bundle_index: int = -1
    scheduling_strategy: Optional[Any] = None
    runtime_env: Optional[Dict[str, Any]] = None
    max_concurrency: int = 1
    submitter: str = "driver"  # worker id hex of the submitting process
    # num_returns="streaming": results stream item-by-item as
    # ObjectID.of(task_id, i); a ("end",) marker closes the stream
    # (reference: ObjectRefStream, src/ray/core_worker/task_manager.h:86).
    streaming: bool = False
    # Stable identity of fn_blob (reference: the GCS function table —
    # functions are exported once and referenced by id).  When set, the
    # node strips fn_blob for workers that have already received it, and
    # workers reuse the unpickled callable instead of re-loading per task.
    fn_id: Optional[bytes] = None
    # W3C traceparent of the submit span (reference:
    # tracing_helper.py:34 — span context propagated in task metadata);
    # None unless tracing is enabled on the submitting process.
    trace_ctx: Optional[str] = None
    # ObjectIDs pickled INSIDE argument values (nested refs): tracked as
    # borrows — retained until this task completes, escalated to
    # escaped-forever only if the worker still holds them afterwards
    # (reference: reference_counter.h:44 borrower bookkeeping).
    nested_refs: Tuple = ()


@dataclass
class RunTask:
    """node -> worker: execute a task whose args are fully resolved."""
    spec: TaskSpec
    resolved_args: List[ValueDesc]
    resolved_kwargs: Dict[str, ValueDesc]


@dataclass
class TaskDone:
    """worker -> node: task finished."""
    task_id: TaskID
    worker_id: WorkerID
    results: List[Tuple[ObjectID, ValueDesc]]
    error: Optional[ValueDesc] = None
    is_application_error: bool = False
    actor_id: Optional[ActorID] = None
    execution_time_s: float = 0.0


@dataclass
class SubmitFromWorker:
    """worker -> node: nested task/actor submission."""
    spec: TaskSpec


@dataclass
class GetRequest:
    """worker -> node: resolve object values for a blocking get."""
    request_id: int
    worker_id: WorkerID
    object_ids: List[ObjectID]
    timeout_s: Optional[float] = None


@dataclass
class GetReply:
    """node -> worker."""
    request_id: int
    values: List[ValueDesc]
    timed_out: bool = False


@dataclass
class WaitRequest:
    request_id: int
    worker_id: WorkerID
    object_ids: List[ObjectID]
    num_returns: int
    timeout_s: Optional[float]
    fetch_local: bool = True


@dataclass
class WaitReply:
    request_id: int
    ready: List[ObjectID]


@dataclass
class PutFromWorker:
    """worker -> node: register a worker-created object."""
    object_id: ObjectID
    desc: ValueDesc
    owner_hint: Optional[str] = None


@dataclass
class ActorStateMsg:
    """worker -> node: actor constructor finished / actor died.

    ``direct_addr`` is the worker's direct-call listener (direct.py):
    peers push actor calls straight to it after resolving through the
    head (reference: actor_task_submitter.h:68 caller->actor stream)."""
    actor_id: ActorID
    state: str  # "alive" | "error"
    error: Optional[ValueDesc] = None
    direct_addr: Optional[Tuple[str, int]] = None


@dataclass
class KillWorker:
    reason: str = ""


@dataclass
class WorkerReady:
    worker_id: WorkerID
    pid: int


@dataclass
class AllocRequest:
    """worker -> node: reserve an arena slot for a large result (plasma
    Create RPC equivalent)."""
    request_id: int
    worker_id: WorkerID
    object_id: ObjectID
    nbytes: int


@dataclass
class AllocReply:
    """node -> worker: (segment, offset) grant, or segment=None on failure
    (worker falls back to a dedicated shm segment)."""
    request_id: int
    segment: Optional[str]
    offset: int = -1


@dataclass
class SealObject:
    """worker -> node: arena slot fully written; object now readable."""
    object_id: ObjectID


@dataclass
class BorrowRetained:
    """worker -> node: these borrowed refs are still alive in the worker
    after its task finished (e.g. stored in actor state): the owner must
    stop auto-collecting them (escape fallback)."""
    object_ids: List[ObjectID]


@dataclass
class ContainedRefs:
    """worker -> node: ``inner`` ObjectRefs were serialized INSIDE the
    value of ``outer`` (a task result / stream item / worker put).  The
    owner retains the inner objects for exactly as long as the outer
    object lives — freeing the outer releases them — instead of pinning
    them forever (reference: reference_counter.h:44 nested-ref
    containment via serializer hooks)."""
    outer: ObjectID
    inner: List[ObjectID]


@dataclass
class ReadDone:
    """worker -> node: descriptors from a GetReply are no longer referenced.
    retain=True (actor context) transfers the pins to the worker's lifetime
    instead of releasing them, since the actor may hold zero-copy views."""
    request_id: int
    retain: bool = False


@dataclass
class StackDumpRequest:
    """node -> worker: snapshot every thread's Python stack (reference:
    ``ray stack`` / the py-spy dump the dashboard triggers).  Handled on
    the worker's receive thread — NOT the executor pool — so a worker
    whose task threads are wedged still answers; that is the whole point
    of the diagnostic."""
    dump_id: int


@dataclass
class StackDumpReply:
    """worker -> node: the ``sys._current_frames()`` snapshot plus the
    task/actor identity each thread was executing (see
    diagnostics.capture_process_stacks for the record shape)."""
    dump_id: int
    worker_id: WorkerID
    record: Dict


@dataclass
class ProfileRequest:
    """node -> worker: profile this process for ``duration_s`` (host
    thread sampling at ``hz``; optionally a jax.profiler window) and
    reply with the capture record.  Received on the worker's RECEIVE
    thread — like stack capture — but the blocking capture itself runs
    on a spawned thread so replies/tasks keep flowing meanwhile.
    ``driver_wall_s`` is the driver's clock at send time: the worker
    reports its clock offset against it so the driver can merge every
    process's events onto one clock."""
    profile_id: int
    duration_s: float
    hz: float = 67.0
    jax_profile: bool = False
    driver_wall_s: float = 0.0


@dataclass
class ProfileReply:
    """worker -> node: one process's capture record (see
    profiler/capture.py for the shape; ``record["error"]`` set when the
    capture could not run, e.g. one was already in flight)."""
    profile_id: int
    worker_id: WorkerID
    record: Dict


@dataclass
class FlushTelemetry:
    """node -> worker: ship the buffered profile spans and a final metrics
    snapshot now (fire and forget), then answer with the control call
    ``telemetry_flushed``.  Handled on the worker's receive thread, so a
    worker busy in a task still answers.  The head sends it at shutdown,
    before it closes the connections."""


@dataclass
class RpcCall:
    """worker -> node: generic control-plane call (KV, actor lookup, ...)."""
    request_id: int
    worker_id: WorkerID
    method: str
    args: Tuple
    kwargs: Dict


@dataclass
class RpcReply:
    request_id: int
    value: Any = None
    error: Optional[str] = None
