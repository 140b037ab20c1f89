"""Worker process: task execution loop.

The reference's worker is a language process embedding the C++ CoreWorker
(reference: src/ray/core_worker/core_worker.h:167) — a gRPC server receiving
PushTask, a TaskReceiver with per-concurrency-group thread/fiber pools
(reference: task_execution/task_receiver.h:43), and client stubs for
submitting nested work.  Here the worker is a spawned Python process with a
receiver thread (the transport endpoint), an executor pool (the concurrency
groups), and a ``WorkerRuntime`` that the public API routes through when
called from inside a task — so nested ``.remote()`` / ``get`` / ``put`` work
exactly as on the driver (reference: core_worker.h SubmitTask/Get/Put).
"""

from __future__ import annotations

import os
import queue
import threading
import traceback
from typing import Any, Dict, List, Optional, Tuple

from . import serialization, wire
from .config import Config
from .exceptions import TaskError
from .ids import ActorID, ObjectID, TaskID, WorkerID
from .object_store import ArenaReader, RemoteObjectReader
from .protocol import (ActorStateMsg, AllocReply, AllocRequest,
                       BorrowRetained, FlushTelemetry, GetReply,
                       GetRequest, KillWorker,
                       ProfileReply, ProfileRequest, PutFromWorker,
                       ReadDone, RpcCall, RpcReply, RunTask, SealObject,
                       StackDumpReply, StackDumpRequest, SubmitFromWorker,
                       TaskDone, WaitReply, WaitRequest, WorkerReady)


def _materialize(desc, keepalives: List, rt=None) -> Any:
    kind = desc[0]
    if kind == "inline":
        return serialization.unpack_payload(desc[1])
    if kind == "shm":
        value, shm = RemoteObjectReader.read(desc[1], desc[2])
        keepalives.append(shm)
        return value
    if kind == "shma":
        value, shm = ArenaReader.read(desc)
        keepalives.append(shm)
        return value
    if kind == "err":
        raise serialization.unpack_payload(desc[1])
    if kind == "ref" and rt is not None:
        # Unresolved dependency (direct worker->worker call frames carry
        # raw refs; the callee resolves): blocks until the value lands.
        return rt.get([ObjectID(desc[1])])[0]
    raise ValueError(f"unknown value descriptor {kind!r}")


def _serialize_result(rt: "WorkerRuntime", object_id: ObjectID, value: Any):
    meta, buffers = serialization.serialize_payload(value)
    nbytes = serialization.payload_nbytes(meta, buffers)
    if nbytes <= Config.get("max_inline_object_size"):
        out = bytearray(nbytes)
        serialization.write_payload_into(memoryview(out), meta, buffers)
        return ("inline", bytes(out))
    # Preferred path: zero-copy write into the node's C++ arena store
    # (plasma Create/Seal protocol). Fallback: dedicated shm segment.
    if rt.arena_segment:
        grant = rt.alloc_arena(object_id, nbytes)
        if grant is not None:
            seg, off = grant
            ArenaReader.write(seg, off, meta, buffers)
            rt.send(SealObject(object_id))
            return ("shma", seg, off, nbytes, object_id.binary())
    shm_name, nbytes = RemoteObjectReader.write_payload(object_id, meta,
                                                        buffers)
    return ("shm", shm_name, nbytes)


class WorkerRuntime:
    """Runtime facade available inside a worker process.

    Implements the same surface the driver Runtime exposes to the public API
    (submit/get/put/wait/kv/actor lookup), forwarding over the worker pipe.
    """

    def __init__(self, conn, worker_id: WorkerID, job_id):
        self.conn = conn
        self.worker_id = worker_id
        self.job_id = job_id
        self._send_lock = threading.Lock()
        # Outgoing messages coalesce through a sender thread (mirror of the
        # node's _send_loop): everything queued since the last write goes
        # out as one list frame.  FIFO preserves Seal-before-TaskDone and
        # alive-before-results ordering.
        import collections
        self._outbox: Any = collections.deque()
        self._out_ev = threading.Event()
        self._send_closed = False
        self._sender = threading.Thread(target=self._send_loop,
                                        name="worker-sender", daemon=True)
        self._sender.start()
        self._req_lock = threading.Lock()
        self._next_req = 0
        self._pending: Dict[int, queue.Queue] = {}
        self.current_task_id: Optional[TaskID] = None
        self.current_actor_id: Optional[ActorID] = None
        # thread ident -> (task_id_hex, task_name) while that thread runs a
        # task: lets a StackDumpRequest name what each thread executes
        # (concurrent actor methods make the single current_task_id racy).
        self.thread_tasks: Dict[int, Tuple[str, str]] = {}
        self._obj_index_lock = threading.Lock()
        self._obj_index = 1 << 20  # put-objects live above return indices
        self.arena_segment = os.environ.get("RAY_TPU_ARENA_SEG") or None
        # Per-task deferred pin releases for GetReply descriptors: released
        # when the task that materialized them finishes (its zero-copy views
        # die with it). Thread-local so concurrent tasks don't cross-release.
        self._tls = threading.local()
        # -- direct worker->worker actor calls (see direct.py) ------------ #
        # Caller-owned results of direct calls live here (oid bytes ->
        # _LocalObject); the head only learns about them on escape.
        tok = os.environ.get("RAY_TPU_DIRECT_TOKEN")
        self.direct_token = bytes.fromhex(tok) if tok else None
        self._local_lock = threading.Lock()
        self._local_objects: Dict[bytes, Any] = {}
        self._channels: Dict[bytes, Any] = {}   # actor_id bytes -> channel
        self._direct_mode: Dict[bytes, str] = {}  # "direct" | "classic"

    # -- direct-call plumbing (caller side) -------------------------------- #

    def local_ready(self, oid_bytes: bytes, desc) -> None:
        with self._local_lock:
            lo = self._local_objects.get(oid_bytes)
            if lo is None:
                return
            promote = lo.promote_on_ready and desc[0] in ("inline", "err")
            lo.set(desc)
            lo.promote_on_ready = False
            if lo.refcount <= 0 and lo.ref_seen and not promote:
                # Fire-and-forget call whose ref was created AND dropped:
                # nothing will ever read this result — don't accumulate
                # it.  ref_seen guards the submit window where the reply
                # can land before the caller has built its ObjectRef.
                self._local_objects.pop(oid_bytes, None)
        if promote:
            self.send(PutFromWorker(ObjectID(oid_bytes), desc))

    def promote_local(self, object_id) -> None:
        """A direct-call result ref escapes this process (pickled into a
        task arg / user payload): register it with the head so classic
        resolution works anywhere (reference: borrow registration,
        reference_counter.h:44).  Pending results promote on arrival."""
        ob = object_id.binary() if not isinstance(object_id, bytes) \
            else object_id
        with self._local_lock:
            lo = self._local_objects.get(ob)
            if lo is None:
                return
            if not lo.event.is_set():
                lo.promote_on_ready = True
                return
        if lo.desc[0] in ("inline", "err"):
            self.send(PutFromWorker(ObjectID(ob), lo.desc))

    def drop_local(self, oid_bytes: bytes) -> None:
        with self._local_lock:
            lo = self._local_objects.get(oid_bytes)
            if lo is None:
                return
            lo.refcount -= 1
            if lo.refcount <= 0 and lo.event.is_set() \
                    and not lo.promote_on_ready:
                # Pending entries (event unset) are cleaned by
                # local_ready when the reply lands and refcount is 0.
                self._local_objects.pop(oid_bytes, None)

    def note_local_ref(self, oid_bytes: bytes) -> None:
        with self._local_lock:
            lo = self._local_objects.get(oid_bytes)
            if lo is not None:
                lo.refcount += 1
                lo.ref_seen = True

    def note_new_ref(self, ref) -> None:
        """Every ObjectRef constructed in this worker passes through here:
        local-table refcounting plus borrow tracking while task args are
        being materialized (reference: reference_counter.h:44 borrower
        registration on deserialization)."""
        self.note_local_ref(ref._id.binary())
        borrows = getattr(self._tls, "arg_borrows", None)
        if borrows is not None:
            import weakref
            try:
                borrows.append((weakref.ref(ref), ref._id))
            except TypeError:
                pass

    def begin_arg_borrows(self) -> None:
        self._tls.arg_borrows = []

    def end_arg_borrows(self) -> list:
        borrows = getattr(self._tls, "arg_borrows", None)
        self._tls.arg_borrows = None
        return borrows or []

    def report_retained_borrows(self, borrows: list) -> None:
        """After the task: any arg-borrowed ref still alive (stored in
        actor state, a module global, ...) escalates to owner-side
        escaped pinning — the bounded fallback."""
        survivors = [oid for (wref, oid) in borrows
                     if wref() is not None]
        if survivors:
            self.send(BorrowRetained(survivors))

    def submit_actor_direct(self, actor_id, task_id, name: str,
                            method_name: Optional[str], return_ids: List,
                            args: list, kwargs: dict,
                            max_concurrency: int, streaming: bool,
                            fn_blob: Optional[bytes] = None) -> bool:
        """Push an actor call straight to the actor's worker over this
        process's channel.  Mode (direct vs classic) is sticky per actor
        so the two paths never interleave for one caller (ordering)."""
        if self.direct_token is None:
            return False
        ab = actor_id.binary()
        mode = self._direct_mode.get(ab)
        if mode is None:
            try:
                res = self.control("resolve_actor_direct", ab)
            except Exception:
                res = None
            state = res[0] if res else "unknown"
            if state == "alive" and res[1] is not None:
                mode = "direct"
            else:
                # Classic is STICKY: once any call from this process rode
                # the head's dispatch queue, later direct pushes could
                # overtake it on a separate socket and break per-caller
                # ordering — so this caller stays classic for this actor.
                mode = "classic"
            self._direct_mode[ab] = mode
        if mode != "direct":
            return False
        from .direct import DirectChannel
        ch = self._channels.get(ab)
        if ch is None:
            ch = self._channels.setdefault(
                ab, DirectChannel(self, actor_id))
            with ch.lock:
                ch._ensure_resolver_locked()
        tb = task_id.binary()
        if not streaming:
            with self._local_lock:
                from .direct import _LocalObject
                for oid in return_ids:
                    self._local_objects[oid.binary()] = _LocalObject()
        frame = (wire.RUN_TASK, tb, name, fn_blob, None, method_name,
                 tuple(r.binary() for r in return_ids), ab,
                 streaming, max_concurrency, None, args, kwargs, None)
        ch.submit(frame, return_ids)
        return True

    # -- plumbing -----------------------------------------------------------

    def send(self, msg) -> None:
        self._outbox.append(msg)
        self._out_ev.set()

    def _send_loop(self) -> None:
        outbox, ev = self._outbox, self._out_ev
        while True:
            ev.wait()
            ev.clear()
            batch: List = []
            while True:
                try:
                    batch.append(outbox.popleft())
                except IndexError:
                    break
            if batch:
                try:
                    with self._send_lock:
                        self.conn.send(batch if len(batch) > 1 else batch[0])
                except (BrokenPipeError, OSError):
                    return  # node gone; recv loop exits the process
                except Exception:
                    # Unpicklable message: send individually so one bad
                    # frame can't kill the sender (which would silently
                    # wedge every future TaskDone/reply).
                    for m in batch:
                        try:
                            with self._send_lock:
                                self.conn.send(m)
                        except (BrokenPipeError, OSError):
                            return
                        except Exception:
                            traceback.print_exc()
            if self._send_closed and not outbox:
                return

    def flush_and_close(self, timeout: float = 2.0) -> None:
        """Drain queued messages (the final TaskDone must hit the wire
        before os._exit)."""
        self._send_closed = True
        self._out_ev.set()
        self._sender.join(timeout=timeout)

    def _call(self, make_msg, timeout: Optional[float] = None):
        with self._req_lock:
            self._next_req += 1
            rid = self._next_req
            q: queue.Queue = queue.Queue()
            self._pending[rid] = q
        self.send(make_msg(rid))
        try:
            return q.get(timeout=timeout)
        finally:
            with self._req_lock:
                self._pending.pop(rid, None)

    def deliver_reply(self, request_id: int, reply) -> None:
        with self._req_lock:
            q = self._pending.get(request_id)
        if q is not None:
            q.put(reply)

    # -- API surface --------------------------------------------------------

    def submit_spec(self, spec) -> None:
        # Caller-local direct-call results used as args must be
        # registered with the head before it resolves this spec's deps.
        for kind, p in list(spec.arg_descs) + list(spec.kwarg_descs.values()):
            if kind == "ref":
                self.promote_local(p)
        self.send(SubmitFromWorker(spec))

    def get(self, object_ids: List[ObjectID], timeout: Optional[float] = None):
        # Safe bare read: empty-dict fast path; _split_local takes the
        # lock before touching individual entries.
        if self._local_objects:  # ray-tpu: noqa[RT401]
            local = self._split_local(object_ids, timeout)
            if local is not None:
                return local
        reply: GetReply = self._call(
            lambda rid: GetRequest(rid, self.worker_id, object_ids, timeout),
            timeout=None)
        has_arena = any(isinstance(d, tuple) and d and d[0] == "shma"
                        for d in reply.values)
        if reply.timed_out:
            # The node pinned the ready arena objects before replying; no
            # views were created, so release immediately.
            if has_arena:
                self._send_read_done(reply.request_id, retain=False)
            from .exceptions import GetTimeoutError
            raise GetTimeoutError(f"get timed out on {object_ids}")
        keepalives: List = []
        values = None
        try:
            values = [_materialize(d, keepalives) for d in reply.values]
            return values
        finally:
            if has_arena:
                arena_values = None
                if values is not None:
                    arena_values = [v for d, v in zip(reply.values, values)
                                    if isinstance(d, tuple) and d
                                    and d[0] == "shma"]
                self._note_arena_read(reply.request_id, arena_values)

    def _split_local(self, object_ids: List[ObjectID],
                     timeout: Optional[float] = None):
        """Resolve ids that are local direct-call results without a head
        round-trip; the rest go through the classic get.  Returns ordered
        values, or None when nothing is local."""
        with self._local_lock:
            entries = [self._local_objects.get(o.binary())
                       for o in object_ids]
        if not any(e is not None for e in entries):
            return None
        values: List[Any] = [None] * len(object_ids)
        classic_ids: List[ObjectID] = []
        classic_pos: List[int] = []
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        for i, (oid, lo) in enumerate(zip(object_ids, entries)):
            if lo is None:
                classic_ids.append(oid)
                classic_pos.append(i)
                continue
            remaining = None if deadline is None \
                else deadline - _time.monotonic()
            if not lo.event.wait(remaining):
                from .exceptions import GetTimeoutError
                raise GetTimeoutError(f"get timed out on {oid}")
            desc = lo.desc
            if desc[0] == "err":
                raise serialization.unpack_payload(desc[1])
            if desc[0] == "inline":
                values[i] = serialization.unpack_payload(desc[1])
            else:
                # Result registered upstream (non-inline): the head owns
                # it now — drop the local entry (else the classic get
                # below would re-enter this path forever) and resolve
                # through the head.
                with self._local_lock:
                    self._local_objects.pop(oid.binary(), None)
                classic_ids.append(oid)
                classic_pos.append(i)
        if classic_ids:
            remaining = None if deadline is None \
                else max(deadline - _time.monotonic(), 0.0)
            rest = self.get(classic_ids, remaining)
            for pos, v in zip(classic_pos, rest):
                values[pos] = v
        return values

    def _send_read_done(self, request_id: int, retain: bool) -> None:
        try:
            self.send(ReadDone(request_id, retain))
        except (BrokenPipeError, OSError):
            pass  # node gone; pins die with it

    def _note_arena_read(self, request_id: int, arena_values) -> None:
        """Schedule the pin release for a GetReply holding arena descriptors.

        Task context: released when the task ends (its views die with it).
        Actor context: the actor may retain zero-copy views in its state, so
        release when the *values* are garbage-collected (plasma buffer
        release semantics); values that can't carry a weakref fall back to
        worker-lifetime pins. No context / materialize error: release now.
        """
        if self.current_actor_id is None:
            deferred = getattr(self._tls, "read_dones", None)
            if deferred is not None:
                deferred.append(request_id)
            else:
                self._send_read_done(request_id, retain=False)
            return
        if not arena_values:
            self._send_read_done(request_id, retain=False)
            return
        import weakref
        remaining = {"n": len(arena_values)}
        rlock = threading.Lock()

        def one_collected():
            with rlock:
                remaining["n"] -= 1
                done = remaining["n"] == 0
            if done:
                self._send_read_done(request_id, retain=False)

        finalizers = []
        try:
            for v in arena_values:
                finalizers.append(weakref.finalize(v, one_collected))
        except TypeError:
            # Some value can't be weakly referenced: pin for the worker's
            # lifetime instead (node releases at worker death).
            for f in finalizers:
                f.detach()
            self._send_read_done(request_id, retain=True)

    def begin_task_reads(self) -> None:
        self._tls.read_dones = []

    def flush_task_reads(self) -> None:
        deferred = getattr(self._tls, "read_dones", None)
        self._tls.read_dones = None
        for rid in deferred or ():
            self.send(ReadDone(rid, retain=False))

    def alloc_arena(self, object_id: ObjectID, nbytes: int):
        reply: AllocReply = self._call(
            lambda rid: AllocRequest(rid, self.worker_id, object_id, nbytes))
        if reply.segment is None:
            return None
        return reply.segment, reply.offset

    def wait(self, object_ids: List[ObjectID], num_returns: int,
             timeout: Optional[float], fetch_local: bool = True):
        local_map = {}
        if self._local_objects:
            with self._local_lock:
                for o in object_ids:
                    lo = self._local_objects.get(o.binary())
                    if lo is not None:
                        local_map[o] = lo
        if not local_map:
            reply: WaitReply = self._call(
                lambda rid: WaitRequest(rid, self.worker_id, object_ids,
                                        num_returns, timeout, fetch_local))
            ready_set = set(reply.ready)
            ready = [o for o in object_ids if o in ready_set]
            not_ready = [o for o in object_ids if o not in ready_set]
            return ready, not_ready
        # Mixed local/classic: poll in slices — local results complete via
        # channel replies, the rest via short head waits.
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        classic = [o for o in object_ids if o not in local_map]
        while True:
            ready = []
            for o in object_ids:
                lo = local_map.get(o)
                if lo is not None and lo.event.is_set():
                    ready.append(o)
            classic_ready: set = set()
            if classic:
                # 0.5s slices bound the polling load on the head while
                # local channel replies keep landing concurrently.
                reply = self._call(
                    lambda rid: WaitRequest(
                        rid, self.worker_id, classic,
                        len(classic), 0.5, fetch_local))
                classic_ready = set(reply.ready)
                ready.extend(o for o in object_ids if o in classic_ready)
            if len(ready) >= num_returns or (
                    deadline is not None
                    and _time.monotonic() >= deadline):
                ready = ready[:max(num_returns, 0)] \
                    if len(ready) > num_returns else ready
                rset = set(ready)
                return ready, [o for o in object_ids if o not in rset]
            if not classic:
                remaining = None if deadline is None \
                    else deadline - _time.monotonic()
                # Pure local: block on the first unready event in slices.
                pending = [lo for o, lo in local_map.items()
                           if not lo.event.is_set()]
                if pending:
                    pending[0].event.wait(
                        0.05 if remaining is None
                        else min(0.05, max(remaining, 0.0)))

    def put(self, value: Any) -> ObjectID:
        task_id = self.current_task_id or TaskID.for_driver(self.job_id)
        with self._obj_index_lock:
            self._obj_index += 1
            idx = self._obj_index
        object_id = ObjectID.of(task_id, idx)
        # Refs inside the value: containment-retained by the owner for
        # this object's lifetime (see _run_task's result handling).
        from .api import _nested_collector
        inner: list = []
        token = _nested_collector.set(inner)
        try:
            desc = _serialize_result(self, object_id, value)
        finally:
            _nested_collector.reset(token)
        if inner:
            from .protocol import ContainedRefs
            self.send(ContainedRefs(object_id, list(inner)))
        self.send(PutFromWorker(object_id, desc))
        return object_id

    def control(self, method: str, *args, **kwargs):
        """Generic control-plane call (KV, named actors, PGs, metadata)."""
        reply: RpcReply = self._call(
            lambda rid: RpcCall(rid, self.worker_id, method, args, kwargs))
        if reply.error is not None:
            raise RuntimeError(reply.error)
        return reply.value


class _TaskPool:
    """Minimal thread pool: SimpleQueue + persistent threads.  Replaces
    ThreadPoolExecutor on the task path — no Future allocation, no
    work-item wrapper, ~10us less per submit."""

    def __init__(self, size: int = 1):
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._size = 0
        self.resize(size)

    def resize(self, n: int) -> None:
        while self._size < n:
            self._size += 1
            from . import sanitizer
            sanitizer.spawn(self._loop, name="task-exec")

    @property
    def size(self) -> int:
        return self._size

    def submit(self, fn, arg) -> None:
        self._q.put((fn, arg))

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, arg = item
            try:
                fn(arg)
            except Exception:
                traceback.print_exc()

    def shutdown(self) -> None:
        for _ in range(self._size):
            self._q.put(None)


class WorkerLoop:
    def __init__(self, conn, worker_id: WorkerID, job_id):
        self.runtime = WorkerRuntime(conn, worker_id, job_id)
        # fn_id -> unpickled callable (reference: worker-side function
        # cache over the GCS function table) — repeated tasks on the same
        # function skip both the blob bytes on the wire and the unpickle.
        self._fn_cache: Dict[bytes, Any] = {}
        self.actor_instance: Any = None
        self.actor_id: Optional[ActorID] = None
        self._executor = _TaskPool(1)
        self._actor_lock = threading.Lock()
        # With max_concurrency > 1 the executor pool may pick up method
        # tasks while __init__ is still running on another thread; methods
        # gate on this event (set when construction finishes or fails).
        self._actor_ready = threading.Event()
        # Shm segments backing zero-copy views that an actor may retain in
        # its state must outlive the task that mapped them.
        self._actor_keepalives: List = []
        self._direct_server: Any = None

    def _direct_addr(self) -> Optional[Tuple[str, int]]:
        """Start (once) and advertise this worker's direct-call listener —
        peers push actor calls straight here (direct.py)."""
        if self.runtime.direct_token is None:
            return None
        if self._direct_server is None:
            try:
                from .direct import DirectServer
                self._direct_server = DirectServer(
                    self, self.runtime.direct_token,
                    host=os.environ.get("RAY_TPU_DIRECT_HOST",
                                        "127.0.0.1"))
            except Exception:
                traceback.print_exc()
                return None
        return self._direct_server.address

    def _load_fn(self, spec) -> Any:
        """Resolve the task's callable: cached by fn_id, blob from the
        spec, or fetched from the driver's function table (stripped spec
        raced a lost first delivery)."""
        if spec.fn_id is None:
            return serialization.loads_control(spec.fn_blob)
        fn = self._fn_cache.get(spec.fn_id)
        if fn is None:
            blob = spec.fn_blob
            if blob is None:
                blob = self.runtime.control("get_fn_blob", spec.fn_id)
                if blob is None:
                    raise RuntimeError(
                        f"function {spec.fn_id.hex()} not in the driver "
                        "function table")
            fn = serialization.loads_control(blob)
            self._fn_cache[spec.fn_id] = fn
        return fn

    # -- task execution -----------------------------------------------------

    def _run_task(self, msg: RunTask, deliver=None) -> None:
        spec = msg.spec
        trace_ctx = getattr(spec, "trace_ctx", None)
        if trace_ctx is not None:
            # Execute span + context install: nested submits inside the
            # task join the same trace (reference: tracing_helper.py:181).
            from ray_tpu.util import tracing
            with tracing.task_span(trace_ctx, spec.name,
                                   spec.task_id.hex()):
                self._run_task_inner(msg, deliver)
        else:
            self._run_task_inner(msg, deliver)

    def _run_task_inner(self, msg: RunTask, deliver=None) -> None:
        spec = msg.spec
        rt = self.runtime
        rt.current_task_id = spec.task_id
        _tident = threading.get_ident()
        rt.thread_tasks[_tident] = (spec.task_id.hex(), spec.name)
        # Actor tasks may stash zero-copy arg views in actor state, so their
        # backing shm segments live as long as the actor.
        is_actor_task = (spec.create_actor_id is not None
                         or spec.actor_id is not None)
        if is_actor_task:
            keepalives = self._actor_keepalives
            # Set before __init__ runs so gets inside the constructor pin
            # with actor-lifetime (retain) semantics.
            if spec.create_actor_id is not None:
                rt.current_actor_id = spec.create_actor_id
        else:
            keepalives = []
            rt.begin_task_reads()
        results: List[Tuple[ObjectID, tuple]] = []
        error = None
        is_app_error = False
        import time as _time
        t0 = _time.monotonic()
        borrows: list = []
        try:
            if spec.runtime_env and spec.runtime_env.get("env_vars"):
                os.environ.update(spec.runtime_env["env_vars"])
            # Refs unpickled out of the args are borrows: tracked so
            # still-alive ones escalate to owner pinning at task end.
            rt.begin_arg_borrows()
            try:
                args = [_materialize(d, keepalives, rt)
                        for d in msg.resolved_args]
                kwargs = {k: _materialize(d, keepalives, rt)
                          for k, d in msg.resolved_kwargs.items()}
            finally:
                borrows = rt.end_arg_borrows()
            if spec.create_actor_id is not None:
                try:
                    cls = self._load_fn(spec)
                    self.actor_instance = cls(*args, **kwargs)
                except BaseException as init_exc:  # noqa: BLE001
                    self._actor_init_error = init_exc
                    raise
                finally:
                    self._actor_ready.set()
                self.actor_id = spec.create_actor_id
                rt.current_actor_id = spec.create_actor_id
                rt.send(ActorStateMsg(spec.create_actor_id, "alive",
                                      direct_addr=self._direct_addr()))
                value_list = [None] * len(spec.return_ids)
            elif spec.actor_id is not None:
                if self.actor_instance is None:
                    # No timeout: __init__ may legitimately take as long as
                    # a large-model load/compile on a TPU slice.
                    self._actor_ready.wait()
                if self.actor_instance is None:
                    cause = getattr(self, "_actor_init_error", None)
                    raise RuntimeError(
                        f"actor __init__ failed: {cause!r}" if cause
                        else "actor instance not initialized")
                if spec.method_name is None and spec.fn_blob is not None:
                    # __ray_call__-style apply: run fn(actor_instance, ...)
                    # on the actor's worker (used by compiled DAG loops).
                    fn = serialization.loads_control(spec.fn_blob)
                    call = lambda: fn(self.actor_instance, *args, **kwargs)  # noqa: E731
                else:
                    method = getattr(self.actor_instance, spec.method_name)
                    call = lambda: method(*args, **kwargs)  # noqa: E731
                if getattr(spec, "streaming", False):
                    # Streaming actor method: yielded items publish
                    # one-by-one (reference: streaming actor calls).
                    self._run_stream(call, spec, rt, results)
                    value_list = []
                else:
                    value_list = self._split_returns(call(), spec)
                call = None
            elif spec.streaming:
                fn = self._load_fn(spec)
                self._run_stream(lambda: fn(*args, **kwargs), spec, rt,
                                 results)
                value_list = []
            else:
                fn = self._load_fn(spec)
                value_list = self._split_returns(fn(*args, **kwargs), spec)
            # A ref serialized into a RESULT outlives the task at its
            # consumer: the owner retains it for the result object's
            # lifetime (containment, reference: reference_counter.h:44)
            # — ContainedRefs must hit the wire BEFORE TaskDone (FIFO
            # outbox) so the retention exists before the consumer reads.
            from .api import _nested_collector
            from .protocol import ContainedRefs
            for i, oid in enumerate(spec.return_ids):
                in_result: list = []
                token = _nested_collector.set(in_result)
                try:
                    desc = _serialize_result(rt, oid, value_list[i])
                finally:
                    _nested_collector.reset(token)
                results.append((oid, desc))
                if in_result:
                    rt.send(ContainedRefs(oid, list(in_result)))
            # Release the arg/result locals so the borrow survivor check
            # in the finally sees only refs the USER kept (actor state,
            # globals) — not this frame's own temporaries.
            args = kwargs = value_list = None
        except BaseException as exc:  # noqa: BLE001 - forwarded to caller
            is_app_error = True
            wrapped = TaskError(exc, spec.name, traceback.format_exc())
            try:
                error = ("err", serialization.pack_payload(wrapped))
            except Exception:
                error = ("err", serialization.pack_payload(
                    TaskError(RuntimeError(str(exc)), spec.name,
                              traceback.format_exc())))
            if spec.create_actor_id is not None:
                rt.send(ActorStateMsg(spec.create_actor_id, "error", error))
            # Release the frame's own references (locals + the exception's
            # traceback chain) so failed tasks don't spuriously escalate
            # their arg borrows to escaped-forever.
            args = kwargs = value_list = wrapped = None  # noqa: F841
        finally:
            rt.current_task_id = None
            rt.thread_tasks.pop(_tident, None)
            if not is_actor_task:
                # Results are serialized (copied) by now; arg/get views are
                # dead, so release their arena pins before TaskDone.
                rt.flush_task_reads()
            if borrows:
                # Borrowed refs kept beyond the task (actor state etc.)
                # escalate to owner-side pinning; must hit the wire
                # BEFORE TaskDone or the owner could free first (FIFO
                # outbox preserves the order).
                rt.report_retained_borrows(borrows)
        # Metrics recorded by this task must be at the driver before the
        # task is observed complete (FIFO outbox orders the push ahead of
        # TaskDone); no-op unless something was recorded since last flush.
        try:
            from ..util.metrics import flush_on_task_done
            flush_on_task_done()
        except Exception:
            pass
        aid = spec.actor_id or spec.create_actor_id
        frame = wire.encode_task_done(
            spec.task_id.binary(), rt.worker_id.binary(),
            [(oid.binary(), desc) for oid, desc in results],
            error, is_app_error,
            aid.binary() if aid is not None else None,
            _time.monotonic() - t0)
        if deliver is not None:
            deliver(frame, spec)
        else:
            rt.send(frame)

    @staticmethod
    def _run_stream(produce, spec, rt, results) -> None:
        """Streaming generator (reference: ObjectRefStream,
        task_manager.h:86): each yielded item is published immediately
        as ObjectID.of(task_id, i); the final ("end",) marker closes the
        stream, and a mid-stream exception lands as an err descriptor at
        the failing index so the consumer raises at the right position."""
        from .api import _nested_collector
        from .protocol import ContainedRefs
        count = 0
        try:
            for item in produce():
                oid = ObjectID.of(spec.task_id, count)
                inner: list = []
                token = _nested_collector.set(inner)
                try:
                    desc = _serialize_result(rt, oid, item)
                finally:
                    _nested_collector.reset(token)
                if inner:
                    rt.send(ContainedRefs(oid, list(inner)))
                rt.send(PutFromWorker(oid, desc))
                count += 1
        except BaseException as exc:  # noqa: BLE001
            stream_err = TaskError(exc, spec.name, traceback.format_exc())
            results.append((
                ObjectID.of(spec.task_id, count),
                ("err", serialization.pack_payload(stream_err))))
        else:
            results.append((ObjectID.of(spec.task_id, count), ("end",)))

    @staticmethod
    def _split_returns(out: Any, spec) -> List[Any]:
        n = len(spec.return_ids)
        if n == 0:
            return []
        if n == 1:
            return [out]
        if not isinstance(out, (tuple, list)) or len(out) != n:
            raise ValueError(
                f"task {spec.name!r} declared num_returns={n} but returned "
                f"{type(out).__name__} of length "
                f"{len(out) if isinstance(out, (tuple, list)) else 'n/a'}")
        return list(out)

    # -- receive loop -------------------------------------------------------

    def _dispatch(self, msg) -> bool:
        """Route one received message; returns False on KillWorker."""
        rt = self.runtime
        if type(msg) is tuple:
            if msg[0] == wire.RUN_TASK:
                spec, args, kwargs = wire.decode_run_task(msg)
                if spec.max_concurrency > self._executor.size:
                    self._executor.resize(spec.max_concurrency)
                self._executor.submit(self._run_task,
                                      RunTask(spec, args, kwargs))
                return True
            raise ValueError(f"unknown wire frame tag {msg[0]!r}")
        if isinstance(msg, RunTask):
            if msg.spec.max_concurrency > self._executor.size:
                self._executor.resize(msg.spec.max_concurrency)
            self._executor.submit(self._run_task, msg)
        elif isinstance(msg, (GetReply, WaitReply, RpcReply, AllocReply)):
            rt.deliver_reply(msg.request_id, msg)
        elif isinstance(msg, StackDumpRequest):
            # Runs on THIS (receive) thread, never the executor pool: a
            # worker wedged in user code must still answer the dump.
            try:
                from .diagnostics import capture_process_stacks
                record = capture_process_stacks(
                    rt.worker_id.hex(),
                    actor_id=self.actor_id.hex() if self.actor_id else None,
                    thread_tasks=rt.thread_tasks)
                rt.send(StackDumpReply(msg.dump_id, rt.worker_id, record))
            except Exception:  # noqa: BLE001 — diagnostics must not kill us
                traceback.print_exc()
        elif isinstance(msg, ProfileRequest):
            # Received here (not the executor pool) so a busy worker
            # still starts the capture; the capture itself blocks for
            # the whole duration, so it runs on its own thread — the
            # receive loop must keep routing replies meanwhile.
            def _capture(req=msg):
                try:
                    from ray_tpu.profiler.capture import capture_profile
                    record = capture_profile(
                        rt.worker_id.hex(), req.duration_s, hz=req.hz,
                        jax_profile=req.jax_profile,
                        driver_wall_s=req.driver_wall_s)
                except Exception as e:  # noqa: BLE001 — reported upward
                    record = {"worker_id": rt.worker_id.hex(),
                              "pid": os.getpid(), "samples": [],
                              "error": f"{type(e).__name__}: {e}"}
                rt.send(ProfileReply(req.profile_id, rt.worker_id,
                                     record))
            from . import sanitizer
            sanitizer.spawn(_capture, name="profile-capture")
        elif isinstance(msg, FlushTelemetry):
            # The head is about to close: spans and final metrics go out
            # ahead of the answer (one FIFO outbox), from THIS thread so
            # that a worker busy in a task answers too.
            from ..util.metrics import flush_terminal
            flush_terminal()
            rt.send(RpcCall(0, rt.worker_id, "telemetry_flushed", (), {}))
        elif isinstance(msg, KillWorker):
            return False
        return True

    def run(self) -> None:
        rt = self.runtime
        rt.send(WorkerReady(rt.worker_id, os.getpid()))
        conn = rt.conn
        alive = True
        while alive:
            try:
                frame = conn.recv()
            except (EOFError, OSError):
                break
            if type(frame) is list:
                for m in frame:
                    try:
                        if not self._dispatch(m):
                            alive = False
                            break
                    except Exception:
                        # Isolate a corrupt message: dropping the rest of
                        # the batch would lose TaskDone-ordered siblings.
                        traceback.print_exc()
            else:
                try:
                    alive = self._dispatch(frame)
                except Exception:
                    traceback.print_exc()
        try:
            self._executor.shutdown()
            # Terminal metrics push rides the outbox drain below (fire
            # and forget: the recv loop that would deliver a reply is
            # gone).  Unconditional, NOT the dirty-flag-gated task-done
            # flush: samples recorded after the last task's flush (during
            # executor shutdown, teardown hooks, atexit-adjacent paths)
            # have no later completion to retry on.
            try:
                from ..util.metrics import flush_terminal
                flush_terminal()
            except Exception:
                pass
            rt.flush_and_close()
        finally:
            os._exit(0)


