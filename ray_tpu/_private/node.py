"""Node plane: worker pool + per-node dispatch (the raylet equivalent).

The reference's raylet owns the WorkerPool (reference:
src/ray/raylet/worker_pool.h:283 — process spawning, idle pools, prestart),
local dispatch with resource pinning (local_lease_manager.h:61) and the
node's object store.  Here NodeManager plays that role for one host: it
spawns Python worker processes (multiprocessing ``spawn`` so jax state never
leaks across fork), keeps an idle pool, pins TPU chips to granted tasks via
``TPU_VISIBLE_CHIPS``-style env isolation (reference:
python/ray/_private/accelerators/tpu.py set_current_process_visible_accelerator_ids),
and runs one receiver thread per worker that routes TaskDone / nested
submissions / get requests back into the Runtime.

Chaos hooks are built into the send path from day one (reference:
src/ray/rpc/rpc_chaos.cc:33 RAY_testing_rpc_failure): configured drop
probabilities and injected delays apply to every message class.
"""

from __future__ import annotations

import os
import random
import socket
import subprocess
import sys
import tempfile
import threading
import time
from select import select as _select
from dataclasses import dataclass, field
from multiprocessing.connection import Listener
from typing import Any, Callable, Dict, List, Optional, Set

from . import wire
from .config import Config
from .controller import NodeInfo
from .ids import ActorID, NodeID, TaskID, WorkerID
from .object_store import NativeArenaStore, create_store
from .protocol import (ActorStateMsg, AllocReply, AllocRequest,
                       BorrowRetained, ContainedRefs, FlushTelemetry,
                       GetRequest, KillWorker, ProfileReply, ProfileRequest,
                       PutFromWorker, ReadDone, RpcCall, RunTask,
                       SealObject, StackDumpReply, StackDumpRequest,
                       SubmitFromWorker, TaskDone, TaskSpec, WaitRequest,
                       WorkerReady)
from .resources import ResourceSet, TPU
from ..util import telemetry

IDLE = "idle"
BUSY = "busy"
DEAD = "dead"

_WIRE_NAMES = {wire.RUN_TASK: "RunTask", wire.TASK_DONE: "TaskDone"}


def _wire_msg_name(msg) -> str:
    """Message-class name for chaos config matching; wire tuples map back
    to the dataclass names so existing testing_rpc_failure specs apply."""
    if type(msg) is tuple:
        return _WIRE_NAMES.get(msg[0], str(msg[0]))
    return type(msg).__name__


@dataclass
class WorkerHandle:
    worker_id: WorkerID
    proc: Any
    conn: Any
    state: str = IDLE
    actor_id: Optional[ActorID] = None
    # Chip-holding workers are dedicated: they are killed after their task
    # and their chips return to the pool only when the process death is
    # observed (libtpu releases device locks at exit).  Env-only workers
    # are pooled per env signature instead.
    dedicated: bool = False
    env_key: str = ""
    death_reason: str = ""
    # fn_ids whose blobs this worker has already received — later specs
    # ship without the blob (reference: function-table export-once).
    seen_fns: Set[bytes] = field(default_factory=set)
    # Registration-timeout Timer; cancelled the moment the worker
    # registers (otherwise one timer thread per spawn idles out the
    # full worker_register_timeout_s — a leak the sanitizer flags).
    register_watchdog: Optional[Any] = None
    running: Set[TaskID] = field(default_factory=set)
    # task_id -> (start_monotonic, retriable) for the OOM kill policy.
    task_meta: Dict[TaskID, Any] = field(default_factory=dict)
    # Direct actor calls in flight (no running/task_meta entries): count +
    # oldest-start, enough for the OOM victim policy to see the worker.
    direct_inflight: int = 0
    direct_since: float = 0.0
    reader: Optional[threading.Thread] = None
    ready: threading.Event = field(default_factory=threading.Event)
    # (wall, monotonic) at spawn: the start of the worker_start span that
    # ends when the worker registers.
    spawned_at: tuple = (0.0, 0.0)
    send_lock: threading.Lock = field(default_factory=threading.Lock)
    assigned_chips: Dict[TaskID, List[int]] = field(default_factory=dict)
    # Messages queued before the worker registered (async spawn): flushed
    # in order by the acceptor as soon as the connection lands.
    pending_msgs: List[Any] = field(default_factory=list)
    # Arena-store pin bookkeeping (native store only; see object_store.py):
    # args pinned for in-flight tasks, pins from outstanding GetReplies, pins
    # promoted to worker lifetime (actor-retained views), unsealed allocs.
    arg_pins: Dict[TaskID, List[bytes]] = field(default_factory=dict)
    get_pins: Dict[int, List[bytes]] = field(default_factory=dict)
    lifetime_pins: List[bytes] = field(default_factory=list)
    unsealed: Set[Any] = field(default_factory=set)


class NodeManager:
    def __init__(self, node_info: NodeInfo, runtime, num_tpu_chips: int = 0):
        self.info = node_info
        self.runtime = runtime  # driver Runtime; provides message handlers
        self.store = create_store()
        self._native_store = isinstance(self.store, NativeArenaStore)
        self._workers: Dict[WorkerID, WorkerHandle] = {}
        self._idle: Dict[str, List[WorkerID]] = {}
        self._lock = threading.RLock()
        self._num_tpu_chips = num_tpu_chips
        self._chip_pool: List[int] = list(range(num_tpu_chips))
        self._closed = False
        # (sys.path ships per SPAWN, not frozen here: a driver that
        # appends an import dir after init — compiled protos, generated
        # code — must still resolve in later workers.)
        # Workers are spawned as fresh interpreters that dial back in
        # (reference: worker_pool.h StartWorkerProcess + raylet socket
        # registration) — no fork, no __main__ re-import, no jax inheritance.
        self._sock_path = os.path.join(
            tempfile.mkdtemp(prefix="ray_tpu_"), "node.sock")
        self._authkey = os.urandom(16)
        # Direct worker->worker call channels (direct.py): the token all
        # listeners/callers authenticate with, and the host workers bind
        # their listeners on.  Cluster setups overwrite these with the
        # cluster token + advertise host so channels work across nodes.
        self.direct_token: bytes = self._authkey
        self.direct_host: str = "127.0.0.1"
        self._listener = Listener(self._sock_path, "AF_UNIX",
                                  authkey=self._authkey)
        # One multiplexed poller over every worker connection instead of a
        # reader thread per worker (reference: asio io_service event loops)
        # — N reader threads ping-ponging the GIL with the dispatch thread
        # measurably halved task throughput at 8+ workers.
        self._poll_conns: Dict[Any, WorkerHandle] = {}
        self._conns_version = 0
        self._poll_wake_r, self._poll_wake_w = os.pipe()
        self._poller = threading.Thread(target=self._poll_loop,
                                        name="node-poller", daemon=True)
        self._poller.start()
        # Outgoing messages ride one sender thread: callers enqueue (cheap)
        # and move on; the sender coalesces everything queued per worker
        # into a single list frame — one pickle, one write — so a burst of
        # dispatches costs O(batches) syscalls instead of O(tasks)
        # (reference: the C++ core worker's pooled gRPC streams amortize
        # the same way).
        import collections
        self._outbox: Any = collections.deque()
        self._out_ev = threading.Event()
        self._sender = threading.Thread(target=self._send_loop,
                                        name="node-sender", daemon=True)
        self._sender.start()
        self._acceptor = threading.Thread(target=self._accept_loop,
                                          name="node-acceptor", daemon=True)
        self._acceptor.start()
        # chaos config parsed once
        self._drop_probs: Dict[str, float] = {}
        spec = Config.get("testing_rpc_failure")
        if spec:
            for part in spec.split(","):
                if "=" in part:
                    m, p = part.split("=")
                    self._drop_probs[m.strip()] = float(p)
        # OOM protection (reference: raylet MemoryMonitor + worker-killing
        # policy); no-op unless memory_monitor_refresh_ms > 0.
        from .memory_monitor import MemoryMonitor
        self.memory_monitor = MemoryMonitor(self)
        self.memory_monitor.start()
        # Worker resource isolation (reference: cgroup2/cgroup_manager.h);
        # no-op unless enable_resource_isolation.
        from .cgroup import CgroupManager
        self.cgroup = CgroupManager()

    # -- worker lifecycle ---------------------------------------------------

    def _accept_loop(self) -> None:
        # Safe bare reads: _closed is a monotonic shutdown latch; the
        # worst a stale False costs is one extra loop iteration.
        while not self._closed:  # ray-tpu: noqa[RT401]
            try:
                conn = self._listener.accept()
            except Exception:  # noqa: BLE001
                # Covers OSError/EOFError AND AuthenticationError: a worker
                # SIGKILLed mid-handshake (OOM kill, ray_tpu.kill, chaos)
                # leaves a half-written challenge response — the accept
                # loop must survive it or no worker can ever register
                # again.
                if self._closed:
                    return
                continue
            if self._closed:
                try:
                    conn.close()
                except Exception:  # ray-tpu: noqa[RT202] — teardown close
                    pass
                return
            try:
                hello: WorkerReady = conn.recv()
            except (EOFError, OSError):
                conn.close()
                continue
            with self._lock:
                handle = self._workers.get(hello.worker_id)
            if handle is None:
                conn.close()
                continue
            # Install the connection and flush messages dispatched while
            # the worker was still booting (async spawn), preserving order
            # against concurrent _send calls via the send lock.
            with handle.send_lock:
                handle.conn = conn
                for m in handle.pending_msgs:
                    try:
                        conn.send(m)
                    except (BrokenPipeError, OSError):
                        break
                handle.pending_msgs.clear()
            handle.ready.set()
            self._cancel_register_watchdog(handle)
            # Process start-up of this worker: spawn -> registered.
            wall, mono = handle.spawned_at
            telemetry._emit_span(
                "worker_start", "system", wall,
                wall + (time.monotonic() - mono),
                extra={"pid": handle.proc.pid,
                       "worker_id": handle.worker_id.hex()})
            with self._lock:
                self._poll_conns[conn] = handle
                self._conns_version += 1
            self._wake_poller()

    def _wake_poller(self) -> None:
        try:
            os.write(self._poll_wake_w, b"x")
        except OSError:
            pass

    def _poll_loop(self) -> None:
        """Single event loop over all worker pipes (reference: the
        raylet's asio loop servicing every worker connection).

        The selector is persistent — connections register once when they
        land and unregister at death — because rebuilding a selector per
        poll (multiprocessing.connection.wait's behavior) re-registered
        every fd every iteration and showed up directly in dispatch
        profiles.  After a conn turns readable, every already-buffered
        frame is drained before re-polling.

        Known tradeoff: recv() after readability is frame-blocking, so a
        worker stopped mid-frame (SIGSTOP) would stall the loop — the
        per-worker-thread model confined that to one worker but cost ~2x
        task throughput in GIL ping-pong.  True non-blocking framing
        belongs in the native transport when this pipe moves to C++.
        """
        import selectors
        sel = selectors.DefaultSelector()
        sel.register(self._poll_wake_r, selectors.EVENT_READ, None)
        registered: Dict[Any, Any] = {}  # conn -> handle
        seen_version = -1
        while not self._closed:
            with self._lock:
                version = self._conns_version
                current = dict(self._poll_conns) if version != seen_version \
                    else None
            if current is not None:
                seen_version = version
                for c in list(registered):
                    if c not in current:
                        registered.pop(c)
                        try:
                            sel.unregister(c)
                        except (KeyError, ValueError, OSError):
                            pass
                for c, h in current.items():
                    if c not in registered:
                        try:
                            sel.register(c, selectors.EVENT_READ, h)
                        except (KeyError, ValueError, OSError):
                            # fd already dead (worker crashed between accept
                            # and registration): run the death path now —
                            # no EOF event will ever arrive for this conn.
                            with self._lock:
                                self._poll_conns.pop(c, None)
                                self._conns_version += 1
                            seen_version = -1
                            self._on_worker_death(h)
                            continue
                        registered[c] = h
            try:
                events = sel.select(timeout=1.0)
            except OSError:
                events = []
            for key, _mask in events:
                c = key.fileobj
                if c is self._poll_wake_r:
                    try:
                        os.read(self._poll_wake_r, 4096)
                    except OSError:
                        pass
                    continue
                handle = key.data
                # Drain every buffered frame before re-polling (cap keeps
                # one chatty worker from starving the rest).
                for _ in range(64):
                    try:
                        frame = c.recv()
                    except (EOFError, OSError):
                        with self._lock:
                            self._poll_conns.pop(c, None)
                            self._conns_version += 1
                        registered.pop(c, None)
                        try:
                            sel.unregister(c)
                        except (KeyError, ValueError, OSError):
                            pass
                        self._on_worker_death(handle)
                        break
                    if type(frame) is list:
                        # Per-message isolation: one bad message must not
                        # drop the rest of its batch (a lost TaskDone
                        # hangs the caller forever).
                        for m in frame:
                            try:
                                self._handle_msg(handle, m)
                            except Exception:
                                import traceback
                                traceback.print_exc()
                    else:
                        try:
                            self._handle_msg(handle, frame)
                        except Exception:
                            import traceback
                            traceback.print_exc()
                    try:
                        # Raw select probe: Connection.poll(0) builds a
                        # fresh selector object per call (~15us); this is
                        # one cheap syscall.
                        readable, _, _ = _select([c], [], [], 0)
                    except (OSError, ValueError):
                        break
                    if not readable:
                        break
        sel.close()

    def _send_loop(self) -> None:
        """Drain the outbox, grouping queued messages per worker into one
        list frame (single pickle + single write).  FIFO order within a
        worker is preserved — actor-method ordering and the
        creation-before-methods invariant depend on it."""
        outbox, ev = self._outbox, self._out_ev
        while True:
            ev.wait()
            ev.clear()
            if self._closed:
                # Checked after clear(): a close racing the wakeup must not
                # have its set() erased and leave join() to time out.
                return
            groups: List[tuple] = []  # (handle, [msgs]) in first-seen order
            index: Dict[int, int] = {}
            while True:
                try:
                    handle, msg = outbox.popleft()
                except IndexError:
                    break
                i = index.get(id(handle))
                if i is None:
                    index[id(handle)] = len(groups)
                    groups.append((handle, [msg]))
                else:
                    groups[i][1].append(msg)
            for handle, msgs in groups:
                try:
                    with handle.send_lock:
                        if handle.conn is None:
                            # Worker still booting (async spawn): queue in
                            # order; the acceptor flushes on registration.
                            handle.pending_msgs.extend(msgs)
                            continue
                        handle.conn.send(msgs if len(msgs) > 1 else msgs[0])
                except (BrokenPipeError, OSError):
                    pass  # poll loop will notice the death
                except Exception:
                    # e.g. an unpicklable field: isolate the poisonous
                    # message so the rest of the batch (and this thread!)
                    # survives — a dead sender wedges all outbound traffic.
                    self._send_individually(handle, msgs)

    def _send_individually(self, handle: WorkerHandle, msgs: List) -> None:
        for m in msgs:
            try:
                with handle.send_lock:
                    if handle.conn is None:
                        handle.pending_msgs.append(m)
                    else:
                        handle.conn.send(m)
            except (BrokenPipeError, OSError):
                return
            except Exception:
                import traceback
                traceback.print_exc()
                # A RunTask that can't serialize must fail its task, not
                # silently hang the caller — and the node-side worker/pin
                # state must unwind as if the task had died.
                if type(m) is tuple and m[0] == wire.RUN_TASK:
                    ids = (m[1], m[6])
                elif isinstance(m, RunTask):
                    ids = (m.spec.task_id.binary(),
                           [r.binary() for r in m.spec.return_ids])
                else:
                    continue
                try:
                    self._abort_sent_task(handle, TaskID(ids[0]))
                except ValueError:
                    pass
                self.runtime.fail_task_bytes(
                    ids[0], ids[1], "task message failed to serialize")

    def _abort_sent_task(self, handle: WorkerHandle, task_id: TaskID) -> None:
        """Unwind node-side state for a task whose RunTask never made it to
        the worker (sender-side failure): drop running/meta, release arg
        pins, return the worker to the pool."""
        handle.running.discard(task_id)
        handle.task_meta.pop(task_id, None)
        if self._native_store:
            for k in handle.arg_pins.pop(task_id, []):
                self.store.unpin_key(k)
        if handle.actor_id is None and not handle.dedicated:
            self._release_worker(handle)

    def _spawn_worker(self, env: Optional[Dict[str, str]] = None) -> WorkerHandle:
        worker_id = WorkerID.from_random()
        child_env = dict(os.environ)
        child_env.update(env or {})
        child_env.update({
            "RAY_TPU_WORKER_ID": worker_id.hex(),
            "RAY_TPU_JOB_ID": self.runtime.job_id.hex(),
            "RAY_TPU_NODE_SOCK": self._sock_path,
            "RAY_TPU_AUTHKEY": self._authkey.hex(),
            "RAY_TPU_DIRECT_TOKEN": self.direct_token.hex(),
            "RAY_TPU_DIRECT_HOST": self.direct_host,
            "RAY_TPU_CONFIG_BLOB": Config.blob(),
            # Driver sys.path travels to workers so functions pickled
            # by reference (importable modules, incl. test files) resolve
            # (reference: runtime-env working_dir/py_modules propagation).
            # Computed per spawn — exists (not isdir): zip/egg/pyz
            # entries are importable too.
            "RAY_TPU_SYS_PATH": os.pathsep.join(
                p for p in sys.path if p and os.path.exists(p)),
            # Arena segment name: workers write large results straight into
            # the node's C++ store (empty = fall back to per-object segments).
            "RAY_TPU_ARENA_SEG":
                self.store.segment_name if self._native_store else "",
        })
        # Per-worker log files in the session dir, tailed back to the
        # driver by the log monitor (reference: workers log to
        # /tmp/ray/session_*/logs, republished by log_monitor.py:116).
        popen_kw: Dict[str, Any] = {}
        logs_dir = getattr(self.runtime, "session_logs_dir", None)
        if logs_dir and Config.get("redirect_worker_logs"):
            tag = f"worker-{worker_id.hex()[:8]}"
            out = None
            try:
                out = open(os.path.join(logs_dir, tag + ".out"), "ab")
                err = open(os.path.join(logs_dir, tag + ".err"), "ab")
                popen_kw = {"stdout": out, "stderr": err}
            except OSError:
                if out is not None:
                    out.close()
                popen_kw = {}
        child_env.update(self.cgroup.spawn_env())
        # pip runtime envs run the worker under their venv interpreter
        # (reference: pip plugin's python_interpreter override).
        python = child_env.pop("RAY_TPU_PYTHON", sys.executable)
        spawned_wall = time.time()
        try:
            proc = subprocess.Popen(
                [python, "-m", "ray_tpu._private.worker_main"],
                env=child_env, cwd=os.getcwd(), **popen_kw)
        finally:
            for f in popen_kw.values():
                f.close()  # child holds the fd; parent must not leak it
        self.cgroup.add_process(proc.pid)
        handle = WorkerHandle(worker_id, proc, None,
                              spawned_at=(spawned_wall, time.monotonic()))
        with self._lock:
            self._workers[worker_id] = handle
        # Async spawn: dispatches queue in pending_msgs and the task starts
        # the moment the worker registers — the dispatch thread never
        # blocks on interpreter boot.  A watchdog converts a never-
        # registering worker into the normal death path (queued tasks
        # retry elsewhere).
        def _watchdog(h=handle):
            if not h.ready.is_set():
                self._kill_and_reap(h)
        t = threading.Timer(Config.get("worker_register_timeout_s"),
                            _watchdog)
        t.daemon = True
        with self._lock:
            if self._closed:
                # shutdown()'s cancel sweep already ran (or is running):
                # starting the timer now would leave it ticking against
                # a torn-down manager for the full register timeout.
                return handle
            handle.register_watchdog = t
        t.start()
        return handle

    def _cancel_register_watchdog(self, handle: WorkerHandle) -> None:
        t, handle.register_watchdog = handle.register_watchdog, None
        if t is not None:
            t.cancel()

    def _kill_and_reap(self, handle: WorkerHandle) -> None:
        """SIGKILL a worker and guarantee its death handler runs.

        A worker killed before (or during) registration produces no pipe
        EOF for the poller, so reap explicitly: wait for the process, give
        the EOF path a moment, then run the (idempotent) death handler if
        it hasn't fired.  Shared by OOM kills, forced actor kills and the
        registration watchdog so the three paths cannot drift.
        """
        try:
            if handle.proc.poll() is None:
                handle.proc.kill()
        except Exception as e:
            telemetry.note_swallowed("node.kill_worker", e)

        def _reap(h=handle):
            try:
                h.proc.wait(timeout=60)
            except Exception as e:
                telemetry.note_swallowed("node.reap_worker", e)
            time.sleep(1.0)
            if h.state != DEAD:
                self._on_worker_death(h)
        from . import sanitizer
        sanitizer.spawn(_reap, name="worker-reap")

    def _acquire_worker(self, env_key: str = "",
                        env: Optional[Dict[str, str]] = None) -> WorkerHandle:
        """Reuse an idle worker with a matching spawn env, else spawn.

        Workers are pooled per env signature: boot-time env (jax platform,
        flags) can't change after spawn, but identical-env tasks reuse the
        same interpreters.
        """
        with self._lock:
            bucket = self._idle.get(env_key, [])
            while bucket:
                wid = bucket.pop()
                h = self._workers.get(wid)
                if h is not None and h.state == IDLE:
                    h.state = BUSY
                    return h
        h = self._spawn_worker(env=env)
        h.state = BUSY
        h.env_key = env_key
        return h

    def _release_worker(self, handle: WorkerHandle) -> None:
        with self._lock:
            if handle.state == DEAD or handle.actor_id is not None:
                return
            handle.state = IDLE
            self._idle.setdefault(handle.env_key, []).append(
                handle.worker_id)

    # -- dispatch -----------------------------------------------------------

    def dispatch_task(self, spec: TaskSpec,
                      resolved_args, resolved_kwargs,
                      target_worker: Optional[WorkerID] = None,
                      _retry_deadline: Optional[float] = None,
                      _env_bg: bool = False) -> None:
        """Send a fully-resolved task to a worker (lease grant + push)."""
        env_vars: Dict[str, str] = dict(
            spec.runtime_env.get("env_vars", {})) if spec.runtime_env else {}
        if spec.runtime_env and (spec.runtime_env.get("working_dir")
                                 or spec.runtime_env.get("py_modules")
                                 or spec.runtime_env.get("pip")):
            from .runtime_env import pip_env_ready
            if not _env_bg and not pip_env_ready(spec.runtime_env):
                # Cold pip env: venv creation + pip install can take
                # minutes — building it inline would stall the single
                # dispatch thread (and with it every other task in the
                # cluster).  Re-enter on a builder thread instead
                # (reference: runtime-env agent builds envs off the
                # raylet's dispatch path).
                def _bg():
                    try:
                        self.dispatch_task(spec, resolved_args,
                                           resolved_kwargs, target_worker,
                                           _retry_deadline, _env_bg=True)
                    except Exception as e:  # noqa: BLE001
                        self.runtime.scheduler.release(
                            self.info.node_id, spec.resources,
                            spec.placement_group, spec.bundle_index)
                        self.runtime.on_dispatch_failed(spec, repr(e))
                from . import sanitizer
                sanitizer.spawn(_bg, name="runtime-env-build")
                return
            # Extract content-addressed packages into the node session dir;
            # workers apply them at boot (reference: runtime-env agent
            # GetOrCreateRuntimeEnv before the lease grant).
            from .runtime_env import node_setup_env_vars
            env_vars.update(node_setup_env_vars(spec.runtime_env))
        # TPU chip pinning: integral chip grants get exclusive visibility via
        # spawn-time env (libtpu/jax read it at process boot).
        n_chips = int(spec.resources.get(TPU))
        grant: List[int] = []
        if n_chips > 0 and target_worker is None:
            with self._lock:
                if len(self._chip_pool) >= n_chips:
                    grant = self._chip_pool[:n_chips]
                    del self._chip_pool[:n_chips]
            if not grant:
                # Chips freed in the scheduler but physically still held by
                # a dying worker (libtpu locks release at process exit):
                # retry until the death handler returns them.
                if _retry_deadline is None:
                    _retry_deadline = time.monotonic() + \
                        Config.get("lease_timeout_s")
                if time.monotonic() > _retry_deadline:
                    self.runtime.scheduler.release(
                        self.info.node_id, spec.resources,
                        spec.placement_group, spec.bundle_index)
                    self.runtime.on_dispatch_failed(
                        spec, f"timed out waiting for {n_chips} TPU chips")
                    return

                def _retry():
                    try:
                        self.dispatch_task(spec, resolved_args,
                                           resolved_kwargs, target_worker,
                                           _retry_deadline)
                    except Exception as e:  # noqa: BLE001
                        self.runtime.scheduler.release(
                            self.info.node_id, spec.resources,
                            spec.placement_group, spec.bundle_index)
                        self.runtime.on_dispatch_failed(spec, repr(e))
                t = threading.Timer(0.05, _retry)
                t.daemon = True
                t.start()
                return
            # Always overwrite: a retried task must see its fresh grant,
            # not the first attempt's chips.  The pinning env comes from
            # the accelerator plugin (accelerators/accelerator.py); the
            # config override supports tests faking the env name.
            env_name = Config.get("visible_accelerator_env")
            from ..accelerators.accelerator import get_accelerator
            mgr = get_accelerator("TPU")
            if mgr is not None and env_name == "TPU_VISIBLE_CHIPS":
                env_vars.update(mgr.visibility_env(
                    grant, host_chips=self._num_tpu_chips))
            else:
                env_vars[env_name] = ",".join(str(c) for c in grant)
        if target_worker is not None:
            with self._lock:
                handle = self._workers.get(target_worker)
            if handle is None or handle.state == DEAD:
                self.runtime.on_dispatch_failed(spec, "target worker dead")
                return
        else:
            env_key = ""
            if env_vars:
                env_key = repr(sorted(env_vars.items()))  # boot-env identity
            try:
                if grant:
                    # Chip-holding workers are never pooled: the process
                    # must die before its chips are reusable.
                    handle = self._spawn_worker(env=env_vars)
                    handle.state = BUSY
                    handle.dedicated = True
                else:
                    handle = self._acquire_worker(env_key, env_vars or None)
            except Exception:
                if grant:
                    with self._lock:
                        self._chip_pool.extend(grant)
                # Propagate: the scheduler's dispatch-error path releases
                # the booked resources and fails the task.
                raise
        if spec.create_actor_id is not None:
            handle.actor_id = spec.create_actor_id
        if grant:
            died = False
            with self._lock:
                if handle.state == DEAD or \
                        handle.worker_id not in self._workers:
                    # Worker died between spawn and chip assignment: the
                    # death handler saw no assigned chips, so return them
                    # here and fail the task cleanly.
                    self._chip_pool.extend(grant)
                    died = True
                else:
                    handle.assigned_chips[spec.task_id] = grant
            if died:
                # Fail OUTSIDE the node lock (RT404): the dispatch-failed
                # path re-enters scheduler/runtime state and must not
                # hold this lock across that work.
                self.runtime.on_dispatch_failed(
                    spec, "worker died before chip assignment")
                return
        if env_vars:
            # Never mutate the caller's spec (retries rebuild from it).
            import copy as _copy
            spec = _copy.copy(spec)
            spec.runtime_env = dict(spec.runtime_env or {}, env_vars=env_vars)
        fn_blob = spec.fn_blob
        if spec.fn_id is not None and fn_blob is not None:
            if spec.fn_id in handle.seen_fns:
                # Worker already holds this function: ship the frame without
                # the blob (workers fall back to a ctl fetch on a miss).
                # The strip happens at encode time — the driver-side spec
                # (lineage, retries) keeps its blob.
                fn_blob = None
            else:
                handle.seen_fns.add(spec.fn_id)
        if self._native_store:
            # Refresh + pin arena-resident args so their offsets stay valid
            # for the task's lifetime (plasma client-pin semantics).
            ok, resolved_args, resolved_kwargs = self._pin_args(
                handle, spec, resolved_args, resolved_kwargs)
            if not ok:
                return
        handle.running.add(spec.task_id)
        handle.task_meta[spec.task_id] = (
            time.monotonic(),
            spec.create_actor_id is None and spec.actor_id is None
            and spec.retry_count < spec.max_retries)
        self.runtime.note_task_running(spec.task_id, self.info.node_id,
                                       handle.worker_id)
        if spec.create_actor_id is None:
            # Hot path: compact tuple frame (no dataclass pickling, no
            # double-shipped arg payloads) — see wire.py.
            self._send(handle, wire.encode_run_task(
                spec, resolved_args, resolved_kwargs, fn_blob))
        else:
            if fn_blob is not spec.fn_blob:
                import copy as _copy
                spec = _copy.copy(spec)
                spec.fn_blob = fn_blob
            self._send(handle, RunTask(spec, resolved_args, resolved_kwargs))
        if spec.create_actor_id is not None:
            # Bind only after the creation message is on the wire so queued
            # method calls can never overtake __init__ on the worker pipe.
            self.runtime.bind_actor_worker(
                spec.create_actor_id, self.info.node_id, handle.worker_id)

    def dispatch_actor_task(self, spec: TaskSpec, resolved_args,
                            resolved_kwargs, worker_id: WorkerID) -> None:
        """Slim dispatch for actor method calls: the worker is known and
        bound, there is no env/chip/strategy work to do — just pin, track
        and ship (reference: direct actor submission over the persistent
        gRPC stream, actor_task_submitter.h)."""
        with self._lock:
            handle = self._workers.get(worker_id)
        if handle is None or handle.state == DEAD:
            self.runtime.on_dispatch_failed(spec, "target worker dead")
            return
        if self._native_store:
            ok, resolved_args, resolved_kwargs = self._pin_args(
                handle, spec, resolved_args, resolved_kwargs)
            if not ok:
                return
        handle.running.add(spec.task_id)
        handle.task_meta[spec.task_id] = (time.monotonic(), False)
        self.runtime.note_task_running(spec.task_id, self.info.node_id,
                                       handle.worker_id)
        self._send(handle, wire.encode_run_task(
            spec, resolved_args, resolved_kwargs, spec.fn_blob))

    @staticmethod
    def _pipeline_eligible(h, max_depth: int) -> bool:
        """Can this pooled worker take a queued-ahead (pipelined) task?
        Single definition shared by the has_pipeline_room precheck and
        the dispatch_pipelined selection loop — they must never drift."""
        return (h.state in (BUSY, IDLE) and h.actor_id is None
                and not h.dedicated and h.env_key == ""
                and h.ready.is_set() and len(h.running) < max_depth)

    def has_pipeline_room(self, max_depth: int = 4) -> bool:
        """Cheap precheck for dispatch_pipelined: is any pooled worker
        below the queue-ahead depth cap?  Lets the topup loop skip the
        resolve/queue/requeue cycle when the pool is full."""
        with self._lock:
            return any(self._pipeline_eligible(h, max_depth)
                       for h in self._workers.values())

    def dispatch_pipelined(self, spec: TaskSpec, resolved_args,
                           resolved_kwargs, max_depth: int = 4) -> bool:
        """Queue a plain task ahead on a busy pooled worker (pipelined
        submission, reference: the C++ submitter's
        max_tasks_in_flight_per_worker).  The task holds no resource
        booking — per-worker execution is serial, so real parallelism
        stays bounded by booked capacity; queueing ahead only hides the
        TaskDone -> dispatch round-trip latency.  Returns False if no
        worker has pipeline room."""
        with self._lock:
            best = None
            best_depth = max_depth
            for h in self._workers.values():
                if self._pipeline_eligible(h, best_depth):
                    best = h
                    best_depth = len(h.running)
            if best is None:
                return False
            handle = best
            claimed_idle = handle.state == IDLE
            if claimed_idle:
                # Claim it like _acquire_worker would (a worker released
                # by lease reuse an instant ago, possibly with queued
                # pipeline work).
                handle.state = BUSY
                bucket = self._idle.get(handle.env_key)
                if bucket and handle.worker_id in bucket:
                    bucket.remove(handle.worker_id)
        if self._native_store:
            ok, resolved_args, resolved_kwargs = self._pin_args(
                handle, spec, resolved_args, resolved_kwargs,
                release_on_fail=False)
            if not ok:
                if claimed_idle:
                    # Revert the claim or the worker is stranded BUSY with
                    # nothing running (unreachable by _acquire_worker).
                    self._release_worker(handle)
                return False
        fn_blob = spec.fn_blob
        if spec.fn_id is not None and fn_blob is not None:
            if spec.fn_id in handle.seen_fns:
                fn_blob = None
            else:
                handle.seen_fns.add(spec.fn_id)
        handle.running.add(spec.task_id)
        handle.task_meta[spec.task_id] = (
            time.monotonic(), spec.retry_count < spec.max_retries)
        self.runtime.note_task_running(spec.task_id, self.info.node_id,
                                       handle.worker_id)
        self._send(handle, wire.encode_run_task(
            spec, resolved_args, resolved_kwargs, fn_blob))
        return True

    def _pin_args(self, handle: WorkerHandle, spec: TaskSpec,
                  resolved_args, resolved_kwargs, release_on_fail=True):
        """Refresh + pin every arena descriptor among the resolved args.

        Pinning under the store lock guarantees the offsets we ship stay
        valid until the matching unpin (TaskDone for normal tasks, worker
        death for actor workers, which may retain zero-copy views in state).
        """
        pinned: List[bytes] = []
        lost_key = [None]

        def refresh(d):
            if isinstance(d, tuple) and d and d[0] == "shma":
                nd = self.store.pin_desc_by_key(d[4])
                if nd is not None:
                    pinned.append(nd[4])
                elif lost_key[0] is None:
                    lost_key[0] = d[4]
                return nd
            return d

        ok = True
        new_args = []
        for d in resolved_args:
            nd = refresh(d)
            if nd is None:
                ok = False
                break
            new_args.append(nd)
        new_kwargs = {}
        if ok:
            for k, d in resolved_kwargs.items():
                nd = refresh(d)
                if nd is None:
                    ok = False
                    break
                new_kwargs[k] = nd
        if not ok:
            for key in pinned:
                self.store.unpin_key(key)
            if not release_on_fail:
                # Pipelined attempt: no booking to release, no failure to
                # report — the caller just re-queues the task.
                return False, resolved_args, resolved_kwargs
            if handle.dedicated:
                # Chips stay in assigned_chips: they return to the pool only
                # when the process death is observed (libtpu lock release).
                self._send(handle, KillWorker("dispatch aborted"))
            elif handle.actor_id is None:
                self._release_worker(handle)
            if not spec.resources.is_empty() or spec.placement_group is not None:
                self.runtime.scheduler.release(
                    self.info.node_id, spec.resources,
                    spec.placement_group, spec.bundle_index)
            self.runtime.on_dispatch_failed(
                spec, "arena object freed while dispatching",
                lost_object_bytes=lost_key[0])
            return False, resolved_args, resolved_kwargs
        if pinned:
            handle.arg_pins[spec.task_id] = pinned
        return True, new_args, new_kwargs

    def track_get_pins(self, worker_id: WorkerID, request_id: int,
                       keys: List[bytes]) -> None:
        with self._lock:
            handle = self._workers.get(worker_id)
            if handle is not None and handle.state != DEAD:
                # Insert under the lock so _on_worker_death's pin drain
                # cannot interleave and strand these pins.
                handle.get_pins[request_id] = keys
                return
        for k in keys:
            self.store.unpin_key(k)

    def _send(self, handle: WorkerHandle, msg) -> None:
        if self._drop_probs or Config.get("testing_delay_us"):
            # Chaos hooks run on the caller (per message, pre-queue) so
            # drop/delay semantics are unchanged by sender coalescing.
            name = _wire_msg_name(msg)
            delay_us = Config.get("testing_delay_us")
            if delay_us:
                time.sleep(random.random() * delay_us / 1e6)
            p = self._drop_probs.get(name)
            if p and random.random() < p:
                return  # chaos: message dropped
        self._outbox.append((handle, msg))
        self._out_ev.set()

    def send_direct(self, worker_id: WorkerID, frame: tuple) -> bool:
        """Ship a pre-encoded direct-call frame to a bound actor worker.
        Returns False if the worker is unknown/dead (caller fails the
        refs)."""
        with self._lock:
            handle = self._workers.get(worker_id)
            if handle is None or handle.state == DEAD:
                return False
            if handle.direct_inflight == 0:
                handle.direct_since = time.monotonic()
            handle.direct_inflight += 1
        self._send(handle, frame)
        return True

    def send_to_worker(self, worker_id: WorkerID, msg) -> None:
        with self._lock:
            handle = self._workers.get(worker_id)
        if handle is not None and handle.state != DEAD:
            self._send(handle, msg)

    def broadcast_stack_dump(self, dump_id: int) -> List[WorkerID]:
        """Ship a StackDumpRequest to every registered live worker;
        returns the worker ids a reply is expected from.  Workers that
        have not finished registering are skipped — their pending-message
        queue would hold the request until boot completes, stalling the
        dump on an interpreter that is not running anything yet."""
        with self._lock:
            handles = [h for h in self._workers.values()
                       if h.state != DEAD and h.ready.is_set()
                       and h.conn is not None]
        for h in handles:
            self._send(h, StackDumpRequest(dump_id))
        return [h.worker_id for h in handles]

    def broadcast_profile(self, req: ProfileRequest) -> List[WorkerID]:
        """Ship a ProfileRequest to every registered live worker (same
        ready-gating as broadcast_stack_dump: a worker still booting
        would just hold the capture open past its window); returns the
        worker ids a reply is expected from."""
        with self._lock:
            handles = [h for h in self._workers.values()
                       if h.state != DEAD and h.ready.is_set()
                       and h.conn is not None]
        for h in handles:
            self._send(h, req)
        return [h.worker_id for h in handles]

    def broadcast_flush(self) -> List[WorkerID]:
        """Ship a FlushTelemetry to every registered live worker (same
        ready-gating as broadcast_stack_dump); returns the worker ids an
        answer is expected from."""
        with self._lock:
            handles = [h for h in self._workers.values()
                       if h.state != DEAD and h.ready.is_set()
                       and h.conn is not None]
        for h in handles:
            self._send(h, FlushTelemetry())
        return [h.worker_id for h in handles]

    # -- receive ------------------------------------------------------------

    def _handle_msg(self, handle: WorkerHandle, msg) -> None:
        rt = self.runtime
        if type(msg) is tuple:
            if msg[0] == wire.TASK_DONE:
                # Direct actor calls (runtime.submit_actor_direct) never
                # entered running/pin bookkeeping: route their replies
                # straight to the caller-held refs.
                if rt.on_direct_task_done(msg):
                    if handle.direct_inflight > 0:
                        handle.direct_inflight -= 1
                    return
                self._handle_msg(handle, wire.decode_task_done(msg))
                return
            raise ValueError(f"unknown wire frame tag {msg[0]!r}")
        if isinstance(msg, WorkerReady):
            handle.ready.set()
            self._cancel_register_watchdog(handle)
        elif isinstance(msg, TaskDone):
            handle.running.discard(msg.task_id)
            handle.task_meta.pop(msg.task_id, None)
            if self._native_store:
                keys = handle.arg_pins.pop(msg.task_id, [])
                if keys:
                    if handle.actor_id is not None:
                        # Actor may hold zero-copy views of its args in state;
                        # keep them pinned for the worker's lifetime.
                        handle.lifetime_pins.extend(keys)
                    else:
                        for k in keys:
                            self.store.unpin_key(k)
            # Chips NEVER return to the pool at TaskDone: libtpu holds the
            # device locks until process exit, so reuse must wait for
            # _on_worker_death (actors and dedicated task workers alike).
            is_actor_worker = handle.actor_id is not None
            if not is_actor_worker and not handle.dedicated:
                # Release BEFORE the done callback: lease-reuse dispatch
                # inside on_task_done then lands on this (hot, LIFO-first)
                # worker instead of spawning a new one.
                self._release_worker(handle)
            rt.on_task_done(msg, self.info.node_id)
            if not is_actor_worker:
                if handle.dedicated:
                    # Graceful exit request, with a hard-terminate fallback:
                    # if the KillWorker message is lost (chaos, broken pipe)
                    # the process must still die or its chips leak forever.
                    self._send(handle, KillWorker("dedicated worker done"))

                    def _ensure_dead(h=handle):
                        if h.proc.poll() is None:
                            try:
                                h.proc.terminate()
                            except Exception as e:
                                telemetry.note_swallowed(
                                    "node.ensure_dead", e)
                    t = threading.Timer(2.0, _ensure_dead)
                    t.daemon = True
                    t.start()
        elif isinstance(msg, SubmitFromWorker):
            rt.submit_spec(msg.spec)
        elif isinstance(msg, GetRequest):
            rt.on_get_request(self, msg)
        elif isinstance(msg, WaitRequest):
            rt.on_wait_request(self, msg)
        elif isinstance(msg, PutFromWorker):
            rt.on_put_from_worker(msg)
        elif isinstance(msg, ActorStateMsg):
            rt.on_actor_state(msg, self.info.node_id, handle.worker_id)
        elif isinstance(msg, AllocRequest):
            res = self.store.allocate_for_worker(msg.object_id, msg.nbytes) \
                if self._native_store else None
            if res is None:
                self._send(handle, AllocReply(msg.request_id, None))
            else:
                handle.unsealed.add(msg.object_id)
                self._send(handle, AllocReply(msg.request_id, res[0], res[1]))
        elif isinstance(msg, SealObject):
            if self._native_store:
                self.store.seal(msg.object_id)
                handle.unsealed.discard(msg.object_id)
        elif isinstance(msg, ReadDone):
            keys = handle.get_pins.pop(msg.request_id, [])
            if msg.retain:
                handle.lifetime_pins.extend(keys)
            else:
                for k in keys:
                    self.store.unpin_key(k)
        elif isinstance(msg, BorrowRetained):
            for oid in msg.object_ids:
                rt.mark_escaped(oid)
        elif isinstance(msg, ContainedRefs):
            rt.note_contained(msg.outer, msg.inner)
        elif isinstance(msg, StackDumpReply):
            rt.on_stack_reply(msg, self.info.node_id)
        elif isinstance(msg, ProfileReply):
            rt.on_profile_reply(msg, self.info.node_id)
        elif isinstance(msg, RpcCall):
            rt.on_rpc_call(self, msg)

    def _on_worker_death(self, handle: WorkerHandle) -> None:
        if self._closed:
            return
        with self._lock:
            if handle.state == DEAD:
                return
            handle.state = DEAD
            self._workers.pop(handle.worker_id, None)
            # A worker killed before registering still holds a live
            # register-watchdog timer; once popped from _workers the
            # shutdown sweep can't reach it, so cancel here.
            self._cancel_register_watchdog(handle)
            bucket = self._idle.get(handle.env_key)
            if bucket and handle.worker_id in bucket:
                bucket.remove(handle.worker_id)
            for task_id, chips in handle.assigned_chips.items():
                self._chip_pool.extend(chips)
            handle.assigned_chips.clear()
            running = list(handle.running)
            pin_keys: List[bytes] = list(handle.lifetime_pins)
            for keys in handle.arg_pins.values():
                pin_keys.extend(keys)
            for keys in handle.get_pins.values():
                pin_keys.extend(keys)
            handle.arg_pins.clear()
            handle.get_pins.clear()
            handle.lifetime_pins.clear()
            unsealed = list(handle.unsealed)
            handle.unsealed.clear()
        if self._native_store:
            for k in pin_keys:
                self.store.unpin_key(k)
            for oid in unsealed:
                try:
                    self.store.delete(oid)
                except KeyError:
                    pass
        self.runtime.on_worker_died(handle.worker_id, self.info.node_id,
                                    running, handle.actor_id,
                                    reason=handle.death_reason)

    # -- OOM killing (reference: worker_killing_policy_retriable_fifo) ------

    def select_oom_victim(self) -> Optional[WorkerHandle]:
        """Pick the worker to sacrifice under memory pressure.

        Idle pooled workers first (killing them fails nothing), then busy
        workers via the retriable-LIFO policy in memory_monitor.select_victim.
        Actor workers count as non-retriable here — the node can't see how
        many restarts the actor has left, so they're protected last.
        """
        from .memory_monitor import select_victim
        with self._lock:
            for bucket in self._idle.values():
                for wid in bucket:
                    h = self._workers.get(wid)
                    if h is not None and h.state == IDLE:
                        return h
            candidates = []
            for h in self._workers.values():
                if h.state != BUSY or not (h.running or h.direct_inflight):
                    continue
                metas = [h.task_meta.get(t) for t in h.running]
                metas = [m for m in metas if m is not None]
                if not metas and not h.direct_inflight:
                    continue
                retriable = bool(metas) and all(m[1] for m in metas) \
                    and h.actor_id is None
                starts = [m[0] for m in metas]
                if h.direct_inflight:
                    starts.append(h.direct_since)
                candidates.append((h, retriable, min(starts)))
        return select_victim(candidates)

    def oom_kill_worker(self, handle: WorkerHandle, reason: str) -> None:
        handle.death_reason = f"OOM-killed: {reason}"
        with self._lock:
            bucket = self._idle.get(handle.env_key)
            if bucket and handle.worker_id in bucket:
                bucket.remove(handle.worker_id)
        self._kill_and_reap(handle)

    # -- misc ---------------------------------------------------------------

    def kill_actor_worker(self, worker_id: WorkerID, force: bool = True) -> None:
        with self._lock:
            handle = self._workers.get(worker_id)
        if handle is None:
            return
        if force and handle.proc.poll() is None:
            # SIGKILL, not SIGTERM: workers running jax install a
            # preemption-notifier SIGTERM handler that swallows the signal,
            # which would leave the "killed" actor training forever and its
            # resources never released.
            self._kill_and_reap(handle)
        else:
            self._send(handle, KillWorker("actor killed"))

    def kill_all_actor_workers(self, reason: str = "") -> None:
        """Hard-kill every bound actor worker (head restarted from its
        WAL: these actors are being revived elsewhere; a surviving stale
        worker would be a second live instance)."""
        with self._lock:
            doomed = [h.worker_id for h in self._workers.values()
                      if h.actor_id is not None]
        for wid in doomed:
            self.kill_actor_worker(wid, force=True)

    def num_workers(self) -> int:
        with self._lock:
            return len(self._workers)

    def local_view(self) -> Dict[str, Any]:
        """Load/resource snapshot for the syncer (reference:
        ResourceViewSyncMessage contents — resources + load by node)."""
        with self._lock:
            n_workers = len(self._workers)
            n_idle = sum(len(b) for b in self._idle.values())
            n_running = sum(len(h.running) for h in self._workers.values())
            free_chips = len(self._chip_pool)
        view: Dict[str, Any] = {
            "workers": n_workers,
            "idle_workers": n_idle,
            "running_tasks": n_running,
            "free_tpu_chips": free_chips,
        }
        try:
            snap = self.memory_monitor.snapshot()
            view["memory_used_bytes"] = snap.used_bytes
            view["memory_total_bytes"] = snap.total_bytes
        except Exception as e:
            telemetry.note_swallowed("node.local_view", e)
        try:
            stats = self.store.stats()
            view["store_bytes_used"] = int(stats["used_bytes"])
            # Full store sub-view for the head's memory summary, riding
            # the existing change-driven syncer.  Only idle-stable fields
            # (no ages/timestamps): an idle cluster must not resync.
            view["store"] = self._store_view(stats)
        except Exception as e:
            telemetry.note_swallowed("node.local_view", e)
        return view

    def _store_view(self, stats: Dict[str, Any],
                    top_n: int = 5) -> Dict[str, Any]:
        """Store occupancy + lifecycle summary for UpSyncView fan-out."""
        out: Dict[str, Any] = dict(stats)
        ring = getattr(self.store, "view", None)
        if ring is None:
            return out
        out["counts"] = dict(ring.counts)
        out["transfer_bytes"] = dict(ring.transfer_bytes)
        states = ring.latest_index()
        live = [st for st in states
                if st["state"] not in ("deleted", "evicted")]
        live.sort(key=lambda st: st["nbytes"], reverse=True)
        out["top_objects"] = [
            {"object_id": st["object_id"], "nbytes": st["nbytes"],
             "state": st["state"], "pins": st["pins"],
             "pinners": st["pinners"]}
            for st in live[:top_n]]
        with self._lock:
            live_tokens = {wid.hex() for wid in self._workers}
        out["leak_candidates"] = [
            {"object_id": rec["object_id"], "nbytes": rec["nbytes"],
             "reason": rec["reason"], "reads": rec["reads"],
             "pins": rec["pins"], "pinners": rec["pinners"]}
            for rec in ring.leak_candidates(live_tokens=live_tokens)[:top_n]]
        return out

    def prestart_workers(self, n: int) -> None:
        for _ in range(n):
            h = self._spawn_worker()
            with self._lock:
                self._idle.setdefault("", []).append(h.worker_id)

    def shutdown(self) -> None:
        # _closed flips under the lock: a racing _spawn_worker either
        # sees it and skips its watchdog timer, or has already published
        # handle.register_watchdog under the same lock — in which case
        # the sweep below cancels it.
        with self._lock:
            self._closed = True
            handles = list(self._workers.values())
        self.memory_monitor.stop()
        # Workers that never registered still hold a live watchdog timer.
        for h in handles:
            self._cancel_register_watchdog(h)
        self._out_ev.set()  # sender thread sees _closed and exits
        self._sender.join(timeout=3.0)
        self._wake_poller()
        # The acceptor must be OUT of accept() before the listener fd is
        # closed: a thread blocked in accept() on a closed fd can adopt
        # the fd number when the OS reuses it for a NEW runtime's listener
        # — it then steals that runtime's worker handshakes and rejects
        # them with this (stale) authkey.  Wake it with a dummy connect,
        # join, then close.  The poller gets the same treatment for its
        # wake-pipe fds (the wake write above kicks it; _closed ends it).
        if self._acceptor.is_alive():
            try:
                s = socket.socket(socket.AF_UNIX)
                s.settimeout(1.0)
                s.connect(self._sock_path)
                s.close()
            except OSError:
                pass
            self._acceptor.join(timeout=3.0)
        self._poller.join(timeout=3.0)
        try:
            self._listener.close()
        except Exception:  # ray-tpu: noqa[RT202] — best-effort teardown
            pass
        try:
            os.close(self._poll_wake_w)
            os.close(self._poll_wake_r)
        except OSError:
            pass
        with self._lock:
            handles = list(self._workers.values())
            self._workers.clear()
            self._idle.clear()
        for h in handles:
            try:
                if h.conn is not None:
                    h.conn.close()
            except Exception:  # ray-tpu: noqa[RT202] — best-effort teardown
                pass
            if h.proc.poll() is None:
                h.proc.terminate()
        for h in handles:
            try:
                h.proc.wait(timeout=2)
            except subprocess.TimeoutExpired:
                h.proc.kill()
                # Reaped means its files are closed: a worker that held
                # chips has given them back before shutdown returns (a
                # four-chip worker takes seconds to go; the next process
                # found /dev/vfio/<n> busy: PERF.md, PR 33).
                try:
                    h.proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
        # Cleanup only after the workers are dead: rmdir on a cgroup with
        # live members fails EBUSY and strands the tree.
        self.cgroup.cleanup()
        self.store.shutdown()
