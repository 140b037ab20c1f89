"""Train controller: worker group lifecycle, failure handling, reports.

Clone of the reference's Train v2 control loop (reference:
python/ray/train/v2/_internal/execution/controller/controller.py:103, loop
:682,739 — poll worker group, consult failure policy, restart from latest
checkpoint) with the torch/NCCL backend swapped for jax.distributed world
formation (reference: train/v2/jax/config.py:40 _JaxBackend — rank-0
address broadcast, per-worker env, jax.distributed.initialize, MEGASCALE
multi-slice env plumbing :95-103).
"""

from __future__ import annotations

import pickle
import socket
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from .._private import serialization
from ._checkpoint import Checkpoint, CheckpointManager
from ._context import drain_ack_prefix, drain_key


class CrashLoopError(RuntimeError):
    """The same error signature recurred immediately N times: restarting
    will not fix a deterministic crash.  Raised (as ``Result.error``) by
    the crash-loop circuit breaker with the diagnosis bundle path."""

    def __init__(self, signature: str, count: int,
                 last_error: Optional[BaseException] = None,
                 bundle_path: Optional[str] = None):
        super().__init__(
            f"crash loop: {count} consecutive restarts died with the "
            f"same signature [{signature}]"
            + (f"; diagnosis bundle: {bundle_path}" if bundle_path
               else ""))
        self.signature = signature
        self.count = count
        self.last_error = last_error
        self.bundle_path = bundle_path


def _error_signature(exc: BaseException) -> str:
    """Stable identity of a failure for crash-loop detection: type plus
    the first line of the message (line numbers / object ids in later
    lines would make every recurrence look 'different')."""
    first = str(exc).splitlines()[0] if str(exc) else ""
    return f"{type(exc).__name__}: {first[:200]}"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TrainWorker:
    """Actor hosting one training process (reference:
    train/v2/_internal/execution/worker_group/worker.py:124)."""

    def __init__(self, rank: int, world_size: int, run_id: str):
        self.rank = rank
        self.world_size = world_size
        self.run_id = run_id
        self._dist_initialized = False

    def setup_dist(self, coordinator_addr: str,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> bool:
        """Form the jax.distributed world (gloo on CPU, ICI/DCN on TPU).

        ``num_processes``/``process_id`` override the global rank/world for
        slice-local worlds: in multi-slice mode each slice is its own
        jax.distributed world and the cross-slice (DCN) axis is handled
        above it (reference: train/v2/jax/config.py:95-133 — per-slice
        coordinators + MEGASCALE env for the inter-slice fabric)."""
        import jax
        jax.distributed.initialize(
            coordinator_addr,
            num_processes=self.world_size if num_processes is None
            else num_processes,
            process_id=self.rank if process_id is None else process_id)
        self._dist_initialized = True
        return True

    def run(self, fn_blob: bytes, config: Optional[Dict[str, Any]],
            ctx_info: Dict[str, Any]) -> str:
        import os

        from . import _context
        ctx = _context.TrainContext(
            run_id=self.run_id, rank=self.rank,
            world_size=self.world_size, local_rank=self.rank,
            storage_path=ctx_info["storage_path"],
            experiment_name=ctx_info["experiment_name"],
            latest_checkpoint=ctx_info.get("latest_checkpoint"),
            slice_id=int(os.environ.get(
                "MEGASCALE_SLICE_ID", ctx_info.get("slice_id", 0))),
            num_slices=ctx_info.get("num_slices", 1),
            checkpoint_options=ctx_info.get("checkpoint"),
            mesh_info=ctx_info.get("mesh"))
        _context.set_context(ctx)
        from ..util import telemetry
        try:
            # Unpickling restores the fn's module imports, jax among them.
            with telemetry.profile_span("train_load_fn", "train"):
                fn = serialization.loads_control(fn_blob)
            if ctx_info.get("use_tpu"):
                from ..accelerators.tpu import init_backend
                init_backend()
            # Compile accounting: every trace, lowering and backend
            # compile of this worker is a ``jax_trace`` / ``jax_lower`` /
            # ``xla_compile`` span (the jax.monitoring listener: it needs
            # jax imported, so this runs AFTER the train fn deserialized
            # and after any setup_dist import), and a site wrapped with
            # ``ray_tpu.profiler.track()`` warns when it compiles again
            # after its warm-up.  ``jax.jit`` itself stays jax's.  (The
            # comment keeps its ten lines: a Mosaic kernel's body holds
            # its callers' line numbers, ``fn(config)``'s below among
            # them where they reach it, and is in its compile-cache key.)
            from ..profiler import recompile
            recompile.install()
            # group: it holds the whole step loop, whose parts are the
            # user's own and train_place_batch / train_report.
            with telemetry.profile_span(
                    "train_loop", "train", extra={"rank": self.rank},
                    group=True):
                if config is not None:
                    fn(config)
                else:
                    fn()
            # Drain the async checkpoint writer BEFORE reporting success:
            # every submitted save must have published + acked (or raised)
            # by the time the controller sees this rank finish.
            ctx.teardown()
            return "ok"
        finally:
            _context.set_context(None)

    def shutdown_dist(self) -> bool:
        if self._dist_initialized:
            try:
                import jax
                jax.distributed.shutdown()
            except Exception:
                pass
        return True

    def ping(self) -> str:
        return "pong"


@dataclass
class WorkerGroupState:
    workers: List[Any] = field(default_factory=list)  # ActorHandles
    run_refs: List[Any] = field(default_factory=list)


class TrainController:
    """Drives the worker group to completion (runs in the driver)."""

    def __init__(self, train_fn: Callable, train_loop_config,
                 scaling_config, run_config):
        from .scaling_policy import make_scaling_policy
        self.train_fn = train_fn
        self.train_loop_config = train_loop_config
        self.scaling = scaling_config
        self.run_config = run_config
        self.run_id = uuid.uuid4().hex[:12]
        # Fail fast on a mesh no configured world size can tile (the
        # sizing error belongs at fit(), not one group-formation later).
        self.mesh_config = getattr(scaling_config, "mesh_config", None)
        if self.mesh_config is not None:
            self.mesh_config.validate_scaling(scaling_config)
        #: Mesh axis sizes of the current incarnation (Result.mesh; a
        #: change between incarnations is a mesh reshape).
        self._mesh_axes: Optional[Dict[str, int]] = None
        self.policy = make_scaling_policy(scaling_config)
        self.manager = CheckpointManager(
            run_config.storage_path, run_config.name,
            num_to_keep=run_config.checkpoint_config.num_to_keep)
        self._reports: List[Dict[str, Any]] = []
        self._seen_report_keys: set = set()
        self._seen_ack_keys: set = set()
        # Rank-0 step-phase attribution totals (seconds per phase) from
        # the report stream — Result.step_phases.
        self._phase_totals: Dict[str, float] = {}
        # Goodput accounting (reference analog: MegaScale-style wall-time
        # partitioning): init/step/checkpoint/restart/idle phases; the
        # ratio lands on the ray_tpu_train_goodput_ratio gauge live.
        from ..util.telemetry import GoodputTracker
        self.goodput = GoodputTracker(initial_phase="init")
        # Hang/straggler watchdog over the per-rank report stream
        # (watchdog.py); fed from _poll_reports, polled on its own thread.
        from .watchdog import TrainWatchdog
        self.watchdog = TrainWatchdog(
            self.run_id, getattr(run_config, "watchdog", None))
        # Drain protocol / restart-hardening state.
        self._last_drain_poll_mono = 0.0
        # Monotonic stamp of the newest durable checkpoint (manifest
        # commit or legacy dir registration): the failure path books
        # "lost" from here, not from group start.
        self._last_ckpt_mono = 0.0
        self.num_drains = 0
        self._failure_times: "deque[float]" = deque()
        self._last_error_sig: Optional[str] = None
        self._crash_streak = 0

    # -- worker group -------------------------------------------------------

    def _worker_env(self, rank: int, world: int) -> Dict[str, str]:
        env: Dict[str, str] = dict(self.scaling.env_per_worker or {})
        if not self.scaling.use_tpu:
            env.setdefault("JAX_PLATFORMS", "cpu")
            env.setdefault("XLA_FLAGS", "")
            dpw = self.mesh_config.devices_per_worker \
                if self.mesh_config is not None else 1
            if dpw > 1:
                # Multi-device worker processes on the CPU substrate:
                # force XLA host-platform devices so tier-1 and the
                # bench exercise REAL multi-device meshes (on TPU the
                # chips-per-worker resource grant does this instead).
                from .mesh.runtime import xla_host_device_flags
                env["XLA_FLAGS"] = xla_host_device_flags(
                    env.get("XLA_FLAGS"), dpw)
        if self.scaling.num_slices > 1:
            from ..accelerators.tpu import get_tpu_coordinator_env_vars
            # Slice layout follows the ACTUAL group size (elastic groups
            # may be smaller than the configured num_workers).
            workers_per_slice = max(1, world // self.scaling.num_slices)
            env.update(get_tpu_coordinator_env_vars(
                slice_id=rank // workers_per_slice,
                num_slices=self.scaling.num_slices,
                coordinator_address=self._megascale_addr))
        return env

    def _devices_per_worker(self) -> int:
        if self.mesh_config is not None:
            return self.mesh_config.devices_per_worker
        # No mesh config: TPU workers still own chips_per_worker chips
        # (the status/Result display must not undercount them).
        if self.scaling.use_tpu:
            return self.scaling.chips_per_worker
        return 1

    def _resolved_axes(self, world: int) -> Dict[str, int]:
        """Mesh axis sizes a group of ``world`` processes forms (raises
        ValueError when the mesh cannot tile that world — callers treat
        it as a formation failure)."""
        total = world * self._devices_per_worker()
        if self.mesh_config is not None:
            spec = self.mesh_config.spec_for(total,
                                             self.scaling.num_slices)
        else:
            from ..parallel.mesh import MeshSpec
            spec = MeshSpec(dp=total)
        return {a: s for a, s in spec.shape()}

    def _valid_resize(self, target: int) -> int:
        """Snap a resize target to a world size the mesh can tile (the
        drain-to-invalid-size fix: never plan a group the MeshConfig
        cannot factor).  Falls back to ``target`` when nothing in range
        is valid — formation then fails into the failure budget."""
        if self.mesh_config is None:
            return target
        ceiling = self.scaling.max_workers or max(
            self.scaling.num_workers, target)
        v = self.mesh_config.nearest_valid_world(
            target, floor=1, ceiling=ceiling,
            num_slices=self.scaling.num_slices)
        return v if v is not None else target

    def _note_mesh_formed(self, world: int) -> None:
        """Record a SUCCESSFULLY formed group's mesh shape: axis gauges,
        the reshape counter (shape changed across incarnations), the KV
        status record `ray-tpu status` reads, and Result.mesh.  Called
        after the gang forms — a formation attempt that dies must not
        count as a reshape or publish a mesh that never existed."""
        from ..util import telemetry
        from .mesh.runtime import note_mesh_axes, publish_mesh_status
        axes = self._resolved_axes(world)
        if self._mesh_axes is not None and axes != self._mesh_axes:
            telemetry.inc("ray_tpu_train_mesh_reshapes_total")
        self._mesh_axes = axes
        note_mesh_axes(axes)
        publish_mesh_status(self.run_id, axes, world,
                            self._devices_per_worker())

    def _start_group(self, n: Optional[int] = None) -> WorkerGroupState:
        import ray_tpu

        n = n if n is not None else self.scaling.num_workers
        # The mesh must tile this world BEFORE actors spawn: a shape
        # mismatch is a formation failure here, not a cryptic per-worker
        # jax error after the gang formed.
        self._resolved_axes(n)
        self._megascale_addr = f"127.0.0.1:{_free_port()}"
        resources = dict(self.scaling.resources_per_worker or {})
        if self.scaling.use_tpu:
            resources["TPU"] = self.scaling.chips_per_worker

        worker_cls = ray_tpu.remote(TrainWorker)
        group = WorkerGroupState()
        for rank in range(n):
            opts: Dict[str, Any] = {
                "runtime_env": {"env_vars": self._worker_env(rank, n)},
            }
            if resources:
                opts["resources"] = resources
            group.workers.append(
                worker_cls.options(**opts).remote(rank, n, self.run_id))
        # Liveness check before dist init.
        form_t = getattr(self.scaling, "formation_timeout_s", 300.0)
        ray_tpu.get([w.ping.remote() for w in group.workers],
                    timeout=min(120.0, form_t))
        if n > 1 or self.scaling.force_distributed:
            self._init_dist(group, n, form_t)
        return group

    def _init_dist(self, group: WorkerGroupState, n: int,
                   form_t: float) -> None:
        """Form the group's jax.distributed world(s)."""
        import ray_tpu
        from ..util import telemetry
        with telemetry.profile_span("train_dist_init", "train",
                                    extra={"world": n}):
            if self.scaling.num_slices > 1 and not self.scaling.use_tpu \
                    and n % self.scaling.num_slices == 0:
                # CPU multi-slice emulation: each slice forms its own
                # jax.distributed (gloo) world; the cross-slice axis is
                # exercised by the train fn over the collective backend —
                # the DCN stand-in (reference: train/v2/jax/config.py:95,
                # per-slice coordinators).  On TPU a single world +
                # MEGASCALE env lets XLA drive the real DCN fabric.
                wps = max(1, n // self.scaling.num_slices)
                addrs = {s: f"127.0.0.1:{_free_port()}"
                         for s in range(self.scaling.num_slices)}
                ray_tpu.get([
                    w.setup_dist.remote(addrs[rank // wps],
                                        num_processes=wps,
                                        process_id=rank % wps)
                    for rank, w in enumerate(group.workers)],
                    timeout=form_t)
            else:
                addr = f"127.0.0.1:{_free_port()}"
                ray_tpu.get(
                    [w.setup_dist.remote(addr) for w in group.workers],
                    timeout=form_t)

    def _teardown_group(self, group: WorkerGroupState) -> None:
        import ray_tpu
        for w in group.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass

    # -- reports ------------------------------------------------------------

    def _poll_reports(self) -> None:
        from .._private.api import _control
        prefix = f"train/{self.run_id}/report/"
        for key in _control("kv_keys", prefix):
            if key in self._seen_report_keys:
                continue
            self._seen_report_keys.add(key)
            data = _control("kv_get", key)
            if data is None:
                continue
            payload = pickle.loads(data)
            self._reports.append(payload)
            self.watchdog.note_report(payload["rank"], payload["time"],
                                      payload.get("pid"),
                                      report_mono=payload.get("mono"),
                                      incarnation=payload.get("incarnation"))
            if payload["rank"] == 0:
                # Worker-measured checkpoint time happened inside what
                # the driver observes as the "step" phase: reattribute.
                self.goodput.reattribute(
                    "checkpoint", payload.get("ckpt_seconds", 0.0) or 0.0)
                phases = payload.get("phases") or {}
                for phase, seconds in phases.items():
                    if seconds > 0:
                        self._phase_totals[phase] = \
                            self._phase_totals.get(phase, 0.0) + seconds
                # Data-wait is idle devices, not productive step time:
                # an input-bound run's goodput should sag even though
                # the step loop never stops "stepping".
                self.goodput.reattribute(
                    "data_wait", phases.get("data_wait", 0.0) or 0.0)
                if payload.get("checkpoint_dir"):
                    self.manager.register(payload["checkpoint_dir"],
                                          payload["metrics"])
                    self._last_ckpt_mono = time.monotonic()
            # Consumed: GC the key (RT303) — report keys are write-once
            # per (rank, incarnation, seq); without the delete every run
            # grows the head KV forever.  The payload lives on in
            # self._reports.
            _control("kv_del", key)
        self._poll_ckpt_acks()

    def _poll_ckpt_acks(self) -> None:
        """Sharded-save commit protocol: collect per-rank shard acks and
        commit the global manifest once a step's ack set is complete (the
        coordinator half of ray_tpu.checkpoint; a crash before this
        commit leaves "latest" untouched)."""
        from .._private.api import _control
        from ..checkpoint.manager import ack_prefix
        for key in _control("kv_keys", ack_prefix(self.run_id)):
            if key in self._seen_ack_keys:
                continue
            data = _control("kv_get", key)
            if data is None:
                continue  # not marked seen: the read stays retryable
            self._seen_ack_keys.add(key)
            self.manager.note_ack(pickle.loads(data))
            # Consumed: GC the ack key (each is one (step, rank, nonce)
            # write-once record; note_ack holds the payload from here).
            _control("kv_del", key)
        if self.manager.commit_ready():
            self._last_ckpt_mono = time.monotonic()

    def _release_orphan_pins(self) -> None:
        """End-of-run sweep of ``ckpt/pin/<experiment>/*``.

        A worker killed mid-save leaves its newest blob pinned in the
        host object store with only its KV entry pointing at it — by
        design, so the NEXT incarnation chain-unpins it.  When the run
        ends there is no next incarnation: release whatever is left, or
        the blobs stay pinned (and escape-marked) for the rest of the
        session.  Live workers already released their own pins at
        train-fn teardown; this only reaps dead incarnations' leftovers
        (a leak the runtime sanitizer catches without this sweep).
        """
        from .._private.api import _control
        from ..util import telemetry
        try:
            prefix = f"ckpt/pin/{self.run_config.name}/"
            for key in _control("kv_keys", prefix):
                entry = _control("kv_get", key)
                if entry is None:
                    continue
                try:
                    ref = pickle.loads(entry).get("ref")
                except Exception:
                    ref = None
                if ref is not None:
                    _control("unpin_object", ref)
                _control("kv_del", key)
        except Exception as e:  # noqa: BLE001 — sweep is best-effort
            telemetry.note_swallowed("train.release_orphan_pins", e)

    # -- drain protocol (graceful preemption) -------------------------------

    def _poll_drain_notices(self, group: "WorkerGroupState"):
        """Check whether any live rank sits on a DRAINING node.  Returns
        ``(ranks, budget_s)`` — the covered ranks and the tightest
        remaining drain budget — or None.  Rate-limited: the node table
        scan costs a control round-trip per second, not per poll."""
        now = time.monotonic()
        if now - self._last_drain_poll_mono < 1.0:
            return None
        self._last_drain_poll_mono = now
        from .._private.api import _control
        from ..util import telemetry
        try:
            nodes = _control("nodes")
        except Exception as e:  # noqa: BLE001 — retried next poll
            telemetry.note_swallowed("train.drain_poll", e)
            return None
        draining = {n["node_id"]: n for n in nodes
                    if n.get("alive") and n.get("draining")}
        if not draining:
            return None
        try:
            actor_nodes = {a["actor_id"]: a.get("node_id")
                           for a in _control("list_actors")}
        except Exception as e:  # noqa: BLE001
            telemetry.note_swallowed("train.drain_poll", e)
            return None
        ranks = []
        covering = set()
        for rank, w in enumerate(group.workers):
            node = actor_nodes.get(w._actor_id.hex())
            if node in draining:
                ranks.append(rank)
                covering.add(node)
        if not ranks:
            return None
        budget_s = min(draining[n].get("drain_remaining_s", 0.0)
                       for n in covering)
        return ranks, max(0.5, budget_s)

    def _handle_drain(self, group: "WorkerGroupState", world: int,
                      budget_s: float, generation: int):
        """Drive the urgent-checkpoint half of a drain: publish the
        generation-tagged request, wait (bounded by the drain budget,
        minus a teardown margin) for every rank's flush ack while
        committing checkpoint acks as they land, then GC the protocol
        keys.  Returns ``(error, finished)``: a worker error if one died
        mid-drain (the caller then takes the failure path), and whether
        every rank's train fn already completed (the run is done — no
        re-formation needed)."""
        import ray_tpu

        from .._private.api import _control
        from ..util import telemetry
        telemetry.inc("ray_tpu_train_urgent_ckpt_total")
        # EVERY rank flushes (the commit needs all shards) and so every
        # rank can stall past the hang deadline — suppress verdicts for
        # the whole group, not just the draining ranks.
        self.watchdog.note_drain(range(world), budget_s + 30.0)
        wait_s = max(0.5, budget_s - 1.0)  # margin for teardown itself
        _control("kv_put", drain_key(self.run_id),
                 pickle.dumps({"generation": generation,
                               "budget_s": wait_s}))
        ack_prefix = drain_ack_prefix(self.run_id, generation)
        deadline = time.monotonic() + wait_s
        error: Optional[Exception] = None
        finished = False
        try:
            while time.monotonic() < deadline:
                self._poll_reports()  # commits ckpt acks as they land
                if len(set(_control("kv_keys", ack_prefix))) >= world:
                    break
                done_now, _ = ray_tpu.wait(
                    group.run_refs, num_returns=len(group.run_refs),
                    timeout=0.25)
                dead = False
                for ref in done_now:
                    try:
                        ray_tpu.get(ref)
                    except Exception as e:  # noqa: BLE001
                        error = e
                        dead = True
                if len(done_now) == len(group.run_refs):
                    finished = not dead
                    break
                if dead:
                    break
            # Final harvest: the last flush's shard acks may have landed
            # after the loop's poll.
            self._poll_reports()
        finally:
            # GC the ack keys (write-once per generation; RT303).  The
            # drain REQUEST key stays until after teardown — acked ranks
            # park on it ("my work is durable, take me down"), and
            # deleting it now would un-park them into manufacturing an
            # uncommitted tail.  _gc_drain_key() runs post-teardown.
            try:
                for key in _control("kv_keys", ack_prefix):
                    _control("kv_del", key)
            except Exception as e:  # noqa: BLE001 — best-effort GC
                telemetry.note_swallowed("train.drain_gc", e)
        return error, finished

    def _gc_drain_key(self) -> None:
        """Delete the drain request key once the group is gone (parked
        workers are dead; the next incarnation must not read it), and
        sweep straggler ack keys across ALL generations — a rank that
        acked after _handle_drain's deadline sweep would otherwise leak
        its key in the head KV forever (RT303 invariant)."""
        from .._private.api import _control
        from ..util import telemetry
        try:
            _control("kv_del", drain_key(self.run_id))
            for key in _control("kv_keys",
                                drain_ack_prefix(self.run_id)):
                _control("kv_del", key)
        except Exception as e:  # noqa: BLE001 — best-effort GC
            telemetry.note_swallowed("train.drain_gc", e)

    def _run_incarnation(self, group: "WorkerGroupState",
                         world: int):
        """Submit the train fn to a freshly formed group and drive it:
        poll reports/acks, watch for drain notices and elastic upsizes,
        and account lost work on failure.  Returns ``(error,
        resize_to)`` — the caller tears the group down either way."""
        import ray_tpu

        fn_blob = serialization.dumps_control(self.train_fn)
        ckpt_cfg = self.run_config.checkpoint_config
        if getattr(ckpt_cfg, "emergency_replica", False):
            # Peer RAM copy of the newest shards: spawn (or find)
            # the experiment's replica holder before workers run.
            from ..checkpoint import replica as _replica
            _replica.ensure_holder(self.run_config.name)
        ctx_info = {
            "storage_path": self.run_config.storage_path,
            "experiment_name": self.run_config.name,
            "latest_checkpoint": self.manager.latest(),
            "num_slices": self.scaling.num_slices,
            # Chip workers touch the backend themselves, under a span,
            # before the user's function does (worker_backend_init).
            "use_tpu": bool(self.scaling.use_tpu),
            # Resolved mesh for THIS incarnation's world: workers build
            # the global mesh from it (train.get_mesh()).  The rules
            # overrides ride along so every rank shards identically.
            # Without a MeshConfig no axes are sent — the worker falls
            # back to a dp mesh over whatever devices it actually sees
            # (the controller cannot know a TPU worker's chip count).
            "mesh": {
                "axes": dict(self._mesh_axes or {})
                    if self.mesh_config is not None else {},
                "num_slices": self.scaling.num_slices,
                "devices_per_worker": self._devices_per_worker(),
                "rules": dict(self.mesh_config.rules or {})
                    if self.mesh_config is not None else {},
                "configured": self.mesh_config is not None,
            },
            "checkpoint": {
                "async_save": getattr(ckpt_cfg, "async_save", True),
                "max_inflight": getattr(ckpt_cfg, "max_inflight", 2),
                "emergency_replica": getattr(
                    ckpt_cfg, "emergency_replica", False),
                "generation": len(self.world_size_history),
            },
        }
        group.run_refs = [
            w.run.remote(fn_blob, self.train_loop_config, ctx_info)
            for w in group.workers]
        self.goodput.enter("step")
        t_step = time.monotonic()
        error = None
        resize_to: Optional[int] = None
        last_elastic_check = time.monotonic()
        pending = list(group.run_refs)
        while pending:
            done, pending = ray_tpu.wait(
                pending, num_returns=1, timeout=0.5)
            self._poll_reports()
            for ref in done:
                # A finished rank legitimately stops reporting — tell
                # the watchdog before its hang deadline can fire.
                try:
                    self.watchdog.note_done(group.run_refs.index(ref))
                except ValueError:
                    pass
                try:
                    ray_tpu.get(ref)
                except Exception as e:  # noqa: BLE001
                    error = e
                    pending = []
                    break
            # Drain notices (preemption/maintenance): a DRAINING
            # node covering live ranks triggers the graceful path —
            # urgent checkpoint flush on every rank, then a PLANNED
            # downsize before the deadline.  The preemption books
            # ~0 lost work (the resize path restores the
            # just-committed checkpoint) instead of everything
            # since the last periodic save, and burns no
            # max_failures budget.
            if pending and error is None:
                notice = self._poll_drain_notices(group)
                if notice is not None:
                    drain_ranks, budget_s = notice
                    error, finished = self._handle_drain(
                        group, world, budget_s,
                        len(self.world_size_history))
                    if error is None and not finished:
                        self.num_drains += 1
                        # Snap to a world the mesh can tile: a drain
                        # that strands an un-factorable worker count
                        # must not plan an unformable group.
                        resize_to = self._valid_resize(
                            max(1, world - len(drain_ranks)))
                    pending = []
            # Elastic upsize check (reference: elastic.py monitor
            # decision): new capacity -> teardown + re-form the world
            # at the larger size, resuming from the latest checkpoint.
            # Gated to a CHECKPOINT BOUNDARY: the reform restores from
            # the latest committed checkpoint, so re-forming before one
            # committed this incarnation would replay the whole
            # incarnation — the upsize would cost more than it buys.
            # (The interval keeps re-checking; the upsize fires at the
            # first boundary after capacity joined.)  A run that has
            # never checkpointed at all replays from the start whenever
            # the reform fires, so gating it buys nothing — it keeps
            # the pre-gate behavior and upsizes immediately.
            if pending and error is None and \
                    time.monotonic() - last_elastic_check >= \
                    self.scaling.elastic_check_interval_s and \
                    (self._last_ckpt_mono >= t_step
                     or self._last_ckpt_mono == 0.0):
                last_elastic_check = time.monotonic()
                d = self.policy.monitor_decision(len(group.workers))
                if d is not None:
                    # A crashed worker frees resources that look like
                    # growth; drain already-failed refs first so a
                    # crash takes the failure path (and max_failures
                    # accounting), not the resize path.
                    done_now, _ = ray_tpu.wait(
                        pending, num_returns=len(pending), timeout=0)
                    for ref in done_now:
                        try:
                            ray_tpu.get(ref)
                        except Exception as e:  # noqa: BLE001
                            error = e
                            break
                    if error is None:
                        resize_to = d.num_workers
                        if d.num_workers > world:
                            from ..util import telemetry
                            telemetry.inc("ray_tpu_train_upsize_total")
                    pending = []
        # Drain reports while still in the "step" phase so their
        # ckpt_seconds reattribution has step time to pull from.
        self._poll_reports()
        if error is not None:
            # Step time SINCE THE LAST COMMITTED CHECKPOINT
            # produced no surviving work (the restart replays it):
            # badput, not goodput (MegaScale-style lost-work
            # accounting).  Work up to that commit survived — it
            # must not be booked lost.
            self.goodput.reattribute(
                "lost", time.monotonic()
                - max(t_step, self._last_ckpt_mono))
        return error, resize_to

    def _trip_crash_loop(self, signature: str,
                         last_error: Exception) -> "CrashLoopError":
        """Circuit breaker tripped: capture a diagnosis bundle (error
        signature, failure history, goodput so far) and build the
        terminal error.  Forensics are best-effort — the breaker itself
        never fails."""
        from .._private.api import _control
        from ..util import telemetry
        bundle_path = None
        diagnosis = {
            "signature": signature,
            "consecutive": self._crash_streak,
            "world_size_history": list(self.world_size_history),
            "run_id": self.run_id,
            "experiment": self.run_config.name,
            "goodput": self.goodput.summary(),
        }
        try:
            _control("export_event", "EXPORT_TRAIN_WATCHDOG",
                     {"kind": "crash_loop", "run_id": self.run_id,
                      "signature": signature,
                      "consecutive": self._crash_streak})
            bundle_path = _control("debug_dump", "crash_loop", False,
                                   {"crash_loop": diagnosis})
        except Exception as e:  # noqa: BLE001 — forensics best-effort
            telemetry.note_swallowed("train.crash_loop_bundle", e)
        return CrashLoopError(signature, self._crash_streak,
                              last_error=last_error,
                              bundle_path=bundle_path)

    # -- main loop ----------------------------------------------------------

    def run(self):
        import ray_tpu

        from ..util import telemetry
        from .trainer import Result

        failures = 0
        error: Optional[Exception] = None
        carry_target: Optional[int] = None
        self.world_size_history: List[int] = []
        self._backoff_s = \
            self.run_config.failure_config.restart_backoff_initial_s
        self.watchdog.start()
        try:
            while True:
                # First group formation is "init"; every re-formation after a
                # failure is "restart" overhead (resizes count as restart too:
                # the world re-forms and resumes from the checkpoint).
                self.goodput.enter(
                    "init" if not self.world_size_history else "restart")
                decision = self.policy.initial_decision(prefer=carry_target)
                carry_target = None
                world = decision.num_workers
                self.world_size_history.append(world)
                # Fresh incarnation: stale rank clocks must not trip on the
                # re-formed group.
                self.watchdog.reset_ranks()
                # And stale checkpoint acks from the torn-down group must
                # never complete a new incarnation's ack set (the retried
                # step re-acks under a fresh per-worker nonce key; the
                # generation tag drops straggler acks that race in late).
                self.manager.reset_pending_acks(
                    generation=len(self.world_size_history))
                t_form = time.monotonic()
                error = None
                resize_to: Optional[int] = None
                group: Optional[WorkerGroupState] = None
                try:
                    with telemetry.profile_span(
                            "train_start_group", "train",
                            extra={"world": world}):
                        group = self._start_group(world)
                    self._note_mesh_formed(world)
                except Exception as e:  # noqa: BLE001 — restartable
                    # Formation failure (capacity vanished between the
                    # sizing decision and the gang forming — e.g. a node
                    # died mid-ping): a failure like any other, not a
                    # crash of fit().  The failure budget + backoff below
                    # decide whether to try again.
                    error = e
                if group is not None:
                    error, resize_to = self._run_incarnation(group, world)
                self.goodput.enter("idle")
                if group is not None:
                    self._teardown_group(group)
                    self._gc_drain_key()
                if resize_to is not None:
                    carry_target = resize_to
                    continue  # not a failure: re-run at the new size
                if error is None:
                    break
                failures += 1
                fc = self.run_config.failure_config
                now = time.monotonic()
                incarnation_lifetime = now - t_form
                # Crash-loop circuit breaker: the same signature dying
                # immediately, N times in a row, is deterministic — more
                # restarts only burn quota.  Fail fast with a diagnosis
                # bundle naming the signature.
                sig = _error_signature(error)
                if sig == self._last_error_sig and \
                        incarnation_lifetime < fc.crash_loop_window_s:
                    self._crash_streak += 1
                else:
                    self._crash_streak = 1
                self._last_error_sig = sig
                if fc.crash_loop_threshold and \
                        self._crash_streak >= fc.crash_loop_threshold:
                    error = self._trip_crash_loop(sig, error)
                    break
                # Failure budget: rolling window when configured (a long
                # run shouldn't die on its Nth *unrelated* failure),
                # lifetime counter otherwise.
                if fc.failure_window_s is not None:
                    self._failure_times.append(now)
                    cutoff = now - fc.failure_window_s
                    while self._failure_times and \
                            self._failure_times[0] < cutoff:
                        self._failure_times.popleft()
                    over_budget = len(self._failure_times) > fc.max_failures
                else:
                    over_budget = failures > fc.max_failures
                if over_budget:
                    break
                from ..util import telemetry
                telemetry.inc("ray_tpu_train_worker_restarts_total", world)
                # Bounded exponential backoff between re-formations: a
                # flapping cluster (or a slow-to-release resource pool)
                # shouldn't be hammered with group formation attempts.
                # An incarnation that proved stable resets the ladder.
                if fc.restart_backoff_initial_s > 0:
                    if incarnation_lifetime >= fc.restart_backoff_reset_s:
                        self._backoff_s = fc.restart_backoff_initial_s
                    delay = min(self._backoff_s, fc.restart_backoff_max_s)
                    self._backoff_s = min(
                        self._backoff_s * fc.restart_backoff_factor,
                        fc.restart_backoff_max_s)
                    telemetry.observe(
                        "ray_tpu_train_restart_backoff_seconds", delay)
                    self.goodput.enter("restart")
                    time.sleep(delay)
                # Restart: fresh group resumes from the latest committed
                # checkpoint (reference: controller failure policy ->
                # group teardown -> re-create -> resume, SURVEY §3.4 step 6).
                # Prefer the previous size so the policy grace-waits for the
                # dead group's resources to release instead of greedily
                # under-sizing on the first partial fit.
                carry_target = world

        finally:
            # Any escape from the fit loop (group-formation
            # failure, KeyboardInterrupt) must still stop the
            # monitor thread and join pending bundle writers.
            self.watchdog.stop()
            self.goodput.finish()
            if getattr(self.run_config.checkpoint_config,
                       "emergency_replica", False):
                self._release_orphan_pins()
        rank0 = sorted((r for r in self._reports if r["rank"] == 0),
                       key=lambda r: r["time"])
        last_metrics = rank0[-1]["metrics"] if rank0 else {}
        latest = self.manager.latest()
        total_phase_s = sum(self._phase_totals.values())
        step_phases = {
            "seconds": {k: round(v, 6)
                        for k, v in sorted(self._phase_totals.items())},
            "fraction": {k: round(v / total_phase_s, 4)
                         for k, v in sorted(self._phase_totals.items())}
            if total_phase_s > 0 else {},
        } if self._phase_totals else None
        return Result(
            metrics=last_metrics,
            checkpoint=Checkpoint(latest) if latest else None,
            error=error,
            all_reports=self._reports,
            num_failures=failures,
            num_drains=self.num_drains,
            world_size_history=self.world_size_history,
            goodput=self.goodput.summary(),
            step_phases=step_phases,
            mesh=dict(self._mesh_axes) if self._mesh_axes else None)
