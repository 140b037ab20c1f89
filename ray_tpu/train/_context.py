"""Per-worker training context + report API.

Reference analog: ray.train.get_context()/report
(reference: python/ray/train/v2/api/train_fn_utils.py:23 report,
.../execution/context.py).  report() publishes metrics (and optionally a
checkpoint) to the controller through the runtime KV store; the rank-0
checkpoint is committed by the CheckpointManager.
"""

from __future__ import annotations

import os
import pickle
import time
from typing import Any, Dict, Optional

from ._checkpoint import Checkpoint

_context: Optional["TrainContext"] = None


class TrainContext:
    def __init__(self, run_id: str, rank: int, world_size: int,
                 local_rank: int, storage_path: str,
                 experiment_name: str,
                 latest_checkpoint: Optional[str] = None,
                 slice_id: int = 0, num_slices: int = 1,
                 checkpoint_options: Optional[Dict[str, Any]] = None,
                 mesh_info: Optional[Dict[str, Any]] = None):
        self.run_id = run_id
        self._rank = rank
        self._world_size = world_size
        self._local_rank = local_rank
        self.storage_path = storage_path
        self.experiment_name = experiment_name
        self._latest_checkpoint = latest_checkpoint
        self.slice_id = slice_id
        self.num_slices = num_slices
        self._ckpt_options = dict(checkpoint_options or {})
        self._ckpt_client = None
        self._report_seq = 0
        # Unique per worker incarnation: keeps report keys distinct across
        # failure-recovery restarts (seq restarts at 0 in a fresh worker).
        import uuid as _uuid
        self._incarnation = _uuid.uuid4().hex[:8]
        # Telemetry: report-to-report interval = one observed step.  The
        # wall stamp anchors the timeline span; the interval itself is
        # measured on the monotonic clock (NTP-immune).
        self._last_report_wall = time.time()
        self._last_report_mono = time.monotonic()
        # Drain protocol (preemption notice): report() polls the
        # controller's generation-tagged drain request and answers it
        # once with an urgent checkpoint flush + ack.
        self._generation = self._ckpt_options.get("generation")
        self._last_drain_check_mono = 0.0
        self._drain_acked = False
        # Mesh runtime (train/mesh): the controller resolves the axis
        # sizes for THIS incarnation's world; the worker builds the
        # global jax mesh lazily on first get_mesh()/shard() use.
        self._mesh_info = dict(mesh_info or {})
        self._mesh = None

    # -- mesh runtime -------------------------------------------------------

    def mesh(self):
        """The group's global SPMD mesh (built on first use over the
        jax.distributed world's full device set; falls back to a pure
        data-parallel mesh when no MeshConfig was configured)."""
        if self._mesh is None:
            import jax

            from ..parallel.mesh import MeshSpec
            from .mesh.runtime import build_worker_mesh
            axes = self._mesh_info.get("axes") or {}
            num_slices = int(self._mesh_info.get("num_slices",
                                                 self.num_slices) or 1)
            if axes:
                spec = MeshSpec(num_slices=num_slices,
                                **{a: int(s) for a, s in axes.items()})
            else:
                spec = MeshSpec(dp=len(jax.devices()),
                                num_slices=num_slices)
            self._mesh = build_worker_mesh(spec)
        return self._mesh

    def sharding_rules(self):
        """Logical-axis rules: defaults + the MeshConfig's overrides
        (same merge as MeshConfig.sharding_rules — one implementation,
        so worker-side resolution can never drift from config-side)."""
        from .mesh.config import rules_with_overrides
        return rules_with_overrides(self._mesh_info.get("rules"))

    def get_world_rank(self) -> int:
        return self._rank

    def get_world_size(self) -> int:
        return self._world_size

    def get_local_rank(self) -> int:
        return self._local_rank

    def get_experiment_name(self) -> str:
        return self.experiment_name

    def get_checkpoint(self) -> Optional[Checkpoint]:
        if self._latest_checkpoint and os.path.exists(self._latest_checkpoint):
            return Checkpoint(self._latest_checkpoint)
        return None

    # -- sharded checkpoint subsystem ---------------------------------------

    def checkpoint_client(self):
        """This worker's save/restore client (ray_tpu.checkpoint)."""
        if self._ckpt_client is None:
            from ..checkpoint.manager import (WorkerCheckpointClient,
                                             _dir_step)
            opts = self._ckpt_options
            start = 0
            if self._latest_checkpoint:
                # Resume the auto-step sequence past the restored
                # checkpoint so a restarted worker never overwrites a
                # committed step directory.
                s = _dir_step(os.path.basename(
                    os.path.normpath(self._latest_checkpoint)))
                if s is not None:
                    start = s + 1
            self._ckpt_client = WorkerCheckpointClient(
                run_id=self.run_id, rank=self._rank,
                world_size=self._world_size,
                run_root=os.path.join(os.path.abspath(self.storage_path),
                                      self.experiment_name),
                experiment=self.experiment_name,
                async_save=opts.get("async_save", True),
                max_inflight=opts.get("max_inflight", 2),
                emergency_replica=opts.get("emergency_replica", False),
                initial_step=start,
                generation=opts.get("generation"))
        return self._ckpt_client

    def teardown(self) -> None:
        """Flush + close the async checkpoint writer (run at the end of
        the train fn so every submitted save acks before the worker
        reports success)."""
        if self._ckpt_client is not None:
            self._ckpt_client.close()
            self._ckpt_client = None


def set_context(ctx: Optional[TrainContext]) -> None:
    global _context
    _context = ctx


def get_context() -> TrainContext:
    if _context is None:
        raise RuntimeError(
            "ray_tpu.train.get_context() called outside a train worker")
    return _context


def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    """Report metrics (+ checkpoint) from inside the train fn."""
    from ..util import telemetry
    ctx = get_context()
    with telemetry.profile_span("train_report", "train",
                                extra={"step": ctx._report_seq + 1,
                                       **_moe_loads(metrics),
                                       **_loop_readings(metrics)}):
        _report(ctx, metrics, checkpoint)


#: a train step's metrics of its expert layers' loads (parallel.spmd) ->
#: the built-in metric each is recorded as
_MOE_KEYS = {"moe_held_assignments": "ray_tpu_moe_held_assignments",
             "moe_load_max_over_mean": "ray_tpu_moe_load_max_over_mean",
             "moe_dropped": "ray_tpu_moe_dropped_total",
             "moe_sliced_calls": "ray_tpu_moe_sliced_calls_total"}


def _moe_loads(metrics: Dict[str, Any]) -> Dict[str, float]:
    """The reported step's expert loads under their built-in names, read to
    the host (they may still be device scalars)."""
    return {name: float(metrics[key]) for key, name in _MOE_KEYS.items()
            if key in metrics}


#: a train step's metrics of its passes over a looped stack (parallel.spmd)
#: -> the gauge each is recorded as; one sample a pass (tag ``pass``) where
#: the metric has one value a pass
_LOOP_KEYS = {"loop_loss": "ray_tpu_train_loop_loss",
              "loop_exit_share": "ray_tpu_train_loop_exit_share",
              "loop_exit_entropy": "ray_tpu_train_loop_exit_entropy",
              # and of a widened residual stream and a prediction module
              "mtp_loss": "ray_tpu_lm_mtp_loss",
              "hc_sinkhorn_residual": "ray_tpu_hc_sinkhorn_residual",
              # and of a state-space model's chunked scans
              "ssm_chunk_carry": "ray_tpu_ssm_chunk_carry",
              "ssm_chunks_with_boundary": "ray_tpu_ssm_chunks_with_boundary",
              # and of a batch of packed rows
              "pack_documents_a_row": "ray_tpu_pack_documents_a_row",
              "pack_pairs_share": "ray_tpu_pack_pairs_share",
              # and of a delta-rule model's
              "kda_chunk_carry": "ray_tpu_kda_chunk_carry",
              # and of a differential attention's pair
              "gdla_lambda_mean": "ray_tpu_gdla_lambda_mean",
              # and of a window attention's learned sink
              "sink_mass_mean": "ray_tpu_attn_sink_mass_mean"}
#: and of a model's several output heads a position: one sample a head
_HEAD_KEYS = {"head_loss": "ray_tpu_train_head_loss"}
#: the tag a gauge's samples are told apart by where it has one a pass or head
_LIST_TAGS = {**{name: "pass" for name in _LOOP_KEYS.values()},
              **{name: "head" for name in _HEAD_KEYS.values()}}


def _loop_readings(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """The reported step's passes and heads under their gauges' names, read
    to the host: a float, or a list with one float a pass or head."""
    import numpy as np
    return {name: np.asarray(metrics[key], float).tolist()
            for key, name in {**_LOOP_KEYS, **_HEAD_KEYS}.items()
            if key in metrics}


def _report(ctx: "TrainContext", metrics: Dict[str, Any],
            checkpoint: Optional[Checkpoint]) -> None:
    ctx._report_seq += 1
    from .._private.api import _control
    from ..profiler import attribution
    from ..util import telemetry
    now = time.time()
    now_mono = time.monotonic()
    ckpt_s = telemetry.pop_checkpoint_seconds()
    # Step-phase attribution: whatever this step declared through
    # train.step_phase(), plus checkpoint-blocking time and the derived
    # unattributed remainder ("other").  seq 1's window is init/compile,
    # not a step — no remainder is derived for it.
    step_s = (now_mono - ctx._last_report_mono) \
        if ctx._report_seq > 1 else None
    phases = attribution.finalize_step_phases(
        attribution.pop_phases(), step_s, ckpt_s)
    payload = {
        "metrics": dict(metrics),
        "rank": ctx.get_world_rank(),
        "seq": ctx._report_seq,
        "time": now,
        # Same-process monotonic stamp: the watchdog measures this
        # rank's report-to-report intervals from it (wall time steps
        # under NTP; deltas of one process's monotonic clock do not).
        # The incarnation scopes the stamp: a restarted worker's clock
        # has a different base and must not be differenced.
        "mono": now_mono,
        "incarnation": ctx._incarnation,
        # Worker pid: lets the watchdog's stack auto-capture mark which
        # process record belongs to a flagged rank.
        "pid": os.getpid(),
        "checkpoint_dir": checkpoint.path if checkpoint else None,
        # Checkpoint seconds inside this report window (goodput
        # reattribution at the controller).
        "ckpt_seconds": ckpt_s,
        # Per-phase step decomposition (data_wait/h2d/compute/.../other):
        # the controller aggregates Result.step_phases from rank 0 and
        # reattributes data-wait out of goodput's productive phase.
        "phases": phases,
    }
    _note_step(ctx, now, now_mono, metrics, phases)
    _control("kv_put",
             f"train/{ctx.run_id}/report/{ctx.get_world_rank()}/"
             f"{ctx._incarnation}/{ctx._report_seq}",
             pickle.dumps(payload))
    # Progress published first, THEN answer any pending drain request:
    # the controller sees this step's checkpoint registration before the
    # urgent-flush ack completes its ack set.
    _maybe_drain_flush(ctx)


def save_checkpoint(tree: Any, metrics: Optional[Dict[str, Any]] = None,
                    *, shard_spec=None, step: Optional[int] = None,
                    sync: Optional[bool] = None) -> str:
    """Save this rank's shards of ``tree`` through the distributed
    checkpoint subsystem; returns the checkpoint directory.

    With async saves (the default, ``CheckpointConfig.async_save``), the
    call blocks only for the device->host snapshot — serialization and
    the write happen on a background thread while training continues —
    and the checkpoint becomes ``latest`` only after EVERY rank's shard
    landed and the coordinator committed the manifest atomically.
    ``shard_spec(key, leaf) -> (global_shape, index)`` declares the slice
    of a global array this rank holds (see
    ``ray_tpu.checkpoint.even_shard_spec``)."""
    ctx = get_context()
    if ctx._mesh is not None:
        # Stamp the saving mesh's shape so a later restore can tell a
        # same-shape resume from a mesh reshape (reshape counter).
        from .mesh.reshape import save_metrics as _mesh_save_metrics
        metrics = _mesh_save_metrics(ctx._mesh, metrics)
    return ctx.checkpoint_client().save(tree, metrics=metrics,
                                        shard_spec=shard_spec, step=step,
                                        sync=sync)


def load_checkpoint(placement=None) -> Optional[Any]:
    """Restore the latest committed checkpoint's pytree, resharded to
    ``placement(key, global_shape) -> index`` (None = full arrays; see
    ``ray_tpu.checkpoint.even_placement``).  Prefers in-memory emergency
    replica shards over disk when replication is enabled.  Returns None
    when the run has no checkpoint yet."""
    ctx = get_context()
    if not ctx._latest_checkpoint or \
            not os.path.exists(ctx._latest_checkpoint):
        return None
    return ctx.checkpoint_client().load(ctx._latest_checkpoint,
                                        placement=placement)


def get_mesh():
    """The worker group's global SPMD mesh (inside a train fn).  Built
    from the controller-resolved MeshConfig axes; without a MeshConfig
    it is a pure data-parallel mesh over every device in the world."""
    return get_context().mesh()


def shard(tree: Any, logical_tree: Any):
    """Place a pytree of host arrays onto the group mesh per a parallel
    pytree of logical-axis tuples (``parallel.sharding`` rules + the
    MeshConfig's overrides).  Every process passes the same full host
    values; each device materializes only its shard."""
    ctx = get_context()
    from .mesh.runtime import shard_tree
    return shard_tree(tree, logical_tree, ctx.mesh(),
                      rules=ctx.sharding_rules())


def shard_batch(batch: Any):
    """Place this process's LOCAL batch rows onto the mesh's data axes
    (leading dim over (dp, fsdp), seq over sp when sized): together the
    processes' rows form one global batch array."""
    ctx = get_context()
    from .mesh.runtime import shard_batch_tree
    return shard_batch_tree(batch, ctx.mesh(),
                            rules=ctx.sharding_rules())


def load_sharded(logical_tree: Any) -> Optional[Any]:
    """Restore the latest committed checkpoint directly onto the group
    mesh (mesh-reshape restore: the saved mesh shape may differ — each
    process reads only the index slices its devices own).  Returns None
    when the run has no checkpoint yet."""
    ctx = get_context()
    if not ctx._latest_checkpoint or \
            not os.path.exists(ctx._latest_checkpoint):
        return None
    from .mesh.reshape import restore_to_mesh, sharding_tree
    shardings = sharding_tree(logical_tree, ctx.mesh(),
                              rules=ctx.sharding_rules())
    client = ctx.checkpoint_client()
    return restore_to_mesh(
        ctx._latest_checkpoint, shardings,
        loader=lambda path, placement: client.load(path,
                                                   placement=placement),
        # One reshape event per GROUP restore, not one per process.
        count_reshape=ctx.get_world_rank() == 0)


def drain_key(run_id: str) -> str:
    """KV key the controller publishes a drain request under."""
    return f"train/{run_id}/drain"


def drain_ack_prefix(run_id: str, generation=None) -> str:
    """Ack-key prefix — ONE source of truth for the protocol's key
    layout (the controller polls and GCs by this prefix; generation=None
    spans every generation for the post-teardown sweep)."""
    base = f"train/{run_id}/drain_ack/"
    return base if generation is None else f"{base}{generation}/"


def drain_ack_key(run_id: str, generation, rank: int) -> str:
    return drain_ack_prefix(run_id, generation) + str(rank)


def _maybe_drain_flush(ctx: "TrainContext") -> None:
    """Worker half of the drain protocol: when the controller posts a
    drain request for this generation, flush the async checkpoint writer
    (every submitted save publishes, acks, and pushes its emergency RAM
    replica) and ack — the urgent checkpoint that makes a preemption a
    planned downsize instead of lost work.  Rate-limited so fast step
    loops don't pay a KV round-trip per report."""
    now_mono = time.monotonic()
    if ctx._drain_acked or \
            now_mono - ctx._last_drain_check_mono < 0.25:
        return
    ctx._last_drain_check_mono = now_mono
    from .._private.api import _control
    raw = _control("kv_get", drain_key(ctx.run_id))
    if raw is None:
        return
    try:
        req = pickle.loads(raw)
    except Exception:
        return
    if req.get("generation") != ctx._generation:
        return  # stale request from a torn-down incarnation
    ctx._drain_acked = True
    budget_s = max(1.0, float(req.get("budget_s", 30.0)))
    err = None
    try:
        if ctx._ckpt_client is not None:
            ctx._ckpt_client.flush(timeout=budget_s)
    except Exception as e:  # noqa: BLE001 — reported in the ack
        err = f"{type(e).__name__}: {e}"
    _control("kv_put",
             drain_ack_key(ctx.run_id, ctx._generation,
                           ctx.get_world_rank()),
             pickle.dumps({"rank": ctx.get_world_rank(),
                           "incarnation": ctx._incarnation,
                           "flushed": ctx._ckpt_client is not None,
                           "error": err}))
    # Park until the controller tears this group down: the ack means
    # "my work is durable — take me down".  Stepping on would only
    # manufacture an uncommitted tail (work the restart re-executes as
    # lost) and race fresh saves/pins against the teardown kill.
    # Bounded: if the drain is cancelled (key gone) or the deadline
    # passes with this worker still alive, resume training.
    deadline = time.monotonic() + budget_s + 15.0
    while time.monotonic() < deadline:
        if _control("kv_get", drain_key(ctx.run_id)) is None:
            break
        time.sleep(0.2)


def _note_step(ctx: "TrainContext", now: float, now_mono: float,
               metrics: Dict[str, Any],
               phases: Optional[Dict[str, float]] = None) -> None:
    """Built-in train metrics from the report stream: each rank-0
    report-to-report interval is one step (histogram + timeline span);
    token counts ride along when the user metrics carry a tokens key."""
    from ..profiler import attribution
    from ..util import telemetry
    telemetry.inc("ray_tpu_train_reports_total")
    for name, value in _moe_loads(metrics).items():
        if name.endswith("_total"):
            telemetry.inc(name, value)
        else:
            telemetry.set_gauge(name, value)
    for name, value in _loop_readings(metrics).items():
        if isinstance(value, list):
            for t, v in enumerate(value):
                telemetry.set_gauge(name, v, tags={_LIST_TAGS[name]: str(t)})
        else:
            telemetry.set_gauge(name, value)
    for key in ("tokens", "num_tokens", "tokens_per_step"):
        v = metrics.get(key)
        if isinstance(v, (int, float)) and v > 0:
            telemetry.inc("ray_tpu_train_tokens_total", v)
            break
    # Per-device HBM used/peak gauges (rate-limited; absent on backends
    # without memory_stats) — creeping HBM is a silent step-time killer.
    attribution.note_hbm_gauges()
    # seq 1 measures from context construction — that window is
    # init/JIT compile, not a step (the controller's goodput tracker
    # accounts it as "init"); report-to-report starts at seq 2.
    if ctx.get_world_rank() == 0 and ctx._report_seq > 1:
        dur = now_mono - ctx._last_report_mono
        if dur > 0:
            telemetry.observe("ray_tpu_train_step_seconds", dur)
            for phase, seconds in (phases or {}).items():
                telemetry.observe("ray_tpu_train_step_phase_seconds",
                                  seconds, tags={"phase": phase})
            # Span: wall anchor for position, monotonic length.
            telemetry._emit_span(
                "train_step", "train", ctx._last_report_wall,
                ctx._last_report_wall + dur,
                extra={"seq": ctx._report_seq, "run_id": ctx.run_id,
                       "phases": {k: round(v, 6)
                                  for k, v in (phases or {}).items()}})
    ctx._last_report_wall = now
    ctx._last_report_mono = now_mono
