"""JaxTrainer: the user-facing Train API.

Reference analog: DataParallelTrainer/JaxTrainer (reference:
python/ray/train/v2/api/data_parallel_trainer.py:159 fit,
python/ray/train/v2/jax/jax_trainer.py:20) with configs modeled on
ScalingConfig/RunConfig (reference: python/ray/air/config.py, re-exported by
train v2 with use_tpu/topology/num_slices fields,
python/ray/train/v2/api/config.py).
"""

from __future__ import annotations

import os
import tempfile
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ._checkpoint import Checkpoint
from .controller import TrainController
from .mesh.config import MeshConfig
from .watchdog import WatchdogConfig


@dataclass
class FailureConfig:
    """reference: train/v2/_internal/execution/failure_handling.

    ``max_failures`` is a lifetime budget by default; setting
    ``failure_window_s`` turns it into a rolling-window budget (a 3-day
    run shouldn't die on its 4th *unrelated* failure — only a burst of
    failures inside one window should end the run).  Restarts back off
    exponentially (bounded) so a flapping cluster isn't hammered with
    group re-formations, and an optional crash-loop circuit breaker
    fails fast — with a diagnosis bundle — when the same error signature
    recurs immediately ``crash_loop_threshold`` times in a row (no
    amount of restarting fixes a deterministic crash)."""
    max_failures: int = 0
    # Count failures against max_failures only inside this trailing
    # window (seconds).  None = lifetime counter (legacy behavior).
    failure_window_s: Optional[float] = None
    # Bounded exponential backoff between group re-formations after a
    # failure: initial * factor^n, capped at max.  0 disables.  The
    # backoff resets once an incarnation survives reset_s (a stable run
    # that hits a rare fault restarts promptly again).
    restart_backoff_initial_s: float = 1.0
    restart_backoff_max_s: float = 30.0
    restart_backoff_factor: float = 2.0
    restart_backoff_reset_s: float = 60.0
    # Crash-loop circuit breaker: when the same error signature recurs
    # this many times consecutively — each incarnation dying within
    # crash_loop_window_s of forming — stop restarting and raise
    # CrashLoopError with a diagnosis bundle.  0 disables.
    crash_loop_threshold: int = 0
    crash_loop_window_s: float = 60.0


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "min"
    # Sharded-save knobs (ray_tpu.checkpoint): async saves block the step
    # only for the device->host snapshot; the bounded write queue applies
    # backpressure past ``max_inflight`` outstanding saves.
    async_save: bool = True
    max_inflight: int = 2
    # Keep the newest shards in a peer's RAM (and pinned in the host
    # object store) so single-worker-failure recovery restores from
    # memory over the wire instead of cold storage.
    emergency_replica: bool = False


@dataclass
class ScalingConfig:
    """reference: air/config.py ScalingConfig + TPU fields of
    train/v2/api/config.py (use_tpu, topology, num_slices)."""
    num_workers: int = 1
    use_tpu: bool = False
    chips_per_worker: int = 0
    topology: Optional[str] = None
    num_slices: int = 1
    resources_per_worker: Optional[Dict[str, float]] = None
    env_per_worker: Optional[Dict[str, str]] = None
    # Form a jax.distributed world even for num_workers == 1.
    force_distributed: bool = False
    # SPMD mesh shape for the worker group (train/mesh/config.py): axis
    # sizes (or auto factorization) validated against num_workers x
    # devices_per_worker at every group (re)formation.  None = the
    # legacy pure-data-parallel path (one device per worker, no mesh).
    mesh_config: Optional["MeshConfig"] = None
    # Elastic scaling (reference: train/v2/_internal/execution/
    # scaling_policy/elastic.py): when min/max are set, the controller
    # sizes each (re)started group to what the cluster can currently fit,
    # clamped to [min_workers, max_workers], and upsizes between polls
    # when capacity appears (resize = teardown + re-form the jax world +
    # resume from the latest checkpoint — a live mesh cannot be resized).
    min_workers: Optional[int] = None
    max_workers: Optional[int] = None
    elastic_check_interval_s: float = 5.0
    # Gang-formation deadline: how long setup_dist (the jax.distributed
    # rendezvous) may block before the formation counts as failed and
    # the failure budget decides on a retry.  The default matches jax's
    # own coordination-service patience; spot-fleet runs set it low —
    # a churn kill landing mid-rendezvous otherwise stalls the whole
    # run for the full window (the dead rank never arrives, the
    # survivors block inside initialize).
    formation_timeout_s: float = 300.0

    def __post_init__(self):
        # A chip is only ever given by a grant: a "TPU" worker with no
        # chips would land in a pooled process that takes the chip if it
        # happens to be free and the CPU if not.
        if self.use_tpu and self.chips_per_worker < 1:
            raise ValueError(
                "ScalingConfig(use_tpu=True) needs chips_per_worker >= 1: "
                "a worker reaches a chip only through a TPU grant")

    @property
    def elastic(self) -> bool:
        return self.min_workers is not None or self.max_workers is not None


@dataclass
class RunConfig:
    name: str = "ray_tpu_experiment"
    storage_path: str = ""
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(
        default_factory=CheckpointConfig)
    # Hang/straggler watchdog knobs (straggler multiple, hang deadline;
    # see train/watchdog.py).
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)

    def __post_init__(self):
        if not self.storage_path:
            self.storage_path = os.path.join(
                tempfile.gettempdir(), "ray_tpu_results")


@dataclass
class Result:
    """reference: python/ray/air/result.py."""
    metrics: Dict[str, Any]
    checkpoint: Optional[Checkpoint]
    error: Optional[Exception] = None
    all_reports: List[Dict[str, Any]] = field(default_factory=list)
    num_failures: int = 0
    # Drain notices handled gracefully (urgent checkpoint + planned
    # downsize instead of a crash) — preemptions that did NOT count as
    # failures.
    num_drains: int = 0
    # World size of each group incarnation (len > 1 = elastic resizes /
    # failure restarts happened).
    world_size_history: List[int] = field(default_factory=list)
    # Goodput accounting for this run: {goodput_ratio, total_s,
    # productive_s, phases_s} (telemetry.GoodputTracker.summary()).
    goodput: Optional[Dict[str, Any]] = None
    # Rank-0 step-phase attribution: {"seconds": {phase: s},
    # "fraction": {phase: f}} summed over the run (None when no rank-0
    # report carried phases — e.g. zero completed steps).  Phases are
    # data_wait / h2d / compute / collective / ckpt_block / other; see
    # ray_tpu.train.step_phase.
    step_phases: Optional[Dict[str, Any]] = None
    # Mesh axis sizes of the final worker-group incarnation (elastic
    # resizes re-form the mesh; world_size_history says how often).
    mesh: Optional[Dict[str, int]] = None


class JaxTrainer:
    """SPMD data-parallel trainer over a gang-scheduled worker group.

    ``train_loop_per_worker`` runs once per worker with the jax.distributed
    world already formed; inside it, use ``ray_tpu.train.get_context()``
    and ``ray_tpu.train.report(...)``.
    """

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None):
        self._train_fn = train_loop_per_worker
        self._config = train_loop_config
        self._scaling = scaling_config or ScalingConfig()
        self._run_config = run_config or RunConfig()

    def fit(self) -> Result:
        import ray_tpu
        from ..util import telemetry
        if not ray_tpu.is_initialized():
            ray_tpu.init()
        controller = TrainController(
            self._train_fn, self._config, self._scaling, self._run_config)
        with telemetry.profile_span(
                "train_fit", "train",
                extra={"experiment": self._run_config.name,
                       "num_workers": self._scaling.num_workers},
                group=True):
            result = controller.run()
        return result
