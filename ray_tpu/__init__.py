"""ray_tpu — a TPU-native distributed compute framework.

Capability surface of Ray (tasks, actors, objects, placement groups,
collectives, Train/Tune/Data/Serve/RL libraries) re-architected TPU-first:
scheduling is slice/chip aware, the data plane between chips is XLA
collectives over ICI/DCN (not NCCL object push), and the compute path is
jax/pjit/pallas SPMD programs.

Quick start::

    import ray_tpu

    ray_tpu.init(num_cpus=4)

    @ray_tpu.remote
    def f(x):
        return x * 2

    assert ray_tpu.get(f.remote(21)) == 42

Heavy subsystems (``ray_tpu.train``, ``ray_tpu.data``, ``ray_tpu.parallel``,
``ray_tpu.ops``, ``ray_tpu.models``, ``ray_tpu.collective``) are imported
lazily so that worker processes and non-jax users never pay jax import cost.
"""

from __future__ import annotations

import os as _os
import threading
from typing import Any, Dict, Optional

# Compile cache directory: settled once, before anything imports jax
# (_private/compile_cache.py); spawned workers inherit it.
from ._private import compile_cache as _compile_cache
_compile_cache.configure()

# Opt-in runtime lock-order detector (devtools/lockdebug.py).  Installed
# BEFORE the _private imports so the wrappers catch module-level framework
# locks too, not just ones created after init().  Workers inherit the env
# var, so the whole cluster is instrumented consistently.
if _os.environ.get("RAY_TPU_DEBUG_LOCKS") == "1":
    from .devtools import lockdebug as _lockdebug
    _lockdebug.install()

# Lighter opt-in lock-contention profiler (same module): per-site
# wait/hold histograms only, no order graph — cheap enough for real
# runs.  A no-op when the full debug mode above is active (its wrappers
# already collect contention stats).
if _os.environ.get("RAY_TPU_LOCK_PROFILE") == "1":
    from .devtools import lockdebug as _lockdebug
    _lockdebug.install_profile()

# Opt-in implicit host-sync tripwire (devtools/syncdebug.py): patches
# jax's ArrayImpl host-coercion points so every implicit device->host
# sync (float()/.item()/np.asarray() on a device array) is timed and
# attributed to its call site.  Silently a no-op when jax isn't
# importable in this process.
if _os.environ.get("RAY_TPU_SYNC_DEBUG") == "1":
    from .devtools import syncdebug as _syncdebug
    _syncdebug.install()

# Opt-in runtime resource-leak sanitizer (_private/sanitizer.py):
# registries for framework threads / pins / tracked files / named
# actors, snapshotted at cluster start and diffed at shutdown.
# Installed before the _private imports so module-level framework
# threads are attributed too.
if _os.environ.get("RAY_TPU_SANITIZE") == "1":
    from ._private import sanitizer as _sanitizer
    _sanitizer.install()

from ._private import runtime as _runtime_mod
from ._private.api import (ActorClass, ActorHandle, ActorMethod, ObjectRef,
                           ObjectRefGenerator, PlacementGroup, RemoteFunction,
                           available_resources, cluster_resources, get,
                           get_actor, kill, nodes, placement_group, put,
                           remote, remove_placement_group, wait)
from ._private.exceptions import (ActorError, GetTimeoutError, ObjectLostError,
                                  OutOfMemoryError, RayTpuError, TaskError,
                                  WorkerCrashedError)
from ._private.scheduler import (NodeAffinitySchedulingStrategy,
                                 PlacementGroupSchedulingStrategy)

__version__ = "0.1.0"

_init_lock = threading.Lock()


def init(*, num_cpus: Optional[float] = None, num_tpus: Optional[int] = None,
         resources: Optional[Dict[str, float]] = None,
         namespace: str = "default", ignore_reinit_error: bool = True,
         head_port: Optional[int] = None,
         cluster_token: Optional[bytes] = None,
         address: Optional[str] = None,
         state_dir: Optional[str] = None,
         **_compat: Any):
    """Start the ray_tpu runtime in this process (driver).

    Reference analog: ray.init (python/ray/_private/worker.py:1441) — but the
    control plane, node plane and driver live in one process for single-host
    sessions; worker processes are spawned on demand.

    ``head_port`` (0 = ephemeral) opens the cluster join point so remote
    nodes can register via ``ray-tpu start --address=<host:port>``
    (reference: ray start joining a GCS).  The bound address is
    ``runtime.head_server.address``.

    ``address="host:port"`` connects as a remote driver instead of starting
    a runtime (reference: ray.init("ray://...") via python/ray/util/client):
    API calls are proxied to the running head.  ``cluster_token`` must match
    the head's.
    """
    with _init_lock:
        if address is not None:
            from ._private import client as _client_mod
            from ._private import cluster as _cluster_mod
            existing = _runtime_mod.current_runtime()
            if existing is not None:
                if ignore_reinit_error:
                    return existing
                raise RuntimeError("ray_tpu.init() already called")
            return _client_mod.connect(
                address, cluster_token or _cluster_mod.DEFAULT_TOKEN)
        if _runtime_mod.driver_runtime() is not None:
            if ignore_reinit_error:
                return _runtime_mod.driver_runtime()
            raise RuntimeError("ray_tpu.init() already called")
        # Cluster start: runtime, node, object store up.  The span ends
        # with a runtime in place, so it is the first one recorded.
        from .util import telemetry as _telemetry
        with _telemetry.profile_span("runtime_init", "system"):
            return _runtime_mod.init_runtime(
                num_cpus=num_cpus, num_tpus=num_tpus, resources=resources,
                namespace=namespace, head_port=head_port,
                cluster_token=cluster_token, state_dir=state_dir)


def is_initialized() -> bool:
    return _runtime_mod.current_runtime() is not None


def timeline(filename: Optional[str] = None) -> str:
    """Chrome-trace dump of task execution (reference: ray.timeline)."""
    from .util.state import timeline as _timeline
    return _timeline(filename)


def shutdown() -> None:
    from ._private.client import ClientRuntime, disconnect as _client_disconnect
    if isinstance(_runtime_mod.current_runtime(), ClientRuntime):
        _client_disconnect()
        return
    rt = _runtime_mod.driver_runtime()
    if rt is not None:
        # Leak-sanitizer gate (RAY_TPU_SANITIZE=1): named actors are
        # inspected before teardown marks everything DEAD; threads /
        # pins / file handles are diffed after teardown completes, so a
        # LeakError never leaves a half-shut cluster behind.
        from ._private import sanitizer as _san
        pre = _san.pre_shutdown(rt)
        rt.shutdown()
        _san.check_after_shutdown(pre)


def _private_worker_mode(worker_runtime) -> None:
    """Called by worker_entry to install the worker-side runtime facade."""
    _runtime_mod.set_worker_runtime(worker_runtime)


def __getattr__(name: str):
    # Lazy submodule loading: ray_tpu.train / data / parallel / ops / models /
    # collective / tune / serve / rl / util.
    import importlib
    if name in ("train", "data", "parallel", "ops", "models", "collective",
                "tune", "serve", "rl", "util", "accelerators", "llm",
                "dashboard", "autoscaler"):
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "init", "shutdown", "is_initialized", "timeline",
    "remote", "get", "put", "wait",
    "kill", "get_actor", "cluster_resources", "available_resources", "nodes",
    "placement_group", "remove_placement_group", "PlacementGroup",
    "ObjectRef", "ObjectRefGenerator", "ActorHandle", "ActorClass",
    "ActorMethod", "RemoteFunction",
    "NodeAffinitySchedulingStrategy", "PlacementGroupSchedulingStrategy",
    "RayTpuError", "TaskError", "ActorError", "WorkerCrashedError",
    "OutOfMemoryError",
    "ObjectLostError", "GetTimeoutError",
]
