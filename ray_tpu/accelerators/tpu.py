"""TPU accelerator plugin: detection, topology, chip visibility.

Models the reference's accelerator plugin system (reference:
python/ray/_private/accelerators/accelerator.py:16 AcceleratorManager ABC;
TPU implementation python/ray/_private/accelerators/tpu.py:345 — resource
name "TPU", TPU_VISIBLE_CHIPS isolation, per-generation chips/host logic
:237, slice-head marker resource :670, topology validation :426).

Detection deliberately avoids importing jax in the driver: initializing the
TPU runtime takes exclusive hold of the chips, which must stay free for
worker processes.  Chips are discovered from the device tree / environment
instead, the same way the reference reads GCE metadata and env vars.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

from .._private.config import Config
from .accelerator import AcceleratorManager, register_accelerator

# Generation -> default chips per host for common slices (reference:
# tpu.py:237 per-generation logic).
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5e": 8, "v5p": 4, "v6e": 8}

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
# Chips owned by one process -> its TPU_CHIPS_PER_HOST_BOUNDS.
_SUBHOST_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-256"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_NAME_ENV = "TPU_NAME"
MEGASCALE_COORDINATOR_ENV = "MEGASCALE_COORDINATOR_ADDRESS"
MEGASCALE_NUM_SLICES_ENV = "MEGASCALE_NUM_SLICES"
MEGASCALE_SLICE_ID_ENV = "MEGASCALE_SLICE_ID"


class TPUAcceleratorManager(AcceleratorManager):
    resource_name = "TPU"

    @staticmethod
    def visibility_env(chip_ids: List[int],
                       host_chips: Optional[int] = None) -> Dict[str, str]:
        """Pin a process to ``chip_ids``.  A process that owns only part
        of a host's ``host_chips`` also gets per-process bounds, or
        libtpu lays it out for the whole host (reference: tpu.py
        set_current_process_visible_accelerator_ids)."""
        env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chip_ids)}
        bounds = _SUBHOST_BOUNDS.get(len(chip_ids))
        if bounds and host_chips and len(chip_ids) < host_chips:
            env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
            env[TPU_HOST_BOUNDS_ENV] = "1,1,1"
        return env

    @staticmethod
    def detect_num_chips() -> int:
        """Chips on this host, without initializing a TPU runtime."""
        override = Config.get("tpu_chips_per_host_override")
        if override:
            return override
        visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if visible:
            return len([c for c in visible.split(",") if c.strip() != ""])
        # Device nodes: /dev/accel* (TPU VM) or vfio for newer stacks.
        accel = glob.glob("/dev/accel*")
        if accel:
            return len(accel)
        vfio = glob.glob("/dev/vfio/[0-9]*")
        if vfio:
            return len(vfio)
        acc_type = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if acc_type:
            gen = TPUAcceleratorManager.generation_from_type(acc_type)
            return _CHIPS_PER_HOST.get(gen, 4)
        return 0

    @staticmethod
    def generation_from_type(accelerator_type: str) -> str:
        """'v5litepod-256' -> 'v5e', 'v4-8' -> 'v4'."""
        m = re.match(r"v(\d+)(lite)?(pod|p|e)?", accelerator_type or "")
        if not m:
            return "unknown"
        ver = m.group(1)
        if m.group(2) == "lite" or m.group(3) == "e":
            return f"v{ver}e"
        if m.group(3) == "p" and ver == "5":
            return "v5p"
        return f"v{ver}"

    @staticmethod
    def accelerator_type() -> Optional[str]:
        return os.environ.get(TPU_ACCELERATOR_TYPE_ENV)

    @staticmethod
    def slice_head_resource_name() -> Optional[str]:
        """Marker resource advertised only by a slice's worker 0, used for
        gang-scheduling one coordinator per slice (reference: tpu.py:670
        TPU-{version}-head)."""
        acc_type = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if not acc_type:
            return None
        worker_id = os.environ.get(TPU_WORKER_ID_ENV, "0")
        if worker_id != "0":
            return None
        gen = TPUAcceleratorManager.generation_from_type(acc_type)
        return f"TPU-{gen}-head"

    @staticmethod
    def num_hosts_for_type(accelerator_type: str) -> int:
        """'v5litepod-256' -> 32 hosts (256 chips / 8 per host)."""
        m = re.search(r"-(\d+)$", accelerator_type or "")
        if not m:
            return 1
        chips = int(m.group(1))
        gen = TPUAcceleratorManager.generation_from_type(accelerator_type)
        per_host = _CHIPS_PER_HOST.get(gen, 4)
        return max(1, chips // per_host)

    @staticmethod
    def set_visible_chips(chip_ids: List[int]) -> None:
        os.environ.update(TPUAcceleratorManager.visibility_env(chip_ids))

    @staticmethod
    def get_current_process_visible_chips() -> Optional[List[int]]:
        v = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if v is None:
            return None
        return [int(c) for c in v.split(",") if c.strip() != ""]


def wait_for_chips(granted: Optional[List[int]],
                   timeout_s: float = 60.0) -> float:
    """Seconds waited until the host's chips can be opened.  A chip worker
    that was killed keeps its device nodes for seconds after its parent has
    returned (a four-chip worker longest), and a backend that starts
    meanwhile dies of ``open(/dev/vfio/<n>): Device or resource busy``
    (PERF.md, PR 33).  Only where this process's grant is every chip of the
    host: a node another live worker holds is not ours to wait for."""
    import errno
    import time
    nodes = glob.glob("/dev/vfio/[0-9]*")
    if not granted or len(granted) != len(nodes):
        return 0.0
    t0 = time.monotonic()
    for node in nodes:
        while time.monotonic() - t0 < timeout_s:
            try:
                os.close(os.open(node, os.O_RDWR))
                break
            except OSError as e:
                if e.errno != errno.EBUSY:
                    break                # not ours to judge: jax will say
                time.sleep(0.5)
    return time.monotonic() - t0


def init_backend() -> int:
    """The program's own first touch of the backend, under the span
    ``worker_backend_init``: without it the user's function (or its
    weights) is the first to call jax, and the seconds a process takes to
    reach its chips hide inside user code.  Returns the number of local
    devices; on a TPU that differs from this process's chip grant it
    raises, before anything is placed on the wrong devices.  Also the
    place where a process that runs jax gets its ``xla_compile`` listener
    (``profiler/recompile.py``), and its ``py_gc`` and ``worker_sample``
    spans (``profiler/attribution.watch_process``)."""
    from ..profiler import attribution, recompile
    from ..util import telemetry
    import time
    extra: Dict[str, float] = {}
    granted = TPUAcceleratorManager.get_current_process_visible_chips()
    with telemetry.profile_span("worker_backend_init", "system", extra):
        t0 = time.monotonic()
        import jax
        extra["import_s"] = time.monotonic() - t0     # the rest: the backend
        extra["chip_wait_s"] = wait_for_chips(granted)
        devices = jax.local_devices()
    recompile.ensure_listener()
    attribution.watch_process()
    if granted and devices[0].platform == "tpu" \
            and len(devices) != len(granted):
        raise RuntimeError(
            f"this process was granted chips {granted} and jax sees "
            f"{len(devices)} devices")
    return len(devices)


def get_tpu_coordinator_env_vars(slice_id: int, num_slices: int,
                                 coordinator_address: str) -> Dict[str, str]:
    """MEGASCALE env plumbing for multi-slice (DCN) jobs (reference:
    python/ray/util/tpu.py:206 get_tpu_coordinator_env_vars and
    python/ray/train/v2/jax/config.py:95-103)."""
    if num_slices <= 1:
        return {}
    return {
        MEGASCALE_COORDINATOR_ENV: coordinator_address,
        MEGASCALE_NUM_SLICES_ENV: str(num_slices),
        MEGASCALE_SLICE_ID_ENV: str(slice_id),
    }


register_accelerator(TPUAcceleratorManager)
