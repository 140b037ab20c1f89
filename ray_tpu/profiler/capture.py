"""Worker/driver-side profile capture: host sampling + jax.profiler.

One half of the on-demand cluster profiler (the other half — fan-out,
collection and merging — lives in ``_private/runtime.py`` and
``profiler/merge.py``).  ``capture_profile`` runs IN the profiled
process: a pure-Python sampling profiler walks ``sys._current_frames()``
at a fixed rate (no py-spy dependency, works in any interpreter we own),
and optionally brackets the window with ``jax.profiler``
start_trace/stop_trace so the XLA-level TensorBoard artifacts ride along.

Clock alignment: the ProfileRequest carries the driver's wall clock at
send time; the capturing process records ``clock_offset_s = local_wall -
driver_wall`` at receipt (bounded above by transit time), and the merger
shifts every event by ``-clock_offset_s`` so the merged trace is in
driver-clock coordinates.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

#: One capture at a time per process: jax.profiler is process-global and
#: overlapping samplers would double the sampling load mid-incident.
_active_lock = threading.Lock()

#: Cap on jax artifact bytes shipped driver-ward per capture (the
#: TensorBoard xplane protos are usually ~100KB on small programs but can
#: balloon; past the cap the files stay on the worker and only their
#: paths are reported).
MAX_JAX_ARTIFACT_BYTES = 8 * 1024 * 1024


def _thread_names() -> Dict[int, str]:
    names: Dict[int, str] = {}
    for t in threading.enumerate():
        if t.ident is not None:
            names[t.ident] = t.name
    return names


def _sample_once(skip_ident: int, max_depth: int = 12) -> Dict[int, Dict]:
    """One ``sys._current_frames()`` snapshot: per-thread leaf frame plus
    a bounded stack of ``func (file:line)`` strings, innermost first."""
    out: Dict[int, Dict] = {}
    for tid, frame in sys._current_frames().items():
        if tid == skip_ident:
            continue  # never profile the sampler itself
        stack: List[str] = []
        f = frame
        while f is not None and len(stack) < max_depth:
            code = f.f_code
            stack.append(f"{code.co_name} "
                         f"({os.path.basename(code.co_filename)}:"
                         f"{f.f_lineno})")
            f = f.f_back
        if stack:
            out[tid] = {"leaf": stack[0], "stack": stack}
    return out


def _run_sampler(duration_s: float, hz: float,
                 samples: List[Dict[str, Any]]) -> None:
    period = 1.0 / max(1.0, hz)
    ident = threading.get_ident()
    deadline = time.monotonic() + max(0.0, duration_s)
    names = _thread_names()
    refreshed = time.monotonic()
    while time.monotonic() < deadline:
        t0 = time.monotonic()
        threads = _sample_once(ident)
        now_wall = time.time()
        if t0 - refreshed > 0.5:  # new threads appear mid-capture
            names = _thread_names()
            refreshed = t0
        samples.append({
            "t": now_wall,
            "threads": {tid: dict(rec, name=names.get(tid, f"t{tid}"))
                        for tid, rec in threads.items()},
        })
        sleep = period - (time.monotonic() - t0)
        if sleep > 0:
            time.sleep(sleep)


def _jax_profile_window(duration_s: float) -> Dict[str, Any]:
    """Bracket ``duration_s`` with jax.profiler and collect the artifact
    files.  Only runs when jax is ALREADY imported in this process — a
    profile capture must never be the thing that pulls jax into a worker
    that wasn't using it."""
    info: Dict[str, Any] = {"attempted": False, "files": {}, "error": None}
    if "jax" not in sys.modules:
        info["error"] = "jax not imported in this process"
        return info
    import shutil
    import tempfile

    import jax
    tmpdir = tempfile.mkdtemp(prefix="ray_tpu_jaxprof_")
    info["attempted"] = True
    try:
        jax.profiler.start_trace(tmpdir)
        time.sleep(max(0.0, duration_s))
        jax.profiler.stop_trace()
        total = 0
        for root, _dirs, files in os.walk(tmpdir):
            for fname in files:
                full = os.path.join(root, fname)
                rel = os.path.relpath(full, tmpdir)
                size = os.path.getsize(full)
                if total + size > MAX_JAX_ARTIFACT_BYTES:
                    info["error"] = (f"artifacts exceed "
                                     f"{MAX_JAX_ARTIFACT_BYTES}B cap; "
                                     f"truncated")
                    break
                with open(full, "rb") as f:
                    info["files"][rel] = f.read()
                total += size
    except Exception as e:  # noqa: BLE001 — capture is best-effort
        info["error"] = f"{type(e).__name__}: {e}"
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return info


#: What a record of ``device_memory_stats`` holds of a device's
#: ``memory_stats()``: None where the backend does not give the key.
#: ``bytes_reserved`` is what the runtime keeps for the programs'
#: temporaries, outside ``bytes_in_use``.
MEMORY_KEYS = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
               "peak_bytes_reserved", "largest_free_block_bytes",
               "num_allocs", "bytes_limit")


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per-device memory stats from jax (empty when jax isn't loaded or
    the backend doesn't report them — CPU usually doesn't).  The one
    place that reads them: the profile capture, the HBM gauges and the
    ``worker_sample`` span all take their numbers from here."""
    if "jax" not in sys.modules:
        return []
    out: List[Dict[str, Any]] = []
    try:
        import jax
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            out.append({"device": str(d),
                        **{k: stats.get(k) for k in MEMORY_KEYS}})
    except Exception:  # noqa: BLE001 — stats are garnish
        return out
    return out


def capture_profile(worker_id: str, duration_s: float,
                    hz: float = 67.0, jax_profile: bool = False,
                    driver_wall_s: Optional[float] = None,
                    is_driver: bool = False) -> Dict[str, Any]:
    """Profile THIS process for ``duration_s``; returns the capture
    record shipped to the driver (see merge.py for the shape consumed).
    Blocks for the duration — callers run it off the receive thread."""
    recv_wall = time.time()
    # Wall-minus-wall on purpose: this measures the CLOCK OFFSET between
    # two hosts (monotonic clocks have unrelated bases across processes).
    offset = 0.0
    if driver_wall_s:
        offset = recv_wall - driver_wall_s  # ray-tpu: noqa[RT203]
    if not _active_lock.acquire(blocking=False):
        return {"worker_id": worker_id, "pid": os.getpid(),
                "is_driver": is_driver, "error": "capture already running",
                "clock_offset_s": offset, "samples": []}
    try:
        samples: List[Dict[str, Any]] = []
        if jax_profile:
            # The jax window sleeps for the duration, so the host sampler
            # runs on its own thread alongside it.
            box: Dict[str, Any] = {}

            def sample():
                _run_sampler(duration_s, hz, samples)
            from ray_tpu._private import sanitizer
            t = sanitizer.spawn(sample, name="profile-sampler")
            box["jax"] = _jax_profile_window(duration_s)
            t.join(timeout=duration_s + 5.0)
            jax_info = box["jax"]
        else:
            _run_sampler(duration_s, hz, samples)
            jax_info = {"attempted": False, "files": {}, "error": None}
        return {
            "worker_id": worker_id,
            "pid": os.getpid(),
            "is_driver": is_driver,
            "clock_offset_s": offset,
            "duration_s": duration_s,
            "hz": hz,
            "samples": samples,
            "jax_profile": jax_info,
            "memory": device_memory_stats(),
            "error": None,
        }
    finally:
        _active_lock.release()
