"""Always-on step attribution: decompose a training step into phases.

``ray_tpu.train.step_phase`` (re-exported from here) marks what each
slice of a step's wall time actually was — waiting on the input
pipeline, host→device transfer, dispatched compute, collectives — by
fencing with ``jax.block_until_ready`` at phase boundaries so XLA's
async dispatch cannot smear one phase's device work into the next::

    with train.step_phase("data_wait"):
        batch = next(it)
    with train.step_phase("h2d"):
        batch = train.fence(place(batch))
    with train.step_phase("compute"):
        state, loss = train.fence(step_fn(state, batch))
    train.report({"loss": float(loss)})

``report()`` pops the accumulated phases, publishes per-phase
``ray_tpu_train_step_phase_seconds{phase}`` observations (rank 0), adds
a derived ``other`` phase for the unattributed remainder, and ships the
dict to the controller — which feeds the goodput tracker's data-wait
idle attribution and the ``Result.step_phases`` summary.

Canonical phase names (free-form strings are accepted but keep tag
cardinality in mind): ``data_wait``, ``h2d``, ``compute``,
``collective``; ``ckpt_block`` and ``other`` are added automatically.
"""

from __future__ import annotations

import gc
import resource
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

DERIVED_PHASES = ("ckpt_block", "other")

_tls = threading.local()


def _phases() -> Dict[str, float]:
    acc = getattr(_tls, "phases", None)
    if acc is None:
        acc = _tls.phases = {}
    return acc


def fence(value: Any) -> Any:
    """Block until every array in ``value`` is computed, then return it
    unchanged — the phase boundary.  A no-op when jax isn't loaded (the
    attribution API stays importable in array-free train fns)."""
    if "jax" in sys.modules:
        try:
            import jax
            jax.block_until_ready(value)
        except Exception:  # noqa: BLE001 — non-array pytrees etc.
            pass
    return value


class step_phase:
    """Context manager charging its wall time to one named phase of the
    current step.  Re-entrant (per-entry state is a stack) and nestable:
    nested time is charged to the INNER phase only, so phases sum to at
    most the step time instead of double counting.

    ``fence_result=x`` (or calling :meth:`fence` inside the block)
    blocks on ``x`` before the phase closes, so asynchronously
    dispatched device work lands inside the phase that launched it.
    """

    __slots__ = ("name", "_fence_result", "_stack")

    def __init__(self, name: str, fence_result: Any = None):
        self.name = name
        self._fence_result = fence_result
        self._stack: list = []

    def fence(self, value: Any) -> Any:
        """Fence inline and return ``value`` (sugar for assignments)."""
        return fence(value)

    def __enter__(self) -> "step_phase":
        self._stack.append({"t0": time.monotonic(), "child_s": 0.0,
                            "parent": getattr(_tls, "open_phase", None)})
        _tls.open_phase = self._stack[-1]
        return self

    def __exit__(self, *exc) -> bool:
        if self._fence_result is not None:
            fence(self._fence_result)
        entry = self._stack.pop()
        dur = max(0.0, time.monotonic() - entry["t0"])
        _tls.open_phase = entry["parent"]
        if entry["parent"] is not None:
            entry["parent"]["child_s"] += dur
        mine = max(0.0, dur - entry["child_s"])
        acc = _phases()
        acc[self.name] = acc.get(self.name, 0.0) + mine
        return False


def pop_phases() -> Dict[str, float]:
    """Return and clear this thread's accumulated phase seconds (called
    by ``train.report`` once per step)."""
    acc = _phases()
    out = dict(acc)
    acc.clear()
    return out


def finalize_step_phases(phases: Dict[str, float], step_s: Optional[float],
                         ckpt_s: float = 0.0) -> Dict[str, float]:
    """Fold checkpoint-blocking time in and derive ``other`` — the slice
    of the step no phase claimed.  ``step_s`` None (first report: no
    prior report to difference against) skips the derivation."""
    out = {k: v for k, v in phases.items() if v > 0.0}
    if ckpt_s > 0.0:
        out["ckpt_block"] = out.get("ckpt_block", 0.0) + ckpt_s
    if step_s is not None and step_s > 0.0:
        attributed = sum(out.values())
        out["other"] = max(0.0, step_s - attributed)
    return out


_last_hbm_mono = 0.0
_hbm_lock = threading.Lock()


def set_hbm_gauges(records: List[Dict[str, Any]]) -> None:
    """The per-device HBM used/peak gauges from ``device_memory_stats``
    records."""
    from ..util import telemetry
    for rec in records:
        tags = {"device": rec["device"]}
        if rec.get("bytes_in_use") is not None:
            telemetry.set_gauge("ray_tpu_train_hbm_used_bytes",
                                float(rec["bytes_in_use"]), tags=tags)
        if rec.get("peak_bytes_in_use") is not None:
            telemetry.set_gauge("ray_tpu_train_hbm_peak_bytes",
                                float(rec["peak_bytes_in_use"]), tags=tags)


def note_hbm_gauges(min_interval_s: float = 1.0) -> None:
    """Refresh the per-device HBM used/peak gauges from jax memory
    stats.  Rate-limited so sub-second report loops don't pay a device
    query per step; silently absent on backends without memory_stats
    (CPU).  Between reports ``worker_sample`` keeps them live."""
    global _last_hbm_mono
    now = time.monotonic()
    with _hbm_lock:
        if now - _last_hbm_mono < min_interval_s:
            return
        _last_hbm_mono = now
    from .capture import device_memory_stats
    set_hbm_gauges(device_memory_stats())


# -- what a process that holds chips does beside its step loop ------------
#
# A step that takes twice its usual time names no cause by itself.  These
# put the candidates on the recorder's clock, in every run: Python's
# collector (``py_gc``) and, once a metrics-flusher tick, the state no
# span has (``worker_sample``).  ``telemetry.stalls`` reads them beside
# the step's own cadence.

#: A collection shorter than this leaves no span.
GC_SPAN_FLOOR_S = 1e-3


class GcSpans:
    """A ``gc.callbacks`` entry: a collection of ``GC_SPAN_FLOOR_S`` or
    more is a ``py_gc{generation, collected}`` span; a shorter one (every
    generation-0 pass of a quiet process) costs its two clock reads.
    Where jax is loaded the collection is also a ``TraceAnnotation``, so
    a profiler session has it on the host plane whatever its length.
    The interpreter runs one collection at a time: one slot is enough."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._open: Optional[tuple] = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            note = None
            jax = sys.modules.get("jax")
            if jax is not None:
                try:
                    note = jax.profiler.TraceAnnotation(
                        "py_gc", generation=info.get("generation"))
                    note.__enter__()
                except Exception:  # noqa: BLE001 — a half-imported jax
                    note = None
            self._open = (self._clock(), note)
            return
        if self._open is None:
            return                  # registered in the middle of a pass
        (t0, note), self._open = self._open, None
        dur = self._clock() - t0
        if note is not None:
            note.__exit__(None, None, None)
        if dur < GC_SPAN_FLOOR_S:
            return
        from ..util import telemetry
        # Wall clock on purpose: it anchors the span among the others.
        end = time.time()
        telemetry._emit_span(
            "py_gc", "system", end - dur, end,  # ray-tpu: noqa[RT203]
            extra={"generation": info.get("generation"),
                   "collected": info.get("collected")})


def _pressure(root: str) -> Dict[str, float]:
    """``some avg10`` of ``<root>/cpu``, ``memory`` and ``io``: the
    percentage of the last ten seconds in which some task of this machine
    waited for the resource.  A kernel without the files gives nothing."""
    out: Dict[str, float] = {}
    for what in ("cpu", "memory", "io"):
        try:
            with open(f"{root}/{what}") as f:
                some = f.readline()
            out[f"pressure_{what}"] = float(
                some.split("avg10=")[1].split()[0])
        except (OSError, IndexError, ValueError):
            continue
    return out


def worker_sample(pressure_root: str = "/proc/pressure") -> Dict[str, Any]:
    """One ``worker_sample`` span, its length the sample's own cost, its
    ``extra`` (returned) the state of this process and its chips: CPU
    seconds (user, system), voluntary and involuntary context switches
    and major faults, all since the process started, so that two samples'
    difference is what was spent between them; the machine's pressure;
    and the memory readings of the fullest local device, whichever the
    backend gives (none on a CPU).  Sets the HBM gauges of every device
    on the way."""
    from ..util import telemetry
    from .capture import device_memory_stats
    extra: Dict[str, Any] = {}
    with telemetry.profile_span("worker_sample", "system", extra):
        used = resource.getrusage(resource.RUSAGE_SELF)
        extra.update(cpu_user_s=used.ru_utime, cpu_system_s=used.ru_stime,
                     switches_voluntary=used.ru_nvcsw,
                     switches_involuntary=used.ru_nivcsw,
                     major_faults=used.ru_majflt)
        extra.update(_pressure(pressure_root))
        records = device_memory_stats()
        set_hbm_gauges(records)
        if records:
            fullest = max(records, key=lambda r: (r["bytes_in_use"] or 0)
                          + (r["bytes_reserved"] or 0))
            extra.update({k: v for k, v in fullest.items()
                          if k != "device" and v is not None})
    return extra


def _watching() -> bool:
    return any(isinstance(c, GcSpans) for c in gc.callbacks)


def watch_process() -> None:
    """``accelerators.tpu.init_backend`` calls this in a process that
    holds chips: from here on its collections are ``py_gc`` spans and its
    metrics flusher (started here if nothing has yet) takes a
    ``worker_sample`` a tick.  Once a process."""
    if _watching():
        return
    gc.callbacks.append(GcSpans())
    from ..util import metrics
    metrics.note_pending()


def sample_if_watching() -> None:
    """The metrics flusher's call, every tick, in every process."""
    if _watching():
        worker_sample()


def _reset_for_tests() -> None:
    global _last_hbm_mono
    _phases().clear()
    _tls.open_phase = None
    _last_hbm_mono = 0.0
    gc.callbacks[:] = [c for c in gc.callbacks if not isinstance(c, GcSpans)]
