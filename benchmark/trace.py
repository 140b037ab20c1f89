"""From a profiler trace (``*.xplane.pb``) to the numbers the readers use.

One reduction, kept with the benchmark so that every PR computes the same
thing the same way.  A device plane (``/device:TPU:n``) has a line of
program runs (``XLA Modules``) and a line of the operations inside them
(``XLA Ops``); the host plane has one line per thread.  ``reduce`` returns
plain data (lists and dicts), so tests can build a trace by hand.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

Interval = Tuple[float, float]          # start, end in seconds

_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.I)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    return files[-1]


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off: it slows a host loop
    of many small calls several times over, and the host's own TraceMe
    events (``PjitFunction``, ``np.asarray``) are what the gaps need."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def load(path: str) -> Dict[str, Any]:
    """Read an xplane file into {"devices": {name: {"modules", "ops"}},
    "host": [(thread, name, start, end)]}; times in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            rec = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    rec[key].append((ev.name, start,
                                     start + ev.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    host.append((line.name, ev.name, start,
                                 start + ev.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def union_seconds(intervals: Iterable[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def label(name: str) -> str:
    """What an operation is summed under: its name without numbering and,
    for a Mosaic kernel, the types of its results, which is all that tells
    one unnamed kernel from another (``closed_call<bf16,f32>`` is flash
    attention's forward, with its log-sum-exp)."""
    short = short_op(name)
    if 'custom_call_target="tpu_custom_call"' not in name:
        return short
    shape = name.partition(" = ")[2].partition(" custom-call(")[0]
    return f"{short}<{','.join(re.findall(r'([a-z]+[0-9]+)\[', shape))}>"


def short_op(name: str) -> str:
    """An operation's name without its numbering: ``fusion.123`` ->
    ``fusion``; ``%all-gather-start.4 = ...`` -> ``all-gather-start``."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"(\.clone\d*|[.\d])+$", "", name) or name


def opcode(name: str) -> str:
    """The HLO opcode of an operation whose event name is its instruction
    text, ``%x.1 = f32[8]{0} fusion(...)`` -> ``fusion`` ("" where the name
    is not an instruction)."""
    _, sep, rest = name.partition(" = ")
    if not sep:
        return ""
    if rest.startswith("("):                 # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    m = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1) if m else ""


#: operations that only hold others (their bodies' operations have events
#: of their own): counted in no sum, though they do mark the device busy
CONTAINERS = {"while", "conditional", "call"}


def exposed_seconds(ops: List[Tuple[str, float, float]]) -> Tuple[float, float]:
    """(seconds in which a collective ran, seconds of those in which no
    other operation ran) on one device."""
    ops = [(short_op(n), a, b) for n, a, b in ops
           if opcode(n) not in CONTAINERS]
    coll = [(a, b) for n, a, b in ops if _COLLECTIVE.search(n)]
    rest = [(a, b) for n, a, b in ops if not _COLLECTIVE.search(n)]
    busy_coll = union_seconds(coll)
    both = union_seconds(coll + rest)
    return busy_coll, both - union_seconds(rest)


def idle_attribution(idle: List[Interval],
                     host: List[Tuple[str, str, float, float]],
                     floor_s: float = 1e-3) -> Dict[str, float]:
    """Seconds of device idleness by what the host was doing: each gap of
    ``floor_s`` or more goes to the host event that covers most of it (the
    shortest such event where several cover it alike)."""
    out: Dict[str, float] = {}
    small = sum(b - a for a, b in idle if b - a < floor_s)
    if small:
        out["gaps_under_1_ms"] = small
    events = sorted(host, key=lambda e: e[2])
    for gap in idle:
        if gap[1] - gap[0] < floor_s:
            continue
        best, best_key = "host_idle", (0.0, 0.0)
        for _thread, name, a, b in events:
            if a >= gap[1]:
                break
            ov = _overlap(gap, (a, b))
            key = (round(ov, 6), -(b - a))
            if ov > 0 and key > best_key:
                best, best_key = name, key
        out[best] = out.get(best, 0.0) + gap[1] - gap[0]
    return out


def reduce(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """The facts every reader shares.  Per device the window is the span
    from the first to the last operation seen on any device; busy is the
    union of operation intervals; everything is averaged over devices."""
    devices = trace["devices"]
    if not devices or not any(d["ops"] for d in devices.values()):
        return {"busy_s": 0.0, "window_s": 0.0}
    lo = min(a for d in devices.values() for _n, a, _b in d["ops"])
    hi = max(b for d in devices.values() for _n, _a, b in d["ops"])
    n = len(devices)
    busy = coll = exposed = 0.0
    op_time: Dict[str, float] = {}
    op_count: Dict[str, int] = {}
    module_runs: Dict[str, List[Interval]] = {}
    for d in devices.values():
        busy += union_seconds((a, b) for _n, a, b in d["ops"])
        c, e = exposed_seconds(d["ops"])
        coll, exposed = coll + c, exposed + e
    first = devices[sorted(devices)[0]]
    for name, a, b in first["modules"]:
        module_runs.setdefault(name, []).append((a, b))
    # Operations by program and name, on the first device.
    runs = sorted((a, b, nm) for nm, a, b in first["modules"])
    starts = [r[0] for r in runs]
    for name, a, b in first["ops"]:
        if opcode(name) in CONTAINERS:
            continue
        i = bisect.bisect_right(starts, a) - 1
        mod = runs[i][2] if i >= 0 and a < runs[i][1] else ""
        key = f"{mod}/{label(name)}"
        op_time[key] = op_time.get(key, 0.0) + (b - a)
        op_count[key] = op_count.get(key, 0) + 1
    idle = gaps(((a, b) for _n, a, b in first["ops"]), lo, hi)
    attribution = idle_attribution(idle, trace["host"])
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": busy / n, "window_s": hi - lo, "devices": n,
        "collective_s": coll / n, "collective_exposed_s": exposed / n,
        "op_seconds": op_time, "op_counts": op_count,
        "module_runs": {k: sorted(v) for k, v in module_runs.items()},
        "breakdown": {"device_ops": rank(op_time),
                      "idle_gaps": rank(attribution)},
    }


def ops_matching(reduced: Dict[str, Any], pattern: str
                 ) -> Tuple[float, int]:
    """(seconds, calls) of the operations whose ``program/name`` matches."""
    rx = re.compile(pattern)
    keys = [k for k in reduced.get("op_seconds", {}) if rx.search(k)]
    return (sum(reduced["op_seconds"][k] for k in keys),
            sum(reduced["op_counts"][k] for k in keys))


def modules_with_op(reduced: Dict[str, Any], pattern: str) -> List[str]:
    """Programs in which an operation matching ``pattern`` ran."""
    rx = re.compile(pattern)
    return sorted({k.split("/", 1)[0] for k in reduced.get("op_seconds", {})
                   if rx.search(k.split("/", 1)[1])})


def start_to_start(runs: List[Interval]) -> List[float]:
    starts = [a for a, _b in sorted(runs)]
    return [b - a for a, b in zip(starts, starts[1:])]
