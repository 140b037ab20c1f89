"""Runtime resource-leak sanitizer (``RAY_TPU_SANITIZE=1``).

The static RT3xx rules prove per-function release discipline; this is
the runtime twin — the ASan/LSan of the control plane.  When enabled
(env var at ``import ray_tpu`` time, or :func:`install` directly) it
keeps lightweight registries of the resources whose leaks erode
long-run goodput:

* **framework threads** — ``threading.Thread.start`` is patched to
  record a creation-site stack for every thread started *from* the
  ``ray_tpu`` tree (test/user threads are ignored); the
  :func:`spawn` helper is the sanctioned fire-and-forget spawn path
  (RT301 recognizes it as tracked registration),
* **pinned objects** — ``ctl_pin_object`` / ``ctl_unpin_object`` report
  here, so an unpaired emergency-replica pin is visible,
* **tracked file handles** — debug-bundle / checkpoint writers open
  through :func:`tracked_open`,
* **named actors** — registration reports name + creation site;
  session-lifetime-by-design names (serve controller, checkpoint
  replica holders) are declared with :func:`session_scoped`.

:func:`snapshot` (called by ``init_runtime``) records the baseline;
``ray_tpu.shutdown()`` calls :func:`pre_shutdown` (named actors must be
inspected before teardown marks everything DEAD) and, after the runtime
is down, :func:`check_after_shutdown` — a nonzero diff raises
:class:`LeakError` listing every leaked resource with its creation-site
summary.  ``tests/conftest.py`` turns the sanitizer on for the whole
tier-1 suite, so every existing test doubles as a leak test.  Reports
also land in flight-recorder debug bundles as ``leak_findings.json``.

Scope: the check runs in the *driver* process (worker-process threads
die with their process).  Overhead when disabled is zero — nothing is
patched; when enabled it is one dict write per tracked event.
"""

from __future__ import annotations

import fnmatch
import os
import threading
import time
import traceback
import weakref
from typing import Any, Dict, List, Optional

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Frames kept per creation-site summary.
_STACK_DEPTH = 5

#: Post-shutdown grace for framework threads to wind down before a
#: still-alive one counts as leaked.
DEFAULT_GRACE_S = 4.0


class LeakError(RuntimeError):
    """Raised at shutdown when the sanitizer's diff is nonzero."""


class _State:
    def __init__(self) -> None:
        self.mu = threading.Lock()
        self.installed = False
        # thread -> {"name", "site", "stack", "tracked"} (weak keys: a
        # dead, collected thread can never be reported).
        self.threads: "weakref.WeakKeyDictionary[threading.Thread, dict]" \
            = weakref.WeakKeyDictionary()
        self.pins: Dict[str, dict] = {}          # oid hex -> info
        self.files: Dict[int, dict] = {}         # id(wrapper) -> info
        self.named_actors: Dict[str, dict] = {}  # "ns/name" -> info
        self.session_patterns: List[str] = []
        self.thread_allow: List[str] = []
        self.baseline_threads: set = set()       # Thread idents
        self.baseline_pins: set = set()
        self.baseline_files: set = set()
        self.baseline_named: set = set()


_state = _State()
_real_thread_start = threading.Thread.start


_SELF_FILE = os.path.abspath(__file__)

#: Frames walked looking for the creation site.  A bounded
#: ``sys._getframe`` walk, NOT ``traceback.extract_stack()`` — the full
#: extract (deep pytest stacks + linecache source reads) costs ~100µs
#: per call, which multiplied by every framework thread start blew the
#: sanitizer's 2% budget on the core task/actor loop.
_WALK_DEPTH = 14


def _site_and_stack(skip_self: bool = True):
    """(innermost ray_tpu frame "file:line", short outer->inner stack)
    — or ``(None, stack)`` when no frame is inside the package (not
    framework-created)."""
    import sys
    frames: List[str] = []
    site = None
    try:
        f = sys._getframe(2 if skip_self else 1)
    except ValueError:
        f = None
    depth = 0
    while f is not None and depth < _WALK_DEPTH:
        fn = f.f_code.co_filename
        frames.append(f"{os.path.basename(fn)}:{f.f_lineno} "
                      f"in {f.f_code.co_name}")
        if site is None and fn.startswith(_PKG_DIR) and fn != _SELF_FILE:
            site = f"{os.path.relpath(fn, os.path.dirname(_PKG_DIR))}" \
                   f":{f.f_lineno}"
        f = f.f_back
        depth += 1
    frames.reverse()
    return site, frames[-_STACK_DEPTH:]


# -- install ---------------------------------------------------------------


def _recording_start(self: threading.Thread) -> None:
    if _state.installed and self not in _state.threads:
        # Threads registered by spawn() keep their entry (and its
        # tracked=True flag) — this path only records direct
        # Thread.start() calls made from framework code.
        site, stack = _site_and_stack()
        if site is not None:
            with _state.mu:
                _state.threads[self] = {
                    "name": self.name, "site": site, "stack": stack,
                    "tracked": False, "time": time.time()}
    _real_thread_start(self)


def install() -> None:
    """Patch ``threading.Thread.start`` to record framework creation
    sites.  Idempotent; :func:`uninstall` restores the original."""
    with _state.mu:
        if _state.installed:
            return
        _state.installed = True
    threading.Thread.start = _recording_start  # type: ignore[assignment]


def uninstall() -> None:
    with _state.mu:
        if not _state.installed:
            return
        _state.installed = False
    threading.Thread.start = _real_thread_start  # type: ignore[assignment]


def is_enabled() -> bool:
    return _state.installed


# -- spawn helper ----------------------------------------------------------


def spawn(target, *, name: Optional[str] = None, args: tuple = (),
          kwargs: Optional[dict] = None,
          daemon: bool = True) -> threading.Thread:
    """Create, register and start a framework background thread — THE
    sanctioned fire-and-forget spawn (RT301 counts it as registration
    in a tracked set; a bare ``threading.Thread(...).start()`` with no
    reachable join is a lint finding)."""
    t = threading.Thread(target=target, name=name, args=args,
                         kwargs=kwargs or {}, daemon=daemon)
    if _state.installed:
        site, stack = _site_and_stack()
        with _state.mu:
            _state.threads[t] = {"name": t.name, "site": site or "<app>",
                                 "stack": stack, "tracked": True,
                                 "time": time.time()}
    t.start()
    return t


def allow_thread(name_prefix: str) -> None:
    """Declare a thread-name prefix that may legitimately outlive
    ``shutdown()`` (use sparingly; prefer joining at teardown)."""
    with _state.mu:
        if name_prefix not in _state.thread_allow:
            _state.thread_allow.append(name_prefix)


# -- pins ------------------------------------------------------------------


def note_pin(oid_hex: str) -> None:
    if not _state.installed:
        return
    site, stack = _site_and_stack()
    with _state.mu:
        info = _state.pins.setdefault(
            oid_hex, {"count": 0, "site": site or "<rpc>",
                      "stack": stack, "time": time.time()})
        info["count"] += 1


def note_unpin(oid_hex: str) -> None:
    if not _state.installed:
        return
    with _state.mu:
        info = _state.pins.get(oid_hex)
        if info is None:
            return
        info["count"] -= 1
        if info["count"] <= 0:
            del _state.pins[oid_hex]


# -- tracked files ---------------------------------------------------------


class TrackedFile:
    """Thin wrapper whose ``close`` unregisters; returned by
    :func:`tracked_open`."""

    def __init__(self, f, info: dict):
        self._f = f
        self._info = info

    def __getattr__(self, name: str) -> Any:
        return getattr(self._f, name)

    def __enter__(self) -> "TrackedFile":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def __iter__(self):
        return iter(self._f)

    def close(self) -> None:
        with _state.mu:
            _state.files.pop(id(self), None)
        self._f.close()


def tracked_open(path: str, mode: str = "r", **kw):
    """``open()`` that registers the handle while the sanitizer is on
    (debug-bundle/checkpoint writers use this, so a handle that never
    closes shows up in the shutdown diff with its opening site)."""
    f = open(path, mode, **kw)
    if not _state.installed:
        return f
    site, stack = _site_and_stack()
    tf = TrackedFile(f, {})
    with _state.mu:
        _state.files[id(tf)] = {"path": path, "mode": mode,
                                "site": site or "<app>", "stack": stack,
                                "time": time.time()}
    return tf


# -- named actors ----------------------------------------------------------


def _framework_created() -> Optional[str]:
    """Innermost *subsystem* frame (under ray_tpu/ but outside
    ``_private``/``scripts``) on the current stack, or None.  User code
    creating a named actor goes straight through the ``_private`` API
    machinery; framework subsystems (serve, checkpoint, ...) add their
    own frame."""
    for fr in reversed(traceback.extract_stack()[:-2]):
        fn = os.path.abspath(fr.filename)
        if not fn.startswith(_PKG_DIR):
            continue
        rel = os.path.relpath(fn, _PKG_DIR)
        top = rel.split(os.sep)[0]
        if top not in ("_private", "scripts", "__init__.py"):
            return f"ray_tpu/{rel}:{fr.lineno}"
    return None


def note_named_actor(name: str, namespace: str,
                     class_name: Optional[str] = None) -> None:
    """Record a *framework-created* named actor.  User-created named
    actors are their owner's business — cluster shutdown reaps them by
    design; only subsystem-owned ones must be cleaned up (or declared
    :func:`session_scoped`) and count as leaks."""
    if not _state.installed or not name:
        return
    fw_site = _framework_created()
    if fw_site is None:
        return
    _, stack = _site_and_stack()
    with _state.mu:
        _state.named_actors[f"{namespace}/{name}"] = {
            "name": name, "namespace": namespace,
            "class_name": class_name, "site": fw_site,
            "stack": stack, "time": time.time()}


def session_scoped(name: str) -> None:
    """Declare a named actor as session-lifetime by design (fnmatch
    pattern): it will not be reported at shutdown."""
    with _state.mu:
        if name not in _state.session_patterns:
            _state.session_patterns.append(name)


# -- snapshot / check ------------------------------------------------------


def snapshot(rt: Any = None) -> None:
    """Record the baseline at cluster start: resources alive NOW belong
    to the environment (or to a previous, already-reported cluster) and
    are never re-reported."""
    if not _state.installed:
        return
    with _state.mu:
        _state.baseline_threads = {
            t.ident for t in threading.enumerate() if t.ident is not None}
        _state.baseline_pins = set(_state.pins)
        _state.baseline_files = set(_state.files)
        _state.baseline_named = set(_state.named_actors)


def _live_named(rt: Any) -> List[dict]:
    """Framework-created named actors still alive in ``rt``, minus
    session-scoped and baseline names — must run BEFORE teardown marks
    actors DEAD."""
    out: List[dict] = []
    with _state.mu:
        recorded = {k: dict(v) for k, v in _state.named_actors.items()}
        baseline = set(_state.baseline_named)
        patterns = list(_state.session_patterns)
    for key, rec in recorded.items():
        if key in baseline:
            continue
        name, ns = rec["name"], rec["namespace"]
        if any(fnmatch.fnmatch(name, pat) for pat in patterns):
            continue
        try:
            info = rt.controller.get_named_actor(name, ns)
        except Exception:
            continue
        if info is None or getattr(info, "state", "DEAD") == "DEAD":
            continue
        rec["kind"] = "named_actor"
        out.append(rec)
    return out


def pre_shutdown(rt: Any, grace_s: float = 2.0) -> List[dict]:
    """First half of the shutdown gate (returns pending named-actor
    leaks; pass to :func:`check_after_shutdown`).  ``kill()`` is
    asynchronous — an actor its subsystem reaped moments ago may not
    have landed DEAD yet, so leaks get a short settle window."""
    if not _state.installed:
        return []
    leaks = _live_named(rt)
    deadline = time.monotonic() + grace_s
    while leaks and time.monotonic() < deadline:
        time.sleep(0.05)
        leaks = _live_named(rt)
    return leaks


def _leaked_now() -> List[dict]:
    out: List[dict] = []
    with _state.mu:
        for t, info in list(_state.threads.items()):
            if not t.is_alive() or t.ident in _state.baseline_threads:
                continue
            if any(t.name.startswith(p) for p in _state.thread_allow):
                continue
            rec = dict(info)
            rec["kind"] = "thread"
            rec["alive_thread"] = t
            out.append(rec)
        for oid, info in _state.pins.items():
            if oid in _state.baseline_pins:
                continue
            rec = dict(info)
            rec["kind"] = "pin"
            rec["object_id"] = oid
            out.append(rec)
        for fid, info in _state.files.items():
            if fid in _state.baseline_files:
                continue
            rec = dict(info)
            rec["kind"] = "file"
            out.append(rec)
    return out


def check_after_shutdown(pre: Optional[List[dict]] = None,
                         grace_s: Optional[float] = None) -> None:
    """Second half of the shutdown gate: wait up to ``grace_s`` (module
    default: :data:`DEFAULT_GRACE_S`) for framework threads to wind
    down, then raise :class:`LeakError` on any nonzero diff."""
    if not _state.installed:
        return
    if grace_s is None:
        grace_s = DEFAULT_GRACE_S
    pre = pre or []
    deadline = time.monotonic() + grace_s
    leaks = _leaked_now()
    # Only threads can resolve themselves (by exiting); wait the grace
    # out for them, not for pins/files that cannot un-leak.
    while any(rec["kind"] == "thread" for rec in leaks) and \
            time.monotonic() < deadline:
        time.sleep(0.05)
        leaks = _leaked_now()
    leaks = pre + leaks
    for rec in leaks:
        rec.pop("alive_thread", None)
    if leaks:
        raise LeakError(format_report(leaks))


def format_report(leaks: List[dict]) -> str:
    lines = [f"resource leak sanitizer: {len(leaks)} leaked resource(s) "
             f"at shutdown (RAY_TPU_SANITIZE=1)"]
    for rec in leaks:
        kind = rec.get("kind")
        if kind == "thread":
            head = f"[thread] {rec.get('name')} created at " \
                   f"{rec.get('site')}"
        elif kind == "pin":
            head = f"[pin] object {rec.get('object_id', '')[:16]} pinned " \
                   f"at {rec.get('site')}"
        elif kind == "file":
            head = f"[file] {rec.get('path')} ({rec.get('mode')}) opened " \
                   f"at {rec.get('site')}"
        else:
            head = f"[named_actor] {rec.get('namespace')}/" \
                   f"{rec.get('name')} ({rec.get('class_name')}) " \
                   f"created at {rec.get('site')}"
        lines.append("  " + head)
        for fr in rec.get("stack", [])[-_STACK_DEPTH:]:
            lines.append("      " + fr)
    lines.append("  (declare intentional session-lifetime resources via "
                 "_private.sanitizer.session_scoped/allow_thread, or fix "
                 "the missing release)")
    return "\n".join(lines)


def report() -> Dict[str, Any]:
    """Snapshot for the flight recorder's ``leak_findings.json``: every
    currently-tracked live resource with its creation site."""
    with _state.mu:
        threads = [
            {"name": t.name, "site": info.get("site"),
             "tracked": info.get("tracked"), "stack": info.get("stack")}
            for t, info in list(_state.threads.items()) if t.is_alive()]
        return {
            "enabled": _state.installed,
            "pid": os.getpid(),
            "threads": threads,
            "pins": [{"object_id": oid, "count": i.get("count"),
                      "site": i.get("site")}
                     for oid, i in _state.pins.items()],
            "files": [{"path": i.get("path"), "site": i.get("site")}
                      for i in _state.files.values()],
            "named_actors": [
                {"name": i.get("name"), "namespace": i.get("namespace"),
                 "class_name": i.get("class_name"), "site": i.get("site")}
                for i in _state.named_actors.values()],
            "session_scoped": list(_state.session_patterns),
        }


def _reset_for_tests() -> None:
    """Drop registries and baseline (test isolation; does not change
    installed state)."""
    with _state.mu:
        _state.threads = weakref.WeakKeyDictionary()
        _state.pins.clear()
        _state.files.clear()
        _state.named_actors.clear()
        _state.baseline_threads = set()
        _state.baseline_pins = set()
        _state.baseline_files = set()
        _state.baseline_named = set()
