"""Recompile detector: per-site XLA compile accounting + shape-churn
warnings.

Shape churn — a batch dimension that wobbles, a dtype that flips — makes
``jax.jit`` silently recompile, and on TPU a recompile is seconds of
stalled devices that shows up as nothing but a mysteriously slow step.
This module hooks ``jax.monitoring``'s compile-duration events and
attributes them to *tracked call sites*:

* :func:`track` wraps a (usually jitted) callable; every XLA backend
  compile that fires while the wrapped call runs is charged to the
  site's telemetry series (``ray_tpu_profiler_compile_total`` /
  ``_seconds{fn}``).
* A site is **warm** once a call completes with no compile (the cache
  hit proves steady state).  A compile AFTER that is a post-warmup
  recompilation: ``ray_tpu_profiler_recompiles_total`` is bumped and a
  once-per-site warning names the argument shapes/dtypes that changed —
  the culprit, not just the symptom.
* :func:`install` turns the detector on and registers the listener (a
  train worker calls it); ``jax.jit`` itself stays jax's.

The same listener names the seconds of a program's way to the device,
tracked site or not: ``jax_trace`` (Python tracing a jitted function),
``jax_lower`` (the jaxpr to an MLIR module, every Pallas call's lowering
in it) and ``xla_compile`` (the backend compile, or the fetch from the
persistent cache that stands for it) are spans of their own.  It does
work only when jax traces, lowers or compiles: nothing on a step's path.

Everything degrades to a no-op when jax (or its monitoring API) is
absent — the module never imports jax on its own.
"""

from __future__ import annotations

import logging
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from ..util import telemetry

logger = logging.getLogger("ray_tpu.profiler")

#: jax.monitoring's events on a program's way to the device, each fired
#: with ``fun_name`` when the stretch ends: a jitted function traced to a
#: jaxpr (nested ones too), the jaxpr lowered to an MLIR module, the module
#: compiled by the backend (or fetched from the persistent cache).
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: What a fetch from the persistent cache records inside that compile, on
#: its thread and before it ends: the hit, the seconds the read took, and
#: the seconds the stored compile had taken less those.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_FETCH_FIELDS = {_RETRIEVAL_EVENT: "retrieval_s", _SAVED_EVENT: "saved_s"}
#: The two stretches before the compile: (span, counter of its seconds).
_STRETCHES = {
    _TRACE_EVENT: ("jax_trace", "ray_tpu_jax_trace_seconds_total"),
    _LOWER_EVENT: ("jax_lower", "ray_tpu_jax_lower_seconds_total")}
#: A compile long enough to be stored, about to be written to the cache.
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"

#: A trace inside another (a nested ``jax.jit``) that took less than this
#: is no span of its own: its seconds are its parent's.
NESTED_TRACE_FLOOR_S = 0.010

_lock = threading.Lock()
_listener_registered = False
_enabled = False

#: site name -> _SiteState
_sites: Dict[str, "_SiteState"] = {}

_tls = threading.local()


class _SiteState:
    __slots__ = ("name", "signatures", "compiles", "compile_s", "warm",
                 "recompiles", "warned", "last_signature",
                 "static_argnums", "static_argnames", "donate_argnums")

    def __init__(self, name: str):
        self.name = name
        self.signatures: List[str] = []
        self.compiles = 0
        self.compile_s = 0.0
        self.warm = False
        self.recompiles = 0
        self.warned = False
        self.last_signature: Optional[str] = None
        self.static_argnums: tuple = ()
        self.static_argnames: tuple = ()
        self.donate_argnums: tuple = ()


def _emit_stretch(span: str, duration_s: float,
                  extra: Dict[str, Any]) -> None:
    """One ``jax_trace`` / ``jax_lower`` / ``xla_compile`` span that ends
    now and began ``duration_s`` ago.  Wall clock on purpose: it anchors
    the span among the others; the length is jax's own measurement."""
    end = time.time()
    telemetry._emit_span(
        span, "compile", end - duration_s, end,  # ray-tpu: noqa[RT203]
        extra={**extra, "seconds": duration_s})


def _on_event_duration(event: str, duration_s: float, **kw) -> None:
    """jax.monitoring listener.  EVERY trace, lowering and backend compile
    (or fetch from the persistent cache: jax times both under the one
    event) becomes a span with the program's name, tracked site or not;
    with the detector on a compile is also charged to whichever tracked
    site is currently executing on this thread."""
    if event in _STRETCHES:
        # ``_on_scalar`` counted this stretch in when it began.
        depth = _tls.open = max(getattr(_tls, "open", 1) - 1, 0)
        if depth and event == _TRACE_EVENT \
                and duration_s < NESTED_TRACE_FLOOR_S:
            return
        span, counter = _STRETCHES[event]
        program = str(kw.get("fun_name") or "unknown")
        if depth:
            _emit_stretch(span, duration_s,
                          {"program": program, "nested": True})
        else:
            # The counters hold top-level programs only: every nested
            # function's name would blow the tag set up.
            _emit_stretch(span, duration_s, {"program": program})
            telemetry.inc(counter, duration_s, tags={"program": program})
        return
    if event in _FETCH_FIELDS:
        _fetched()[_FETCH_FIELDS[event]] = duration_s
        return
    if event != _COMPILE_EVENT:
        return
    program = str(kw.get("fun_name") or "unknown")
    fetched = _tls.__dict__.pop("fetched", {})
    _emit_stretch("xla_compile", duration_s,
                  {"program": program, "cache_hit": False, **fetched})
    telemetry.inc("ray_tpu_xla_compiles_total", tags={"program": program})
    if fetched.get("cache_hit"):
        telemetry.inc("ray_tpu_compile_cache_hits_total")
    if not _enabled:
        return
    frame = getattr(_tls, "site", None)
    if frame is None:
        return
    frame["compiles"] += 1
    frame["compile_s"] += duration_s


def _fetched() -> Dict[str, Any]:
    """What the persistent cache has said of the compile that this thread
    is inside; the compile's own event takes it."""
    found = getattr(_tls, "fetched", None)
    if found is None:
        found = _tls.fetched = {}
    return found


def _on_event(event: str, **_kw) -> None:
    """jax.monitoring listener: a persistent-cache hit, reported (where
    this jax reports it) inside the compile that it saves, on its
    thread; and an entry about to be written."""
    if event == _CACHE_HIT_EVENT:
        _fetched()["cache_hit"] = True
    elif event == _CACHE_WRITE_EVENT:
        telemetry.inc("ray_tpu_compile_cache_writes_total")


def _on_scalar(event: str, _value: float, **_kw) -> None:
    """jax.monitoring listener: a trace or a lowering begins (jax records
    the start of each timed stretch as a scalar).  Counted a thread, so
    that a trace that ends while another stretch is open (a nested
    ``jax.jit``; a jitted helper that a lowering rule calls) is known as
    nested."""
    if event in _STRETCHES:
        _tls.open = getattr(_tls, "open", 0) + 1


def ensure_listener() -> bool:
    """Register the ``jax.monitoring`` listeners once, if jax is loaded
    (never imports it).  ``accelerators.tpu.init_backend`` calls this for
    every process that runs jax on a main path, so ``xla_compile`` spans
    do not depend on the detector being installed."""
    global _listener_registered
    if _listener_registered:
        return True
    if "jax" not in sys.modules:
        return False
    try:
        import jax
        register = getattr(jax.monitoring,
                           "register_event_duration_secs_listener", None)
        if register is None:
            return False
        with _lock:
            if not _listener_registered:
                register(_on_event_duration)
                for name, listener in (
                        ("register_event_listener", _on_event),
                        ("register_scalar_listener", _on_scalar)):
                    also = getattr(jax.monitoring, name, None)
                    if also is not None:
                        also(listener)
                _listener_registered = True
    except Exception:  # noqa: BLE001 — detector must never break user code
        return False
    return True


def _norm_argnums(v: Any) -> tuple:
    if v is None:
        return ()
    if isinstance(v, int):
        return (v,)
    return tuple(v)


def _norm_argnames(v: Any) -> tuple:
    if v is None:
        return ()
    if isinstance(v, str):
        return (v,)
    return tuple(v)


def _signature(args: tuple, kwargs: dict, static_argnums: tuple = (),
               static_argnames: tuple = ()) -> str:
    """Compact shape/dtype signature of a call's arguments.  Static
    arguments (per the site's jit kwargs) are rendered by VALUE in a
    separate ``static(...)`` suffix — a changed static value is an
    expected recompile, and the warning path tells them apart by this
    split.  Only computed when a compile actually fired (never on the
    per-step hot path), so an O(tree) walk here is fine."""
    def leaf(x: Any) -> str:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return f"{dtype}[{','.join(str(d) for d in shape)}]"
        if isinstance(x, (bool, int, float, complex, str, bytes,
                          type(None))):
            return f"{type(x).__name__}={x!r}"
        return type(x).__name__

    def flat(x: Any) -> list:
        try:
            import jax
            return jax.tree_util.tree_leaves(x)
        except Exception:  # noqa: BLE001
            return [x]

    parts: List[str] = []
    static: List[str] = []
    for i, a in enumerate(args):
        if i in static_argnums:
            static.append(f"[{i}]={a!r}")
        else:
            parts.extend(leaf(x) for x in flat(a))
    for k in sorted(kwargs):
        if k in static_argnames:
            static.append(f"{k}={kwargs[k]!r}")
        else:
            parts.extend(leaf(x) for x in flat(kwargs[k]))
    if len(parts) > 64:
        parts = parts[:64] + [f"...(+{len(parts) - 64} leaves)"]
    sig = "(" + ", ".join(parts) + ")"
    if static:
        sig += " static(" + ", ".join(static) + ")"
    return sig


def _traced_part(sig: str) -> str:
    return sig.split(" static(")[0]


class TrackedFunction:
    """Transparent wrapper around a (jitted) callable: forwards every
    attribute (``.lower``, ``.compile``, ...) to the wrapped function so
    AOT workflows keep working."""

    def __init__(self, fn, site: str, static_argnums: Any = None,
                 static_argnames: Any = None, donate_argnums: Any = None):
        self.__wrapped__ = fn
        self._site = _site_state(site)
        # Jit kwargs forwarded from track(): static args are
        # signature'd by VALUE (a change there is an expected
        # recompile, not shape churn) and donation is surfaced so
        # tooling reading the wrapper sees the same contract the
        # underlying jit was built with.
        self.static_argnums = _norm_argnums(static_argnums)
        self.static_argnames = _norm_argnames(static_argnames)
        self.donate_argnums = _norm_argnums(donate_argnums)
        if self.static_argnums:
            self._site.static_argnums = self.static_argnums
        if self.static_argnames:
            self._site.static_argnames = self.static_argnames
        if self.donate_argnums:
            self._site.donate_argnums = self.donate_argnums

    def __getattr__(self, name: str):
        if name == "__wrapped__":
            # Instance dict not populated yet (unpickle path): avoid
            # recursing through this very lookup.
            raise AttributeError(name)
        return getattr(self.__wrapped__, name)

    def __call__(self, *args, **kwargs):
        if not _enabled or not ensure_listener():
            return self.__wrapped__(*args, **kwargs)
        frame = {"compiles": 0, "compile_s": 0.0}
        prev = getattr(_tls, "site", None)
        _tls.site = frame
        try:
            return self.__wrapped__(*args, **kwargs)
        finally:
            # Nested tracked calls shadow this frame while they run, so
            # their compiles are charged to the INNER site only.
            _tls.site = prev
            if frame["compiles"]:
                self._note_compiles(frame, args, kwargs)
            else:
                self._site.warm = True

    def _note_compiles(self, frame: Dict[str, float], args, kwargs) -> None:
        site = self._site
        tags = {"fn": site.name}
        telemetry.inc("ray_tpu_profiler_compile_total",
                      frame["compiles"], tags=tags)
        telemetry.observe("ray_tpu_profiler_compile_seconds",
                          frame["compile_s"], tags=tags)
        sig = _signature(args, kwargs, self.static_argnums,
                         self.static_argnames)
        with _lock:
            site.compiles += frame["compiles"]
            site.compile_s += frame["compile_s"]
            known = sig in site.signatures
            if not known:
                site.signatures.append(sig)
            site.last_signature = sig
            post_warmup = site.warm and not known
            if post_warmup:
                site.recompiles += 1
                warn_now = not site.warned
                site.warned = True
            else:
                warn_now = False
            prior = [s for s in site.signatures if s != sig]
        if post_warmup:
            telemetry.inc("ray_tpu_profiler_recompiles_total", tags=tags)
            # Same traced shapes as an earlier signature -> only the
            # static(...) suffix changed: an expected recompile (each
            # static value compiles its own program by design), so the
            # advice differs from the shape-churn warning.
            static_only = any(_traced_part(p) == _traced_part(sig)
                              for p in prior)
            if warn_now and static_only:
                logger.warning(
                    "post-warmup recompilation of %r (%.2fs of XLA "
                    "compile): a STATIC argument changed value — %s "
                    "(previously seen: %s).  Each distinct static value "
                    "compiles its own program; if this static varies "
                    "per step, make it a traced argument or bucket its "
                    "values.  (warned once per site; "
                    "ray_tpu_profiler_recompiles_total{fn=%r} keeps "
                    "counting)",
                    site.name, frame["compile_s"], sig,
                    "; ".join(prior[-3:]) or "<none recorded>", site.name)
            elif warn_now:
                logger.warning(
                    "post-warmup recompilation of %r (%.2fs of XLA "
                    "compile): argument shapes/dtypes changed to %s "
                    "(previously seen: %s).  Pad or bucket the varying "
                    "dimension — every distinct shape compiles its own "
                    "program.  (warned once per site; "
                    "ray_tpu_profiler_recompiles_total{fn=%r} keeps "
                    "counting)",
                    site.name, frame["compile_s"], sig,
                    "; ".join(prior[-3:]) or "<none recorded>", site.name)


def _site_state(name: str) -> _SiteState:
    with _lock:
        st = _sites.get(name)
        if st is None:
            st = _sites[name] = _SiteState(name)
        return st


def track(fn, name: Optional[str] = None, static_argnums: Any = None,
          static_argnames: Any = None, donate_argnums: Any = None):
    """Wrap ``fn`` (typically a jitted function) with per-site compile
    accounting and post-warmup recompile detection.  Pass the same
    ``static_argnums``/``static_argnames``/``donate_argnums`` the jit
    was built with so signatures classify static-value changes as
    expected recompiles."""
    if isinstance(fn, TrackedFunction):
        return fn
    site = name or getattr(fn, "__name__", None) \
        or type(fn).__name__
    global _enabled
    _enabled = True
    return TrackedFunction(fn, site, static_argnums=static_argnums,
                           static_argnames=static_argnames,
                           donate_argnums=donate_argnums)


def install() -> bool:
    """Enable the detector process-wide: the listener, and the sites that
    :func:`track` wraps.  Safe to call repeatedly; returns False where jax
    is not imported yet (callers install after their own jax import)."""
    global _enabled
    _enabled = True
    return ensure_listener()


def uninstall() -> None:
    """Disable the detector (the monitoring listener stays registered:
    the spans are not the detector's to stop)."""
    global _enabled
    _enabled = False


def report() -> Dict[str, Any]:
    """Per-site compile accounting snapshot (diagnostics / tests)."""
    with _lock:
        return {name: {
            "compiles": st.compiles,
            "compile_seconds": round(st.compile_s, 4),
            "warm": st.warm,
            "recompiles": st.recompiles,
            "signatures": list(st.signatures),
            "last_signature": st.last_signature,
            "static_argnums": list(st.static_argnums),
            "static_argnames": list(st.static_argnames),
            "donate_argnums": list(st.donate_argnums),
        } for name, st in _sites.items()}


def _reset_for_tests() -> None:
    global _enabled
    with _lock:
        _sites.clear()
    _enabled = False
