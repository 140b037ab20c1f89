"""Serve core: deployments, replicas, router, handles, HTTP ingress.

The control plane lives in the ``SERVE_CONTROLLER`` actor (reference:
_private/controller.py:126 — ServeController as a detached actor): it
owns replica actors, so deployments keep serving after the creating
driver exits.  Versioned replica-set snapshots flow through the cluster
KV (reference: _private/long_poll.py LongPollHost); each consuming
process runs a local ``_Router`` that rebuilds replica handles from the
snapshot and does power-of-two-choices over its own in-flight counts
(reference: pow_2_router.py — per-router counts, exactly the reference's
model), pushing totals back to the controller for request-based
autoscaling.  The optional HTTP proxy is an aiohttp app on a daemon
thread (reference: proxy.py uvicorn ingress) with chunked streaming for
generator responses.
"""

from __future__ import annotations

import random
import threading
import time

from .._private import aioloop as _aioloop
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, TYPE_CHECKING

if TYPE_CHECKING:
    from .controller import AutoscalingConfig

_app_lock = threading.Lock()
_routers: Dict[str, "_Router"] = {}
_http_server = None
_controller_handle = None


class OverloadError(RuntimeError):
    """A request was shed by admission control (deployment queue bound
    or SLO router).  Retriable: the service is healthy but saturated —
    back off and resend instead of treating it as a failure."""

    retriable = True


@dataclass
class Deployment:
    cls_or_fn: Any
    name: str
    num_replicas: int = 1
    max_ongoing_requests: int = 8
    num_cpus: float = 0.0
    num_tpus: int = 0
    ray_actor_options: Dict[str, Any] = field(default_factory=dict)
    init_args: tuple = ()
    init_kwargs: Dict[str, Any] = field(default_factory=dict)
    # Queue-depth autoscaling (reference: serve/autoscaling_policy.py);
    # None = fixed num_replicas.
    autoscaling_config: Optional["AutoscalingConfig"] = None
    # Admission bound on the handle path: reject (OverloadError) once
    # in-flight requests exceed replica capacity (num_replicas *
    # max_ongoing_requests) plus this queue allowance.  None = queue
    # unboundedly (legacy behavior).
    max_queued_requests: Optional[int] = None

    def options(self, **kw) -> "Deployment":
        import dataclasses
        known = {f.name for f in dataclasses.fields(Deployment)}
        return dataclasses.replace(
            self, **{k: v for k, v in kw.items() if k in known})

    def bind(self, *args, **kwargs) -> "Application":
        import dataclasses
        d = dataclasses.replace(self, init_args=args, init_kwargs=kwargs)
        return Application(d)


@dataclass
class Application:
    deployment: Deployment


def deployment(_cls=None, *, name: Optional[str] = None,
               num_replicas: int = 1, max_ongoing_requests: int = 8,
               num_cpus: float = 0.0, num_tpus: int = 0,
               ray_actor_options: Optional[Dict[str, Any]] = None,
               autoscaling_config: Optional["AutoscalingConfig"] = None,
               max_queued_requests: Optional[int] = None):
    """@serve.deployment (reference: serve/api.py:471)."""
    def wrap(cls):
        return Deployment(cls, name or cls.__name__,
                          num_replicas=num_replicas,
                          max_ongoing_requests=max_ongoing_requests,
                          num_cpus=num_cpus, num_tpus=num_tpus,
                          ray_actor_options=ray_actor_options or {},
                          autoscaling_config=autoscaling_config,
                          max_queued_requests=max_queued_requests)
    if _cls is not None:
        return wrap(_cls)
    return wrap


class _ReplicaActor:
    """Hosts the user callable (reference: replica.py UserCallableWrapper)."""

    def __init__(self, cls_blob: bytes, init_args, init_kwargs):
        from .._private import serialization
        from ..util import telemetry
        # Seconds to a ready replica: unpickling the deployment and its
        # constructor (for an LLM replica: backend, weights, engine).
        with telemetry.profile_span("serve_replica_init", "serve"):
            target = serialization.loads_control(cls_blob)
            if isinstance(target, type):
                self._callable = target(*init_args, **init_kwargs)
            else:
                self._callable = target

    def _resolve_target(self, method: str):
        target = getattr(self._callable, method, None)
        if target is None and method == "__call__":
            target = self._callable
        if target is None:
            raise AttributeError(f"deployment has no method {method!r}")
        return target

    def handle_request(self, method: str, args, kwargs,
                       multiplexed_model_id: Optional[str] = None):
        target = self._resolve_target(method)
        if multiplexed_model_id is None:
            return target(*args, **kwargs)
        # Multiplexed request: expose the model id for the duration of the
        # call (reference: serve.get_multiplexed_model_id()).
        from .multiplex import _set_current_model_id
        token = _set_current_model_id(multiplexed_model_id)
        try:
            return target(*args, **kwargs)
        finally:
            from .multiplex import _current_model_id
            _current_model_id.reset(token)

    def ping(self):
        return "ok"

    def handle_request_stream(self, method: str, args, kwargs,
                              multiplexed_model_id: Optional[str] = None):
        """Generator entry point: runs as a streaming actor call — each
        yielded item publishes immediately (token streaming).  Must BE a
        generator (not return one) so the multiplexed-model context stays
        installed while the body executes, not just until first return."""
        target = self._resolve_target(method)
        if multiplexed_model_id is None:
            yield from target(*args, **kwargs)
            return
        from .multiplex import _current_model_id, _set_current_model_id
        token = _set_current_model_id(multiplexed_model_id)
        try:
            yield from target(*args, **kwargs)
        finally:
            _current_model_id.reset(token)


class _DeploymentState:
    """Replica set + router state; mutated only by start/stop and the
    ServeController's reconcile loop (self-healing + autoscaling)."""

    def __init__(self, dep: Deployment):
        self.deployment = dep
        self.replicas: List[Any] = []
        self.inflight: Dict[int, int] = {}  # id(replica) -> in-flight count
        self.stopped = False
        # Reconcile-backfill crash-loop backoff (controller-owned).
        self.backfill_not_before = 0.0
        self.backfill_backoff_s = 0.5
        ac = dep.autoscaling_config
        self.target_replicas = max(dep.num_replicas, ac.min_replicas) \
            if ac is not None else dep.num_replicas
        from .multiplex import _MultiplexedDescriptor
        # Mirror the replica LRU size so routers stop preferring a
        # replica once it would have evicted the model (avoids reload
        # thrash pinning all hot models to one replica); shipped to
        # routers in the replica-set snapshot.
        cap = None
        target = dep.cls_or_fn
        if isinstance(target, type):
            for klass in target.__mro__:  # loaders may be inherited
                for attr in vars(klass).values():
                    if isinstance(attr, _MultiplexedDescriptor):
                        cap = attr._max
                        break
                if cap is not None:
                    break
        self.multiplex_cap = cap if cap is not None else 8
        self._lock = threading.Lock()
        self._opts: Optional[Dict[str, Any]] = None
        self._cls_blob: Optional[bytes] = None

    def _replica_opts(self):
        from .._private import serialization
        if self._opts is None:
            self._cls_blob = serialization.dumps_control(
                self.deployment.cls_or_fn)
            opts: Dict[str, Any] = {
                "max_concurrency": self.deployment.max_ongoing_requests,
                "num_cpus": self.deployment.num_cpus,
            }
            if self.deployment.num_tpus:
                opts["num_tpus"] = self.deployment.num_tpus
            opts.update(self.deployment.ray_actor_options)
            self._opts = opts
        return self._cls_blob, self._opts

    def add_replica(self, wait_ready: bool = False):
        import ray_tpu
        # Safe bare read: stopped is a monotonic shutdown latch; a stale
        # False only delays the error to the actor-create round trip.
        if self.stopped:  # ray-tpu: noqa[RT401]
            raise RuntimeError("deployment is stopped")
        cls_blob, opts = self._replica_opts()
        actor_cls = ray_tpu.remote(_ReplicaActor)
        r = actor_cls.options(**opts).remote(
            cls_blob, self.deployment.init_args, self.deployment.init_kwargs)
        if wait_ready:
            try:
                ray_tpu.get(r.ping.remote(), timeout=120)
            except Exception:
                ray_tpu.kill(r)
                raise
        with self._lock:
            if self.stopped:
                ray_tpu.kill(r)
                raise RuntimeError("deployment is stopped")
            self.replicas.append(r)
            self.inflight[id(r)] = 0
        return r

    def pop_replica(self, min_load: Optional[Dict[str, int]] = None,
                    specific=None):
        """Detach and return a replica WITHOUT killing it — the
        controller drains it first.  Default pick: least-loaded (by the
        router-reported per-replica loads); ``specific`` detaches that
        exact replica instead (node-drain evacuation)."""
        with self._lock:
            if not self.replicas:
                return None
            if specific is not None:
                if specific not in self.replicas:
                    return None  # already detached (double-drain race)
                idx = self.replicas.index(specific)
            else:
                loads = min_load or {}
                idx = min(range(len(self.replicas)),
                          key=lambda i: loads.get(
                              self.replicas[i]._actor_id.hex(), 0))
            r = self.replicas.pop(idx)
            self.inflight.pop(id(r), None)
            return r

    def start(self):
        import ray_tpu
        refs = [self.add_replica().ping.remote()
                for _ in range(self.target_replicas)]
        ray_tpu.get(refs, timeout=120)

    def stop(self):
        import ray_tpu
        with self._lock:
            self.stopped = True
            replicas, self.replicas = self.replicas, []
            self.inflight.clear()
        for r in replicas:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass


def _rt_token() -> int:
    from .._private import runtime as rtmod
    return id(rtmod.current_runtime())


def _cached_controller() -> Optional[Any]:
    """Cached handle, valid only for the CURRENT runtime (a new init()
    after shutdown must not reuse a dead cluster's controller)."""
    with _app_lock:
        if _controller_handle is not None and \
                _controller_handle[0] == _rt_token():
            return _controller_handle[1]
    return None


def _controller() -> Any:
    """Get-or-create the cluster's SERVE_CONTROLLER actor handle."""
    global _controller_handle
    import ray_tpu
    cached = _cached_controller()
    if cached is not None:
        return cached
    from .controller import (CONTROLLER_NAME, CONTROLLER_NAMESPACE,
                             ServeControllerActor)
    # Session-lifetime by design: deployments keep serving after the
    # driver's handles are gone — declare it to the leak sanitizer.
    from .._private import sanitizer
    sanitizer.session_scoped(CONTROLLER_NAME)
    cls = ray_tpu.remote(ServeControllerActor)
    last_exc: Optional[Exception] = None
    for _attempt in range(10):
        handle = cls.options(
            name=CONTROLLER_NAME, namespace=CONTROLLER_NAMESPACE,
            get_if_exists=True, max_restarts=10, num_cpus=0,
            max_concurrency=16).remote()
        try:
            ray_tpu.get(handle.ping.remote(), timeout=120)
        except Exception as e:  # noqa: BLE001
            # A dying controller (shutdown race) can win the name lookup;
            # wait for its death to land, then create fresh.
            last_exc = e
            time.sleep(0.3)
            continue
        with _app_lock:
            _controller_handle = (_rt_token(), handle)
        return handle
    raise RuntimeError(
        f"could not reach or recreate the serve controller: {last_exc!r}")


def _existing_controller() -> Optional[Any]:
    global _controller_handle
    cached = _cached_controller()
    if cached is not None:
        return cached
    import ray_tpu
    from .controller import CONTROLLER_NAME, CONTROLLER_NAMESPACE
    try:
        handle = ray_tpu.get_actor(CONTROLLER_NAME,
                                   namespace=CONTROLLER_NAMESPACE)
    except ValueError:
        return None
    with _app_lock:
        _controller_handle = (_rt_token(), handle)
    return handle


class _Router:
    """Per-process replica-set cache + pow-2 routing over LOCAL in-flight
    counts (reference: pow_2_router.py — routers track their own counts;
    the controller aggregates pushed totals for autoscaling)."""

    REFRESH_S = 1.0

    def __init__(self, name: str):
        import os
        self.name = name
        self.router_id = os.urandom(8).hex()
        self._lock = threading.Lock()
        self._version = -1
        self._replicas: List[tuple] = []  # (actor_id_hex, handle)
        self._inflight: Dict[str, int] = {}
        self._fetched = 0.0
        # Admission state from the KV snapshot: total replica capacity
        # (sum of max_ongoing) and the deployment's queue allowance
        # (None = unbounded, the legacy behavior).
        self._capacity = 0
        self._max_queued: Optional[int] = None
        from .multiplex import RouterAffinity
        self.affinity = RouterAffinity(8)
        self._metrics_started = False
        # Driver-local fast path: evict replicas the moment the controller
        # marks their actor DEAD (reference: router reacting to
        # long-poll replica-set pushes) — the KV TTL refresh alone leaves
        # a window where fresh requests route to a corpse.
        import weakref

        from .._private import runtime as rtmod
        rt = rtmod.current_runtime()
        if rt is not None and hasattr(rt, "controller"):
            self_ref = weakref.ref(self)

            def on_actor_state(msg, _ref=self_ref):
                router = _ref()
                if router is None:
                    return
                actor_id, state = msg
                if state == "DEAD":
                    router.evict(actor_id.hex())
            rt.controller.subscribe("actor_state", on_actor_state)

    def evict(self, hexid: str) -> None:
        with self._lock:
            before = len(self._replicas)
            self._replicas = [e for e in self._replicas if e[0] != hexid]
            if len(self._replicas) != before:
                self._inflight.pop(hexid, None)
                self.affinity.drop_replica(hexid)
                # Force the next pick to consult the KV snapshot.
                self._fetched = 0.0

    def _refresh(self, force: bool = False) -> None:
        import pickle
        now = time.monotonic()
        with self._lock:
            if not force and now - self._fetched < self.REFRESH_S:
                return
        from .._private.api import _control
        from .controller import REPLICA_KV_PREFIX
        blob = _control("kv_get", REPLICA_KV_PREFIX + self.name)
        entries: List[tuple] = []
        version = None
        cap = None
        max_queued = None
        if blob is not None:
            snap = pickle.loads(blob)
            version, entries = snap[0], snap[1]
            if len(snap) > 2:
                cap = snap[2]
            if len(snap) > 3:
                max_queued = snap[3]
        with self._lock:
            self._fetched = now
            if version is None or version == self._version:
                if blob is None:
                    self._replicas = []
                return
            self._version = version
            self._capacity = sum(e[2] for e in entries)
            self._max_queued = max_queued
            if cap is not None and cap != self.affinity._max:
                from .multiplex import RouterAffinity
                self.affinity = RouterAffinity(cap)
            from .._private.api import ActorHandle
            from .._private.ids import ActorID
            live = set()
            handles = []
            for hexid, cls_name, max_ongoing in entries:
                live.add(hexid)
                handles.append((hexid, ActorHandle(
                    ActorID(bytes.fromhex(hexid)), cls_name)))
            self._replicas = handles
            for gone in set(self._inflight) - live:
                self._inflight.pop(gone, None)
                self.affinity.drop_replica(gone)

    def pick(self, model_id: Optional[str]) -> Optional[tuple]:
        with self._lock:
            n = len(self._replicas)
            if n == 0:
                return None
            if model_id is not None and n > 1:
                affine = set(self.affinity.replicas_for(model_id))
                cands = [e for e in self._replicas if e[0] in affine]
                if cands:
                    return min(cands, key=lambda e:
                               self._inflight.get(e[0], 0))
            if n == 1:
                return self._replicas[0]
            ia, ib = random.sample(range(n), 2)
            a, b = self._replicas[ia], self._replicas[ib]
            return a if self._inflight.get(a[0], 0) <= \
                self._inflight.get(b[0], 0) else b

    def note_start(self, hexid: str) -> None:
        with self._lock:
            self._inflight[hexid] = self._inflight.get(hexid, 0) + 1
            # Under the lock: an out-of-order set after release could
            # leave a stale in-flight count on a quiescent deployment.
            self._set_ongoing_gauge(sum(self._inflight.values()))
        self._ensure_metrics_thread()

    def note_done(self, hexid: str) -> None:
        with self._lock:
            if hexid in self._inflight:
                self._inflight[hexid] = max(0, self._inflight[hexid] - 1)
            self._set_ongoing_gauge(sum(self._inflight.values()))

    def _set_ongoing_gauge(self, total: int) -> None:
        from ..util import telemetry
        telemetry.set_gauge("ray_tpu_serve_ongoing_requests", total,
                            tags={"deployment": self.name})

    def total_inflight(self) -> int:
        with self._lock:
            return sum(self._inflight.values())

    def over_admission_bound(self) -> bool:
        """True when this router's in-flight count exceeds replica
        capacity plus the deployment's max_queued_requests allowance —
        the handle sheds instead of queueing unboundedly."""
        with self._lock:
            if self._max_queued is None or not self._replicas:
                return False
            return sum(self._inflight.values()) >= \
                self._capacity + self._max_queued

    def _ensure_metrics_thread(self) -> None:
        with self._lock:
            if self._metrics_started:
                return
            self._metrics_started = True

        def push():
            try:
                while True:
                    time.sleep(1.0)
                    with _app_lock:
                        if _routers.get(self.name) is not self:
                            return  # router replaced (redeploy): retire
                    from .._private import runtime as rtmod
                    if rtmod.current_runtime() is None:
                        return  # runtime shut down
                    try:
                        ctrl = _existing_controller()
                        if ctrl is None:
                            continue  # controller restarting: keep trying
                        with self._lock:
                            counts = {k: v
                                      for k, v in self._inflight.items()
                                      if v}
                        # Best-effort stats push; a lost tick is
                        # replaced by the next one.
                        ctrl.report_metrics.remote(  # ray-tpu: detached
                            self.name, self.router_id, counts)
                    except Exception:
                        # Transient (controller swap, runtime teardown
                        # race): retry next tick; the loop exits via the
                        # runtime/router checks above.
                        continue
            finally:
                # Let a future request respawn the pusher if this router
                # is still the live one (a dead pusher would silently
                # starve the autoscaler and mis-drain downscales).
                with self._lock:
                    self._metrics_started = False
        from .._private import sanitizer
        sanitizer.spawn(push, name=f"serve-metrics-{self.name}")


def _router_for(name: str) -> _Router:
    with _app_lock:
        r = _routers.get(name)
        if r is None:
            r = _routers[name] = _Router(name)
    return r


class DeploymentHandle:
    """reference: serve/handle.py:1041 — .remote() routes a request;
    ``options(stream=True)`` returns an ObjectRefGenerator over a
    generator method's yielded items (token streaming)."""

    def __init__(self, name: str, method: str = "__call__",
                 multiplexed_model_id: Optional[str] = None,
                 stream: bool = False):
        self._name = name
        self._method = method
        self._model_id = multiplexed_model_id
        self._stream = stream

    def options(self, method_name: Optional[str] = None,
                multiplexed_model_id: Optional[str] = None,
                stream: Optional[bool] = None) -> "DeploymentHandle":
        return DeploymentHandle(self._name, method_name or self._method,
                                multiplexed_model_id or self._model_id,
                                self._stream if stream is None else stream)

    def __getattr__(self, item):
        if item.startswith("_"):
            raise AttributeError(item)
        return DeploymentHandle(self._name, item, self._model_id,
                                self._stream)

    def remote(self, *args, **kwargs):
        from ..util import telemetry, tracing
        t_route = time.perf_counter()
        t_route_wall = time.time()
        tags = {"deployment": self._name}

        def _note_latency():
            telemetry.observe("ray_tpu_serve_request_latency_seconds",
                              time.perf_counter() - t_route, tags=tags)

        router = _router_for(self._name)
        router._refresh()
        if router.over_admission_bound():
            # SLO-aware shedding: overload degrades into a fast
            # retriable rejection, not a queue that times out later.
            telemetry.inc("ray_tpu_serve_shed_total", tags=tags)
            raise OverloadError(
                f"deployment {self._name!r} is over its admission bound "
                "(max_queued_requests); retry with backoff")
        # A reconcile may briefly leave zero replicas (all died at once);
        # wait for the controller to backfill rather than failing the
        # request (reference: router retries against the long-poll set).
        deadline = time.monotonic() + 60
        while True:
            picked = router.pick(self._model_id)
            if picked is not None:
                break
            if time.monotonic() > deadline:
                telemetry.inc("ray_tpu_serve_request_errors_total",
                              tags=tags)
                raise RuntimeError(
                    f"deployment {self._name!r} has no live replicas")
            time.sleep(0.05)
            router._refresh(force=True)
        hexid, replica = picked
        # Handle-path queue wait as a trace span: admission + replica
        # pick, parented under the caller's context — and installed as
        # the parent of the actor submit below, so the whole request
        # (route -> submit -> execute -> engine phases) is ONE tree even
        # when the caller had no ambient context.
        route_ctx = tracing.record_span(
            tracing.current(), f"serve_route {self._name}",
            t_route_wall, t_route_wall + (time.perf_counter() - t_route),
            {"deployment": self._name, "replica": hexid[:12]})
        telemetry.inc("ray_tpu_serve_requests_total", tags=tags)
        router.note_start(hexid)
        if self._model_id is not None:
            router.affinity.note(hexid, self._model_id)
        method = "handle_request_stream" if self._stream \
            else "handle_request"
        submit = getattr(replica, method)
        if self._stream:
            submit = submit.options(num_returns="streaming")
        prev_ctx = tracing.current()
        if route_ctx is not None:
            tracing.set_current(route_ctx)
        try:
            if self._model_id is not None:
                ref = submit.remote(self._method, args, kwargs,
                                    multiplexed_model_id=self._model_id)
            else:
                ref = submit.remote(self._method, args, kwargs)
        finally:
            if route_ctx is not None:
                tracing.set_current(prev_ctx)
        if self._stream:
            # Streamed request: the wrapper decrements in-flight when the
            # consumer finishes (or abandons) the stream.
            def _stream_refs(gen=ref):
                try:
                    for item_ref in gen:
                        yield item_ref
                finally:
                    router.note_done(hexid)
                    _note_latency()
            return _stream_refs()

        def _done():
            _wait_quiet(ref)
            router.note_done(hexid)
            _note_latency()
        # Decrement when the result materializes.
        from .._private import sanitizer
        sanitizer.spawn(_done, name="serve-done-watch")
        return ref


def _wait_quiet(ref):
    import ray_tpu
    try:
        ray_tpu.wait([ref], num_returns=1, timeout=3600)
    except Exception:
        pass


def run(app: Application, *, name: Optional[str] = None,
        route_prefix: Optional[str] = None,
        http_port: Optional[int] = None) -> DeploymentHandle:
    """Deploy through the controller actor and return a handle
    (reference: serve/api.py:902).  The controller owns the replicas, so
    the deployment keeps serving if this driver exits."""
    import ray_tpu
    if not ray_tpu.is_initialized():
        ray_tpu.init()
    dep = app.deployment if isinstance(app, Application) else app
    from .._private import serialization
    from ..util import telemetry
    with telemetry.profile_span("serve_run", "serve",
                                extra={"deployment": dep.name}):
        ctrl = _controller()
        ray_tpu.get(ctrl.deploy.remote(serialization.dumps_control(dep)),
                    timeout=300)
    with _app_lock:
        _routers.pop(dep.name, None)  # drop stale replica cache
    if http_port is not None:
        _ensure_http(http_port)
    return DeploymentHandle(dep.name)


def get_deployment_handle(name: str) -> DeploymentHandle:
    import pickle

    from .._private.api import _control
    from .controller import REPLICA_KV_PREFIX
    if _control("kv_get", REPLICA_KV_PREFIX + name) is None:
        raise ValueError(f"no deployment named {name!r}")
    _ = pickle  # (snapshot validated lazily by the router)
    return DeploymentHandle(name)


def status() -> Dict[str, Dict[str, Any]]:
    import ray_tpu
    ctrl = _existing_controller()
    if ctrl is None:
        return {}
    return ray_tpu.get(ctrl.status.remote(), timeout=60)


def shutdown() -> None:
    """Stop every deployment and the controller actor (reference:
    serve.shutdown tearing down the Serve instance)."""
    global _http_server, _controller_handle
    import ray_tpu
    ctrl = _existing_controller()
    if ctrl is not None:
        try:
            ray_tpu.get(ctrl.shutdown_all.remote(), timeout=120)
        except Exception:
            pass
        try:
            ray_tpu.kill(ctrl)
        except Exception:
            pass
    with _app_lock:
        _controller_handle = None
        _routers.clear()
    if _http_server is not None:
        _http_server.stop()
        _http_server = None


# --------------------------------------------------------------------- #
# HTTP ingress (reference: _private/proxy.py; aiohttp instead of uvicorn)
# --------------------------------------------------------------------- #

def build_ingress_app():
    """The ingress aiohttp application: POST /{deployment} routes the
    JSON body through a deployment handle (chunked ndjson when
    ``stream`` is set).  Shared by the in-process _HttpServer and the
    per-node ProxyActor (serve/proxy.py)."""
    import asyncio

    from aiohttp import web

    async def handle(request: "web.Request"):
            import json as _json
            name = request.match_info["deployment"]
            try:
                body = await request.json()
            except Exception:
                body = {}
            stream = bool(body.pop("stream", False)) if isinstance(
                body, dict) else False
            try:
                handle_ = get_deployment_handle(name)
                import ray_tpu
                loop = asyncio.get_event_loop()
                if stream:
                    # Chunked streaming ingress (reference: proxy.py
                    # streaming responses): each generator item is one
                    # newline-delimited JSON chunk.
                    gen = handle_.options(stream=True).remote(body)
                    resp = web.StreamResponse(headers={
                        "Content-Type": "application/x-ndjson"})
                    await resp.prepare(request)
                    it = iter(gen)
                    try:
                        while True:
                            item_ref = await loop.run_in_executor(
                                None, lambda: next(it, None))
                            if item_ref is None:
                                break
                            item = await loop.run_in_executor(
                                None, lambda: ray_tpu.get(item_ref,
                                                          timeout=300))
                            await resp.write(
                                (_json.dumps({"result": item})
                                 + "\n").encode())
                    except Exception as e:  # noqa: BLE001
                        # Mid-stream failure: the chunked response is
                        # already prepared — emit an error CHUNK, never a
                        # second response.
                        await resp.write(
                            (_json.dumps({"error": repr(e)})
                             + "\n").encode())
                    await resp.write_eof()
                    return resp
                try:
                    ref = handle_.remote(body)
                except Exception as e:  # noqa: BLE001
                    # Handle-level failure (e.g. no live replicas):
                    # remote() already counted it — don't double-count.
                    return web.json_response({"error": repr(e)},
                                             status=500)
                result = await loop.run_in_executor(
                    None, lambda: ray_tpu.get(ref, timeout=300))
                return web.json_response({"result": result})
            except Exception as e:  # noqa: BLE001
                from ..util import telemetry
                telemetry.inc("ray_tpu_serve_request_errors_total",
                              tags={"deployment": name})
                return web.json_response({"error": repr(e)}, status=500)

    app = web.Application()
    app.router.add_post("/{deployment}", handle)
    app.router.add_get("/-/healthz",
                       lambda r: web.Response(text="ok"))
    return app


class _HttpServer:
    def __init__(self, port: int, host: str = "127.0.0.1"):
        self.port = port
        self.host = host
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._started = threading.Event()
        self._runner = None
        self._loop = None
        self._thread.start()
        if not self._started.wait(10):
            raise RuntimeError("serve http ingress failed to start")

    def _serve(self):
        import asyncio

        from aiohttp import web

        async def main():
            app = build_ingress_app()
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, self.host, self.port)
            await site.start()
            try:
                # Ephemeral bind (port 0): record the real port.
                self.port = site._server.sockets[0].getsockname()[1]
            except Exception:
                pass
            self._runner = runner
            self._started.set()
            while True:
                await asyncio.sleep(3600)

        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_until_complete(main())
        except Exception:
            pass
        finally:
            # Executor + loop retirement shared across the three
            # daemon-loop servers (see _private/aioloop.py).
            _aioloop.shutdown_loop(self._loop)

    def stop(self):
        _aioloop.stop_loop_thread(self._loop, self._thread)


def _ensure_http(port: int) -> None:
    global _http_server
    if _http_server is None:
        _http_server = _HttpServer(port)
