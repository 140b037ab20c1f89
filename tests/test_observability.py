"""State API, task events, user metrics, timeline tests.

Reference analogs: python/ray/tests/test_state_api.py, test_metrics_agent.py,
test_task_events.py.
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import state as state_api


@ray_tpu.remote
def quick(x):
    return x + 1


@ray_tpu.remote
def failing():
    raise RuntimeError("intentional")


@ray_tpu.remote
class StatefulThing:
    def ping(self):
        return "pong"


class TestStateAPI:
    def test_list_tasks_records_lifecycle(self, ray_start):
        ref = quick.remote(1)
        assert ray_tpu.get(ref) == 2
        time.sleep(0.1)
        tasks = state_api.list_tasks()
        mine = [t for t in tasks if t["name"].startswith("quick")]
        assert mine, f"no quick task in {tasks[:3]}"
        done = [t for t in mine if t["state"] == "FINISHED"]
        assert done
        ev = done[-1]
        assert ev["state_times"].get("RUNNING") is not None
        assert ev["state_times"]["FINISHED"] >= ev["state_times"]["RUNNING"]

    def test_failed_task_records_error(self, ray_start):
        ref = failing.remote()
        with pytest.raises(Exception):
            ray_tpu.get(ref)
        time.sleep(0.1)
        failed = state_api.list_tasks(filters=[("state", "=", "FAILED")])
        assert any("intentional" in (t["error_message"] or "")
                   for t in failed)

    def test_list_actors_and_summary(self, ray_start):
        h = StatefulThing.remote()
        assert ray_tpu.get(h.ping.remote()) == "pong"
        actors = state_api.list_actors()
        assert any(a["class_name"] == "StatefulThing" and a["state"] == "ALIVE"
                   for a in actors)
        summary = state_api.summarize_actors()
        assert summary.get("StatefulThing", {}).get("ALIVE", 0) >= 1

    def test_list_nodes_objects_jobs_pgs(self, ray_start):
        ref = ray_tpu.put(b"x" * 10)
        nodes = state_api.list_nodes()
        assert nodes and nodes[0]["is_head"]
        objects = state_api.list_objects()
        assert any(o["object_id"] == ref.hex() for o in objects)
        jobs = state_api.list_jobs()
        assert len(jobs) >= 1
        pg = ray_tpu.placement_group([{"CPU": 1}])
        assert pg.ready(timeout=10)
        pgs = state_api.list_placement_groups()
        assert any(p["placement_group_id"] == pg.id.hex() for p in pgs)
        ray_tpu.remove_placement_group(pg)

    def test_summarize_tasks(self, ray_start):
        ray_tpu.get([quick.remote(i) for i in range(3)])
        time.sleep(0.1)
        summary = state_api.summarize_tasks()
        q = [v for k, v in summary.items() if k.startswith("quick")]
        assert q and q[0].get("FINISHED", 0) >= 3

    def test_state_api_from_worker(self, ray_start):
        @ray_tpu.remote
        def introspect():
            from ray_tpu.util import state
            return len(state.list_nodes())

        assert ray_tpu.get(introspect.remote()) >= 1


class TestTimeline:
    def test_timeline_chrome_trace(self, ray_start, tmp_path):
        ray_tpu.get([quick.remote(i) for i in range(2)])
        time.sleep(0.1)
        out = tmp_path / "trace.json"
        payload = ray_tpu.timeline(str(out))
        trace = json.loads(payload)
        assert isinstance(trace, list) and trace
        ev = [e for e in trace if e["ph"] == "X" and e["cat"] == "task"]
        assert ev
        assert {"name", "ts", "dur", "pid", "tid"} <= set(ev[0])
        assert json.loads(out.read_text()) == trace


# profile_span (user and framework spelling alike): tests/test_span_recorder.py


class TestMetrics:
    def setup_method(self):
        metrics_mod._reset_for_tests()

    def test_counter_gauge_histogram(self, ray_start):
        c = metrics_mod.Counter("test_requests_total", "reqs",
                                tag_keys=("route",))
        c.inc(tags={"route": "/a"})
        c.inc(2.0, tags={"route": "/a"})
        g = metrics_mod.Gauge("test_queue_depth", "depth")
        g.set(7)
        h = metrics_mod.Histogram("test_latency_s", "lat",
                                  boundaries=[0.1, 1.0])
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = metrics_mod.prometheus_text()
        assert 'test_requests_total{route="/a"} 3.0' in text
        assert "test_queue_depth 7.0" in text
        assert 'test_latency_s_bucket{le="0.1"} 1.0' in text
        assert 'test_latency_s_bucket{le="+Inf"} 3.0' in text
        assert "test_latency_s_count 3.0" in text
        assert "# TYPE test_requests_total counter" in text

    def test_counter_validation(self, ray_start):
        c = metrics_mod.Counter("test_val_total", tag_keys=("k",))
        with pytest.raises(ValueError):
            c.inc(-1)
        with pytest.raises(ValueError):
            c.inc(tags={"bogus": "x"})

    def test_metrics_http_server(self, ray_start):
        metrics_mod.Gauge("test_http_gauge").set(1.5)
        port = metrics_mod.start_metrics_server(0)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "test_http_gauge 1.5" in body

    def test_stop_metrics_server_releases_listener(self, ray_start):
        metrics_mod.Gauge("test_stop_gauge").set(2.0)
        port = metrics_mod.start_metrics_server(0)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "test_stop_gauge 2.0" in body
        metrics_mod.stop_metrics_server()
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2)
        # Idempotent (and safe from the reset path).
        metrics_mod.stop_metrics_server()

    def test_task_final_metrics_flush_deterministic(self, ray_start):
        """Metrics recorded just before a task finishes are at the driver
        the moment the task is observed complete — no 2 s flusher race,
        no explicit flush() in the task."""
        @ray_tpu.remote
        def last_gasp():
            from ray_tpu.util import metrics
            metrics.Counter("test_last_gasp_total").inc(3.0)
            return True  # exits well inside the flusher's 2 s window

        assert ray_tpu.get(last_gasp.remote(), timeout=60)
        assert "test_last_gasp_total 3.0" in metrics_mod.prometheus_text()

    def test_worker_metrics_flow_to_driver(self, ray_start):
        @ray_tpu.remote
        def work():
            from ray_tpu.util import metrics
            c = metrics.Counter("test_worker_side_total")
            c.inc(5.0)
            metrics.flush()
            return True

        assert ray_tpu.get(work.remote())
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if "test_worker_side_total 5.0" in metrics_mod.prometheus_text():
                break
            time.sleep(0.2)
        assert "test_worker_side_total 5.0" in metrics_mod.prometheus_text()


class TestTracing:
    """W3C trace-context propagation through task submission (reference:
    python/ray/util/tracing/tracing_helper.py:34,181)."""

    def test_driver_task_nested_task_one_tree(self, ray_start_isolated):
        import ray_tpu
        from ray_tpu.util import tracing

        @ray_tpu.remote
        def inner(x):
            return x * 2

        @ray_tpu.remote
        def outer(x):
            return ray_tpu.get(inner.remote(x)) + 1

        tracing.enable()
        try:
            assert ray_tpu.get(outer.remote(20), timeout=60) == 41
        finally:
            tracing.disable()

        # Give the workers' span RPCs a moment to land.
        import time as _t
        deadline = _t.monotonic() + 20
        spans = []
        while _t.monotonic() < deadline:
            ids = tracing.list_traces()
            if ids:
                spans = tracing.get_trace(ids[0])
                if len(spans) >= 4:
                    break
            _t.sleep(0.2)
        names = [s["name"] for s in spans]
        assert "submit outer" in names and "execute outer" in names
        assert "submit inner" in names and "execute inner" in names
        # One trace id across the whole cascade.
        assert len({s["trace_id"] for s in spans}) == 1
        by_id = {s["span_id"]: s for s in spans}
        sub_inner = next(s for s in spans if s["name"] == "submit inner")
        exec_outer = next(s for s in spans if s["name"] == "execute outer")
        # The nested submit is a child of the outer execute span.
        assert sub_inner["parent_span_id"] == exec_outer["span_id"]
        # The outer execute chains to the driver's submit span.
        sub_outer = next(s for s in spans if s["name"] == "submit outer")
        assert exec_outer["parent_span_id"] == sub_outer["span_id"]
        assert sub_outer["parent_span_id"] is None
        # The tree renders with every span on its own line.
        txt = tracing.render_trace(spans[0]["trace_id"])
        assert txt.count("- ") >= 4

    def test_actor_method_cascade_shares_trace(self, ray_start_isolated):
        """Actor-method calls propagate the W3C context exactly like plain
        tasks: driver -> actor method -> nested task is ONE trace tree."""
        import ray_tpu
        from ray_tpu.util import tracing

        @ray_tpu.remote
        def leaf(x):
            return x + 1

        @ray_tpu.remote
        class Middle:
            def call(self, x):
                return ray_tpu.get(leaf.remote(x)) * 2

        tracing.enable()
        try:
            h = Middle.remote()
            assert ray_tpu.get(h.call.remote(1), timeout=60) == 4
        finally:
            tracing.disable()

        import time as _t
        deadline = _t.monotonic() + 20
        spans = []
        while _t.monotonic() < deadline:
            ids = tracing.list_traces()
            for tid in ids:
                got = tracing.get_trace(tid)
                if any("Middle.call" in s["name"] for s in got):
                    spans = got
            if len(spans) >= 4:
                break
            _t.sleep(0.2)
        names = [s["name"] for s in spans]
        assert "submit Middle.call" in names, names
        assert "execute Middle.call" in names
        assert "submit leaf" in names and "execute leaf" in names
        # The whole cascade shares one trace id.
        assert len({s["trace_id"] for s in spans}) == 1
        exec_call = next(s for s in spans
                         if s["name"] == "execute Middle.call")
        sub_call = next(s for s in spans
                        if s["name"] == "submit Middle.call")
        sub_leaf = next(s for s in spans if s["name"] == "submit leaf")
        exec_leaf = next(s for s in spans if s["name"] == "execute leaf")
        # Nested submit inside the actor method chains to its execute
        # span; the method execute chains to the driver's submit.
        assert sub_leaf["parent_span_id"] == exec_call["span_id"]
        assert exec_call["parent_span_id"] == sub_call["span_id"]
        assert exec_leaf["parent_span_id"] == sub_leaf["span_id"]
        assert sub_call["parent_span_id"] is None

    def test_otlp_json_export(self, ray_start_isolated, tmp_path):
        import json

        import ray_tpu
        from ray_tpu.util import tracing

        @ray_tpu.remote
        def f():
            return 1

        tracing.enable()
        try:
            ray_tpu.get(f.remote(), timeout=60)
        finally:
            tracing.disable()
        out = tracing.export_otlp_json(str(tmp_path / "trace.json"))
        doc = json.load(open(out))
        spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert spans and all(s["traceId"] and s["spanId"] for s in spans)

    def test_tracing_disabled_adds_no_context(self, ray_start_isolated):
        import ray_tpu
        from ray_tpu.util import tracing

        @ray_tpu.remote
        def f():
            return 1

        assert not tracing.is_enabled()
        ray_tpu.get(f.remote(), timeout=60)
        assert tracing.list_traces() == []


class TestOtlpMetricsExport:
    def test_export_shape(self, ray_start, tmp_path):
        """OTLP/JSON resourceMetrics export (reference: the OTel metrics
        exporter behind open_telemetry_metric_recorder.h)."""
        import json

        from ray_tpu.util import metrics as m
        c = m.Counter("otlp_test_total", "d", tag_keys=("k",))
        c.inc(3, tags={"k": "a"})
        g = m.Gauge("otlp_test_gauge")
        g.set(7.5)
        h = m.Histogram("otlp_test_hist", boundaries=[1.0, 10.0])
        h.observe(0.5)
        h.observe(5.0)
        h.observe(50.0)

        path = m.export_otlp_json(str(tmp_path / "metrics.json"))
        doc = json.load(open(path))
        scope = doc["resourceMetrics"][0]["scopeMetrics"][0]
        by_name = {mm["name"]: mm for mm in scope["metrics"]}
        s = by_name["otlp_test_total"]["sum"]
        assert s["isMonotonic"] and s["dataPoints"][0]["asDouble"] == 3.0
        assert by_name["otlp_test_gauge"]["gauge"]["dataPoints"][0][
            "asDouble"] == 7.5
        hist = by_name["otlp_test_hist"]["histogram"]["dataPoints"][0]
        assert hist["count"] == "3" and hist["sum"] == 55.5
        assert hist["explicitBounds"] == [1.0, 10.0]
        assert hist["bucketCounts"] == ["1", "1", "1"]
