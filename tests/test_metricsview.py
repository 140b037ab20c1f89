"""Metrics time-series backplane (ray_tpu.metricsview).

Store downsampling/eviction, reset-aware windowed queries, histogram
window percentiles, the SLO dual-window burn-rate lifecycle, windowed
OTLP export, the unconditional terminal worker flush, and the live
query -> alert -> bundle loop end to end (state API, job-server REST,
`ray-tpu metrics`/`ray-tpu alerts` CLIs, flight-recorder bundle).

Reference analogs: Prometheus TSDB head-block semantics (PromQL
``increase``/``histogram_quantile``) + the SRE-workbook multiwindow
multi-burn-rate alerting pattern.
"""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu._private.config import Config
from ray_tpu.metricsview import (AGGS, MetricsView, SeriesStore, SloEngine,
                                 SloObjective, parse_quantile,
                                 parse_tag_args, validate_agg)
from ray_tpu.metricsview.slo import FIRING_GAUGE, TRANSITIONS_TOTAL
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import telemetry


LAT = "ray_tpu_serve_request_latency_seconds"
BOUNDS = (0.01, 0.1, 1.0)


def _hist(counts, total_sum, count):
    """Cumulative store-shape histogram value (counts include +Inf)."""
    return {"counts": list(counts), "sum": total_sum, "count": count}


class TestSeriesStore:
    def test_downsample_one_point_per_interval(self):
        store = SeriesStore(interval_s=1.0, max_points=10)
        store.append("g", {}, "gauge", 1.0, 0.1)
        store.append("g", {}, "gauge", 2.0, 0.9)   # same bucket: replaces
        store.append("g", {}, "gauge", 3.0, 1.2)   # next bucket
        hist = store.history("g", window_s=10.0, now=2.0)
        pts = hist["series"][0]["points"]
        assert [v for _age, v in pts] == [2.0, 3.0]
        assert store.stats()["points_total"] == 2

    def test_ring_eviction_accounts_drops(self):
        store = SeriesStore(interval_s=1.0, max_points=3)
        for i in range(6):
            store.append("c", {}, "counter", float(i), float(i))
        st = store.stats()
        assert st["live_points"] == 3
        assert st["points_total"] == 6
        assert st["dropped_total"] == 3
        # Retention window slides: only the newest 3 points answer.
        out = store.query("c", window_s=100.0, agg="last", now=6.0)
        assert out["value"] == 5.0
        assert out["points"] == 3

    def test_max_series_cap_rejects_new_series(self):
        store = SeriesStore(interval_s=1.0, max_points=4, max_series=2)
        store.append("a", {"k": "1"}, "gauge", 1.0, 0.0)
        store.append("a", {"k": "2"}, "gauge", 2.0, 0.0)
        store.append("a", {"k": "3"}, "gauge", 3.0, 0.0)  # over cap
        st = store.stats()
        assert st["series"] == 2
        assert st["dropped_total"] == 1
        # Existing series keep ingesting.
        store.append("a", {"k": "1"}, "gauge", 9.0, 1.5)
        assert store.query("a", 10.0, "last", tags={"k": "1"},
                           now=2.0)["value"] == 9.0

    def test_counter_delta_measures_from_last_reset(self):
        store = SeriesStore(interval_s=1.0, max_points=16)
        for t, v in enumerate([0.0, 5.0, 10.0, 2.0, 4.0]):
            store.append("c", {}, "counter", v, float(t))
        # Reset at t=3 (10 -> 2): the window's increase is 4 - 2.
        assert store.query("c", 10.0, "delta", now=4.0)["value"] == 2.0
        # A single post-reset point alone yields no delta (zero-width).
        store2 = SeriesStore(interval_s=1.0, max_points=16)
        store2.append("c", {}, "counter", 50.0, 0.0)
        store2.append("c", {}, "counter", 1.0, 1.0)
        assert store2.query("c", 10.0, "delta", now=1.0)["value"] == 0.0

    def test_gauge_delta_is_signed(self):
        store = SeriesStore(interval_s=1.0, max_points=16)
        store.append("g", {}, "gauge", 10.0, 0.0)
        store.append("g", {}, "gauge", 4.0, 3.0)
        assert store.query("g", 10.0, "delta", now=3.0)["value"] == -6.0

    def test_baseline_point_before_window_extends_delta(self):
        """PromQL range-vector semantics: the last pre-window point is
        the delta baseline, so a sparse series still answers."""
        store = SeriesStore(interval_s=1.0, max_points=16)
        store.append("c", {}, "counter", 100.0, 0.0)
        store.append("c", {}, "counter", 160.0, 50.0)
        out = store.query("c", 20.0, "delta", now=55.0)
        assert out["value"] == 60.0
        assert out["points"] == 1  # only one point IN the window

    def test_scalar_aggs(self):
        store = SeriesStore(interval_s=1.0, max_points=16)
        for t, v in enumerate([1.0, 3.0, 2.0]):
            store.append("g", {}, "gauge", v, float(t))
        q = lambda agg: store.query("g", 10.0, agg, now=2.0)["value"]
        assert q("avg") == pytest.approx(2.0)
        assert q("min") == 1.0
        assert q("max") == 3.0
        assert q("last") == 2.0

    def test_histogram_window_percentile_from_bucket_deltas(self):
        """p99 answers from the WINDOW's observations: the pre-window
        cumulative state cancels out of the bucket delta."""
        store = SeriesStore(interval_s=1.0, max_points=64)
        # 100 old observations, all fast (cumulative at t=0).
        store.append("h", {}, "histogram",
                     _hist([100, 100, 100, 100], 0.5, 100), 0.0,
                     bounds=BOUNDS)
        # Window adds 90 fast + 10 slow (between 0.1 and 1.0).
        store.append("h", {}, "histogram",
                     _hist([190, 190, 200, 200], 6.0, 200), 100.0,
                     bounds=BOUNDS)
        p99 = store.query("h", 60.0, "p99", now=100.0)["value"]
        # Window distribution: 90 in (0, 0.01], 10 in (0.1, 1.0].
        assert 0.1 < p99 <= 1.0
        p50 = store.query("h", 60.0, "p50", now=100.0)["value"]
        assert p50 <= 0.01
        # Window avg uses the sum/count delta, not lifetime.
        avg = store.query("h", 60.0, "avg", now=100.0)["value"]
        assert avg == pytest.approx(5.5 / 100)
        assert store.query("h", 60.0, "delta", now=100.0)["value"] == 100.0

    def test_histogram_restart_exports_post_restart_state(self):
        store = SeriesStore(interval_s=1.0, max_points=64)
        store.append("h", {}, "histogram",
                     _hist([50, 60, 70, 70], 9.0, 70), 0.0, bounds=BOUNDS)
        # Count shrank: source restarted; window = post-restart state.
        store.append("h", {}, "histogram",
                     _hist([5, 6, 7, 7], 0.9, 7), 10.0, bounds=BOUNDS)
        assert store.query("h", 60.0, "delta", now=10.0)["value"] == 7.0

    def test_multi_series_combination_rules(self):
        store = SeriesStore(interval_s=1.0, max_points=16)
        for w, incr in (("a", 10.0), ("b", 30.0)):
            store.append("c", {"w": w}, "counter", 0.0, 0.0)
            store.append("c", {"w": w}, "counter", incr, 10.0)
        # Counter deltas SUM across series (cluster total)...
        assert store.query("c", 20.0, "delta", now=10.0)["value"] == 40.0
        # ...and a tag filter narrows to one series.
        assert store.query("c", 20.0, "delta", tags={"w": "a"},
                           now=10.0)["value"] == 10.0
        # Gauges average; min/max take extremes.
        for w, v in (("a", 2.0), ("b", 6.0)):
            store.append("g", {"w": w}, "gauge", v, 0.0)
        assert store.query("g", 10.0, "avg", now=1.0)["value"] == 4.0
        assert store.query("g", 10.0, "min", now=1.0)["value"] == 2.0
        assert store.query("g", 10.0, "max", now=1.0)["value"] == 6.0

    def test_history_sparkline_shape_and_cap(self):
        store = SeriesStore(interval_s=1.0, max_points=600)
        for i in range(100):
            store.append("g", {}, "gauge", float(i), float(i))
        out = store.history("g", window_s=1000.0, now=100.0, max_points=10)
        pts = out["series"][0]["points"]
        assert len(pts) <= 11  # strided + preserved tail
        assert pts[-1][1] == 99.0
        ages = [a for a, _v in pts]
        assert ages == sorted(ages, reverse=True)  # oldest first

    def test_history_histogram_renders_interval_average(self):
        store = SeriesStore(interval_s=1.0, max_points=16)
        store.append("h", {}, "histogram", _hist([10, 10, 10, 10], 0.1, 10),
                     0.0, bounds=BOUNDS)
        store.append("h", {}, "histogram", _hist([10, 10, 20, 20], 5.1, 20),
                     1.0, bounds=BOUNDS)
        pts = store.history("h", 10.0, now=1.0)["series"][0]["points"]
        # Second row: 10 new observations totalling 5.0s -> 0.5 avg.
        assert pts[-1][1] == pytest.approx(0.5)

    def test_window_rows_for_delta_export(self):
        store = SeriesStore(interval_s=1.0, max_points=16)
        store.append("c", {}, "counter", 5.0, 0.0)
        store.append("c", {}, "counter", 25.0, 10.0)
        store.append("g", {}, "gauge", 7.0, 10.0)
        store.append("h", {}, "histogram", _hist([1, 1, 1, 1], 0.001, 1),
                     0.0, bounds=BOUNDS)
        store.append("h", {}, "histogram", _hist([1, 1, 101, 101], 30.0, 101),
                     10.0, bounds=BOUNDS)
        rows = {name: (mtype, value, bounds) for name, _t, mtype, value,
                bounds in store.window_rows(8.0, now=10.0)}
        assert rows["c"][1] == 20.0           # window increase
        assert rows["g"][1] == 7.0            # latest value
        per = rows["h"][1]["per"]
        assert per == [0.0, 0.0, 100.0, 0.0]  # window's per-bucket delta
        assert rows["h"][1]["count"] == 100
        assert rows["h"][2] == list(BOUNDS)

    def test_validate_agg_and_quantile_parse(self):
        assert all(validate_agg(a) for a in AGGS)
        assert validate_agg("p99") and validate_agg("p99.9")
        assert not validate_agg("sum") and not validate_agg("p0")
        assert parse_quantile("p75") == pytest.approx(0.75)
        assert parse_quantile("avg") is None

    def test_parse_tag_args(self):
        assert parse_tag_args(("a=1", "b = x ")) == {"a": "1", "b": "x"}
        assert parse_tag_args(()) is None
        with pytest.raises(ValueError):
            parse_tag_args(("nokey",))


class TestSloEngine:
    def _store_with_latency(self):
        store = SeriesStore(interval_s=1.0, max_points=600)
        # Healthy baseline: all observations fast.
        store.append(LAT, {}, "histogram", _hist([100, 100, 100, 100],
                                                 0.5, 100), 0.0,
                     bounds=BOUNDS)
        store.append(LAT, {}, "histogram", _hist([200, 200, 200, 200],
                                                 1.0, 200), 10.0,
                     bounds=BOUNDS)
        return store

    def _objective(self, **kw):
        base = dict(name="serve-p99", metric=LAT, agg="p99", op="<",
                    threshold=0.5, fast_window_s=30.0, slow_window_s=60.0,
                    pending_for_s=0.0, cooldown_s=20.0)
        base.update(kw)
        return SloObjective(**base)

    def test_objective_validation(self):
        with pytest.raises(ValueError):
            self._objective(op="==")
        with pytest.raises(ValueError):
            self._objective(agg="p200")
        with pytest.raises(ValueError):
            self._objective(fast_window_s=60.0, slow_window_s=30.0)
        spec = self._objective().spec()
        assert SloObjective.from_spec(spec).spec() == spec
        # from_spec drops unknown keys (forward-compatible payloads).
        spec["bogus"] = 1
        assert SloObjective.from_spec(spec).name == "serve-p99"

    def test_full_alert_lifecycle(self):
        """ok -> pending -> firing -> resolved -> ok, each edge driven
        by logical-time evaluation over real bucket-delta burn rates."""
        store = self._store_with_latency()
        events = []
        eng = SloEngine(store, event_sink=lambda st, e: events.append((st, e)))
        eng.set_objectives([self._objective()])

        assert eng.evaluate(now=10.0) == []   # healthy: stays ok
        st = eng.status(now=10.0)["objectives"][0]
        assert st["state"] == "ok" and st["burn_fast"] == 0.0

        # Latency spike: 100 new observations, all over 0.5s.
        store.append(LAT, {}, "histogram", _hist([200, 200, 200, 300],
                                                 250.0, 300), 20.0,
                     bounds=BOUNDS)
        fired = eng.evaluate(now=20.0)
        assert [t["to"] for t in fired] == ["pending"]
        assert fired[0]["burn_fast"] >= 1.0
        # Slow window burns too -> firing on the next pass.
        fired = eng.evaluate(now=21.0)
        assert [t["to"] for t in fired] == ["firing"]
        assert eng.status(now=21.0)["firing"] == 1

        # Recovery: fresh fast observations push the spike out of the
        # fast window (baseline extends from the spike point).
        store.append(LAT, {}, "histogram", _hist([400, 400, 400, 500],
                                                 251.0, 500), 60.0,
                     bounds=BOUNDS)
        fired = eng.evaluate(now=60.0)
        assert [t["to"] for t in fired] == ["resolved"]
        # Cooldown holds resolved...
        assert eng.evaluate(now=70.0) == []
        # ...then decays to ok.
        fired = eng.evaluate(now=81.0)
        assert [t["to"] for t in fired] == ["ok"]

        # Every transition hit the export sink with the objective's
        # identity and burn rates attached.
        assert [e["to"] for _st, e in events] == \
            ["pending", "firing", "resolved", "ok"]
        assert all(st == "EXPORT_ALERT" for st, _e in events)
        assert all(e["objective"] == "serve-p99" for _st, e in events)
        assert all("_t" not in e for _st, e in events)

        # Transition ring renders with ages for `ray-tpu alerts`.
        trans = eng.status(now=90.0)["transitions"]
        assert len(trans) == 4
        assert trans[-1]["age_s"] == pytest.approx(9.0, abs=0.1)

    def test_reburn_during_cooldown_returns_to_firing(self):
        store = self._store_with_latency()
        eng = SloEngine(store)
        eng.set_objectives([self._objective()])
        store.append(LAT, {}, "histogram", _hist([200, 200, 200, 300],
                                                 250.0, 300), 20.0,
                     bounds=BOUNDS)
        eng.evaluate(now=20.0)
        eng.evaluate(now=21.0)
        store.append(LAT, {}, "histogram", _hist([400, 400, 400, 500],
                                                 251.0, 500), 60.0,
                     bounds=BOUNDS)
        eng.evaluate(now=60.0)  # resolved
        # Second spike inside the cooldown: same incident, back to firing
        # without a fresh pending dwell.
        store.append(LAT, {}, "histogram", _hist([400, 400, 400, 700],
                                                 500.0, 700), 70.0,
                     bounds=BOUNDS)
        fired = eng.evaluate(now=70.0)
        assert [t["to"] for t in fired] == ["firing"]

    def test_pending_blip_returns_to_ok(self):
        store = self._store_with_latency()
        eng = SloEngine(store)
        # Long pending dwell: the blip may not fire.
        eng.set_objectives([self._objective(pending_for_s=30.0)])
        store.append(LAT, {}, "histogram", _hist([200, 200, 200, 300],
                                                 250.0, 300), 20.0,
                     bounds=BOUNDS)
        fired = eng.evaluate(now=20.0)
        assert [t["to"] for t in fired] == ["pending"]
        # Dwell not reached; then the fast window recovers.
        assert eng.evaluate(now=25.0) == []
        store.append(LAT, {}, "histogram", _hist([400, 400, 400, 500],
                                                 251.0, 500), 55.0,
                     bounds=BOUNDS)
        fired = eng.evaluate(now=55.0)
        assert [t["to"] for t in fired] == ["ok"]

    def test_scalar_objective_binary_breach(self):
        store = SeriesStore(interval_s=1.0, max_points=64)
        store.append("ray_tpu_train_goodput_ratio", {}, "gauge", 0.9, 0.0)
        eng = SloEngine(store)
        eng.set_objectives([SloObjective(
            name="goodput", metric="ray_tpu_train_goodput_ratio",
            agg="avg", op=">=", threshold=0.5, fast_window_s=10.0,
            slow_window_s=20.0)])
        assert eng.evaluate(now=1.0) == []
        store.append("ray_tpu_train_goodput_ratio", {}, "gauge", 0.1, 15.0)
        fired = eng.evaluate(now=15.0)
        assert [t["to"] for t in fired] == ["pending"]
        st = eng.status(now=15.0)["objectives"][0]
        assert st["burn_fast"] == 1.0  # binary breach, not a ratio

    def test_no_data_objective_stays_ok(self):
        eng = SloEngine(SeriesStore())
        eng.set_objectives([self._objective(metric="ray_tpu_nope")])
        assert eng.evaluate(now=5.0) == []
        st = eng.status(now=5.0)["objectives"][0]
        assert st["state"] == "ok" and st["no_data"] is True

    def test_state_survives_objective_replacement(self):
        store = self._store_with_latency()
        eng = SloEngine(store)
        eng.set_objectives([self._objective()])
        store.append(LAT, {}, "histogram", _hist([200, 200, 200, 300],
                                                 250.0, 300), 20.0,
                     bounds=BOUNDS)
        eng.evaluate(now=20.0)
        eng.evaluate(now=21.0)
        assert eng.status(now=21.0)["firing"] == 1
        # Re-set with the same name (new threshold): state carries over.
        eng.set_objectives([self._objective(threshold=0.4)])
        assert eng.status(now=22.0)["firing"] == 1
        # A different name starts fresh.
        eng.set_objectives([self._objective(name="other")])
        assert eng.status(now=23.0)["firing"] == 0


class TestMetricsViewUnit:
    def test_refresh_throttles_to_interval(self):
        view = MetricsView(interval_s=5.0)
        assert view.refresh(now=100.0) is True
        assert view.refresh(now=101.0) is False   # inside the interval
        assert view.refresh(now=106.0) is True
        assert view.refresh(now=106.5, force=True) is True

    def test_query_rejects_unknown_agg(self):
        view = MetricsView(interval_s=1.0)
        with pytest.raises(ValueError, match="unknown agg"):
            view.query("x", agg="sum")

    def test_bundle_snapshot_caps_series(self):
        view = MetricsView(interval_s=1.0)
        for i in range(8):
            view.store.append(f"s{i}", {}, "gauge", float(i), 0.0)
        snap = view.bundle_snapshot(max_series=3, max_points=5)
        assert len(snap["series"]) == 3
        assert snap["stats"]["series"] == 8


class TestTerminalFlush:
    """Worker-teardown metrics contract: the terminal push is
    UNCONDITIONAL.  The dirty-flag-gated task-done flush has a teardown
    race — a sample recorded after the flag check (teardown hooks,
    executor-shutdown stragglers, atexit-adjacent user code) has no next
    completion to retry on — so shutdown must push regardless."""

    class _FakeWorkerRt:
        class _Id(bytes):
            pass

        def __init__(self):
            self.sent = []
            self.worker_id = self._Id(b"\xab\xcd")

        def send(self, frame):
            self.sent.append(frame)

    @pytest.fixture()
    def worker_rt(self, monkeypatch):
        from ray_tpu._private import runtime as rt_mod
        metrics_mod._reset_for_tests()
        rt = self._FakeWorkerRt()
        monkeypatch.setattr(rt_mod, "current_runtime", lambda: rt)
        monkeypatch.setattr(rt_mod, "driver_runtime", lambda: None)
        yield rt
        metrics_mod._reset_for_tests()

    def test_terminal_flush_pushes_clean_registry(self, worker_rt):
        telemetry.inc("ray_tpu_data_rows_total", 3.0,
                      tags={"operator": "map"})
        # The race's post-state: flag observed clean while the registry
        # holds the sample (recorded between check and exit).
        metrics_mod._dirty = False
        metrics_mod.flush_on_task_done()
        assert worker_rt.sent == []      # gated flush drops it...
        metrics_mod.flush_terminal()
        assert len(worker_rt.sent) == 1  # ...terminal flush does not
        frame = worker_rt.sent[0]
        assert frame.method == "metrics_push"
        source_id, snaps = frame.args
        assert source_id == worker_rt.worker_id.hex()
        rows = [(s["name"], sample)
                for s in snaps for sample in s["samples"]]
        assert any(n == "ray_tpu_data_rows_total" and v == 3.0
                   for n, (_sn, _tags, v) in rows)

    def test_task_done_flush_still_gated_and_retries(self, worker_rt):
        metrics_mod._dirty = False
        metrics_mod.flush_on_task_done()
        assert worker_rt.sent == []  # metric-free task: only a bool check
        telemetry.inc("ray_tpu_data_rows_total", tags={"operator": "map"})
        assert metrics_mod._dirty is True
        metrics_mod.flush_on_task_done()
        assert len(worker_rt.sent) == 1
        assert metrics_mod._dirty is False

    def test_worker_teardown_calls_terminal_flush(self):
        """The recv-loop teardown must use the unconditional flush, not
        the dirty-gated one (the regression this class guards)."""
        import inspect

        from ray_tpu._private import worker as worker_mod
        src = inspect.getsource(worker_mod)
        assert "flush_terminal" in src


class TestOtlpWindowedExport:
    def test_windowed_export_requires_driver(self):
        with pytest.raises(RuntimeError, match="driver runtime"):
            metrics_mod.export_otlp_json("/tmp/_nope.json", window_s=60.0)

    def test_roundtrip_live_and_windowed(self, ray_start_isolated,
                                         tmp_path):
        telemetry.inc("ray_tpu_data_rows_total", 5.0,
                      tags={"operator": "map"})
        telemetry.set_gauge("ray_tpu_serve_replicas", 3.0,
                            tags={"deployment": "d"})
        telemetry.observe(LAT, 0.02, tags={"deployment": "d"})
        telemetry.observe(LAT, 0.7, tags={"deployment": "d"})

        # Live export: cumulative temporality.
        live = tmp_path / "live.json"
        metrics_mod.export_otlp_json(str(live))
        doc = json.loads(live.read_text())
        metrics = {m["name"]: m for m in
                   doc["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]}
        row = metrics["ray_tpu_data_rows_total"]["sum"]
        assert row["isMonotonic"] and row["aggregationTemporality"] == 2
        assert any(p["asDouble"] == 5.0 for p in row["dataPoints"])
        assert metrics["ray_tpu_serve_replicas"]["gauge"]["dataPoints"]
        h = metrics[LAT]["histogram"]
        assert h["aggregationTemporality"] == 2
        hp = h["dataPoints"][0]
        assert int(hp["count"]) == 2
        assert hp["sum"] == pytest.approx(0.72)
        assert len(hp["bucketCounts"]) == len(hp["explicitBounds"]) + 1

        # Windowed export answers from the head store with DELTA
        # temporality.
        from ray_tpu._private import runtime as rt_mod
        rt_mod.driver_runtime().metricsview.refresh(force=True)
        win = tmp_path / "window.json"
        metrics_mod.export_otlp_json(str(win), window_s=120.0)
        doc = json.loads(win.read_text())
        metrics = {m["name"]: m for m in
                   doc["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]}
        assert metrics["ray_tpu_data_rows_total"]["sum"][
            "aggregationTemporality"] == 1
        h = metrics[LAT]["histogram"]
        assert h["aggregationTemporality"] == 1
        assert int(h["dataPoints"][0]["count"]) == 2


@pytest.fixture()
def metricsview_cluster():
    """Cluster with a near-continuous ingest interval so consecutive
    API reads drive distinct SLO evaluation passes."""
    prev = Config.get("metricsview_interval_s")
    Config.set("metricsview_interval_s", 0.05)
    metrics_mod._reset_for_tests()  # drop prior tests' driver-side samples
    rt = ray_tpu.init(num_cpus=2)
    yield rt
    ray_tpu.shutdown()
    Config.set("metricsview_interval_s", prev)


class TestLiveBackplane:
    """The acceptance path: live history answers windowed queries, an
    injected latency spike walks one objective through its lifecycle,
    and every surface (state API, REST, CLI, export events, bundle)
    shows it."""

    @pytest.fixture()
    def server(self, metricsview_cluster):
        from ray_tpu.job_submission.manager import JobManager
        from ray_tpu.job_submission.server import JobServer
        server = JobServer(JobManager(), port=0)
        server.rt = metricsview_cluster
        yield server
        server.stop()

    def _cli(self, args):
        from click.testing import CliRunner

        from ray_tpu.scripts.cli import cli
        return CliRunner().invoke(cli, args)

    def test_query_alert_lifecycle_all_surfaces(self, server, tmp_path):
        from ray_tpu.util import state as rstate
        rt = server.rt
        addr = server.address

        # -- seed healthy latency history ------------------------------
        for _ in range(20):
            telemetry.observe(LAT, 0.01, tags={"deployment": "d"})
        out = rstate.metrics_query(LAT, window_s=120.0, agg="p99")
        assert out["value"] is not None and out["value"] < 0.5
        assert out["series"] >= 1

        # -- objective: p99 < 0.5 with a short fast window -------------
        assert rstate.slo_set([{
            "name": "serve-p99", "metric": LAT, "agg": "p99",
            "op": "<", "threshold": 0.5, "fast_window_s": 2.0,
            "slow_window_s": 4.0, "pending_for_s": 0.0,
            "cooldown_s": 0.2}]) == 1
        assert rstate.slo_list()[0]["name"] == "serve-p99"
        st = rstate.alerts()
        assert st["objectives"][0]["state"] == "ok"

        # -- inject the spike ------------------------------------------
        for _ in range(50):
            telemetry.observe(LAT, 2.0, tags={"deployment": "d"})
        saw = set()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = rstate.alerts()
            saw.add(st["objectives"][0]["state"])
            if "firing" in saw:
                break
            time.sleep(0.1)
        assert "firing" in saw, st

        # p99 over the window now reports the spike.
        spike = rstate.metrics_query(LAT, window_s=120.0, agg="p99")
        assert spike["value"] > 0.5

        # -- recovery: spike ages out of the 2 s fast window -----------
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            telemetry.observe(LAT, 0.01, tags={"deployment": "d"})
            st = rstate.alerts()
            saw.add(st["objectives"][0]["state"])
            if {"resolved", "ok"} & saw:
                break
            time.sleep(0.25)
        assert {"resolved", "ok"} & saw, st
        trans = [t["to"] for t in st["transitions"]]
        assert "pending" in trans and "firing" in trans

        # -- history + series surfaces ---------------------------------
        hist = rstate.metrics_history(LAT, window_s=300.0)
        assert hist["series"] and hist["series"][0]["points"]
        assert LAT in rstate.metrics_series()

        # -- REST surface (addr already carries the scheme) ------------
        import urllib.request
        with urllib.request.urlopen(
                f"{addr}/api/cluster/metrics/query?name={LAT}"
                f"&window=120&agg=p99") as r:
            doc = json.loads(r.read())
        assert doc["value"] > 0.5
        with urllib.request.urlopen(f"{addr}/api/cluster/alerts") as r:
            doc = json.loads(r.read())
        assert doc["objectives"][0]["objective"] == "serve-p99"
        assert any(t["to"] == "firing" for t in doc["transitions"])
        with urllib.request.urlopen(
                f"{addr}/api/cluster/metrics/history?name={LAT}") as r:
            assert json.loads(r.read())["series"]

        # -- CLI surfaces ----------------------------------------------
        r = self._cli(["metrics", "query", "--address", addr,
                       "--window", "120", "--agg", "p99", LAT])
        assert r.exit_code == 0, r.output
        assert "p99 over 120s" in r.output
        r = self._cli(["metrics", "history", "--address", addr, LAT])
        assert r.exit_code == 0, r.output
        r = self._cli(["metrics", "series", "--address", addr])
        assert r.exit_code == 0 and LAT in r.output
        r = self._cli(["alerts", "--address", addr])
        assert r.exit_code == 0, r.output
        assert "serve-p99" in r.output
        assert "firing" in r.output  # transition log carries the edge
        r = self._cli(["slo", "list", "--address", addr])
        assert r.exit_code == 0 and "serve-p99" in r.output
        spec_file = tmp_path / "slo.json"
        spec_file.write_text(json.dumps([{
            "name": "second", "metric": LAT, "agg": "avg",
            "op": "<", "threshold": 10.0}]))
        r = self._cli(["slo", "set", "--address", addr, str(spec_file)])
        assert r.exit_code == 0, r.output
        assert "registered 1 objective" in r.output

        # -- export-event stream + alert telemetry ---------------------
        with open(rt.export_events._path) as f:
            alert_events = [json.loads(line) for line in f
                            if '"EXPORT_ALERT"' in line]
        assert any(e["to"] == "firing" and e["objective"] == "serve-p99"
                   for e in alert_events)
        prom = metrics_mod.prometheus_text()
        assert TRANSITIONS_TOTAL in prom
        assert FIRING_GAUGE in prom
        assert "ray_tpu_metricsview_points_total" in prom

        # -- flight-recorder bundle carries the alert story ------------
        bundle = rstate.debug_dump("metricsview-test")
        with open(os.path.join(bundle, "alerts.json")) as f:
            alerts_doc = json.load(f)
        assert alerts_doc["objectives"]
        assert any(t["to"] == "firing" for t in alerts_doc["transitions"])
        with open(os.path.join(bundle, "metrics_history.json")) as f:
            hist_doc = json.load(f)
        assert LAT in hist_doc["series"]
        with open(os.path.join(bundle, "manifest.json")) as f:
            manifest = json.load(f)
        assert {"alerts.json", "metrics_history.json"} <= \
            set(manifest["contents"])

    def test_dashboard_http_surface(self, metricsview_cluster):
        import urllib.error
        import urllib.request

        from ray_tpu.dashboard.server import DashboardServer
        telemetry.observe(LAT, 0.05, tags={"deployment": "d"})
        dash = DashboardServer(metricsview_cluster, port=0)
        try:
            base = f"http://127.0.0.1:{dash.port}"
            with urllib.request.urlopen(
                    f"{base}/api/metrics/history?name={LAT}") as r:
                doc = json.loads(r.read())
            assert doc["name"] == LAT
            with urllib.request.urlopen(
                    f"{base}/api/metrics/query?name={LAT}&window=60"
                    f"&agg=avg") as r:
                assert "value" in json.loads(r.read())
            with urllib.request.urlopen(f"{base}/api/alerts") as r:
                assert "objectives" in json.loads(r.read())
            # Missing ?name= and bad aggs are 400s, not 500s.
            for bad in ("/api/metrics/history",
                        f"/api/metrics/query?name={LAT}&agg=bogus"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(base + bad)
                assert ei.value.code == 400
        finally:
            dash.stop()


class TestGoodputPolicyOnBackplane:
    """Satellite: the autoscaler's sag window rides the shared store."""

    def test_policy_window_is_a_series_store(self):
        from ray_tpu.autoscaler import (GoodputAutoscalePolicy,
                                        GoodputPolicyConfig)
        pol = GoodputAutoscalePolicy(GoodputPolicyConfig(window_s=30.0))
        assert isinstance(pol._window, SeriesStore)
        pol.observe_goodput({"productive_s": 1.0, "total_s": 10.0}, now=0.0)
        pol.observe_goodput({"productive_s": 2.0, "total_s": 20.0}, now=5.0)
        assert pol.windowed_goodput() == pytest.approx(0.1)
        # Tracker restart: reset-aware delta -> no phantom window.
        pol.observe_goodput({"productive_s": 0.5, "total_s": 1.0}, now=10.0)
        assert pol.windowed_goodput() is None
