"""Live diagnostics: cluster stack capture, hang/straggler watchdog,
flight recorder, export events, monotonic span timing.

Reference analogs: `ray stack` (python/ray/scripts/scripts.py), the
dashboard's hang investigation, and the GCS task-event history a
postmortem pulls (gcs_task_manager.h).
"""

import glob
import json
import os
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import state as state_api


def _read_export_events(rt):
    path = os.path.join(rt.session_logs_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _wait_for(predicate, timeout=15.0, period=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(period)
    return predicate()


@ray_tpu.remote
def stack_probe_sleeper(flag_path, marker_path):
    open(marker_path, "w").close()
    import time as _t
    while not os.path.exists(flag_path):
        _t.sleep(0.05)
    return "done"


class TestStackCapture:
    def test_list_stacks_names_running_tasks(self, ray_start, tmp_path):
        """Acceptance: >=2 live workers each contribute a stack naming
        the running task's function."""
        flag = str(tmp_path / "release")
        markers = [str(tmp_path / f"m{i}") for i in range(2)]
        refs = [stack_probe_sleeper.remote(flag, m) for m in markers]
        assert _wait_for(
            lambda: all(os.path.exists(m) for m in markers), 30), \
            "probe tasks never started"

        dump = state_api.stack_dump(timeout_s=10.0)
        try:
            assert dump["unresponsive"] == []
            stacks = dump["stacks"]
            # Driver record present and marked.
            assert any(r.get("is_driver") for r in stacks)
            workers_with_probe = set()
            for rec in stacks:
                for th in rec["threads"]:
                    in_frames = any("stack_probe_sleeper" in f
                                    for f in th["frames"])
                    if in_frames:
                        workers_with_probe.add(rec["worker_id"])
                        # The thread is annotated with the task identity,
                        # not just the frames.
                        assert th["task_name"] == "stack_probe_sleeper"
                        assert th["task_id"]
                        assert rec["pid"] > 0
            assert len(workers_with_probe) >= 2, (
                f"expected >=2 workers running the probe, got "
                f"{workers_with_probe}")
            # list_stacks is the stacks list of the same capture.
            assert isinstance(state_api.list_stacks(timeout_s=5.0), list)
        finally:
            open(flag, "w").close()
        assert ray_tpu.get(refs, timeout=60) == ["done", "done"]

    def test_stack_dump_from_inside_a_task(self, ray_start):
        """The ctl verb is blocking-safe when invoked from a worker: the
        head must run it off the poller thread that routes the replies
        (deadlock regression guard)."""
        @ray_tpu.remote
        def nested():
            from ray_tpu.util import state
            return len(state.list_stacks(timeout_s=5.0))

        # Driver record + at least the calling worker itself.
        assert ray_tpu.get(nested.remote(), timeout=60) >= 2

    def test_format_stack_dump_renders(self, ray_start):
        from ray_tpu._private.diagnostics import format_stack_dump
        dump = state_api.stack_dump(timeout_s=5.0)
        txt = format_stack_dump(dump)
        assert "cluster stack dump" in txt
        assert "driver" in txt


class TestFlightRecorder:
    def test_debug_dump_writes_bundle(self, ray_start):
        ray_tpu.get(ray_tpu.put(1))  # some state to snapshot
        path = state_api.debug_dump("unit_test_reason")
        assert os.path.isdir(path)
        names = set(os.listdir(path))
        assert {"stacks.json", "task_events.json", "metrics.prom",
                "manifest.json"} <= names
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["reason"] == "unit_test_reason"
        assert set(manifest["contents"]) <= names | {"manifest.json"}
        stacks = json.load(open(os.path.join(path, "stacks.json")))
        assert stacks["stacks"], "bundle must embed the stack capture"
        # The bundle lands under the session's debug dir.
        from ray_tpu._private.runtime import driver_runtime
        assert path.startswith(
            os.path.join(driver_runtime().session_dir, "debug"))


class TestPointLookups:
    def test_get_task_filter_pushdown(self, ray_start):
        @ray_tpu.remote
        def lookup_me(x):
            return x

        ref = lookup_me.remote(7)
        assert ray_tpu.get(ref) == 7
        time.sleep(0.1)
        tasks = [t for t in state_api.list_tasks()
                 if t["name"].startswith("lookup_me")]
        assert tasks
        tid = tasks[-1]["task_id"]
        got = state_api.get_task(tid)
        assert got is not None and got["task_id"] == tid
        assert state_api.get_task("ffff" * 8) is None

    def test_get_actor_filter_pushdown(self, ray_start):
        @ray_tpu.remote
        class Pointed:
            def ping(self):
                return 1

        h = Pointed.remote()
        assert ray_tpu.get(h.ping.remote()) == 1
        mine = [a for a in state_api.list_actors()
                if a["class_name"] == "Pointed"]
        assert mine
        aid = mine[-1]["actor_id"]
        got = state_api.get_actor(aid)
        assert got is not None and got["actor_id"] == aid
        assert got["class_name"] == "Pointed"
        assert state_api.get_actor("eeee" * 4) is None

    def test_server_side_actor_filter(self, ray_start):
        """The equality filter is applied in the control plane, not by a
        client-side scan."""
        from ray_tpu._private.api import _control
        rows = _control("list_actors", {"state": "ALIVE"})
        assert all(r["state"] == "ALIVE" for r in rows)
        assert _control("list_actors", {"actor_id": "nope"}) == []


class TestWatchdogUnit:
    """Detection logic without a cluster (no bundles, no KV)."""

    def _wd(self, **kw):
        from ray_tpu.train.watchdog import TrainWatchdog, WatchdogConfig
        kw.setdefault("write_bundle", False)
        kw.setdefault("capture_stacks", False)
        return TrainWatchdog("unit_run", WatchdogConfig(**kw))

    def test_straggler_once_per_incident_and_rearm(self):
        wd = self._wd(straggler_multiple=2.0, min_samples=1)
        t = 100.0
        # Two healthy ranks at 1s/step; rank 2 at 5s/step.
        for step in range(1, 5):
            for rank in (0, 1):
                wd.note_report(rank, t + step * 1.0)
        wd.note_report(2, t)
        wd.note_report(2, t + 5.0)
        assert wd.straggler_count == 1
        wd.note_report(2, t + 10.0)  # still slow: same incident
        assert wd.straggler_count == 1
        wd.note_report(2, t + 11.0)  # recovered: re-arm
        wd.note_report(2, t + 16.0)  # slow again: new incident
        assert wd.straggler_count == 2
        assert wd.last_verdict["status"] == "straggler"

    def test_incarnation_change_resets_interval_baseline(self):
        """A restarted worker's monotonic clock has a different base
        (possibly a different host): stamps across incarnations must
        never be differenced — neither a multi-day bogus interval (false
        straggler) nor a clamped 0.0 that drags the median down."""
        wd = self._wd(straggler_multiple=2.0, min_samples=1)
        # Healthy peers: 1s/step baseline.
        for step in range(1, 5):
            for rank in (0, 1):
                wd.note_report(rank, 0.0, report_mono=100.0 + step,
                               incarnation="peer")
        # Rank 2, incarnation A, huge monotonic base (long-lived host).
        wd.note_report(2, 0.0, report_mono=9_000_000.0, incarnation="a")
        wd.note_report(2, 0.0, report_mono=9_000_001.0, incarnation="a")
        assert wd.straggler_count == 0
        # Restart lands on a freshly booted host: tiny monotonic base.
        # The cross-incarnation delta (~ -9e6 or +9e6) must be dropped.
        wd.note_report(2, 0.0, report_mono=5.0, incarnation="b")
        assert wd.straggler_count == 0
        assert len(wd._ranks[2].intervals) == 0
        # Intervals within the new incarnation count normally again.
        wd.note_report(2, 0.0, report_mono=6.0, incarnation="b")
        assert list(wd._ranks[2].intervals) == [1.0]

    def test_single_rank_has_no_peer_baseline(self):
        wd = self._wd(straggler_multiple=2.0, min_samples=1)
        for i in range(5):
            wd.note_report(0, 100.0 + i * 3.0)
        assert wd.straggler_count == 0

    def test_hang_detected_and_done_rank_exempt(self):
        wd = self._wd(hang_deadline_s=0.3, poll_interval_s=0.05)
        wd.start()
        try:
            wd.note_report(0, time.time())
            wd.note_report(1, time.time())
            wd.note_done(1)  # finished rank: silence is legitimate
            assert _wait_for(lambda: wd.hang_count >= 1, timeout=5)
            assert wd.hang_count == 1  # only rank 0
            assert wd.last_verdict["status"] == "hang"
            assert wd.last_verdict["rank"] == 0
            # A fresh report recovers the rank and re-arms detection.
            wd.note_report(0, time.time())
            assert not wd._ranks[0].hung
        finally:
            wd.stop()

    def test_never_reported_rank_is_not_hung(self):
        """Hang detection starts after a rank's FIRST report, so an
        init/compile window cannot trip it."""
        wd = self._wd(hang_deadline_s=0.1, poll_interval_s=0.05)
        wd.start()
        try:
            time.sleep(0.4)
            assert wd.hang_count == 0
        finally:
            wd.stop()


class TestMonotonicSpans:
    """NTP steps must not produce negative/garbage span durations: the
    wall clock anchors a span's position, the monotonic clock measures
    its length."""

    def _with_wall_clock_jump(self, enter_exit_pair, jump_s=-3600.0):
        import time as real_time
        enter, exit_ = enter_exit_pair
        enter()
        real_time.sleep(0.02)
        orig = real_time.time
        real_time.time = lambda: orig() + jump_s
        try:
            exit_()
        finally:
            real_time.time = orig

    # profile_span's case: tests/test_span_recorder.py (both spellings).

    def test_tracing_task_span_survives_clock_step(self, ray_start):
        from ray_tpu.util import tracing
        tp = f"00-{'ab' * 16}-{'cd' * 8}-01"
        span = tracing.task_span(tp, "ntp_probe_trace", "t" * 8)
        self._with_wall_clock_jump(
            (span.__enter__,
             lambda: span.__exit__(None, None, None)))
        from ray_tpu._private.api import _control
        spans = [s for s in _control("get_trace_spans", "ab" * 16)
                 if s["name"] == "execute ntp_probe_trace"]
        assert spans
        assert spans[0]["end_s"] >= spans[0]["start_s"]
        assert spans[0]["end_s"] - spans[0]["start_s"] < 60


# -- isolated-runtime tests below: ray_start_isolated tears the
# (shared) global runtime down, so every test that relies on the
# module-scoped ray_start fixture must run BEFORE this point. ----


class TestExportEvents:
    def test_task_failure_appends_export_record(self, ray_start_isolated):
        rt = ray_start_isolated

        @ray_tpu.remote
        def boom():
            raise RuntimeError("export-me")

        with pytest.raises(Exception):
            ray_tpu.get(boom.remote(), timeout=60)

        recs = _wait_for(lambda: [
            r for r in _read_export_events(rt)
            if r["source_type"] == "EXPORT_TASK"
            and r.get("state") == "FAILED"])
        assert recs, "no EXPORT_TASK FAILED record in events.jsonl"
        assert any("export-me" in (r.get("error_message") or "")
                   for r in recs)
        for r in recs:
            assert "timestamp" in r and r.get("task_id")

    def test_worker_death_appends_export_record_and_bundle(
            self, ray_start_isolated, tmp_path):
        rt = ray_start_isolated

        @ray_tpu.remote
        class Sleeper:
            def mark_and_sleep(self, marker):
                open(marker, "w").close()
                import time as _t
                _t.sleep(60)

        a = Sleeper.remote()
        marker = str(tmp_path / "started")
        ref = a.mark_and_sleep.remote(marker)
        assert _wait_for(lambda: os.path.exists(marker), 30), \
            "actor method never started"
        ray_tpu.kill(a)  # dies WHILE running -> unexpected death
        with pytest.raises(Exception):
            ray_tpu.get(ref, timeout=60)

        recs = _wait_for(lambda: [
            r for r in _read_export_events(rt)
            if r["source_type"] == "EXPORT_WORKER"
            and r.get("state") == "DEAD"
            and r.get("num_running_tasks", 0) > 0])
        assert recs, "no EXPORT_WORKER DEAD record for a busy worker"
        assert recs[-1].get("worker_id")
        # The unexpected death also trips the (rate-limited) flight
        # recorder: a bundle appears under <session>/debug/.  The bundle
        # is written on a background thread; the manifest lands last.
        manifests = _wait_for(lambda: glob.glob(os.path.join(
            rt.session_dir, "debug", "*worker_death*", "manifest.json")))
        assert manifests, "no worker-death flight-recorder bundle"
        names = set(os.listdir(os.path.dirname(manifests[0])))
        assert {"task_events.json", "metrics.prom",
                "manifest.json"} <= names


def _chaos_train_fn(config):
    import time as _t

    import ray_tpu.train as train
    rank = train.get_context().get_world_rank()
    if rank == 1:
        # Straggler: ~6x slower steps than the healthy rank.
        for _ in range(4):
            _t.sleep(0.9)
            train.report({"loss": 1.0})
    elif rank == 2:
        # Stall: two quick reports, then silence past the hang deadline.
        for _ in range(2):
            _t.sleep(0.15)
            train.report({"loss": 1.0})
        _t.sleep(3.5)
        train.report({"loss": 1.0})
    else:
        for _ in range(12):
            _t.sleep(0.15)
            train.report({"loss": 1.0})


class TestWatchdogChaos:
    def test_straggler_and_hang_flagged(self, ray_start_isolated,
                                        tmp_path):
        """Acceptance: one slow rank + one stalled rank in a multi-worker
        run -> distinct straggler/hang export events, metric increments,
        and a postmortem bundle with stacks + event tail + metrics +
        goodput."""
        from ray_tpu.train import (JaxTrainer, RunConfig, ScalingConfig,
                                   WatchdogConfig)
        rt = ray_start_isolated
        metrics_mod._reset_for_tests()

        result = JaxTrainer(
            _chaos_train_fn, train_loop_config={},
            scaling_config=ScalingConfig(num_workers=3, num_slices=3),
            run_config=RunConfig(
                name="watchdog_chaos", storage_path=str(tmp_path),
                watchdog=WatchdogConfig(straggler_multiple=3.0,
                                        hang_deadline_s=1.5,
                                        poll_interval_s=0.2,
                                        min_samples=2)),
        ).fit()
        assert result.error is None

        # Distinct verdicts for the injected faults.
        events = [r for r in _read_export_events(rt)
                  if r["source_type"] == "EXPORT_TRAIN_WATCHDOG"]
        kinds = {(r["kind"], r["rank"]) for r in events}
        assert ("straggler", 1) in kinds, kinds
        assert any(k == "hang" for k, _ in kinds), kinds
        hang_ranks = {r for k, r in kinds if k == "hang"}
        assert 2 in hang_ranks, kinds
        straggler_ev = next(r for r in events
                            if r["kind"] == "straggler" and r["rank"] == 1)
        assert straggler_ev["step_seconds"] > \
            straggler_ev["median_step_seconds"]

        # Metric increments on the catalog counters.
        text = metrics_mod.prometheus_text()
        def _value(name):
            for line in text.splitlines():
                if line.startswith(name + " "):
                    return float(line.split()[-1])
            return 0.0
        assert _value("ray_tpu_train_straggler_total") >= 1.0
        assert _value("ray_tpu_train_hang_total") >= 1.0

        # Postmortem bundle: stacks + event tail + metrics + goodput.
        bundles = glob.glob(os.path.join(rt.session_dir, "debug",
                                         "*watchdog*"))
        assert bundles, "watchdog verdicts wrote no bundle"
        complete = [b for b in bundles
                    if {"stacks.json", "events_tail.jsonl", "metrics.prom",
                        "goodput.json", "manifest.json"}
                    <= set(os.listdir(b))]
        assert complete, [sorted(os.listdir(b)) for b in bundles]
        stacks = json.load(open(os.path.join(complete[0], "stacks.json")))
        assert stacks["stacks"]
        goodput = json.load(open(os.path.join(complete[0],
                                              "goodput.json")))
        assert "phases_s" in goodput and goodput["total_s"] > 0

        # The verdict is published for `ray-tpu status`.
        from ray_tpu._private.api import _control
        from ray_tpu.train.watchdog import VERDICT_KV_KEY
        raw = _control("kv_get", VERDICT_KV_KEY)
        assert raw is not None
        verdict = json.loads(raw)
        assert verdict["status"] in ("straggler", "hang")
        assert verdict["straggler_total"] >= 1
