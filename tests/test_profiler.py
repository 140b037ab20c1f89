"""Cluster profiler: on-demand merged capture, recompile detection,
step-phase attribution, span nesting.

Reference analogs: the reference dashboard's py-spy/`ray timeline`
integration and the OpenTelemetry substrate its native layer ships —
here the TPU-native equivalents built in PR 10 (ISSUE 10).
"""

import json
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu import profiler
from ray_tpu.profiler import attribution, recompile
from ray_tpu.util import state as state_api
from ray_tpu.util import telemetry


def _wait_for(predicate, timeout=15.0, period=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(period)
    return predicate()


@ray_tpu.remote
def profiler_probe(flag_path, marker_path):
    open(marker_path, "w").close()
    while not os.path.exists(flag_path):
        sum(i * i for i in range(2000))
    return "done"


class TestLiveCapture:
    def test_two_worker_merged_trace(self, ray_start, tmp_path):
        """Acceptance: a capture on a >=2-worker cluster produces ONE
        merged Chrome-trace JSON whose sample events span both workers
        AND the driver on a common (driver) clock."""
        flag = str(tmp_path / "release")
        markers = [str(tmp_path / f"m{i}") for i in range(2)]
        refs = [profiler_probe.remote(flag, m) for m in markers]
        assert _wait_for(
            lambda: all(os.path.exists(m) for m in markers), 30), \
            "probe tasks never started"
        t0 = time.time()
        try:
            out = state_api.profile(duration_s=1.0)
        finally:
            open(flag, "w").close()
        t1 = time.time()
        assert ray_tpu.get(refs, timeout=60) == ["done", "done"]

        assert out["unresponsive"] == []
        assert len(out["workers"]) >= 2
        # The merged trace landed on disk (atomic publish) and is the
        # same document returned inline.
        assert os.path.isfile(out["path"])
        with open(out["path"]) as f:
            on_disk = json.load(f)
        doc = out["trace"]
        assert on_disk["otherData"]["profile_id"] == \
            doc["otherData"]["profile_id"]

        samples = [e for e in doc["traceEvents"]
                   if e.get("ph") == "X" and e.get("cat") == "sample"]
        pids = {e["pid"] for e in samples}
        worker_pids = {p for p in pids if str(p).startswith("worker:")}
        assert len(worker_pids) >= 2, pids
        assert any(str(p).startswith("driver") for p in pids), pids
        # The busy probe function is visible in the sampled slices.
        assert any("profiler_probe" in str(e.get("name", ""))
                   or any("profiler_probe" in fr for fr in
                          e.get("args", {}).get("stack", ()))
                   for e in samples)

        # Clock alignment: every sample slice sits inside the capture
        # window IN DRIVER TIME (worker events were shifted by their
        # reported clock offset), and per-process offsets are sane for
        # a same-host cluster.
        lo, hi = (t0 - 2.0) * 1e6, (t1 + 2.0) * 1e6
        for e in samples:
            assert lo <= e["ts"] <= hi, e
        procs = [p for p in doc["otherData"]["processes"]
                 if not p.get("error")]
        assert len(procs) >= 3  # driver + 2 workers
        for p in procs:
            assert abs(p["clock_offset_s"]) < 5.0, p
            assert p["num_samples"] > 5, p

    def test_profile_from_inside_a_task(self, ray_start):
        """The ctl verb is blocking-listed: calling it from a worker
        must not deadlock the poller thread that routes the replies."""
        @ray_tpu.remote
        def nested():
            from ray_tpu import profiler as prof
            out = prof.profile(duration_s=0.3)
            return len(out["workers"])

        # At least the calling worker itself captured.
        assert ray_tpu.get(nested.remote(), timeout=120) >= 1

    def test_bundle_attaches_profile(self, ray_start):
        """Flight-recorder bundles attach the merged profile trace when
        asked (the watchdog's bundle_profile_s knob rides this)."""
        path = ray_start.ctl_debug_dump("profiler_unit",
                                        capture_stacks=False,
                                        profile_s=0.3)
        trace_path = os.path.join(path, "profile_trace.json")
        assert os.path.isfile(trace_path)
        with open(trace_path) as f:
            doc = json.load(f)
        assert doc["traceEvents"], "bundle profile has no events"
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert "profile_trace.json" in manifest["contents"]


class TestRestSurface:
    def test_job_server_profile_endpoint(self, ray_start):
        """POST /api/cluster/profile (the `ray-tpu profile` transport)
        returns the merged trace + summary."""
        from ray_tpu.job_submission import JobSubmissionClient
        from ray_tpu.job_submission.manager import JobManager
        from ray_tpu.job_submission.server import JobServer
        server = JobServer(JobManager(), port=0)
        try:
            client = JobSubmissionClient(server.address)
            out = client._request(
                "POST", "/api/cluster/profile?duration_s=0.3")
            assert "traceEvents" in out["trace"]
            assert out["num_events"] == len(out["trace"]["traceEvents"])
            slim = client._request(
                "POST",
                "/api/cluster/profile?duration_s=0.2&include_trace=0")
            assert "trace" not in slim and "path" in slim
        finally:
            server.stop()


class TestRecompileDetector:
    def setup_method(self):
        recompile._reset_for_tests()

    def teardown_method(self):
        recompile._reset_for_tests()

    def test_shape_churn_flagged_post_warmup(self, caplog):
        """Acceptance: an injected post-warmup shape change is flagged,
        naming the offending shapes/dtypes."""
        import jax
        import jax.numpy as jnp
        fn = profiler.track(jax.jit(lambda x: x * 2), name="churny")
        with caplog.at_level("WARNING", logger="ray_tpu.profiler"):
            fn(jnp.ones((4,), jnp.float32))   # compile 1 (warmup)
            fn(jnp.ones((4,), jnp.float32))   # cache hit -> warm
            assert not caplog.records
            fn(jnp.ones((8,), jnp.float32))   # post-warmup churn
        rep = recompile.report()["churny"]
        assert rep["warm"] is True
        assert rep["compiles"] >= 2
        assert rep["recompiles"] == 1
        assert "(float32[4])" in rep["signatures"]
        assert "(float32[8])" in rep["signatures"]
        warnings = [r for r in caplog.records
                    if "post-warmup recompilation" in r.message]
        assert len(warnings) == 1
        msg = warnings[0].getMessage()
        # The warning names BOTH the new and the previously-seen shapes.
        assert "float32[8]" in msg and "float32[4]" in msg
        assert "churny" in msg

    def test_warns_once_but_counts_every_recompile(self, caplog):
        import jax
        import jax.numpy as jnp
        fn = profiler.track(jax.jit(lambda x: x + 1), name="churny2")
        with caplog.at_level("WARNING", logger="ray_tpu.profiler"):
            fn(jnp.ones((2,)))
            fn(jnp.ones((2,)))
            fn(jnp.ones((3,)))
            fn(jnp.ones((5,)))
        rep = recompile.report()["churny2"]
        assert rep["recompiles"] == 2
        assert sum("post-warmup recompilation" in r.message
                   for r in caplog.records) == 1

    def test_pre_warmup_bucket_sweep_is_not_churn(self):
        """Compiling several shapes BEFORE any cache hit (bucketed
        prefill warmup, multi-shape eval) is not a recompile verdict."""
        import jax
        import jax.numpy as jnp
        fn = profiler.track(jax.jit(lambda x: x.sum()), name="buckets")
        for n in (2, 4, 8):
            fn(jnp.ones((n,)))
        rep = recompile.report()["buckets"]
        assert rep["recompiles"] == 0 and not rep["warm"]


class TestStepPhases:
    def setup_method(self):
        attribution._reset_for_tests()

    def test_phases_sum_to_elapsed_property(self):
        """Property: attributed phases never exceed the elapsed window,
        and finalize's derived 'other' makes them sum EXACTLY to the
        step time."""
        t0 = time.monotonic()
        with attribution.step_phase("data_wait"):
            time.sleep(0.03)
        with attribution.step_phase("compute"):
            time.sleep(0.02)
            with attribution.step_phase("collective"):
                time.sleep(0.02)
        elapsed = time.monotonic() - t0
        phases = attribution.pop_phases()
        assert attribution.pop_phases() == {}  # popped = cleared
        assert sum(phases.values()) <= elapsed + 0.005
        # Nested time is charged to the INNER phase only.
        assert 0.015 <= phases["compute"] <= 0.04
        assert 0.015 <= phases["collective"] <= 0.04
        step_s = elapsed + 0.05  # pretend the step had untracked tail
        final = attribution.finalize_step_phases(phases, step_s,
                                                 ckpt_s=0.01)
        assert abs(sum(final.values()) - step_s) < 1e-9 \
            or final["other"] == 0.0
        assert final["ckpt_block"] == pytest.approx(0.01)

    def test_fence_returns_value(self):
        import jax.numpy as jnp
        x = jnp.ones((4,))
        assert attribution.fence(x) is x
        assert attribution.fence({"a": 1})["a"] == 1

    def test_e2e_trainer_attribution(self, ray_start, tmp_path):
        """Acceptance: a real fit() decomposes every step; per-report
        phases (incl. the derived 'other') sum to the report-to-report
        interval, Result.step_phases summarizes them, and the goodput
        tracker books data-wait out of the productive phase."""
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

        def fn(config):
            import time as _t

            import ray_tpu.train as train
            for _ in range(4):
                with train.step_phase("data_wait"):
                    _t.sleep(0.05)
                with train.step_phase("compute"):
                    _t.sleep(0.03)
                train.report({"loss": 1.0})

        res = JaxTrainer(
            fn, train_loop_config={},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="profiler_phases",
                                 storage_path=str(tmp_path))).fit()
        assert res.error is None
        sp = res.step_phases
        assert sp is not None
        assert sp["seconds"]["data_wait"] >= 0.15
        assert sp["seconds"]["compute"] >= 0.09
        assert sum(sp["fraction"].values()) == pytest.approx(1.0, abs=0.02)

        # Per-report property: phases sum to the step interval (mono
        # report-to-report delta), within scheduler tolerance.
        rank0 = sorted((r for r in res.all_reports if r["rank"] == 0),
                       key=lambda r: r["seq"])
        assert len(rank0) == 4
        for prev, cur in zip(rank0, rank0[1:]):
            if prev["incarnation"] != cur["incarnation"]:
                continue
            step_s = cur["mono"] - prev["mono"]
            assert "other" in cur["phases"]
            assert sum(cur["phases"].values()) == \
                pytest.approx(step_s, abs=0.05)

        # Goodput learned the data-wait idle attribution.
        assert res.goodput["phases_s"].get("data_wait", 0.0) >= 0.1
        # And the catalog histogram carries per-phase observations.
        from ray_tpu.util.metrics import prometheus_text
        text = prometheus_text()
        assert 'ray_tpu_train_step_phase_seconds_count' \
            '{phase="data_wait"}' in text


class TestSpanNesting:
    """Satellite regression: profile_span is re-entrant with parent
    linkage — an inner span's duration is no longer attributed to both
    levels (extra.self_s excludes children)."""

    def _capture_spans(self, body):
        spans = []
        orig = telemetry._emit_span

        def capture(name, category, start_s, end_s, extra=None):
            spans.append({"name": name, "start": start_s, "end": end_s,
                          "extra": extra or {}})
        telemetry._emit_span = capture
        try:
            body()
        finally:
            telemetry._emit_span = orig
        return {s["name"]: s for s in spans}

    def test_nested_spans_link_and_exclude_child_time(self):
        def body():
            with telemetry.profile_span("outer"):
                time.sleep(0.04)
                with telemetry.profile_span("inner"):
                    time.sleep(0.05)
        spans = self._capture_spans(body)
        outer, inner = spans["outer"], spans["inner"]
        assert inner["extra"]["parent_id"] == outer["extra"]["span_id"]
        assert outer["extra"]["parent_id"] is None
        outer_dur = outer["end"] - outer["start"]
        inner_dur = inner["end"] - inner["start"]
        # Inclusive duration still covers the child; SELF time doesn't.
        assert outer_dur >= inner_dur
        assert outer["extra"]["self_s"] == pytest.approx(
            outer_dur - inner_dur, abs=0.02)
        assert inner["extra"]["self_s"] == pytest.approx(inner_dur,
                                                         abs=0.02)

    def test_single_instance_reentrant(self):
        sp = telemetry.profile_span("re")

        def body():
            with sp:
                time.sleep(0.01)
                with sp:
                    time.sleep(0.01)
        spans = []
        orig = telemetry._emit_span
        telemetry._emit_span = \
            lambda n, c, s, e, extra=None: spans.append(extra)
        try:
            body()
        finally:
            telemetry._emit_span = orig
        assert len(spans) == 2
        inner, outer = spans  # inner exits first
        assert inner["parent_id"] == outer["span_id"]


class TestRequestTrace:
    """Satellite: W3C trace context through the serve handle path and
    the disagg prefill->decode pipeline — one LLM request renders as a
    single trace tree with queue-wait / prefill / KV-transfer /
    decode-admission spans (TTFT is no longer one opaque histogram)."""

    def test_disagg_request_is_one_trace_tree(self, ray_start):
        import jax
        import jax.numpy as jnp

        from ray_tpu.llm.disagg import DisaggServer
        from ray_tpu.models import LlamaConfig
        from ray_tpu.models.llama import init_params
        from ray_tpu.util import tracing

        cfg = LlamaConfig(vocab_size=128, hidden=32, layers=2, heads=4,
                          kv_heads=2, head_dim=8, mlp_dim=64,
                          max_seq_len=128, attention_impl="reference",
                          remat=False, dtype=jnp.float32)
        params = init_params(cfg, jax.random.key(0))
        tracing.enable()
        srv = DisaggServer(
            lambda: (params, cfg), mode="disagg",
            engine_options={"max_slots": 2, "page_size": 8,
                            "num_pages": 64, "prefill_buckets": (16,)})
        try:
            out = srv({"prompt_tokens": [3, 17, 92, 5], "max_tokens": 4,
                       "timeout_s": 120})
            assert len(out["output_tokens"]) == 4
        finally:
            srv.close()
            tracing.disable()
        want = {"llm_request", "queue_wait", "prefill", "kv_transfer",
                "decode_admission"}
        match = None
        for tid in tracing.list_traces():
            spans = tracing.get_trace(tid)
            if "llm_request" in {s["name"] for s in spans}:
                match = spans
                break
        assert match is not None, "no llm_request trace recorded"
        names = {s["name"] for s in match}
        assert want <= names, names
        root = next(s for s in match if s["name"] == "llm_request")
        kids = {s["name"] for s in match
                if s.get("parent_span_id") == root["span_id"]}
        assert want - {"llm_request"} <= kids, kids
        # One trace id across the whole pipeline.
        assert len({s["trace_id"] for s in match}) == 1
        # Phase spans nest inside the root's window.
        for s in match:
            assert s["start_s"] >= root["start_s"] - 0.001
            assert s["end_s"] <= root["end_s"] + 0.001

    def test_tracing_span_context_manager(self, ray_start):
        """tracing.span: in-thread nesting installs/restores the current
        context — children inherit the trace id and parent linkage, and
        an error is stamped on the span."""
        from ray_tpu.util import tracing
        tracing.enable()
        prev = tracing.current()
        try:
            with tracing.span("outer_cm", {"k": "v"}):
                with tracing.span("inner_cm"):
                    time.sleep(0.01)
            assert tracing.current() is prev  # context restored
            with pytest.raises(ValueError):
                with tracing.span("boom_cm"):
                    raise ValueError("x")
        finally:
            tracing.disable()
        spans = [s for tid in tracing.list_traces()
                 for s in tracing.get_trace(tid)
                 if s["name"].endswith("_cm")]
        by_name = {s["name"]: s for s in spans}
        outer, inner = by_name["outer_cm"], by_name["inner_cm"]
        assert inner["parent_span_id"] == outer["span_id"]
        assert inner["trace_id"] == outer["trace_id"]
        assert outer["attributes"]["k"] == "v"
        assert inner["start_s"] >= outer["start_s"]
        assert by_name["boom_cm"]["attributes"]["error"] == "ValueError"

    def test_serve_handle_route_span_joins_request_trace(self, ray_start):
        from ray_tpu import serve
        from ray_tpu.util import tracing

        @serve.deployment(name="traced_echo")
        class _Echo:
            def __call__(self, body):
                return body

        tracing.enable()
        try:
            handle = serve.run(_Echo.bind())
            assert ray_tpu.get(handle.remote({"x": 1}),
                               timeout=60) == {"x": 1}
            route = _wait_for(lambda: [
                s for tid in tracing.list_traces()
                for s in tracing.get_trace(tid)
                if s["name"] == "serve_route traced_echo"])
            assert route, "no serve_route span recorded"
            trace = tracing.get_trace(route[0]["trace_id"])
            names = {s["name"] for s in trace}
            # The route span and the actor-method submit/execute spans
            # share ONE trace: the handle path extends the context.
            assert any(n.startswith("submit") for n in names), names
        finally:
            serve.shutdown()
            tracing.disable()


class TestCaptureUnits:
    def test_host_sampler_sees_named_thread(self):
        from ray_tpu.profiler.capture import capture_profile
        stop = threading.Event()

        def busy():
            while not stop.is_set():
                sum(i * i for i in range(500))
        t = threading.Thread(target=busy, name="unit-busy-thread")
        t.start()
        try:
            rec = capture_profile("unit", 0.4, hz=80,
                                  driver_wall_s=time.time())
        finally:
            stop.set()
            t.join()
        assert rec["error"] is None
        assert len(rec["samples"]) >= 10
        names = {th["name"] for s in rec["samples"]
                 for th in s["threads"].values()}
        assert "unit-busy-thread" in names
        assert abs(rec["clock_offset_s"]) < 1.0

    def test_concurrent_capture_reports_busy(self):
        from ray_tpu.profiler import capture as cap
        results = []

        def one(dur):
            results.append(cap.capture_profile("x", dur, hz=50))
        t = threading.Thread(target=one, args=(0.6,))
        t.start()
        time.sleep(0.1)
        one(0.1)
        t.join()
        errors = [r.get("error") for r in results]
        assert errors.count("capture already running") == 1

    def test_merge_is_deterministic_and_serializable(self):
        from ray_tpu.profiler.capture import capture_profile
        from ray_tpu.profiler.merge import merge_records
        rec = capture_profile("m", 0.2, hz=50, driver_wall_s=time.time())
        doc = merge_records([rec], meta={"profile_id": 7})
        json.dumps(doc)  # wire/disk safe
        assert doc["otherData"]["profile_id"] == 7
        assert doc["otherData"]["processes"][0]["num_samples"] == \
            len(rec["samples"])
