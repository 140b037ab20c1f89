"""A stalled train step says what it waited for: the step's number on its
span, the worker's background work and state on the recorder's clock, and
``telemetry.stalls`` reading them back (PERF.md, PR 38)."""

from __future__ import annotations

import gc
import json
import os

import pytest

import ray_tpu
from ray_tpu.profiler import attribution, capture
from ray_tpu.util import telemetry

HEAD, WORKER, OTHER = 100, 200, 300


def span(name, start, seconds, process=WORKER, **extra):
    return {"name": name, "cat": "x", "start": start, "end": start + seconds,
            "process": process, "thread": 1, **extra}


def beats(periods, process=WORKER, t0=1000.0, first_step=0):
    """``train_place_batch`` spans whose starts lie ``periods`` apart."""
    out, t = [], t0
    for i, p in enumerate(list(periods) + [0.0]):
        out.append(span("train_place_batch", t, 0.002, process,
                        step=first_step + i))
        t += p
    return out


def sample(start, process=WORKER, **state):
    return span("worker_sample", start, 0.0003, process, span_id=9,
                parent_id=None, self_s=0.0003, **state)


@pytest.fixture
def captured_spans(monkeypatch):
    spans = []
    monkeypatch.setattr(
        telemetry, "_emit_span",
        lambda name, category, start_s, end_s, extra=None: spans.append(
            {"name": name, "cat": category, "start": start_s, "end": end_s,
             **(extra or {})}))
    return spans


class TestStalls:
    def a_run(self):
        # steps 0..9 at 1.5 s, step 4 takes 4.5 s: [1006, 1010.5)
        run = beats([1.5] * 4 + [4.5] + [1.5] * 5)
        run += [
            span("runtime_init", 900.0, 0.1, HEAD),
            span("train_fit", 990.0, 40.0, HEAD),           # holds the loop
            span("train_loop", 995.0, 30.0, WORKER),        # holds the loop
            span("py_gc", 1007.0, 0.8, WORKER, generation=2, collected=11),
            span("py_gc", 1001.0, 0.2, WORKER, generation=2, collected=0),
            span("worker_flush", 1008.0, 0.004, WORKER, spans=3, series=40),
            span("worker_flush", 1010.4, 0.3, WORKER, spans=3, series=40),
            span("head_thing", 1009.0, 0.5, HEAD),
            span("elsewhere", 1007.0, 2.0, OTHER),
            sample(1004.0, cpu_user_s=10.0, switches_involuntary=5,
                   major_faults=1, pressure_cpu=0.5, bytes_in_use=9.0e9,
                   bytes_reserved=5.0e9),
            sample(1005.9, cpu_user_s=10.5, switches_involuntary=5,
                   major_faults=1, pressure_cpu=0.5, bytes_in_use=9.0e9,
                   bytes_reserved=5.0e9),
            sample(1008.0, cpu_user_s=11.0, switches_involuntary=9,
                   major_faults=1, pressure_cpu=30.0, bytes_in_use=9.0e9,
                   bytes_reserved=5.0e9),
            sample(1012.0, cpu_user_s=12.5, switches_involuntary=45,
                   major_faults=4, pressure_cpu=61.5, bytes_in_use=9.5e9,
                   bytes_reserved=5.0e9),
        ]
        return run

    def test_the_long_period_is_named_with_what_covered_it(self):
        out = telemetry.stalls(self.a_run())
        assert out["cadence"] == "train_place_batch"
        assert out["factor"] == 1.5
        [stall] = out["stalls"]
        assert (stall["process"], stall["step"]) == (WORKER, 4)
        assert stall["start"] == pytest.approx(1006.0)
        assert stall["seconds"] == pytest.approx(4.5)
        assert stall["median_s"] == pytest.approx(1.5)
        covered = {(o["name"], o["process"]): o for o in stall["overlapping"]}
        # this process's and the head's; not another worker's, not the
        # spans that hold the whole loop, not the cadence itself
        assert set(covered) == {("py_gc", WORKER), ("worker_flush", WORKER),
                                ("head_thing", HEAD)}
        assert covered["py_gc", WORKER]["seconds"] == pytest.approx(0.8)
        assert covered["py_gc", WORKER]["spans"] == 1
        # two flushes, the second cut at the period's end
        assert covered["worker_flush", WORKER]["spans"] == 2
        assert covered["worker_flush", WORKER]["seconds"] == \
            pytest.approx(0.004 + 0.1)
        # the largest first
        assert stall["overlapping"][0]["name"] == "py_gc"

    def test_the_bracketing_samples_and_their_differences(self):
        [stall] = telemetry.stalls(self.a_run())["stalls"]
        got = stall["samples"]
        # the last sample that starts before it, the first that ends after
        assert got["before"]["at"] == pytest.approx(1005.9)
        assert got["after"]["at"] == pytest.approx(1012.0)
        diff = got["difference"]
        assert diff["cpu_user_s"] == pytest.approx(2.0)
        assert diff["switches_involuntary"] == 40
        assert diff["major_faults"] == 3
        assert diff["pressure_cpu"] == pytest.approx(61.0)
        assert diff["bytes_in_use"] == pytest.approx(0.5e9)
        assert diff["bytes_reserved"] == 0
        assert diff["at"] == pytest.approx(6.1)
        assert "span_id" not in diff and "thread" not in diff

    def test_no_period_over_the_factor(self):
        run = beats([1.5, 1.52, 1.49, 1.5, 1.51, 2.2]) + [
            span("py_gc", 1003.0, 0.1, WORKER)]
        out = telemetry.stalls(run)
        assert out["stalls"] == []
        [proc] = out["processes"]
        assert proc["process"] == WORKER and proc["periods"] == 6
        assert proc["median_s"] == pytest.approx(1.505)
        assert proc["max_s"] == pytest.approx(2.2)
        assert proc["max_step"] == 5
        # the same run read more strictly
        assert [s["step"] for s in telemetry.stalls(
            run, factor=1.2)["stalls"]] == [5]

    def test_two_processes_are_kept_apart(self):
        # A slow process beside a fast one: each against its own median,
        # and a span of one never covers the other's period.
        run = beats([1.0] * 5 + [3.0] + [1.0] * 2, process=WORKER) \
            + beats([4.0] * 6, process=OTHER, t0=1000.5) \
            + [span("py_gc", 1005.5, 0.5, OTHER),
               sample(1004.0, OTHER, cpu_user_s=1.0),
               sample(1009.0, OTHER, cpu_user_s=2.0)]
        out = telemetry.stalls(run)
        assert {p["process"]: p["median_s"] for p in out["processes"]} == {
            WORKER: pytest.approx(1.0), OTHER: pytest.approx(4.0)}
        [stall] = out["stalls"]
        assert (stall["process"], stall["step"]) == (WORKER, 5)
        assert stall["overlapping"] == [] and stall["samples"] == {}

    def test_another_closures_spans_and_a_lost_span_give_no_period(self):
        # steps 0..3, then a second closure from 0 after a pause, then a
        # span lost (step 1 -> 4): neither pause is a period.
        run = beats([1.0] * 3) + beats([1.0], t0=1033.0) \
            + beats([1.0, 1.0], t0=1050.0, first_step=4)
        out = telemetry.stalls(run)
        assert out["stalls"] == []
        assert out["processes"][0]["periods"] == 3 + 1 + 2
        # spans of a program that does not number its steps still count
        plain = [{k: v for k, v in s.items() if k != "step"}
                 for s in beats([1.0] * 4 + [5.0] + [1.0])]
        assert [s["seconds"] for s in telemetry.stalls(plain)["stalls"]] \
            == [pytest.approx(5.0)]

    def test_set_up_reads_as_a_stall_covered_by_its_compile(self):
        run = beats([20.0] + [1.5] * 6) + [
            span("xla_compile", 1001.0, 16.0, WORKER, program="jit(step)")]
        [stall] = telemetry.stalls(run)["stalls"]
        assert stall["step"] == 0
        assert stall["overlapping"][0]["name"] == "xla_compile"
        assert stall["overlapping"][0]["seconds"] == pytest.approx(16.0)

    def test_no_cadence_at_all(self):
        assert telemetry.stalls([span("runtime_init", 1.0, 1.0, HEAD)]) == {
            "cadence": "train_place_batch", "factor": 1.5, "processes": [],
            "stalls": []}


class TestStepNumbers:
    def test_place_batch_numbers_its_spans_from_0_per_closure(
            self, captured_spans):
        import jax
        import numpy as np

        from ray_tpu.models import LlamaConfig
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.spmd import make_lm_train_step
        cfg = LlamaConfig(vocab_size=64, hidden=32, layers=1, heads=2,
                          kv_heads=1, head_dim=16, mlp_dim=64,
                          max_seq_len=32, attention_impl="reference")
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        batch = {"tokens": np.zeros((1, 32), np.int32)}
        _, _, first = make_lm_train_step(cfg, mesh)
        _, _, second = make_lm_train_step(cfg, mesh)
        for place in (first, first, second, first, second):
            place(batch)
        assert [s["step"] for s in captured_spans
                if s["name"] == "train_place_batch"] == [0, 1, 0, 2, 1]


class TestGcSpans:
    def collect(self, seconds, generation=2):
        ticks = iter([50.0, 50.0 + seconds])
        watch = attribution.GcSpans(clock=lambda: next(ticks))
        watch("start", {"generation": generation, "collected": 0,
                        "uncollectable": 0})
        watch("stop", {"generation": generation, "collected": 17,
                       "uncollectable": 0})

    def test_a_short_collection_leaves_no_span(self, captured_spans):
        self.collect(0.0009, generation=0)
        assert captured_spans == []

    def test_a_long_collection_is_one_span(self, captured_spans):
        self.collect(0.25)
        [got] = captured_spans
        assert got["name"] == "py_gc"
        assert got["end"] - got["start"] == pytest.approx(0.25)
        assert (got["generation"], got["collected"]) == (2, 17)

    def test_a_stop_without_its_start_is_ignored(self, captured_spans):
        attribution.GcSpans()("stop", {"generation": 0, "collected": 0})
        assert captured_spans == []

    def test_watch_process_registers_once(self, monkeypatch):
        from ray_tpu.util import metrics
        monkeypatch.setattr(metrics, "note_pending", lambda: None)
        attribution._reset_for_tests()
        try:
            attribution.watch_process()
            attribution.watch_process()
            assert sum(isinstance(c, attribution.GcSpans)
                       for c in gc.callbacks) == 1
        finally:
            attribution._reset_for_tests()
        assert not any(isinstance(c, attribution.GcSpans)
                       for c in gc.callbacks)


class _Device:
    def __init__(self, name, stats):
        self.name, self.stats = name, stats

    def __str__(self):
        return self.name

    def memory_stats(self):
        return self.stats


class TestWorkerSample:
    PROCESS_KEYS = {"cpu_user_s", "cpu_system_s", "switches_voluntary",
                    "switches_involuntary", "major_faults"}

    def test_no_memory_stats_and_no_pressure_files(
            self, captured_spans, monkeypatch, tmp_path):
        # What a CPU backend on a kernel without PSI gives: the process's
        # own state and nothing else.
        import jax
        monkeypatch.setattr(jax, "local_devices",
                            lambda: [_Device("cpu:0", None)])
        state = attribution.worker_sample(str(tmp_path / "absent"))
        assert set(state) == self.PROCESS_KEYS
        assert state["cpu_user_s"] > 0
        [got] = captured_spans
        assert got["name"] == "worker_sample" and got["end"] >= got["start"]
        assert self.PROCESS_KEYS <= set(got)

    def test_pressure_and_the_fullest_device(self, captured_spans,
                                             monkeypatch, tmp_path):
        import jax

        from ray_tpu.util import metrics
        for what, avg10 in (("cpu", "12.50"), ("io", "0.00")):
            (tmp_path / what).write_text(
                f"some avg10={avg10} avg60=1.00 avg300=0.10 total=99\n"
                "full avg10=77.00 avg60=0.00 avg300=0.00 total=1\n")
        (tmp_path / "memory").write_text("not a pressure line\n")
        gib = 2 ** 30
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _Device("TPU_0", {"bytes_in_use": 3 * gib, "bytes_limit": 16 * gib,
                              "bytes_reserved": 1 * gib,
                              "peak_bytes_in_use": 4 * gib}),
            _Device("TPU_1", {"bytes_in_use": 2 * gib, "bytes_limit": 16 * gib,
                              "bytes_reserved": 9 * gib,
                              "peak_bytes_in_use": 5 * gib,
                              "largest_free_block_bytes": gib,
                              "num_allocs": 321, "something_else": 1})])
        metrics._reset_for_tests()
        state = attribution.worker_sample(str(tmp_path))
        assert state["pressure_cpu"] == 12.5 and state["pressure_io"] == 0.0
        assert "pressure_memory" not in state
        # TPU_1 holds more (in use + reserved); only what it gives is there
        assert state["bytes_in_use"] == 2 * gib
        assert state["bytes_reserved"] == 9 * gib
        assert state["num_allocs"] == 321
        assert state["largest_free_block_bytes"] == gib
        assert "peak_bytes_reserved" not in state
        assert "something_else" not in state and "device" not in state
        # ... and every device's gauges are live from the same reading
        used = {tuple(sorted(tags.items())): value for _n, tags, value
                in telemetry.gauge("ray_tpu_train_hbm_used_bytes")
                .snapshot()["samples"]}
        assert used == {(("device", "TPU_0"),): 3.0 * gib,
                        (("device", "TPU_1"),): 2.0 * gib}
        peak = {tags["device"]: value for _n, tags, value
                in telemetry.gauge("ray_tpu_train_hbm_peak_bytes")
                .snapshot()["samples"]}
        assert peak == {"TPU_0": 4.0 * gib, "TPU_1": 5.0 * gib}
        metrics._reset_for_tests()

    def test_the_flusher_samples_only_a_watched_process(self, monkeypatch):
        taken = []
        monkeypatch.setattr(attribution, "worker_sample",
                            lambda: taken.append(1))
        attribution._reset_for_tests()
        attribution.sample_if_watching()
        assert taken == []
        monkeypatch.setattr(gc, "callbacks",
                            gc.callbacks + [attribution.GcSpans()])
        attribution.sample_if_watching()
        assert taken == [1]

    def test_device_memory_stats_holds_every_key(self, monkeypatch):
        import jax
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _Device("TPU_0", {"bytes_in_use": 5, "bytes_reserved": 7}),
            _Device("TPU_1", {})])
        [rec] = capture.device_memory_stats()
        assert set(rec) == {"device", *capture.MEMORY_KEYS}
        assert (rec["device"], rec["bytes_in_use"], rec["bytes_reserved"],
                rec["bytes_limit"]) == ("TPU_0", 5, 7, None)


def _steps(config):
    """A worker's loop: a jitted function of its own, then a few steps."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import LlamaConfig
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step
    out = {"jit_is_jax": jax.jit.__module__.startswith("jax."),
           "jit_name": jax.jit.__qualname__}

    @jax.jit
    def jitted_by_the_worker_38(x):
        return x * 2 + 1
    out["jitted_type"] = type(jitted_by_the_worker_38).__module__
    jitted_by_the_worker_38(jnp.ones((3,))).block_until_ready()
    cfg = LlamaConfig(vocab_size=64, hidden=32, layers=1, heads=2,
                      kv_heads=1, head_dim=16, mlp_dim=64, max_seq_len=32,
                      attention_impl="reference")
    _, _, place = make_lm_train_step(
        cfg, build_mesh(MeshSpec(dp=1), jax.devices()[:1]))
    for i in range(6):
        place({"tokens": np.zeros((1, 32), np.int32)})
        time.sleep(config["pause"][i])
    train.report(out)


class TestTrainWorker:
    @pytest.fixture(scope="class")
    def run(self, tmp_path_factory):
        """One worker that holds a (CPU) chip, through ``JaxTrainer``: what
        it reported and what the head left in the session's trace files."""
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
        rt = ray_tpu.init(num_cpus=2, num_tpus=1)
        try:
            result = JaxTrainer(
                _steps,
                # the last pause follows the last step: it is no period,
                # and it holds the flusher tick that samples after the stall
                train_loop_config={"pause": [.3, .3, .3, 2.6, .3, 3.0]},
                scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                             chips_per_worker=1),
                run_config=RunConfig(
                    name="stalled", storage_path=str(
                        tmp_path_factory.mktemp("stalled")))).fit()
            assert result.error is None
            session = rt.session_dir
        finally:
            ray_tpu.shutdown()
        trace = os.path.join(session, "trace")
        with open(os.path.join(trace, "spans.jsonl")) as f:
            spans = [json.loads(line) for line in f]
        with open(os.path.join(trace, "stalls.json")) as f:
            stalls = json.load(f)
        return {"reported": result.metrics, "spans": spans, "stalls": stalls}

    def named(self, run, name):
        return [s for s in run["spans"] if s["name"] == name]

    def test_jit_is_left_unpatched(self, run):
        got = run["reported"]
        assert got["jit_is_jax"], got
        assert got["jit_name"] == "jit"
        assert not got["jitted_type"].startswith("ray_tpu")

    def test_compiles_are_still_recorded(self, run):
        programs = [s["program"] for s in self.named(run, "xla_compile")]
        assert any("jitted_by_the_worker_38" in p for p in programs), programs

    def test_steps_are_numbered(self, run):
        assert [s["step"] for s in self.named(run, "train_place_batch")] \
            == list(range(6))

    def test_flushes_and_samples_are_spans_of_the_worker(self, run):
        worker = self.named(run, "train_place_batch")[0]["process"]
        flushes = self.named(run, "worker_flush")
        assert flushes and {s["process"] for s in flushes} == {worker}
        assert all(s["spans"] >= 0 and s["series"] >= 0 for s in flushes)
        assert any(s["spans"] > 0 for s in flushes)
        samples = self.named(run, "worker_sample")
        assert samples and {s["process"] for s in samples} == {worker}
        assert all(s["cpu_user_s"] > 0 for s in samples)
        # no sample before the process held its chip
        up = self.named(run, "worker_backend_init")[0]
        assert min(s["start"] for s in samples) >= up["end"]

    def test_the_head_leaves_the_stalled_step_in_stalls_json(self, run):
        stalls = run["stalls"]
        [proc] = stalls["processes"]
        assert proc["periods"] == 5 and proc["max_step"] == 3
        [stall] = stalls["stalls"]
        # no upper bound: a loaded host only lengthens the pause
        assert stall["step"] == 3 and stall["seconds"] > 2.5
        # 2.6 s hold at least one flusher tick of 2 s
        assert "worker_flush" in {o["name"] for o in stall["overlapping"]}
        # bracketed where the flusher's first tick (2 s) came before step 3,
        # which on a loaded host the clock decides: ``_bracket``'s own tests
        # are above
        difference = stall["samples"].get("difference")
        assert difference is None or difference["cpu_user_s"] >= 0


def test_the_new_instruments_are_spans_and_no_series():
    assert not [name for name in telemetry.CATALOG
                if any(word in name for word in (
                    "stall", "py_gc", "worker_sample", "worker_flush",
                    "step_period", "hbm_held"))]
