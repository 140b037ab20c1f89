"""The span recorder: one class for the framework and for users, on the
device trace's clock, shipped in batches, written out at shutdown; the
spans, stamps and names it puts on the two main paths."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu.util import state as state_api
from ray_tpu.util import telemetry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Both spellings of the one recorder (``state.profile_span`` is the same
#: class under the category "user"): every case holds for both.
SPAN_CLASSES = pytest.mark.parametrize(
    "span_cls", [telemetry.profile_span, state_api.profile_span],
    ids=["telemetry", "state"])


def _timeline(name):
    return [e for e in json.loads(ray_tpu.timeline()) if e["name"] == name]


class TestOneRecorder:
    def test_state_span_is_the_telemetry_class(self):
        assert issubclass(state_api.profile_span, telemetry.profile_span)
        assert state_api.profile_span("x").category == "user"
        assert telemetry.profile_span("x").category == "system"

    @SPAN_CLASSES
    def test_span_in_timeline(self, ray_start, span_cls):
        name = f"phase_{span_cls.__module__.rsplit('.', 1)[-1]}"
        with span_cls(name, category="demo"):
            time.sleep(0.01)
        spans = _timeline(name)
        assert spans and spans[0]["cat"] == "demo"
        assert spans[0]["dur"] >= 10_000  # >= 10 ms in microseconds

    @SPAN_CLASSES
    def test_parent_and_request_ids_survive_nesting(self, ray_start,
                                                    span_cls):
        tag = span_cls.__module__.rsplit(".", 1)[-1]
        with span_cls(f"outer_{tag}", extra={"request_id": 41}):
            with span_cls(f"inner_{tag}", extra={"request_id": 41}):
                time.sleep(0.01)
        outer = _timeline(f"outer_{tag}")[0]["args"]
        inner = _timeline(f"inner_{tag}")[0]["args"]
        assert inner["parent_id"] == outer["span_id"]
        assert outer["parent_id"] is None
        assert inner["request_id"] == outer["request_id"] == 41
        assert outer["self_s"] <= 0.009     # the child's time is not its own

    @SPAN_CLASSES
    def test_threads_keep_their_own_parents(self, ray_start, span_cls):
        """A span opened in another thread while one is open here is not
        its child: the open-span stack is per thread."""
        tag = span_cls.__module__.rsplit(".", 1)[-1]

        def other():
            with span_cls(f"elsewhere_{tag}", extra={"request_id": 2}):
                pass

        with span_cls(f"here_{tag}", extra={"request_id": 1}):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        here, there = _timeline(f"here_{tag}")[0], \
            _timeline(f"elsewhere_{tag}")[0]
        assert there["args"]["parent_id"] is None
        assert there["args"]["request_id"] == 2
        assert here["tid"] != there["tid"]

    @SPAN_CLASSES
    def test_span_from_worker(self, ray_start, span_cls):
        name = f"inner_work_{span_cls.__module__.rsplit('.', 1)[-1]}"

        @ray_tpu.remote
        def traced(cls, name):
            with cls(name):
                time.sleep(0.01)
            return os.getpid()

        pid = ray_tpu.get(traced.remote(span_cls, name))
        # The span rode ahead of the task's completion: no sleep needed.
        spans = _timeline(name)
        assert spans and f"pid:{pid}:" in spans[0]["tid"]

    @SPAN_CLASSES
    def test_span_survives_clock_step(self, ray_start, span_cls):
        """Length comes from the monotonic clock: a wall-clock step while
        the span is open cannot make it an hour long or negative."""
        name = f"ntp_{span_cls.__module__.rsplit('.', 1)[-1]}"
        sp = span_cls(name)
        sp.__enter__()
        time.sleep(0.02)
        orig = time.time
        time.time = lambda: orig() + 3600.0
        try:
            sp.__exit__(None, None, None)
        finally:
            time.time = orig
        spans = _timeline(name)
        assert spans and 0 <= spans[0]["dur"] < 60e6

    def test_group_span_is_recorded(self, ray_start):
        with telemetry.profile_span("whole_loop", group=True):
            with telemetry.profile_span("one_part"):
                pass
        whole, part = _timeline("whole_loop")[0], _timeline("one_part")[0]
        assert part["args"]["parent_id"] == whole["args"]["span_id"]


class TestBatchesAndFiles:
    def test_worker_spans_ship_in_batches_and_land_in_files(self):
        rt = ray_tpu.init(num_cpus=2)
        try:
            batches = []
            orig = rt.ctl_add_profile_span

            def counting(spans):
                batches.append(len(spans))
                return orig(spans)
            rt.ctl_add_profile_span = counting

            @ray_tpu.remote
            def many():
                for i in range(40):
                    with telemetry.profile_span("many_small",
                                                extra={"step": i}):
                        pass
                telemetry.inc("ray_tpu_llm_preemptions_total")
                return os.getpid()

            @ray_tpu.remote
            class Late:
                def start(self):
                    def later():
                        time.sleep(0.2)
                        with telemetry.profile_span("after_the_call"):
                            pass
                    threading.Thread(target=later).start()
                    return os.getpid()

            pid = ray_tpu.get(many.remote())
            # One frame for the task's 40 spans, not 40 frames.
            assert max(batches) >= 40
            late = Late.remote()
            late_pid = ray_tpu.get(late.start.remote())
            time.sleep(0.4)     # recorded, and still in the worker's buffer
            session = rt.session_dir
        finally:
            ray_tpu.shutdown()
        spans = [json.loads(line) for line in open(
            os.path.join(session, "trace", "spans.jsonl"))]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert len(by_name["many_small"]) == 40
        assert {s["process"] for s in by_name["many_small"]} == {pid}
        assert sorted(s["step"] for s in by_name["many_small"]) == \
            list(range(40))
        # Shipped by the terminal flush that shutdown asks for.
        assert by_name["after_the_call"][0]["process"] == late_pid
        # The head's own spans, and the node's for each worker it spawned.
        assert by_name["runtime_init"][0]["process"] == os.getpid()
        assert {pid, late_pid} <= {s["pid"] for s in by_name["worker_start"]}
        for s in spans:
            assert s["end"] >= s["start"] and "thread" in s
        counters = json.load(open(
            os.path.join(session, "trace", "counters.json")))
        assert counters["types"]["ray_tpu_llm_preemptions_total"] == "counter"
        assert counters["samples"]["ray_tpu_llm_preemptions_total"][0][
            "value"] >= 1.0

    def test_driver_does_not_import_jax(self, tmp_path):
        script = tmp_path / "driver.py"
        script.write_text(
            "import sys, time\n"
            "import ray_tpu\n"
            "from ray_tpu.util import telemetry, state\n"
            "ray_tpu.init(num_cpus=1)\n"
            "with telemetry.profile_span('a'):\n"
            "    with state.profile_span('b'):\n"
            "        pass\n"
            "@ray_tpu.remote\n"
            "def f():\n"
            "    with telemetry.profile_span('c'):\n"
            "        return 1\n"
            "assert ray_tpu.get(f.remote()) == 1\n"
            "ray_tpu.timeline()\n"
            "ray_tpu.shutdown()\n"
            "assert 'jax' not in sys.modules, 'the driver imported jax'\n"
            "print('clean')\n")
        env = dict(os.environ, PYTHONPATH=REPO_ROOT, RAY_TPU_SANITIZE="0")
        done = subprocess.run([sys.executable, str(script)], env=env,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stdout + done.stderr
        assert "clean" in done.stdout


class TestDeviceTraceClock:
    def test_span_shows_on_the_profilers_host_plane(self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with telemetry.profile_span("a_whole_loop", group=True):
                with telemetry.profile_span("host_plane_probe",
                                            extra={"request_id": 9}):
                    jnp.ones((8,)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        assert files
        host = [p for p in ProfileData.from_file(files[0]).planes
                if p.name == "/host:CPU"]
        if not host:
            pytest.skip("this profiler gives no host plane")
        names = {ev.name for line in host[0].lines for ev in line.events}
        if not names:
            pytest.skip("this profiler's host plane holds no events")
        assert any(n.startswith("host_plane_probe") for n in names), names
        # A span that only holds others stays off the plane: a device gap
        # goes to the part, not to the whole.
        assert not any(n.startswith("a_whole_loop") for n in names)


@pytest.fixture
def captured_spans(monkeypatch):
    spans = []
    monkeypatch.setattr(
        telemetry, "_emit_span",
        lambda name, category, start_s, end_s, extra=None: spans.append(
            {"name": name, "start": start_s, "end": end_s,
             "extra": extra or {}}))
    return spans


class TestCompileSpans:
    def test_xla_compile_fires_for_an_untracked_jit(self, captured_spans):
        import jax
        import jax.numpy as jnp

        from ray_tpu.profiler import recompile
        from ray_tpu.util import metrics

        assert recompile.ensure_listener()

        def never_tracked_fn_27(x):
            return x * 3 + 1

        before = time.time()
        jax.jit(never_tracked_fn_27)(jnp.ones((5,))).block_until_ready()
        mine = [s for s in captured_spans if s["name"] == "xla_compile"
                and "never_tracked_fn_27" in s["extra"]["program"]]
        assert len(mine) == 1
        span = mine[0]
        assert span["extra"]["seconds"] > 0
        assert span["extra"]["cache_hit"] is False
        assert before - 1.0 <= span["start"] <= span["end"] <= time.time()
        _by_name, acc = metrics._aggregate_snapshots()
        assert any("never_tracked_fn_27" in dict(k).get("program", "")
                   for k in acc["ray_tpu_xla_compiles_total"])
        # A second call compiles nothing.
        jax.jit(never_tracked_fn_27)
        n = len(captured_spans)
        jax.jit(never_tracked_fn_27)(jnp.ones((5,)))
        assert len([s for s in captured_spans[n:]
                    if "never_tracked_fn_27" in s["extra"].get(
                        "program", "")]) <= 1

    def test_trace_lower_compile_in_that_order(self, captured_spans):
        """An untracked ``jax.jit``: one span for each stretch of its way
        to the device, under its program's name, one after the other."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.profiler import recompile
        from ray_tpu.util import metrics

        assert recompile.ensure_listener()

        def staged_fn_53(x):
            return jnp.tanh(x) * 2 + x

        jax.jit(staged_fn_53)(jnp.ones((7,))).block_until_ready()
        mine = [s for s in captured_spans
                if "staged_fn_53" in s["extra"].get("program", "")]
        assert [s["name"] for s in mine] == [
            "jax_trace", "jax_lower", "xla_compile"]
        trace, lower, compile_ = mine
        assert "nested" not in trace["extra"]
        for s in mine:
            assert s["extra"]["seconds"] == pytest.approx(
                s["end"] - s["start"])
        assert trace["start"] <= trace["end"] <= lower["end"] \
            <= compile_["end"]
        assert trace["start"] <= lower["start"] <= compile_["start"]
        _by_name, acc = metrics._aggregate_snapshots()
        for series in ("ray_tpu_jax_trace_seconds_total",
                       "ray_tpu_jax_lower_seconds_total"):
            assert any("staged_fn_53" in dict(k).get("program", "")
                       for k in acc[series]), series

    @pytest.mark.parametrize("floor, spans_of_the_inner", [(0.0, 1),
                                                           (1e9, 0)])
    def test_nested_trace_is_a_span_over_the_floor(
            self, captured_spans, monkeypatch, floor, spans_of_the_inner):
        """A jit called inside another's trace: a ``nested`` span where it
        is held longer than the floor, no span under it (its seconds are
        its parent's), and no series of its own either way."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.profiler import recompile
        from ray_tpu.util import metrics

        assert recompile.ensure_listener()
        monkeypatch.setattr(recompile, "NESTED_TRACE_FLOOR_S", floor)
        tag = f"nest53_{spans_of_the_inner}"

        def inner(x):
            return x * x + 1
        inner.__name__ = f"inner_{tag}"
        inner = jax.jit(inner)

        def outer(x):
            return inner(x) - 3
        outer.__name__ = f"outer_{tag}"

        jax.jit(outer)(jnp.ones((3,))).block_until_ready()
        traces = {s["extra"]["program"]: s for s in captured_spans
                  if s["name"] == "jax_trace"
                  and tag in s["extra"]["program"]}
        assert f"outer_{tag}" in traces
        assert "nested" not in traces[f"outer_{tag}"]["extra"]
        assert len(traces) == 1 + spans_of_the_inner
        if spans_of_the_inner:
            got, whole = traces[f"inner_{tag}"], traces[f"outer_{tag}"]
            assert got["extra"]["nested"] is True
            assert whole["start"] <= got["start"] <= got["end"] \
                <= whole["end"]
        _by_name, acc = metrics._aggregate_snapshots()
        tagged = {dict(k).get("program", "")
                  for k in acc["ray_tpu_jax_trace_seconds_total"]}
        assert f"outer_{tag}" in tagged and f"inner_{tag}" not in tagged
        # The thread's count of open stretches is back where it was.
        assert recompile._tls.open == 0

    def test_fetch_from_the_persistent_cache_says_what_it_saved(
            self, tmp_path):
        """One program compiled twice from a clean in-memory state on a
        cache directory of its own: the first compile is written there,
        the second comes from it and says so."""
        script = tmp_path / "twice.py"
        script.write_text(
            "import json\n"
            "import jax, jax.numpy as jnp\n"
            "from ray_tpu.profiler import recompile\n"
            "from ray_tpu.util import metrics, telemetry\n"
            "spans = []\n"
            "telemetry._emit_span = lambda name, cat, start, end, "
            "extra=None: spans.append({'name': name, **(extra or {})})\n"
            "assert recompile.ensure_listener()\n"
            "def cached_fn_53(x):\n"
            "    return jnp.sin(x) @ x.T\n"
            "def writes():\n"
            "    acc = metrics._aggregate_snapshots()[1]\n"
            "    return sum(v for _t, v in acc.get(\n"
            "        'ray_tpu_compile_cache_writes_total', {}).values())\n"
            "out = []\n"
            "for _ in range(2):\n"
            "    jax.clear_caches()\n"
            "    jax.jit(cached_fn_53)(jnp.ones((4, 4))).block_until_ready()\n"
            "    out.append({'writes': writes(), 'compiles': [\n"
            "        s for s in spans if s['name'] == 'xla_compile'\n"
            "        and 'cached_fn_53' in s['program']]})\n"
            "    spans.clear()\n"
            "print(json.dumps(out))\n")
        env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu",
                   JAX_ENABLE_COMPILATION_CACHE="true",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
        done = subprocess.run([sys.executable, str(script)], env=env,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stdout + done.stderr
        first, second = json.loads(done.stdout.strip().splitlines()[-1])
        (cold,), (warm,) = first["compiles"], second["compiles"]
        assert cold["cache_hit"] is False
        assert "retrieval_s" not in cold and "saved_s" not in cold
        assert first["writes"] >= 1
        assert warm["cache_hit"] is True
        assert warm["retrieval_s"] > 0
        # jax's own count: the stored compile's seconds (kept whole in
        # the entry, and a part of the cold span's, which also made the
        # key and wrote the entry) less the read's.
        stored = warm["saved_s"] + warm["retrieval_s"]
        assert stored == pytest.approx(round(stored), abs=1e-6)
        assert 0 <= stored <= cold["seconds"]
        assert second["writes"] == first["writes"]

    def test_a_group_span_says_so_in_its_record(self, captured_spans):
        with telemetry.profile_span("a_holder_53", group=True):
            with telemetry.profile_span("a_part_53"):
                pass
        part, holder = captured_spans
        assert holder["name"] == "a_holder_53"
        assert holder["extra"]["group"] is True
        assert "group" not in part["extra"]

    def test_backend_init_is_a_span(self, captured_spans):
        from ray_tpu.accelerators.tpu import init_backend
        assert init_backend() >= 1
        assert [s["name"] for s in captured_spans] == ["worker_backend_init"]


def _tiny_engine(**options):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import InferenceEngine
    from ray_tpu.models import LlamaConfig
    from ray_tpu.models.llama import init_params
    cfg = LlamaConfig(vocab_size=128, hidden=32, layers=2, heads=4,
                      kv_heads=2, head_dim=8, mlp_dim=64, max_seq_len=128,
                      dtype=jnp.float32, attention_impl="reference",
                      remat=False)
    params = init_params(cfg, jax.random.key(0))
    return InferenceEngine(params, cfg, max_slots=2, page_size=8,
                           num_pages=64, prefill_buckets=(16, 64),
                           **options), params, cfg


def _has_scope(lowered_text: str, scope: str) -> bool:
    """An operation's location names the scope: at the start of the name
    inside a scanned body, after the enclosing scopes elsewhere, inside
    ``jvp(...)`` / ``transpose(...)`` under a gradient."""
    import re
    return re.search(rf'["/(]{scope}[/)]', lowered_text) is not None


class TestServingPath:
    def test_admission_stamp_and_queue_wait(self, captured_spans):
        from ray_tpu.llm import SamplingParams
        from ray_tpu.util import metrics

        def queue_waits():
            _by, acc = metrics._aggregate_snapshots()
            return sum(v for _t, v in acc.get(
                "ray_tpu_llm_queue_wait_seconds_count", {}).values())

        eng, _params, _cfg = _tiny_engine()
        before = queue_waits()
        # Three requests on two slots: the third waits for a slot.
        reqs = [eng.submit([3, 17, 92, 5, 41 + i],
                           SamplingParams(max_tokens=4)) for i in range(3)]
        assert all(r.t_admit == 0.0 for r in reqs)
        guard = 0
        while eng.has_work() and guard < 1000:
            eng.step()
            guard += 1
        for r in reqs:
            assert r.finished and len(r.output_tokens) == 4
            assert r.t_submit <= r.t_admit <= r.t_first
        assert reqs[2].t_admit > reqs[0].t_first    # it queued behind them
        assert queue_waits() - before == 3
        names = [s["name"] for s in captured_spans]
        for name in ("engine_step", "engine_admit", "engine_prefill",
                     "engine_prefill_sync", "engine_upload",
                     "engine_decode_dispatch", "engine_logits_read",
                     "engine_sample"):
            assert name in names, name
        steps = [s for s in captured_spans if s["name"] == "engine_step"]
        reads = [s for s in captured_spans
                 if s["name"] == "engine_logits_read"]
        assert {s["extra"]["parent_id"] for s in reads} <= \
            {s["extra"]["span_id"] for s in steps}
        prefills = [s for s in captured_spans
                    if s["name"] == "engine_prefill"]
        assert sorted(s["extra"]["request_id"] for s in prefills) == \
            sorted(r.request_id for r in reqs)

    def test_program_names_and_scopes(self):
        import jax.numpy as jnp
        import numpy as np

        eng, params, _cfg = _tiny_engine(prefill_chunk=32)
        assert eng._decode.__name__ == "decode_step"
        assert {b: f.__name__ for b, f in eng._prefills.items()} == \
            {16: "prefill_16", 64: "prefill_64"}
        assert eng._write_prefill.__name__ == "write_prefill"
        decode = eng._decode.lower(
            params, eng.kv_pages, jnp.asarray(eng.slot_tokens),
            jnp.asarray(eng.slot_pos), jnp.asarray(eng.block_tables),
            jnp.asarray(eng.slot_active)).as_text(debug_info=True)
        assert "jit_decode_step" in decode
        for scope in ("attn", "attn/cache_write", "mlp", "logits"):
            assert _has_scope(decode, scope), scope
        prefill = eng._prefills[16].lower(
            params, jnp.asarray(np.zeros((1, 16), np.int32)),
            jnp.asarray(5)).as_text(debug_info=True)
        assert "jit_prefill_16" in prefill
        for scope in ("attn", "mlp", "logits"):
            assert _has_scope(prefill, scope), scope
        # The programs built on demand carry their names too.
        from ray_tpu.llm import SamplingParams
        eng.submit(list(range(1, 41)), SamplingParams(max_tokens=2))
        eng.step()
        assert eng._prefill_chunk_jit.__name__ == "prefill_chunk"

    def test_stream_of_a_request_that_finished_first(self, monkeypatch):
        """``stream`` holds the Request that ``_submit`` created: one that
        has finished (and left ``engine.running``) before the stream looks
        still streams its tokens, where it used to raise AttributeError."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.llm.serving import LLMServer
        from ray_tpu.models import LlamaConfig
        from ray_tpu.models.llama import init_params
        cfg = LlamaConfig(vocab_size=128, hidden=32, layers=2, heads=4,
                          kv_heads=2, head_dim=8, mlp_dim=64,
                          max_seq_len=128, dtype=jnp.float32,
                          attention_impl="reference", remat=False)
        server = LLMServer(
            lambda: (init_params(cfg, jax.random.key(0)), cfg),
            {"max_slots": 2, "page_size": 8, "num_pages": 64,
             "prefill_buckets": (16,)})
        try:
            submit = server._submit

            def submit_then_wait(*args):
                rid, ev, req = submit(*args)
                assert ev.wait(60)
                assert rid not in server.engine.running
                return rid, ev, req
            monkeypatch.setattr(server, "_submit", submit_then_wait)
            items = list(server.stream({"prompt_tokens": [3, 17, 92],
                                        "max_tokens": 3}))
        finally:
            server.close()
        assert [i["token"] for i in items[:-1]] and len(items) == 4
        assert items[-1] == {"finish_reason": "length", "num_tokens": 3}


class TestTrainPath:
    def test_train_step_name_scopes_and_place_span(self, captured_spans):
        import jax
        import numpy as np

        from ray_tpu.models import LlamaConfig
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.spmd import make_lm_train_step
        cfg = LlamaConfig(vocab_size=256, hidden=64, layers=2, heads=4,
                          kv_heads=2, head_dim=16, mlp_dim=128,
                          max_seq_len=128, remat="full",
                          attention_impl="flash_interpret", loss_chunks=2)
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        init_fn, step_fn, place = make_lm_train_step(cfg, mesh)
        assert step_fn.__name__ == "train_step"
        params, opt_state = init_fn(jax.random.key(0))
        batch = place({"tokens": np.zeros((2, 128), np.int32),
                       "loss_mask": np.ones((2, 128), np.int32)})
        assert [s["name"] for s in captured_spans
                if s["name"].startswith("train_")] == ["train_place_batch"]
        lowered = step_fn.lower(params, opt_state, batch)
        text = lowered.as_text(debug_info=True)
        assert "jit_train_step" in text
        for scope in ("forward_backward", "optimizer", "embed", "block/attn",
                      "block/mlp", "final_norm", "loss", "flash_fwd_d16",
                      "flash_bwd_d16"):   # heads of 16, the one pass
            assert _has_scope(text, scope), scope

    def test_latent_attention_s_products_have_scopes_of_their_own(self):
        """``models/xing4._mla`` (PR 51), in both latent models' steps:
        ``mla`` and ``rope`` stay the parents the shipped readers match,
        and the four products lie under ``mla/q``, ``mla/kv_a``,
        ``mla/kv_b`` and ``mla/out``."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import deepseek_v3_tiny, xing4_tiny
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.spmd import make_lm_train_step
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
                 for k in ("tokens", "loss_mask")}
        for cfg in (deepseek_v3_tiny(), xing4_tiny()):
            init_fn, step_fn, _ = make_lm_train_step(cfg, mesh)
            text = step_fn.lower(
                *jax.eval_shape(init_fn, jax.random.key(0)), batch).as_text(
                    debug_info=True)
            for scope in ("block/attn", "mla", "rope", "mla/q", "mla/kv_a",
                          "mla/kv_b", "mla/out", "block/moe"):
                assert _has_scope(text, scope), (type(cfg).__name__, scope)

    def test_the_hybrid_model_s_mixers_have_scopes_of_their_own(self):
        """``models/bailing_hybrid.py`` (PR 55): the KDA layers' six parts
        lie under ``block/attn`` as ``kda/proj``, ``kda/conv``, ``kda/gate``,
        ``kda/scan`` (the op's own), ``kda/norm`` and ``kda/out``; its
        latent-attention layer keeps ``mla/*`` and gains ``mla/gate``; the
        router's groups lie under ``block/moe``."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import bailing_hybrid_tiny
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.spmd import make_lm_train_step
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
                 for k in ("tokens", "loss_mask")}
        init_fn, step_fn, _ = make_lm_train_step(bailing_hybrid_tiny(), mesh)
        text = step_fn.lower(
            *jax.eval_shape(init_fn, jax.random.key(0)), batch).as_text(
                debug_info=True)
        for scope in ("block/attn", "kda/proj", "kda/conv", "kda/gate",
                      "kda/scan", "kda/norm", "kda/out", "mla", "rope",
                      "mla/q", "mla/kv_a", "mla/kv_b", "mla/gate", "mla/out",
                      "block/moe", "groups"):
            assert _has_scope(text, scope), scope

    def test_trainer_spans_reach_the_session_files(self, tmp_path):
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

        def loop(config):
            from ray_tpu import train
            for step in range(3):
                train.report({"step": step})

        rt = ray_tpu.init(num_cpus=2)
        try:
            result = JaxTrainer(
                loop, train_loop_config={},
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(name="spans",
                                     storage_path=str(tmp_path))).fit()
            assert result.error is None
            session = rt.session_dir
        finally:
            ray_tpu.shutdown()
        spans = [json.loads(line) for line in open(
            os.path.join(session, "trace", "spans.jsonl"))]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        for name in ("train_fit", "train_start_group", "train_load_fn",
                     "train_loop", "train_report"):
            assert name in by_name, (name, sorted(by_name))
        fit, group = by_name["train_fit"][0], \
            by_name["train_start_group"][0]
        assert group["parent_id"] == fit["span_id"]
        assert fit["start"] <= group["start"] <= group["end"] <= fit["end"]
        loop_span = by_name["train_loop"][0]
        reports = by_name["train_report"]
        assert [r["step"] for r in reports] == [1, 2, 3]
        assert {r["parent_id"] for r in reports} == {loop_span["span_id"]}
        assert {r["process"] for r in reports} == {loop_span["process"]}
        assert loop_span["process"] != fit["process"]
