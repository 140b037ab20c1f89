"""The four readers of ``setup_s`` by phase (PERF.md, PR 53) on hand-made
spans, the four entries that name them, and the rehearsal that walks
them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import setup_phases
from benchmark.layer_metrics import (cache_fetch_s, cold_compile_s,
                                     setup_unnamed_s, trace_lower_s)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
W = 1_790_000_000.0           # window_start on the wall clock
SETUP_S = 100.0               # run.py's T0 is W - 100
HEAD, WORKER, OTHER = 100, 200, 300


def span(name, start, length, process=WORKER, **extra):
    """A span that starts ``start`` seconds after run.py's T0."""
    return {"name": name, "cat": "x", "start": W - SETUP_S + start,
            "end": W - SETUP_S + start + length, "process": process,
            "thread": 1, "span_id": 1, "parent_id": None, **extra}


def compiled(start, seconds, hit, process=WORKER, program="jit(step)"):
    return span("xla_compile", start, seconds, process, program=program,
                seconds=seconds, cache_hit=hit)


def facts_of(found, setup_s=SETUP_S):
    return {"spans": found, "window_start": W, "setup_s": setup_s}


def chip(process=WORKER, start=3.0):
    return span("worker_backend_init", start, 10.0, process, import_s=3.0)


def test_nested_traces_and_a_helper_inside_a_lowering_count_once():
    found = [chip(),
             span("jax_trace", 20.0, 30.0, program="train_step"),
             span("jax_trace", 25.0, 5.0, program="kernel", nested=True),
             span("jax_trace", 40.0, 8.0, program="scoped", nested=True),
             span("jax_lower", 50.0, 20.0, program="jit(train_step)"),
             span("jax_trace", 55.0, 1.0, program="helper", nested=True),
             span("jax_trace", 80.0, 2.0, program="init")]
    assert trace_lower_s.read(facts_of(found)) == pytest.approx(52.0)


def test_spans_after_the_window_s_start_are_left_out():
    found = [chip(),
             span("jax_trace", 20.0, 5.0, program="train_step"),
             span("jax_lower", 25.0, 5.0, program="jit(train_step)"),
             compiled(30.0, 4.0, True), compiled(35.0, 1.5, False),
             # the check after the window traces and compiles its own
             span("jax_trace", 150.0, 9.0, program="program_loss"),
             span("jax_lower", 160.0, 9.0, program="jit(program_loss)"),
             compiled(170.0, 6.0, False), compiled(180.0, 3.0, True),
             # one that ends inside the window is not set-up's either
             span("jax_trace", 99.5, 1.0, program="late")]
    facts = facts_of(found)
    assert trace_lower_s.read(facts) == pytest.approx(10.0)
    assert cache_fetch_s.read(facts) == pytest.approx(4.0)
    assert cold_compile_s.read(facts) == pytest.approx(1.5)


def test_several_workers_the_slowest_and_only_those_that_hold_chips():
    found = [chip(WORKER), chip(OTHER),
             span("jax_trace", 20.0, 5.0, WORKER),
             span("jax_trace", 20.0, 9.0, OTHER),
             compiled(30.0, 2.0, True, WORKER),
             compiled(30.0, 7.0, False, WORKER),
             compiled(30.0, 3.0, True, OTHER),
             compiled(34.0, 1.0, False, OTHER),
             # the head compiles nothing for the chips: not counted
             span("jax_trace", 1.0, 50.0, HEAD),
             compiled(1.0, 50.0, True, HEAD)]
    facts = facts_of(found)
    assert trace_lower_s.read(facts) == pytest.approx(9.0)
    assert cache_fetch_s.read(facts) == pytest.approx(3.0)
    assert cold_compile_s.read(facts) == pytest.approx(7.0)


def test_all_fetched_reads_zero_cold_and_the_reverse():
    warm = facts_of([chip(), compiled(30.0, 4.0, True)])
    assert cold_compile_s.read(warm) == 0.0
    assert isinstance(cold_compile_s.read(warm), float)
    cold = facts_of([chip(), compiled(30.0, 40.0, False)])
    assert cache_fetch_s.read(cold) == 0.0
    assert cold_compile_s.read(cold) == pytest.approx(40.0)


def test_jax_s_seconds_are_taken_where_the_span_holds_them():
    s = compiled(30.0, 4.0, True)
    s["end"] += 0.5             # the span's clock and jax's own differ
    assert cache_fetch_s.read(facts_of([chip(), s])) == pytest.approx(4.0)
    del s["seconds"]
    assert cache_fetch_s.read(facts_of([chip(), s])) == pytest.approx(4.5)


@pytest.mark.parametrize("reader", [trace_lower_s, cache_fetch_s,
                                    cold_compile_s, setup_unnamed_s])
def test_a_run_with_no_such_span_gives_none_and_never_raises(reader):
    # the parent's program: xla_compile without the new fields is read,
    # the rest is not there
    assert reader.read(facts_of([])) is None
    assert reader.read({"spans": None, "window_start": W,
                        "setup_s": SETUP_S}) is None
    older = [chip(), span("train_fit", 2.0, 200.0, HEAD),
             span("train_loop", 14.0, 180.0),
             span("xla_compile", 30.0, 4.0, program="jit(step)",
                  seconds=4.0, cache_hit=True)]
    got = reader.read(facts_of(older))
    if reader in (cache_fetch_s, cold_compile_s):
        assert got == pytest.approx(4.0 if reader is cache_fetch_s else 0.0)
    else:
        assert got is None


def setup_spans():
    """A run whose set-up is 100 s: 1 s before the first span, 2 s of
    ``ray_tpu.init``, the worker's start, its backend, 5 s that nothing
    names, the compile path, the step-0 batch before it, 3 s between the
    compile and the second batch, and the warm-up to the window."""
    return [
        span("runtime_init", 1.0, 2.0, HEAD),
        span("train_fit", 3.0, 300.0, HEAD, group=True),
        span("train_start_group", 3.0, 1.0, HEAD),
        span("worker_start", 3.0, 0.5, HEAD, pid=WORKER),
        chip(WORKER, 3.5),                              # to 13.5
        span("train_loop", 13.5, 280.0, group=True),
        span("train_load_fn", 13.0, 0.5),
        # 13.5 .. 18.5: imports and the state's making, unnamed
        span("train_place_batch", 18.5, 0.5, step=0),
        span("jax_trace", 19.0, 40.0, program="train_step"),
        span("jax_trace", 30.0, 5.0, program="kernel", nested=True),
        span("jax_lower", 59.0, 20.0, program="jit(train_step)"),
        compiled(79.0, 6.0, True),                      # to 85
        # 85 .. 88: the runner reads the program's text, the first step
        span("train_place_batch", 88.0, 0.01, step=1),
        span("train_place_batch", 92.0, 0.01, step=2),
        span("train_place_batch", 96.0, 0.01, step=3),
        span("train_place_batch", 100.0, 0.01, step=4),  # the window's
        span("worker_sample", 50.0, 0.0003), span("py_gc", 16.0, 0.002),
        compiled(170.0, 6.0, False)]                    # the check's


def test_setup_unnamed_is_what_no_span_and_no_warm_up_covers():
    # 1 (before runtime_init) + 5 (13.5 .. 18.5, less the 2 ms of py_gc)
    # + 3 (85 .. 88)
    assert setup_unnamed_s.read(facts_of(setup_spans())) == \
        pytest.approx(9.0 - 0.002)


def test_a_group_span_names_nothing_and_step_zero_starts_no_warm_up():
    found = setup_spans()
    # without the flag the holders would cover everything after 3 s
    plain = [{k: v for k, v in s.items() if k != "group"} for s in found]
    assert setup_unnamed_s.read(facts_of(plain)) is None
    # counted from step 0's batch the stretch would hide the hole at 85
    only_zero = [s for s in found if not (
        s["name"] == "train_place_batch" and 1 <= s["step"] <= 3)]
    assert setup_unnamed_s.read(facts_of(only_zero)) == \
        pytest.approx(1.0 + 5.0 - 0.002 + 15.0)
    # spans of a process that started before T0 are cut at T0
    early = found + [span("node_up", -50.0, 50.5, HEAD)]
    assert setup_unnamed_s.read(facts_of(early)) == \
        pytest.approx(9.0 - 0.002 - 0.5)


def test_a_trace_s_own_seconds_leave_its_nested_traces_out():
    found = [s for s in setup_spans()
             if s["name"] in ("jax_trace", "jax_lower")]
    found.append(span("jax_trace", 31.0, 2.0, program="inner", nested=True))
    # another thread's trace at the same time is nobody's child
    found.append({**span("jax_trace", 32.0, 1.0, program="elsewhere"),
                  "thread": 2})
    assert setup_phases.own_seconds(found) == {
        ("jax_trace", "train_step"): pytest.approx(35.0),
        ("jax_trace", "kernel"): pytest.approx(3.0),
        ("jax_trace", "inner"): pytest.approx(2.0),
        ("jax_trace", "elsewhere"): pytest.approx(1.0),
        ("jax_lower", "jit(train_step)"): pytest.approx(20.0)}


def test_holes_are_named_by_their_edges():
    lo = W - SETUP_S
    got = setup_phases.holes(setup_spans(), lo, W, 2.0)
    # the 2 ms py_gc at 16 s and the 10 ms batches cut nothing in two
    assert [(round(h["at_s"], 3), round(h["seconds"], 3), h["after"],
             h["before"]) for h in got] == [
        (13.5, 5.0, "worker_backend_init", "train_place_batch"),
        (85.0, 15.0, "xla_compile", "window")]
    # with the ticks as edges, as a span of any length would be
    assert len(setup_phases.holes(setup_spans(), lo, W, 2.0, tick=0.0)) == 6


NEW = {"trace_lower_s": "compile cache", "cache_fetch_s": "compile cache",
       "cold_compile_s": "compile cache", "setup_unnamed_s": "entry points"}


def test_the_four_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    got = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    # found by name, in this order among themselves: where they stand in
    # the table is no one's to assert (PR 61 merged the copies before them)
    assert list(got) == list(NEW)
    for name, m in got.items():
        assert m == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": NEW[name],
                     "moves": "setup_s"}
    # no list of cells: every cell reports them, as it does setup_s
    assert "workloads" not in next(
        m for m in bench["end_to_end"] if m["name"] == "setup_s")


def test_the_rehearsal_walks_the_four_entries():
    """On the CPU with the cache off everything compiles here: the trace,
    the cold compile and the unnamed rest are read, the fetch is 0."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "yi-coder-1.5b.train-sft4k", "--seed", str(2 ** 31 + 53),
         "--seconds", "3", "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    walked = {}
    for line in done.stdout.splitlines():
        if line.startswith("[metric] name=") and " value=" in line:
            name, value = line[len("[metric] name="):].split(" value=")
            walked[name] = value.split(" unit=")[0]
    assert float(walked["trace_lower_s"]) > 0
    assert float(walked["cold_compile_s"]) > 0
    assert float(walked["cache_fetch_s"]) == 0
    assert 0 < float(walked["setup_unnamed_s"]) < float(walked["ready_s"])
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(NEW) <= set(last["metrics_named"])
