"""The two readers of a stalled step (PERF.md, PR 38) on hand-made spans,
the two entries that name them, and the rehearsal that walks them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.layer_metrics import hbm_held_share, step_period_max_over_median

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
T0 = 1_790_000_000.0          # window_start on the wall clock
GIB = 2 ** 30

CELLS = {"train": ["yi-coder-1.5b.train-sft4k", "mistral-7b-v0.3.train-fsdp4"],
         "moe8k": ["trinity-mini.train-moe8k"],
         "loop4k": ["ouro-2.6b.train-loop4k"],
         "eva32k": ["evabyte-6.5b.train-eva32k"]}


def span(name, start, length, process=200, **extra):
    return {"name": name, "cat": "x", "start": T0 + start,
            "end": T0 + start + length, "process": process, "thread": 1,
            "span_id": 1, "parent_id": None, **extra}


def steps(periods, first_step=3, process=200):
    """(numbered ``train_place_batch`` spans ``periods`` apart, the window's
    length): one span before the window, the first of the window at its
    start, the last one's step ending the window 1.5 s after it started,
    and the check's span after that."""
    out = [span("train_place_batch", -1.5, 0.001, process,
                step=first_step - 1)]
    t = 0.0
    for i, p in enumerate(list(periods) + [1.5 + 5.0]):
        out.append(span("train_place_batch", t, 0.001, process,
                        step=first_step + i))
        t += p
    return out, t - 5.0


def facts_of(found, window_s, trace_steps=3):
    return {"spans": found, "window_start": T0, "attempted": 1,
            "tokens_per_step": 1000, "train_tok_s_chip": 1000.0 / window_s,
            "device": {"count": 1}, "trace_steps": trace_steps}


def test_a_window_without_a_stall_reads_near_one():
    found, end = steps([1.9, 2.4, 1.6, 1.6, 2.8] + [1.5, 1.5, 1.53, 1.5,
                                                    1.5, 1.47])
    assert step_period_max_over_median.read(facts_of(found, end)) == \
        pytest.approx(1.53 / 1.5)


def test_the_traced_periods_are_left_out_and_a_stall_is_not():
    # Periods 0..4 hold the profiler's start and stop (2.4, 2.8): left out
    # whatever they read.  Period 8 stalled.
    found, end = steps([1.5, 2.4, 1.6, 1.6, 2.8] + [1.5, 1.5, 1.5, 4.5, 1.5,
                                                    1.5, 1.5])
    facts = facts_of(found, end)
    assert step_period_max_over_median.read(facts) == pytest.approx(3.0)
    # one traced step: only the first three periods are left out, so the
    # profiler's stop of a three-step trace (2.8) would count
    found, end = steps([1.5, 2.4, 1.6, 1.6, 2.8] + [1.5] * 6)
    facts = facts_of(found, end)
    assert step_period_max_over_median.read(facts) == pytest.approx(1.0)
    assert step_period_max_over_median.read(
        {**facts, "trace_steps": 1}) == pytest.approx(2.8 / 1.5)


def test_under_five_periods_there_is_no_number():
    found, end = steps([1.5] * 5 + [1.5] * 4)
    assert step_period_max_over_median.read(facts_of(found, end)) is None
    found, end = steps([1.5] * 5 + [1.5] * 5)
    assert step_period_max_over_median.read(facts_of(found, end)) == \
        pytest.approx(1.0)


def test_spans_without_a_step_number_give_none_and_never_raise():
    # the parent's program: the span is there, its number is not
    found, end = steps([1.5] * 12)
    plain = [{k: v for k, v in s.items() if k != "step"} for s in found]
    assert step_period_max_over_median.read(facts_of(plain, end)) is None
    assert step_period_max_over_median.read(facts_of([], end)) is None
    assert hbm_held_share.read(facts_of(plain, end)) is None


def test_two_processes_the_worst_counts():
    one, end = steps([1.5] * 12)
    two, _ = steps([1.5] * 8 + [3.0] + [1.5] * 2, process=300)
    assert step_period_max_over_median.read(facts_of(one + two, end)) == \
        pytest.approx(2.0)


def sample(start, in_use, reserved, limit=16 * GIB, **extra):
    state = {"bytes_in_use": in_use, "bytes_reserved": reserved, **extra}
    if limit is not None:
        state["bytes_limit"] = limit
    return span("worker_sample", start, 0.0003, cpu_user_s=1.0, **state)


def test_hbm_held_share_is_the_largest_inside_the_window():
    found = [sample(-1.0, 15 * GIB, 0),             # before the window
             sample(1.0, 8 * GIB, 4 * GIB),
             sample(3.0, 9 * GIB, 5 * GIB),         # 14 of 16
             sample(5.0, 8 * GIB, 5 * GIB),
             sample(11.0, 1 * GIB, 15 * GIB)]       # after it
    assert hbm_held_share.read(facts_of(found, 10.0)) == \
        pytest.approx(100.0 * 14 / 16)


def test_no_bytes_limit_no_share():
    # a CPU's sample holds the process's state and no memory reading
    found = [span("worker_sample", 1.0, 0.0003, cpu_user_s=1.0),
             span("worker_sample", 3.0, 0.0003, cpu_user_s=1.2)]
    assert hbm_held_share.read(facts_of(found, 10.0)) is None
    mixed = [sample(1.0, 8 * GIB, 4 * GIB),
             sample(3.0, 8 * GIB, 4 * GIB, limit=None)]
    assert hbm_held_share.read(facts_of(mixed, 10.0)) is None
    assert hbm_held_share.read(facts_of([], 10.0)) is None


def _entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_two_entries():
    """One entry a reader since PR 61 (eight suffixed copies before it):
    found by name, listing every train cell in ``workloads``' order."""
    bench = _entries()
    cells = [w["name"] for w in bench["workloads"]]
    for stem in ("step_period_max_over_median", "hbm_held_share"):
        found = [m for m in bench["per_layer"]
                 if m["name"].split(".")[0] == stem]
        assert [m["name"] for m in found] == [stem]
        m = found[0]
        assert m["workloads"] == cells
        assert all(cell in m["workloads"]
                   for listed in CELLS.values() for cell in listed)
        assert (m["source"], m["moves"], m["better"]) == (
            "program_span", "train_tok_s_chip", "lower")
        assert (m["unit"], m["layer"]) == (
            ("ratio", "train step") if stem.startswith("step")
            else ("%", "device"))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_the_rehearsal_walks_the_new_entries(suffix):
    """On the CPU the period's reader has spans to read and the share's has
    no ``bytes_limit``: the first is named, the second is not."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[suffix][-1],
         "--seed", str(2 ** 31 + 38), "--seconds", "4", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    walked = [line for line in done.stdout.splitlines()
              if line.startswith("[metric]")]
    assert any("name=step_period_max_over_median " in line
               and "value=" in line for line in walked), walked
    assert any("name=hbm_held_share value=None" in line
               for line in walked), walked
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert "step_period_max_over_median" in last["metrics_named"]
    assert "hbm_held_share" not in last["metrics_named"]
