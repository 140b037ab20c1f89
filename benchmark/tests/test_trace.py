"""The reduction from a trace to the readers' facts: on intervals built by
hand, and on a small trace recorded on a TPU v5e (three runs of a jitted
``toy_step`` of eight [2048, 2048] matmuls, 30 ms of host sleep after each,
under ``jax.profiler.start_trace``)."""

import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "small.xplane.pb")


def test_union_and_gaps():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert trace.union_seconds(spans) == 3.0
    assert trace.gaps(spans, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]


def test_exposed_collective_time():
    ops = [("fusion.1", 0.0, 2.0), ("all-gather-start.3", 1.0, 3.0),
           ("%reduce-scatter.2 = ...", 5.0, 6.0), ("fusion.2", 5.5, 7.0)]
    busy, exposed = trace.exposed_seconds(ops)
    assert busy == 3.0
    assert exposed == pytest.approx(1.0 + 0.5)


def test_idle_goes_to_the_host_event_that_covers_it():
    host = [("main", "step", 0.0, 10.0), ("main", "np.asarray", 2.0, 3.1),
            ("io", "sleep", 6.0, 6.2)]
    idle = [(2.05, 3.05), (6.0, 6.0004), (8.0, 8.5)]
    got = trace.idle_attribution(idle, host)
    assert got["np.asarray"] == pytest.approx(1.0)
    assert got["step"] == pytest.approx(0.5)
    assert got["gaps_under_1_ms"] == pytest.approx(0.0004)


def test_names_from_instruction_text():
    flash = ('%closed_call.11 = (bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}, '
             'f32[64,4096,128]{2,1,0}) custom-call(bf16[64,4096,128]{2,1,0} '
             '%a, bf16[64,4096,128] %b), custom_call_target="tpu_custom_call"')
    assert trace.label(flash) == "closed_call<bf16,f32>"
    assert trace.opcode(flash) == "custom-call"
    loop = "%while.42 = (s32[], bf16[3]{0}) while((s32[], bf16[3]) %t), body=%b"
    assert trace.opcode(loop) == "while" and trace.label(loop) == "while"
    assert trace.label("%fusion.12.clone = f32[8]{0} fusion(f32[8] %x)") \
        == "fusion"


def test_reduce_on_a_hand_built_trace():
    dev = {"modules": [("jit_step(7)", 0.0, 1.0), ("jit_step(7)", 2.0, 3.0)],
           "ops": [("fusion.1", 0.0, 0.6), ("flash_kernel.2", 0.6, 1.0),
                   ("fusion.1", 2.0, 2.6), ("flash_kernel.2", 2.6, 3.0)]}
    r = trace.reduce({"devices": {"/device:TPU:0": dev},
                      "host": [("main", "float()", 0.9, 2.1)]})
    assert r["busy_s"] == pytest.approx(2.0) and r["window_s"] == 3.0
    assert trace.ops_matching(r, "flash") == (pytest.approx(0.8), 2)
    assert trace.modules_with_op(r, "flash") == ["jit_step(7)"]
    assert trace.start_to_start(r["module_runs"]["jit_step(7)"]) == [2.0]
    assert r["breakdown"]["idle_gaps"] == [["float()", pytest.approx(1.0)]]


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_reduce_on_the_recorded_trace():
    r = trace.reduce(trace.load(SMALL))
    runs = next(v for k, v in r["module_runs"].items()
                if k.startswith("jit_toy_step("))
    assert len(runs) == 3
    assert 0 < r["busy_s"] < r["window_s"]
    # 30 ms of sleep after each of the first two runs lies in the window
    idle = r["window_s"] - r["busy_s"]
    assert 0.055 < idle < 0.2
    gaps = trace.start_to_start(runs)
    assert all(g > 0.03 for g in gaps)
    assert r["breakdown"]["device_ops"][0][0].startswith("jit_toy_step(")
    assert r["breakdown"]["idle_gaps"][0][0] == "$time sleep"
