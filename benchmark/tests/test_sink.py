"""The counts and readers that the cell ``mimo-v2-flash.train-sink8k`` brought,
by hand at its shapes, its configuration's file, its arch module's counts and
its rehearsal on the CPU.  (Cases for ``test_roofline.py`` and
``test_rehearsal.py``, kept in a file of their own: a PR that adds a cell
edits no file the benchmark already has.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import roofline, roofline_sink

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = "TPU v5 lite"
CELL = "mimo-v2-flash.train-sink8k"
SIZES = {"E": 4096, "L": 6, "Ld": 0, "kinds": "wwwwwf", "H": 16, "Hkv": 1,
         "Hskv": 2, "D": 192, "Dv": 128, "W": 128, "Me": 2048, "X": 256,
         "Xh": 8}


def test_the_two_kinds_calls_by_hand():
    """A window layer's forward over the band of 128 at 8,192 tokens: 128 x
    129 / 2 + 8,064 x 128 pairs a head, 2 x (192 + 128) operations a pair,
    16 heads; bytes q and o once a query head, k and v once a KEY head (2),
    LSE in float32 and the 16 sinks.  The full layer's over the triangle,
    one key head."""
    pairs = 128 * 129 // 2 + (8192 - 128) * 128
    ops, moved = roofline_sink.window_call("fwd", 1, SIZES, 8192)
    assert pairs == 1_040_448 and ops == 2 * 16 * pairs * 320
    assert moved == 2 * 8192 * (16 + 2) * (192 + 128) + 16 * 8192 * 4 \
        + 16 * 4
    # memory bound by this floor: 0.116 ms against 0.054 of products
    assert roofline.least_seconds(ops, moved, V5E) == pytest.approx(
        moved / 819e9)
    for which in ("dq", "dkv"):     # no sink crosses HBM in the backward
        assert roofline_sink.window_call(which, 2, SIZES, 8192) == \
            roofline.flash_call(which, 2, 16, 2, 8192, 192, 128, 128)
    ops, moved = roofline_sink.full_call("fwd", 1, SIZES, 8192)
    assert ops == 2 * 16 * (8192 * 8193 // 2) * 320
    assert moved == 2 * 8192 * (16 + 1) * (192 + 128) + 16 * 8192 * 4
    assert roofline.least_seconds(ops, moved, V5E) == pytest.approx(
        ops / 197e12)
    # the three kernels of a full layer a row: 1.58 TFLOP
    assert sum(roofline_sink.full_call(w, 1, SIZES, 8192)[0]
               for w in ("fwd", "dq", "dkv")) == pytest.approx(1.58e12,
                                                               rel=0.01)


def _facts(by_scope=None, ops=None, sizes=SIZES):
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 6 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 16384, "seq_len": 8192,
            "rows": 2, "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": 1, "expert_layers": 6,
                     "moe_traced": [{"moe_held_assignments": 4096.0}] * 3,
                     "parameters": {"always": 224710736, "expert": 25165824},
                     "scopes": by_scope and {"scopes": by_scope}}}


def test_the_sink_readers_by_their_scopes_and_names():
    from benchmark.layer_metrics import (expert_rows_a_call,
                                         gqa192_attn_roofline,
                                         grouped_mm_roofline, mfu_active_pct,
                                         sink_device_share,
                                         sink_window_roofline)
    by = {"forward_backward/block/attn/block/attn_window/flash_fwd": 0.25,
          "forward_backward/block/attn/block/attn_window": 0.25,
          "forward_backward/block/attn/block/attn_window/attn/sink_grad":
          0.125,
          "forward_backward/block/attn/block/attn_full": 0.375,
          "forward_backward/block/attn": 2.0,
          "forward_backward/block/moe/experts": 3.0}
    ops = {"jit_train_step/flash_fwd_d192v128_w128_sink<bf16,f32>": 0.012,
           "jit_train_step/flash_dq_d192v128_w<bf16>": 0.024,
           "jit_train_step/flash_dkv_d192v128_w<bf16,bf16>": 0.03,
           "jit_train_step/flash_fwd_d192v<bf16,f32>": 0.03,
           "jit_train_step/flash_dq_d192v<bf16>": 0.04,
           "jit_train_step/flash_dkv_d192v<f32,f32>": 0.05,
           "jit_train_step/flash_fwd<bf16,f32>": 9.0,
           "jit_train_step/gmm<bf16>": 0.4, "jit_train_step/tgmm<bf16>": 0.2}
    facts = _facts(by, ops)
    assert sink_device_share.read(facts) == pytest.approx(10.0)
    least = lambda call, which: 6 * roofline.least_seconds(
        *call(which, 1, SIZES, 8192), V5E)
    kinds = ("fwd", "dq", "dkv")
    assert sink_window_roofline.read(facts) == pytest.approx(
        100 * sum(least(roofline_sink.window_call, w) for w in kinds)
        / 0.066)
    assert gqa192_attn_roofline.read(facts) == pytest.approx(
        100 * sum(least(roofline_sink.full_call, w) for w in kinds) / 0.12)
    assert 0 < sink_window_roofline.read(facts) < 100
    # the readers it shares: 4,096 assignments a step to 8 held experts, a
    # row a call of the step's two
    assert expert_rows_a_call.read(facts) == pytest.approx(256.0)
    assert 0 < grouped_mm_roofline.read(facts) < 100
    flops = 6.0 * 3 * (224710736 * 16384 + 25165824 * 6 * 4096.0)
    assert mfu_active_pct.read(facts) == pytest.approx(
        100 * flops / 10.0 / 197e12)
    # A program without the scopes or the kernels, another model's sizes,
    # a run without a trace: nothing, and no error.
    bare = _facts({"forward_backward/block/moe/experts": 1.0},
                  {"jit_train_step/flash_fwd<bf16,f32>": 9.0})
    other = _facts(by, ops, sizes={"E": 4096, "L": 4, "H": 80, "Hkv": 16,
                                   "dn": 128, "dr": 64, "dv": 128, "W": 128})
    for reader in (sink_device_share, sink_window_roofline,
                   gqa192_attn_roofline):
        assert reader.read(bare) is None, reader.__name__
        assert reader.read(other) is None, reader.__name__
        assert reader.read({"trace": None, "arch": None}) is None


def test_the_mass_s_reader_reads_nothing_without_a_session(monkeypatch,
                                                           tmp_path):
    from benchmark.layer_metrics import sink_mass_mean
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    assert sink_mass_mean.read({}) is None
    trace = tmp_path / f"session_1_{os.getpid()}" / "trace"
    trace.mkdir(parents=True)
    (trace / "spans.jsonl").write_text("")
    assert sink_mass_mean.read({}) is None       # a parent: no counters
    (trace / "counters.json").write_text(json.dumps({"samples": {
        "ray_tpu_moe_load_max_over_mean": [{"value": 1.5}]}}))
    assert sink_mass_mean.read({}) is None       # ... or no such gauge
    (trace / "counters.json").write_text(json.dumps({"samples": {
        "ray_tpu_attn_sink_mass_mean": [{"value": 0.46}]}}))
    assert sink_mass_mean.read({}) == pytest.approx(0.46)


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row under the same key, but the seven
    keys ``reduced`` names; the count is the arch module's and the
    issue's."""
    from benchmark import common, weights
    from benchmark.archs import mimo_v2_flash as arch
    config = common.load_json("configs", "mimo-v2-flash.json")
    published = {
        "hidden_size": 4096, "intermediate_size": 16384, "head_dim": 192,
        "v_head_dim": 128, "swa_head_dim": 192, "swa_v_head_dim": 128,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
        "sliding_window": 128, "sliding_window_size": 128,
        "attention_chunk_size": 128, "attention_value_scale": 0.707,
        "partial_rotary_factor": 0.334, "rope_theta": 5000000,
        "swa_rope_theta": 10000, "layernorm_epsilon": 1e-05,
        "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "n_shared_experts": None,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None}
    for key, value in published.items():
        assert config[key] == value, key
    assert len(config["hybrid_layer_pattern"]) == 48
    assert [i for i, k in enumerate(config["hybrid_layer_pattern"])
            if k == 0] == [0] + list(range(5, 48, 6))
    assert config["moe_layer_freq"] == [0] + [1] * 47
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "num_attention_heads",
        "swa_num_attention_heads", "swa_num_key_value_heads",
        "num_key_value_heads", "vocab_size"]
    assert {k: config[k] for k in config["reduced"]} == {
        "num_hidden_layers": 6, "n_routed_experts": 8,
        "num_attention_heads": 16, "swa_num_attention_heads": 16,
        "swa_num_key_value_heads": 2, "num_key_value_heads": 1,
        "vocab_size": 19072}
    assert {k: config["published"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 48, "n_routed_experts": 256,
        "num_attention_heads": 64, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 8, "num_key_value_heads": 4,
        "vocab_size": 152576}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "mimo-v2-flash")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert config["share"]["router_outputs"] == 256
    assert set(config["correct"]) == set(config["correct_why"]) - {
        "not_judged", "without_the_sink"}
    for key in ("value_scale", "partial_rotary", "sink", "routing",
                "left_out"):
        assert key in config["assumed"], key
    assert weights.sizes_of(config)["D"] == 192
    s = arch.sizes_of(config)
    assert s["kinds"] == "wwwwwf" and s["Ld"] == 0 and s["Xh"] == 8
    assert (s["H"], s["Hkv"], s["Hskv"], s["Hp"], s["R"]) == (16, 1, 2, 64,
                                                              64)
    counts = arch.parameters(s)
    assert counts["held"] == config["parameters"] == 1510789200
    assert counts["expert"] == 3 * 4096 * 2048
    assert counts["always"] == 1510789200 - 6 * 8 * 25165824 \
        - 19072 * 4096
    with pytest.raises(ValueError, match="says otherwise"):
        arch.sizes_of({**config, "add_full_attention_sink_bias": True})
    with pytest.raises(ValueError, match="one share"):
        arch.sizes_of({**config, "swa_num_key_value_heads": 4})
    # the ladder's last rung: heads eight ways, a full layer's one key head
    # held by the two shares under it
    eight = arch.sizes_of({**config, "num_attention_heads": 8,
                           "swa_num_attention_heads": 8,
                           "swa_num_key_value_heads": 1})
    assert (eight["H"], eight["Hkv"], eight["Hskv"]) == (8, 1, 1)
    assert arch.parameters(eight)["held"] == 1441321000


def test_the_cell_rehearses_and_names_every_entry_a_cpu_can():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", ())}
    step, kernels = "train step", "kernels"
    brought = {
        "sink_window_roofline": ("%", "higher", "device_trace", kernels),
        "gqa192_attn_roofline": ("%", "higher", "device_trace", kernels),
        "sink_device_share": ("%", "lower", "device_trace", step),
        "sink_mass_mean": ("share", "higher", "program_counter", step)}
    assert entries == set(brought) | {
        "idle_share", "cluster_start_s", "worker_chip_s", "place_batch_ms",
        "compiles_in_window", "step_period_max_over_median",
        "hbm_held_share", "grouped_mm_roofline", "moe_device_share",
        "mfu_active_pct", "moe_load_max_over_mean", "expert_rows_a_call"}
    for m in bench["per_layer"]:
        if m["name"] in brought:
            assert (m["unit"], m["better"], m["source"], m["layer"]) == \
                brought[m["name"]], m
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tok_s_chip"
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(brought)
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "mimo-v2-flash", "traffic": "train-sink8k",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    assert CELL == next(m for m in bench["end_to_end"] if m["name"]
                        == "train_tok_s_chip")["workloads"][-1]
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 3), "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    named = set(last["metrics_named"])
    for name in entries:
        assert f"[metric] name={name} " in done.stdout, name
    assert {"expert_rows_a_call", "moe_load_max_over_mean", "place_batch_ms",
            "step_period_max_over_median", "sink_mass_mean"} <= named
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "device_share" in n}
    for name in ("norm_grad_distance", "step_moments_distance",
                 "step_update_mismatch", "routing_mismatch_share"):
        assert f"[correct] name={name}" in done.stdout, name
    assert "sinks_alone_distance=" in done.stdout
    assert "[sink] sink_grad_s=" in done.stdout


def test_the_control_rehearses_and_is_called_wrong():
    """``control_sink.py`` walks its three readings on the CPU at the toy
    sizes; the int8 control moves every distance, and the reference without
    its sinks is called wrong by ``norm_grad_distance``."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    for who in ("control", "program", "nosink"):
        done = subprocess.run(
            [sys.executable, "benchmark/control_sink.py", CELL, "--rehearse",
             "--who", who, "--seeds", "5"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
        assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
        first = json.loads(next(line for line in done.stdout.splitlines()
                                if line.startswith('{"seed"')))
        assert first["who"] == who
        for name in ("norm_grad_distance", "sinks_alone_distance",
                     "step_moments_distance"):
            assert first[name] > 0, name
        if who == "nosink":
            assert "norm_grad_distance" in first["called_wrong_by"]
            assert first["sinks_alone_distance"] == pytest.approx(1.0)
