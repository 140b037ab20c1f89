"""The counts and readers that the cell ``lfm2-24b-a2b.train-conv8k`` brought,
by hand at its shapes, and its rehearsal on the CPU.  (Cases for
``test_roofline.py`` and ``test_rehearsal.py``, kept in a file of their own:
a PR that adds a cell edits no file the benchmark already has.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import roofline, roofline_conv

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = "TPU v5 lite"
CELL = "lfm2-24b-a2b.train-conv8k"
SIZES = {"E": 2048, "L": 9, "Ld": 1, "kinds": "cacccaccc", "H": 32, "Hkv": 8,
         "D": 64, "K": 3, "M": 11776, "Me": 1536, "X": 64, "Xh": 8}


def test_gated_convolution_by_hand():
    # One token of one layer: 2,048 channels in bf16, 15 arrays a step.
    ops, moved = roofline_conv.gated_conv_passes(1, 2048, 3)
    assert moved == (4 + 4 + 7) * 2048 * 2 == 61440
    assert ops == 4 * (2 * 3 + 2) * 2048
    # 32,768 tokens: bound by memory on a v5e, 2.46 ms a layer.
    ops, moved = roofline_conv.gated_conv_passes(32768, 2048, 3)
    assert roofline.least_seconds(ops, moved, V5E) == pytest.approx(
        15 * 32768 * 2048 * 2 / 819e9)
    assert moved / 819e9 > 100 * ops / 197e12


def _facts(scopes=None, ops=None, sizes=SIZES, traced=3):
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 6 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 32768, "seq_len": 8192,
            "rows": 4, "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": 4, "expert_layers": 8,
                     "moe_traced": [{"moe_held_assignments": 16384.0}]
                     * traced,
                     "parameters": {"always": 228671744, "expert": 9437184},
                     "scopes": scopes and {"scopes": scopes}}}


def test_readers_of_the_new_scopes_and_kernels():
    from benchmark.layer_metrics import (conv_device_share,
                                         expert_rows_a_call,
                                         flash_d64_roofline,
                                         gated_conv_roofline,
                                         grouped_mm_roofline, mfu_active_pct,
                                         moe_device_share)
    scopes = {"forward_backward/block/conv/gate/gated_conv_bwd": 0.06,
              "forward_backward/jvp(block/conv/gate)/gated_conv_fwd": 0.04,
              "forward_backward/block/conv/proj": 0.3,
              "forward_backward/jvp(block/conv/proj)": 0.1,
              "forward_backward/block/attn/rope": 0.05,
              "forward_backward/block/moe/experts": 2.0}
    facts = _facts(scopes)
    assert conv_device_share.read(facts) == pytest.approx(5.0)
    assert moe_device_share.read(facts) == pytest.approx(20.0)
    least = 7 * 15 * 3 * 32768 * 2048 * 2 / 819e9
    assert gated_conv_roofline.read(facts) == pytest.approx(100 * least / 0.1)
    # 16,384 assignments a step to 8 held experts, one call a layer a step
    assert expert_rows_a_call.read(facts) == pytest.approx(2048.0)
    assert expert_rows_a_call.read(
        {**facts, "arch": {**facts["arch"], "rows_a_call": 1}}
    ) == pytest.approx(512.0)
    ops = {"jit_train_step/flash_fwd_d<bf16,f32>": 0.1,
           "jit_train_step/flash_dkv_d<bf16,bf16>": 0.2,
           # the one pass under the group (PR 60), counted since PR 61
           "jit_train_step/flash_bwd_d<f32,f32,bf16>": 0.25,
           "jit_train_step/flash_fwd<bf16,f32>": 9.0,
           "jit_train_step/flash_fwd_dv<bf16,f32>": 9.0,
           "jit_train_step/gmm<bf16>": 0.4, "jit_train_step/tgmm<bf16>": 0.2}
    facts = _facts(ops=ops)
    want = 6 * sum(roofline.least_seconds(*roofline.flash_call(
        w, 4, 32, 8, 8192, 64), V5E) for w in ("fwd", "dkv", "bwd"))
    assert flash_d64_roofline.read(facts) == pytest.approx(100 * want / 0.55)
    assert 0 < grouped_mm_roofline.read(facts) < 100
    flops = 6.0 * 3 * (228671744 * 32768 + 9437184 * 8 * 16384.0)
    assert mfu_active_pct.read(facts) == pytest.approx(
        100 * flops / 10.0 / 197e12)
    # A program without the scopes or the kernels, or another model's sizes:
    # nothing, and no error.
    empty = _facts({"forward_backward/block/moe/experts": 1.0},
                   {"jit_train_step/flash_fwd<bf16,f32>": 9.0})
    for reader in (gated_conv_roofline, conv_device_share,
                   flash_d64_roofline):
        assert reader.read(empty) is None
    other = _facts(scopes, ops, sizes={"E": 2688, "L": 13, "Hm": 64,
                                       "kinds": "MEMEM*EMEMEM*"})
    for reader in (gated_conv_roofline, flash_d64_roofline,
                   expert_rows_a_call):
        assert reader.read(other) is None
    assert expert_rows_a_call.read(_facts(traced=0)) is None
    for reader in (gated_conv_roofline, conv_device_share,
                   flash_d64_roofline, expert_rows_a_call):
        assert reader.read({"trace": None, "arch": None}) is None


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row under the same key, but the four
    keys ``reduced`` names; the count is the arch module's."""
    from benchmark import common, weights
    from benchmark.archs import lfm2_moe as arch
    config = common.load_json("configs", "lfm2-24b-a2b.json")
    published = {
        "hidden_size": 2048, "intermediate_size": 11776,
        "moe_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts_per_tok": 4,
        "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-05,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "use_expert_bias": True, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe",
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    for key, value in published.items():
        assert config[key] == value, key
    assert len(config["layer_types"]) == 40
    assert [i for i, k in enumerate(config["layer_types"])
            if k == "full_attention"] == list(range(2, 40, 4))
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert config["published"]["num_experts"] == 64
    assert config["share"]["router_outputs"] == 64
    assert set(config["correct"]) == set(config["correct_why"]) - {
        "not_judged"}
    assert weights.sizes_of(config)["D"] == 64
    s = arch.sizes_of(config)
    assert s["kinds"] == "cacccaccc" and s["Ld"] == 1 and s["Xh"] == 8
    counts = arch.parameters(s)
    assert counts["held"] == config["parameters"] == 832651520
    assert counts["expert"] == 3 * 2048 * 1536
    assert counts["always"] == 832651520 - 8 * 8 * 9437184
    with pytest.raises(ValueError, match="says otherwise"):
        arch.sizes_of({**config, "rope_theta": 10000})


def test_the_cell_rehearses_and_names_no_device_metric():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 3), "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    named = set(last["metrics_named"])
    assert "expert_rows_a_call" in named
    assert "moe_load_max_over_mean" in named
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "share" in n}
    for name in ("norm_grad_distance", "step_moments_distance",
                 "step_update_mismatch", "routing_mismatch_share",
                 "tied_embed_grad_distance"):
        assert f"[correct] name={name}" in done.stdout, name
    assert "taps_alone_distance=" in done.stdout


def test_the_control_rehearses_and_is_called_wrong():
    """``control_conv.py`` walks both of its readings on the CPU at the toy
    sizes; the int8 control moves every distance."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    for who in ("control", "program"):
        done = subprocess.run(
            [sys.executable, "benchmark/control_conv.py", CELL, "--rehearse",
             "--who", who, "--seeds", "5"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
        assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
        first = json.loads(next(line for line in done.stdout.splitlines()
                                if line.startswith('{"seed"')))
        assert first["who"] == who
        for name in ("norm_grad_distance", "tied_embed_grad_distance",
                     "taps_alone_distance", "step_moments_distance"):
            assert first[name] > 0, name
