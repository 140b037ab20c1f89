"""The reader that the cell ``kanana-2-30b-a3b.train-mla8k`` brought, the
shipped readers at its shapes, and its rehearsal on the CPU.  (Cases for
``test_roofline.py`` and ``test_rehearsal.py``, kept in a file of their own:
a PR that adds a cell edits no file the benchmark already has.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import roofline, scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SMALL = os.path.join(HERE, "small.xplane.pb")
V5E = "TPU v5 lite"
CELL = "kanana-2-30b-a3b.train-mla8k"


def _facts(by_scope=None, ops=None, rows_a_call=1):
    sizes = {"E": 2048, "L": 12, "H": 32, "dn": 128, "dr": 64, "dv": 128,
             "Xh": 16}
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 36 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 32768, "seq_len": 8192,
            "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": rows_a_call,
                     "scopes": by_scope and {"scopes": by_scope}}}


def test_latent_attention_s_share_of_the_device_by_its_scopes():
    from benchmark.layer_metrics import mla_device_share
    by = {"forward_backward/block/attn/mla/q": 1.0,
          "forward_backward/block/attn/mla/kv_b": 0.5,
          "forward_backward/block/attn/rope/rope_to_heads": 0.25,
          "forward_backward/block/attn/flash_dkv_d192v128": 2.0,
          # the unrolled dense layer's first forward, wrapped whole
          "forward_backward/jvp(block/attn)/flash_fwd_d192v128": 0.25,
          "forward_backward/block/moe/experts": 3.0,
          "forward_backward/block/mlp": 1.0}
    assert mla_device_share.read(_facts(by)) == pytest.approx(40.0)
    # A program without the scope, a model without latent attention and a
    # run without a trace: nothing, and no error.
    assert mla_device_share.read(
        _facts({"forward_backward/block/moe/experts": 1.0})) is None
    other = _facts(by)
    other["arch"]["sizes"] = {"E": 2048, "L": 9, "H": 32, "D": 128}
    assert mla_device_share.read(other) is None
    assert mla_device_share.read({"trace": None, "arch": None}) is None


@pytest.mark.parametrize("rows", [1, 4])
def test_the_shipped_roofline_reader_counts_the_rows_of_a_call(rows):
    """``mla_attn_roofline`` at this cell's shape, a call of ``layer_rows``
    rows: 36 calls of a kernel over three traced steps at one row a call
    (12 layers x 4 rows), by hand."""
    from benchmark.layer_metrics import mla_attn_roofline
    ops = {"jit_train_step/flash_fwd_d192v<bf16,f32>": 0.3,
           "jit_train_step/flash_dkv_d192v<bf16>": 0.6,
           "jit_train_step/flash_fwd<bf16,f32>": 9.0}
    want = 36 * sum(roofline.flash_call(w, rows, 32, 32, 8192, 192,
                                            128)[0]
                    for w in ("fwd", "dkv")) / 197e12
    assert mla_attn_roofline.read(_facts(ops=ops, rows_a_call=rows)) == \
        pytest.approx(100 * want / 0.9)
    # compute bound on a v5e at either size of call
    fwd, moved = roofline.flash_call("fwd", rows, 32, 32, 8192, 192, 128)
    assert roofline.least_seconds(fwd, moved, V5E) == \
        pytest.approx(fwd / 197e12)


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_the_new_reader_on_the_recorded_trace_reads_nothing():
    """A recorded trace of a program without the scope (``toy_step``'s),
    joined with a program text that names no scope: the reader returns
    None and does not raise, as on the parent's side of a traced run."""
    from benchmark.layer_metrics import mla_device_share
    loaded = trace.load(SMALL)
    facts = _facts()
    facts["trace"] = trace.reduce(loaded)
    facts["arch"]["scopes"] = scopes.seconds_by_scope(loaded, "")
    assert facts["trace"]["busy_s"] > 0
    assert mla_device_share.read(facts) is None


def test_the_arch_module_s_counts_by_hand():
    from benchmark import common
    from benchmark.archs import deepseek_v3 as arch
    config = common.load_json("configs", "kanana-2-30b-a3b.json")
    s = arch.sizes_of(config)
    counts = arch.parameters(s)
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256 \
        + 4096 * 2048
    layers = config["num_hidden_layers"]
    assert counts["expert"] == 3 * 2048 * 768 == 4_718_592
    assert counts["held"] == config["parameters"] == (
        attention + 4096 + 3 * 2048 * 6144
        + (layers - 1) * (attention + 4096 + 2048 * 128 + 3 * 2048 * 1536
                          + 16 * 4_718_592)
        + 2 * 16032 * 2048 + 2048)
    assert counts["always"] == counts["held"] - (layers - 1) * 16 * \
        4_718_592 - 16032 * 2048
    # Every published key of the catalog's row is in the file, as published
    # unless ``reduced`` names it.
    for key in config["reduced"]:
        assert config["published"][key] != config[key], key
    assert config["share"]["vocab_rows"] == [0, config["vocab_size"]]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    with pytest.raises(ValueError, match="config.json"):
        arch.sizes_of({**config, "q_lora_rank": 1536})


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
    assert {"expert_rows_a_call", "moe_load_max_over_mean",
            "place_batch_ms", "step_period_max_over_median"} <= named
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "share" in n}
    for name in ("norm_grad_distance", "step_moments_distance",
                 "step_update_mismatch", "routing_mismatch_share"):
        assert f"[correct] name={name}" in done.stdout
