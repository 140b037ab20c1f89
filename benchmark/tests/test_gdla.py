"""The readers that the cell ``motif-3-beta.train-gdla8k`` brought, the
shipped readers at its shapes, its arch module's counts and its rehearsal on
the CPU.  (Cases for ``test_roofline.py`` and ``test_rehearsal.py``, kept in
a file of their own: a PR that adds a cell edits no file the benchmark
already has.)"""

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
CELL = "motif-3-beta.train-gdla8k"


def _facts(by_scope=None, ops=None, rows_a_call=1):
    sizes = {"E": 4096, "L": 4, "H": 80, "Hkv": 16, "noise": 16, "dn": 128,
             "dr": 64, "dv": 128, "W": 128, "Xh": 8, "n": 4}
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 3 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 8192, "seq_len": 8192,
            "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": rows_a_call,
                     "scopes": by_scope and {"scopes": by_scope}}}


def test_the_banded_grouped_call_s_operations_and_bytes_by_hand():
    """Forward over the band of 128 at 8,192 tokens: 128 x 129 / 2 + 8,064
    x 128 pairs a head, 2 x (192 + 128) operations a pair, 80 heads; bytes
    q and o once a query head, k and v once a KEY head, LSE in float32."""
    pairs = 128 * 129 // 2 + (8192 - 128) * 128
    ops, moved = roofline.flash_call("fwd", 1, 80, 16, 8192, 192, 128,
                                          128)
    assert pairs == 1_040_448 and ops == 2 * 80 * pairs * 320
    assert moved == 2 * 8192 * (80 * (192 + 128) + 16 * (192 + 128)) \
        + 80 * 8192 * 4 == 505_937_920
    # the band's call is memory bound on a v5e by this floor, the full
    # triangle's compute bound
    assert roofline.least_seconds(ops, moved, V5E) == \
        pytest.approx(moved / 819e9)
    full = roofline.flash_call("fwd", 1, 80, 16, 8192, 192, 128)
    assert full[0] == 2 * 80 * (8192 * 8193 // 2) * 320 and full[1] == moved
    assert roofline.least_seconds(*full, V5E) == pytest.approx(
        full[0] / 197e12)
    # with as many key heads as query heads and no window it is the latent
    # cells' count (``test_mhc.py`` has it by hand; one function since
    # PR 61); the one pass is dq + dk/dv less the second S and dP and the
    # operands read twice
    assert roofline.flash_call("fwd", 1, 32, 32, 8192, 192, 128)[0] == \
        2 * 32 * (8192 * 8193 / 2) * (192 + 128)
    one = roofline.flash_call("bwd", 1, 80, 16, 8192, 192, 128)
    dq = roofline.flash_call("dq", 1, 80, 16, 8192, 192, 128)
    dkv = roofline.flash_call("dkv", 1, 80, 16, 8192, 192, 128)
    assert one[0] == dq[0] + dkv[0] - full[0]
    assert one[1] < dq[1] + dkv[1]


def test_the_gdla_readers_by_their_scopes_and_names():
    from benchmark.layer_metrics import (gdla_attn_roofline,
                                         gdla_device_share,
                                         gdla_window_roofline,
                                         polynorm_device_share)
    by = {"forward_backward/block/attn/mla/q": 1.0,
          "forward_backward/block/attn/mla/diff": 0.5,
          "forward_backward/block/attn/mla/gate": 0.25,
          "forward_backward/block/attn/block/attn_window/flash_fwd": 0.75,
          "forward_backward/block/attn/block/attn_full/flash_bwd": 1.5,
          "forward_backward/block/mlp/polynorm": 0.5,
          "forward_backward/block/moe/shared/polynorm": 0.125,
          "forward_backward/block/moe/experts/polynorm": 0.125,
          "forward_backward/block/moe/experts": 3.0}
    ops = {"jit_train_step/flash_fwd_d192v<bf16>": 0.06,
           "jit_train_step/flash_bwd_d192v<bf16>": 0.1,
           "jit_train_step/flash_fwd_d192v128_w<bf16>": 0.003,
           "jit_train_step/flash_dq_d192v128_w<bf16>": 0.006,
           "jit_train_step/flash_dkv_d192v128_w<bf16>": 0.009}
    facts = _facts(by, ops)
    assert gdla_device_share.read(facts) == pytest.approx(40.0)
    assert polynorm_device_share.read(facts) == pytest.approx(7.5)
    least = lambda which, window: 3 * roofline.least_seconds(
        *roofline.flash_call(which, 1, 80, 16, 8192, 192, 128, window),
        V5E)
    assert gdla_attn_roofline.read(facts) == pytest.approx(
        100 * (least("fwd", None) + least("bwd", None)) / 0.16)
    assert gdla_window_roofline.read(facts) == pytest.approx(
        100 * sum(least(w, 128) for w in ("fwd", "dq", "dkv")) / 0.018)
    # A program without the scopes or the kernels, a model without noise
    # heads or a window and a run without a trace: nothing, and no error.
    bare = _facts({"forward_backward/block/moe/experts": 1.0})
    for reader in (gdla_device_share, gdla_attn_roofline,
                   gdla_window_roofline, polynorm_device_share):
        assert reader.read(bare) is None, reader.__name__
        assert reader.read({"trace": None, "arch": None}) is None
    other = _facts(by, ops)
    other["arch"]["sizes"] = {"E": 2048, "L": 12, "H": 32, "dv": 128}
    for reader in (gdla_device_share, gdla_attn_roofline,
                   gdla_window_roofline):
        assert reader.read(other) is None, reader.__name__


def test_the_lambda_s_reader_reads_nothing_without_a_session(monkeypatch,
                                                             tmp_path):
    from benchmark.layer_metrics import gdla_lambda_mean
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    assert gdla_lambda_mean.read({}) is None


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_the_new_readers_on_the_recorded_trace_read_nothing():
    """A recorded trace of a program without the scopes or the kernels
    (``toy_step``'s), joined with a program text that names no scope: the
    readers return None and do not raise, as on the parent's side of a
    traced run."""
    from benchmark.layer_metrics import (gdla_attn_roofline,
                                         gdla_device_share,
                                         gdla_window_roofline,
                                         polynorm_device_share)
    loaded = trace.load(SMALL)
    facts = _facts()
    facts["trace"] = trace.reduce(loaded)
    facts["arch"]["scopes"] = scopes.seconds_by_scope(loaded, "")
    assert facts["trace"]["busy_s"] > 0
    for reader in (gdla_device_share, gdla_attn_roofline,
                   gdla_window_roofline, polynorm_device_share):
        assert reader.read(facts) is None


def test_the_arch_module_s_counts_by_hand():
    from benchmark import common
    from benchmark.archs import Motif as arch
    config = common.load_json("configs", "motif-3-beta.json")
    s = arch.sizes_of(config)
    counts = arch.parameters(s)
    E = 4096
    attn = (E * 1024 + 1024 * 80 * 192 + E * 576 + 512 * 16 * 256 + E * 64
            + E * 8192 + 8192 * E)                          # 91.75 M
    maps = 2 * (4 * E * 24 + 24 + 3)
    norms = 2 * E + 1024 + 512
    dense = 3 * E * 12288 + 4
    moe = E * 384 + 3 * E * 1280 + 4 + 8 * 3 * E * 1280 + 4
    assert round(attn / 1e6, 2) == 91.75
    assert counts["expert"] == 3 * E * 1280 == 15_728_640
    assert counts["held"] == config["parameters"] == (
        4 * (attn + maps + norms + moe) + 2 * 27520 * E + E) \
        == 1_168_156_920
    # the issue's five layers, with the leading dense one the cell dropped
    assert counts["held"] + attn + maps + norms + dense == 1_411_698_482
    assert counts["always"] == counts["held"] - 4 * 8 * 15_728_640 \
        - 27520 * E
    # Every published key of the catalog's row is in the file, as published
    # unless ``reduced`` names it.
    for key in config["reduced"]:
        assert config["published"][key] != config[key], key
    assert config["share"]["vocab_rows"] == [0, config["vocab_size"]]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * config["share"][
        "chips_sharing_a_layer"] == config["published"]["num_experts"]
    assert set(config["correct"]) == set(config["correct_why"]) == {
        "norm_grad_distance", "step_moments_distance",
        "step_update_mismatch", "routing_mismatch_share"}
    with pytest.raises(ValueError, match="config.json"):
        arch.sizes_of({**config, "diff_v2": False})
    with pytest.raises(ValueError, match="config.json"):
        arch.sizes_of({**config, "rope_scaling": {
            **config["rope_scaling"], "apply_yarn_scaling": True}})
    # with the module the tree gains it and the head counts twice
    with_module = arch.sizes_of({**config, "num_nextn_predict_layers": 1})
    assert "mtp" in arch.shapes(with_module) and "mtp" not in arch.shapes(s)
    more = arch.parameters(with_module)
    assert more["held"] - counts["held"] == (
        attn + maps + norms + moe + 2 * E * E + 3 * E)
    assert more["always"] == more["held"] - 5 * 8 * 15_728_640


def test_the_cell_rehearses_and_names_every_entry_a_cpu_can():
    """Found by name (one entry a reader since PR 61; ``per_layer`` was
    full before it, and the cell stood under other cells' suffixes): the
    cell's own five readers, which a traced run also says on a ``[gdla]``
    line, and the fifteen it shares.  ``mla_attn_roofline`` is not among
    them: it counts keys and values once a QUERY head, and
    ``gdla_attn_roofline`` reads the same kernels at 80 over 16.
    ``hc_sinkhorn_residual.mhc8k`` keeps the name ``tests/`` asserts."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", ())}
    step, kernels = "train step", "kernels"
    brought = {
        "gdla_device_share": ("%", "lower", "device_trace", step),
        "gdla_attn_roofline": ("%", "higher", "device_trace", kernels),
        "gdla_window_roofline": ("%", "higher", "device_trace", kernels),
        "gdla_lambda_mean": ("share", "higher", "program_counter", step),
        "polynorm_device_share": ("%", "lower", "device_trace", step)}
    assert entries == set(brought) | {
        "mfu_active_pct", "grouped_mm_roofline", "moe_device_share",
        "moe_load_max_over_mean", "idle_share", "place_batch_ms",
        "compiles_in_window", "worker_chip_s", "cluster_start_s",
        "step_period_max_over_median", "hbm_held_share", "hc_device_share",
        "hc_sinkhorn_residual.mhc8k", "mla_device_share",
        "expert_rows_a_call"}
    for m in bench["per_layer"]:
        if m["name"] in brought:
            assert (m["unit"], m["better"], m["source"], m["layer"]) == \
                brought[m["name"]], m
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tok_s_chip"
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_tok_s_chip")["workloads"]
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 3), "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    named = set(last["metrics_named"])
    # every entry is read (a [metric] line each); a CPU gives a number to
    # those that need no device trace
    for name in entries:
        assert f"[metric] name={name} " in done.stdout, name
    assert {"hc_sinkhorn_residual.mhc8k", "expert_rows_a_call",
            "moe_load_max_over_mean", "place_batch_ms",
            "step_period_max_over_median", "gdla_lambda_mean"} <= named
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "device_share" in n}
    for name in ("norm_grad_distance", "step_moments_distance",
                 "step_update_mismatch", "routing_mismatch_share"):
        assert f"[correct] name={name}" in done.stdout
    assert "lambdas_alone_distance=" in done.stdout
    assert "polys_alone_distance=" in done.stdout
    own = next(line for line in done.stdout.splitlines()
               if line.startswith("[gdla]"))
    assert "gdla_lambda_mean=0.4" in own or "gdla_lambda_mean=0.5" in own
    assert "gdla_attn_roofline=None" in own     # no device trace on a CPU
