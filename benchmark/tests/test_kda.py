"""The readers that the cell ``ling-3.0-flash.train-kda8k`` brought, the
shipped readers at its shapes, its arch module's counts and its rehearsal on
the CPU.  (Cases for ``test_roofline.py`` and ``test_rehearsal.py``, kept in
a file of their own: a PR that adds a cell edits no file the benchmark
already has.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import roofline, roofline_kda, roofline_ssm, scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SMALL = os.path.join(HERE, "small.xplane.pb")
V5E = "TPU v5 lite"
CELL = "ling-3.0-flash.train-kda8k"
KINDS = ("kda",) * 4 + ("mla",) + ("kda",) * 2


def _facts(by_scope=None, ops=None, rows_a_call=1):
    sizes = {"E": 2560, "L": 7, "H": 32, "D": 128, "K": 4, "Q": 64,
             "dn": 128, "dr": 64, "dv": 128, "Xh": 16, "kinds": KINDS}
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 12 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 32768, "seq_len": 8192,
            "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": rows_a_call,
                     "scopes": by_scope and {"scopes": by_scope}}}


def test_the_scan_s_operations_and_bytes_by_hand():
    """A token a head at chunk 64 and 128 channels: the four triangles
    2 x 64 x (128 + 128), the three products with the state 6 x 128 x 128;
    q, k, v and o in bf16, the decay and beta float32."""
    ops, moved = roofline_kda.scan_passes(1.0, 32, 128, 128, 64, passes=1)
    assert ops == 32 * (2 * 64 * 256 + 6 * 128 * 128) == 4_194_304
    assert moved == 32 * (2 * 4 * 128 + 4 * 128 + 4) == 49_280
    four = roofline_kda.scan_passes(98304.0, 32, 128, 128, 64)
    assert four == (4 * 98304 * ops, 4 * 98304 * moved)
    # memory bound on a v5e by this floor: 21.3 us of products, 60.2 us of
    # bytes a thousand tokens
    assert roofline.least_seconds(ops * 1000, moved * 1000, V5E) == \
        pytest.approx(moved * 1000 / 819e9)


def test_the_kda_readers_by_their_scopes():
    from benchmark.layer_metrics import (kda_conv_roofline, kda_device_share,
                                         kda_scan_roofline, mla_device_share)
    by = {"forward_backward/block/attn/kda/scan": 1.5,
          "forward_backward/block/attn/kda/scan/kda_bwd_c64": 0.5,
          "forward_backward/block/attn/kda/conv/block/ssm/conv": 0.25,
          "forward_backward/block/attn/kda/proj": 1.0,
          "forward_backward/block/attn/kda/out": 0.75,
          "forward_backward/block/attn/mla/q": 0.5,
          "forward_backward/block/attn/flash_fwd_d192v128": 0.5,
          "forward_backward/block/moe/experts": 3.0}
    facts = _facts(by)
    assert kda_device_share.read(facts) == pytest.approx(40.0)
    # the one latent layer shares ``block/attn`` with the six KDA layers:
    # its share is what lies there and not under ``kda``
    assert mla_device_share.read(facts) == pytest.approx(10.0)
    tokens = 3 * 32768
    scan = 6 * roofline.least_seconds(
        *roofline_kda.scan_passes(tokens, 32, 128, 128, 64), V5E)
    assert kda_scan_roofline.read(facts) == pytest.approx(100 * scan / 2.0)
    conv = 6 * roofline.least_seconds(
        *roofline_ssm.conv_passes(tokens, 3 * 4096, 4), V5E)
    assert kda_conv_roofline.read(facts) == pytest.approx(100 * conv / 0.25)
    # A program without the scopes, a model without KDA layers and a run
    # without a trace: nothing, and no error.
    bare = _facts({"forward_backward/block/moe/experts": 1.0})
    for reader in (kda_device_share, kda_scan_roofline, kda_conv_roofline):
        assert reader.read(bare) is None
        assert reader.read({"trace": None, "arch": None}) is None
    other = _facts(by)
    other["arch"]["sizes"] = {"E": 2048, "L": 12, "H": 32, "dv": 128}
    assert kda_scan_roofline.read(other) is None
    assert kda_conv_roofline.read(other) is None


def test_the_carry_s_reader_reads_nothing_without_a_session(monkeypatch,
                                                            tmp_path):
    from benchmark.layer_metrics import kda_chunk_carry
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    assert kda_chunk_carry.read({}) is None


def test_the_shipped_latent_reader_counts_this_cell_s_one_layer():
    """``mla_attn_roofline`` at this cell's shape: one latent layer, 12 calls
    of a kernel over three traced steps at one row a call (1 layer x 4
    rows), by hand."""
    from benchmark.layer_metrics import mla_attn_roofline
    ops = {"jit_train_step/flash_fwd_d192v<bf16,f32>": 0.1}
    want = 12 * roofline.flash_call("fwd", 1, 32, 32, 8192, 192,
                                        128)[0] / 197e12
    assert mla_attn_roofline.read(_facts(ops=ops)) == \
        pytest.approx(100 * want / 0.1)


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_the_new_readers_on_the_recorded_trace_read_nothing():
    """A recorded trace of a program without the scopes (``toy_step``'s),
    joined with a program text that names no scope: the readers return
    None and do not raise, as on the parent's side of a traced run."""
    from benchmark.layer_metrics import (kda_conv_roofline, kda_device_share,
                                         kda_scan_roofline)
    loaded = trace.load(SMALL)
    facts = _facts()
    facts["trace"] = trace.reduce(loaded)
    facts["arch"]["scopes"] = scopes.seconds_by_scope(loaded, "")
    assert facts["trace"]["busy_s"] > 0
    for reader in (kda_device_share, kda_scan_roofline, kda_conv_roofline):
        assert reader.read(facts) is None


def test_the_arch_module_s_counts_by_hand():
    from benchmark import common
    from benchmark.archs import bailing_hybrid as arch
    config = common.load_json("configs", "ling-3.0-flash.json")
    s = arch.sizes_of(config)
    counts = arch.parameters(s)
    E, F = 2560, 4096
    kda = 6 * E * F + E * 32 + 4 * 3 * F + 32 + F + 128     # 63.05 M
    mla = E * 32 * 192 + E * 576 + 512 + 512 * 32 * 256 + E * 32 + F * E
    moe = E * 512 + 3 * E * 768 + 16 * 3 * E * 768
    assert counts["expert"] == 3 * E * 768 == 5_898_240
    assert round(kda / 1e6, 2) == 63.05 and round(mla / 1e6, 2) == 31.97
    assert counts["held"] == config["parameters"] == (
        (kda + 2 * E + 3 * E * 6144) + 5 * (kda + 2 * E + moe)
        + (mla + 2 * E + moe) + 2 * 19648 * E + E) == 1_167_571_904
    assert counts["always"] == counts["held"] - 6 * 16 * 5_898_240 \
        - 19648 * E
    assert s["kinds"] == KINDS
    # Every published key of the catalog's row is in the file, as published
    # unless ``reduced`` names it.
    for key in config["reduced"]:
        assert config["published"][key] != config[key], key
    assert config["share"]["vocab_rows"] == [0, config["vocab_size"]]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * config["share"][
        "chips_sharing_a_layer"] == config["published"]["num_experts"]
    with pytest.raises(ValueError, match="config.json"):
        arch.sizes_of({**config, "kda_safe_gate": False})
    # a held layer with a clamp (published layers 34 on) is refused
    with pytest.raises(ValueError, match="clamp"):
        arch.sizes_of({**config, "share": {**config["share"],
                                           "first_layer": 30}})


def test_the_cell_rehearses_and_names_every_entry_a_cpu_can():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name (one entry a reader since PR 61): the cell's own four,
    # the twelve it shares with other cells, and the two PR 55 had no room
    # for, ``cluster_start_s`` and ``mla_device_share``
    entries = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", ())}
    assert entries == {
        "kda_scan_roofline", "kda_conv_roofline", "kda_device_share",
        "kda_chunk_carry", "mla_attn_roofline", "mla_device_share",
        "grouped_mm_roofline", "moe_device_share", "moe_load_max_over_mean",
        "expert_rows_a_call", "mfu_active_pct", "hbm_held_share",
        "idle_share", "place_batch_ms", "compiles_in_window",
        "worker_chip_s", "cluster_start_s", "step_period_max_over_median"}
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"]
               if m["name"].startswith("kda_"))
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
    assert {"kda_chunk_carry", "expert_rows_a_call",
            "moe_load_max_over_mean", "place_batch_ms",
            "step_period_max_over_median"} <= named
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "device_share" in n}
    for name in ("norm_grad_distance", "step_moments_distance",
                 "step_update_mismatch", "routing_mismatch_share"):
        assert f"[correct] name={name}" in done.stdout
    assert "o_norm_alone_distance=" in done.stdout
