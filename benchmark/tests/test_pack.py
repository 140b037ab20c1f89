"""What the cell ``granite-4.0-h-micro.train-pack32k`` brought: the packed
traffic's properties, the counts of ``roofline_pack.py`` and the four
readers by hand, the configuration's widths against the catalog's row, and
the cell's rehearsal on the CPU.  (In a file of their own: a PR that adds a
cell edits no file the benchmark already has.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, roofline, roofline_pack
from benchmark.kinds import train_pack

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = "TPU v5 lite"
CELL = "granite-4.0-h-micro.train-pack32k"
MIX = common.load_json("traffic", "train-pack32k.json")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
@pytest.mark.parametrize("rows,seq", [(1, 32768), (2, 16384)])
def test_rows_are_full_ids_rise_by_one_and_the_mask_marks_the_ends(seed, rows,
                                                                   seq):
    d = MIX["documents"]
    source = train_pack.rows_of(seed, rows, seq, 100352, d)
    again = train_pack.rows_of(seed, rows, seq, 100352, d)
    carried = 0                     # of a document that a cut runs through
    for _ in range(3):
        b, same = next(source), next(again)
        for k in ("tokens", "loss_mask", "segment_ids"):
            assert b[k].shape == (rows, seq) and b[k].dtype == np.int32
            np.testing.assert_array_equal(b[k], same[k])    # the same seed
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 100352
        for r in range(rows):
            ids, mask, lengths = (b["segment_ids"][r], b["loss_mask"][r],
                                  b["lengths"][r])
            assert sum(lengths) == seq                  # full: no padding
            assert ids[0] == 0 and set(np.diff(ids)) <= {0, 1}
            assert ids[-1] == len(lengths) - 1
            np.testing.assert_array_equal(np.bincount(ids), lengths)
            # whole documents keep to the clip; the two a cut runs through
            # are parts of one that does
            for n in lengths[1:-1]:
                assert d["min"] <= n <= d["max"]
            assert carried + lengths[0] <= d["max"] or len(lengths) == 1
            ends = np.cumsum(lengths) - 1
            want = np.ones(seq, np.int32)
            want[ends[:-1]] = 0         # each document's last token
            want[-1] = 0                # and the row's last position
            # the last document may go on in the next row: then its end is
            # not in this one, and only the row's last position is masked
            np.testing.assert_array_equal(mask, want)
            carried = lengths[-1]
    assert MIX["seq_len"] == 32768 and MIX["kind"] == "train_pack"
    assert (d["median"], d["sigma"], d["min"], d["max"]) == (1536, 1.0, 64,
                                                            16384)


def test_about_thirteen_documents_a_row_and_the_pairs_they_keep():
    source = train_pack.rows_of(11, 1, 32768, 100352, MIX["documents"])
    rows = [next(source)["lengths"][0] for _ in range(200)]
    whole = [n for row in rows for n in row[1:-1]]
    assert 11 < np.mean([len(r) for r in rows]) < 16
    # (whole documents inside a row: the long ones are the likelier to be cut)
    assert 1200 < np.median(whole) < 1700 and 1900 < np.mean(whole) < 2800
    share = np.mean([roofline_pack.pairs_share(r, 32768) for r in rows])
    assert 0.1 < share < 0.25


def test_pairs_and_a_packed_call_by_hand():
    assert roofline_pack.pairs_inside([4, 2]) == (16 + 4) / 2
    assert roofline_pack.pairs_share([16384, 16384], 32768) == 0.5
    assert roofline_pack.pairs_share([32768], 32768) == 1.0
    ops, moved = roofline_pack.flash_seg_call("fwd", 1, 32, 8, 32768, 64,
                                              1e8)
    assert ops == 2 * 32 * 1e8 * (64 + 64)
    assert moved == roofline.flash_call("fwd", 1, 32, 8, 32768, 64)[1]
    whole = roofline.flash_attention_call("dkv", 1, 32, 8, 32768, 64)
    assert roofline_pack.flash_seg_call(
        "dkv", 1, 32, 8, 32768, 64,
        roofline_pack.pairs_inside([32768])) == whole


SIZES = {"E": 2048, "L": 10, "kinds": "MMMMM*MMMM", "H": 32, "Hkv": 8,
         "D": 64, "M": 8192, "Hm": 64, "P": 64, "N": 128, "G": 1, "K": 4,
         "Q": 256}


def _facts(scopes=None, ops=None, traced=None, parameters=None):
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 3 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 32768, "seq_len": 32768,
            "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": SIZES, "rows_a_call": 1,
                     "parameters": parameters or {"held": 951991232,
                                                  "multiplied": 951991232},
                     "pack_traced": traced,
                     "scopes": scopes and {"scopes": scopes}}}


def test_the_four_readers_by_hand_and_on_a_program_without_them():
    from benchmark.layer_metrics import (mfu_hybrid_pct, mlp_device_share,
                                         pack_pairs_share,
                                         packed_attn_roofline,
                                         ssd_scan_roofline, ssm_device_share)
    traced = [{"pack_pairs_share": 0.2, "lengths": [[16384, 16384]]},
              {"pack_pairs_share": 0.1, "lengths": [[8192] * 4]},
              {"pack_pairs_share": 0.3, "lengths": [[32768]]}]
    scopes = {"forward_backward/block/mlp": 4.0,
              "forward_backward/block/ssm/scan": 0.5,
              "forward_backward/block/ssm/proj": 2.5}
    ops = {"jit_train_step/flash_seg_fwd_d<bf16,f32>": 0.02,
           "jit_train_step/flash_seg_dq_d<bf16>": 0.03,
           "jit_train_step/flash_seg_dkv_d<bf16,bf16>": 0.04,
           "jit_train_step/flash_fwd_d<bf16,f32>": 9.0}
    facts = _facts(scopes, ops, traced)
    assert pack_pairs_share.read(facts) == pytest.approx(0.2)
    assert mlp_device_share.read(facts) == pytest.approx(40.0)
    assert ssm_device_share.read(facts) == pytest.approx(30.0)
    assert mfu_hybrid_pct.read(facts) == pytest.approx(
        100 * 6 * 951991232 * 3 * 32768 / 10.0 / 197e12)
    # the accepted scan reader takes the cell as it is: nine mixers at 256
    assert 0 < ssd_scan_roofline.read(facts) < 100
    pairs = (2 * 16384 ** 2 + 4 * 8192 ** 2 + 32768 ** 2) / 2 / 3
    least = 3 * sum(roofline.least_seconds(*roofline.flash_call(
        w, 1, 32, 8, 32768, 64, pairs=pairs), V5E)
        for w in ("fwd", "dq", "dkv"))
    assert packed_attn_roofline.read(facts) == pytest.approx(
        100 * least / 0.09)
    # A program without the scopes, the kernels, the report or the count
    # (the parent's, another cell's): nothing, and no error.
    empty = _facts({"forward_backward/block/moe": 1.0},
                   {"jit_train_step/flash_fwd_d<bf16,f32>": 9.0},
                   parameters={"held": 1, "always": 1})
    for reader in (pack_pairs_share, mlp_device_share, mfu_hybrid_pct,
                   packed_attn_roofline):
        assert reader.read(empty) is None
        assert reader.read({"trace": None, "arch": None}) is None
        assert reader.read({}) is None
    assert packed_attn_roofline.read(_facts(ops=ops)) is None


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row under the same key but
    ``num_hidden_layers`` (``layer_types`` whole, the program taking its
    first ten); the count is the arch module's and ISSUE 65's table's."""
    from benchmark.archs import granitemoehybrid as arch
    config = common.load_json("configs", "granite-4.0-h-micro.json")
    published = {
        "hidden_size": 2048, "intermediate_size": 8192,
        "shared_intermediate_size": 8192, "mamba_n_heads": 64,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "vocab_size": 100352, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "logits_scaling": 8, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "max_position_embeddings": 131072, "num_local_experts": 0,
        "num_experts_per_tok": 0}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    # kept whole, as published: the program takes its first ten
    assert config["num_hidden_layers"] == 10 and len(
        config["layer_types"]) == 40
    assert [i for i, t in enumerate(config["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["head_dim"] == 64
    s = arch.sizes_of(config)
    assert s["kinds"] == "MMMMM*MMMM" and s["G"] == 1
    assert s["Q"] == config["train"]["chunk"]
    counts = arch.parameters(s)
    mamba = 2048 * 8512 + 4096 * 2048 + 4 * 4352 + 4352 + 3 * 64 + 4096
    ff = 2048 * 16384 + 8192 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert counts["held"] == config["parameters"] == 951991232 == (
        9 * mamba + attn + 10 * (ff + 2 * 2048) + 100352 * 2048 + 2048)
    with pytest.raises(ValueError, match="experts"):
        arch.sizes_of({**config, "num_local_experts": 8})
    assert set(config["correct"]) == {"norm_grad_distance",
                                      "step_moments_distance",
                                      "step_update_mismatch"}
    assert set(config["correct_why"]) >= set(config["correct"])


def test_the_cell_is_entered_where_the_issue_says_and_nowhere_else():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert bench["workloads"].index(cell) == 12 and cell["chips"] == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"][:13]) == 1
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "idle_share", "cluster_start_s", "worker_chip_s", "place_batch_ms",
        "compiles_in_window", "step_period_max_over_median",
        "hbm_held_share", "ssd_scan_roofline", "ssm_conv_roofline",
        "ssm_device_share", "ssm_chunk_carry", "packed_attn_roofline",
        "pack_pairs_share", "mlp_device_share", "mfu_hybrid_pct"}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("packed_attn_roofline")    # after what PR 64 had
    assert at >= 60 and names[at:at + 4] == [
        "packed_attn_roofline", "pack_pairs_share", "mlp_device_share",
        "mfu_hybrid_pct"]
    assert len(cell["why"]) <= 200
    assert all(len(c["why"]) <= 200 for c in bench["configs"])


def test_the_cell_rehearses_and_names_no_device_metric():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 65), "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True
    named = set(last["metrics_named"])
    assert {"pack_pairs_share", "ssm_chunk_carry", "place_batch_ms",
            "compiles_in_window"} <= named
    assert not named & {"packed_attn_roofline", "mfu_hybrid_pct",
                        "mlp_device_share", "ssd_scan_roofline",
                        "train_tok_s_chip"} or "train_tok_s_chip" in named
    assert "ok=True" in done.stdout and "ok=False" not in done.stdout
