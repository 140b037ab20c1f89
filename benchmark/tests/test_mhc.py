"""The counts and readers that the cell ``xing4.0-29b-a4b.train-mhc8k``
brought, by hand at its shapes, and its rehearsal on the CPU.  (Cases for
``test_roofline.py`` and ``test_rehearsal.py``, kept in a file of their own:
a PR that adds a cell edits no file the benchmark already has.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import roofline, roofline_hc

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = "TPU v5 lite"


def test_latent_flash_calls_by_hand():
    # 1 row, 32 heads, 8,192 tokens, scores over 192 and values over 128.
    pairs = 8192 * 8193 / 2
    fwd, moved = roofline.flash_call("fwd", 1, 32, 32, 8192, 192, 128)
    assert fwd == 2 * 32 * pairs * (192 + 128)          # 687 GFLOP
    q, o, lse = 32 * 8192 * 192 * 2, 32 * 8192 * 128 * 2, 32 * 8192 * 4
    assert moved == 2 * q + 2 * o + lse                 # q, k | v, o | lse
    dq, moved_dq = roofline.flash_call("dq", 1, 32, 32, 8192, 192, 128)
    dkv, moved_dkv = roofline.flash_call("dkv", 1, 32, 32, 8192, 192, 128)
    assert dq == 2 * 32 * pairs * (2 * 192 + 128)
    assert dkv == 2 * 32 * pairs * (2 * 192 + 2 * 128)
    assert moved_dq == 3 * q + 2 * o + 2 * lse
    assert moved_dkv == 3 * q + 3 * o + 2 * lse
    # compute bound on a v5e: 3.5 ms against 0.25 ms
    assert roofline.least_seconds(fwd, moved, V5E) == \
        pytest.approx(fwd / 197e12)
    # At equal head sizes the count is the one the sparse cell's reader
    # makes with one size (one function since PR 61).
    for which in ("fwd", "dq", "dkv", "bwd"):
        assert roofline.flash_call(which, 2, 32, 4, 8192, 128, 128) \
            == roofline.flash_call(which, 2, 32, 4, 8192, 128)


def test_hyper_connection_passes_by_hand():
    # 16,384 tokens, 4 lanes of 3,584: a lane-set is 28,672 B a token.
    ops, moved = roofline_hc.sublayer_passes(16384, 4, 3584)
    assert moved == 4 * 3 * 16384 * 28672               # 5.64 GB
    assert ops == 4 * 2 * 16384 * 4 * 3584 * (24 + 1 + 4 + 1)
    # memory bound on a v5e: 6.9 ms against 0.29 ms
    assert roofline.least_seconds(ops, moved, V5E) == \
        pytest.approx(moved / 819e9)


def _facts(scopes=None, ops=None):
    sizes = {"E": 3584, "L": 5, "H": 32, "dn": 128, "dr": 64, "dv": 128,
             "n": 4}
    return {"trace": {"busy_s": 10.0, "op_seconds": ops or {},
                      "op_counts": {k: 18 for k in ops or {}}},
            "trace_steps": 3, "tokens_per_step": 16384, "seq_len": 8192,
            "device": {"count": 1, "kind": V5E},
            "arch": {"sizes": sizes, "rows_a_call": 1,
                     "scopes": scopes and {"scopes": scopes}}}


def test_readers_of_the_new_scopes_and_kernels():
    from benchmark.layer_metrics import (hc_device_share, hc_stream_roofline,
                                         mla_attn_roofline, mtp_device_share)
    scopes = {"forward_backward/block/hc/maps": 0.3,
              "forward_backward/mtp/block/hc/deposit": 0.2,
              "forward_backward/mtp/loss": 0.5,
              "forward_backward/block/attn/mla": 2.0}
    facts = _facts(scopes)
    assert hc_device_share.read(facts) == pytest.approx(5.0)
    assert mtp_device_share.read(facts) == pytest.approx(7.0)
    # 12 sublayers (5 layers and the module's), 3 steps of 16,384 tokens.
    least = 12 * 4 * 3 * (3 * 16384) * 28672 / 819e9
    assert hc_stream_roofline.read(facts) == pytest.approx(
        100 * least / 0.5)
    ops = {"jit_train_step/flash_fwd_d192v<bf16,f32>": 0.1,
           "jit_train_step/flash_dq_d192v<bf16>": 0.2,
           "jit_train_step/flash_fwd<bf16,f32>": 9.0}
    want = 18 * sum(roofline.flash_call(w, 1, 32, 32, 8192, 192, 128)[0]
                    for w in ("fwd", "dq")) / 197e12
    assert mla_attn_roofline.read(_facts(ops=ops)) == pytest.approx(
        100 * want / 0.3)
    # A program without the scopes or the kernels: nothing, and no error.
    empty = _facts({"forward_backward/block/moe/experts": 1.0},
                   {"jit_train_step/flash_fwd<bf16,f32>": 9.0})
    for reader in (hc_device_share, hc_stream_roofline, mtp_device_share,
                   mla_attn_roofline):
        assert reader.read(empty) is None
        assert reader.read({"trace": None, "arch": None}) is None
    other = _facts()
    other["arch"]["sizes"] = {"E": 2048, "L": 9, "H": 32, "D": 128}
    assert mla_attn_roofline.read(other) is None
    assert hc_stream_roofline.read(other) is None


def test_residual_reader_finds_nothing_without_a_session(tmp_path,
                                                         monkeypatch):
    from benchmark.layer_metrics import hc_sinkhorn_residual
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    assert hc_sinkhorn_residual.read({}) is None


def test_the_cell_rehearses_and_names_no_device_metric():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "xing4.0-29b-a4b.train-mhc8k", "--seed", str(2 ** 31 + 3),
         "--seconds", "3", "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    named = set(last["metrics_named"])
    assert "hc_sinkhorn_residual" in {n.split(".")[0] for n in named}
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "share" in n}
    assert "[correct] name=mtp_loss_distance" in done.stdout
