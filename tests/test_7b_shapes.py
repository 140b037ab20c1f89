"""North-star shape verification: the Llama-2-7B training step
(BASELINE.json config) AOT-lowers and compiles on a virtual 8-device mesh
with fsdp=8 and a pp=2 variant — no weights materialized, nothing
executed.  Proves the multi-chip 7B sharding is compile-clean before
hardware exists (reference: BASELINE.json Llama-2-7B SFT north star)."""

import json
import os
import subprocess
import sys


def test_llama2_7b_aot_compiles():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--spec", "7b"],
        capture_output=True, text=True, timeout=1500, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    names = {d["metric"]: d for d in lines}
    assert "llama2_7b_fsdp8_aot_compile" in names
    assert "llama2_7b_pp2_fsdp4_aot_compile" in names
    for name, d in names.items():
        assert d["ok"] and d["params_b"] > 6.0
