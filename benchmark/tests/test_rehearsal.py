"""run.py walks its whole control flow on the CPU at toy sizes, and refuses
to print a result or any device metric there."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("workload", ["yi-coder-1.5b.train-sft4k",
                                      "yi-coder-1.5b.chat-sat",
                                      "mistral-7b-v0.3.train-fsdp4"])
def test_rehearsal_names_no_device_metric(workload):
    done = _run("--workload", workload, "--seed", str(2 ** 31 + 3),
                "--seconds", "3", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["device"]["platform"] == "cpu"
    named = set(last["metrics_named"])
    assert not {n for n in named if "roofline" in n or "idle" in n
                or "mfu" in n or "gap" in n or "share" in n}
    assert "[correct]" in done.stdout


def test_no_chip_no_result():
    done = _run("--workload", "yi-coder-1.5b.train-sft4k", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
