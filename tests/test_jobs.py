"""Job submission tests: manager, REST server + SDK client, CLI.

Reference analogs: dashboard/modules/job/tests/test_job_manager.py and
release job-submission smoke tests.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.job_submission import JobManager, JobStatus, JobSubmissionClient
from ray_tpu.job_submission.server import JobServer


class TestJobManager:
    def test_successful_job(self, ray_start):
        mgr = JobManager()
        sid = mgr.submit_job(
            entrypoint=f"{sys.executable} -c \"print('hello from job')\"")
        status = mgr.wait_until_finished(sid, timeout=60)
        assert status == JobStatus.SUCCEEDED
        assert "hello from job" in mgr.get_job_logs(sid)

    def test_failing_job(self, ray_start):
        mgr = JobManager()
        sid = mgr.submit_job(
            entrypoint=f"{sys.executable} -c 'import sys; sys.exit(3)'")
        assert mgr.wait_until_finished(sid, timeout=60) == JobStatus.FAILED
        assert "code 3" in mgr.get_job_info(sid).message

    def test_stop_job(self, ray_start):
        mgr = JobManager()
        sid = mgr.submit_job(
            entrypoint=f"{sys.executable} -c 'import time; time.sleep(60)'")
        time.sleep(0.5)
        assert mgr.get_job_status(sid) == JobStatus.RUNNING
        assert mgr.stop_job(sid)
        assert mgr.get_job_status(sid) == JobStatus.STOPPED

    def test_env_vars_runtime_env(self, ray_start):
        mgr = JobManager()
        sid = mgr.submit_job(
            entrypoint=(f"{sys.executable} -c "
                        "\"import os; print(os.environ['MY_FLAG'])\""),
            runtime_env={"env_vars": {"MY_FLAG": "flag-value-42"}})
        assert mgr.wait_until_finished(sid, timeout=60) == JobStatus.SUCCEEDED
        assert "flag-value-42" in mgr.get_job_logs(sid)

    def test_duplicate_and_invalid_ids(self, ray_start):
        mgr = JobManager()
        sid = mgr.submit_job(entrypoint="true", submission_id="job-a")
        with pytest.raises(ValueError):
            mgr.submit_job(entrypoint="true", submission_id="job-a")
        with pytest.raises(ValueError):
            mgr.submit_job(entrypoint="true", submission_id="bad id;rm")
        mgr.wait_until_finished(sid, timeout=60)


class TestJobServerAndClient:
    @pytest.fixture()
    def client(self, ray_start):
        mgr = JobManager()
        server = JobServer(mgr, port=0)
        yield JobSubmissionClient(server.address)
        server.stop()

    def test_submit_status_logs(self, client):
        sid = client.submit_job(
            entrypoint=f"{sys.executable} -c \"print('via rest')\"")
        assert client.wait_until_finished(sid, 60) == "SUCCEEDED"
        assert "via rest" in client.get_job_logs(sid)
        jobs = client.list_jobs()
        assert any(j["submission_id"] == sid for j in jobs)

    def test_tail_and_stop(self, client):
        sid = client.submit_job(
            entrypoint=(f"{sys.executable} -u -c "
                        "\"import time\nfor i in range(100):\n"
                        "    print('tick', i, flush=True)\n"
                        "    time.sleep(0.1)\""))
        # Wait for output rather than a fixed sleep: under load the
        # interpreter can take >1s to boot, and stopping before the first
        # tick makes the log assertion racy.
        deadline = time.monotonic() + 30
        while "tick" not in client.get_job_logs(sid):
            assert time.monotonic() < deadline, "job never produced output"
            time.sleep(0.2)
        assert client.stop_job(sid)
        assert client.get_job_status(sid) == "STOPPED"
        assert "tick" in client.get_job_logs(sid)

    def test_cluster_status(self, client):
        s = client.cluster_status()
        assert s["nodes"] and "CPU" in s["total_resources"]
        # Operator-health fields for `ray-tpu status` (watchdog/goodput
        # are None until a training run has been observed, but the keys
        # are always present).
        assert "goodput" in s and "watchdog" in s

    def test_cluster_stacks_and_debug_dump(self, client):
        # `ray-tpu stack` surface: the driver record is always there.
        dump = client._request("GET", "/api/cluster/stacks?timeout_s=3")
        assert any(r.get("is_driver") for r in dump["stacks"])
        assert "unresponsive" in dump
        # `ray-tpu debug dump` surface: writes a bundle, returns its path.
        out = client._request("POST",
                              "/api/cluster/debug_dump?reason=resttest")
        assert os.path.isdir(out["path"])
        assert "resttest" in os.path.basename(out["path"])
        assert "manifest.json" in os.listdir(out["path"])

    def test_missing_job_404(self, client):
        with pytest.raises(RuntimeError, match="404"):
            client.get_job_status("nonexistent")


@pytest.mark.slow
class TestCli:
    def test_start_submit_status_stop(self, tmp_path):
        addr_file = str(tmp_path / "head_address")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))

        def cli(*args, check=True, timeout=90):
            r = subprocess.run(
                [sys.executable, "-m", "ray_tpu.scripts.cli", *args],
                capture_output=True, text=True, env=env, timeout=timeout)
            if check:
                assert r.returncode == 0, r.stdout + r.stderr
            return r

        r = cli("start", "--head", "--port", "0", "--num-cpus", "2",
                "--address-file", addr_file)
        assert "head started at" in r.stdout
        address = json.load(open(addr_file))["address"]
        try:
            r = cli("status", "--address", address)
            assert "nodes: 1" in r.stdout
            r = cli("job", "submit", "--address", address, "--",
                    sys.executable, "-c", "\"print('cli job ran')\"")
            assert "cli job ran" in r.stdout
            assert "SUCCEEDED" in r.stdout
            r = cli("job", "list", "--address", address)
            assert "SUCCEEDED" in r.stdout
        finally:
            cli("stop", "--address-file", addr_file)
        deadline = time.monotonic() + 10
        while os.path.exists(addr_file) and time.monotonic() < deadline:
            time.sleep(0.2)
        assert not os.path.exists(addr_file)
