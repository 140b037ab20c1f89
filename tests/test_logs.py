"""Log monitor + export event tests (reference analogs:
python/ray/tests/test_output.py worker-log redirection,
_private/log_monitor.py tailing, export_*.proto event records)."""

import json
import os
import time

import pytest

import ray_tpu


@pytest.fixture
def logged_runtime(tmp_path, monkeypatch):
    # A session directory of its own: every cluster that starts under the
    # default one re-points its ``session_latest``, other workers' too.
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    rt = ray_tpu.init(num_cpus=2)
    yield rt
    ray_tpu.shutdown()


@ray_tpu.remote
def chatty(msg):
    print(f"hello-from-worker {msg}")
    import sys
    print(f"warn-{msg}", file=sys.stderr)
    return msg


class TestLogMonitor:
    def test_worker_output_lands_in_session_logs(self, logged_runtime,
                                                 capsys):
        rt = logged_runtime
        assert os.path.isdir(rt.session_logs_dir)
        assert ray_tpu.get(chatty.remote("abc")) == "abc"
        # The worker's prints were redirected to per-worker files...
        deadline = time.time() + 10
        found_out = found_err = False
        while time.time() < deadline and not (found_out and found_err):
            for fname, _size in rt.ctl_log_files():
                if fname.endswith(".out") and "hello-from-worker abc" in \
                        "\n".join(rt.ctl_log_tail(fname)):
                    found_out = True
                if fname.endswith(".err") and "warn-abc" in \
                        "\n".join(rt.ctl_log_tail(fname)):
                    found_err = True
            time.sleep(0.1)
        assert found_out and found_err
        # ...and the monitor republishes them to the driver streams with a
        # worker prefix (reference: "(pid=...)" echo).
        deadline = time.time() + 5
        while time.time() < deadline:
            cap = capsys.readouterr()
            if "hello-from-worker abc" in cap.out:
                assert "(worker-" in cap.out
                break
            time.sleep(0.2)
        else:
            pytest.fail("worker stdout was not republished to the driver")

    def test_session_latest_symlink(self, logged_runtime):
        rt = logged_runtime
        base = os.path.dirname(rt.session_dir)
        link = os.path.join(base, "session_latest")
        assert os.path.islink(link)
        assert os.path.realpath(link) == os.path.realpath(rt.session_dir)

    def test_export_events_written(self, logged_runtime):
        rt = logged_runtime

        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        a = A.remote()
        assert ray_tpu.get(a.ping.remote()) == 1
        ray_tpu.kill(a)
        path = os.path.join(rt.session_logs_dir, "events.jsonl")
        deadline = time.time() + 10
        states = set()
        while time.time() < deadline:
            if os.path.exists(path):
                with open(path) as f:
                    recs = [json.loads(line) for line in f if line.strip()]
                states = {(r["source_type"], r.get("state"))
                          for r in recs}
                if ("EXPORT_ACTOR", "ALIVE") in states and \
                        ("EXPORT_ACTOR", "DEAD") in states:
                    break
            time.sleep(0.1)
        assert ("EXPORT_ACTOR", "ALIVE") in states
        assert ("EXPORT_ACTOR", "DEAD") in states
        for r in recs:
            assert "timestamp" in r
