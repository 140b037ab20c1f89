"""The spans helper and the four readers built on it, on a hand-made
``spans.jsonl``: numbers where the spans are there, None where the file or
a span is missing, never an exception."""

import json
import os

import pytest

from benchmark import spans
from benchmark.layer_metrics import (cluster_start_s, compiles_in_window,
                                     place_batch_ms, worker_chip_s)

T0 = 1_790_000_000.0          # process start on the wall clock
WINDOW = 30.0                 # window_start - T0


def span(name, start, length, process=100, **extra):
    return {"name": name, "cat": "x", "start": T0 + start,
            "end": T0 + start + length, "process": process, "thread": 1,
            "span_id": 1, "parent_id": None, **extra}


def a_run():
    """One training run: the head is process 100, the chip worker 200, an
    idle pooled worker 300."""
    out = [
        span("runtime_init", 0.5, 0.25),
        span("worker_start", 1.0, 0.3, pid=300, worker_id="c"),
        span("worker_start", 2.0, 0.4, pid=200, worker_id="a"),
        span("train_load_fn", 2.6, 2.4, process=200),
        span("worker_backend_init", 5.0, 9.0, process=200),
        span("xla_compile", 15.0, 2.5, process=200, program="jit(init)"),
        span("xla_compile", 20.0, 2.6, process=200,
             program="jit(train_step)"),
        # After the window: the check's own programs do not count.
        span("xla_compile", WINDOW + 10.5, 1.0, process=200,
             program="jit(reference)"),
        span("train_place_batch", 24.0, 0.5, process=200),     # warm-up
        span("train_place_batch", WINDOW + 10.2, 0.3, process=200),  # check
    ]
    for i, ms in enumerate([0.4, 0.6, 0.5, 9.0, 0.5]):
        out.append(span("train_place_batch", WINDOW + 2.0 * i, ms / 1e3,
                        process=200))
    return out


def facts_of(found):
    # 5 steps of 1,000 tokens on one chip in 10 s: the window's length.
    return {"spans": found, "window_start": T0 + WINDOW,
            "setup_s": WINDOW, "attempted": 5, "tokens_per_step": 1000,
            "train_tok_s_chip": 500.0, "device": {"count": 1}}


def test_readers_on_a_run():
    facts = facts_of(a_run())
    assert cluster_start_s.read(facts) == pytest.approx(0.25)
    # Spawn of the process that holds the chips (200) to its backend up.
    assert worker_chip_s.read(facts) == pytest.approx(14.0 - 2.0)
    assert place_batch_ms.read(facts) == pytest.approx(0.5, abs=1e-3)
    assert compiles_in_window.read(facts) == 0.0


def test_a_compile_inside_the_window_counts():
    found = a_run() + [span("xla_compile", WINDOW + 3.0, 0.8, process=200,
                            program="jit(train_step)")]
    assert compiles_in_window.read(facts_of(found)) == 1.0


def test_a_serving_window_is_taken_as_stated():
    facts = {"spans": a_run(), "window_start": T0 + WINDOW,
             "window": (T0 + WINDOW, T0 + WINDOW + 11.0)}
    assert spans.window(facts) == (T0 + WINDOW, T0 + WINDOW + 11.0)
    assert compiles_in_window.read(facts) == 1.0      # the one at +10.5


@pytest.mark.parametrize("missing", [
    "runtime_init", "worker_start", "worker_backend_init",
    "train_place_batch", "xla_compile"])
def test_a_missing_span_gives_none(missing):
    facts = facts_of([s for s in a_run() if s["name"] != missing])
    reader = {"runtime_init": cluster_start_s, "worker_start": worker_chip_s,
              "worker_backend_init": worker_chip_s,
              "train_place_batch": place_batch_ms,
              "xla_compile": compiles_in_window}[missing]
    assert reader.read(facts) is None
    others = [r for r in (cluster_start_s, worker_chip_s, place_batch_ms,
                          compiles_in_window) if r is not reader]
    assert all(r.read(facts) is not None for r in others)


@pytest.mark.parametrize("reader", [cluster_start_s, worker_chip_s,
                                    place_batch_ms, compiles_in_window])
def test_no_file_gives_none(reader, tmp_path, monkeypatch):
    """The parent commit writes no spans: every reader leaves its metric
    out, with or without a session directory."""
    facts = facts_of(None)
    monkeypatch.delenv("RAY_TPU_SESSION_DIR", raising=False)
    assert reader.read(facts) is None
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    assert reader.read(facts) is None
    os.makedirs(tmp_path / f"session_x_{os.getpid()}" / "logs")
    assert reader.read(facts) is None


def test_load_finds_this_process_newest_file(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))

    def write(session, found, torn=False):
        d = tmp_path / session / "trace"
        os.makedirs(d)
        with open(d / "spans.jsonl", "w") as f:
            for s in found:
                f.write(json.dumps(s) + "\n")
            if torn:
                f.write('{"name": "half a li')
        return str(d / "spans.jsonl")

    # Another process's session (another run sharing the directory).
    write("session_20260927-100000_1", [span("runtime_init", 0, 9.0)])
    assert spans.load() is None
    old = write(f"session_20260927-100001_{os.getpid()}",
                [span("runtime_init", 0, 7.0)])
    os.utime(old, (1, 1))
    new = write(f"session_20260927-100002_{os.getpid()}", a_run(), torn=True)
    assert spans.find() == new
    loaded = spans.load()
    assert len(loaded) == len(a_run())
    assert cluster_start_s.read({"window_start": T0}) == pytest.approx(0.25)
    assert [s["name"] for s in spans.named(loaded, "worker_start")] == \
        ["worker_start"] * 2
