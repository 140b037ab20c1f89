"""The program's own spans of the run that just ended.

``ray_tpu.shutdown()`` on the head writes ``<session>/trace/spans.jsonl``:
every span of every process, one JSON object a line, ``start`` and ``end``
on the wall clock that ``common.now()`` and ``run.py``'s ``T0`` use.  The
runner hands the readers no spans, so they find the file themselves, under
``RAY_TPU_SESSION_DIR`` (``run.py`` sets it), in the session directory of
this very process.  A program that writes no such file (every commit
before the one that brought the recorder) gives ``None``, and so does
every reader built on this.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
from typing import Any, Dict, List, Optional

Span = Dict[str, Any]

_loaded: Dict[str, List[Span]] = {}


def find() -> Optional[str]:
    """The newest ``spans.jsonl`` that a head running as this process left
    under ``RAY_TPU_SESSION_DIR``."""
    base = os.environ.get("RAY_TPU_SESSION_DIR")
    if not base:
        return None
    files = glob.glob(os.path.join(
        base, f"session_*_{os.getpid()}", "trace", "spans.jsonl"))
    return max(files, key=os.path.getmtime) if files else None


def read_file(path: str) -> List[Span]:
    spans = []
    with open(path) as f:
        for line in f:
            try:
                span = json.loads(line)
            except ValueError:
                continue            # a torn last line
            if isinstance(span, dict) and {"name", "start", "end"} <= set(span):
                spans.append(span)
    return spans


def load(facts: Optional[Dict[str, Any]] = None) -> Optional[List[Span]]:
    """This run's spans, or None.  ``facts["spans"]``, where a test put
    spans there, is taken as it is."""
    if facts and facts.get("spans") is not None:
        return facts["spans"]
    path = find()
    if path is None:
        return None
    if path not in _loaded:
        try:
            _loaded[path] = read_file(path)
        except OSError:
            return None
    return _loaded[path]


def named(spans: Optional[List[Span]], name: str) -> List[Span]:
    return sorted((s for s in spans or [] if s["name"] == name),
                  key=lambda s: s["start"])


def seconds(span: Span) -> float:
    return span["end"] - span["start"]


def median_seconds(spans: List[Span]) -> Optional[float]:
    return statistics.median(seconds(s) for s in spans) if spans else None


def window(facts: Dict[str, Any]) -> tuple:
    """(start, end) of the measured window on the spans' clock.  A serving
    run states both; a training run states the start, its whole steps and
    the rate they gave, which is the window's length."""
    if "window" in facts:
        return tuple(facts["window"])
    lo = facts["window_start"]
    return lo, lo + facts["attempted"] * facts["tokens_per_step"] / (
        facts["train_tok_s_chip"] * facts["device"]["count"])


def inside(found: List[Span], facts: Dict[str, Any]) -> List[Span]:
    """The spans that start inside the measured window."""
    lo, hi = window(facts)
    return [s for s in found if lo <= s["start"] < hi]
