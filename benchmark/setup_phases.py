#!/usr/bin/env python3
"""benchmark/setup_phases.py <spans.jsonl> --warmup-steps n --setup-s s

What a finished run's spans say of its set-up, beyond the numbers its
result line holds: the phases of ``setup_s`` in order, the programs and
nested functions with the most seconds of tracing and lowering (a trace's
own seconds: its nested ``jax_trace`` spans taken off), every stretch of
set-up over ``--hole`` seconds that no span (of 10 ms or more) covers with
the spans on either side of it, and the compile-path spans that start inside the window (there
should be none).  ``<spans.jsonl>`` is ``<session>/trace/spans.jsonl`` of a
run started with ``RAY_TPU_SESSION_DIR`` set to a directory that is kept.

The window's start is not in the file: it is taken as the start of the
chip worker's ``train_place_batch`` numbered ``--warmup-steps`` (the
traffic file's ``warmup_steps``: step 0 is the batch the step compiles on,
the window's first batch follows the warm-up's), and ``--setup-s`` is the
run's ``setup_s`` (its ``[metric]`` line).  Prints one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import spans  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    cache_fetch_s, cluster_start_s, cold_compile_s, setup_unnamed_s,
    trace_lower_s, worker_chip_s)

COMPILE_PATH = ("jax_trace", "jax_lower", "xla_compile")


def own_seconds(found):
    """{(span name, program): seconds}: a ``jax_trace``'s less the nested
    traces that lie directly inside it, so that a program's Python and each
    nested function's are told apart; ``jax_lower`` whole."""
    own = {id(s): spans.seconds(s) for s in found}
    threads = {}
    for s in found:
        if s["name"] == "jax_trace":
            threads.setdefault((s.get("process"), s.get("thread")),
                               []).append(s)
    for traces in threads.values():
        holding = []            # the traces that hold the one looked at
        for s in sorted(traces, key=lambda s: (s["start"], -s["end"])):
            while holding and holding[-1]["end"] < s["end"]:
                holding.pop()
            if holding:
                own[id(holding[-1])] -= spans.seconds(s)
            holding.append(s)
    by = {}
    for s in found:
        key = (s["name"], s.get("program"))
        by[key] = by.get(key, 0.0) + own[id(s)]
    return by


def holes(loaded, lo, hi, floor, tick=0.01):
    """Stretches of [lo, hi] over ``floor`` seconds that no span other
    than a ``group`` covers, each with the spans that end and start at its
    edges.  A span under ``tick`` seconds (the flusher's ``worker_sample``
    and ``worker_flush`` every 2 s, a ``py_gc``) ends no stretch: it would
    cut every long one into pieces of 2 s."""
    named = sorted((s for s in loaded if not s.get("group")
                    and s["end"] > lo and s["start"] < hi
                    and spans.seconds(s) >= tick),
                   key=lambda s: s["start"])
    out, reach, last = [], lo, None
    for s in named + [{"name": "window", "start": hi, "end": hi}]:
        if s["start"] - reach > floor:
            out.append({"at_s": reach - lo, "seconds": s["start"] - reach,
                        "after": last and last["name"], "before": s["name"]})
        if s["end"] > reach:
            reach, last = s["end"], s
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("spans")
    ap.add_argument("--warmup-steps", type=int, required=True)
    ap.add_argument("--setup-s", type=float, required=True)
    ap.add_argument("--window-s", type=float, default=40.0)
    ap.add_argument("--hole", type=float, default=2.0)
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args()
    loaded = spans.read_file(args.spans)
    beats = [s for s in spans.named(loaded, "train_place_batch")
             if "step" in s]
    start = min(s["start"] for s in beats if s["step"] == args.warmup_steps)
    facts = {"spans": loaded, "window_start": start, "setup_s": args.setup_s}
    init = spans.named(loaded, "worker_backend_init")
    warm_up = [s["start"] for s in beats if 1 <= s["step"] and
               s["start"] < start]
    setup = [s for s in loaded if s["name"] in COMPILE_PATH[:2]
             and s["end"] <= start]
    ranked = sorted(own_seconds(setup).items(), key=lambda kv: -kv[1])
    print(json.dumps({
        "setup_s": args.setup_s,
        "cluster_start_s": cluster_start_s.read(facts),
        "worker_chip_s": worker_chip_s.read(facts),
        "import_s": max((s.get("import_s", 0.0) for s in init), default=None),
        "chip_wait_s": max((s.get("chip_wait_s", 0.0) for s in init),
                           default=None),
        "backend_init_s": max(map(spans.seconds, init), default=None),
        "trace_lower_s": trace_lower_s.read(facts),
        "cache_fetch_s": cache_fetch_s.read(facts),
        "cold_compile_s": cold_compile_s.read(facts),
        "warm_up_s": start - min(warm_up) if warm_up else None,
        "setup_unnamed_s": setup_unnamed_s.read(facts),
        "top": [{"span": k[0], "program": k[1], "own_s": round(v, 3)}
                for k, v in ranked[:args.top]],
        # the warm-up counts as named, as ``setup_unnamed_s`` has it
        "holes": holes(loaded + [{"name": "warm_up", "start": min(warm_up),
                                  "end": start}] if warm_up else loaded,
                       start - args.setup_s, start, args.hole),
        "compile_path_spans_in_window": {
            name: sum(1 for s in spans.named(loaded, name)
                      if start <= s["start"] < start + args.window_s)
            for name in COMPILE_PATH},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
