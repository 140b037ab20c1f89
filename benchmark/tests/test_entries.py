"""``BENCHMARK.json``'s ``per_layer`` table against its own rules, a case a
rule (PR 61: one entry a reader, which lists its cells; until then a copy a
cell under a suffix, 128 of 128)."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = os.path.join(os.path.dirname(HERE), "layer_metrics")

#: Names that keep a suffix, each with what holds it.  ``tests/`` is not a
#: ``benchmark`` PR's to edit, and these files assert these names letter for
#: letter: the entry keeps its name and, where its reader serves other cells
#: too, the suffixed entry lists the asserting test's cell alone and the
#: stem's entry the others.  A PR that makes those tests look an entry up by
#: its stem frees the next ``benchmark`` PR to rename them (PERF.md, 7).
PINNED = {
    "loop_exit_entropy.loop4k": "tests/test_ouro.py",
    "moe_load_max_over_mean.moe8k": "tests/test_afmoe.py",
    "eva_attn_roofline.eva32k": "tests/test_evabyte.py",
    "eva_pool_roofline.eva32k": "tests/test_evabyte.py",
    "eva_device_share.eva32k": "tests/test_evabyte.py",
    "eva_remote_key_share.eva32k": "tests/test_evabyte.py",
    "mfu_looped_pct.eva32k": "tests/test_evabyte.py",
    "idle_share.eva32k": "tests/test_evabyte.py",
    "hc_sinkhorn_residual.mhc8k": "tests/test_xing4_cell.py",
}
#: Readers with no entry here or in ``later_cells.json``, and why.
KEPT = {
    "gen_late_p99_ms": "the open-loop serving cells of PERF.md section 7; "
                       "tests/test_loadgen.py runs it",
    "ttft_p90_ms": "the same cells; also an end-to-end reader of theirs",
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def later():
    with open(os.path.join(HERE, "later_cells.json")) as f:
        return json.load(f)


def _stem(name):
    return name.split(".")[0]


def _cells(metric, bench):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def rule_no_cell_reads_a_reader_twice(bench, later):
    for cell in (w["name"] for w in bench["workloads"]):
        stems = [_stem(m["name"]) for m in bench["per_layer"]
                 if cell in _cells(m, bench)]
        assert len(stems) == len(set(stems)), (cell, sorted(stems))


def rule_no_two_entries_share_a_stem_but_a_pinned_one(bench, later):
    by_stem = {}
    for m in bench["per_layer"]:
        by_stem.setdefault(_stem(m["name"]), []).append(m["name"])
    for stem, names in by_stem.items():
        assert len(names) == len(set(names)), names
        assert len([n for n in names if n not in PINNED]) <= 1, names


def rule_no_name_holds_a_dot_but_a_pinned_one(bench, later):
    dotted = {m["name"] for m in bench["per_layer"] if "." in m["name"]}
    assert dotted == set(PINNED)
    for name, holder in PINNED.items():
        with open(os.path.join(ROOT, holder)) as f:
            assert f'"{name}"' in f.read(), (name, holder)
    # what waits for a later PR: a suffix only where an entry's ``moves``
    # differs from its stem's in BENCHMARK.json (an entry has one)
    moves = {_stem(m["name"]): m["moves"] for m in bench["per_layer"]}
    for m in later["per_layer"]:
        if "." in m["name"]:
            assert moves[_stem(m["name"])] != m["moves"], m


def rule_copies_of_a_stem_agree(bench, later):
    seen = {}
    for m in bench["per_layer"]:
        facts = tuple(m[k] for k in ("unit", "better", "source", "layer",
                                     "moves"))
        assert seen.setdefault(_stem(m["name"]), facts) == facts, m


def rule_lists_name_cells_that_exist_once_each_in_order(bench, later):
    order = [w["name"] for w in bench["workloads"]]
    reports = {w for m in bench["end_to_end"] for w in _cells(m, bench)}
    for m in bench["per_layer"]:
        listed = m.get("workloads")
        if listed is None:
            continue
        assert listed and set(listed) <= set(order), m
        assert len(listed) == len(set(listed)), m
        assert listed == sorted(listed, key=order.index), m
        assert set(listed) <= reports
    waiting = {w["name"] for w in later["workloads"]}
    for m in later["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= waiting, m


def rule_every_reader_has_an_entry_or_a_reason(bench, later):
    files = {f[:-3] for f in os.listdir(READERS)
             if f.endswith(".py") and f != "__init__.py"}
    entered = {_stem(m["name"]) for m in bench["per_layer"]}
    waiting = {_stem(m["name"]) for m in later["per_layer"]}
    assert entered <= files and waiting <= files
    assert files - entered - waiting == set(KEPT)


def rule_every_cell_reads_a_layer_and_the_table_has_room(bench, later):
    assert len(bench["per_layer"]) <= 128
    assert len(bench["per_layer"]) == 56        # PR 61; 128 before it
    for cell in (w["name"] for w in bench["workloads"]):
        assert any(cell in m["workloads"] for m in bench["per_layer"]
                   if "workloads" in m), cell
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


RULES = [v for k, v in sorted(globals().items()) if k.startswith("rule_")]


@pytest.mark.parametrize("rule", RULES, ids=lambda r: r.__name__[5:])
def test_per_layer(rule, bench, later):
    rule(bench, later)
