"""Every seed offers the same load: the same number of requests, the same
prompt and answer tokens, the same multiset of arrival gaps."""

import json
import os

import numpy as np
import pytest

from benchmark import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
SEEDS = (0, 7, 2 ** 31 + 11)


def _mix(name):
    """A committed mix; ``chat-open`` is chat-sat's lengths offered in an
    open loop at 14 requests/s (no cell runs one yet: PERF.md section 7)."""
    with open(os.path.join(HERE, "..", "traffic", "chat-sat.json")) as f:
        mix = json.load(f)
    if name == "chat-open":
        mix.update(loop="open", rate_per_s=14.0)
    return mix


@pytest.mark.parametrize("name", ["chat-open", "chat-sat"])
def test_same_multiset_under_every_seed(name):
    mix = _mix(name)
    runs = [traffic.build(mix, seed, 40.0, 64000) for seed in SEEDS]
    pairs = [sorted((len(r.prompt), r.max_tokens) for r in run if r.measured)
             for run in runs]
    assert pairs[0] == pairs[1] == pairs[2]
    assert len({json.dumps(traffic.offered_totals(run)) for run in runs}) == 1
    # ... in another order, with other token ids.
    assert [len(r.prompt) for r in runs[0]] != [len(r.prompt) for r in runs[1]]
    assert runs[0][0].prompt != runs[1][0].prompt


def test_open_loop_gaps_are_one_multiset():
    mix = _mix("chat-open")
    gaps = []
    for seed in SEEDS:
        run = traffic.build(mix, seed, 40.0, 64000)
        due = [mix["lead_s"]] + [r.due_s for r in run if r.measured]
        assert due == sorted(due)
        assert due[-1] == pytest.approx(mix["lead_s"] + 40.0)
        gaps.append(np.sort(np.diff(due)))
        assert len(due) - 1 == round(mix["rate_per_s"] * 40.0) == 560
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    np.testing.assert_allclose(gaps[0], gaps[2], rtol=0, atol=1e-9)
    # the grid has the exponential's mean and its long tail
    assert gaps[0].mean() == pytest.approx(1 / mix["rate_per_s"])
    assert gaps[0].max() > 4 / mix["rate_per_s"]


def test_a_seed_only_turns_the_sequence_round():
    """Which requests crowd together is the file's, not the seed's."""
    mix = _mix("chat-open")
    def window(seed):
        run = [r for r in traffic.build(mix, seed, 40.0, 64000) if r.measured]
        gaps = np.diff([mix["lead_s"]] + [r.due_s for r in run])
        return [(round(float(g), 9), len(r.prompt), r.max_tokens)
                for g, r in zip(gaps, run)]
    a, b = window(SEEDS[0]), window(SEEDS[2])
    assert a != b
    assert any(a[k:] + a[:k] == b for k in range(len(a)))


def test_lengths_keep_their_limits_and_median():
    spec = _mix("chat-open")["prompt_tokens"]
    grid = traffic.length_grid(spec, 400)
    assert grid.min() == spec["min"] and grid.max() == spec["max"]
    assert abs(np.median(grid) - spec["median"]) <= 1


def test_buckets_used_cover_the_prompts():
    assert traffic.buckets_used(_mix("chat-open")) == [128, 256, 512]
