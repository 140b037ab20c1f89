"""The control has to come out as not correct: the reference computed in
int8 lies several times farther from the float32 reference than the
program's bfloat16 does, at a size a test can hold (the chip's readings at
the cells' own sizes are in PERF.md)."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, control, reference, weights

S = {"V": 2048, "E": 256, "L": 4, "H": 4, "Hkv": 2, "D": 64, "M": 704,
     "theta": 1e6, "eps": 1e-5}
CELL = ({"chips": 1},
        {"train": {**common.load_json("configs", "yi-coder-1.5b.json")["train"],
                   "tokens_per_chip": 512, "attention": "reference",
                   "loss_chunks": 0}},
        {"seq_len": 256})
SEEDS = [1, 2, 3]


def test_int8_control_is_farther_than_the_program_in_training():
    """Through the compiled train step and through the loss function: the
    numbers that separate the two precisions do so threefold, and the
    update of the RMSNorm weights is the float32 AdamW step's exactly."""
    program = dict(control.program_numbers(*CELL, S, SEEDS))
    ctrl = dict(control.control_numbers(*CELL, S, SEEDS))
    for seed in SEEDS:
        p, c = program[seed], ctrl[seed]
        print(seed, p, c)
        for name in ("norm_grad_distance", "step_moments_distance"):
            assert c[name] > 3 * p[name], (seed, name)
        assert p["step_update_mismatch"] == 0
        assert p["step_loss_distance"] < 1e-3


def test_a_wrong_step_is_called_wrong():
    """A step whose gradient missed a factor (a mean taken as a sum over
    two chips) or whose update moved the weights fails the step's check."""
    from benchmark.kinds import train
    opts = CELL[1]["train"]
    a = opts["adamw"]
    rng = np.random.default_rng(0)
    g = {"final_norm": rng.normal(size=64).astype(np.float32)}
    ones = {"final_norm": jnp.ones(64, jnp.bfloat16)}
    step = lambda scale, w: {
        "loss": 10.0, "count": 1,
        "mu": {"final_norm": (1 - a["b1"]) * scale * g["final_norm"]},
        "nu": {"final_norm": (1 - a["b2"]) * (scale * g["final_norm"]) ** 2},
        "weights": {"final_norm": np.full(64, w, np.float32)}}
    sound = train.judge_step(step(1.0, 1.0), 10.0, g, ones, opts)
    assert sound["step_moments_distance"] < 1e-6
    assert sound["step_update_mismatch"] == 0 == sound["step_loss_distance"]
    doubled = train.judge_step(step(2.0, 1.0), 10.0, g, ones, opts)
    assert doubled["step_moments_distance"] == pytest.approx(1.0, rel=1e-5)
    moved = train.judge_step(step(1.0, 0.99609375), 10.0, g, ones, opts)
    assert moved["step_update_mismatch"] == 1.0


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_control_is_farther_than_the_program_in_serving(seed):
    """The mean margin of the tokens each would serve."""
    from ray_tpu.models.llama import forward
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(1, S["V"], (2, 256)), jnp.int32)
    w = weights.make(S, seed)
    cfg = common.llama_config(S, 256, remat=True, attention_impl="reference")
    pick = forward(w, tokens, cfg)[:, :-1].argmax(-1)
    scored = jnp.ones((2, 255), bool)
    lg = reference.logits(w, tokens, S)[:, :-1]
    served = (lg.max(-1) - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0])
    _, ctrl = reference.served_margins(w, tokens, scored, S, "int8")
    print("serve", float(served.mean()), float(ctrl.mean()))
    assert float(ctrl.mean()) > 3 * float(served.mean())
