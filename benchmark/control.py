#!/usr/bin/env python3
"""The two readings a limit of ``correct`` is set from, at a cell's own
sizes, several seeds in one process on the chips the cell asks for.

    python3 benchmark/control.py <workload> --seeds 1 2 3
    python3 benchmark/control.py <train workload> --who program --seeds ...

Without ``--who`` it reads the control: the reference in the program's
place, computed in int8, the precision below the bfloat16 that the
configurations state.  Every number it prints that has a limit in the
config file has to lie above it (``tests/test_control.py`` keeps the same
at a size a test can hold).  ``--who program`` reads, for a training cell,
what the program itself gives on each seed: one call of the compiled train
step and its loss function's gradient against the reference, as a run of
the cell does round its window.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _train_setup(entry, config, mix, s):
    """(cfg, init_fn, compiled-step maker, place, rows) of a training cell,
    on a mesh of the chips it asks for, as kinds/train.py builds them."""
    import jax.numpy as jnp
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step

    from benchmark import common
    chips, seq, opts = entry["chips"], mix["seq_len"], config["train"]
    cfg = common.llama_config(s, seq, **common.train_options(opts))
    mesh = build_mesh(MeshSpec(fsdp=chips) if chips > 1 else MeshSpec())
    init_fn, step_fn, place = make_lm_train_step(
        cfg, mesh, learning_rate=opts["learning_rate"],
        param_dtype=jnp.bfloat16)
    return cfg, init_fn, step_fn, place, chips * (
        opts["tokens_per_chip"] // seq)


def program_numbers(entry, config, mix, s, seeds):
    """For each seed, every number ``correct`` compares, as the program
    gives it: one call of the compiled train step on the check batch and
    the loss function's gradient, against the reference (what a run of the
    cell does round its window, without the window)."""
    import jax

    from benchmark import weights
    from benchmark.kinds import train
    cfg, init_fn, step_fn, place, rows = _train_setup(entry, config, mix, s)
    chips, seq, compiled = entry["chips"], mix["seq_len"], None
    for seed in seeds:
        params, opt_state, shardings = train.fresh_state(init_fn, s, seed)
        check, check_rows = train.check_batch(seed, rows, seq, chips, s["V"])
        batch = place(check)
        if compiled is None:
            compiled = step_fn.lower(params, opt_state, batch).compile()
        params, opt_state, m = compiled(params, opt_state, batch)
        got = train.step_readings(m, params, opt_state)
        jax.tree.map(lambda a: a.delete(), (params, opt_state))
        w = weights.make(s, seed, shardings)
        small = place({k: v[check_rows] for k, v in check.items()})
        out = train.compare_with_reference(w, small, cfg, s, got,
                                           config["train"])
        out["step_grad_norm"] = got["grad_norm"]
        del w, small        # the next seed's state needs their room
        yield seed, out


def control_numbers(entry, config, mix, s, seeds):
    """For each seed, the same numbers with the int8 reference in the
    program's place: its loss, its gradient, and a float32 AdamW step on
    that gradient kept in bfloat16 as the program keeps its state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, weights
    from benchmark.kinds import train
    _cfg, init_fn, _step, place, rows = _train_setup(entry, config, mix, s)
    chips, seq, opts = entry["chips"], mix["seq_len"], config["train"]
    a, lr = opts["adamw"], opts["learning_rate"]
    bf16 = lambda x: np.asarray(jnp.asarray(x, jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32))
    shardings = None
    for seed in seeds:
        if shardings is None:
            params, opt_state, shardings = train.fresh_state(init_fn, s, seed)
            jax.tree.map(lambda x: x.delete(), (params, opt_state))
        w = weights.make(s, seed, shardings)
        check, check_rows = train.check_batch(seed, rows, seq, chips, s["V"])
        small = place({k: v[check_rows] for k, v in check.items()})
        run = lambda q: jax.jit(
            lambda w, t, m: reference.loss_and_norm_grads(w, t, m, s, q))(
                w, small["tokens"], small["loss_mask"])
        (want_loss, want), (loss, got) = run(None), run("int8")
        g = jax.tree.map(lambda x: bf16(np.asarray(x)), got)
        p0 = jax.tree.map(lambda x: np.asarray(x.astype(jnp.float32)),
                          train.norms_of(w))
        step = {"loss": float(loss), "count": 1,
                "mu": jax.tree.map(lambda g: bf16((1 - a["b1"]) * g), g),
                "nu": jax.tree.map(lambda g: bf16((1 - a["b2"]) * g * g), g),
                "weights": jax.tree.map(
                    lambda g, p: bf16(p - lr * (
                        g / (np.abs(g) + a["eps"]) + a["weight_decay"] * p)),
                    g, p0)}
        yield seed, {
            "norm_grad_distance": float(
                reference.relative_distance(got, want)),
            **train.judge_step(step, float(want_loss), want,
                               train.norms_of(w), opts)}


def serve_numbers(s, seed, mix, n, width):
    """{served_margin_mean: mean margin of the tokens the int8 control would
    have served} over a sample shaped like a run's: prompts and answers of
    the mix's lengths, teacher forced on seeded tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, traffic, weights
    w = weights.make(s, seed)
    reqs = traffic.build({**mix, "loop": "closed", "requests": n}, seed,
                         0.0, s["V"])
    rng = np.random.default_rng(seed)
    seqs = np.zeros((n, width), np.int32)
    scored = np.zeros((n, width - 1), bool)
    for i, r in enumerate(reqs):
        p, a = len(r.prompt), r.max_tokens
        seqs[i, :p + a] = r.prompt + rng.integers(1, s["V"], a).tolist()
        scored[i, p - 1:p + a - 1] = True
    _, control = jax.jit(
        lambda w, q, m: reference.served_margins(w, q, m, s, "int8"))(
            w, jnp.asarray(seqs), jnp.asarray(scored))
    return {"served_margin_mean": float(np.asarray(control)[scored].mean())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--who", choices=("control", "program"),
                    default="control")
    args = ap.parse_args()

    from benchmark import common, weights
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == args.workload)
    config = common.load_json("configs", entry["config"] + ".json")
    mix = common.load_json("traffic", entry["traffic"] + ".json")
    s = weights.sizes_of(config)
    device = common.device_facts(entry["chips"], rehearse=False)
    limits = config["correct"]
    worst = {}
    if mix["kind"] == "train":
        numbers = (program_numbers if args.who == "program"
                   else control_numbers)(entry, config, mix, s, args.seeds)
    else:
        eo = mix["engine_options"]
        numbers = ((seed, serve_numbers(s, seed, mix, mix["verify_requests"],
                                        eo["max_seq_len"]))
                   for seed in args.seeds)
    pick = max if args.who == "program" else min
    for seed, got in numbers:
        for k, v in got.items():
            worst[k] = pick(worst.get(k, v), v)
            limit = limits.get(k)
            print(json.dumps({
                "seed": seed, args.who: k, "value": v, "limit": limit,
                "called_wrong": None if limit is None else not v <= limit,
                "device": device}), flush=True)
    print(json.dumps({("largest" if args.who == "program" else "smallest"):
                      worst}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
