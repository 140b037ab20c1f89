#!/usr/bin/env python3
"""``control_ssm.py`` for the cells that ``kinds/train_pack.py`` runs: the
readings each limit of ``correct`` is set from, at the cell's own sizes,
several seeds in one process on the chip.

    python3 benchmark/control_pack.py <workload> --seeds 1 2 3
    python3 benchmark/control_pack.py <workload> --who program --seeds ...
    python3 benchmark/control_pack.py <workload> --who blind --seeds ...

Without ``--who`` it reads the int8 control: the plain reference in the
program's place, computed in int8 (the inputs of every linear layer, the tied
head's too; the recurrence itself stays float32) against the same reference
in float32, on the same packed row, over every judged weight (RMSNorm weights
and every mixer's ``A_log``, ``dt_bias``, ``D`` and convolution).  ``--who
blind`` reads the second control: THE PROGRAM with the boundaries dropped
(the same packed row and loss mask handed over without ``segment_ids``: a
state that runs through every document's start, a convolution and an
attention that read across it) against the reference that keeps them.  Every
run of either has to be called wrong by at least one limit.  ``--who
program`` reads what the program gives on each seed: one call of the compiled
train step on the check batch (its moments and its update) and its loss
function's gradient against the reference, as a run of the cell does round
its window.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _setup(entry, config, mix):
    import jax.numpy as jnp
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.spmd import make_lm_train_step

    from benchmark import archs
    arch = archs.of(config)
    s, opts, seq = arch.sizes_of(config), config["train"], mix["seq_len"]
    cfg = arch.program_config(s, seq, opts)
    init_fn, step_fn, place = make_lm_train_step(
        cfg, build_mesh(MeshSpec()), learning_rate=opts["learning_rate"],
        param_dtype=jnp.bfloat16)
    return arch, s, cfg, init_fn, step_fn, place, \
        entry["chips"] * (opts["tokens_per_chip"] // seq)


def _check(entry, mix, s, rows, seed, place):
    """(the check batch on the host, its check rows placed)."""
    from benchmark.kinds import train_pack
    check, check_rows = train_pack.check_batch(
        seed, rows, mix["seq_len"], entry["chips"], s["V"], mix["documents"])
    return check, place({k: v[check_rows]
                         for k, v in train_pack.arrays(check).items()})


def program_numbers(entry, config, mix, seeds):
    import jax

    from benchmark.kinds import train_loop, train_pack
    arch, s, cfg, init_fn, step_fn, place, rows = _setup(entry, config, mix)
    compiled = None
    for seed in seeds:
        params, state, shardings = train_loop.fresh_state(arch, s, init_fn,
                                                          seed)
        params = arch.finish(params, s, seed)
        check, small = _check(entry, mix, s, rows, seed, place)
        batch = place(train_pack.arrays(check))
        if compiled is None:
            compiled = step_fn.lower(params, state, batch).compile()
        params, state, m = compiled(params, state, batch)
        got = train_pack.step_readings(m, params, state, arch.judged_of)
        jax.tree.map(lambda a: a.delete(), (params, state))
        w = arch.make_weights(s, seed, shardings)
        out = train_pack.compare_with_reference(arch, w, small, cfg, s, got,
                                                config["train"])
        out.update(got["pack"], step_grad_norm=got["grad_norm"])
        del w, small
        yield seed, out


def _as_a_step(got, loss, start, opts):
    """What a train step would leave behind had its gradient been ``got``
    from the judged weights ``start`` (float32 AdamW's first step with
    bfloat16 moments and weights), for ``judge_step``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    a, lr = opts["adamw"], opts["learning_rate"]
    bf16 = lambda x: np.asarray(jnp.asarray(x, jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32))
    g = jax.tree.map(lambda x: bf16(np.asarray(x)), got)
    p0 = jax.tree.map(lambda x: np.asarray(x.astype(jnp.float32)), start)
    return {"loss": float(loss), "count": 1,
            "mu": jax.tree.map(lambda g: bf16((1 - a["b1"]) * g), g),
            "nu": jax.tree.map(lambda g: bf16((1 - a["b2"]) * g * g), g),
            "weights": jax.tree.map(
                lambda g, p: bf16(p - lr * (
                    g / (np.abs(g) + a["eps"]) + a["weight_decay"] * p)),
                g, p0)}


def control_numbers(entry, config, mix, seeds, who="control"):
    """The int8 reference (``control``) or the program without its
    boundaries (``blind``) in the program's place, against the float32
    reference with them."""
    import jax

    from benchmark.kinds import train, train_loop, train_pack, train_ssm
    arch, s, cfg, init_fn, _step, place, rows = _setup(entry, config, mix)
    ref, opts = arch.reference(), config["train"]
    for seed in seeds:
        w, state, _ = train_loop.fresh_state(arch, s, init_fn, seed)
        w = arch.finish(w, s, seed)
        jax.tree.map(lambda x: x.delete(), state)
        _, small = _check(entry, mix, s, rows, seed, place)
        want_loss, want = ref.loss_and_judged_grads(
            w, small["tokens"], small["loss_mask"], small["segment_ids"], s)
        if who == "blind":
            loss, got = train_pack.program_grads(
                arch, w, {k: v for k, v in small.items()
                          if k != "segment_ids"}, cfg)
        else:
            loss, got = ref.loss_and_judged_grads(
                w, small["tokens"], small["loss_mask"], small["segment_ids"],
                s, "int8")
        step = _as_a_step(got, loss, arch.judged_of(w), opts)
        yield seed, {
            "norm_grad_distance": float(ref.relative_distance(got, want)),
            "norms_alone_distance": float(ref.relative_distance(
                arch.norms_of(got), arch.norms_of(want))),
            "ssm_alone_distance": float(ref.relative_distance(
                arch.ssm_of(got), arch.ssm_of(want))),
            "loss": float(loss), "want_loss": float(want_loss),
            **train.judge_step(step, float(want_loss), want,
                               arch.judged_of(w), opts),
            **train_ssm.update_mismatch(step["weights"], want,
                                        arch.judged_of(w), opts)}
        del w, small


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--who", choices=("control", "blind", "program"),
                    default="control")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import common
    from benchmark.kinds import train_pack
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == args.workload)
    config = common.load_json("configs", entry["config"] + ".json")
    mix = common.load_json("traffic", entry["traffic"] + ".json")
    if args.rehearse:
        tiny = common.load_json("tests", "tiny.json")
        config = {**config, **tiny["config"],
                  "train": {**config["train"], **tiny["train"]}}
    config, mix = train_pack.cell_config(
        {"config": config, "traffic": mix, "rehearse": args.rehearse})
    device = common.device_facts(entry["chips"], rehearse=args.rehearse)
    limits, worst, wrong = config["correct"], {}, []
    numbers = program_numbers(entry, config, mix, args.seeds) \
        if args.who == "program" \
        else control_numbers(entry, config, mix, args.seeds, args.who)
    pick = max if args.who == "program" else min
    for seed, got in numbers:
        called = [k for k, v in got.items()
                  if k in limits and not v <= limits[k]]
        wrong.append(bool(called))
        for k, v in got.items():
            if isinstance(v, (int, float)):
                worst[k] = pick(worst.get(k, v), v)
        print(json.dumps({"seed": seed, "who": args.who, **got,
                          "called_wrong_by": called, "device": device}),
              flush=True)
    print(json.dumps({("largest" if args.who == "program" else "smallest"):
                      worst, "runs_called_wrong": sum(wrong),
                      "runs": len(wrong)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
