#!/usr/bin/env python3
"""``control_conv.py`` for the cells that ``kinds/train_sink.py`` runs: the two
readings each limit of ``correct`` is set from, at the cell's own sizes,
several seeds in one process on the chip.

    python3 benchmark/control_sink.py <workload> --seeds 1 2 3
    python3 benchmark/control_sink.py <workload> --who program --seeds ...
    python3 benchmark/control_sink.py <workload> --who nosink --seeds ...

Without ``--who`` it reads the control: the plain reference in the program's
place, computed in int8 (the inputs of every linear layer, the routers' and
the head's too) against the same reference in float32, over every judged
weight (RMSNorm weights and every window layer's sink).  Every run has to be
called wrong by at least one limit.  ``--who program`` reads what the program
gives on each seed: one call of the compiled train step on the check batch
(its moments, its update and its routers' choices on every row) and its loss
function's gradient against the reference, as a run of the cell does round its
window.  ``--who nosink`` reads the float32 reference with the sinks left out
(every ``b_h`` at -1e4, where the column's mass is 0) against itself with
them: it has to be called wrong by ``norm_grad_distance``, or the comparison
would not see a program that forgot the sink.  The benchmark's own runs never
run this.
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


def program_numbers(entry, config, mix, seeds):
    import jax
    import jax.numpy as jnp

    from benchmark.kinds import train, train_moe, train_sink
    arch, s, cfg, init_fn, step_fn, place, rows = _setup(entry, config, mix)
    compiled = None
    for seed in seeds:
        params, state, shardings, bias = train_moe.fresh_state(
            arch, s, init_fn, seed)
        check, check_rows = train.check_batch(seed, rows, mix["seq_len"],
                                              entry["chips"], s["V"])
        batch = place(check)
        if compiled is None:
            compiled = step_fn.lower(params, state, batch).compile()
        params, state, m = compiled(params, state, batch)
        got = train_moe.step_readings(m, params, state, arch.judged_of)
        jax.tree.map(lambda a: a.delete(), (params, state))
        w = arch.make_weights(s, seed, shardings)
        small = place({k: v[check_rows] for k, v in check.items()})
        out = train_sink.compare_with_reference(
            arch, w, jnp.asarray(bias), check["tokens"], small, cfg, s, got,
            config["train"])
        out.update(got["moe"], step_grad_norm=got["grad_norm"])
        del w, small
        yield seed, out


def control_numbers(entry, config, mix, seeds, who="control"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.kinds import train, train_moe
    arch, s, _cfg, init_fn, _step, place, rows = _setup(entry, config, mix)
    ref, opts = arch.reference(), config["train"]
    a, lr = opts["adamw"], opts["learning_rate"]
    bf16 = lambda x: np.asarray(jnp.asarray(x, jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32))
    run = lambda w, b, t, m, q: ref.loss_judged_grads_and_routing(
        w, b, t, m, s, q)

    def faulty(w):
        """What stands in the program's place: the weights in int8's
        reference, or (``nosink``) the weights with every sink at -1e4."""
        if who == "control":
            return w, "int8"
        return {**w, "layers": [
            {**layer, **({"sink": jnp.full_like(layer["sink"], -1e4)}
                         if "sink" in layer else {})}
            for layer in w["layers"]]}, None
    for seed in seeds:
        w, state, _, bias = train_moe.fresh_state(arch, s, init_fn, seed)
        jax.tree.map(lambda x: x.delete(), state)
        check, check_rows = train.check_batch(seed, rows, mix["seq_len"],
                                              entry["chips"], s["V"])
        small = place({k: v[check_rows] for k, v in check.items()})
        (want_loss, want, want_top), (loss, got, top) = (
            run(w_, jnp.asarray(bias), small["tokens"], small["loss_mask"], q)
            for w_, q in ((w, None), faulty(w)))
        g = jax.tree.map(lambda x: bf16(np.asarray(x)), got)
        p0 = jax.tree.map(lambda x: np.asarray(x.astype(jnp.float32)),
                          arch.judged_of(w))
        step = {"loss": float(loss), "count": 1,
                "mu": jax.tree.map(lambda g: bf16((1 - a["b1"]) * g), g),
                "nu": jax.tree.map(lambda g: bf16((1 - a["b2"]) * g * g), g),
                "weights": jax.tree.map(
                    lambda g, p: bf16(p - lr * (
                        g / (np.abs(g) + a["eps"]) + a["weight_decay"] * p)),
                    g, p0)}
        yield seed, {
            "norm_grad_distance": float(ref.relative_distance(got, want)),
            "routing_mismatch_share": float(
                ref.routing_mismatch_share(top, want_top, s["X"])),
            "norms_alone_distance": float(ref.relative_distance(
                arch.norms_of(got), arch.norms_of(want))),
            "sinks_alone_distance": float(ref.relative_distance(
                arch.sinks_of(got), arch.sinks_of(want))),
            **train.judge_step(step, float(want_loss), want,
                               arch.judged_of(w), opts)}
        del w, small


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--who", choices=("control", "program", "nosink"),
                    default="control")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import common
    from benchmark.kinds import train_sink
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == args.workload)
    config = common.load_json("configs", entry["config"] + ".json")
    mix = common.load_json("traffic", entry["traffic"] + ".json")
    if args.rehearse:
        tiny = common.load_json("tests", "tiny.json")
        config = {**config, **tiny["config"],
                  "train": {**config["train"], **tiny["train"]}}
    config, mix = train_sink.cell_config(
        {"config": config, "traffic": mix, "rehearse": args.rehearse})
    device = common.device_facts(entry["chips"], rehearse=args.rehearse)
    limits, worst, wrong = config["correct"], {}, []
    numbers = (program_numbers(entry, config, mix, args.seeds)
               if args.who == "program"
               else control_numbers(entry, config, mix, args.seeds, args.who))
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
