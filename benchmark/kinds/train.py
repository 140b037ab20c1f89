"""Runner for training cells: ``JaxTrainer`` -> ``make_lm_train_step``.

The driver process never imports jax; the one worker owns the chips and
does everything that needs them: the steps, the trace and its reduction,
and the comparison with the plain reference after the window.
"""

from __future__ import annotations

import queue
import shutil
import tempfile
import threading
import time
from typing import Any, Dict

from benchmark import common


def _batches(seed: int, rows: int, seq: int, vocab: int):
    """Seeded token batches from a host thread that runs during the step."""
    import numpy as np
    rng = np.random.default_rng(seed)
    mask = np.ones((rows, seq), np.int32)
    mask[:, -1] = 0
    q: "queue.Queue" = queue.Queue(maxsize=2)

    def fill():
        while True:
            q.put({"tokens": rng.integers(0, vocab, (rows, seq),
                                          dtype=np.int32),
                   "loss_mask": mask})

    threading.Thread(target=fill, daemon=True, name="bench-batches").start()
    while True:
        yield q.get()


def norms_of(p):
    return {"final_norm": p["final_norm"],
            "attn_norm": p["blocks"]["attn_norm"],
            "mlp_norm": p["blocks"]["mlp_norm"]}


def program_loss_and_norm_grads(cfg):
    """The program's loss function and its gradient in the RMSNorm weights,
    as the train step uses them (same kernels, remat and loss chunks)."""
    import jax
    from ray_tpu.models.llama import loss_fn

    def program_loss(norms, w, batch):
        blocks = {**w["blocks"], "attn_norm": norms["attn_norm"],
                  "mlp_norm": norms["mlp_norm"]}
        return loss_fn({**w, "blocks": blocks,
                        "final_norm": norms["final_norm"]}, batch, cfg)

    return jax.value_and_grad(program_loss)


def adam_state(opt_state):
    """The part of the optimizer's state that holds the two moments."""
    import jax
    has = lambda x: hasattr(x, "mu") and hasattr(x, "nu")
    found = [x for x in jax.tree.leaves(opt_state, is_leaf=has) if has(x)]
    if len(found) != 1:
        raise RuntimeError("the optimizer's state does not hold one pair of "
                           f"moments: {type(opt_state)}")
    return found[0]


def step_readings(metrics, params, opt_state) -> Dict[str, Any]:
    """What the compiled step's first call leaves behind, read to the host:
    its loss and gradient norm, the two moments of its own gradient in the
    RMSNorm weights (after one step they are (1-b1) g and (1-b2) g^2, so
    they carry the step's gradient as the step's sharding, its gradient
    reduction and the moments' own type left it), and those weights after
    the update."""
    import jax
    import numpy as np
    adam = adam_state(opt_state)
    host = lambda tree: jax.tree.map(
        lambda a: np.asarray(a.astype("float32")), norms_of(tree))
    return {"loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "count": int(adam.count), "mu": host(adam.mu),
            "nu": host(adam.nu), "weights": host(params)}


def judge_step(step, want_loss, want, start, opts) -> Dict[str, float]:
    """The step's readings against a float32 AdamW step on the reference's
    gradient ``want`` from the weights ``start`` (both over the RMSNorm
    weights).  ``opts["adamw"]`` holds the program's optimizer constants."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference
    a, lr = opts["adamw"], opts["learning_rate"]
    if step["count"] != 1:
        raise RuntimeError(f"the optimizer counts {step['count']} steps "
                           "after the first")
    g = jax.tree.map(lambda x: np.asarray(x, np.float32), want)
    p0 = jax.tree.map(lambda x: np.asarray(x.astype(jnp.float32)), start)
    got = {"mu": jax.tree.map(lambda m: m / (1 - a["b1"]), step["mu"]),
           "nu": jax.tree.map(lambda v: np.sqrt(v / (1 - a["b2"])),
                              step["nu"])}
    # Bias-corrected, the first step's moments are g and g*g.
    after = jax.tree.map(
        lambda g, p: np.asarray(jnp.asarray(
            p - lr * (g / (np.abs(g) + a["eps"]) + a["weight_decay"] * p),
            jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)), g, p0)
    differ = sum(int(np.sum(x != y)) for x, y in zip(
        jax.tree.leaves(step["weights"]), jax.tree.leaves(after)))
    return {
        "step_loss_distance": abs(step["loss"] - want_loss) / abs(want_loss),
        "step_moments_distance": float(reference.relative_distance(
            got, {"mu": g, "nu": jax.tree.map(np.abs, g)})),
        "step_update_mismatch": differ / sum(
            x.size for x in jax.tree.leaves(after))}


def compare_with_reference(w, batch, cfg, s, step=None,
                           opts=None) -> Dict[str, Any]:
    """The program against the plain reference on the same weights and
    rows: its loss function's gradient in the RMSNorm weights (see
    reference.loss_and_norm_grads), held in float32 so that it is not
    rounded on the way out, and, where ``step`` holds the readings of the
    compiled step's own first call on these rows, that step."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference

    norms = jax.tree.map(lambda a: a.astype(jnp.float32), norms_of(w))
    loss, grads = jax.jit(program_loss_and_norm_grads(cfg))(norms, w, batch)
    want_loss, want = jax.jit(
        lambda w, t, m: reference.loss_and_norm_grads(w, t, m, s))(
            w, batch["tokens"], batch["loss_mask"])
    out = {"loss": float(loss), "want_loss": float(want_loss),
           "norm_grad_distance": float(
               reference.relative_distance(grads, want))}
    if step is not None:
        out.update(judge_step(step, out["want_loss"], want, norms_of(w),
                              opts))
    return out


def fresh_state(init_fn, s, seed):
    """(params, opt_state, parameter shardings): the program's optimizer
    state round the benchmark's own weights, which the reference reads
    too."""
    import jax

    from benchmark import weights
    params, opt_state = init_fn(jax.random.key(0))
    shardings = jax.tree.map(lambda a: a.sharding, params)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    jax.tree.map(lambda a: a.delete(), params)
    params = weights.make(s, seed, shardings)
    if jax.tree.map(lambda a: (a.shape, a.dtype), params) != shapes:
        raise RuntimeError("the program's parameter tree is not the layout "
                           "benchmark/weights.py makes")
    return params, opt_state, shardings


def check_batch(seed, rows, seq, chips, vocab):
    """(batch of ``rows`` rows, indices of its check rows): only the check
    rows count towards the loss, one on each chip, so that the float32
    reference has one row a chip to do."""
    import numpy as np
    rng = np.random.default_rng(seed)
    check_rows = [i * (rows // chips) for i in range(chips)]
    check = {"tokens": rng.integers(0, vocab, (rows, seq), dtype=np.int32),
             "loss_mask": np.zeros((rows, seq), np.int32)}
    check["loss_mask"][check_rows, :-1] = 1
    return check, check_rows


def train_loop(spec: Dict[str, Any]) -> None:
    """Runs in the trainer's worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu import train
    from ray_tpu.parallel.spmd import make_lm_train_step

    from benchmark import reference, trace, weights

    out: Dict[str, Any] = {
        "device": common.device_facts(spec["chips"], spec["rehearse"])}
    s, opts = spec["sizes"], spec["train"]
    seq, rows, seed = spec["seq_len"], spec["rows"], spec["seed"]
    cfg = common.llama_config(s, seq, **common.train_options(opts))
    mesh = train.get_mesh()
    out["mesh"] = {a: int(n) for a, n in mesh.shape.items() if n > 1}
    if mesh.size != spec["chips"]:
        raise RuntimeError(f"mesh {mesh.shape} is not {spec['chips']} chips")
    init_fn, step_fn, place = make_lm_train_step(
        cfg, mesh, learning_rate=opts["learning_rate"],
        param_dtype=jnp.bfloat16)

    t0 = common.now()
    params, opt_state, shardings = fresh_state(init_fn, s, seed)
    out["init_s"] = common.now() - t0
    check, check_rows = check_batch(seed, rows, seq, spec["chips"], s["V"])
    check_dev = place(check)

    t0 = common.now()
    compiled = step_fn.lower(params, opt_state, check_dev).compile()
    out["compile_s"] = common.now() - t0
    mem = compiled.memory_analysis()
    out["memory_analysis"] = {"argument": mem.argument_size_in_bytes,
                              "temp": mem.temp_size_in_bytes}
    out["kernels_in_step"] = compiled.as_text().count("tpu_custom_call")

    def step(batch):
        nonlocal params, opt_state
        params, opt_state, m = compiled(params, opt_state, batch)
        return float(m["loss"]), m          # the host read ends the step

    # Warm-up; its first step is the one compared with the reference.
    _, m = step(check_dev)
    got = step_readings(m, params, opt_state)
    batches = _batches(seed + 1, rows, seq, s["V"])
    for _ in range(spec["warmup_steps"] - 1):
        step(place(next(batches)))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if spec["trace"] \
        else None
    step_s, losses = [], []
    out["window_start"] = common.now()
    t_start = time.perf_counter()
    while True:
        n = len(step_s)
        if trace_dir and n == 2:
            trace.start(trace_dir)
        ts = time.perf_counter()
        loss, _ = step(place(next(batches)))
        te = time.perf_counter()
        step_s.append(te - ts)
        losses.append(loss)
        if trace_dir and n == 1 + spec["trace_steps"]:
            jax.profiler.stop_trace()
        if te - t_start >= spec["seconds"] and not (
                trace_dir and n < 1 + spec["trace_steps"]):
            break
    out["window_s"] = time.perf_counter() - t_start
    out.update(steps=len(step_s), rows=rows, seq_len=seq,
               tokens_per_step=rows * seq, loss_first=losses[0],
               loss_last=losses[-1], trace_steps=spec["trace_steps"],
               memory_stats=common.memory_stats(),
               memory_peak_bytes=common.memory_peak_bytes())
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"the loss is not finite: {losses[:5]}...")

    # Everything below is outside the window.
    jax.tree.map(lambda a: a.delete(), (params, opt_state))
    if trace_dir:
        out["trace"] = trace.reduce(trace.load(trace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = common.now()
    w = weights.make(s, seed, shardings)
    small = place({"tokens": check["tokens"][check_rows],
                   "loss_mask": check["loss_mask"][check_rows]})
    out["check"] = {"step": {k: got[k] for k in ("loss", "grad_norm")},
                    **compare_with_reference(w, small, cfg, s, got, opts)}
    out["check_s"] = common.now() - t0
    train.report({"summary": out})


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    """Driver side.  ``cell`` is what run.py assembled; returns the facts
    the metrics are read from."""
    import ray_tpu
    from ray_tpu.train import (JaxTrainer, MeshConfig, RunConfig,
                               ScalingConfig)

    config, mix, chips = cell["config"], cell["traffic"], cell["chips"]
    seq = mix["seq_len"]
    spec = {
        "chips": chips, "rehearse": cell["rehearse"], "seed": cell["seed"],
        "seconds": cell["seconds"], "trace": cell["trace"],
        "sizes": cell["sizes"], "train": config["train"], "seq_len": seq,
        "rows": chips * (config["train"]["tokens_per_chip"] // seq),
        "warmup_steps": mix["warmup_steps"],
        "trace_steps": mix["trace_steps"]}
    ray_tpu.init(**({"num_tpus": chips} if cell["rehearse"] else {}))
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if have < chips:
            raise RuntimeError(f"this host has {have} TPU chips; the cell "
                               f"needs {chips}")
        with tempfile.TemporaryDirectory(prefix="bench_train_") as tmp:
            result = JaxTrainer(
                train_loop, train_loop_config=spec,
                scaling_config=ScalingConfig(
                    num_workers=1, use_tpu=True, chips_per_worker=chips,
                    mesh_config=MeshConfig.parse(
                        mix["mesh"], devices_per_worker=chips)
                    if mix["mesh"] else None,
                    env_per_worker={"XLA_FLAGS": "--xla_force_host_platform_"
                                    f"device_count={chips}"}
                    if cell["rehearse"] else None),
                run_config=RunConfig(name="benchmark_train",
                                     storage_path=tmp)).fit()
        if result.error is not None:
            raise result.error
        worker = result.metrics["summary"]
    finally:
        ray_tpu.shutdown()

    steps, window, check = (worker["steps"], worker["window_s"],
                            worker["check"])
    facts = {
        "device": worker["device"],
        "memory_peak_bytes": worker["memory_peak_bytes"],
        "window_start": worker["window_start"],
        "compile_s": worker["compile_s"],
        "attempted": steps, "failed": 0,
        "train_tok_s_chip": steps * worker["tokens_per_step"] / window
        / chips,
        "tokens_per_step": worker["tokens_per_step"],
        "trace_steps": worker["trace_steps"], "rows": worker["rows"],
        "seq_len": seq,
        "trace": worker.get("trace"),
        # Judged: the numbers the config file gives a limit.  The others go
        # on the [check] line (PERF.md section 2 says why each is not).
        "compared": {k: v for k, v in check.items()
                     if k in config["correct"]},
    }
    common.say("check", **{k: v for k, v in check.items()
                           if k not in config["correct"]})
    common.say("train", mesh=worker["mesh"] or {"dp": 1},
               rows=worker["rows"], steps=steps,
               window_s=round(window, 3), init_s=round(worker["init_s"], 2),
               compile_s=round(worker["compile_s"], 2),
               check_s=round(worker["check_s"], 2),
               kernels_in_step=worker["kernels_in_step"],
               loss=[worker["loss_first"], worker["loss_last"]])
    common.say("train", memory_analysis=worker["memory_analysis"],
               memory_stats=worker["memory_stats"])
    return facts
