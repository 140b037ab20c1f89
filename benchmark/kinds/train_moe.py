"""Runner for training cells of models that are not Llama-shaped:
``JaxTrainer`` -> ``make_lm_train_step``, as ``kinds/train.py``, with the
model's parts (program configuration, weights' layout, judged norms,
reference, counts) from ``benchmark/archs/<model_type>.py`` and the step's
state handed on (a selection bias the optimizer does not touch).

What ``kinds/train.py`` has that is not Llama's is imported from it.  The
comparison adds ``routing_mismatch_share``: the share of the reference's
assignments that the routers of the compiled, timed step chose differently
in its first call, over every row of that call (the step hands out its
routers' choices with its metrics).
"""

from __future__ import annotations

import shutil
import tempfile
import time
from typing import Any, Dict

from benchmark import archs, common
from benchmark.kinds.train import (_batches, adam_state, check_batch,
                                   judge_step)


#: The spread of the selection bias a run starts from: the size the
#: program's own rule leaves when it is run to its fixed point on four rows
#: at the cell's sizes (0.012 to 0.014 over three seeds; PERF.md section 6,
#: PR 29).  Four times that, the bias itself decided which experts are
#: busy: the held experts' largest load over their mean rose from 1.3 (no
#: bias) to 2.0 - 2.9, and their share of a step's assignments, and with it
#: tokens/s, moved with the seed (11 % between 12 seeds).
BIAS_SIGMA = 0.0125


def cell_config(cell: Dict[str, Any]) -> Dict[str, Any]:
    """(config, traffic) of the cell; a rehearsal takes its toy sizes from
    ``tests/tiny_moe.json`` on top of ``tests/tiny.json``'s, which knows no
    expert keys."""
    config, mix = cell["config"], cell["traffic"]
    if cell["rehearse"]:
        tiny = common.load_json("tests", "tiny_moe.json")
        config = {**config, **tiny["config"],
                  "share": {**config["share"], **tiny["config"]["share"]}}
        mix = {**mix, **tiny["traffic"]}
    return config, mix


def step_readings(metrics, params, opt_state, norms_of) -> Dict[str, Any]:
    """``kinds/train.step_readings`` over this model's norms, with the
    step's own counts of its experts' loads."""
    import jax
    import numpy as np
    adam = adam_state(opt_state)
    host = lambda tree: jax.tree.map(
        lambda a: np.asarray(a.astype("float32")), norms_of(tree))
    return {"loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "count": int(adam.count), "mu": host(adam.mu),
            "nu": host(adam.nu), "weights": host(params),
            "moe": moe_readings(metrics),
            "choices": np.asarray(metrics["moe_choices"])}


def moe_readings(metrics) -> Dict[str, float]:
    """The step's scalars of its experts' loads."""
    return {k: float(v) for k, v in metrics.items()
            if k.startswith("moe_") and not v.ndim}


def compare_with_reference(arch, w, bias, tokens, small, cfg, s, step,
                           opts) -> Dict[str, Any]:
    """Against the plain reference on the same weights and selection bias:
    the program's loss function (its kernels, remat and loss chunks, as the
    step uses them) on the check rows ``small``, by the gradient in every
    RMSNorm weight; and the compiled step's own first call on ``tokens``,
    by its moments and update on the check rows and by its routers' choices
    on every row."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.afmoe import loss_fn

    ref = arch.reference()
    norms = jax.tree.map(lambda a: a.astype(jnp.float32), arch.norms_of(w))

    # The bias is an argument: closed over, it would be a constant of the
    # program, and every seed would compile its own.
    t0 = common.now()
    loss, grads = jax.block_until_ready(jax.jit(jax.value_and_grad(
        lambda norms, w, bias, batch: loss_fn(
            arch.with_norms(w, norms), batch, cfg, {"bias": bias})))(
                norms, w, bias, small))
    t1 = common.now()
    want_loss, want, _ = jax.block_until_ready(
        ref.loss_norm_grads_and_routing(
            w, bias, small["tokens"], small["loss_mask"], s))
    want_choices = jax.block_until_ready(ref.routing(w, bias, tokens, s))
    common.say("check", program_s=round(t1 - t0, 2),
               reference_s=round(common.now() - t1, 2))
    return {"loss": float(loss), "want_loss": float(want_loss),
            "norm_grad_distance": float(ref.relative_distance(grads, want)),
            "routing_mismatch_share": float(ref.routing_mismatch_share(
                step["choices"], want_choices, s["X"])),
            **judge_step(step, float(want_loss), want, arch.norms_of(w),
                         opts)}


def fresh_state(arch, s, init_fn, seed):
    """(params, step state, parameter shardings, the selection bias on the
    host): the program's optimizer state round the benchmark's own weights,
    which the reference reads too, and a selection bias that is not zero
    (normal, ``BIAS_SIGMA``), so that choosing by s + b and weighting by s
    are told apart.  The step donates its state, so the bias is kept on the
    host for the check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    params, state = init_fn(jax.random.key(0))
    shardings = jax.tree.map(lambda a: a.sharding, params)
    layout = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    jax.tree.map(lambda a: a.delete(), params)
    params = archs.make_weights(arch.shapes(s), seed, shardings)
    if jax.tree.map(lambda a: (a.shape, a.dtype), params) != layout:
        raise RuntimeError("the program's parameter tree is not the layout "
                           f"{arch.__name__} makes")
    bias = np.asarray(BIAS_SIGMA * jax.random.normal(
        jax.random.fold_in(jax.random.key(seed % (2 ** 31)), 7),
        state.model["bias"].shape, jnp.float32))
    return (params, state._replace(model={"bias": jnp.asarray(bias)}),
            shardings, bias)


def train_loop(spec: Dict[str, Any]) -> None:
    """Runs in the trainer's worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu import train
    from ray_tpu.parallel.spmd import make_lm_train_step

    from benchmark import scopes, trace

    out: Dict[str, Any] = {
        "device": common.device_facts(spec["chips"], spec["rehearse"])}
    arch = archs.of(spec["config"])
    s, opts = arch.sizes_of(spec["config"]), spec["config"]["train"]
    seq, rows, seed = spec["seq_len"], spec["rows"], spec["seed"]
    cfg = arch.program_config(s, seq, opts)
    mesh = train.get_mesh()
    if mesh.size != spec["chips"]:
        raise RuntimeError(f"mesh {mesh.shape} is not {spec['chips']} chips")
    init_fn, step_fn, place = make_lm_train_step(
        cfg, mesh, learning_rate=opts["learning_rate"],
        param_dtype=jnp.bfloat16)

    t0 = common.now()
    params, state, shardings, bias0 = fresh_state(arch, s, init_fn, seed)
    out["init_s"] = common.now() - t0
    check, check_rows = check_batch(seed, rows, seq, spec["chips"], s["V"])
    check_dev = place(check)

    t0 = common.now()
    compiled = step_fn.lower(params, state, check_dev).compile()
    out["compile_s"] = common.now() - t0
    mem = compiled.memory_analysis()
    out["memory_analysis"] = {"argument": mem.argument_size_in_bytes,
                              "temp": mem.temp_size_in_bytes}
    program_text = compiled.as_text()
    out["kernels_in_step"] = program_text.count("tpu_custom_call")
    dropped = 0.0       # over every step this run makes, warm-up included

    def step(batch):
        # Nothing of a step stays on the device past the next one: buffers
        # held across steps were followed by steps that stalled for seconds
        # (PERF.md section 6, PR 29).
        nonlocal params, state, dropped
        params, state, m = compiled(params, state, batch)
        loss = float(m["loss"])             # the host read ends the step
        dropped += float(m["moe_dropped"])
        return loss, m

    # Warm-up; its first step is the one compared with the reference.
    _, m = step(check_dev)
    got = step_readings(m, params, state, arch.norms_of)
    bias1 = np.asarray(state.model["bias"])
    batches = _batches(seed + 1, rows, seq, s["V"])
    for _ in range(spec["warmup_steps"] - 1):
        step(place(next(batches)))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if spec["trace"] \
        else None
    step_s, losses, traced = [], [], []
    out["window_start"] = common.now()
    t_start = time.perf_counter()
    while True:
        n = len(step_s)
        if trace_dir and n == 2:
            trace.start(trace_dir)
        ts = time.perf_counter()
        loss, m = step(place(next(batches)))
        te = time.perf_counter()
        step_s.append(te - ts)
        losses.append(loss)
        if trace_dir and 2 <= n <= 1 + spec["trace_steps"]:
            traced.append(moe_readings(m))
        if trace_dir and n == 1 + spec["trace_steps"]:
            jax.profiler.stop_trace()
        if te - t_start >= spec["seconds"] and not (
                trace_dir and n < 1 + spec["trace_steps"]):
            break
    out["window_s"] = time.perf_counter() - t_start
    # The last step's loads, and the assignments not computed in any step
    # this run made, warm-up included.
    last = {**moe_readings(m), "moe_dropped": dropped}
    out.update(steps=len(step_s), rows=rows, seq_len=seq,
               rows_a_call=min(opts["layer_rows"] or rows, rows),
               tokens_per_step=rows * seq, loss_first=losses[0],
               loss_last=losses[-1], trace_steps=spec["trace_steps"],
               memory_stats=common.memory_stats(),
               memory_peak_bytes=common.memory_peak_bytes(),
               moe_last=last, moe_traced=traced,
               step_ms=[round(1e3 * float(q), 1) for q in np.quantile(
                   step_s, (0, 0.25, 0.5, 0.75, 1))])
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"the loss is not finite: {losses[:5]}...")

    # Everything below is outside the window.
    jax.tree.map(lambda a: a.delete(), (params, state))
    if trace_dir:
        loaded = trace.load(trace.find_xplane(trace_dir))
        out["trace"] = trace.reduce(loaded, top=16)
        out["scopes"] = scopes.seconds_by_scope(loaded, program_text)
        shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = common.now()
    w = archs.make_weights(arch.shapes(s), seed, shardings)
    small = place({"tokens": check["tokens"][check_rows],
                   "loss_mask": check["loss_mask"][check_rows]})
    out["check"] = {"step": {k: got[k] for k in ("loss", "grad_norm", "moe")},
                    **compare_with_reference(
                        arch, w, jnp.asarray(bias0), check["tokens"], small,
                        cfg, s, got, opts)}
    # That the bias moved by the rule's size (the rule itself: tests/).
    out["check"]["bias_step_max"] = float(np.abs(bias1 - bias0).max())
    out["check_s"] = common.now() - t0
    # The loads go through train.report's own keys, so that the program
    # records them (counters.json, the train_report span).
    train.report({"summary": out, **last})


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    """Driver side.  ``cell`` is what run.py assembled; returns the facts
    the metrics are read from."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, mix = cell_config(cell)
    chips, seq = cell["chips"], mix["seq_len"]
    if mix["mesh"]:
        raise RuntimeError("this runner runs one chip's share, without a "
                           "mesh")
    arch = archs.of(config)
    s = arch.sizes_of(config)
    spec = {
        "chips": chips, "rehearse": cell["rehearse"], "seed": cell["seed"],
        "seconds": cell["seconds"], "trace": cell["trace"],
        "config": config, "seq_len": seq,
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
                    env_per_worker={"XLA_FLAGS": "--xla_force_host_platform_"
                                    f"device_count={chips}"}
                    if cell["rehearse"] else None),
                run_config=RunConfig(name="benchmark_train_moe",
                                     storage_path=tmp)).fit()
        if result.error is not None:
            raise result.error
        worker = result.metrics["summary"]
    finally:
        ray_tpu.shutdown()

    steps, window, check = (worker["steps"], worker["window_s"],
                            worker["check"])
    counts = arch.parameters(s)
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
        # What the new readers need of the model: sizes, counts, and the
        # loads of the traced steps (layer-means, from the device).
        "arch": {"sizes": s, "parameters": counts,
                 "expert_layers": s["L"] - s["Ld"],
                 "moe_traced": worker["moe_traced"],
                 "rows_a_call": worker["rows_a_call"],
                 "scopes": worker.get("scopes")},
        "compared": {k: v for k, v in check.items()
                     if k in config["correct"]},
    }
    common.say("check", **{k: v for k, v in check.items()
                           if k not in config["correct"]})
    common.say("train", rows=worker["rows"], steps=steps,
               window_s=round(window, 3), init_s=round(worker["init_s"], 2),
               compile_s=round(worker["compile_s"], 2),
               check_s=round(worker["check_s"], 2),
               kernels_in_step=worker["kernels_in_step"],
               loss=[worker["loss_first"], worker["loss_last"]],
               parameters=counts, moe_last=worker["moe_last"],
               step_ms_quartiles=worker["step_ms"])
    common.say("train", memory_analysis=worker["memory_analysis"],
               memory_stats=worker["memory_stats"])
    by = worker.get("scopes")
    if by and by["ops_s"]:
        common.say("scopes", named_s=round(by["named_s"], 4),
                   ops_s=round(by["ops_s"], 4),
                   seconds={k: round(v, 4) for k, v in sorted(
                       by["scopes"].items(), key=lambda kv: -kv[1])[:24]})
    return facts
