"""Runner for training cells of MiMo-V2-Flash's block (``model_type``
mimo_v2_flash: window layers whose softmax carries a learned sink beside full
layers, head counts and rotary bases by layer kind, sparse experts with no
shared one, a chip's share of the heads as well as of the experts):
``JaxTrainer`` -> ``make_lm_train_step``, as ``kinds/train_gdla.py``, whose
pieces that are not its model's are imported from ``kinds/train_mla.py``,
``kinds/train_moe.py`` and ``kinds/train.py`` (the fewest steps of a traced
run, the selection bias a run starts from, the step's readings of its experts'
loads, the check batch, the judgement of the first step).  The step's report
carries ``sink_mass_mean``, the share of a window row's softmax mass that the
sinks took.

The comparison judges the gradient in every RMSNorm weight and in every window
layer's sink (``archs/mimo_v2_flash.judged_of``; the sinks alone are printed
beside it: their gradient exists only through the softmax's extra column),
the first step's moments over the same weights, its update of them exactly,
and its routers' choices.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from typing import Any, Dict

from benchmark import archs, common
from benchmark.kinds.train import _batches, check_batch, judge_step
from benchmark.kinds.train_mla import TRACED_RUN_STEPS
from benchmark.kinds.train_moe import (fresh_state, moe_readings,
                                       step_readings)

#: what the step reports beside its loss and its experts' loads
REPORT_KEYS = ("sink_mass_mean",)


def cell_config(cell: Dict[str, Any]) -> Dict[str, Any]:
    """(config, traffic) of the cell; a rehearsal takes its toy sizes from
    ``tests/tiny_sink.json`` on top of ``tests/tiny.json``'s, which knows no
    window, sink, share or expert keys."""
    config, mix = cell["config"], cell["traffic"]
    if cell["rehearse"]:
        tiny = common.load_json("tests", "tiny_sink.json")
        config = {**config, **tiny["config"],
                  "share": {**config["share"], **tiny["config"]["share"]}}
        mix = {**mix, **tiny["traffic"]}
    return config, mix


def program_has_the_model() -> bool:
    """Whether this checkout's program has the model at all, asked of the
    files and not by import: the model's module imports jax, which the
    driver process may not."""
    from importlib.machinery import PathFinder

    import ray_tpu
    return PathFinder.find_spec("mimo_v2", [os.path.join(
        os.path.dirname(ray_tpu.__file__), "models")]) is not None


def report_readings(metrics) -> Dict[str, float]:
    """The step's report of its sinks, read to the host."""
    return {k: float(metrics[k]) for k in REPORT_KEYS}


def compare_with_reference(arch, w, bias, tokens, small, cfg, s, step,
                           opts) -> Dict[str, Any]:
    """Against the plain reference on the same weights and selection bias:
    the program's loss function (its kernels, remat and loss chunks, as the
    step uses them) on the check rows ``small``, by the gradient in every
    judged weight; and the compiled step's own first call on ``tokens``, by
    its moments over and its update of the judged weights on the check
    rows, and by its routers' choices on every row."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.mimo_v2 import loss_fn

    ref = arch.reference()
    judged = jax.tree.map(lambda a: a.astype(jnp.float32), arch.judged_of(w))

    # The bias is an argument: closed over, it would be a constant of the
    # program, and every seed would compile its own.
    t0 = common.now()
    loss, grads = jax.block_until_ready(jax.jit(jax.value_and_grad(
        lambda judged, w, bias, batch: loss_fn(
            arch.with_judged(w, judged), batch, cfg, {"bias": bias})))(
                judged, w, bias, small))
    t1 = common.now()
    want_loss, want, _ = jax.block_until_ready(
        ref.loss_judged_grads_and_routing(
            w, bias, small["tokens"], small["loss_mask"], s))
    want_choices = jax.block_until_ready(ref.routing(w, bias, tokens, s))
    common.say("check", program_s=round(t1 - t0, 2),
               reference_s=round(common.now() - t1, 2))
    part = lambda pick: float(ref.relative_distance(pick(grads), pick(want)))
    return {"loss": float(loss), "want_loss": float(want_loss),
            "norm_grad_distance": float(ref.relative_distance(grads, want)),
            "norms_alone_distance": part(arch.norms_of),
            "sinks_alone_distance": part(arch.sinks_of),
            "routing_mismatch_share": float(ref.routing_mismatch_share(
                step["choices"], want_choices, s["X"])),
            **judge_step(step, float(want_loss), want, arch.judged_of(w),
                         opts)}


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
        # Nothing of a step stays on the device past the next one (PERF.md
        # section 6, PR 29).
        nonlocal params, state, dropped
        params, state, m = compiled(params, state, batch)
        loss = float(m["loss"])             # the host read ends the step
        dropped += float(m["moe_dropped"])
        return loss, m

    # Warm-up; its first step is the one compared with the reference.
    _, m = step(check_dev)
    got = {**step_readings(m, params, state, arch.judged_of),
           "sinks": report_readings(m)}
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
                trace_dir and n < TRACED_RUN_STEPS + spec["trace_steps"] - 1):
            break
    out["window_s"] = time.perf_counter() - t_start
    # The last step's loads, and the assignments not computed in any step
    # this run made, warm-up included.
    last = {**moe_readings(m), "moe_dropped": dropped, **report_readings(m)}
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
    w = arch.make_weights(s, seed, shardings)
    small = place({"tokens": check["tokens"][check_rows],
                   "loss_mask": check["loss_mask"][check_rows]})
    out["check"] = {"step": {k: got[k] for k in ("loss", "grad_norm", "moe",
                                                 "sinks")},
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
    if not program_has_the_model():
        # Before any cluster or worker starts: a checkout from before the
        # model fails at once, and cleanly.
        raise SystemExit("this checkout's program has no mimo_v2 model "
                         "(ray_tpu/models/mimo_v2.py): the cell cannot run")
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
                run_config=RunConfig(name="benchmark_train_sink",
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
        # What the readers need of the model: sizes, counts, and the loads
        # of the traced steps (layer-means, from the device).
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
    if cell["trace"] and worker.get("scopes"):
        # Beside ``sink_device_share``: the seconds round db alone.
        from benchmark.layer_metrics.conv_device_share import seconds_under
        common.say("sink", sink_grad_s=round(seconds_under(
            worker["scopes"], "attn/sink_grad"), 6),
            busy_s=worker["trace"]["busy_s"])
    by = worker.get("scopes")
    if by and by["ops_s"]:
        common.say("scopes", named_s=round(by["named_s"], 4),
                   ops_s=round(by["ops_s"], 4),
                   seconds={k: round(v, 4) for k, v in sorted(
                       by["scopes"].items(), key=lambda kv: -kv[1])[:40]})
    return facts
