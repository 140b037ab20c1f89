"""Runner for training cells of a chunked-attention stack with several
output heads a position (``model_type`` evabyte): ``JaxTrainer`` ->
``make_lm_train_step``, as ``kinds/train.py``, with the model's parts
(program configuration, weights' layout, judged weights, reference, counts)
from ``benchmark/archs/<model_type>.py``.

What ``kinds/train.py`` and ``kinds/train_loop.py`` have that is not their
model's is imported from them.  The comparison adds what only this loss has:
the compiled, timed step's own report of its first call (the masked mean
loss of every head) against the reference's on the same rows
(``head_loss_distance``), and the pooling vectors among the weights whose
gradients are judged.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from typing import Any, Dict

from benchmark import archs, common
from benchmark.kinds.train import (_batches, adam_state, check_batch,
                                   judge_step)
from benchmark.kinds.train_loop import fresh_state

#: what the step reports of its heads, beside ``loss`` and ``grad_norm``
HEAD_KEYS = ("head_loss",)


def cell_config(cell: Dict[str, Any]) -> Dict[str, Any]:
    """(config, traffic) of the cell; a rehearsal takes its toy sizes from
    ``tests/tiny_eva.json`` on top of ``tests/tiny.json``'s, which knows no
    window, chunk or second head."""
    config, mix = cell["config"], cell["traffic"]
    if cell["rehearse"]:
        tiny = common.load_json("tests", "tiny_eva.json")
        config = {**config, **tiny["config"]}
        mix = {**mix, **tiny["traffic"]}
    return config, mix


def program_has_the_model() -> bool:
    """Whether this checkout's program has the model at all, asked of the
    files and not by import: the model's module imports jax, which the
    driver process may not."""
    from importlib.machinery import PathFinder

    import ray_tpu
    return PathFinder.find_spec("evabyte", [os.path.join(
        os.path.dirname(ray_tpu.__file__), "models")]) is not None


def head_readings(metrics) -> Dict[str, Any]:
    """The step's report of its heads, read to the host."""
    import numpy as np
    return {k: np.asarray(metrics[k], np.float64).tolist() for k in HEAD_KEYS}


def step_readings(metrics, params, opt_state, judged_of) -> Dict[str, Any]:
    """``kinds/train.step_readings`` over this model's judged weights, with
    the step's own report of its heads."""
    import jax
    import numpy as np
    adam = adam_state(opt_state)
    host = lambda tree: jax.tree.map(
        lambda a: np.asarray(a.astype("float32")), judged_of(tree))
    return {"loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "count": int(adam.count), "mu": host(adam.mu),
            "nu": host(adam.nu), "weights": host(params),
            "heads": head_readings(metrics)}


def head_distance(got: Dict[str, Any], want: Dict[str, Any]):
    """Largest relative difference of the heads' losses."""
    import numpy as np
    a, b = (np.asarray(r["head_loss"], np.float64) for r in (got, want))
    return {"head_loss_distance": float(np.max(np.abs(a - b) / np.abs(b)))}


def update_mismatch(weights, want, start, opts) -> float:
    """Share of the weights ``weights`` (after the step's first call, read to
    the host) that are not a float32 AdamW step from ``start`` on the
    reference's gradient ``want``, to within a hundredth of the learning
    rate and half a bfloat16 step.  A norm's offset starts at 0, where
    bfloat16 is fine enough to hold a step of 1e-5 (a Llama norm's weight of
    1 does not move at all, and ``kinds/train.judge_step`` compares
    exactly): the step's own bfloat16 moments round the update by up to
    0.4 % of it, which an exact comparison would count; a component whose
    gradient's sign the precision decides is a whole step off, and is what
    this counts, beside any update that is wrong."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    a, lr = opts["adamw"], opts["learning_rate"]
    wrong = total = 0
    for got, g, p0 in zip(*(jax.tree.leaves(t) for t in (weights, want,
                                                         start))):
        g = np.asarray(g, np.float32)
        p0 = np.asarray(p0.astype(jnp.float32))
        after = p0 - lr * (g / (np.abs(g) + a["eps"]) + a["weight_decay"] * p0)
        wrong += int(np.sum(np.abs(got - after)
                            > 0.01 * lr + np.abs(after) * 2.0 ** -8))
        total += got.size
    return wrong / total


def judge(arch, step, want_loss, want_report, want, w, opts):
    """The step's readings against the reference's: its moments over every
    judged weight, its update over the RMSNorm offsets, its report of the
    heads."""
    full = judge_step(step, want_loss, want, arch.judged_of(w), opts)
    return {**full, **head_distance(step["heads"], want_report),
            "step_update_mismatch": update_mismatch(
                arch.norms_of(step["weights"]), arch.norms_of(want),
                arch.norms_of(w), opts)}


def compare_with_reference(arch, w, small, cfg, s, step,
                           opts) -> Dict[str, Any]:
    """Against the plain reference on the same weights: the program's loss
    function (its kernels, remat and loss chunks, as the step uses them) on
    the check rows ``small``, by the gradient in every RMSNorm weight and
    in every pooling vector; and the compiled step's own first call, whose
    loss counts the check rows alone."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.evabyte import loss_fn

    ref = arch.reference()
    judged = jax.tree.map(lambda a: a.astype(jnp.float32), arch.judged_of(w))
    t0 = common.now()
    loss, grads = jax.block_until_ready(jax.jit(jax.value_and_grad(
        lambda judged, w, batch: loss_fn(
            arch.with_judged(w, judged), batch, cfg)))(judged, w, small))
    t1 = common.now()
    want_loss, want_report, want = jax.block_until_ready(
        ref.loss_and_judged_grads(w, small["tokens"], small["loss_mask"], s))
    common.say("check", program_s=round(t1 - t0, 2),
               reference_s=round(common.now() - t1, 2))
    want_report = head_readings(want_report)
    part = lambda names: float(ref.relative_distance(
        *({"blocks": {n: g["blocks"][n] for n in names}}
          for g in (grads, want))))
    return {"loss": float(loss), "want_loss": float(want_loss),
            "want_heads": want_report,
            "norm_grad_distance": float(ref.relative_distance(grads, want)),
            "norms_alone_distance": part(arch.NORMS),
            "pooling_alone_distance": part(arch.POOLING),
            **judge(arch, step, float(want_loss), want_report, want, w,
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
    params, state, shardings = fresh_state(arch, s, init_fn, seed)
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

    def step(batch):
        nonlocal params, state
        params, state, m = compiled(params, state, batch)
        return float(m["loss"]), m          # the host read ends the step

    # Warm-up; its first step is the one compared with the reference.
    _, m = step(check_dev)
    got = step_readings(m, params, state, arch.judged_of)
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
        loss, m = step(place(next(batches)))
        te = time.perf_counter()
        step_s.append(te - ts)
        losses.append(loss)
        if trace_dir and n == 1 + spec["trace_steps"]:
            jax.profiler.stop_trace()
        if te - t_start >= spec["seconds"] and not (
                trace_dir and n < 1 + spec["trace_steps"]):
            break
    out["window_s"] = time.perf_counter() - t_start
    last = head_readings(m)
    out.update(steps=len(step_s), rows=rows, seq_len=seq,
               tokens_per_step=rows * seq, loss_first=losses[0],
               loss_last=losses[-1], trace_steps=spec["trace_steps"],
               memory_stats=common.memory_stats(),
               memory_peak_bytes=common.memory_peak_bytes(),
               heads_last=last,
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
    out["check"] = {"step": {k: got[k] for k in ("loss", "grad_norm",
                                                 "heads")},
                    **compare_with_reference(arch, w, small, cfg, s, got,
                                             opts)}
    out["check_s"] = common.now() - t0
    # The last step's heads go through train.report's own keys, so that
    # the program records them (counters.json, the train_report span).
    train.report({"summary": out, **last})


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    """Driver side.  ``cell`` is what run.py assembled; returns the facts
    the metrics are read from."""
    if not program_has_the_model():
        # Before any cluster or worker starts: a checkout from before the
        # model fails at once, and cleanly.
        raise SystemExit("this checkout's program has no EVA model "
                         "(ray_tpu/models/evabyte.py): the cell cannot run")
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, mix = cell_config(cell)
    chips, seq = cell["chips"], mix["seq_len"]
    if mix["mesh"]:
        raise RuntimeError("this runner runs one chip, without a mesh")
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
                run_config=RunConfig(name="benchmark_train_eva",
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
        # traced steps' device seconds by the program's scopes.
        "arch": {"sizes": s, "parameters": counts,
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
               parameters=counts, heads_last=worker["heads_last"],
               step_ms_quartiles=worker["step_ms"])
    common.say("train", memory_analysis=worker["memory_analysis"],
               memory_stats=worker["memory_stats"])
    by = worker.get("scopes")
    if by and by["ops_s"]:
        common.say("scopes", named_s=round(by["named_s"], 4),
                   ops_s=round(by["ops_s"], 4),
                   seconds={k: round(v, 4) for k, v in sorted(
                       by["scopes"].items(), key=lambda kv: -kv[1])[:32]})
    return facts
