"""Runner for training cells on PACKED rows (``kind`` train_pack; Granite-4.0-
H-Micro's hybrid stack): ``JaxTrainer`` -> ``make_lm_train_step``, as
``kinds/train_ssm.py``, whose pieces that are not its model's are imported
from it, from ``kinds/train.py``, ``kinds/train_eva.py`` and
``kinds/train_loop.py`` (the judgement of the first step, the rule on its
update, the step's readings, the state round the benchmark's own weights).

What is this runner's own is the traffic: documents whose lengths are drawn
log-normal from ``--seed`` are laid end to end in one stream, and the stream
is cut into rows of ``seq_len``; a batch carries ``segment_ids`` beside
``tokens`` and ``loss_mask`` (``rows_of``).  The comparison judges the
program WITH its boundaries against a reference that zeroes the state, the
convolution's reach and the attention's at each one, and the step's report
carries the packing (documents a row, the share of the square their pairs
keep, the chunks a boundary cuts).
"""

from __future__ import annotations

import os
import queue
import shutil
import tempfile
import threading
import time
from typing import Any, Dict

from benchmark import archs, common
from benchmark.kinds.train import adam_state, judge_step
from benchmark.kinds.train_loop import fresh_state
from benchmark.kinds.train_ssm import update_mismatch

#: what the step reports of its packing and its scans, beside ``loss`` and
#: ``grad_norm``
PACK_KEYS = ("ssm_chunk_carry", "ssm_chunks_with_boundary",
             "pack_documents_a_row", "pack_pairs_share")


def cell_config(cell: Dict[str, Any]) -> Dict[str, Any]:
    """(config, traffic) of the cell; a rehearsal takes its toy sizes from
    ``tests/tiny_pack.json`` on top of ``tests/tiny.json``'s, which knows no
    state-space key and no documents."""
    config, mix = cell["config"], cell["traffic"]
    if cell["rehearse"]:
        tiny = common.load_json("tests", "tiny_pack.json")
        config = {**config, **tiny["config"],
                  "train": {**config["train"], **tiny["train"]}}
        mix = {**mix, **tiny["traffic"]}
    return config, mix


def program_has_the_model() -> bool:
    """Whether this checkout's program has the model at all, asked of the
    files and not by import: the model's module imports jax, which the
    driver process may not."""
    from importlib.machinery import PathFinder

    import ray_tpu
    return PathFinder.find_spec("granite_hybrid", [os.path.join(
        os.path.dirname(ray_tpu.__file__), "models")]) is not None


def rows_of(seed: int, rows: int, seq: int, vocab: int, documents):
    """Packed batches without end, from ``seed``: {"tokens", "loss_mask",
    "segment_ids"} int32 [rows, seq], and "lengths", a list a row of its
    documents' lengths inside the row.

    ``documents`` is the traffic file's group: lengths log-normal with the
    given median and sigma, rounded and clipped to [min, max].  The
    documents are laid end to end in ONE stream and the stream is cut into
    rows of ``seq``: a row begins and ends inside a document, nothing is
    padded.  ``segment_ids`` count 0, 1, 2, ... along each row (the part of
    a document that a cut leaves at a row's start is its document 0: every
    row starts from a zero state).  The loss mask is 0 at each document's
    last token (its successor is another document's first) and at the
    row's last position, 1 elsewhere.  Token ids are uniform over the whole
    vocabulary."""
    import numpy as np
    rng = np.random.default_rng(seed)
    d = documents
    left = 0                            # of the document a cut runs through
    while True:
        ids = np.empty((rows, seq), np.int32)
        mask = np.ones((rows, seq), np.int32)
        lengths = []
        for r in range(rows):
            at, n, here = 0, 0, []
            while at < seq:
                if not left:
                    left = int(np.clip(np.rint(rng.lognormal(
                        np.log(d["median"]), d["sigma"])), d["min"],
                        d["max"]))
                take = min(left, seq - at)
                ids[r, at:at + take] = n
                left -= take
                at += take
                if not left:
                    mask[r, at - 1] = 0     # the document's last token
                here.append(take)
                n += 1
            mask[r, -1] = 0
            lengths.append(here)
        yield {"tokens": rng.integers(0, vocab, (rows, seq), dtype=np.int32),
               "loss_mask": mask, "segment_ids": ids, "lengths": lengths}


def _batches(seed: int, rows: int, seq: int, vocab: int, documents):
    """``rows_of`` from a host thread that runs during the step."""
    q: "queue.Queue" = queue.Queue(maxsize=2)
    source = rows_of(seed, rows, seq, vocab, documents)

    def fill():
        while True:
            q.put(next(source))

    threading.Thread(target=fill, daemon=True, name="bench-batches").start()
    while True:
        yield q.get()


def check_batch(seed, rows, seq, chips, vocab, documents):
    """(batch of ``rows`` packed rows, indices of its check rows): only the
    check rows count towards the loss, one on each chip, so that the float32
    reference has one row a chip to do."""
    check = next(rows_of(seed, rows, seq, vocab, documents))
    check_rows = [i * (rows // chips) for i in range(chips)]
    others = [r for r in range(rows) if r not in check_rows]
    check["loss_mask"][others] = 0
    return check, check_rows


def arrays(batch):
    """A batch without what is not the step's (the lengths)."""
    return {k: v for k, v in batch.items() if k != "lengths"}


def pack_readings(metrics) -> Dict[str, float]:
    """The step's report of its packing and its scans, read to the host."""
    return {k: float(metrics[k]) for k in PACK_KEYS}


def step_readings(metrics, params, opt_state, judged_of) -> Dict[str, Any]:
    """``kinds/train.step_readings`` over this model's judged weights, with
    the step's own report of its packing."""
    import jax
    import numpy as np
    adam = adam_state(opt_state)
    host = lambda tree: jax.tree.map(
        lambda a: np.asarray(a.astype("float32")), judged_of(tree))
    return {"loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "count": int(adam.count), "mu": host(adam.mu),
            "nu": host(adam.nu), "weights": host(params),
            "pack": pack_readings(metrics)}


def program_grads(arch, w, small, cfg):
    """(loss, its gradient in every judged weight held in float32) of the
    program's loss function on the batch ``small``, as the step uses it (its
    scan, kernels, remat and loss chunks)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.granite_hybrid import loss_fn
    judged = jax.tree.map(lambda a: a.astype(jnp.float32), arch.judged_of(w))
    return jax.block_until_ready(jax.jit(jax.value_and_grad(
        lambda judged, w, batch: loss_fn(
            arch.with_judged(w, judged), batch, cfg)))(judged, w, small))


def compare_with_reference(arch, w, small, cfg, s, step,
                           opts) -> Dict[str, Any]:
    """Against the plain reference on the same weights and the same packed
    row: the program's loss function on the check rows ``small``, by the
    gradient in every judged weight; and the compiled step's own first
    call, by its moments over and its update of the judged weights
    (``train_ssm.update_mismatch``)."""
    import jax

    ref = arch.reference()
    t0 = common.now()
    loss, grads = program_grads(arch, w, small, cfg)
    t1 = common.now()
    want_loss, want = jax.block_until_ready(ref.loss_and_judged_grads(
        w, small["tokens"], small["loss_mask"], small["segment_ids"], s))
    common.say("check", program_s=round(t1 - t0, 2),
               reference_s=round(common.now() - t1, 2))
    part = lambda pick: float(ref.relative_distance(pick(grads), pick(want)))
    return {"loss": float(loss), "want_loss": float(want_loss),
            "norm_grad_distance": float(ref.relative_distance(grads, want)),
            "norms_alone_distance": part(arch.norms_of),
            "ssm_alone_distance": part(arch.ssm_of),
            **judge_step(step, float(want_loss), want, arch.judged_of(w),
                         opts),
            **update_mismatch(step["weights"], want, arch.judged_of(w), opts)}


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
    docs = spec["documents"]
    cfg = arch.program_config(s, seq, opts)
    mesh = train.get_mesh()
    if mesh.size != spec["chips"]:
        raise RuntimeError(f"mesh {mesh.shape} is not {spec['chips']} chips")
    init_fn, step_fn, place = make_lm_train_step(
        cfg, mesh, learning_rate=opts["learning_rate"],
        param_dtype=jnp.bfloat16)

    t0 = common.now()
    params, state, shardings = fresh_state(arch, s, init_fn, seed)
    params = arch.finish(params, s, seed)
    out["init_s"] = common.now() - t0
    check, check_rows = check_batch(seed, rows, seq, spec["chips"], s["V"],
                                    docs)
    check_dev = place(arrays(check))

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
        params, state, m = compiled(params, state, place(arrays(batch)))
        return float(m["loss"]), m          # the host read ends the step

    # Warm-up; its first step is the one compared with the reference.
    _, m = step(check)
    got = step_readings(m, params, state, arch.judged_of)
    batches = _batches(seed + 1, rows, seq, s["V"], docs)
    for _ in range(spec["warmup_steps"] - 1):
        step(next(batches))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if spec["trace"] \
        else None
    step_s, losses, traced = [], [], []
    out["window_start"] = common.now()
    t_start = time.perf_counter()
    while True:
        n = len(step_s)
        if trace_dir and n == 2:
            trace.start(trace_dir)
        batch = next(batches)
        ts = time.perf_counter()
        loss, m = step(batch)
        te = time.perf_counter()
        step_s.append(te - ts)
        losses.append(loss)
        if trace_dir and 2 <= n <= 1 + spec["trace_steps"]:
            # What the traced steps really held: the program's own report,
            # and the documents' lengths as the host laid them out.
            traced.append({**pack_readings(m), "lengths": batch["lengths"]})
        if trace_dir and n == 1 + spec["trace_steps"]:
            jax.profiler.stop_trace()
        if te - t_start >= spec["seconds"] and not (
                trace_dir and n < 1 + spec["trace_steps"]):
            break
    out["window_s"] = time.perf_counter() - t_start
    last = pack_readings(m)
    out.update(steps=len(step_s), rows=rows, seq_len=seq,
               rows_a_call=min(opts["layer_rows"] or rows, rows),
               tokens_per_step=rows * seq, loss_first=losses[0],
               loss_last=losses[-1], trace_steps=spec["trace_steps"],
               memory_stats=common.memory_stats(),
               memory_peak_bytes=common.memory_peak_bytes(),
               pack_last=last, pack_traced=traced,
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
    small = place({k: v[check_rows] for k, v in arrays(check).items()})
    out["check"] = {"step": {k: got[k] for k in ("loss", "grad_norm",
                                                 "pack")},
                    **compare_with_reference(arch, w, small, cfg, s, got,
                                             opts)}
    out["check_s"] = common.now() - t0
    # The packing and the carry go through train.report's own keys, so that
    # the program records them (counters.json, the train_report span).
    train.report({"summary": out, **last})


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    """Driver side.  ``cell`` is what run.py assembled; returns the facts
    the metrics are read from."""
    if not program_has_the_model():
        # Before any cluster or worker starts: a checkout from before the
        # model fails at once, and cleanly.
        raise SystemExit("this checkout's program has no granite_hybrid "
                         "model (ray_tpu/models/granite_hybrid.py): the "
                         "cell cannot run")
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
        "config": config, "seq_len": seq, "documents": mix["documents"],
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
                run_config=RunConfig(name="benchmark_train_pack",
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
        # What the readers need of the model: sizes, counts, the traced
        # steps' device seconds by the program's scopes and their packing.
        "arch": {"sizes": s, "parameters": counts,
                 "rows_a_call": worker["rows_a_call"],
                 "pack_traced": worker["pack_traced"],
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
               parameters=counts, pack_last=worker["pack_last"],
               step_ms_quartiles=worker["step_ms"])
    common.say("train", memory_analysis=worker["memory_analysis"],
               memory_stats=worker["memory_stats"])
    by = worker.get("scopes")
    if by and by["ops_s"]:
        common.say("scopes", named_s=round(by["named_s"], 4),
                   ops_s=round(by["ops_s"], 4),
                   seconds={k: round(v, 4) for k, v in sorted(
                       by["scopes"].items(), key=lambda kv: -kv[1])[:40]})
    return facts
