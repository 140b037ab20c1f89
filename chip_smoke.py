#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of the 1.36B model (hidden 2048, 24 layers, 16 heads of
128, MLP 5632, vocabulary 32,000, bf16; weights random, from ``--seed``):

  kernels  a task with a one-chip grant checks the flash forward and
           backward against ``reference_attention`` (at the model's
           shape, and for one key head's group of 8 query heads under a
           window at 8,192 tokens) and one decode step of the ragged
           paged kernel against the dense-gather path;
  train    ``JaxTrainer`` with one worker that owns one chip builds the
           step with ``train.get_mesh()`` + ``make_lm_train_step``;
  serve    ``build_llm_deployment(..., num_tpus=1)`` answers requests of
           different prompt lengths, some at once, through the handle;
  verify   a task with a one-chip grant scores every served token with
           the plain forward of ``models.llama`` (teacher forced).

``--chips 4`` runs, instead, mesh training (one worker, four chips,
``fsdp4``) and the same seed and global batch on one chip, and compares.

This process never imports jax: the framework gives the chip to one
worker at a time, and a chip worker dies with its task, so the phases
follow one another on the same chip.  Any phase that fails fails the
run.  Without an accelerator it exits non-zero and prints no result.
``--rehearse`` is the sandbox rehearsal (tiny widths, CPU, kernels in
interpret mode): it can never print ``"ok": true``.

Last line of stdout: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

# What the chip run uses, and the sandbox rehearsal of the same control flow.
REAL = dict(
    model=dict(vocab_size=32000, hidden=2048, layers=24, heads=16,
               kv_heads=16, head_dim=128, mlp_dim=5632, max_seq_len=2048),
    train_attention="flash",
    # slots, pages, page size, kv heads, pages per sequence
    ragged_shape=[64, 2200, 16, 16, 40],
    grouped=(8192, 2048),         # tokens, window of the grouped flash check
    engine_options=dict(max_slots=64, page_size=16, num_pages=2200,
                        max_seq_len=640, prefill_buckets=(64, 256)),
    # (prompt tokens, tokens asked for)
    warm_requests=[(17, 8), (200, 8)],
    burst_requests=[(33, 24), (250, 16), (60, 32), (100, 24), (9, 16),
                    (180, 12)])
TOY = dict(
    model=dict(vocab_size=512, hidden=128, layers=2, heads=4, kv_heads=4,
               head_dim=32, mlp_dim=256, max_seq_len=256),
    train_attention="flash_interpret",
    ragged_shape=None,            # the ragged kernel has no interpret mode
    grouped=(256, 96),
    engine_options=dict(max_slots=4, page_size=8, num_pages=64,
                        max_seq_len=128, prefill_buckets=(16, 64)),
    warm_requests=[(5, 4), (40, 4)],
    burst_requests=[(7, 6), (50, 4), (12, 8)])

# bf16 keeps 8 bits of mantissa.  Errors are relative to the largest
# magnitude of the reference tensor.
KERNEL_TOL = 2e-2
# A served token's reference logit may trail the reference maximum by at
# most this much (logits of the random model are ~N(0, 1); an arbitrary
# token trails by ~4).
LOGIT_MARGIN_TOL = 0.15
# fsdp4 and one device run the same step in a different reduction order.
MESH_LOSS_RTOL = 1e-3


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


# -- code that runs in workers (shipped by value; imports jax there) --------

def _device_facts(spec):
    import jax
    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs), "pid": os.getpid(),
             "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
             "compile_cache": jax.config.jax_compilation_cache_dir}
    if not spec["rehearse"] and facts["platform"] != "tpu":
        raise RuntimeError(f"worker is not on a TPU: {facts}")
    if facts["count"] != spec["chips"]:
        raise RuntimeError(
            f"worker sees {facts['count']} devices, granted "
            f"{spec['chips']}: {facts}")
    return facts


def _peak_bytes():
    """Per local device: peak bytes in use + reserved, where reported."""
    import jax
    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({k: st[k] for k in ("peak_bytes_in_use",
                                       "peak_bytes_reserved", "bytes_limit")
                    if k in st})
    return out


def _model_cfg(spec, **kw):
    import jax.numpy as jnp
    from ray_tpu.models import LlamaConfig
    return LlamaConfig(**spec["model"], dtype=jnp.bfloat16, **kw)


def _rel_err(got, want):
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        raise RuntimeError("kernel output is not finite")
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def kernel_checks(spec):
    """Flash fwd/bwd vs reference_attention (the model's head shape; a
    grouped, windowed one) and the ragged paged decode kernel vs the
    dense-gather path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.attention import flash_attention, reference_attention
    from ray_tpu.ops.paged_attention import _exact_path, _ragged_path

    out = {"device": _device_facts(spec), "tolerance": KERNEL_TOL}
    B, H, S, D = spec["flash_shape"]
    ks = jax.random.split(jax.random.key(spec["seed"]), 8)
    errs, out["flash"] = {}, []
    # The model's own shape, then one key head's group of 8 under a window
    # (a grouped step, the band's edges), small enough that the
    # reference's float32 scores fit.
    seq, window = spec["grouped"]
    for tag, (b, h, hkv, seq), window in (
            ("flash", (B, H, H, S), None),
            ("flash_grouped", (1, 8, 1, seq), window)):
        q, do = (jax.random.normal(kk, (b, h, seq, D), jnp.bfloat16)
                 for kk in ks[:2])
        k, v = (jax.random.normal(kk, (b, hkv, seq, D), jnp.bfloat16)
                for kk in ks[2:4])

        def fwd_bwd(attn):
            def run(q, k, v):
                o, vjp = jax.vjp(attn, q, k, v)
                return (o,) + vjp(do)
            return jax.jit(run)

        t0 = time.perf_counter()
        got = fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=spec["rehearse"],
            window=window))(q, k, v)
        want = fwd_bwd(lambda q, k, v: reference_attention(
            q, k, v, causal=True, window=window))(q, k, v)
        errs.update({f"{tag}_{name}": _rel_err(g, w) for name, g, w in
                     zip(("out", "dq", "dk", "dv"), got, want)})
        out["flash"].append({"shape": [b, h, hkv, seq, D], "window": window,
                             "seconds": round(time.perf_counter() - t0, 1)})

    if spec["ragged_shape"] is None:
        out["ragged"] = "skipped: the ragged kernel has no interpret mode"
    else:
        slots, pages, page, hkv, pps = spec["ragged_shape"]
        rng = np.random.default_rng(spec["seed"])
        kv = jax.random.normal(ks[4], (pages, page, 2 * hkv, D),
                               jnp.bfloat16)
        qd = jax.random.normal(ks[5], (slots, H, D), jnp.bfloat16)
        table = jnp.asarray(rng.integers(1, pages, (slots, pps)), jnp.int32)
        lens = rng.integers(1, pps * page + 1, slots)
        lens[0], lens[1] = 1, pps * page
        lens[3::8] = 0           # empty slots, as the engine leaves them
        live = lens > 0
        lens = jnp.asarray(lens, jnp.int32)
        t0 = time.perf_counter()
        ragged = jax.jit(_ragged_path).lower(qd, kv, table, lens).compile()
        if "tpu_custom_call" not in ragged.as_text():
            raise RuntimeError("no Mosaic kernel in the ragged program")
        got = ragged(qd, kv, table, lens)
        want = jax.jit(_exact_path, static_argnums=4)(
            qd, kv, table, lens, page)
        errs["ragged_out"] = _rel_err(got[live], want[live])
        out["ragged"] = {"slots": slots, "empty_slots": int((~live).sum()),
                         "kv_pages": [pages, page, 2 * hkv, D],
                         "pages_per_seq": pps,
                         "seconds": round(time.perf_counter() - t0, 1)}

    out["rel_err"] = errs
    if max(errs.values()) > KERNEL_TOL:
        raise RuntimeError(f"kernel disagrees with its reference: {out}")
    return out


def train_loop(spec):
    """The train function JaxTrainer runs in its worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu import train
    from ray_tpu.parallel.spmd import make_lm_train_step
    from ray_tpu.train.mesh.runtime import per_device_param_bytes

    facts = {"device": _device_facts(spec)}
    cfg = _model_cfg(spec, remat=True,
                     attention_impl=spec["train_attention"])
    mesh = train.get_mesh()
    facts["mesh"] = {a: int(s) for a, s in mesh.shape.items() if s > 1}
    if mesh.size != spec["chips"]:
        raise RuntimeError(f"mesh {mesh.shape} is not {spec['chips']} chips")
    init_fn, step_fn, place = make_lm_train_step(
        cfg, mesh, learning_rate=1e-4, param_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(spec["seed"]))

    # Parameters AND optimizer state: 1.0 = even shards, N = replicated.
    per_dev = per_device_param_bytes(state)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    facts["state_bytes_total"] = int(total)
    facts["state_bytes_per_device"] = per_dev
    facts["shard_balance"] = round(
        max(per_dev.values()) / (total / len(per_dev)), 4)
    if len(per_dev) != spec["chips"] or facts["shard_balance"] > 1.05:
        raise RuntimeError(f"state is not spread over the mesh: {facts}")

    B, S = spec["batch"], spec["seq"]
    rng = np.random.default_rng(spec["seed"])
    batch = place({"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32))})
    t0 = time.perf_counter()
    compiled = step_fn.lower(*state, batch).compile()
    facts["compile_s"] = round(time.perf_counter() - t0, 2)
    mem = compiled.memory_analysis()
    facts["memory_analysis"] = {
        "argument": mem.argument_size_in_bytes,
        "temp": mem.temp_size_in_bytes, "alias": mem.alias_size_in_bytes}
    facts["flash_kernels"] = compiled.as_text().count("tpu_custom_call")
    if not spec["rehearse"] and facts["flash_kernels"] < 3:
        raise RuntimeError("the compiled step lacks the flash fwd/dq/dkv "
                           f"kernels: {facts}")

    losses, step_ms = [], []
    for i in range(spec["warmup"] + spec["steps"]):
        t0 = time.perf_counter()
        *state, metrics = compiled(*state, batch)
        loss = float(metrics["loss"])          # host read ends the step
        ms = (time.perf_counter() - t0) * 1e3
        losses.append(loss)
        if i >= spec["warmup"]:
            step_ms.append(round(ms, 1))
        train.report({"step": i, "loss": loss, "step_ms": round(ms, 1)})
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"loss is not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not move down: {losses}")
    facts.update(batch=[B, S], losses=losses, step_ms=step_ms,
                 tokens_per_step=B * S, peak=_peak_bytes())
    train.report({"summary": facts})


def make_build_params(spec):
    """``build_params`` for build_llm_deployment: runs in the replica."""

    def build_params():
        from functools import partial

        import jax
        import jax.numpy as jnp
        from ray_tpu.llm import _model
        from ray_tpu.models import init_params

        facts = {"device": _device_facts(spec)}
        cfg = _model_cfg(spec, remat=False, attention_impl="reference")
        params = init_params(cfg, jax.random.key(spec["seed"]),
                             param_dtype=jnp.bfloat16)
        # The engine's decode program, at the engine's shapes.
        eo = spec["engine_options"]
        slots, page = eo["max_slots"], eo["page_size"]
        pps = math.ceil(eo["max_seq_len"] / page)
        sds = jax.ShapeDtypeStruct
        kv = tuple(sds((eo["num_pages"], page, 2 * cfg.kv_heads,
                        cfg.head_dim), cfg.dtype)
                   for _ in range(cfg.layers))
        t0 = time.perf_counter()
        decode = jax.jit(
            partial(_model.decode_step, cfg=cfg, page_size=page),
            donate_argnums=(1,)).lower(
                params, kv, sds((slots,), jnp.int32),
                sds((slots,), jnp.int32), sds((slots, pps), jnp.int32),
                sds((slots,), jnp.bool_)).compile()
        facts["decode_compile_s"] = round(time.perf_counter() - t0, 2)
        facts["ragged_kernels"] = decode.as_text().count("tpu_custom_call")
        if not spec["rehearse"] and facts["ragged_kernels"] < cfg.layers:
            raise RuntimeError("the decode program lacks the ragged paged "
                               f"kernel: {facts}")
        with open(os.path.join(spec["facts_dir"], "replica.json"), "w") as f:
            json.dump(facts, f)
        return params, cfg

    return build_params


def verify_served(spec, served):
    """Teacher-forced check of every served token against the plain
    forward of models.llama (reference attention, no paged cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import init_params
    from ray_tpu.models.llama import forward

    out = {"device": _device_facts(spec), "tolerance": LOGIT_MARGIN_TOL}
    cfg = _model_cfg(spec, remat=False, attention_impl="reference")
    params = init_params(cfg, jax.random.key(spec["seed"]),
                         param_dtype=jnp.bfloat16)
    width = max(len(p) + len(o) for p, o in served)
    toks = np.zeros((len(served), width), np.int32)
    scored = np.zeros((len(served), width - 1), bool)
    for i, (p, o) in enumerate(served):
        toks[i, :len(p) + len(o)] = p + o
        scored[i, len(p) - 1:len(p) + len(o) - 1] = True

    @jax.jit
    def score(toks):
        # Position t predicts token t+1; reduce on the device, the
        # logits are [requests, width, vocab] f32.
        logits = forward(params, toks, cfg)[:, :-1]
        served_logit = jnp.take_along_axis(
            logits, toks[:, 1:, None], axis=-1)[..., 0]
        return (logits.max(-1) - served_logit,
                logits.argmax(-1) == toks[:, 1:])

    margin, agree = (np.asarray(x)[scored] for x in score(jnp.asarray(toks)))
    if not np.all(np.isfinite(margin)):
        raise RuntimeError("reference logits are not finite")
    out.update(tokens=int(scored.sum()), argmax_agree=int(agree.sum()),
               worst_margin=round(float(margin.max()), 4))
    if margin.max() > LOGIT_MARGIN_TOL:
        raise RuntimeError(f"served tokens disagree with the plain "
                           f"forward: {out}")
    return out


# -- phases (driver side; no jax) -------------------------------------------

def run_train(spec, label, chips, mesh=None):
    from ray_tpu.train import (JaxTrainer, MeshConfig, RunConfig,
                               ScalingConfig)
    spec = dict(spec, chips=chips)
    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop, train_loop_config=spec,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, chips_per_worker=chips,
            mesh_config=MeshConfig.parse(mesh, devices_per_worker=chips)
            if mesh else None,
            # A rehearsal's "chips" are forced host devices.
            env_per_worker={"XLA_FLAGS": "--xla_force_host_platform_"
                            f"device_count={chips}"}
            if spec["rehearse"] else None),
        run_config=RunConfig(name=f"chip_smoke_{label}",
                             storage_path=spec["facts_dir"])).fit()
    if result.error is not None:
        raise result.error
    facts = result.metrics["summary"]
    say(label, phase_s=round(time.perf_counter() - t0, 1),
        mesh=facts["mesh"] or {"dp": 1}, batch=facts["batch"],
        compile_s=facts["compile_s"], step_ms=facts["step_ms"],
        flash_kernels=facts["flash_kernels"],
        shard_balance=facts["shard_balance"])
    say(label, losses=[round(x, 4) for x in facts["losses"]])
    say(label, memory_analysis=facts["memory_analysis"], peak=facts["peak"],
        state_bytes_per_device=facts["state_bytes_per_device"])
    say(label, device=facts["device"])
    return facts


def run_serve(spec):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    t0 = time.perf_counter()
    handle = serve.run(build_llm_deployment(
        make_build_params(spec), name="chip_smoke_llm", num_tpus=1,
        engine_options=spec["engine_options"]))
    with open(os.path.join(spec["facts_dir"], "replica.json")) as f:
        replica = json.load(f)
    say("serve", replica_ready_s=round(time.perf_counter() - t0, 1),
        decode_compile_s=replica["decode_compile_s"],
        ragged_kernels=replica["ragged_kernels"], device=replica["device"])

    import random
    rng = random.Random(spec["seed"])
    vocab = spec["model"]["vocab_size"]

    def ask(prompt_len, max_tokens):
        prompt = [rng.randrange(1, vocab) for _ in range(prompt_len)]
        body = {"prompt_tokens": prompt, "max_tokens": max_tokens,
                "temperature": 0.0}
        return prompt, body

    def check(prompt, body, reply):
        toks = reply.get("output_tokens")
        if reply.get("finish_reason") != "length" \
                or len(toks or ()) != body["max_tokens"] \
                or not all(0 <= x < vocab for x in toks):
            raise RuntimeError(f"bad reply for a {len(prompt)}-token "
                               f"prompt: {reply}")
        return prompt, toks

    served = []
    # One request alone per prefill bucket: these compile.
    for plen, ntok in spec["warm_requests"]:
        prompt, body = ask(plen, ntok)
        t1 = time.perf_counter()
        served.append(check(prompt, body, ray_tpu.get(
            handle.remote(body), timeout=600)))
        say("serve", alone=True, prompt_len=plen, tokens=ntok,
            seconds=round(time.perf_counter() - t1, 2))
    # Then several in flight at once: continuous batching, shared pages.
    asked = [ask(plen, ntok) for plen, ntok in spec["burst_requests"]]
    t1 = time.perf_counter()
    refs = [handle.remote(body) for _, body in asked]
    burst = [check(prompt, body, ray_tpu.get(ref, timeout=600))
             for (prompt, body), ref in zip(asked, refs)]
    say("serve", at_once=len(burst),
        prompt_lens=[len(p) for p, _ in burst],
        tokens=[len(o) for _, o in burst],
        burst_s=round(time.perf_counter() - t1, 2))
    served += burst
    serve.shutdown()          # the replica dies and gives the chip back
    return replica, served


def run_task(fn, spec, *args):
    """Run ``fn(spec, *args)`` in a worker that owns spec["chips"] chips."""
    import ray_tpu
    return ray_tpu.get(
        ray_tpu.remote(num_tpus=spec["chips"])(fn).remote(spec, *args),
        timeout=900)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: tiny widths, CPU allowed, "
                         "kernels interpreted; never prints ok")
    args = ap.parse_args()

    if args.rehearse:
        # XLA:CPU entries of the persistent cache are tied to the host's
        # CPU features and warn on reload; the chip run is what caches.
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import ray_tpu
    from ray_tpu import _native
    from ray_tpu._private import compile_cache
    say("setup", compile_cache=os.environ[compile_cache.ENV],
        native_store=_native.load_store_library() is not None,
        chips=args.chips, seed=args.seed, rehearse=args.rehearse)

    spec = dict(TOY if args.rehearse else REAL, rehearse=args.rehearse,
                seed=args.seed, chips=1, warmup=2, steps=3,
                # rows of 2048 tokens: 12 fit one chip; 8 split over four
                batch=8 if args.chips == 4 else 12)
    model = spec["model"]
    spec["seq"] = model["max_seq_len"]
    spec["flash_shape"] = [2, model["heads"], spec["seq"], model["head_dim"]]

    # A rehearsal has no chip to find: advertise the count it pretends.
    ray_tpu.init(**({"num_tpus": args.chips} if args.rehearse else {}))
    seen = []
    try:
        advertised = int(ray_tpu.cluster_resources().get("TPU", 0))
        if advertised < args.chips:
            raise RuntimeError(
                f"ray_tpu.init() found {advertised} TPU chips on this "
                f"host; this run needs {args.chips}")
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            spec["facts_dir"] = tmp
            if args.chips == 4:
                mesh = run_train(spec, "train_fsdp4", 4, mesh="fsdp4")
                one = run_train(spec, "train_one_device", 1)
                seen += [mesh["device"], one["device"]]
                worst = max(abs(a - b) / abs(b) for a, b in
                            zip(mesh["losses"], one["losses"]))
                say("compare", worst_rel_loss_diff=round(worst, 5),
                    tolerance=MESH_LOSS_RTOL,
                    speedup=round(min(one["step_ms"])
                                  / min(mesh["step_ms"]), 2))
                if worst > MESH_LOSS_RTOL:
                    raise RuntimeError(
                        "fsdp4 and one-device losses disagree")
            else:
                k = run_task(kernel_checks, spec)
                say("kernels", rel_err=k["rel_err"],
                    tolerance=k["tolerance"])
                say("kernels", flash=k["flash"], ragged=k["ragged"],
                    device=k["device"])
                tr = run_train(spec, "train", 1)
                replica, served = run_serve(spec)
                v = run_task(verify_served, spec, served)
                say("verify", **{x: v[x] for x in (
                    "tokens", "argmax_agree", "worst_margin", "tolerance")})
                seen += [k["device"], tr["device"], replica["device"],
                         v["device"]]
    finally:
        ray_tpu.shutdown()

    if "jax" in sys.modules:
        raise RuntimeError("the driver imported jax")
    kinds = {(d["platform"], d["kind"]) for d in seen}
    if len(kinds) != 1:
        raise RuntimeError(f"the phases saw different devices: {kinds}")
    platform, kind = kinds.pop()
    device = {"platform": platform, "kind": kind,
              "count": max(d["count"] for d in seen)}
    verdict = {"rehearsal": True} if args.rehearse else {"ok": True}
    print(json.dumps({**verdict, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
