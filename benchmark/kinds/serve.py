"""Runner for serving cells: ``build_llm_deployment(num_tpus=1)`` and the
serve handle, loaded from this process.

The driver process never imports jax.  The replica holds the chip while
requests run; after the window it is shut down and a task with a one-chip
grant scores what was served against the plain reference.
"""

from __future__ import annotations

import tempfile
from typing import Any, Dict, List

from benchmark import agent, common, loadgen, traffic


def make_build_params(spec: Dict[str, Any]):
    """``build_params`` for the deployment: runs inside the replica."""

    def build_params():
        from benchmark import weights
        device = common.device_facts(1, spec["rehearse"])
        s = spec["sizes"]
        cfg = common.llama_config(
            s, spec["engine_options"]["max_seq_len"], remat=False,
            attention_impl="reference")
        params = weights.make(s, spec["seed"])
        agent.Agent(spec["run_dir"], device).start()
        return params, cfg

    return build_params


def verify_served(spec: Dict[str, Any], served: List) -> Dict[str, Any]:
    """Runs in a task that owns the chip, after the replica is gone: every
    served token of the sample is scored by the reference's full forward
    (teacher forced on what was served)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, weights
    device = common.device_facts(1, spec["rehearse"])
    s, width = spec["sizes"], spec["engine_options"]["max_seq_len"]
    seqs = np.zeros((len(served), width), np.int32)
    scored = np.zeros((len(served), width - 1), bool)
    for i, (prompt, answer) in enumerate(served):
        seqs[i, :len(prompt) + len(answer)] = prompt + answer
        scored[i, len(prompt) - 1:len(prompt) + len(answer) - 1] = True
    w = weights.make(s, spec["seed"])
    margin, _ = jax.jit(
        lambda w, q, m: reference.served_margins(w, q, m, s))(
            w, jnp.asarray(seqs), jnp.asarray(scored))
    margin = np.asarray(margin)[scored]
    if not np.all(np.isfinite(margin)):
        raise RuntimeError("the reference's logits are not finite")
    return {"device": device, "tokens": int(scored.sum()),
            "margin_mean": float(margin.mean()),
            "margin_max": float(margin.max()),
            "agree_share": float((margin == 0).mean())}


def _warm_up(handle, get, spec, mix) -> float:
    """One request alone in each prefill bucket the mix uses; the first
    also compiles the decode program.  Returns the seconds they took."""
    import numpy as np
    rng = np.random.default_rng(spec["seed"] + 1)
    t0 = common.now()
    for bucket in traffic.buckets_used(mix):
        body = {"prompt_tokens": rng.integers(
                    1, spec["sizes"]["V"], bucket).tolist(),
                "max_tokens": 3, "temperature": 0.0}
        t1 = common.now()
        reply = get(handle.remote(body), timeout=900)
        if len(reply.get("output_tokens") or ()) != 3:
            raise RuntimeError(f"warm-up failed in bucket {bucket}: {reply}")
        common.say("serve", warmed_bucket=bucket,
                   seconds=round(common.now() - t1, 2))
    return common.now() - t0


def _cache_tokens(recs: List[loadgen.Served], lo: float, hi: float) -> float:
    """Time-average over [lo, hi] of the tokens held in the cache, from the
    client's own records: each request in flight holds its prompt and the
    tokens it has received."""
    total = 0.0
    for r in recs:
        times = list(r.token_times)
        if not times:
            continue
        n0 = len(r.offered.prompt)
        edges = times + [r.done or hi]
        for i, (a, b) in enumerate(zip(edges, edges[1:])):
            total += (n0 + i + 1) * max(0.0, min(b, hi) - max(a, lo))
    return total / (hi - lo)


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    config, mix = cell["config"], cell["traffic"]
    seconds, lead = cell["seconds"], mix["lead_s"]
    engine_options = {**config["serve"]["engine_options"],
                      **mix["engine_options"]}
    engine_options["prefill_buckets"] = tuple(
        engine_options["prefill_buckets"])
    spec = {"rehearse": cell["rehearse"], "seed": cell["seed"],
            "sizes": cell["sizes"], "engine_options": engine_options}
    offered = traffic.build(mix, cell["seed"], seconds, cell["sizes"]["V"])
    common.say("serve", loop=mix["loop"], offered=len(offered),
               sample=traffic.offered_totals(offered))

    ray_tpu.init(**({"num_tpus": 1} if cell["rehearse"] else {}))
    get = ray_tpu.get
    facts: Dict[str, Any] = {}
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if have < 1:
            raise RuntimeError("this host has no TPU chip")
        with tempfile.TemporaryDirectory(prefix="bench_serve_") as run_dir:
            spec["run_dir"] = run_dir
            handle = serve.run(build_llm_deployment(
                make_build_params(spec), name="benchmark_llm", num_tpus=1,
                max_ongoing_requests=config["serve"]["max_ongoing_requests"],
                engine_options=engine_options))
            facts["compile_s"] = _warm_up(handle, get, spec, mix)

            window = {}

            def on_start(t0: float) -> None:
                window["lo"], window["hi"] = t0 + lead, t0 + lead + seconds
                agent.ask(run_dir, 0, {
                    "op": "begin",
                    "trace_at": window["lo"] + seconds / 3
                    if cell["trace"] else None,
                    "trace_s": mix["trace_s"]})

            if mix["loop"] == "open":
                recs = loadgen.open_loop(
                    handle, get, offered, mix["client_threads"], on_start)
            else:
                recs = loadgen.closed_loop(
                    handle, get, offered, mix["clients"], lead, seconds,
                    on_start)
            replica = agent.ask(run_dir, 1, {"op": "end"})
            serve.shutdown()          # the replica gives the chip back
        lo, hi = window["lo"], window["hi"]

        if mix["loop"] == "open":
            sample = [r for r in recs if r.offered.measured]
        else:
            sample = [r for r in recs if r.done and lo <= r.done <= hi]
        good = [r for r in sample if r.ok]
        for r in [r for r in sample if not r.ok][:3]:
            common.say("serve", failed_request=dict(
                asked=r.offered.max_tokens, got=len(r.tokens),
                finish_reason=r.finish_reason, error=r.error))
        delivered = sum(1 for r in recs for t in list(r.token_times)
                        if lo <= t <= hi)
        served = [(r.offered.prompt, r.tokens)
                  for r in good[:mix["verify_requests"]]]
        check = ray_tpu.get(
            ray_tpu.remote(num_tpus=1)(verify_served).remote(spec, served),
            timeout=900)
    finally:
        ray_tpu.shutdown()

    if check["device"] != replica["device"]:
        raise RuntimeError("the replica and the check saw different devices")
    facts.update(
        device=replica["device"], window_start=lo, window=(lo, hi),
        memory_peak_bytes=replica["memory_peak_bytes"],
        attempted=len(sample), failed=len(sample) - len(good),
        delivered_tokens=delivered, requests=good, all_requests=recs,
        loop=mix["loop"], counters=replica["counters"],
        kv_occupancy_peak=replica["kv_occupancy_peak"],
        trace=replica.get("trace"), engine_options=engine_options,
        compared={"served_margin_mean": check["margin_mean"]})
    if "trace_wall" in replica:
        a, b = replica["trace_wall"]
        facts["trace_cache_tokens"] = _cache_tokens(recs, a, b)
    common.say("serve", requests=len(sample), ok=len(good),
               delivered_tokens=delivered,
               compile_cache=replica["compile_cache"])
    ttft, tpot = loadgen.ttft_ms(good), loadgen.tpot_ms(good)
    limits = mix.get("limits")
    if ttft and tpot:
        # The medians and the share that met both limits are recorded, not
        # judged: they go on this line, not into the result.
        common.say("serve", latency=dict(
            requests=len(good),
            ttft_p50_ms=round(traffic.percentile(ttft, 50), 3),
            tpot_p50_ms=round(traffic.percentile(tpot, 50), 3),
            met_both_share=None if not limits else round(sum(
                1 for r in sample if r.ok and len(r.token_times) > 1
                and loadgen.ttft_ms([r])[0] <= limits["ttft_ms"]
                and loadgen.tpot_ms([r])[0] <= limits["tpot_ms"])
                / len(sample), 4), limits=limits))
    common.say("serve", check_tokens=check["tokens"],
               agree_share=round(check["agree_share"], 4),
               margin_max=check["margin_max"])
    return facts
