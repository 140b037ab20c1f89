"""Serving-fleet tests: prefix index + cache, affinity routing,
SLO-driven replica autoscaling decisions, the FleetServer end-to-end
plane (exactness vs a single engine, full-hit replay, chaos replica
kill, drain-based scale-down), deadline-feasibility admission shedding,
the cross-host RemoteReplica handoff path on a 2-node cluster, the
`ray-tpu serve status` surface.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import InferenceEngine, SamplingParams
from ray_tpu.llm.fleet import (DEFAULT_BLOCK, FleetConfig, FleetRouter,
                               FleetServer, PrefixCache, RoutingConfig,
                               ServeAutoscalePolicy, ServeScaleConfig,
                               full_hash, prefix_chain, score_summary)
from ray_tpu.models import LlamaConfig
from ray_tpu.models.llama import init_params


CFG = LlamaConfig(vocab_size=128, hidden=32, layers=2, heads=4, kv_heads=2,
                  head_dim=8, mlp_dim=64, max_seq_len=128,
                  dtype=jnp.float32, attention_impl="reference", remat=False)

ENGINE_OPTS = {"max_slots": 2, "page_size": 8, "num_pages": 64,
               "prefill_buckets": (16, 64)}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def _build(params):
    return lambda: (params, CFG)


def _wait_for(fn, timeout=20.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {fn}")


# ---------------------------------------------------------------------------
# prefix index + cache
# ---------------------------------------------------------------------------


class TestPrefix:
    def test_chain_is_cumulative_per_block(self):
        toks = list(range(1, 40))
        chain = prefix_chain(toks, block=16)
        assert len(chain) == 2  # 39 tokens -> 2 full 16-token blocks
        # Shared prefix -> shared leading digests; divergence inside
        # block 2 changes every digest from there on (cumulative).
        other = list(toks)
        other[20] = 99
        chain2 = prefix_chain(other, block=16)
        assert chain2[0] == chain[0]
        assert chain2[1] != chain[1]

    def test_full_hash_is_length_delimited(self):
        # [1,2] followed by 3 must not collide with [1,2,3].
        assert full_hash([1, 2, 3]) != full_hash([1, 2])
        assert full_hash([1, 2, 3]) == full_hash([1, 2, 3])

    def test_cache_lookup_verifies_exact_tokens(self):
        cache = PrefixCache(capacity_bytes=1 << 20, block=4)

        class _H:
            def __init__(self, toks):
                self.prompt_tokens = list(toks)
                self.nbytes = 256
        toks = [5, 6, 7, 8, 9]
        cache.insert(_H(toks))
        assert cache.lookup(toks) is not None
        assert cache.lookup([5, 6, 7, 8]) is None
        assert cache.stats()["hits"] == 1

    def test_lru_eviction_respects_byte_budget(self):
        cache = PrefixCache(capacity_bytes=1000, block=4)

        class _H:
            def __init__(self, toks):
                self.prompt_tokens = list(toks)
                self.nbytes = 400
        a, b, c = [1] * 4, [2] * 4, [3] * 4
        cache.insert(_H(a))
        cache.insert(_H(b))
        cache.lookup(a)          # a is now MRU
        cache.insert(_H(c))      # evicts b (LRU), not a
        assert cache.lookup(a) is not None
        assert cache.lookup(b) is None
        assert cache.lookup(c) is not None
        assert cache.stats()["bytes"] <= 1000

    def test_score_summary_full_and_partial(self):
        cache = PrefixCache(capacity_bytes=1 << 20, block=4)

        class _H:
            def __init__(self, toks):
                self.prompt_tokens = list(toks)
                self.nbytes = 64
        toks = list(range(1, 13))          # 3 full blocks
        cache.insert(_H(toks))
        summ = cache.summary()
        chain = prefix_chain(toks, 4)
        assert score_summary(summ, chain, full_hash(toks)) == (True, 3)
        # Same first 2 blocks, divergent third.
        other = toks[:8] + [99, 98, 97, 96]
        full, shared = score_summary(
            summ, prefix_chain(other, 4), full_hash(other))
        assert (full, shared) == (False, 2)
        assert score_summary(None, chain, full_hash(toks)) == (False, 0)


# ---------------------------------------------------------------------------
# router units (dict fixtures, no engines)
# ---------------------------------------------------------------------------


def _view(name, ongoing=0, assigned=0, summary=None):
    return {"name": name, "load": {"ongoing": ongoing},
            "summary": summary, "assigned": assigned}


def _summary_for(tokens, block=4):
    cache = PrefixCache(capacity_bytes=1 << 20, block=block)

    class _H:
        def __init__(self, toks):
            self.prompt_tokens = list(toks)
            self.nbytes = 64
    cache.insert(_H(tokens))
    return cache.summary()


class TestRouter:
    def test_empty_views_returns_none(self):
        assert FleetRouter().route([], ["x"], "fh") is None

    def test_full_hit_wins_over_less_loaded_miss(self):
        toks = list(range(1, 13))
        views = [_view("hot", ongoing=3, summary=_summary_for(toks)),
                 _view("cold", ongoing=0)]
        d = FleetRouter().route(
            views, prefix_chain(toks, 4), full_hash(toks))
        assert (d.replica, d.outcome, d.rebalanced) == ("hot", "full",
                                                        False)

    def test_partial_prefix_steers_ties_by_load(self):
        toks = list(range(1, 13))
        overlap = toks[:8] + [99, 98, 97, 96]
        views = [_view("some", ongoing=1, summary=_summary_for(toks)),
                 _view("none", ongoing=0)]
        d = FleetRouter().route(
            views, prefix_chain(overlap, 4), full_hash(overlap))
        assert (d.replica, d.outcome) == ("some", "partial")
        assert d.shared_blocks == 2

    def test_miss_routes_least_loaded(self):
        views = [_view("a", ongoing=2, assigned=1),
                 _view("b", ongoing=1, assigned=0)]
        d = FleetRouter().route(views, ["z"], "fh")
        assert (d.replica, d.outcome) == ("b", "miss")

    def test_imbalance_watermark_overrides_affinity(self):
        toks = list(range(1, 13))
        views = [_view("hot", ongoing=10, summary=_summary_for(toks)),
                 _view("cold", ongoing=0)]
        cfg = RoutingConfig(imbalance_watermark=4)
        d = FleetRouter(cfg).route(
            views, prefix_chain(toks, 4), full_hash(toks))
        # Load wins; the outcome reports what the CHOSEN replica holds.
        assert d.replica == "cold"
        assert d.rebalanced is True
        assert d.outcome == "miss"

    def test_assigned_counts_toward_depth(self):
        # assigned-but-not-imported work must count or the router herds
        # a burst onto one replica before any import lands.
        views = [_view("a", ongoing=0, assigned=5),
                 _view("b", ongoing=1, assigned=0)]
        d = FleetRouter().route(views, ["z"], "fh")
        assert d.replica == "b"


# ---------------------------------------------------------------------------
# autoscale policy units (logical clock)
# ---------------------------------------------------------------------------


class TestAutoscalePolicy:
    def _cfg(self, **kw):
        base = dict(min_replicas=1, max_replicas=3, queue_high=2.0,
                    sustain_s=1.0, down_sustain_s=2.0, cooldown_s=5.0,
                    window_s=4.0, queue_low=0.25)
        base.update(kw)
        return ServeScaleConfig(**base)

    def test_sustained_queue_burn_scales_up(self):
        p = ServeAutoscalePolicy(self._cfg())
        t = 100.0
        decision = None
        for i in range(12):
            p.observe(queue_depth=10, shed_total=0, completed_total=i,
                      replicas=1, now=t)
            decision = p.decide(pending=0, now=t) or decision
            t += 0.25
        assert decision is not None
        assert decision.direction == "up"
        assert decision.reason == "queue_depth"
        assert decision.signals["queue_per_replica"] > 2.0

    def test_transient_spike_does_not_scale(self):
        p = ServeAutoscalePolicy(self._cfg(sustain_s=2.0))
        t = 100.0
        p.observe(10, 0, 0, 1, now=t)
        assert p.decide(now=t) is None          # burn just started
        t += 0.5
        p.observe(0, 0, 5, 1, now=t)            # spike gone
        # Idle resets the burn clock: later burn must re-sustain.
        t += 0.5
        p.observe(10, 0, 5, 1, now=t)
        assert p.decide(now=t) is None

    def test_cooldown_spaces_actions_and_forget_unsticks(self):
        p = ServeAutoscalePolicy(self._cfg(sustain_s=0.5, cooldown_s=10.0))
        t = 100.0
        d = None
        for _ in range(8):
            p.observe(10, 0, 0, 1, now=t)
            d = p.decide(now=t) or d
            t += 0.25
        assert d is not None and d.direction == "up"
        # Still burning, but cooldown blocks the next action.
        p.observe(10, 0, 0, 1, now=t)
        assert p.decide(now=t) is None
        # Caller failed to execute: forget_action lifts the stamp.
        p.forget_action()
        p.observe(10, 0, 0, 1, now=t)
        assert p.decide(now=t).direction == "up"

    def test_idle_fleet_scales_down_after_sustain(self):
        p = ServeAutoscalePolicy(self._cfg(cooldown_s=0.5))
        t = 100.0
        d = None
        for _ in range(12):                      # 3s of idle signals
            p.observe(0, 0, 100, 2, now=t)
            d = p.decide(now=t) or d
            t += 0.25
        assert d is not None and d.direction == "down"

    def test_never_below_min_or_above_max(self):
        p = ServeAutoscalePolicy(self._cfg(max_replicas=2, cooldown_s=0.0,
                                           sustain_s=0.0,
                                           down_sustain_s=0.0))
        t = 100.0
        for _ in range(8):
            p.observe(10, 0, 0, 2, now=t)       # burning at max
            assert p.decide(now=t) is None
            t += 0.25
        t += 10.0                               # age out the hot window
        for _ in range(8):
            p.observe(0, 0, 10, 1, now=t)       # idle at min
            assert p.decide(now=t) is None
            t += 0.25

    def test_pending_action_blocks_further_scaling(self):
        p = ServeAutoscalePolicy(self._cfg(sustain_s=0.0, cooldown_s=0.0))
        t = 100.0
        for _ in range(6):
            p.observe(10, 0, 0, 1, now=t)
            t += 0.25
        assert p.decide(pending=1, now=t) is None
        assert p.decide(pending=0, now=t) is not None

    def test_itl_axis_burns_when_enabled(self):
        p = ServeAutoscalePolicy(self._cfg(itl_p99_high_ms=50.0,
                                           sustain_s=0.0, cooldown_s=0.0))
        t = 100.0
        for _ in range(6):
            p.observe(0, 0, 10, 1, itl_samples=[0.2] * 20, now=t)
            t += 0.25
        d = p.decide(now=t)
        assert d is not None and d.reason == "itl_p99"


# ---------------------------------------------------------------------------
# deadline-feasibility admission (satellite: shed at submit, not after
# the queue wait is already lost)
# ---------------------------------------------------------------------------


class TestDeadlineFeasibility:
    def test_infeasible_queue_wait_sheds_at_admission(self):
        from ray_tpu.llm.disagg import AdmissionConfig, AdmissionController
        from ray_tpu.llm.disagg.router import RequestClass
        ctl = AdmissionController(AdmissionConfig(classes={
            "default": RequestClass(max_queue_depth=1000,
                                    queue_deadline_s=0.5)}))
        load = {"kv_occupancy": 0.0, "waiting": 0}
        assert ctl.try_admit("default", 10, load) is None
        # Dispatcher observes multi-second queue waits: new arrivals
        # cannot possibly dispatch inside their 0.5s deadline.
        for _ in range(4):
            ctl.note_queue_wait(3.0)
        assert ctl.try_admit("default", 10, load) == "deadline_infeasible"

    def test_stale_ewma_never_sheds_an_empty_queue(self):
        from ray_tpu.llm.disagg import AdmissionConfig, AdmissionController
        from ray_tpu.llm.disagg.router import RequestClass
        ctl = AdmissionController(AdmissionConfig(classes={
            "default": RequestClass(max_queue_depth=1000,
                                    queue_deadline_s=0.5)}))
        load = {"kv_occupancy": 0.0, "waiting": 0}
        ctl.try_admit("default", 10, load)      # one queued
        for _ in range(4):
            ctl.note_queue_wait(3.0)
        ctl.note_dequeued("default")            # queue now empty
        # The burst is over: a fresh arrival sees an empty queue and
        # must be admitted regardless of the stale wait estimate.
        assert ctl.try_admit("default", 10, load) is None


# ---------------------------------------------------------------------------
# fleet end-to-end (single process, local replicas)
# ---------------------------------------------------------------------------


def _fleet(params, n=2, **cfg_kw):
    cfg_kw.setdefault("engine_options", dict(ENGINE_OPTS))
    cfg_kw.setdefault("cache_capacity_bytes", 1 << 20)
    return FleetServer(_build(params), name="t",
                       config=FleetConfig(num_replicas=n, **cfg_kw),
                       record_token_times=True)


class TestFleetServer:
    def test_matches_single_engine_greedy(self, params):
        prompts = [np.random.default_rng(i).integers(
            1, CFG.vocab_size, 12).tolist() for i in range(6)]
        eng = InferenceEngine(params, CFG, **ENGINE_OPTS)
        # One prompt per call (see TestCrossHostFleet): gold attribution
        # must not depend on multi-slot finish order.
        gold = [eng.generate([p], SamplingParams(max_tokens=6))[0]
                for p in prompts]
        srv = _fleet(params, n=2)
        try:
            pubs = [srv.submit({"prompt_tokens": p, "max_tokens": 6})
                    for p in prompts]
            outs = [srv.result(p, timeout_s=120) for p in pubs]
        finally:
            srv.close()
        for res, g in zip(outs, gold):
            assert "error" not in res, res
            assert res["output_tokens"] == g
        # Both replicas took work (least-loaded miss routing spreads).
        assert {r["replica"] for r in outs if "replica" in r}

    def test_finish_that_outruns_its_registration(self, params):
        """A request may finish on its replica before the dispatcher is
        back from ``import_prefill`` (a RemoteReplica's poller does that
        to a short request on a warm engine): the finish is kept for the
        registration, where it used to be dropped and the caller waited
        out its timeout."""
        from ray_tpu.llm.fleet.replica import DecodeReplica

        class FinishFirst(DecodeReplica):
            delivered = set()

            def import_prefill(self, handoff, retain=True):
                rid = super().import_prefill(handoff, retain=retain)
                if rid is not None:
                    _wait_for(lambda: rid in self.delivered)
                return rid

        def factory(name, on_finish):
            def note(rep, req):
                on_finish(rep, req)
                rep.delivered.add(req.request_id)
            return FinishFirst(_build(params), name=name,
                               engine_options=dict(ENGINE_OPTS),
                               on_finish=note)

        srv = FleetServer(
            _build(params), name="early",
            config=FleetConfig(num_replicas=1,
                               engine_options=dict(ENGINE_OPTS)),
            replica_factory=factory)
        try:
            res = srv({"prompt_tokens": list(range(1, 13)),
                       "max_tokens": 5, "timeout_s": 20})
        finally:
            srv.close()
        assert "error" not in res, res
        assert len(res["output_tokens"]) == 5

    def test_full_hit_replays_identical_tokens(self, params):
        srv = _fleet(params, n=1)
        try:
            prompt = list(range(1, 14))
            r1 = srv({"prompt_tokens": prompt, "max_tokens": 5,
                      "timeout_s": 60})
            r2 = srv({"prompt_tokens": prompt, "max_tokens": 5,
                      "timeout_s": 60})
            assert r1["prefix_outcome"] in ("miss", "partial")
            assert r2["prefix_outcome"] == "full"
            assert r2["output_tokens"] == r1["output_tokens"]
            # Replay skipped prefill: TTFT is registration, not compute.
            assert r2["ttft_s"] < r1["ttft_s"]
            st = srv.status()
            assert st["prefix"]["full"] >= 1
        finally:
            srv.close()

    def test_sampled_requests_never_replay(self, params):
        srv = _fleet(params, n=1)
        try:
            prompt = list(range(2, 15))
            srv({"prompt_tokens": prompt, "max_tokens": 4,
                 "timeout_s": 60})
            r2 = srv({"prompt_tokens": prompt, "max_tokens": 4,
                      "temperature": 0.8, "timeout_s": 60})
            # A sampled request must not get the greedy cached stream.
            assert r2["prefix_outcome"] != "full"
        finally:
            srv.close()

    def test_status_and_load_surface(self, params):
        srv = _fleet(params, n=2)
        try:
            srv({"prompt_tokens": [3, 4, 5], "max_tokens": 3,
                 "timeout_s": 60})
            st = srv.status()
            assert st["name"] == "t"
            assert len(st["replicas"]) == 2
            assert st["target_replicas"] == 2
            assert st["completed"] == 1
            for r in st["replicas"]:
                assert {"name", "state", "ongoing", "cache",
                        "assigned"} <= set(r)
            load = srv.load()
            assert load["mode"] == "fleet" and load["replicas"] == 2
        finally:
            srv.close()


class TestFleetChaos:
    def test_replica_kill_sheds_retriably_and_backfills(self, params):
        srv = _fleet(params, n=2)
        try:
            prompts = [np.random.default_rng(100 + i).integers(
                1, CFG.vocab_size, 12).tolist() for i in range(8)]
            # Long decodes (100 steps) so the victim's in-flight cannot
            # drain between being spotted and the kill landing.
            pubs = [srv.submit({"prompt_tokens": p, "max_tokens": 100,
                                "timeout_s": 120}) for p in prompts]
            # Deterministic victim: a replica with a MAPPED in-flight
            # request.  Spotting via status() races — load_stats blocks
            # on the engine lock behind back-to-back decode steps, so
            # the observation can land ~100 steps late and the whole
            # batch may finish before the kill does.  _rid_map is
            # server-side state (no engine lock), so this peek lands
            # within the first few decode steps, ~95+ steps before the
            # victim's in-flight could drain.
            def victim():
                with srv._lock:
                    for name, _rid in list(srv._rid_map):
                        if name in srv._replicas:
                            return name
                return None
            name = _wait_for(victim)
            assert srv.kill_replica(name)
            results = [srv.result(p, timeout_s=120) for p in pubs]
            shed = [r for r in results if r.get("finish_reason") == "shed"]
            done = [r for r in results if r.get("finish_reason") != "shed"]
            # The killed replica's in-flight shed RETRIABLY (no hang,
            # no timeout), survivors finish normally.  Requests still
            # QUEUED when capacity halved may shed on their class
            # deadline instead — also retriable, also correct.
            assert any(r.get("reason") == "replica_lost"
                       for r in shed), results
            assert all(r.get("reason") in ("replica_lost", "deadline")
                       for r in shed)
            assert all("error" not in r for r in done)
            assert done, results
            # Manager backfills to target: 2 accepting replicas again.
            _wait_for(lambda: len(srv.status()["replicas"]) == 2
                      and not srv.status()["draining"])
            # And the backfilled fleet still serves.
            r = srv({"prompt_tokens": [9, 8, 7], "max_tokens": 3,
                     "timeout_s": 60})
            assert "error" not in r
        finally:
            srv.close()

    def test_scale_down_drains_without_killing_work(self, params):
        srv = _fleet(params, n=2)
        try:
            pubs = [srv.submit({"prompt_tokens": [i + 1, i + 2, i + 3],
                                "max_tokens": 30, "timeout_s": 120})
                    for i in range(4)]
            drained = srv.scale_down()
            assert drained is not None
            results = [srv.result(p, timeout_s=120) for p in pubs]
            # Drain never sheds running work.
            assert all(r.get("finish_reason") != "shed" for r in results)
            assert all("error" not in r for r in results)
            _wait_for(lambda: len(srv.status()["replicas"]) == 1
                      and not srv.status()["draining"])
        finally:
            srv.close()


class TestFleetAutoscaleLoop:
    def test_manager_executes_up_and_down(self, params):
        srv = _fleet(
            params, n=1,
            manager_interval_s=0.05,
            autoscale=ServeScaleConfig(
                min_replicas=1, max_replicas=2, queue_high=0.5,
                sustain_s=0.2, down_sustain_s=0.4, cooldown_s=0.3,
                window_s=1.0))
        try:
            prompts = [np.random.default_rng(7 + i).integers(
                1, CFG.vocab_size, 12).tolist() for i in range(16)]
            pubs = [srv.submit({"prompt_tokens": p, "max_tokens": 30,
                                "timeout_s": 300}) for p in prompts]
            _wait_for(lambda: srv.status()["scales"]["up"] >= 1,
                      timeout=30.0)
            results = [srv.result(p, timeout_s=300) for p in pubs]
            assert all("error" not in r for r in results)
            assert all(r.get("finish_reason") != "shed" for r in results)
            # Load gone: the manager drains the extra replica away.
            _wait_for(lambda: srv.status()["scales"]["down"] >= 1
                      and len(srv.status()["replicas"]) == 1,
                      timeout=30.0)
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# cross-host prefill handoff (2-node cluster, RemoteReplica)
# ---------------------------------------------------------------------------


class TestCrossHostFleet:
    def test_remote_replica_decodes_and_records_pull(self, params):
        from ray_tpu._private.config import Config
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.llm.fleet import RemoteReplica
        from ray_tpu.util import state

        prompts = [np.random.default_rng(50 + i).integers(
            1, CFG.vocab_size, 12).tolist() for i in range(3)]

        # The toy model's KV handoff (~4 KiB) would ride inline in
        # control messages at the default 100 KiB threshold and never
        # touch the store.  Drop the threshold (env is inherited by the
        # cluster's node processes) so handoffs take the p2p pull path
        # this test is about.
        old = os.environ.get("RAY_TPU_MAX_INLINE_OBJECT_SIZE")
        os.environ["RAY_TPU_MAX_INLINE_OBJECT_SIZE"] = "1024"
        Config.initialize()
        try:
            self._run_cross_host(params, prompts)
        finally:
            if old is None:
                os.environ.pop("RAY_TPU_MAX_INLINE_OBJECT_SIZE", None)
            else:
                os.environ["RAY_TPU_MAX_INLINE_OBJECT_SIZE"] = old
            Config.initialize()

    def _run_cross_host(self, params, prompts):
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.llm.fleet import RemoteReplica
        from ray_tpu.util import state

        with Cluster(head_num_cpus=0) as cluster:
            cluster.add_node(num_cpus=2)
            build = _build(params)

            def factory(name, on_finish):
                # num_cpus=2 forces placement on the worker NODE (the
                # head has zero CPUs): every handoff crosses hosts.
                return RemoteReplica(
                    build, name=name,
                    engine_options=dict(ENGINE_OPTS),
                    cache_capacity_bytes=1 << 20,
                    record_token_times=True, on_finish=on_finish,
                    num_cpus=2, poll_interval_s=0.01)

            srv = FleetServer(
                build, name="xhost",
                config=FleetConfig(num_replicas=1,
                                   engine_options=dict(ENGINE_OPTS)),
                record_token_times=True, replica_factory=factory)
            try:
                pubs = [srv.submit({"prompt_tokens": p, "max_tokens": 5,
                                    "timeout_s": 300}) for p in prompts]
                outs = [srv.result(p, timeout_s=300) for p in pubs]
                for res in outs:
                    assert "error" not in res, res
                    assert len(res["output_tokens"]) == 5
                # Replay across hosts: same prompt, full prefix hit on
                # the remote replica's cache, token-identical to the
                # ORIGINAL remote decode.  (No cross-process float
                # equality: per-process XLA cache state can flip an
                # argmax near-tie on this toy model, so a driver-side
                # gold engine is not a stable reference here — the
                # same-process exactness contract lives in
                # TestFleetServer.)
                r2 = srv({"prompt_tokens": prompts[0], "max_tokens": 5,
                          "timeout_s": 300})
                assert r2["prefix_outcome"] == "full"
                assert r2["output_tokens"] == outs[0]["output_tokens"]
            finally:
                srv.close()

            # The KV handoffs rode the object store's p2p pull path:
            # the transfer series recorded cross-node bytes.
            rt = cluster.runtime
            rt.metricsview.refresh(force=True)
            q = state.metrics_query(
                "ray_tpu_store_transfer_bytes_total",
                window_s=300.0, agg="last", tags={"direction": "pull"})
            assert q["value"] and q["value"] > 0


# ---------------------------------------------------------------------------
# CLI / REST surface
# ---------------------------------------------------------------------------


class TestServeStatusSurface:
    def test_cli_serve_status_reads_published_kv(self, ray_start_isolated):
        from click.testing import CliRunner

        from ray_tpu._private.api import _control
        from ray_tpu.job_submission.manager import JobManager
        from ray_tpu.job_submission.server import JobServer
        from ray_tpu.scripts.cli import cli

        snap = {
            "name": "demo", "target_replicas": 2, "router_queue": 1,
            "completed": 41, "shed": 2,
            "prefix": {"full": 30, "partial": 4, "miss": 7},
            "rebalances": 3, "scales": {"up": 1, "down": 1},
            "draining": [],
            "replicas": [{
                "name": "demo-r0", "state": "active", "ongoing": 2,
                "waiting": 0, "assigned": 1, "kv_occupancy": 0.25,
                "cache": {"entries": 5, "bytes": 2048, "hits": 30,
                          "misses": 11, "hit_rate": 30 / 41}}],
            "autoscale": {
                "signals": {"queue_per_replica": 0.5, "shed_rate": 0.0,
                            "itl_p99_ms": 12.0},
                "burning_for_s": None, "idle_for_s": 1.0,
                "cooldown_remaining_s": 0.0,
                "min_replicas": 1, "max_replicas": 4},
        }
        _control("kv_put", "serve:fleet:demo",
                 json.dumps(snap).encode())
        server = JobServer(JobManager(), port=0)
        try:
            client_out = __import__(
                "ray_tpu.job_submission.client",
                fromlist=["JobSubmissionClient"]).JobSubmissionClient(
                server.address).serve_fleet()
            assert client_out["fleets"][0]["name"] == "demo"
            r = CliRunner().invoke(
                cli, ["serve", "status", "--address", server.address])
            assert r.exit_code == 0, r.output
            assert "fleet demo: 1 replica(s) (target 2)" in r.output
            assert "full=30" in r.output
            assert "demo-r0" in r.output and "kv=25%" in r.output
            assert "autoscale:" in r.output
        finally:
            server.stop()
            _control("kv_del", "serve:fleet:demo")

    def test_fleet_server_publishes_to_kv(self, ray_start_isolated,
                                          params):
        from ray_tpu._private.api import _control
        srv = _fleet(params, n=1)
        try:
            srv({"prompt_tokens": [4, 5, 6], "max_tokens": 2,
                 "timeout_s": 60})

            def published():
                raw = _control("kv_get", "serve:fleet:t")
                return json.loads(raw.decode()) if raw else None
            snap = _wait_for(published)
            assert snap["name"] == "t"
            assert len(snap["replicas"]) == 1
        finally:
            srv.close()
        # close() removes the published key (no stale fleets in the CLI).
        assert _control("kv_get", "serve:fleet:t") is None
