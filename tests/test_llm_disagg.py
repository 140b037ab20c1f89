"""Disaggregated serving tests: chunked prefill exactness, KV-pressure
preemption/re-admission, prefill->decode KV handoff (direct + through the
shm object store), SLO-aware admission shedding, and a saturation smoke
under the open-loop generator of ``llm/disagg/loadgen.py``.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import InferenceEngine, LLMServer, SamplingParams
from ray_tpu.models import LlamaConfig
from ray_tpu.models.llama import forward, init_params

CFG = LlamaConfig(vocab_size=128, hidden=32, layers=2, heads=4, kv_heads=2,
                  head_dim=8, mlp_dim=64, max_seq_len=128,
                  dtype=jnp.float32, attention_impl="reference", remat=False)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


#: the gold's forward, one program a length of the prefix (eagerly every
#: operation of the model is a program of its own for every length)
_forward = jax.jit(lambda params, toks: forward(params, toks, CFG))

_GOLD: dict = {}


def naive_greedy(params, prompt, max_new):
    """Gold stream via full re-forward per token; memoized — the
    KV-pressure tests replay the same prompts across three drive
    modes and 80 forwards per replay would dominate tier-1 time."""
    key = (tuple(prompt), max_new)
    if key in _GOLD:
        return list(_GOLD[key])
    toks = list(prompt)
    out = []
    for _ in range(max_new):
        logits = _forward(params, jnp.asarray([toks]))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    _GOLD[key] = list(out)
    return out


class TestChunkedPrefill:
    def test_matches_monolithic_greedy(self, params):
        """Chunked prefill (8-token chunks across decode steps) produces
        exactly the monolithic-prefill greedy stream — including a
        prompt LONGER than every bucket, which only the chunked program
        can cover."""
        rng = np.random.default_rng(7)
        long_prompt = rng.integers(1, CFG.vocab_size, 37).tolist()
        short = [3, 17, 92, 5]
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16,),
                              prefill_chunk=8)
        outs = eng.generate([long_prompt, short],
                            SamplingParams(max_tokens=6))
        assert outs[0] == naive_greedy(params, long_prompt, 6)
        assert outs[1] == naive_greedy(params, short, 6)

    def test_interleaves_with_decode(self, params):
        """While a long prompt chunk-prefills, an already-running
        request keeps decoding: its tokens advance between prefill
        chunks instead of stalling until the prompt is done."""
        rng = np.random.default_rng(11)
        long_prompt = rng.integers(1, CFG.vocab_size, 48).tolist()
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16,),
                              prefill_chunk=8)
        sp = SamplingParams(max_tokens=30)
        short_id = eng.add_request([5, 6, 7], sp)
        eng.step()          # admit + first decode of the short request
        eng.add_request(long_prompt, SamplingParams(max_tokens=4))
        short_req = eng.running[short_id]
        eng.step()            # admits the long prompt into chunked state
        assert eng._prefilling
        progress = [len(short_req.output_tokens)]
        while eng._prefilling:
            eng.step()
            progress.append(len(short_req.output_tokens))
        # The short request decoded DURING the chunked prefill.
        assert progress[-1] > progress[0]
        while eng.has_work():
            eng.step()
        assert short_req.output_tokens == naive_greedy(
            params, [5, 6, 7], 30)


class TestKVPressure:
    """PagePool exhaustion mid-decode: lazy page allocation preempts the
    youngest request, re-queues it at the FRONT, and recompute
    re-admission reproduces the exact greedy stream."""

    def _run(self, params, num_pages=14, max_tokens=20):
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, CFG.vocab_size, 6).tolist()
                   for _ in range(4)]
        want = [naive_greedy(params, p, max_tokens) for p in prompts]
        eng = InferenceEngine(params, CFG, max_slots=4, page_size=4,
                              num_pages=num_pages, prefill_buckets=(16,))
        preempts = []
        orig = type(eng)._preempt

        def counting(self, slot):
            preempts.append(self.slot_req[slot].request_id)
            return orig(self, slot)
        eng._preempt = counting.__get__(eng)
        free0 = eng.pool.num_free
        ids = [eng.add_request(p, SamplingParams(max_tokens=max_tokens))
               for p in prompts]
        done = {}
        guard = 0
        while eng.has_work():
            for r in eng.step():
                done[r.request_id] = r.output_tokens
            guard += 1
            assert guard < 8000
        got = [done[i] for i in ids]
        assert got == want
        assert eng.pool.num_free == free0   # no page leaks
        return preempts

    def test_preemption_step_path(self, params):
        preempts = self._run(params)
        assert preempts, "pool was sized to force preemption"

    def test_readmission_fairness(self, params):
        """Preempted requests re-queue at the FRONT: re-admission keeps
        arrival order ahead of never-admitted requests."""
        rng = np.random.default_rng(5)
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=4,
                              num_pages=10, prefill_buckets=(16,))
        sp = SamplingParams(max_tokens=16)
        prompts = [rng.integers(1, CFG.vocab_size, 5).tolist()
                   for _ in range(4)]
        ids = [eng.add_request(p, sp) for p in prompts]
        done = {}
        order = []
        guard = 0
        while eng.has_work():
            for r in eng.step():
                done[r.request_id] = r.output_tokens
                order.append(r.request_id)
            guard += 1
            assert guard < 8000
        # All exact despite churn, and the first arrival finishes before
        # the last (FIFO preserved through preempt/re-admit cycles).
        for rid, p in zip(ids, prompts):
            assert done[rid] == naive_greedy(params, p, 16)
        assert set(order) == set(ids)
        assert order.index(ids[0]) < order.index(ids[3])


class TestKVHandoff:
    def test_import_prefill_continues_exact(self, params):
        """A decode engine importing a PrefillWorker's handoff produces
        the same greedy stream as local end-to-end generation."""
        from ray_tpu.llm.disagg import PrefillWorker

        prompt = [3, 17, 92, 5, 41]
        pw = PrefillWorker(params, CFG, prefill_buckets=(16,), page_size=8)
        h = pw.prefill(prompt, SamplingParams(max_tokens=8))
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16,))
        rid = eng.import_prefill(h)
        assert rid is not None
        done = {}
        while eng.has_work():
            for r in eng.step():
                done[r.request_id] = r.output_tokens
        assert done[rid] == naive_greedy(params, prompt, 8)

    def test_handoff_through_object_store(self, params):
        """Same-host handoff through the shm object store: export seals
        a page blob, import maps it back (zero-copy views), and the
        decode stream is exact; the staged blob is deleted after
        import."""
        from ray_tpu._private.object_store import SharedMemoryStore
        from ray_tpu._private.ids import ObjectID
        from ray_tpu.llm.disagg import (PrefillWorker, export_handoff,
                                        import_handoff)

        prompt = [7, 9, 23, 6]
        pw = PrefillWorker(params, CFG, prefill_buckets=(16,), page_size=8)
        h = pw.prefill(prompt, SamplingParams(max_tokens=6))
        store = SharedMemoryStore(capacity_bytes=32 << 20)
        try:
            oid = ObjectID.from_random()
            desc = export_handoff(store, oid, h)
            assert desc is not None
            h2, keepalive = import_handoff(desc)
            assert h2.prompt_tokens == h.prompt_tokens
            assert h2.first_token == h.first_token
            np.testing.assert_array_equal(np.asarray(h2.ks),
                                          np.asarray(h.ks))
            eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                                  num_pages=64, prefill_buckets=(16,))
            rid = eng.import_prefill(h2)
            del keepalive
            store.delete(oid)
            assert store.stats()["num_objects"] == 0
            done = {}
            while eng.has_work():
                for r in eng.step():
                    done[r.request_id] = r.output_tokens
            assert done[rid] == naive_greedy(params, prompt, 6)
        finally:
            store.shutdown()

    def test_decode_full_returns_none(self, params):
        """import_prefill under decode-side pressure returns None
        (caller backpressure) instead of silently dropping."""
        from ray_tpu.llm.disagg import PrefillWorker

        pw = PrefillWorker(params, CFG, prefill_buckets=(16,), page_size=8)
        eng = InferenceEngine(params, CFG, max_slots=1, page_size=8,
                              num_pages=64, prefill_buckets=(16,))
        h1 = pw.prefill([1, 2, 3], SamplingParams(max_tokens=8))
        h2 = pw.prefill([4, 5, 6], SamplingParams(max_tokens=8))
        assert eng.import_prefill(h1) is not None
        assert eng.import_prefill(h2) is None  # no free slot
        while eng.has_work():
            eng.step()
        assert eng.import_prefill(h2) is not None


ENGINE_OPTS = {"max_slots": 2, "page_size": 8, "num_pages": 64,
               "prefill_buckets": (16,)}


class TestDisaggServer:
    def test_all_modes_exact(self, params):
        from ray_tpu.llm.disagg import DisaggServer

        prompt = [3, 17, 92, 5, 41]
        want = naive_greedy(params, prompt, 6)
        for mode in ("inline", "chunked", "disagg"):
            srv = DisaggServer(lambda: (params, CFG), mode=mode,
                               engine_options=dict(ENGINE_OPTS),
                               record_token_times=True)
            try:
                out = srv({"prompt_tokens": prompt, "max_tokens": 6,
                           "timeout_s": 120})
                assert out["output_tokens"] == want, mode
                assert out["finish_reason"] == "length"
                assert out["ttft_s"] is not None and out["ttft_s"] >= 0
            finally:
                srv.close()

    def test_admission_sheds_not_queues(self, params):
        """Past the class queue bound, submit raises a retriable
        OverloadError immediately — overload never becomes a silent
        timeout."""
        from ray_tpu.llm.disagg import (AdmissionConfig, DisaggServer,
                                        OverloadError, RequestClass)

        adm = AdmissionConfig(classes={"default": RequestClass(
            max_queue_depth=2, queue_deadline_s=30.0)})
        srv = DisaggServer(lambda: (params, CFG), mode="inline",
                           engine_options=dict(ENGINE_OPTS), admission=adm)
        try:
            shed = 0
            ids = []
            for _ in range(40):
                try:
                    ids.append(srv.submit({"prompt_tokens": [5, 6, 7],
                                           "max_tokens": 12}))
                except OverloadError as e:
                    assert e.retriable
                    shed += 1
            assert shed > 0
            # Admitted requests still complete.
            res = srv.result(ids[0], timeout_s=120)
            assert res["finish_reason"] == "length"
        finally:
            srv.close()

    def test_class_token_budget(self, params):
        from ray_tpu.llm.disagg import (AdmissionConfig, DisaggServer,
                                        OverloadError, RequestClass)

        adm = AdmissionConfig(classes={"default": RequestClass(
            token_budget=40, max_queue_depth=64)})
        srv = DisaggServer(lambda: (params, CFG), mode="inline",
                           engine_options=dict(ENGINE_OPTS), admission=adm)
        try:
            srv.submit({"prompt_tokens": [1, 2, 3], "max_tokens": 30})
            with pytest.raises(OverloadError, match="class_budget"):
                srv.submit({"prompt_tokens": [1, 2, 3], "max_tokens": 30})
        finally:
            srv.close()

    def test_serve_load_saturation_smoke(self, params):
        """Tier-1 serve_load contract: under forced saturation (open-
        loop arrivals far past capacity, tiny queue bounds) the router
        SHEDS instead of queueing unboundedly, and p99 TTFT of ADMITTED
        requests stays bounded."""
        from ray_tpu.llm.disagg import (AdmissionConfig, DisaggServer,
                                        RequestClass, ServeLoadSpec,
                                        run_open_loop)

        adm = AdmissionConfig(classes={
            "interactive": RequestClass("interactive", token_budget=200,
                                        max_queue_depth=4,
                                        queue_deadline_s=1.5),
            "batch": RequestClass("batch", token_budget=120,
                                  max_queue_depth=2,
                                  queue_deadline_s=1.5),
            "default": RequestClass()})
        srv = DisaggServer(lambda: (params, CFG), mode="chunked",
                           engine_options=dict(ENGINE_OPTS), admission=adm,
                           record_token_times=True)
        try:
            spec = ServeLoadSpec(rps=60, duration_s=2.0,
                                 long_fraction=0.3, short_prompt=6,
                                 short_max_tokens=12, long_prompt=14,
                                 long_max_tokens=6, drain_timeout_s=120)
            r = run_open_loop(srv, spec, vocab_size=CFG.vocab_size)
        finally:
            srv.close()
        assert r["offered"] > 20
        assert r["shed_submit"] + r["shed_deadline"] > 0, \
            "saturation must activate shedding"
        assert r["completed"] > 0
        assert r["unfinished"] == 0 and r["errors"] == 0
        # Bounded TTFT for admitted work: the queue deadline caps time-
        # to-dispatch, so admitted p99 TTFT can't grow with offered load.
        assert r["ttft_p99_ms"] is not None and r["ttft_p99_ms"] < 5000.0


class TestLLMServerLifecycle:
    def test_close_joins_drive_thread(self, params):
        srv = LLMServer(lambda: (params, CFG),
                        engine_options=dict(ENGINE_OPTS))
        assert srv._thread.is_alive()
        srv.close()
        assert not srv._thread.is_alive()

    def test_submit_kicks_drive_event(self, params):
        """No sleep-poll: a submitted request completes promptly because
        submit sets the work event (the old 5 ms poll is gone)."""
        srv = LLMServer(lambda: (params, CFG),
                        engine_options=dict(ENGINE_OPTS))
        try:
            out = srv({"prompt_tokens": [5, 6, 7], "max_tokens": 4,
                       "timeout_s": 120})
            assert out["finish_reason"] == "length"
        finally:
            srv.close()

    def test_abandoned_request_swept(self, params, monkeypatch):
        """A caller that vanishes after submit leaves no engine slot,
        pages, or _events/_results entries behind once its deadline +
        grace passes."""
        from ray_tpu.llm import serving as serving_mod

        monkeypatch.setattr(serving_mod, "_ABANDON_GRACE_S", 0.2)
        srv = LLMServer(lambda: (params, CFG),
                        engine_options=dict(ENGINE_OPTS))
        try:
            free0 = srv.engine.pool.num_free
            # Submit and never wait: max_tokens large enough that it is
            # still running when the deadline (0 + grace) passes.
            rid, _ev, _req = srv._submit([5, 6, 7],
                                   SamplingParams(max_tokens=4),
                                   timeout_s=0.0)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with srv._lock:
                    clean = rid not in srv._events \
                        and rid not in srv._results \
                        and rid not in srv._deadlines
                if clean and srv.engine.pool.num_free == free0 \
                        and rid not in srv.engine.running:
                    break
                time.sleep(0.05)
            with srv._lock:
                assert rid not in srv._events
                assert rid not in srv._results
                assert rid not in srv._deadlines
            assert rid not in srv.engine.running
            assert srv.engine.pool.num_free == free0
        finally:
            srv.close()
