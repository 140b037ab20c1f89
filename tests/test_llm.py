"""LLM engine tests: paged-cache decode correctness vs the full forward,
continuous batching, page accounting, serve integration (reference analog:
python/ray/llm tests — the reference delegates correctness to vLLM; here
the engine is ours so exactness is asserted against the training model)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import InferenceEngine, SamplingParams
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


def naive_greedy(params, prompt, max_new):
    """Gold: full forward re-run per token."""
    toks = list(prompt)
    out = []
    for _ in range(max_new):
        logits = _forward(params, jnp.asarray([toks]))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


class TestInferenceEngine:
    def test_greedy_matches_full_forward(self, params):
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16, 64))
        prompt = [3, 17, 92, 5, 41]
        got = eng.generate([prompt], SamplingParams(max_tokens=8))[0]
        want = naive_greedy(params, prompt, 8)
        assert got == want

    def test_continuous_batching_matches_sequential(self, params):
        prompts = [[7, 9, 23], [4, 4, 8, 15, 16, 23, 42], [99], [1, 2]]
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16, 64))
        # max_slots=2 < 4 prompts forces admission waves mid-decode.
        batch = eng.generate(prompts, SamplingParams(max_tokens=6))
        for p, got in zip(prompts, batch):
            assert got == naive_greedy(params, p, 6)

    def test_pages_freed_after_generation(self, params):
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=32, prefill_buckets=(16,))
        free0 = eng.pool.num_free
        eng.generate([[5, 6, 7]] * 3, SamplingParams(max_tokens=4))
        assert eng.pool.num_free == free0

    def test_kv_memory_backpressure(self, params):
        # Tiny pool: requests must queue on page exhaustion yet all finish.
        eng = InferenceEngine(params, CFG, max_slots=4, page_size=8,
                              num_pages=8, prefill_buckets=(16,))
        outs = eng.generate([[i + 1, i + 2] for i in range(5)],
                            SamplingParams(max_tokens=4))
        assert all(len(o) == 4 for o in outs)

    def test_too_long_prompt_rejected(self, params):
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16,),
                              max_seq_len=32)
        outs = eng.generate([list(range(1, 40)), [5, 6]],
                            SamplingParams(max_tokens=4))
        assert outs[0] == []          # rejected: prompt_too_long
        assert len(outs[1]) == 4

    def test_stop_tokens(self, params):
        eng = InferenceEngine(params, CFG, max_slots=1, page_size=8,
                              num_pages=64, prefill_buckets=(16,))
        prompt = [3, 17, 92, 5, 41]
        full = naive_greedy(params, prompt, 8)
        stop = full[2]
        got = eng.generate([prompt], SamplingParams(
            max_tokens=8, stop_token_ids=(stop,)))[0]
        assert got == full[:3]        # stops when the stop token appears

    def test_greedy_beside_a_sampled_request(self, params):
        """A greedy request keeps its exact stream while a request with
        ``temperature > 0`` and a ``top_k`` decodes in the same batch:
        ``step`` samples each slot by its own parameters."""
        eng = InferenceEngine(params, CFG, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16,))
        prompt = [3, 17, 92, 5, 41]
        greedy = eng.submit(prompt, SamplingParams(max_tokens=8))
        sampled = eng.submit([7, 9, 23, 6], SamplingParams(
            max_tokens=8, temperature=0.8, top_k=5))
        shared = 0
        while eng.has_work():
            eng.step()
            shared += int(eng.slot_active.sum()) == 2
        assert shared, "the two requests never shared a decode batch"
        assert greedy.output_tokens == naive_greedy(params, prompt, 8)
        assert len(sampled.output_tokens) == 8
        assert all(0 <= t < CFG.vocab_size for t in sampled.output_tokens)


class TestLLMServing:
    def test_serve_deployment_end_to_end(self, ray_start):
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.llm import build_llm_deployment

        def build():
            return init_params(CFG, jax.random.key(0)), CFG

        app = build_llm_deployment(build, name="tiny_llm",
                                   engine_options={
                                       "max_slots": 2, "page_size": 8,
                                       "num_pages": 64,
                                       "prefill_buckets": (16,)})
        h = serve.run(app)
        prompt = [3, 17, 92, 5, 41]
        out = ray_tpu.get(h.remote({"prompt_tokens": prompt,
                                    "max_tokens": 6}), timeout=120)
        expected = naive_greedy(init_params(CFG, jax.random.key(0)),
                                prompt, 6)
        assert out["output_tokens"] == expected
        assert out["finish_reason"] == "length"
        # Token streaming: the stream method yields the same tokens one by
        # one through a streaming actor call (num_returns="streaming").
        gen = h.options(stream=True, method_name="stream").remote(
            {"prompt_tokens": prompt, "max_tokens": 6})
        items = [ray_tpu.get(r, timeout=120) for r in gen]
        streamed = [it["token"] for it in items if "token" in it]
        assert streamed == expected
        assert items[-1]["finish_reason"] == "length"
        serve.shutdown()
