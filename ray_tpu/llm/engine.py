"""Batched inference engine: continuous batching over a paged KV cache.

Reference analog: the vLLM engine the reference wraps for serving and
batch inference (reference: python/ray/llm/_internal/serve/engines/vllm/,
batch/stages/vllm_engine_stage.py) — rebuilt TPU-native: the decode step
is one jit-compiled SPMD program over all active slots (static shapes:
[max_slots] tokens, [max_slots, pages_per_seq] block tables), prefill runs
per-request on length-bucketed padded shapes, and the scheduler admits
waiting requests into free slots between steps (continuous batching, not
static batches).
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ..util import telemetry
from ._cache import PagePool


@dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0           # 0 = greedy
    top_k: int = 0                     # 0 = full vocab
    stop_token_ids: tuple = ()
    seed: Optional[int] = None

    @classmethod
    def from_body(cls, body: Dict[str, Any]) -> "SamplingParams":
        """The one request-body -> params parser every serving entry
        point shares (LLMServer call/stream, DisaggServer)."""
        return cls(
            max_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            stop_token_ids=tuple(body.get("stop_token_ids", ())))


@dataclass
class Request:
    request_id: int
    prompt_tokens: List[int]
    params: SamplingParams
    # Filled as the request progresses:
    output_tokens: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    pages: List[int] = field(default_factory=list)
    finished: bool = False
    finish_reason: str = ""
    # Telemetry: submission time (perf_counter) for TTFT.
    t_submit: float = 0.0
    # Admission time (perf_counter): the request left ``waiting`` for a
    # slot, so queue wait (t_admit - t_submit) is told apart from
    # prefill (t_first - t_admit).  0.0 until first admitted.
    t_admit: float = 0.0
    # First-token time (perf_counter); 0.0 until the first token lands.
    t_first: float = 0.0
    # Admission sequence (preemption picks the youngest victim; -1 =
    # never admitted).
    admit_seq: int = -1
    # Per-output-token perf_counter stamps, recorded only when the
    # engine was built with record_token_times=True (serve_load bench:
    # inter-token latency percentiles need per-token arrival times).
    token_times: List[float] = field(default_factory=list)


def _named(name: str, fn, **fixed):
    """``fn`` with ``fixed`` keyword arguments bound, under the name
    ``name``: what ``jax.jit`` calls the program (``jit_<name>``)."""
    def call(*args):
        return fn(*args, **fixed)
    call.__name__ = call.__qualname__ = name
    return call


def sample_logits(logits: np.ndarray, params: SamplingParams,
                  rng: np.random.Generator) -> int:
    """Host-side token sampling shared by the engine's admission path
    and the disagg PrefillWorker (both sample the FIRST token from
    prefill logits)."""
    if params.temperature <= 0.0:
        return int(np.argmax(logits))
    logits = logits / params.temperature
    if params.top_k:
        kth = np.partition(logits, -params.top_k)[-params.top_k]
        logits = np.where(logits < kth, -np.inf, logits)
    p = np.exp(logits - logits.max())
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


class InferenceEngine:
    """Single-host continuous-batching engine over the paged cache."""

    def __init__(self, params, cfg, *, max_slots: int = 8,
                 page_size: int = 16, num_pages: int = 512,
                 max_seq_len: Optional[int] = None,
                 prefill_buckets: tuple = (64, 256, 1024),
                 prefill_chunk: Optional[int] = None,
                 record_token_times: bool = False):
        import jax
        import jax.numpy as jnp

        from . import _model

        self._jax = jax
        self._jnp = jnp
        self.params = params
        self.cfg = cfg
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        self.pages_per_seq = math.ceil(self.max_seq_len / page_size)
        self.pool = PagePool(num_pages)
        self.prefill_buckets = tuple(sorted(prefill_buckets))

        L = cfg.layers
        Hkv, D = cfg.kv_heads, cfg.head_dim
        self.num_pages = num_pages
        # One COMBINED page array per layer (tuple pytree): K even / V
        # odd combined-head indices, pages leading — the ragged kernel's
        # native layout and the one whose per-token insert is a single
        # contiguous-window scatter (see _model.decode_step).
        self.kv_pages = tuple(
            jnp.zeros((num_pages, page_size, 2 * Hkv, D), cfg.dtype)
            for _ in range(L))
        # Host-side slot state (mirrored to device each step).
        self.block_tables = np.zeros((max_slots, self.pages_per_seq),
                                     np.int32)
        self.slot_tokens = np.zeros((max_slots,), np.int32)
        self.slot_pos = np.zeros((max_slots,), np.int32)
        self.slot_active = np.zeros((max_slots,), bool)
        self.slot_req: List[Optional[Request]] = [None] * max_slots

        self.waiting: List[Request] = []
        self.running: Dict[int, Request] = {}
        # Requests that finish during admission (immediate stop token,
        # max_tokens=1, rejections) never occupy a slot; step() drains them.
        self._admission_finished: List[Request] = []
        self._req_ids = itertools.count()
        # Chunked prefill (disagg-off fallback): prompts longer than
        # ``prefill_chunk`` tokens are prefilled one bounded chunk per
        # step, interleaved with decode, instead of one monolithic
        # program that stalls every active decode.
        self.prefill_chunk = prefill_chunk
        self.record_token_times = record_token_times
        self._admit_seq = itertools.count()
        self._step_seq = itertools.count()
        self._prefilling: Dict[int, int] = {}   # slot -> prompt tokens done
        self._prefill_chunk_jit = None
        # RLock: step() -> _admit() nests; server threads call
        # add_request/cancel concurrently with the drive thread's step().
        self._lock = threading.RLock()
        self._rng = np.random.default_rng(0)

        # Named functions, not partials: a jitted partial is
        # ``jit__unknown_`` in a device trace, a named one is the
        # program's name there and in ``xla_compile`` spans.
        self._decode = jax.jit(
            _named("decode_step", _model.decode_step, cfg=cfg,
                   page_size=page_size),
            donate_argnums=(1,))
        self._prefills = {
            b: jax.jit(_named(f"prefill_{b}", _model.prefill, cfg=cfg))
            for b in self.prefill_buckets}
        self._write_prefill = jax.jit(_model.write_prefill,
                                      donate_argnums=(0,))

    # -- request intake -----------------------------------------------------

    def submit(self, prompt_tokens: List[int],
               params: Optional[SamplingParams] = None) -> Request:
        """Queue a request and return it: a caller that follows its
        progress holds the object (``running`` drops a finished one)."""
        params = params or SamplingParams()
        req = Request(next(self._req_ids), list(prompt_tokens), params,
                      t_submit=time.perf_counter())
        with self._lock:
            self.waiting.append(req)
            self.running[req.request_id] = req
            self._update_gauges()
        return req

    def add_request(self, prompt_tokens: List[int],
                    params: Optional[SamplingParams] = None) -> int:
        return self.submit(prompt_tokens, params).request_id

    # -- telemetry ----------------------------------------------------------

    def _update_gauges(self) -> None:
        """Occupancy/queue-depth gauges; callers hold the engine lock."""
        telemetry.set_gauge("ray_tpu_llm_active_slots",
                            int(self.slot_active.sum()))
        telemetry.set_gauge("ray_tpu_llm_kv_page_occupancy",
                            1.0 - self.pool.num_free
                            / max(self.pool.num_pages, 1))
        telemetry.set_gauge("ray_tpu_llm_waiting_requests",
                            len(self.waiting))

    def _note_finish(self, req: Request, preempted: bool = False) -> None:
        telemetry.inc("ray_tpu_llm_requests_finished_total",
                      tags={"reason": req.finish_reason or "unknown"})
        if preempted:
            telemetry.inc("ray_tpu_llm_preemptions_total")

    def _bucket_for(self, n: int) -> Optional[int]:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return None

    def _chunk_tokens(self) -> int:
        """Chunk size for incremental prefill: the configured
        ``prefill_chunk``, else the largest bucket (the fallback for
        seeds no monolithic bucket covers)."""
        return self.prefill_chunk if self.prefill_chunk is not None \
            else self.prefill_buckets[-1]

    # -- scheduling ---------------------------------------------------------

    def _admit(self) -> None:
        """Admission under the span ``engine_admit`` (no span for an empty
        queue: that is every step of a saturated batch)."""
        if not self.waiting:
            self._update_gauges()
            return
        with telemetry.profile_span("engine_admit", "llm"):
            self._admit_waiting()

    def _note_admitted(self, req: Request) -> None:
        """``req`` just left ``waiting`` for a slot: stamp the first
        admission (a preempted request's later ones are recompute, not
        queueing) and record its queue wait."""
        if not req.t_admit:
            req.t_admit = time.perf_counter()
            telemetry.observe("ray_tpu_llm_queue_wait_seconds",
                              max(0.0, req.t_admit - req.t_submit))

    def _admit_waiting(self) -> None:
        """Move waiting requests into free slots (prefill + page alloc).

        Host work is batched: every admitted request's last-position
        logits stay on device through the loop and transfer in ONE
        device->host sync at the end — per-request readbacks would pay
        the full host<->device latency once per admission (reference
        analog: batched prefill scheduling)."""
        jnp = self._jnp
        from . import _model  # noqa: F401  (prefill fns built in __init__)

        staged: List = []  # (req, slot, device_logits)
        while self.waiting:
            free_slots = [i for i in range(self.max_slots)
                          if self.slot_req[i] is None]
            if not free_slots:
                break
            req = self.waiting[0]
            # Re-admission after preemption re-prefills prompt + tokens
            # generated so far (recompute preemption): the "seed".
            seed = req.prompt_tokens + req.output_tokens
            n = len(seed)
            total = len(req.prompt_tokens) + req.params.max_tokens
            if total > self.max_seq_len:
                self._reject_head(req, "prompt_too_long")
                continue
            if math.ceil(total / self.page_size) > self.pool.num_pages - 1:
                # Could never fit even an empty pool: reject, don't wedge
                # the FIFO behind an unadmittable request.
                self._reject_head(req, "kv_capacity_exceeded")
                continue
            chunked = (self.prefill_chunk is not None
                       and n > self.prefill_chunk)
            if not chunked:
                bucket = self._bucket_for(n)
                if bucket is None:
                    # Beyond every bucket — an oversized prompt, or a
                    # preempted request whose recompute seed (prompt +
                    # generated-so-far) outgrew them.  The chunked
                    # program covers any length up to max_seq_len.
                    chunked = True
            if chunked:
                # Reserve the slot; _prefill_tick runs one bounded chunk
                # per step.  First chunk's pages allocate up front so an
                # empty pool still backpressures here.
                need0 = math.ceil(min(self._chunk_tokens(), n)
                                  / self.page_size)
                pages = self.pool.alloc(need0)
                if pages is None:
                    break  # no KV memory; stay queued (backpressure)
                self.waiting.pop(0)
                self._note_admitted(req)
                slot = free_slots[0]
                req.slot = slot
                req.pages = pages
                req.admit_seq = next(self._admit_seq)
                self.slot_req[slot] = req
                self.slot_active[slot] = False
                bt = np.zeros((self.pages_per_seq,), np.int32)
                bt[:len(pages)] = pages
                self.block_tables[slot] = bt
                self._prefilling[slot] = 0
                continue
            # Pages are allocated LAZILY: the seed plus the first decode
            # token now, one page at a time as decode crosses page
            # boundaries (see _ensure_decode_capacity) — upfront
            # prompt+max_tokens allocation left most of the pool idle.
            n_pages = math.ceil((n + 1) / self.page_size)
            pages = self.pool.alloc(n_pages)
            if pages is None:
                break  # no KV memory; stay queued (backpressure)
            self.waiting.pop(0)
            self._note_admitted(req)
            slot = free_slots[0]

            # Prefill on the padded bucket; returns last logits + K/V.
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = seed
            with telemetry.profile_span(
                    "engine_prefill", "llm",
                    extra={"request_id": req.request_id, "prompt_len": n}):
                logits, ks, vs = self._prefills[bucket](
                    self.params, jnp.asarray(toks), jnp.asarray(n))
            telemetry.inc("ray_tpu_llm_tokens_total", n,
                          tags={"kind": "prompt"})
            # Scatter prompt K/V into this request's pages: ONE jitted
            # device program for all layers (bucket-static shape; padding
            # positions land in reserved page 0, which no block table
            # references).  Per-layer host-side scatters would cost
            # 2*layers dispatches per admission.
            page_ids_np = np.zeros((bucket,), np.int32)
            for t in range(n):
                page_ids_np[t] = pages[t // self.page_size]
            offs_np = np.arange(bucket, dtype=np.int32) % self.page_size
            self.kv_pages = self._write_prefill(
                self.kv_pages, ks, vs,
                jnp.asarray(page_ids_np), jnp.asarray(offs_np))

            # Mark the slot taken now; the first token lands after the
            # batched sync below.
            req.slot = slot
            req.pages = pages
            req.admit_seq = next(self._admit_seq)
            self.slot_req[slot] = req
            self.slot_active[slot] = True
            self.slot_pos[slot] = n
            bt = np.zeros((self.pages_per_seq,), np.int32)
            bt[:n_pages] = pages
            self.block_tables[slot] = bt
            staged.append((req, slot, logits))

        if not staged:
            self._update_gauges()
            return
        with telemetry.profile_span("engine_prefill_sync", "llm",
                                    extra={"requests": len(staged)}):
            all_logits = np.asarray(self._jax.numpy.stack(
                [lg for _r, _s, lg in staged]))       # ONE host sync
        now = time.perf_counter()
        for (req, slot, _lg), logits in zip(staged, all_logits):
            first_tok = self._sample_host(logits, req.params)
            if not req.output_tokens:   # first admission, not recompute
                telemetry.observe("ray_tpu_llm_ttft_seconds",
                                  max(0.0, now - req.t_submit))
                req.t_first = now
            req.output_tokens.append(int(first_tok))
            if self.record_token_times:
                req.token_times.append(now)
            self.slot_tokens[slot] = first_tok
            self._maybe_finish(req, int(first_tok))
            if req.finished:
                self._admission_finished.append(req)
        self._update_gauges()

    def _reject_head(self, req: Request, reason: str) -> None:
        """Reject the queue-head request at admission (never admitted:
        no slot or pages to release)."""
        req.finished = True
        req.finish_reason = reason
        self.waiting.pop(0)
        self.running.pop(req.request_id, None)
        self._admission_finished.append(req)
        self._note_finish(req)

    def _prefill_tick(self) -> None:
        """Advance ONE chunked prefill by ONE chunk (callers hold the
        lock).  One chunk per step bounds the stall any prefill can
        impose on the active decode batch — the whole point of chunked
        prefill."""
        if not self._prefilling:
            return
        jnp = self._jnp
        from . import _model
        # FIFO fairness: the earliest-admitted prefill advances first.
        slot = min(self._prefilling,
                   key=lambda s: self.slot_req[s].admit_seq)
        req = self.slot_req[slot]
        done = self._prefilling[slot]
        seed = req.prompt_tokens + req.output_tokens
        n = len(seed)
        C = self._chunk_tokens()
        end = min(done + C, n)
        # Pages must cover positions [0, end), plus the first decode
        # token when this chunk completes the prompt.
        cover = end + 1 if end >= n else end
        need = math.ceil(cover / self.page_size) - len(req.pages)
        if need > 0:
            pages = self.pool.alloc(need)
            while pages is None:
                # Preempt strictly-YOUNGER page holders (decoding or
                # prefilling) before stalling: with every slot mid-
                # prefill and the pool dry, nothing would ever free a
                # page otherwise (prefill-vs-prefill deadlock).  An
                # older holder wins instead — we stall and it finishes.
                cands = [s for s in range(self.max_slots)
                         if s != slot and self.slot_req[s] is not None
                         and self.slot_req[s].admit_seq > req.admit_seq]
                if not cands:
                    return  # KV pressure: stall until frees arrive
                self._preempt(max(
                    cands, key=lambda s: self.slot_req[s].admit_seq))
                pages = self.pool.alloc(need)
            base = len(req.pages)
            req.pages.extend(pages)
            self.block_tables[slot, base:base + len(pages)] = pages
        if self._prefill_chunk_jit is None:
            self._prefill_chunk_jit = self._jax.jit(
                _named("prefill_chunk", _model.prefill_chunk, cfg=self.cfg,
                       page_size=self.page_size),
                donate_argnums=(1,))
        toks = np.zeros((1, C), np.int32)
        toks[0, :end - done] = seed[done:end]
        with telemetry.profile_span(
                "engine_prefill_chunk", "llm",
                extra={"request_id": req.request_id, "start": done,
                       "len": end - done}):
            logits, self.kv_pages = self._prefill_chunk_jit(
                self.params, self.kv_pages, jnp.asarray(toks),
                jnp.asarray(np.int32(done)),
                jnp.asarray(np.int32(end - done)),
                jnp.asarray(self.block_tables[slot].copy()))
        telemetry.inc("ray_tpu_llm_prefill_chunks_total")
        telemetry.inc("ray_tpu_llm_tokens_total", end - done,
                      tags={"kind": "prompt"})
        self._prefilling[slot] = end
        if end < n:
            return
        # Prompt complete: sample the first token, join the decode batch.
        first = self._sample_host(np.asarray(logits), req.params)
        now = time.perf_counter()
        if not req.output_tokens:
            telemetry.observe("ray_tpu_llm_ttft_seconds",
                              max(0.0, now - req.t_submit))
            req.t_first = now
        req.output_tokens.append(int(first))
        if self.record_token_times:
            req.token_times.append(now)
        del self._prefilling[slot]
        self.slot_pos[slot] = n
        self.slot_tokens[slot] = int(first)
        self.slot_active[slot] = True
        self._maybe_finish(req, int(first))
        if req.finished:
            self._admission_finished.append(req)
        self._update_gauges()

    def _need_pages(self, slot: int) -> int:
        """Extra pages ``slot`` needs to write its next token's KV."""
        req = self.slot_req[slot]
        cover = int(self.slot_pos[slot]) + 1
        return max(0, math.ceil(cover / self.page_size) - len(req.pages))

    def _ensure_decode_capacity(self) -> None:
        """Lazily extend block tables so every active slot can write KV
        for its next token, preempting the YOUNGEST request
        (recompute preemption: victims re-queue at the FRONT, so
        re-admission preserves arrival order) when the pool runs dry.
        Callers hold the lock."""
        while True:
            active = [s for s in range(self.max_slots)
                      if self.slot_active[s]]
            if sum(self._need_pages(s) for s in active) \
                    <= self.pool.num_free:
                break
            cands = [s for s in range(self.max_slots)
                     if self.slot_req[s] is not None]
            if len(cands) <= 1:
                break  # a lone request's need is always satisfiable
            self._preempt(max(
                cands, key=lambda s: self.slot_req[s].admit_seq))
        for slot in range(self.max_slots):
            if not self.slot_active[slot]:
                continue
            need = self._need_pages(slot)
            if need == 0:
                continue
            pages = self.pool.alloc(need)
            if pages is None:
                # Still short (single-victim granularity): a slot must
                # never decode past its pages (its KV would land on
                # reserved page 0 and attention would read garbage).
                self._preempt(slot)
                continue
            req = self.slot_req[slot]
            base = len(req.pages)
            req.pages.extend(pages)
            self.block_tables[slot, base:base + len(pages)] = pages

    def _preempt(self, slot: int) -> None:
        """Evict the request in ``slot`` back to the FRONT of the
        waiting queue, freeing its pages; re-admission re-prefills
        prompt + generated-so-far (recompute preemption, the vLLM
        default).  Callers hold the lock."""
        req = self.slot_req[slot]
        self.slot_active[slot] = False
        self.slot_req[slot] = None
        self._prefilling.pop(slot, None)
        self.pool.free(req.pages)
        req.pages = []
        req.slot = None
        self.block_tables[slot] = 0
        # Preemption order is youngest-first, each inserting at the
        # front: after multiple preemptions the queue front is back in
        # arrival order ahead of never-admitted requests (which always
        # arrived later than anything that was already running).
        self.waiting.insert(0, req)
        telemetry.inc("ray_tpu_llm_preemptions_total")

    def _sample_host(self, logits: np.ndarray,
                     params: SamplingParams) -> int:
        return sample_logits(logits, params, self._rng)

    def _maybe_finish(self, req: Request, token: int) -> None:
        stop = token in req.params.stop_token_ids
        done = stop or len(req.output_tokens) >= req.params.max_tokens
        if done:
            req.finished = True
            req.finish_reason = "stop" if stop else "length"
            if req.slot is not None:
                slot = req.slot
                self.slot_active[slot] = False
                self.slot_req[slot] = None
                self.pool.free(req.pages)
                req.pages = []
            self.running.pop(req.request_id, None)
            self._note_finish(req)

    def cancel(self, request_id: int) -> None:
        """Abandon a request: free its slot/pages (timeouts, disconnects)."""
        with self._lock:
            req = self.running.pop(request_id, None)
            if req is None:
                return
            if req in self.waiting:
                self.waiting.remove(req)
            preempted = req.slot is not None \
                and self.slot_req[req.slot] is req
            if preempted:
                self.slot_active[req.slot] = False
                self.slot_req[req.slot] = None
                self._prefilling.pop(req.slot, None)
            self.pool.free(req.pages)
            req.pages = []
            req.finished = True
            req.finish_reason = "cancelled"
            self._note_finish(req, preempted=preempted)
            self._update_gauges()

    # -- disaggregated prefill import ---------------------------------------

    def import_prefill(self, handoff) -> Optional[int]:
        """Join a request prefilled ELSEWHERE (a disagg PrefillWorker)
        to this engine's continuous batch: allocate local pages, scatter
        the handed-off K/V in one device program (the same compiled
        ``write_prefill`` the local admission path uses), and activate
        the slot with the already-sampled first token.

        ``handoff`` is a :class:`ray_tpu.llm.disagg.KVHandoff` (duck-
        typed: prompt_tokens / first_token / ks / vs / params / t_submit
        / t_first).  Returns the local request id, or None when no slot
        or pages are free — the caller holds the handoff and retries
        (backpressure), it is never silently dropped."""
        jnp = self._jnp
        with self._lock:
            n = len(handoff.prompt_tokens)
            total = n + handoff.params.max_tokens
            if total > self.max_seq_len:
                raise ValueError(
                    f"handoff needs {total} positions; engine max_seq_len "
                    f"is {self.max_seq_len}")
            free_slots = [i for i in range(self.max_slots)
                          if self.slot_req[i] is None]
            if not free_slots:
                return None
            n_pages = math.ceil((n + 1) / self.page_size)
            pages = self.pool.alloc(n_pages)
            if pages is None:
                return None
            req = Request(next(self._req_ids),
                          list(handoff.prompt_tokens), handoff.params,
                          t_submit=handoff.t_submit or time.perf_counter())
            req.admit_seq = next(self._admit_seq)
            slot = free_slots[0]
            bucket = handoff.ks.shape[1]
            page_ids_np = np.zeros((bucket,), np.int32)
            for t in range(n):
                page_ids_np[t] = pages[t // self.page_size]
            offs_np = np.arange(bucket, dtype=np.int32) % self.page_size
            self.kv_pages = self._write_prefill(
                self.kv_pages, jnp.asarray(np.ascontiguousarray(handoff.ks)),
                jnp.asarray(np.ascontiguousarray(handoff.vs)),
                jnp.asarray(page_ids_np), jnp.asarray(offs_np))
            first = int(handoff.first_token)
            req.slot = slot
            req.pages = pages
            req.t_first = handoff.t_first or time.perf_counter()
            if handoff.t_submit:
                # The disagg path's TTFT (submit -> prefill worker's
                # first token) lands in the same histogram the local
                # admission paths feed.
                telemetry.observe("ray_tpu_llm_ttft_seconds",
                                  max(0.0, req.t_first - req.t_submit))
            req.output_tokens.append(first)
            if self.record_token_times:
                req.token_times.append(req.t_first)
            self.slot_req[slot] = req
            self.slot_pos[slot] = n
            self.slot_tokens[slot] = first
            self.slot_active[slot] = True
            bt = np.zeros((self.pages_per_seq,), np.int32)
            bt[:n_pages] = pages
            self.block_tables[slot] = bt
            self.running[req.request_id] = req
            self._maybe_finish(req, first)
            if req.finished:
                self._admission_finished.append(req)
            self._update_gauges()
            return req.request_id

    # -- stepping -----------------------------------------------------------

    def has_work(self) -> bool:
        with self._lock:
            return bool(self.waiting or any(self.slot_active)
                        or self._prefilling or self._admission_finished)

    def load_stats(self) -> Dict[str, Any]:
        """Live load snapshot for admission control / backpressure
        (router-facing: KV occupancy + queue depths)."""
        with self._lock:
            return {
                "kv_occupancy": 1.0 - self.pool.num_free
                / max(self.pool.num_pages, 1),
                "free_pages": self.pool.num_free,
                "active_slots": int(self.slot_active.sum()),
                "free_slots": sum(1 for i in range(self.max_slots)
                                  if self.slot_req[i] is None),
                "waiting": len(self.waiting),
                "prefilling": len(self._prefilling),
            }

    def step(self) -> List[Request]:
        """Admit + one batched decode step; returns requests finished now.

        Runs under the engine lock: add_request/cancel from server threads
        must not interleave with slot/page mutation (a cancel between page
        alloc and table write would let two sequences share pages)."""
        with self._lock:
            # group: the whole step; its parts are the spans an idle gap
            # on the device can be given to.
            with telemetry.profile_span(
                    "engine_step", "llm",
                    extra={"step": next(self._step_seq)}, group=True):
                return self._step_locked()

    def _step_locked(self) -> List[Request]:
        jnp = self._jnp
        self._admit()
        self._prefill_tick()
        finished = list(self._admission_finished)
        self._admission_finished.clear()
        if not any(self.slot_active):
            return finished
        self._ensure_decode_capacity()
        if not any(self.slot_active):
            return finished
        t0 = time.perf_counter()
        with telemetry.profile_span("engine_upload", "llm"):
            tokens = jnp.asarray(self.slot_tokens.copy())
            positions = jnp.asarray(self.slot_pos.copy())
            tables = jnp.asarray(self.block_tables.copy())
            active = jnp.asarray(self.slot_active.copy())
        with telemetry.profile_span("engine_decode_dispatch", "llm"):
            logits, self.kv_pages = self._decode(
                self.params, self.kv_pages, tokens, positions, tables,
                active)
        with telemetry.profile_span("engine_logits_read", "llm"):
            logits = np.asarray(logits)
        decoded = 0
        with telemetry.profile_span("engine_sample", "llm"):
            for slot in range(self.max_slots):
                if not self.slot_active[slot]:
                    continue
                req = self.slot_req[slot]
                tok = self._sample_host(logits[slot], req.params)
                req.output_tokens.append(tok)
                if self.record_token_times:
                    req.token_times.append(time.perf_counter())
                decoded += 1
                self.slot_pos[slot] += 1
                self.slot_tokens[slot] = tok
                self._maybe_finish(req, tok)
                if req.finished:
                    finished.append(req)
        telemetry.observe("ray_tpu_llm_decode_token_seconds",
                          time.perf_counter() - t0)
        if decoded:
            telemetry.inc("ray_tpu_llm_tokens_total", decoded,
                          tags={"kind": "decode"})
        self._update_gauges()
        return finished

    # -- offline batch API --------------------------------------------------

    def generate(self, prompts: List[List[int]],
                 params: Optional[SamplingParams] = None
                 ) -> List[List[int]]:
        """Batch inference: drives the engine until every prompt drains
        (reference analog: llm batch stages)."""
        reqs = {self.add_request(p, params): i
                for i, p in enumerate(prompts)}
        outputs: Dict[int, List[int]] = {}
        guard = 0
        while len(outputs) < len(prompts):
            for req in self.step():
                if req.request_id in reqs:
                    outputs[reqs[req.request_id]] = req.output_tokens
            # Requests rejected at admission (too long) never hit step():
            with self._lock:
                for rid, idx in list(reqs.items()):
                    if idx not in outputs and rid not in self.running:
                        outputs[idx] = []
            guard += 1
            if guard > 100000:
                raise RuntimeError("engine did not drain")
        return [outputs[i] for i in range(len(prompts))]
