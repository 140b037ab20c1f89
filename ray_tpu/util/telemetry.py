"""Built-in system telemetry: the canonical catalog of framework metrics.

Reference: the dashboard-agent's built-in metric export
(python/ray/_private/metrics_agent.py — tasks, serve request latency,
autoscaler state — and src/ray/observability/open_telemetry_metric_recorder.h).
User code defines its own metrics through ``util/metrics.py``; the
framework's OWN hot paths (serve routing, the LLM engine, the train
controller, the data executor) record through this module instead, so a
single ``GET /metrics`` scrape or ``export_otlp_json`` carries both.

Three pieces:

* ``CATALOG`` — every built-in metric, named ``ray_tpu_<subsystem>_<what>``,
  with type/description/tags declared in ONE place.  Instrumentation sites
  call ``counter(name)`` / ``gauge(name)`` / ``histogram(name)``, which
  lazily instantiate against the catalog — a typo'd or undeclared name
  raises instead of silently minting a new series
  (tests/test_telemetry_catalog.py locks the naming scheme down).
* ``profile_span(name, category)`` — the span recorder feeding the
  chrome-trace timeline buffer (``_private/events.py``) and, at
  shutdown, ``<session>/trace/spans.jsonl``.  On the head it is a direct
  buffer append; in a worker it is a deque append that the metrics
  flusher ships in batches (no frame per span — safe on per-decode-step
  hot paths); with no runtime at all it is a no-op, so library code (the
  inference engine driven without a cluster) can stay instrumented
  unconditionally.
  Where jax is loaded a span is also a ``TraceAnnotation``, so a profiler
  session shows it on the device trace's clock.  ``stalls(spans)`` reads a
  finished run's spans for the step periods that ran long and what each
  coincided with; the head leaves its result as ``trace/stalls.json``.
* ``GoodputTracker`` — partitions a training run's wall time into
  productive-step vs init/checkpoint/restart/idle (MegaScale-style
  goodput accounting) and exposes ``ray_tpu_train_goodput_ratio``.
"""

from __future__ import annotations

import itertools
import os
import statistics
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Optional

from . import metrics as _metrics

# Bucket sets tuned per family: latencies are sub-second-centric; batch
# sizes / step times are coarser.
_LATENCY_BUCKETS = [0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5,
                    1.0, 2.5, 5.0, 10.0, 30.0, 60.0]
_SIZE_BUCKETS = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0]
_STEP_BUCKETS = [0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0,
                 120.0, 300.0, 600.0]

#: name -> {"type", "description", "tag_keys", "boundaries"?}
CATALOG: Dict[str, Dict[str, Any]] = {
    # -- serve -------------------------------------------------------------
    "ray_tpu_serve_requests_total": {
        "type": "counter", "tag_keys": ("deployment",),
        "description": "Requests routed to a deployment replica."},
    "ray_tpu_serve_request_errors_total": {
        "type": "counter", "tag_keys": ("deployment",),
        "description": "Requests that raised at the ingress/handle layer."},
    "ray_tpu_serve_request_latency_seconds": {
        "type": "histogram", "tag_keys": ("deployment",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "End-to-end handle request latency (route -> "
                       "result materialized)."},
    "ray_tpu_serve_queue_wait_seconds": {
        "type": "histogram", "tag_keys": ("method",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Time a @serve.batch item waited in the queue "
                       "before its batch started executing."},
    "ray_tpu_serve_batch_size": {
        "type": "histogram", "tag_keys": ("method",),
        "boundaries": _SIZE_BUCKETS,
        "description": "Items per executed @serve.batch batch."},
    "ray_tpu_serve_replicas": {
        "type": "gauge", "tag_keys": ("deployment",),
        "description": "Live replica count per deployment (controller "
                       "view)."},
    "ray_tpu_serve_ongoing_requests": {
        "type": "gauge", "tag_keys": ("deployment",),
        "description": "This process's in-flight requests per deployment "
                       "(router view)."},
    "ray_tpu_serve_shed_total": {
        "type": "counter", "tag_keys": ("deployment",),
        "description": "Handle-path requests rejected by the "
                       "max_queued_requests admission bound (retriable "
                       "OverloadError instead of unbounded queueing)."},
    # -- serve: decode fleet (ray_tpu.llm.fleet) ---------------------------
    "ray_tpu_serve_replica_count": {
        "type": "gauge", "tag_keys": ("fleet",),
        "description": "Accepting decode replicas in a serving fleet "
                       "(FleetServer view; draining/dead excluded)."},
    "ray_tpu_serve_prefix_hit_total": {
        "type": "counter", "tag_keys": ("outcome",),
        "description": "Fleet routing outcomes per dispatched request: "
                       "full (exact prompt cached, prefill skipped), "
                       "partial (prefix overlap steered placement), "
                       "miss (load-only placement)."},
    "ray_tpu_serve_rebalance_total": {
        "type": "counter", "tag_keys": (),
        "description": "Requests whose prefix affinity was overridden "
                       "by the load-imbalance watermark (routed by load "
                       "instead of cache locality)."},
    "ray_tpu_serve_replica_scale_total": {
        "type": "counter", "tag_keys": ("direction",),
        "description": "Fleet replica scale actions (up = spawn/"
                       "backfill, down = drain-then-remove), autoscaler "
                       "or manual."},
    # -- llm ---------------------------------------------------------------
    "ray_tpu_llm_ttft_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Time to first token: request add -> first output "
                       "token sampled (includes queueing + prefill)."},
    "ray_tpu_llm_queue_wait_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Queue wait: request add -> first admission to a "
                       "slot (Request.t_admit - t_submit); the rest of "
                       "TTFT is prefill."},
    "ray_tpu_llm_stream_polls_total": {
        "type": "counter", "tag_keys": (),
        "description": "10 ms wait rounds of LLMServer.stream, summed per "
                       "finished stream: how much polling ran beside the "
                       "engine's loop."},
    "ray_tpu_llm_decode_token_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Per-token decode latency (batched step wall time; "
                       "chunked steps attribute wall/steps per token)."},
    "ray_tpu_llm_tokens_total": {
        "type": "counter", "tag_keys": ("kind",),
        "description": "Tokens processed by the engine "
                       "(kind=prompt|decode)."},
    "ray_tpu_llm_kv_page_occupancy": {
        "type": "gauge", "tag_keys": (),
        "description": "Fraction of KV-cache pages allocated (0..1)."},
    "ray_tpu_llm_active_slots": {
        "type": "gauge", "tag_keys": (),
        "description": "Decode slots with a running request."},
    "ray_tpu_llm_requests_finished_total": {
        "type": "counter", "tag_keys": ("reason",),
        "description": "Engine requests finished, by finish_reason "
                       "(stop|length|prompt_too_long|"
                       "kv_capacity_exceeded|cancelled)."},
    "ray_tpu_llm_preemptions_total": {
        "type": "counter", "tag_keys": (),
        "description": "Requests evicted mid-flight (cancel/timeout "
                       "releasing an occupied slot)."},
    "ray_tpu_llm_waiting_requests": {
        "type": "gauge", "tag_keys": (),
        "description": "Requests queued for admission (KV/slot "
                       "backpressure depth)."},
    "ray_tpu_llm_admission_queue_depth": {
        "type": "gauge", "tag_keys": ("class",),
        "description": "Requests held in the SLO router's bounded "
                       "admission queue, per request class (disagg "
                       "router; ahead of engine admission)."},
    "ray_tpu_llm_shed_total": {
        "type": "counter", "tag_keys": ("reason",),
        "description": "Requests shed by SLO-aware admission control "
                       "(reason=queue_full|class_budget|backpressure|"
                       "deadline).  Shedding is a retriable overload "
                       "error, never a silent timeout."},
    "ray_tpu_llm_kv_transfer_bytes_total": {
        "type": "counter", "tag_keys": (),
        "description": "KV-cache bytes handed off from prefill to "
                       "decode workers (disagg page-blob transfers)."},
    "ray_tpu_llm_kv_transfer_seconds": {
        "type": "histogram", "tag_keys": ("op",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Prefill->decode KV handoff latency "
                       "(op=export|import: object-store publish / "
                       "decode-side page scatter)."},
    "ray_tpu_llm_prefill_chunks_total": {
        "type": "counter", "tag_keys": (),
        "description": "Chunked-prefill chunks executed (single-engine "
                       "disagg-off fallback: long prompts sliced across "
                       "decode steps)."},
    # -- train -------------------------------------------------------------
    "ray_tpu_train_step_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _STEP_BUCKETS,
        "description": "Wall time between consecutive rank-0 "
                       "train.report() calls (one reporting step)."},
    "ray_tpu_train_tokens_total": {
        "type": "counter", "tag_keys": (),
        "description": "Training tokens, from report() metrics carrying "
                       "a tokens/num_tokens/tokens_per_step key."},
    "ray_tpu_train_reports_total": {
        "type": "counter", "tag_keys": (),
        "description": "train.report() calls across all ranks."},
    # Expert layers' loads, from report() metrics carrying a train step's
    # moe_* keys (parallel.spmd: models with a sigmoid-routed dropless MoE).
    "ray_tpu_moe_held_assignments": {
        "type": "gauge", "tag_keys": (),
        "description": "Assignments to the experts held here in the last "
                       "reported step (layer-mean)."},
    "ray_tpu_moe_load_max_over_mean": {
        "type": "gauge", "tag_keys": (),
        "description": "Held experts' largest load over their mean load "
                       "in the last reported step (layer-mean)."},
    "ray_tpu_moe_dropped_total": {
        "type": "counter", "tag_keys": (),
        "description": "Assignments to held experts that were not "
                       "computed (0: the dispatch is dropless)."},
    "ray_tpu_moe_sliced_calls_total": {
        "type": "counter", "tag_keys": (),
        "description": "Expert-layer calls that took the dropless buffer "
                       "in slices, their held assignments passing its "
                       "rows (0: every call went through it at once)."},
    # A looped stack's passes, from report() metrics carrying a train step's
    # loop_* keys (parallel.spmd: models whose loss reports them).
    "ray_tpu_train_loop_loss": {
        "type": "gauge", "tag_keys": ("pass",),
        "description": "Masked mean next-token loss of each pass over a "
                       "looped stack in the last reported step."},
    "ray_tpu_train_loop_exit_share": {
        "type": "gauge", "tag_keys": ("pass",),
        "description": "Mean share of the exit distribution that each pass "
                       "of a looped stack took in the last reported step."},
    "ray_tpu_train_loop_exit_entropy": {
        "type": "gauge", "tag_keys": (),
        "description": "Mean entropy (nats) of a looped stack's exit "
                       "distribution in the last reported step."},
    "ray_tpu_train_head_loss": {
        "type": "gauge", "tag_keys": ("head",),
        "description": "Masked mean cross-entropy of each output head of a "
                       "model that predicts several tokens a position, in "
                       "the last reported step (head 0 predicts the next)."},
    "ray_tpu_lm_mtp_loss": {
        "type": "gauge", "tag_keys": (),
        "description": "Masked mean cross-entropy of a prediction module "
                       "(token t + 2 from position t) in the last reported "
                       "step, beside the main loss it is added to."},
    "ray_tpu_hc_sinkhorn_residual": {
        "type": "gauge", "tag_keys": (),
        "description": "Largest |row sum - 1| or |column sum - 1| of a "
                       "hyper-connection's lane-to-lane map in the last "
                       "reported step, over every sublayer and token: how "
                       "far Sinkhorn's iterations left it from doubly "
                       "stochastic."},
    "ray_tpu_ssm_chunk_carry": {
        "type": "gauge", "tag_keys": (),
        "description": "Mean over a state-space model's mixers, chunks and "
                       "heads of exp(sum of dt * A over a chunk) in the last "
                       "reported step: the share of a recurrent state that "
                       "a whole chunk hands on.  It moves if someone "
                       "changes the chunk, drops the carry or starts A_log "
                       "elsewhere."},
    "ray_tpu_ssm_chunks_with_boundary": {
        "type": "gauge", "tag_keys": (),
        "description": "Chunks of the last reported step's scans, over all "
                       "mixers and rows, in which a document starts "
                       "(a batch with segment_ids): they hand no state on, "
                       "and ray_tpu_ssm_chunk_carry is the mean over the "
                       "others."},
    "ray_tpu_pack_documents_a_row": {
        "type": "gauge", "tag_keys": (),
        "description": "Mean documents a row of the last reported step's "
                       "batch (runs of equal segment_ids): how a packed "
                       "row was filled."},
    "ray_tpu_pack_pairs_share": {
        "type": "gauge", "tag_keys": (),
        "description": "Sum over the last reported step's documents of "
                       "length squared, over rows x S squared: the share "
                       "of the causal square that attention inside "
                       "documents keeps (1: a row is one document), and so "
                       "what skipping blocks between documents can save."},
    "ray_tpu_kda_chunk_carry": {
        "type": "gauge", "tag_keys": (),
        "description": "Mean over a hybrid model's Kimi-Delta-Attention "
                       "layers, chunks, heads and channels of exp(sum of "
                       "the log-decay g over a chunk) in the last reported "
                       "step: the share of a delta-rule state's row that a "
                       "whole chunk hands on.  It moves if someone changes "
                       "the chunk, the gate's bound or where A_log and "
                       "dt_bias start."},
    "ray_tpu_gdla_lambda_mean": {
        "type": "gauge", "tag_keys": (),
        "description": "Mean over tokens, signal heads and layers of "
                       "sigmoid(lambda), the weight a differential "
                       "attention's noise head is subtracted with "
                       "(models/motif.py), in the last reported step: 0 "
                       "or 1 says the pair is dead."},
    "ray_tpu_attn_sink_mass_mean": {
        "type": "gauge", "tag_keys": (),
        "description": "Mean over a model's window layers, query heads and "
                       "positions of exp(b_h - lse_t), the share of a "
                       "row's softmax mass that the learned attention sink "
                       "took (models/mimo_v2.py on ops/attention's sink), "
                       "in the last reported step: near 0 the sink does "
                       "nothing, near 1 the layers attend to nothing."},
    "ray_tpu_train_checkpoint_seconds": {
        "type": "histogram", "tag_keys": ("op",),
        "boundaries": _STEP_BUCKETS,
        "description": "Checkpoint pytree save/restore duration "
                       "(op=save|restore)."},
    "ray_tpu_train_worker_restarts_total": {
        "type": "counter", "tag_keys": (),
        "description": "Train workers torn down and restarted after a "
                       "failure."},
    "ray_tpu_train_urgent_ckpt_total": {
        "type": "counter", "tag_keys": (),
        "description": "Urgent checkpoint flushes triggered by a drain "
                       "notice (async writer drained + emergency "
                       "replicas pushed before the node dies)."},
    "ray_tpu_train_restart_backoff_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _STEP_BUCKETS,
        "description": "Backoff slept between group re-formations after "
                       "a failure (bounded exponential; resets once an "
                       "incarnation proves stable)."},
    "ray_tpu_train_goodput_ratio": {
        "type": "gauge", "tag_keys": (),
        "description": "Productive-step wall time over total run wall "
                       "time (goodput accounting; see GoodputTracker)."},
    "ray_tpu_train_step_phase_seconds": {
        "type": "histogram", "tag_keys": ("phase",),
        "boundaries": _STEP_BUCKETS,
        "description": "Per-step device-time attribution: seconds each "
                       "reporting step spent in a declared phase "
                       "(data_wait|h2d|compute|collective|ckpt_block|"
                       "other; ray_tpu.train.step_phase fences with "
                       "block_until_ready at phase boundaries so async "
                       "dispatch cannot smear compute into the next "
                       "phase)."},
    "ray_tpu_train_hbm_used_bytes": {
        "type": "gauge", "tag_keys": ("device",),
        "description": "Per-device accelerator memory in use (jax "
                       "memory_stats; absent on backends that do not "
                       "report it).  Creeping HBM is the classic silent "
                       "step-time killer."},
    "ray_tpu_train_hbm_peak_bytes": {
        "type": "gauge", "tag_keys": ("device",),
        "description": "Per-device peak accelerator memory since process "
                       "start (jax memory_stats peak_bytes_in_use)."},
    "ray_tpu_train_straggler_total": {
        "type": "counter", "tag_keys": (),
        "description": "Watchdog straggler verdicts: a rank's step time "
                       "exceeded the configured multiple of the "
                       "across-rank median (one per incident)."},
    "ray_tpu_train_hang_total": {
        "type": "counter", "tag_keys": (),
        "description": "Watchdog hang verdicts: a rank produced no "
                       "report within the hang deadline (one per "
                       "incident)."},
    "ray_tpu_train_mesh_axis_size": {
        "type": "gauge", "tag_keys": ("axis",),
        "description": "Live SPMD mesh axis sizes of the current train "
                       "worker group (axis=dp|fsdp|tp|sp|ep|pp; "
                       "refreshed at every group (re)formation — an "
                       "elastic resize shows up as the shape changing)."},
    "ray_tpu_train_param_shard_bytes": {
        "type": "gauge", "tag_keys": (),
        "description": "This process's addressable parameter-shard "
                       "bytes after train.shard() / a mesh restore "
                       "(~ total/N when parameters are truly sharded; "
                       "~ total means the model is replicated)."},
    "ray_tpu_train_upsize_total": {
        "type": "counter", "tag_keys": (),
        "description": "Elastic upsizes: the worker group tore down at a "
                       "checkpoint boundary and re-formed LARGER because "
                       "joined capacity fit a bigger mesh-tileable world "
                       "(the add_node/pre-buy-arrival reaction; "
                       "downsizes ride the drain/failure paths)."},
    "ray_tpu_train_mesh_reshapes_total": {
        "type": "counter", "tag_keys": (),
        "description": "Mesh reshape events: a worker group re-formed "
                       "at a different mesh shape than its predecessor, "
                       "or a checkpoint restored onto a mesh other than "
                       "the one that saved it (resharding restore)."},
    # -- ckpt (distributed checkpointing subsystem) ------------------------
    "ray_tpu_ckpt_save_blocking_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _STEP_BUCKETS,
        "description": "Train-thread time a save actually stole: the "
                       "device->host snapshot plus any write-queue "
                       "backpressure wait (async saves) or the full "
                       "serialize+write (sync saves)."},
    "ray_tpu_ckpt_write_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _STEP_BUCKETS,
        "description": "Background shard serialize+publish duration "
                       "(tmp-file + atomic rename, off the step path)."},
    "ray_tpu_ckpt_bytes_total": {
        "type": "counter", "tag_keys": (),
        "description": "Checkpoint shard bytes published by this "
                       "process."},
    "ray_tpu_ckpt_inflight": {
        "type": "gauge", "tag_keys": (),
        "description": "Async checkpoint saves queued or writing "
                       "(bounded by CheckpointConfig.max_inflight; "
                       "pinned at the bound = the saver outruns the "
                       "disk and backpressure is biting)."},
    "ray_tpu_ckpt_restore_seconds": {
        "type": "histogram", "tag_keys": ("source",),
        "boundaries": _STEP_BUCKETS,
        "description": "Checkpoint restore duration, by shard source "
                       "(source=disk|replica)."},
    "ray_tpu_ckpt_replica_restores_total": {
        "type": "counter", "tag_keys": (),
        "description": "Restores that used in-memory emergency replica "
                       "shards instead of (or ahead of) cold storage."},
    # -- node (drain / preemption lifecycle) -------------------------------
    "ray_tpu_node_preempted_total": {
        "type": "counter", "tag_keys": (),
        "description": "Nodes the cloud took away while they were "
                       "RUNNING/JOINED (spot reclaim, maintenance) — "
                       "every preemption is counted, graceful or not."},
    "ray_tpu_node_drain_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _STEP_BUCKETS,
        "description": "Drain-notice-to-node-death duration: how much of "
                       "the advertised deadline the cluster actually got "
                       "to evacuate work."},
    "ray_tpu_node_draining": {
        "type": "gauge", "tag_keys": (),
        "description": "Nodes currently draining (unschedulable for new "
                       "leases, waiting for work to evacuate)."},
    # -- autoscaler (goodput-driven scaling + pre-buy) ---------------------
    "ray_tpu_autoscaler_prebuy_total": {
        "type": "counter", "tag_keys": (),
        "description": "Replacement capacity bought at preemption-NOTICE "
                       "time (before the victim's deadline, not after its "
                       "death) so the post-drain reform can upsize back "
                       "instead of limping at n-1."},
    "ray_tpu_autoscaler_goodput_scale_events_total": {
        "type": "counter", "tag_keys": ("direction",),
        "description": "Scaling actions taken by the goodput-driven "
                       "policy (direction=up: capacity bought after the "
                       "goodput ratio sagged below the configured floor "
                       "for the sustain window; direction=down: surplus "
                       "drained back once goodput recovered and nodes "
                       "sat idle)."},
    "ray_tpu_autoscaler_pending_prebuys": {
        "type": "gauge", "tag_keys": (),
        "description": "Pre-bought replacement nodes launched but not "
                       "yet joined (the `ray-tpu status` pre-buy line; "
                       "pinned at max_pending_prebuys = a notice storm "
                       "is being rate-limited)."},
    # -- slice (multi-slice reservation lifecycle) -------------------------
    "ray_tpu_slice_drains_total": {
        "type": "counter", "tag_keys": (),
        "description": "Per-slice drains: one slice of a multi-slice "
                       "SlicePlacementGroup fenced + evacuated while the "
                       "other slices' committed bundles stay untouched."},
    # -- profiler (cluster-wide performance profiling subsystem) -----------
    "ray_tpu_profiler_compile_total": {
        "type": "counter", "tag_keys": ("fn",),
        "description": "XLA compilations attributed to a tracked "
                       "call site (jax.monitoring backend_compile "
                       "events; fn=<site name>)."},
    "ray_tpu_profiler_compile_seconds": {
        "type": "histogram", "tag_keys": ("fn",),
        "boundaries": _STEP_BUCKETS,
        "description": "Seconds spent in XLA backend compilation per "
                       "tracked call site."},
    "ray_tpu_xla_compiles_total": {
        "type": "counter", "tag_keys": ("program",),
        "description": "XLA backend compiles (persistent-cache fetches "
                       "included) by program name; each is also an "
                       "xla_compile span."},
    "ray_tpu_jax_trace_seconds_total": {
        "type": "counter", "tag_keys": ("program",),
        "description": "Seconds jax spent tracing a jitted function to a "
                       "jaxpr, by the function's name; top-level traces "
                       "only (a nested jit's seconds are in its "
                       "parent's).  Each is also a jax_trace span."},
    "ray_tpu_jax_lower_seconds_total": {
        "type": "counter", "tag_keys": ("program",),
        "description": "Seconds jax spent lowering a traced program to "
                       "an MLIR module (every Pallas call's lowering in "
                       "it), by the module's name.  Each is also a "
                       "jax_lower span."},
    "ray_tpu_flash_step_geometry_total": {
        "type": "counter",
        "tag_keys": ("kernel", "block_q", "block_k", "heads_a_step",
                     "scores", "d_qk", "d_v", "d", "rows", "parts",
                     "tiles_a_step", "shares"),
        "description": "Flash-attention kernels traced, by the geometry "
                       "of a grid step that ops/attention._tiles chose "
                       "from the call's shapes (kernel: flash_fwd, and "
                       "flash_dq with flash_dkv, or flash_bwd alone "
                       "where the backward made one pass over the "
                       "tiles; flash_seg_fwd and so on of a call with "
                       "segment_ids, a packed row whose queries see their "
                       "own document's keys alone; scores: qk or kq; "
                       "tiles_a_step only where a grid step fetches a "
                       "major block of that many block_q x block_k tiles "
                       "of the streamed side and walks them: a head size "
                       "over 128, latent attention's, and the one pass "
                       "under a group; shares "
                       "only on a flash_dkv or flash_bwd whose grid rows "
                       "hold fewer query heads than a key head has: that "
                       "many rows' dk / dv leave in float32 and are summed "
                       "outside, which is how the one pass serves a group; "
                       "d_qk "
                       "and d_v only where a call's values are not as "
                       "wide as its keys; d only where the one head size "
                       "is not 128; rows=vo only where the kernel "
                       "addresses v and the result as [B, S, H * D], the "
                       "projections' layout; a kernel without the tag "
                       "took them head-major; parts=128+64 with "
                       "rows=qkvo only on a call that hands q and k in "
                       "the two parts its projections write, every "
                       "128-wide operand as rows: latent attention's)."},
    "ray_tpu_remat_kept_total": {
        "type": "counter",
        "tag_keys": ("names", "kept", "calls", "bytes", "budget"),
        "description": "Stacks traced whose layers run under the remat "
                       "(models/_lm.flash_keep), by what the rule saw and "
                       "did: the names it may keep past a layer's "
                       "recomputation (the flash kernels' out and lse), "
                       "kept=true where it kept them, so that the forward "
                       "kernel runs once a layer, else false; the "
                       "attention calls of the step under the remat, the "
                       "bytes their results take on a device together, "
                       "and the budget they were held to, a sixteenth of "
                       "the device's bytes_limit (None where the backend "
                       "states no limit: the CPU keeps nothing)."},
    "ray_tpu_gmm_tile_geometry_total": {
        "type": "counter",
        "tag_keys": ("kind", "tm", "tk", "tn", "rows_a_group"),
        "description": "Grouped-product kernels traced "
                       "(ops/moe.grouped_matmul), by the tiles that "
                       "ops/moe._gmm_tiles chose from the call's shapes: "
                       "kind fwd (gmm), dlhs (gmm on transposed weights, "
                       "the rows' gradient) or tgmm (the weights' "
                       "gradient); rows_a_group the rows a group is "
                       "expected to hold."},
    "ray_tpu_moe_buffer_total": {
        "type": "counter",
        "tag_keys": ("rows", "tiers", "held", "routed", "tokens", "slots"),
        "description": "Expert-layer calls traced (ops/moe.dropless_experts), "
                       "by the buffer each took: its rows, the tiers the "
                       "worst case of tokens x slots assignments was divided "
                       "by (ops/moe.buffer_tiers: twice the expected load "
                       "where held of the routed experts are held, never "
                       "under 4) and so the slices of the fallback that "
                       "ray_tpu_moe_sliced_calls_total counts."},
    "ray_tpu_mla_call_geometry_total": {
        "type": "counter",
        "tag_keys": ("heads", "dn", "dr", "dv", "q_lora", "rows", "seq",
                     "kv_heads", "noise_heads", "window"),
        "description": "Calls of latent attention traced "
                       "(models/xing4._mla, which models/deepseek_v3.py, "
                       "models/bailing_hybrid.py and models/motif.py run "
                       "too), by what the call is: its heads, a "
                       "head's channels without position (dn), rotary "
                       "(dr) and of values (dv), the query bottleneck's "
                       "rank (q_lora: none where queries come straight "
                       "from the hidden state), and the rows and tokens "
                       "a row of the call; where the model has fewer key "
                       "heads than query heads, noise heads whose result "
                       "is subtracted or a window on some layers "
                       "(models/motif.py), kv_heads, noise_heads and "
                       "window say so, and are absent otherwise."},
    "ray_tpu_kda_call_geometry_total": {
        "type": "counter",
        "tag_keys": ("heads", "dk", "dv", "chunk", "rows", "seq", "path"),
        "description": "Calls of the chunked gated delta rule traced "
                       "(ops/kda.kda, what models/bailing_hybrid.py's KDA "
                       "layers run), by what the call is: its heads, a "
                       "head's key and value channels, the chunk's tokens, "
                       "the rows and tokens a row of the call, and the "
                       "path it takes (kernel: the Pallas pair, forward "
                       "and backward; xla: the jnp form)."},
    "ray_tpu_kda_pass_path_total": {
        "type": "counter",
        "tag_keys": ("pass", "path", "heads", "d", "rows", "seq"),
        "description": "Calls traced of a KDA layer's elementwise passes "
                       "round its recurrence (ops/kda.py), by the pass "
                       "(inputs: the convolution, the unit norm and the "
                       "decay between the projections and the scan, "
                       "kda_mixer; norm: the head norm times the output "
                       "gate after it, gated_head_norm), the path it takes "
                       "(kernel: one Pallas pass each way on the flat "
                       "arrays the scan's kernels read and write; xla: "
                       "jnp), the heads, a head's channels, and the rows "
                       "and tokens a row of the call."},
    "ray_tpu_moe_groups_kept_total": {
        "type": "counter", "tag_keys": ("n_group", "topk_group"),
        "description": "Forward passes traced of a model whose routers "
                       "limit their choice to groups "
                       "(models/bailing_hybrid.py on "
                       "ops/moe.sigmoid_routing): the groups the experts "
                       "form and the groups a token keeps."},
    "ray_tpu_moe_rows_path_total": {
        "type": "counter",
        "tag_keys": ("path", "op", "tokens", "slots", "lanes"),
        "description": "Sums over a token's rows of the experts' buffer "
                       "traced (ops/moe._sum_rows), by the path taken "
                       "(kernel: the Pallas kernel that copies the runs of "
                       "rows in use; xla: the jnp gather over all slots), "
                       "the mover (op: tokens_from_rows, or "
                       "rows_of_tokens_bwd) and the call's tokens, slots a "
                       "token (k) and lanes a row."},
    "ray_tpu_eva_step_geometry_total": {
        "type": "counter",
        "tag_keys": ("kernel", "block_q", "block_k", "block_s",
                     "summary_steps", "token_steps"),
        "description": "EVA kernels traced (ops/eva.py), by a grid step's "
                       "blocks (q rows, token keys, summary keys) and the "
                       "steps a head's walk takes on each operand: the "
                       "earlier windows' summaries, the window's tokens."},
    "ray_tpu_rope_path_total": {
        "type": "counter", "tag_keys": ("path", "rows", "heads"),
        "description": "Calls of ops/rope.rotate_heads traced, by the path "
                       "taken: kernel (rows and heads of a grid step's "
                       "tile) or xla (apply_rope behind a transpose; the "
                       "call's sequence length and heads)."},
    "ray_tpu_hc_path_total": {
        "type": "counter", "tag_keys": ("path", "lanes"),
        "description": "Sublayers' hyper-connections traced "
                       "(ops/hyper.collect), by the path their passes over "
                       "the stream take (kernel: Pallas, forward and "
                       "backward; xla: the jnp forms) and its lanes."},
    "ray_tpu_ssm_path_total": {
        "type": "counter",
        "tag_keys": ("path", "chunk", "segments", "group_channels"),
        "description": "Chunked state-space scans traced "
                       "(ops/ssm.ssd_scan), by the path they take (kernel: "
                       "the Pallas pair, forward and backward; xla: the jnp "
                       "form), the chunk's tokens, whether the call had "
                       "segment ids (yes: a state starts anew at every "
                       "document) and the channels of a state group (over "
                       "512 the kernels take it in slices of its heads)."},
    "ray_tpu_gated_conv_path_total": {
        "type": "counter", "tag_keys": ("path", "taps"),
        "description": "Double-gated short convolutions traced "
                       "(ops/ssm.gated_short_conv), by the form they take "
                       "(kernel: the Pallas pair, forward and backward; "
                       "xla: jnp with the backward written out) and the "
                       "taps."},
    "ray_tpu_ssm_conv_path_total": {
        "type": "counter", "tag_keys": ("path", "taps", "segments"),
        "description": "A mixer's causal convolutions traced "
                       "(ops/ssm.causal_conv), by the form they take "
                       "(kernel: the Pallas pair, forward and backward, on "
                       "the columns the projection wrote; xla: jnp with "
                       "the backward written out), the taps, and whether "
                       "the call had segment ids (yes: a tap reads zero on "
                       "another document's token)."},
    "ray_tpu_norm_path_total": {
        "type": "counter", "tag_keys": ("path", "rows"),
        "description": "Calls of ops/norms.rms_norm traced, by the path "
                       "taken: row_major (a float32 input on a TPU, its "
                       "layout pinned) or xla (the layout left to the "
                       "compiler); rows: the call's rows."},
    "ray_tpu_compile_cache_hits_total": {
        "type": "counter", "tag_keys": (),
        "description": "Programs fetched from the persistent compile "
                       "cache instead of compiled (where this jax "
                       "reports it)."},
    "ray_tpu_compile_cache_writes_total": {
        "type": "counter", "tag_keys": (),
        "description": "Programs compiled here and written to the "
                       "persistent compile cache (a compile long and "
                       "large enough to be stored): above 0, this "
                       "process paid cold compiles."},
    "ray_tpu_profiler_recompiles_total": {
        "type": "counter", "tag_keys": ("fn",),
        "description": "POST-WARMUP recompilations: a tracked site that "
                       "had reached steady state compiled again (shape/"
                       "dtype churn — the #1 silent TPU step-time "
                       "regression).  Each also logs a once-per-site "
                       "warning naming the offending shapes."},
    "ray_tpu_profiler_captures_total": {
        "type": "counter", "tag_keys": (),
        "description": "On-demand cluster profile captures served "
                       "(`ray-tpu profile` / POST /api/profile / "
                       "flight-recorder auto-attach)."},
    # -- sched (control-plane telescope: scheduler decision tracing) -------
    "ray_tpu_sched_decisions_total": {
        "type": "counter", "tag_keys": ("kind",),
        "description": "Scheduler decisions by kind (inline|loop|"
                       "exchange|pipeline|reject|infeasible|pg_commit|"
                       "pg_reject).  Flushed from the decision ring's "
                       "plain-int tallies by the rate-limited publisher "
                       "— never a counter op on the placement hot "
                       "path."},
    "ray_tpu_sched_stage_wait_seconds": {
        "type": "histogram", "tag_keys": ("stage",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Task lifecycle stage waits (stage=deps|queue|"
                       "dispatch|startup|run), derived monotonic-minus-"
                       "monotonic from the TaskEvent ring's per-"
                       "transition stamps.  A fat 'queue' tail means "
                       "placement is the bottleneck; a fat 'dispatch' "
                       "tail means arg resolution / the worker pipe "
                       "is."},
    "ray_tpu_sched_placement_attempts": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _SIZE_BUCKETS,
        "description": "Placement rounds a task needed before it was "
                       "booked onto a node (1 = placed on first look; "
                       "the tail counts retry pressure from full/"
                       "draining clusters)."},
    "ray_tpu_sched_pg_commit_seconds": {
        "type": "histogram", "tag_keys": (),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Placement-group two-phase commit latency: "
                       "register -> every bundle committed (includes "
                       "the PENDING retry window while capacity is "
                       "awaited; node-death re-plans re-enter here)."},
    "ray_tpu_sched_queue_depth": {
        "type": "gauge", "tag_keys": ("queue",),
        "description": "Scheduler queue depths (queue=ready|"
                       "waiting_deps|infeasible|pending_pgs), refreshed "
                       "~1/s by the scheduler loop's metrics "
                       "publisher."},
    # -- internal ----------------------------------------------------------
    "ray_tpu_internal_swallowed_errors_total": {
        "type": "counter", "tag_keys": ("where",),
        "description": "Control-plane exceptions intentionally swallowed "
                       "(best-effort paths), by call site.  A climbing "
                       "series names the subsystem eating errors."},
    "ray_tpu_lock_wait_seconds": {
        "type": "histogram", "tag_keys": ("site",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Sampled lock-acquire wait by creation site "
                       "(~1/64th of releases), from the opt-in "
                       "contention profiler (RAY_TPU_LOCK_PROFILE=1 / "
                       "RAY_TPU_DEBUG_LOCKS=1).  A fat tail names a "
                       "lock threads queue on."},
    "ray_tpu_lock_hold_seconds": {
        "type": "histogram", "tag_keys": ("site",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Sampled lock hold time by creation site "
                       "(~1/64th of releases), from the opt-in "
                       "contention profiler.  Long holds on a "
                       "contended site are the thing to shrink first "
                       "(see ray-tpu lint --lock-report)."},
    # -- jax (host-sync tripwire) ------------------------------------------
    "ray_tpu_jax_host_sync_total": {
        "type": "counter", "tag_keys": ("site",),
        "description": "Implicit jax device->host syncs by call site "
                       "(float()/.item()/np.asarray() on device arrays), "
                       "from the opt-in tripwire (RAY_TPU_SYNC_DEBUG=1).  "
                       "Published in batches of 64 per site; a hot site "
                       "in a step/decode loop is an RT502 to fix."},
    "ray_tpu_jax_host_sync_seconds": {
        "type": "histogram", "tag_keys": ("site",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Sampled blocked-time of implicit device->host "
                       "syncs by call site (~1/64th of syncs), from the "
                       "opt-in tripwire.  The histogram shows how long "
                       "the host thread stalls waiting on the device "
                       "(see ray-tpu lint --sync-report)."},
    # -- metricsview (time-series backplane) -------------------------------
    "ray_tpu_metricsview_points_total": {
        "type": "counter", "tag_keys": (),
        "description": "Points appended to the head's metrics "
                       "time-series store (post-downsample: a burst of "
                       "flushes inside one interval stores one "
                       "point)."},
    "ray_tpu_metricsview_dropped_total": {
        "type": "counter", "tag_keys": (),
        "description": "Store points lost to ring eviction plus series "
                       "refused over the metricsview_max_series cap — "
                       "a climbing rate means history is shorter than "
                       "the configured retention."},
    # -- alerts (SLO burn-rate engine) -------------------------------------
    "ray_tpu_alerts_firing": {
        "type": "gauge", "tag_keys": (),
        "description": "SLO objectives currently in the firing state "
                       "(fast AND slow burn-rate windows breached)."},
    "ray_tpu_alerts_transitions_total": {
        "type": "counter", "tag_keys": ("state",),
        "description": "Alert state-machine transitions by destination "
                       "state (state=pending|firing|resolved|ok)."},
    # -- data --------------------------------------------------------------
    "ray_tpu_data_block_seconds": {
        "type": "histogram", "tag_keys": ("operator",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Per-block processing time in the streaming "
                       "executor (operator=map|reduce)."},
    "ray_tpu_data_rows_total": {
        "type": "counter", "tag_keys": ("operator",),
        "description": "Rows produced by data-pipeline operators."},
    "ray_tpu_data_blocks_total": {
        "type": "counter", "tag_keys": ("operator",),
        "description": "Blocks processed by data-pipeline operators."},
    # -- store (object store + transfer data plane; see storeview/) --------
    "ray_tpu_store_used_bytes": {
        "type": "gauge", "tag_keys": ("node",),
        "description": "Object-store bytes in use per node (arena/shm "
                       "occupancy; spilled objects excluded)."},
    "ray_tpu_store_capacity_bytes": {
        "type": "gauge", "tag_keys": ("node",),
        "description": "Configured object-store capacity per node."},
    "ray_tpu_store_pinned_bytes": {
        "type": "gauge", "tag_keys": ("node",),
        "description": "Bytes held by reader-pinned objects per node "
                       "(never evictable/spillable while pinned)."},
    "ray_tpu_store_spilled_bytes": {
        "type": "gauge", "tag_keys": ("node",),
        "description": "Bytes currently spilled to disk per node."},
    "ray_tpu_store_objects": {
        "type": "gauge", "tag_keys": ("node",),
        "description": "Objects tracked by the store per node (in "
                       "memory + spilled)."},
    "ray_tpu_store_ops_total": {
        "type": "counter", "tag_keys": ("op",),
        "description": "Store operations, from the lifecycle ring's "
                       "per-kind tallies (op=create|seal|get|pin|unpin|"
                       "delete), published by the head's metrics-flush "
                       "piggyback."},
    "ray_tpu_store_spill_ops_total": {
        "type": "counter", "tag_keys": ("op",),
        "description": "Memory-pressure events "
                       "(op=spill|restore|evict)."},
    "ray_tpu_store_spill_reclaimed_bytes_total": {
        "type": "counter", "tag_keys": (),
        "description": "Orphaned spill-file bytes deleted by the "
                       "boot/shutdown GC sweep (files left by dead "
                       "store processes)."},
    "ray_tpu_store_transfer_bytes_total": {
        "type": "counter", "tag_keys": ("direction",),
        "description": "Cross-node object payload bytes moved by this "
                       "process (direction=push|pull: push = served by "
                       "the local data server, pull = localized from a "
                       "remote node)."},
    "ray_tpu_store_transfer_seconds": {
        "type": "histogram", "tag_keys": ("op",),
        "boundaries": _LATENCY_BUCKETS,
        "description": "Cross-node transfer latency (op=push|pull; pull "
                       "= resolve + fetch + local put of one object)."},
}

_instances_lock = threading.Lock()
_instances: Dict[str, _metrics.Metric] = {}


def _get(name: str, expect_type: str) -> _metrics.Metric:
    spec = CATALOG.get(name)
    if spec is None:
        raise KeyError(f"{name!r} is not in the built-in telemetry catalog")
    if spec["type"] != expect_type:
        raise TypeError(f"{name!r} is a {spec['type']}, not a {expect_type}")
    inst = _instances.get(name)
    if inst is not None:
        return inst
    with _instances_lock:
        inst = _instances.get(name)
        if inst is None:
            if spec["type"] == "counter":
                inst = _metrics.Counter(name, spec["description"],
                                        tag_keys=spec["tag_keys"])
            elif spec["type"] == "gauge":
                inst = _metrics.Gauge(name, spec["description"],
                                      tag_keys=spec["tag_keys"])
            else:
                inst = _metrics.Histogram(name, spec["description"],
                                          boundaries=spec.get("boundaries"),
                                          tag_keys=spec["tag_keys"])
            _instances[name] = inst
    return inst


def counter(name: str) -> _metrics.Counter:
    return _get(name, "counter")  # type: ignore[return-value]


def gauge(name: str) -> _metrics.Gauge:
    return _get(name, "gauge")  # type: ignore[return-value]


def histogram(name: str) -> _metrics.Histogram:
    return _get(name, "histogram")  # type: ignore[return-value]


# Exception-safe record helpers: telemetry is never allowed to fail the
# instrumented path (e.g. a user metric squatting on a catalog name makes
# instantiation raise), so framework call sites use these instead of
# hand-rolling try/except around every counter/gauge/histogram call.

def inc(name: str, value: float = 1.0,
        tags: Optional[Dict[str, str]] = None) -> None:
    try:
        counter(name).inc(value, tags=tags)
    except Exception:
        pass


def observe(name: str, value: float,
            tags: Optional[Dict[str, str]] = None) -> None:
    try:
        histogram(name).observe(value, tags=tags)
    except Exception:
        pass


def observe_many(name: str, values, tags: Optional[Dict[str, str]] = None
                 ) -> None:
    """Batch-observe under one lock (amortized publishers: stage-wait
    folds, the scheduler's attempt-sample flush)."""
    try:
        histogram(name).observe_many(values, tags=tags)
    except Exception:
        pass


def set_gauge(name: str, value: float,
              tags: Optional[Dict[str, str]] = None) -> None:
    try:
        gauge(name).set(value, tags=tags)
    except Exception:
        pass


def note_swallowed(where: str, exc: Optional[BaseException] = None) -> None:
    """Account for an intentionally swallowed control-plane exception.

    The RT202 lint rule forbids bare ``except Exception: pass`` in
    control-plane modules: a swallowed error must at least leave a
    debug-log line and bump ``ray_tpu_internal_swallowed_errors_total``
    so a misbehaving subsystem shows up on the scrape instead of only in
    a postmortem."""
    inc("ray_tpu_internal_swallowed_errors_total", tags={"where": where})
    try:
        import logging
        logging.getLogger("ray_tpu").debug(
            "swallowed error in %s: %r", where, exc)
    except Exception:
        pass


def _reset_for_tests() -> None:
    """Drop cached instances (called from metrics._reset_for_tests: the
    registry they were registered in is being cleared, and a stale cached
    instance would record into an orphaned state dict)."""
    global _goodput_latest
    with _instances_lock:
        _instances.clear()
    _goodput_latest = None


# -- profile spans ---------------------------------------------------------

#: Finished spans of a process that is not the head, until the metrics
#: flusher (``util/metrics.py``: every 2 s, at task end, at worker exit)
#: ships them to the head in ONE frame.  Bounded: the oldest are dropped.
_span_buffer: "deque" = deque(maxlen=65536)

# os.getpid() is a system call on every span (microseconds on a sandboxed
# host): read once, and again in a forked child.
_pid = os.getpid()


def _refresh_pid() -> None:
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_refresh_pid)


def drain_spans() -> list:
    """Take every buffered span (deque pops are atomic: safe against
    threads that append meanwhile)."""
    out = []
    try:
        while True:
            out.append(_span_buffer.popleft())
    except IndexError:
        return out


def _emit_span(name: str, category: str, start_s: float, end_s: float,
               extra: Optional[Dict[str, Any]] = None) -> None:
    """Record one finished span: ``(name, category, start, end, process
    id, thread id, extra)``.

    Head: straight into the timeline buffer.  Any other process: a deque
    append; the metrics flusher ships the batch, so a span on a per-token
    path costs no frame of its own.  No runtime: no-op.
    """
    from ray_tpu._private import runtime as rtmod
    rt = rtmod.current_runtime()
    if rt is None:
        return
    span = (name, category, start_s, end_s, _pid, threading.get_ident(),
            extra)
    try:
        add = getattr(rt, "ctl_add_profile_span", None)
        if add is not None:
            add((span,))
        else:
            _span_buffer.append(span)
            _metrics.note_pending()
    except Exception:
        pass  # telemetry is never allowed to fail the instrumented path


# Per-thread open-span stack: gives nested profile_spans parent linkage
# and lets a parent subtract its children's time (``self_s``), so an
# inner span's duration is never silently attributed to both levels.
_span_tls = threading.local()
_span_seq = itertools.count(1)


def _span_stack() -> list:
    stack = getattr(_span_tls, "stack", None)
    if stack is None:
        stack = _span_tls.stack = []
    return stack


def _span_enter(entry: Dict[str, Any]) -> Dict[str, Any]:
    """Push one open-span frame; returns it annotated with its id and
    its parent's id (None at the top level)."""
    stack = _span_stack()
    entry["span_id"] = next(_span_seq)
    entry["parent_id"] = stack[-1]["span_id"] if stack else None
    entry["child_s"] = 0.0
    stack.append(entry)
    return entry


def _span_exit(entry: Dict[str, Any], dur_s: float) -> Dict[str, Any]:
    """Pop a frame (tolerating mismatched exits), charge the duration to
    the parent's child time, and return linkage extras for the span:
    span_id/parent_id plus ``self_s`` — the duration EXCLUSIVE of nested
    spans, which is what nesting used to misattribute."""
    stack = _span_stack()
    if entry in stack:
        # Normal case pops the top; an out-of-order exit (generator
        # suspension etc.) drops everything above it rather than
        # corrupting later pairings.
        del stack[stack.index(entry):]
    if stack:
        stack[-1]["child_s"] += dur_s
    return {"span_id": entry["span_id"],
            "parent_id": entry["parent_id"],
            "self_s": max(0.0, dur_s - entry["child_s"])}


class profile_span:
    """The span recorder: one context manager for the framework's own hot
    paths and (as ``util.state.profile_span``) for user code.

    No-ops without a runtime and never waits on a reply — safe inside the
    engine decode loop or a bench process that never called
    ``ray_tpu.init()``.

    Re-entrant and nesting-aware: a span opened inside another span is
    linked to its parent (``extra["parent_id"]``) and the parent's
    ``extra["self_s"]`` excludes nested time, so inner durations are
    attributed exactly once.  One instance may be entered recursively
    (per-entry state lives on a stack, not the instance).  ``extra``
    carries what the spans of one request or step share (``request_id``,
    ``step``).

    On the device trace's clock: where jax is already imported (it is
    never imported for this) the span is also a
    ``jax.profiler.TraceAnnotation``: an atomic read while no profiler
    session runs, an event on the trace's host plane beside
    ``PjitFunction`` and ``np.asarray`` while one does, so a gap on the
    device can be given to the program's own span.  ``group=True`` marks
    a span that only holds other spans (a whole loop, a whole step): it
    is recorded like any other, with ``group: true`` in its record so
    that a reader of named time can leave the holders out, but stays off
    the host plane, where a gap belongs to the part and not to the whole.
    """

    __slots__ = ("name", "category", "extra", "group", "_frames")

    def __init__(self, name: str, category: str = "system",
                 extra: Optional[Dict[str, Any]] = None,
                 group: bool = False):
        self.name = name
        self.category = category
        self.extra = extra
        self.group = group
        self._frames: list = []

    def __enter__(self) -> "profile_span":
        # Wall clock positions the span; monotonic measures its length so
        # an NTP step mid-span can't yield a negative/garbage duration.
        entry = _span_enter({"start": time.time(),
                             "start_mono": time.monotonic()})
        self._frames.append(entry)
        jax = None if self.group else sys.modules.get("jax")
        if jax is not None:
            try:
                note = jax.profiler.TraceAnnotation(
                    self.name, span_id=entry["span_id"],
                    **(self.extra or {}))
                note.__enter__()
                entry["note"] = note
            except Exception:
                pass  # a half-imported jax: the span itself still counts
        return self

    def __exit__(self, *exc) -> bool:
        entry = self._frames.pop()
        dur = time.monotonic() - entry["start_mono"]
        extra = dict(self.extra or {})
        extra.update(_span_exit(entry, dur))
        if self.group:
            extra["group"] = True
        _emit_span(self.name, self.category, entry["start"],
                   entry["start"] + dur, extra)
        # Closed last: the annotation then outlasts every host event it
        # holds, and a device gap they all cover goes to the span.
        note = entry.get("note")
        if note is not None:
            note.__exit__(None, None, None)
        return False


# -- reading a run's spans: the stalled step ---------------------------------

#: keys of a span's dict (``ProfileSpan.to_dict``) that are not its state
_SPAN_FRAME = frozenset(("name", "cat", "start", "end", "process", "thread",
                         "span_id", "parent_id", "self_s"))


def _sample_state(span: Dict[str, Any]) -> Dict[str, Any]:
    state = {k: v for k, v in span.items()
             if k not in _SPAN_FRAME and isinstance(v, (int, float))}
    state["at"] = span["start"]
    return state


def _periods(beats: list) -> list:
    """(span, seconds to the next span's start) for consecutive cadence
    spans; a pair whose ``step`` does not follow on is another closure's
    or has lost a span, and gives none."""
    beats.sort(key=lambda s: s["start"])
    return [(a, b["start"] - a["start"]) for a, b in zip(beats, beats[1:])
            if a.get("step") is None or b.get("step") is None
            or b["step"] == a["step"] + 1]


def _covering(others: list, start: float, end: float) -> list:
    """Seconds of [start, end) that ``others`` cover, summed by name and
    process, the largest first."""
    covered: Dict[tuple, list] = {}
    for s in others:
        overlap = min(end, s["end"]) - max(start, s["start"])
        if overlap > 0:
            entry = covered.setdefault((s["name"], s.get("process")), [0.0, 0])
            entry[0] += overlap
            entry[1] += 1
    return [{"name": name, "process": pid, "seconds": sec, "spans": n}
            for (name, pid), (sec, n) in sorted(
                covered.items(), key=lambda kv: -kv[1][0])]


def _bracket(samples: list, start: float, end: float) -> Dict[str, Any]:
    """The last sample that starts before [start, end), the first that ends
    after it, and what changed between them."""
    before = [s for s in samples if s["start"] <= start]
    after = [s for s in samples if s["end"] >= end]
    out: Dict[str, Any] = {}
    if before:
        out["before"] = _sample_state(before[-1])
    if after:
        out["after"] = _sample_state(after[0])
    if before and after:
        out["difference"] = {k: out["after"][k] - v
                             for k, v in out["before"].items()
                             if k in out["after"]}
    return out


def stalls(spans, cadence: str = "train_place_batch",
           factor: float = 1.5) -> Dict[str, Any]:
    """What each slow period of a step loop coincided with, from a run's
    own spans (the dicts of ``<session>/trace/spans.jsonl``).  Pure: no
    clock, no file, no runtime.

    Per process, the ``cadence`` spans in order of their start give the
    loop's periods, start to start.  ``processes`` holds each process's
    count, median and largest period.  ``stalls`` holds every period over
    ``factor`` x its process's median: its ``step``, ``start`` and
    ``seconds``; ``overlapping``, the seconds of it that every other span
    of that process and of the head (the process of ``runtime_init``)
    covers, summed by name, without the spans that hold the whole loop;
    and ``samples``, the state of the two ``worker_sample`` spans of the
    process that bracket it and their differences (CPU seconds spent,
    switches, faults, pressure, bytes).  The set-up's periods are stalls
    too, and say so: ``xla_compile`` covers them.
    """
    heads = {s["process"] for s in spans if s["name"] == "runtime_init"}
    by_process: Dict[Any, list] = {}
    for s in spans:
        if s["name"] == cadence:
            by_process.setdefault(s.get("process"), []).append(s)
    processes, found = [], []
    for process, beats in sorted(by_process.items(),
                                 key=lambda kv: str(kv[0])):
        periods = _periods(beats)
        if not periods:
            continue
        median = statistics.median(p for _a, p in periods)
        longest = max(periods, key=lambda ap: ap[1])
        processes.append({"process": process, "periods": len(periods),
                          "median_s": median, "max_s": longest[1],
                          "max_step": longest[0].get("step")})
        slow = [(a, p) for a, p in periods if p > factor * median]
        if not slow:
            continue
        lo, hi = beats[0]["start"], beats[-1]["start"]
        others = [s for s in spans
                  if s["name"] not in (cadence, "worker_sample")
                  and (s.get("process") == process
                       or s.get("process") in heads)
                  and not (s["start"] <= lo and s["end"] >= hi)]
        samples = sorted((s for s in spans if s["name"] == "worker_sample"
                          and s.get("process") == process),
                         key=lambda s: s["start"])
        for beat, seconds in slow:
            start, end = beat["start"], beat["start"] + seconds
            found.append({
                "process": process, "step": beat.get("step"),
                "start": start, "seconds": seconds, "median_s": median,
                "overlapping": _covering(others, start, end),
                "samples": _bracket(samples, start, end)})
    return {"cadence": cadence, "factor": factor, "processes": processes,
            "stalls": found}


# -- goodput accounting ----------------------------------------------------

_goodput_latest: Optional["GoodputTracker"] = None

# Checkpoint seconds accrued in THIS process since the last report():
# save_pytree notes them, train._context.report() pops them into the
# report payload, and the driver-side GoodputTracker reattributes that
# slice of the observed "step" window to the "checkpoint" phase.
_pending_ckpt_lock = threading.Lock()
_pending_ckpt_s = 0.0


def note_checkpoint_seconds(seconds: float) -> None:
    global _pending_ckpt_s
    if seconds > 0:
        with _pending_ckpt_lock:
            _pending_ckpt_s += seconds


def pop_checkpoint_seconds() -> float:
    global _pending_ckpt_s
    with _pending_ckpt_lock:
        s, _pending_ckpt_s = _pending_ckpt_s, 0.0
    return s


class GoodputTracker:
    """Partitions wall time into named phases; goodput = productive/total.

    The productive phase is ``"step"``; everything else (init, restart,
    checkpoint, idle, ...) is overhead.  ``enter(phase)`` switches phase;
    ``reattribute(phase, seconds)`` moves already-elapsed seconds out of
    the current phase (used for worker-reported checkpoint time that
    happened inside a driver-observed "step" window).  Each transition
    refreshes the ``ray_tpu_train_goodput_ratio`` gauge, so the scrape
    endpoint shows live goodput mid-run (MegaScale-style accounting:
    at 10k-chip scale the difference between 0.95 and 0.85 is a
    thousand wasted chips)."""

    PRODUCTIVE = "step"

    def __init__(self, initial_phase: str = "init",
                 update_gauge: bool = True):
        global _goodput_latest
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self._phase = initial_phase
        self._since = self._t0
        self._finished = False
        self.seconds: Dict[str, float] = {}
        self._update_gauge = update_gauge
        _goodput_latest = self

    def _accumulate_locked(self, now: float) -> None:
        dt = max(0.0, now - self._since)
        self.seconds[self._phase] = self.seconds.get(self._phase, 0.0) + dt
        self._since = now

    def enter(self, phase: str) -> None:
        with self._lock:
            if self._finished:
                return
            now = time.monotonic()
            self._accumulate_locked(now)
            self._phase = phase
        self._refresh_gauge()

    def reattribute(self, phase: str, seconds: float) -> None:
        """Move ``seconds`` of already-elapsed current-phase time into
        ``phase`` (clamped to what the current phase has actually
        accrued, including the open interval)."""
        if seconds <= 0:
            return
        with self._lock:
            # Same-phase check under the lock: a concurrent enter() can
            # swap _phase between a bare check and the accounting below.
            if self._finished or phase == self._phase:
                return
            self._accumulate_locked(time.monotonic())
            avail = self.seconds.get(self._phase, 0.0)
            moved = min(seconds, avail)
            self.seconds[self._phase] = avail - moved
            self.seconds[phase] = self.seconds.get(phase, 0.0) + moved
        self._refresh_gauge()

    def finish(self) -> Dict[str, Any]:
        with self._lock:
            if not self._finished:
                self._accumulate_locked(time.monotonic())
                self._finished = True
        self._refresh_gauge()
        return self.summary()

    def ratio(self) -> float:
        with self._lock:
            now = time.monotonic()
            open_dt = 0.0 if self._finished else max(0.0, now - self._since)
            total = sum(self.seconds.values()) + open_dt
            productive = self.seconds.get(self.PRODUCTIVE, 0.0) + (
                open_dt if self._phase == self.PRODUCTIVE else 0.0)
        if total <= 0:
            return 0.0
        return productive / total

    def _refresh_gauge(self) -> None:
        if self._update_gauge:
            set_gauge("ray_tpu_train_goodput_ratio", self.ratio())

    def summary(self) -> Dict[str, Any]:
        r = self.ratio()
        with self._lock:
            phases = dict(self.seconds)
            if not self._finished:
                phases[self._phase] = phases.get(self._phase, 0.0) + max(
                    0.0, time.monotonic() - self._since)
        total = sum(phases.values())
        return {
            "goodput_ratio": r,
            "total_s": total,
            "productive_s": phases.get(self.PRODUCTIVE, 0.0),
            "phases_s": phases,
        }


def goodput_summary() -> Optional[Dict[str, Any]]:
    """The most recent GoodputTracker's summary (None before any run)."""
    return _goodput_latest.summary() if _goodput_latest is not None else None


# -- dashboard summary -----------------------------------------------------


def summary() -> Dict[str, Any]:
    """Cluster-merged built-in metrics grouped by subsystem, for
    ``GET /api/metrics/summary``.  Counters/gauges flatten to scalar
    samples; histograms report count/sum/mean per tag set."""
    by_name, acc = _metrics._aggregate_snapshots()
    subsystems: Dict[str, Dict[str, Any]] = {}
    for name, spec in CATALOG.items():
        subsystem = name.split("_")[2]  # ray_tpu_<subsystem>_...
        if spec["type"] == "histogram":
            sums = acc.get(name + "_sum", {})
            counts = acc.get(name + "_count", {})
            samples = []
            for key, (tags, total) in sorted(sums.items()):
                n = counts.get(key, (tags, 0.0))[1]
                samples.append({"tags": tags, "count": n, "sum": total,
                                "mean": (total / n) if n else 0.0})
        else:
            samples = [{"tags": tags, "value": v}
                       for _k, (tags, v) in sorted(acc.get(name, {}).items())]
        if not samples:
            continue
        subsystems.setdefault(subsystem, {})[name] = {
            "type": spec["type"], "description": spec["description"],
            "samples": samples}
    return {"subsystems": subsystems, "goodput": goodput_summary()}
