"""Built-in telemetry: catalog consistency, cross-subsystem smoke run,
goodput accounting under fault injection.

Reference analogs: python/ray/tests/test_metrics_agent.py (built-in metric
catalog exposure) + the MegaScale-style goodput accounting the train
controller implements.
"""

import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig, FailureConfig
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import telemetry

_NAME_RE = re.compile(r"^ray_tpu_[a-z0-9_]+$")
SUBSYSTEMS = ("serve", "llm", "train", "ckpt", "data", "node", "profiler",
              "internal", "autoscaler", "slice", "sched", "metricsview",
              "alerts", "store", "lock", "jax", "xla", "compile", "moe",
              "flash", "rope", "eva", "norm", "hc", "lm", "ssm", "gmm",
              "gated", "mla", "kda", "gdla", "remat", "attn", "pack")


class TestCatalog:
    def test_names_types_descriptions(self):
        assert len(telemetry.CATALOG) >= 15
        seen = {}
        for name, spec in telemetry.CATALOG.items():
            assert _NAME_RE.match(name), f"bad metric name {name!r}"
            assert spec["description"].strip(), f"{name} has no description"
            assert spec["type"] in ("counter", "gauge", "histogram"), name
            subsystem = name.split("_")[2]
            assert subsystem in SUBSYSTEMS, \
                f"{name}: unknown subsystem {subsystem!r}"
            # No two registrations of one name with different types (the
            # dict keying makes same-name/same-catalog impossible; this
            # guards against later PRs re-declaring outside the catalog).
            assert seen.setdefault(name, spec["type"]) == spec["type"]
        assert {n.split("_")[2] for n in telemetry.CATALOG} == set(SUBSYSTEMS)

    def test_instantiation_matches_catalog(self):
        metrics_mod._reset_for_tests()
        for name, spec in telemetry.CATALOG.items():
            inst = telemetry._get(name, spec["type"])
            assert inst.metric_type == spec["type"]
        # Second pass hits the cache / aliasing path without error.
        for name, spec in telemetry.CATALOG.items():
            telemetry._get(name, spec["type"])
        metrics_mod._reset_for_tests()

    def test_unknown_or_mistyped_name_raises(self):
        with pytest.raises(KeyError):
            telemetry.counter("ray_tpu_bogus_total")
        with pytest.raises(TypeError):
            telemetry.counter("ray_tpu_train_goodput_ratio")

    def test_watchdog_diagnostics_series_registered(self):
        """The watchdog's verdict counters follow the catalog naming
        scheme (PR 2 diagnostics series ride the same lint as PR 1's)."""
        for name in ("ray_tpu_train_straggler_total",
                     "ray_tpu_train_hang_total"):
            assert name in telemetry.CATALOG, name
            spec = telemetry.CATALOG[name]
            assert spec["type"] == "counter", name
            assert name.endswith("_total"), name
            assert _NAME_RE.match(name), name
            assert name.split("_")[2] == "train", name
            assert spec["description"].strip()
        # The exception-safe helper records them without raising.
        telemetry.inc("ray_tpu_train_straggler_total", 0.0)
        telemetry.inc("ray_tpu_train_hang_total", 0.0)

    def test_checkpoint_series_registered(self):
        """The distributed-checkpointing subsystem's series are declared
        in the catalog (and only there — RT204 lints call sites)."""
        specs = {
            "ray_tpu_ckpt_save_blocking_seconds": "histogram",
            "ray_tpu_ckpt_write_seconds": "histogram",
            "ray_tpu_ckpt_bytes_total": "counter",
            "ray_tpu_ckpt_inflight": "gauge",
            "ray_tpu_ckpt_restore_seconds": "histogram",
            "ray_tpu_ckpt_replica_restores_total": "counter",
        }
        for name, typ in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert name.split("_")[2] == "ckpt", name
            assert telemetry.CATALOG[name]["description"].strip(), name
        assert telemetry.CATALOG["ray_tpu_ckpt_restore_seconds"][
            "tag_keys"] == ("source",)

    def test_preemption_series_registered(self):
        """The preemption/drain robustness series (node lifecycle +
        train urgent-checkpoint/backoff) are declared in the catalog —
        RT204 lints every call site against it."""
        specs = {
            "ray_tpu_node_preempted_total": ("counter", ()),
            "ray_tpu_node_drain_seconds": ("histogram", ()),
            "ray_tpu_node_draining": ("gauge", ()),
            "ray_tpu_train_urgent_ckpt_total": ("counter", ()),
            "ray_tpu_train_restart_backoff_seconds": ("histogram", ()),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
        # Exception-safe helpers record them without raising.
        telemetry.inc("ray_tpu_node_preempted_total", 0.0)
        telemetry.observe("ray_tpu_node_drain_seconds", 0.0)
        telemetry.set_gauge("ray_tpu_node_draining", 0.0)
        telemetry.inc("ray_tpu_train_urgent_ckpt_total", 0.0)
        telemetry.observe("ray_tpu_train_restart_backoff_seconds", 0.0)

    def test_lock_contention_series_registered(self):
        """The lock-contention profiler's sampled wait/hold series are
        declared in the catalog — RT204 lints lockdebug's publish path
        against it."""
        for name in ("ray_tpu_lock_wait_seconds",
                     "ray_tpu_lock_hold_seconds"):
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == "histogram", name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == ("site",)
            assert telemetry.CATALOG[name]["description"].strip(), name
        telemetry.observe("ray_tpu_lock_wait_seconds", 0.0,
                          tags={"site": "test.py:1"})
        telemetry.observe("ray_tpu_lock_hold_seconds", 0.0,
                          tags={"site": "test.py:1"})

    def test_disagg_admission_series_registered(self):
        """The disaggregated-serving / admission-control series (PR 6)
        are declared in the catalog: router queue depth, shed counts by
        reason, KV-transfer bytes/latency, chunked-prefill chunks, and
        the serve handle-path shed counter."""
        specs = {
            "ray_tpu_llm_admission_queue_depth": ("gauge", ("class",)),
            "ray_tpu_llm_shed_total": ("counter", ("reason",)),
            "ray_tpu_llm_kv_transfer_bytes_total": ("counter", ()),
            "ray_tpu_llm_kv_transfer_seconds": ("histogram", ("op",)),
            "ray_tpu_llm_prefill_chunks_total": ("counter", ()),
            "ray_tpu_serve_shed_total": ("counter", ("deployment",)),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
        # The exception-safe helpers record them without raising.
        telemetry.inc("ray_tpu_llm_shed_total", 0.0,
                      tags={"reason": "queue_full"})
        telemetry.set_gauge("ray_tpu_llm_admission_queue_depth", 0.0,
                            tags={"class": "default"})
        telemetry.observe("ray_tpu_llm_kv_transfer_seconds", 0.0,
                          tags={"op": "export"})

    def test_fleet_series_registered(self):
        """The serving-fleet series (llm.fleet: replica-count gauge,
        prefix-affinity routing outcomes, imbalance rebalances, and
        autoscaler replica add/remove) are declared in the catalog."""
        specs = {
            "ray_tpu_serve_replica_count": ("gauge", ("fleet",)),
            "ray_tpu_serve_prefix_hit_total": ("counter", ("outcome",)),
            "ray_tpu_serve_rebalance_total": ("counter", ()),
            "ray_tpu_serve_replica_scale_total": ("counter",
                                                  ("direction",)),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
        telemetry.set_gauge("ray_tpu_serve_replica_count", 0.0,
                            tags={"fleet": "t"})
        telemetry.inc("ray_tpu_serve_prefix_hit_total", 0.0,
                      tags={"outcome": "full"})
        telemetry.inc("ray_tpu_serve_rebalance_total", 0.0)
        telemetry.inc("ray_tpu_serve_replica_scale_total", 0.0,
                      tags={"direction": "up"})

    def test_mesh_series_registered(self):
        """The mesh-runtime series (train/mesh: live axis sizes,
        per-process parameter shard bytes, reshape events) are declared
        in the catalog — RT204 lints every call site against it."""
        specs = {
            "ray_tpu_train_mesh_axis_size": ("gauge", ("axis",)),
            "ray_tpu_train_param_shard_bytes": ("gauge", ()),
            "ray_tpu_train_mesh_reshapes_total": ("counter", ()),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
            assert name.split("_")[2] == "train", name
        # The exception-safe helpers record them without raising.
        telemetry.set_gauge("ray_tpu_train_mesh_axis_size", 8.0,
                            tags={"axis": "fsdp"})
        telemetry.set_gauge("ray_tpu_train_param_shard_bytes", 0.0)
        telemetry.inc("ray_tpu_train_mesh_reshapes_total", 0.0)

    def test_spotfleet_series_registered(self):
        """The goodput-driven autoscaling / spot-fleet elasticity series
        (pre-buy, goodput scale events, upsize, slice drains, pending
        pre-buy gauge) are declared in the catalog — RT204 lints every
        call site against it."""
        specs = {
            "ray_tpu_autoscaler_prebuy_total": ("counter", ()),
            "ray_tpu_autoscaler_goodput_scale_events_total":
                ("counter", ("direction",)),
            "ray_tpu_autoscaler_pending_prebuys": ("gauge", ()),
            "ray_tpu_train_upsize_total": ("counter", ()),
            "ray_tpu_slice_drains_total": ("counter", ()),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
        # The exception-safe helpers record them without raising.
        telemetry.inc("ray_tpu_autoscaler_prebuy_total", 0.0)
        telemetry.inc("ray_tpu_autoscaler_goodput_scale_events_total",
                      0.0, tags={"direction": "up"})
        telemetry.set_gauge("ray_tpu_autoscaler_pending_prebuys", 0.0)
        telemetry.inc("ray_tpu_train_upsize_total", 0.0)
        telemetry.inc("ray_tpu_slice_drains_total", 0.0)

    def test_sched_series_registered(self):
        """The control-plane telescope's series (decision counts by
        kind, lifecycle stage waits, placement attempts, PG two-phase
        commit latency, queue depths) are declared in the catalog —
        RT204 lints every call site against it."""
        specs = {
            "ray_tpu_sched_decisions_total": ("counter", ("kind",)),
            "ray_tpu_sched_stage_wait_seconds": ("histogram", ("stage",)),
            "ray_tpu_sched_placement_attempts": ("histogram", ()),
            "ray_tpu_sched_pg_commit_seconds": ("histogram", ()),
            "ray_tpu_sched_queue_depth": ("gauge", ("queue",)),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
            assert name.split("_")[2] == "sched", name
        # The exception-safe helpers record them without raising.
        telemetry.inc("ray_tpu_sched_decisions_total", 0.0,
                      tags={"kind": "inline"})
        telemetry.observe("ray_tpu_sched_stage_wait_seconds", 0.0,
                          tags={"stage": "queue"})
        telemetry.observe_many("ray_tpu_sched_placement_attempts", [1.0])
        telemetry.set_gauge("ray_tpu_sched_queue_depth", 0.0,
                            tags={"queue": "ready"})

    def test_metricsview_series_registered(self):
        """The time-series backplane's own health series (store ingest /
        drop accounting) and the SLO burn-rate engine's alert series are
        declared in the catalog — RT204 lints every call site."""
        specs = {
            "ray_tpu_metricsview_points_total": ("counter", ()),
            "ray_tpu_metricsview_dropped_total": ("counter", ()),
            "ray_tpu_alerts_firing": ("gauge", ()),
            "ray_tpu_alerts_transitions_total": ("counter", ("state",)),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
        # The exception-safe helpers record them without raising.
        telemetry.inc("ray_tpu_metricsview_points_total", 0.0)
        telemetry.inc("ray_tpu_metricsview_dropped_total", 0.0)
        telemetry.set_gauge("ray_tpu_alerts_firing", 0.0)
        telemetry.inc("ray_tpu_alerts_transitions_total", 0.0,
                      tags={"state": "pending"})

    def test_store_series_registered(self):
        """The data-plane telescope's series (object-store occupancy
        gauges, lifecycle/spill op counters, spill-GC reclaimed bytes,
        cross-node transfer bytes + latency) are declared in the
        catalog — RT204 lints every call site against it."""
        specs = {
            "ray_tpu_store_used_bytes": ("gauge", ("node",)),
            "ray_tpu_store_capacity_bytes": ("gauge", ("node",)),
            "ray_tpu_store_pinned_bytes": ("gauge", ("node",)),
            "ray_tpu_store_spilled_bytes": ("gauge", ("node",)),
            "ray_tpu_store_objects": ("gauge", ("node",)),
            "ray_tpu_store_ops_total": ("counter", ("op",)),
            "ray_tpu_store_spill_ops_total": ("counter", ("op",)),
            "ray_tpu_store_spill_reclaimed_bytes_total": ("counter", ()),
            "ray_tpu_store_transfer_bytes_total": ("counter",
                                                   ("direction",)),
            "ray_tpu_store_transfer_seconds": ("histogram", ("op",)),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
            assert name.split("_")[2] == "store", name
        # The exception-safe helpers record them without raising.
        telemetry.set_gauge("ray_tpu_store_used_bytes", 0.0,
                            tags={"node": "smoke"})
        telemetry.inc("ray_tpu_store_ops_total", 0.0, tags={"op": "get"})
        telemetry.inc("ray_tpu_store_transfer_bytes_total", 0.0,
                      tags={"direction": "pull"})
        telemetry.observe("ray_tpu_store_transfer_seconds", 0.0,
                          tags={"op": "pull"})

    def test_profiler_series_registered(self):
        """The profiler subsystem's series (PR 10: step-phase
        attribution, HBM gauges, compile accounting, capture counter)
        are declared in the catalog — RT204 lints every call site."""
        specs = {
            "ray_tpu_train_step_phase_seconds": ("histogram", ("phase",)),
            "ray_tpu_train_hbm_used_bytes": ("gauge", ("device",)),
            "ray_tpu_train_hbm_peak_bytes": ("gauge", ("device",)),
            "ray_tpu_profiler_compile_total": ("counter", ("fn",)),
            "ray_tpu_profiler_compile_seconds": ("histogram", ("fn",)),
            "ray_tpu_profiler_recompiles_total": ("counter", ("fn",)),
            "ray_tpu_profiler_captures_total": ("counter", ()),
        }
        for name, (typ, tags) in specs.items():
            assert name in telemetry.CATALOG, name
            assert telemetry.CATALOG[name]["type"] == typ, name
            assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags
            assert telemetry.CATALOG[name]["description"].strip(), name
        # The exception-safe helpers record them without raising.
        telemetry.observe("ray_tpu_train_step_phase_seconds", 0.0,
                          tags={"phase": "data_wait"})
        telemetry.inc("ray_tpu_profiler_compile_total", 0.0,
                      tags={"fn": "smoke"})
        telemetry.inc("ray_tpu_profiler_captures_total", 0.0)


def test_kda_series_registered():
    """The delta rule's two counters: what a call of the scan is, and which
    path the passes before and after it took (``ops/kda.kda_mixer``,
    ``gated_head_norm``; the kernels against ``jnp``:
    ``tests/test_kda.py``)."""
    specs = {
        "ray_tpu_kda_call_geometry_total":
            ("heads", "dk", "dv", "chunk", "rows", "seq", "path"),
        "ray_tpu_kda_pass_path_total":
            ("pass", "path", "heads", "d", "rows", "seq"),
    }
    for name, tags in specs.items():
        assert telemetry.CATALOG[name]["type"] == "counter", name
        assert tuple(telemetry.CATALOG[name]["tag_keys"]) == tags, name
        assert telemetry.CATALOG[name]["description"].strip(), name


def test_ssm_conv_series_registered():
    """Which form a mixer's convolution took (``ops/ssm.causal_conv``: the
    Pallas pair against ``jnp``; ``tests/test_nemotron_h.py``,
    ``tests/test_ops_ssm_segments.py``)."""
    entry = telemetry.CATALOG["ray_tpu_ssm_conv_path_total"]
    assert entry["type"] == "counter"
    assert tuple(entry["tag_keys"]) == ("path", "taps", "segments")
    assert entry["description"].strip()


def test_flash_geometry_counter_says_which_kernels_took_rows():
    """``ray_tpu_flash_step_geometry_total`` carries ``rows="vo"`` where a
    kernel took v and the result in the projections' layout (PR 49), on
    each of the three kernels, and no such tag on a head-major call's; a
    call in parts (PR 50) says ``parts`` and ``rows="qkvo"`` on its three,
    and no other call says ``parts``."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.util import metrics as metrics_mod

    name = "ray_tpu_flash_step_geometry_total"
    assert telemetry.CATALOG[name]["type"] == "counter"
    assert tuple(telemetry.CATALOG[name]["tag_keys"]) == (
        "kernel", "block_q", "block_k", "heads_a_step", "scores", "d_qk",
        "d_v", "d", "rows", "parts", "tiles_a_step", "shares")
    metrics_mod._reset_for_tests()
    q = jnp.ones((1, 2, 64, 128), jnp.float32)          # [B, H, S, D]
    for rows in (True, False):
        v = jnp.swapaxes(q, 1, 2) if rows else q
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, q, v, interpret=True, rows=rows)))(q)
    lines = [l for l in metrics_mod.prometheus_text().splitlines()
             if l.startswith(name + "{")]
    # a key head a query head: the backward is the one pass (PR 54)
    assert not any("flash_dq" in l or "flash_dkv" in l for l in lines)
    for kernel in ("flash_fwd", "flash_bwd"):
        mine = [l for l in lines if f'kernel="{kernel}"' in l]
        assert len(mine) == 2, mine
        assert sum('rows="vo"' in l for l in mine) == 1
        assert sum("rows=" not in l for l in mine) == 1
    assert not any("parts=" in l for l in lines)
    q_n, q_r = jnp.swapaxes(q, 1, 2), q[..., :64]       # rows, head-major
    kv = jnp.ones((1, 64, 2, 256), jnp.float32)
    jax.grad(lambda q_n: jnp.sum(flash_attention(
        (q_n, q_r), (kv, q_r[:, :1]), None, interpret=True)))(q_n)
    lines = [l for l in metrics_mod.prometheus_text().splitlines()
             if l.startswith(name + "{") and "parts=" in l]
    assert len(lines) == 2, lines
    for kernel, line in zip(("fwd", "bwd"), sorted(
            lines, key=lambda l: "bwd" in l)):
        assert f'kernel="flash_{kernel}_d192v128"' in line, line
        assert 'parts="128+64"' in line and 'rows="qkvo"' in line, line
    metrics_mod._reset_for_tests()


@pytest.mark.parametrize("group,kernels", [
    (1, {"flash_bwd": None}), (4, {"flash_bwd": "4"}),
    (16, {"flash_bwd": "16"})])
def test_flash_geometry_counter_says_the_shares_of_a_group(group, kernels):
    """``shares`` (PR 60): the query-head grid rows a key head's dk / dv is
    summed over outside the kernel, on the backward's kernel that leaves
    them so and on no forward; absent where a grid row holds the whole
    group (1).  ``counters.json`` of a run says by it which calls took the
    one pass under a group."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.util import metrics as metrics_mod

    name = "ray_tpu_flash_step_geometry_total"
    assert "shares" in telemetry.CATALOG[name]["tag_keys"]
    assert "shares" in telemetry.CATALOG[name]["description"]
    metrics_mod._reset_for_tests()
    q = jnp.ones((1, group, 64, 128), jnp.float32)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, q[:, :1], q[:, :1], interpret=True)))(q)
    lines = [l for l in metrics_mod.prometheus_text().splitlines()
             if l.startswith(name + "{")]
    assert not any("shares=" in l for l in lines if "flash_fwd" in l)
    for kernel, shares in kernels.items():
        mine, = [l for l in lines if f'kernel="{kernel}"' in l]
        assert 'heads_a_step="1"' in mine
        assert (f'shares="{shares}"' in mine) if shares else (
            "shares=" not in mine), mine
    assert len(lines) == 1 + len(kernels), lines
    metrics_mod._reset_for_tests()


def _base_series(prom_text):
    """Distinct catalog-level metric names present in an exposition."""
    names = set()
    for line in prom_text.splitlines():
        if not line or line.startswith("#"):
            continue
        sample = line.split("{")[0].split(" ")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if sample.endswith(suffix) and \
                    sample[: -len(suffix)] in telemetry.CATALOG:
                sample = sample[: -len(suffix)]
        if sample in telemetry.CATALOG:
            names.add(sample)
    return names


def _smoke_train_fn(config):
    import time as _t

    import numpy as np

    import ray_tpu.train as train
    w = np.zeros((4, 4), np.float32)
    for i in range(3):
        _t.sleep(0.05)
        # ckpt subsystem rides the same smoke: an async sharded save per
        # step exercises save-blocking/write/bytes/inflight series.
        train.save_checkpoint({"w": w + i, "step": i})
        # moe subsystem: a step's expert loads ride the report's own keys.
        train.report({"loss": 1.0 / (i + 1), "tokens": 64,
                      "moe_held_assignments": 8.0,
                      "moe_load_max_over_mean": 1.25, "moe_dropped": 0.0,
                      "moe_sliced_calls": 0.0,
                      # lm and hc: a prediction module's loss and the
                      # hyper-connections' Sinkhorn residual ride there too.
                      "mtp_loss": 2.0, "hc_sinkhorn_residual": 1e-5,
                      # ssm: the share of a state a chunk hands on
                      "ssm_chunk_carry": 0.3,
                      # kda: the share of a delta-rule state's row a chunk
                      # hands on
                      "kda_chunk_carry": 0.9,
                      # gdla: the mean weight a differential attention's
                      # noise heads are subtracted with
                      "gdla_lambda_mean": 0.5,
                      # sink: the share of a window row's softmax mass
                      # that the learned attention sink took
                      "sink_mass_mean": 0.4,
                      # pack: how a batch of packed rows was filled, and
                      # the chunks of its scans that a boundary cut
                      "pack_documents_a_row": 13.0, "pack_pairs_share": 0.15,
                      "ssm_chunks_with_boundary": 110.0})


@serve.deployment(name="telemetry_echo")
class _Echo:
    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.01)
    def batched(self, items):
        return items

    def __call__(self, body):
        return self.batched(body)


_LLM_CFG_KW = dict(vocab_size=128, hidden=32, layers=2, heads=4, kv_heads=2,
                   head_dim=8, mlp_dim=64, max_seq_len=128,
                   attention_impl="reference", remat=False)


class TestSmokeAllSubsystems:
    def test_metrics_span_all_subsystems(self, ray_start_isolated,
                                          tmp_path):
        metrics_mod._reset_for_tests()

        # -- train: one fit() on the CPU backend -------------------------
        result = JaxTrainer(
            _smoke_train_fn, train_loop_config={},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="telemetry_smoke",
                                 storage_path=str(tmp_path)),
        ).fit()
        assert result.error is None
        assert result.goodput is not None
        assert 0.0 < result.goodput["goodput_ratio"] <= 1.0

        # -- serve: one deployment handling >= 10 requests ----------------
        handle = serve.run(_Echo.bind())
        for i in range(10):
            out = ray_tpu.get(handle.remote({"i": i}), timeout=60)
            assert out == {"i": i}

        # -- llm: one generate() through the engine -----------------------
        from ray_tpu.llm import InferenceEngine, SamplingParams
        from ray_tpu.models import LlamaConfig
        from ray_tpu.models.llama import init_params
        cfg = LlamaConfig(dtype=jnp.float32, **_LLM_CFG_KW)
        params = init_params(cfg, jax.random.key(0))
        eng = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                              num_pages=64, prefill_buckets=(16,))
        toks = eng.generate([[3, 17, 92, 5, 41]],
                            SamplingParams(max_tokens=8))
        assert len(toks[0]) == 8

        # -- profiler: tracked-jit compile accounting ---------------------
        from ray_tpu import profiler
        tracked = profiler.track(jax.jit(lambda x: x + 1),
                                 name="telemetry_smoke_inc")
        tracked(jnp.ones((4,), jnp.float32))

        # -- xla / compile: every backend compile is counted by program;
        # a fetch from the persistent compile cache is counted when jax
        # reports one inside the compile.  The suite runs with the cache
        # off, so the report is made here the way jax makes it.
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.jit(lambda x: x * 5)(jnp.ones((4,), jnp.float32))

        # -- flash: a traced flash-attention kernel counts the geometry of
        # its grid step (interpreted here: no chip).
        from ray_tpu.ops.attention import flash_attention
        x = jnp.ones((1, 2, 64, 32), jnp.float32)
        flash_attention(x, x[:, :1], x[:, :1], interpret=True)

        # -- gmm: a traced grouped product counts the tiles its kernel took.
        from ray_tpu.ops.moe import grouped_matmul
        grouped_matmul(jnp.zeros((16, 8), jnp.float32),
                       jnp.zeros((2, 8, 8), jnp.float32),
                       jnp.asarray([5, 9], jnp.int32), "gmm_interpret")

        # -- rope: a traced rotary embedding counts the path it took.
        from ray_tpu.ops.rope import rope_lane_tables, rotate_heads
        rotate_heads(jnp.ones((1, 8, 2, 32), jnp.float32),
                     *rope_lane_tables(32, 8))

        # -- gated: a traced double-gated short convolution counts its form.
        from ray_tpu.ops.ssm import gated_short_conv
        c = jnp.ones((1, 8, 4), jnp.float32)
        gated_short_conv(c, c, c, jnp.ones((3, 4), jnp.float32))

        # -- mla: a traced call of latent attention counts what it is
        # (DeepSeek-V3's block, no query bottleneck; the reference path).
        from ray_tpu.models import deepseek_v3, xing4
        mla_cfg = deepseek_v3.deepseek_v3_tiny().stack
        xing4._mla(mla_cfg, *rope_lane_tables(64, 8),
                   jnp.ones((1, 8, 64), jnp.float32),
                   jax.tree.map(lambda a: a[0], xing4.init_params(
                       mla_cfg, jax.random.key(0))["dense"]))

        # -- kda: a traced call of the chunked gated delta rule counts what
        # it is (the jnp form here), a KDA layer which path the passes round
        # its scan take, and a model whose routers limit their choice to
        # groups counts the groups (a forward of the tiny model).
        from ray_tpu.models import bailing_hybrid
        from ray_tpu.ops.kda import kda
        kx = jnp.ones((1, 16, 2, 8), jnp.float32)
        kda(kx, kx, kx, -0.1 * kx, jnp.ones((1, 16, 2), jnp.float32), 16)
        bh_cfg = bailing_hybrid.bailing_hybrid_tiny()
        bailing_hybrid.forward(
            bailing_hybrid.init_params(bh_cfg, jax.random.key(0)),
            jnp.zeros((1, 16), jnp.int32), bh_cfg)

        # -- eva: a traced EVA kernel counts its table's steps (two
        # windows of 32 in chunks of 8, interpreted here).
        from ray_tpu.ops.eva import eva_attention
        eva_attention(x, x, x, x[:, :, :8], x[:, :, :8], 32, 8,
                      impl="flash_interpret")

        # -- lock: the contention profiler publishes on a double 1/8
        # sample (hold timing every 8th acquire, telemetry every 8th
        # sampled hold), so 64 acquire/release pairs on a lock created
        # under install_profile() deterministically lands one
        # observation on each ray_tpu_lock_* series.
        from ray_tpu.devtools import lockdebug
        lockdebug.install_profile()
        try:
            lk = threading.Lock()
            for _ in range(64):
                with lk:
                    pass
        finally:
            lockdebug.uninstall_profile()

        # -- jax: the host-sync tripwire publishes on the FIRST sync of a
        # site (then every 64th), so one forced device->host coercion
        # under install() deterministically lands both ray_tpu_jax_*
        # series.
        from ray_tpu.devtools import syncdebug
        syncdebug.install()
        try:
            float(jnp.sum(jnp.arange(8.0)))
        finally:
            syncdebug.uninstall()
            syncdebug.clear()

        # -- data: a small pipeline through the streaming executor --------
        import ray_tpu.data as rdata
        ds = rdata.from_items([{"x": float(i)} for i in range(64)],
                              parallelism=4)
        rows = ds.map(lambda r: {"x": r["x"] * 2}).take_all()
        assert len(rows) == 64

        # -- node: a drain/undrain round-trip (preemption signal plane) --
        from ray_tpu._private.api import _control
        node_hex = _control("nodes")[0]["node_id"]
        assert _control("drain_node", node_hex, 30.0, "smoke") is True
        assert _control("undrain_node", node_hex) is True

        # -- autoscaler + slice: a pre-buy decision through the real
        # policy path (counters book only EXECUTED buys, so the
        # subsystem series land via the pending gauge) + the
        # slice-drain counter the SlicePlacementGroup drain path bumps.
        from ray_tpu.autoscaler import (GoodputAutoscalePolicy,
                                        GoodputPolicyConfig)
        pol = GoodputAutoscalePolicy(GoodputPolicyConfig(
            default_node_type="smoke"))
        assert len(pol.decide([("node-x", None)], pending=0)) == 1
        telemetry.set_gauge("ray_tpu_autoscaler_pending_prebuys", 0.0)
        telemetry.inc("ray_tpu_slice_drains_total")

        # -- sched: the run above placed real tasks through the
        # instrumented scheduler; force the rate-limited publisher so
        # the decision counters / queue gauges land on this scrape,
        # and check the telescope saw the placements.
        from ray_tpu.util import state as rstate
        sched_stats = rstate.sched_stats()
        assert sched_stats["decisions"]["total"] > 0
        assert sched_stats["events"]["num_events"] > 0

        # -- metricsview + alerts: a tiny accounted store pays the
        # ingest/eviction counters deterministically, and one objective
        # walks the full pending -> firing -> resolved -> ok cycle on
        # logical time so the alert gauge + transition counter land on
        # this scrape (the live head store also accounts, but its
        # cadence is wall-clock).
        from ray_tpu.metricsview import SeriesStore, SloEngine, SloObjective
        store = SeriesStore(interval_s=1.0, max_points=2, account=True)
        for i in range(4):  # ring of 2: later appends evict -> dropped
            store.append("smoke_gauge", {}, "gauge", float(i), float(i))
        eng = SloEngine(store)
        eng.set_objectives([SloObjective(
            name="smoke", metric="smoke_gauge", agg="last", op="<",
            threshold=0.5, fast_window_s=2.0, slow_window_s=4.0,
            cooldown_s=0.0)])
        eng.evaluate(now=3.0)   # breach -> pending
        eng.evaluate(now=3.5)   # slow window confirms -> firing
        store.append("smoke_gauge", {}, "gauge", 0.0, 10.0)
        eng.evaluate(now=10.0)  # recovered -> resolved
        eng.evaluate(now=11.0)  # cooldown elapsed -> ok
        assert eng.status(now=11.0)["objectives"][0]["state"] == "ok"
        assert [t["to"] for t in eng.status(now=11.0)["transitions"]] == \
            ["pending", "firing", "resolved", "ok"]

        # -- internal: one accounted swallowed error ----------------------
        telemetry.note_swallowed("test.smoke", RuntimeError("boom"))

        # Worker-side metrics flush deterministically at task completion,
        # but serve latency lands from a watcher thread: poll briefly.
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            series = _base_series(metrics_mod.prometheus_text())
            if len(series) >= 15 and all(
                    any(s.startswith(f"ray_tpu_{sub}_") for s in series)
                    for sub in SUBSYSTEMS):
                break
            time.sleep(0.2)
        series = _base_series(metrics_mod.prometheus_text())
        missing = {sub for sub in SUBSYSTEMS
                   if not any(s.startswith(f"ray_tpu_{sub}_")
                              for s in series)}
        assert not missing, f"no series for {missing}; got {sorted(series)}"
        assert "ray_tpu_kda_pass_path_total" in series, sorted(series)
        assert len(series) >= 15, sorted(series)

        # Timeline carries engine-step and train-step profile spans.
        trace = json.loads(ray_tpu.timeline())
        names = {e["name"] for e in trace}
        assert "engine_step" in names, sorted(names)
        assert "engine_prefill" in names
        assert "train_step" in names
        assert "train_fit" in names

        # Dashboard summary shape (no HTTP server needed: same code path
        # the /api/metrics/summary endpoint serves).
        summary = telemetry.summary()
        assert set(SUBSYSTEMS) <= set(summary["subsystems"])
        assert summary["goodput"] is not None
        serve.shutdown()


def _goodput_sleep_fn(config):
    import os
    import time as _t

    import ray_tpu.train as train
    _t.sleep(0.3)
    train.report({"loss": 1.0, "tokens": 32})
    marker = config.get("die_marker")
    if marker and not os.path.exists(marker):
        open(marker, "w").close()
        raise RuntimeError("injected train worker failure")
    _t.sleep(0.3)
    train.report({"loss": 0.5, "tokens": 32})


class TestGoodputAccounting:
    def test_ratio_drops_under_fault_injection(self, ray_start_isolated,
                                               tmp_path):
        metrics_mod._reset_for_tests()
        clean = JaxTrainer(
            _goodput_sleep_fn, train_loop_config={},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="goodput_clean",
                                 storage_path=str(tmp_path)),
        ).fit()
        assert clean.error is None
        assert 0.0 < clean.goodput["goodput_ratio"] <= 1.0

        faulty = JaxTrainer(
            _goodput_sleep_fn,
            train_loop_config={"die_marker": str(tmp_path / "died_once")},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="goodput_faulty",
                                 storage_path=str(tmp_path),
                                 failure_config=FailureConfig(
                                     max_failures=1)),
        ).fit()
        assert faulty.error is None
        assert faulty.num_failures == 1
        g = faulty.goodput
        assert 0.0 < g["goodput_ratio"] <= 1.0
        # The kill/restart shows up as restart + lost phases, and the
        # ratio drops measurably vs the clean run.
        assert g["phases_s"].get("restart", 0.0) > 0.0
        assert g["phases_s"].get("lost", 0.0) > 0.0
        assert g["goodput_ratio"] < clean.goodput["goodput_ratio"]
        # The restart also shows on the built-in counter.
        text = metrics_mod.prometheus_text()
        assert "ray_tpu_train_worker_restarts_total 1.0" in text
