"""Test fixtures.

TPU-less CI substrate (SURVEY §4.2): jax collective/SPMD tests run on a
virtual 8-device CPU mesh via XLA host-platform device multiplexing — the
same technique the reference uses for TPU-logic tests without hardware
(reference: python/ray/tests/accelerators/test_tpu.py mocks env/metadata).
The env vars must be set before the first jax import anywhere in the process.
"""

import os

# Tests run on the CPU: eight forced host devices stand in for a TPU host.
# The chip is reached only through chip_smoke.py and benchmark/run.py.
# Nothing here (or in any test module) may touch jax or the TPU library at
# import:
# every xdist worker imports every test file.
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The suite compiles from nothing, as it always has: XLA:CPU entries of
# the persistent cache are tied to the host CPU and warn on reload.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# Resource-leak sanitizer on for the whole suite: every test that
# starts a cluster also asserts, at shutdown, that no framework
# threads / pins / tracked file handles / named actors leaked
# (ray_tpu/_private/sanitizer.py).  Opt out with RAY_TPU_SANITIZE=0.
os.environ.setdefault("RAY_TPU_SANITIZE", "1")

import pytest  # noqa: E402

# -- test tiers (reference pattern: bazel size/tags partitioning,
# python/ray/tests/BUILD.bazel) --------------------------------------------
#
# ``pytest -m quick`` is the fast CI tier: every subsystem represented,
# compile-heavy jax modules excluded except for hand-picked cheap
# representatives.  The full suite (no -m) is unchanged.

_SLOW_MODULES = {
    "test_pipeline", "test_llm", "test_rl", "test_rl_breadth", "test_train",
    "test_train_elastic", "test_train_multislice", "test_collective",
    "test_dag", "test_tune", "test_chaos", "test_recovery", "test_oom",
    "test_serve_ha", "test_runtime_env", "test_autoscaler", "test_head_ft",
    "test_reconnect",
}

# Fast representatives inside slow modules so the quick tier still touches
# every subsystem (node ids are matched by substring).
_QUICK_IN_SLOW = {
    "test_llm": ("TestInferenceEngine",),
    "test_rl": ("TestBuffers", "TestGAE"),
    "test_pipeline": ("test_pp_requires_mesh",),
    "test_tune": ("test_variant_expansion", "test_schedulers_unit",
                  "test_concurrency_limiter"),
    "test_collective": ("TestKVBackend::test_all_ops",),
    "test_dag": ("TestShmChannel::test_roundtrip", "test_chain"),
    "test_train": ("test_single_worker_e2e",),
    "test_recovery": ("test_put_refs_freed_on_drop",
                      "test_reconstruct_lost_object_on_get"),
    "test_oom": ("TestPolicy",),
    "test_autoscaler": ("test_demand_driven_scale_up",
                        "test_idle_downscale_drains_before_terminate"),
    "test_head_ft": ("test_wal_snapshot_roundtrip",
                     "test_torn_tail_is_ignored"),
    "test_runtime_env": ("test_working_dir_ships_files", "test_endpoints"),
    "test_chaos": ("test_workload_correct_under_message_delays",),
    "test_serve_ha": (),
    "test_rl_breadth": (),
    "test_train_elastic": (),
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = os.path.basename(item.nodeid.split("::", 1)[0])
        mod = mod[:-3] if mod.endswith(".py") else mod
        if item.get_closest_marker("slow") is not None:
            continue  # source-level @pytest.mark.slow wins
        if mod in _SLOW_MODULES:
            picks = _QUICK_IN_SLOW.get(mod, ())
            if any(p in item.nodeid for p in picks):
                item.add_marker(pytest.mark.quick)
            else:
                item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(scope="module")
def ray_start():
    """Module-scoped runtime (reference: conftest ray_start_regular)."""
    import ray_tpu
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_isolated():
    """Function-scoped runtime for tests that mutate cluster state."""
    import ray_tpu
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def no_mesh_left_by_another_file():
    """``build_mesh`` sets the process's global mesh, and a test file that
    ran before in the same worker may have left one of several devices,
    which a model that runs on one device refuses by name: the test starts
    without one and hands back what it found (a file of such a model's
    tests asks for it by ``pytestmark``)."""
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    before = get_global_mesh()
    set_global_mesh(None)
    yield
    set_global_mesh(before)
