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


# -- the order in which ``--dist loadfile`` hands the files out ------------
#
# xdist's own order (``loadscopereorder``: most tests first) ends the run
# with the files of one to five cases, and those are the heavy ones: the
# whole-step compiles (``tests/test_tpu_compile_<cell>.py``, 100-200 s in
# ONE fixture each) and a model's longest cases, six workers in them at
# once with nothing left to fill the gaps.  The order is the tests' own
# instead: the files whose seconds are known to be many go out first, a
# compile and a model's file in turn, and the rest follow by number of
# cases as before, so that the run ends on small files.  What says that a
# file is heavy is a fixture its cases ask for: no list of names, no table
# of seconds, nothing read from the machine, so every worker arrives at the
# same order and a new ``test_tpu_compile_<cell>.py`` or ``test_<model>.py``
# falls in its place.


#: what a file's cases ask for that says its seconds are many: the described
#: v5e (a whole step compiled for it) and a process without a mesh (a model
#: run on one CPU device against its reference)
_COMPILES, _MODEL = "topo", "no_mesh_left_by_another_file"


def _hand_out_order(files):
    """``{file: (number of cases, the fixtures its cases ask for)}`` -> the
    files in the order the work queue hands them out: the files that compile
    for the described v5e and the models' files first and in turn, then the
    rest; each kind by number of cases, most first, equal counts by name."""
    most_first = sorted(files, key=lambda f: (-files[f][0], f))
    compiles = [f for f in most_first if _COMPILES in files[f][1]]
    models = [f for f in most_first
              if _MODEL in files[f][1] and f not in compiles]
    heavy = []
    while compiles or models:
        heavy += compiles[:1] + models[:1]
        del compiles[:1], models[:1]
    return heavy + [f for f in most_first if f not in heavy]


def _file_of(nodeid):
    return nodeid.split("::", 1)[0]             # ``loadfile``'s unit


def _files_of(cases):
    """``(node id, the fixtures it asks for)`` a case -> what
    ``_hand_out_order`` takes."""
    files = {}
    for nodeid, asks in cases:
        n, asked = files.get(_file_of(nodeid), (0, frozenset()))
        files[_file_of(nodeid)] = (n + 1, asked | frozenset(asks))
    return files


class _FilesInOrder:
    """On an xdist worker, after ``-m`` has deselected: the files in
    ``_hand_out_order``, the cases of a file in the order they had."""

    @pytest.hookimpl(trylast=True)
    def pytest_collection_modifyitems(self, items):
        order = _hand_out_order(_files_of(
            (item.nodeid, getattr(item, "fixturenames", ()))
            for item in items))
        place = {f: i for i, f in enumerate(order)}
        items.sort(key=lambda item: place[_file_of(item.nodeid)])


def pytest_configure(config):
    if not hasattr(config.option, "loadscopereorder"):
        return                                  # ``-p no:xdist``: as it was
    # the controller queues the files in the order the workers collected
    config.option.loadscopereorder = False
    if hasattr(config, "workerinput"):
        config.pluginmanager.register(_FilesInOrder(), "files-in-order")


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
    tests asks for it by ``pytestmark``; ``_hand_out_order`` reads the same
    request as "a model's file", and hands it out early)."""
    from ray_tpu.parallel.mesh import get_global_mesh, set_global_mesh
    before = get_global_mesh()
    set_global_mesh(None)
    yield
    set_global_mesh(before)
