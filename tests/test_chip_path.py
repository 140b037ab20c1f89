"""The rules of the chip path that can be checked without a chip: one
compile-cache decision, chips only by grant, no fallback when the device
is missing, and the flash kernel's shard_map island on a mesh."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCache:
    def test_env_wins_and_is_left_alone(self, monkeypatch):
        from ray_tpu._private import compile_cache
        monkeypatch.setenv(compile_cache.ENV, "/somewhere/else")
        monkeypatch.setitem(sys.modules, "jax", None)  # leave jax's config
        assert compile_cache.configure() == "/somewhere/else"
        assert os.environ[compile_cache.ENV] == "/somewhere/else"

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        from ray_tpu._private import compile_cache
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        monkeypatch.setitem(sys.modules, "jax", None)  # as before jax loads
        assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
        assert os.environ[compile_cache.ENV] == compile_cache.DEFAULT_DIR

    def test_import_settles_it_before_jax_in_a_fresh_process(self):
        code = ("import os, sys; os.environ.pop('JAX_COMPILATION_CACHE_DIR',"
                " None); import ray_tpu; assert 'jax' not in sys.modules; "
                "import jax; print(jax.config.jax_compilation_cache_dir)")
        out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == os.path.join(REPO, ".jax_cache")


class TestChipsOnlyByGrant:
    @pytest.mark.parametrize("chips", [0, -1])
    def test_use_tpu_without_chips_is_an_error(self, chips):
        from ray_tpu.train import ScalingConfig
        with pytest.raises(ValueError, match="chips_per_worker"):
            ScalingConfig(use_tpu=True, chips_per_worker=chips)
        assert ScalingConfig(use_tpu=True, chips_per_worker=1).use_tpu

    @pytest.mark.parametrize("grant,host,bounds", [
        ([0], 4, "1,1,1"), ([2, 3], 4, "1,2,1"),
        ([0], 1, None), ([0, 1, 2, 3], 4, None), ([0], None, None)])
    def test_sub_host_grant_sets_process_bounds(self, grant, host, bounds):
        from ray_tpu.accelerators.tpu import TPUAcceleratorManager
        env = TPUAcceleratorManager.visibility_env(grant, host_chips=host)
        assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, grant))
        assert env.get("TPU_CHIPS_PER_HOST_BOUNDS") == bounds
        assert env.get("TPU_HOST_BOUNDS") == ("1,1,1" if bounds else None)

    @pytest.mark.parametrize("env,chips,raises", [
        ({"RAY_TPU_WORKER_ID": "ab"}, 1, True),        # placed, no grant
        ({"RAY_TPU_WORKER_ID": "ab"}, 0, False),       # no chips on host
        ({"RAY_TPU_WORKER_ID": "ab", "TPU_VISIBLE_CHIPS": "0"}, 1, False),
        ({"RAY_TPU_WORKER_ID": "ab", "JAX_PLATFORMS": "cpu"}, 1, False),
        ({}, 1, False),                                # caller's own process
    ])
    def test_llm_replica_needs_a_grant_where_chips_exist(
            self, monkeypatch, env, chips, raises):
        from ray_tpu._private.config import Config
        from ray_tpu.llm import serving
        for k in ("RAY_TPU_WORKER_ID", "TPU_VISIBLE_CHIPS", "JAX_PLATFORMS"):
            monkeypatch.delenv(k, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        monkeypatch.setattr(
            Config, "get", classmethod(
                lambda cls, name, _orig=Config.get:
                chips if name == "tpu_chips_per_host_override"
                else _orig(name)))
        if raises:
            with pytest.raises(RuntimeError, match="num_tpus=1"):
                serving._require_chip_grant()
        else:
            serving._require_chip_grant()


class TestWaitForChips:
    """A killed chip worker keeps its device nodes for seconds; the next
    process's backend waits for them, bounded, and only where its grant
    is the whole host."""

    @pytest.fixture
    def nodes(self, monkeypatch):
        """Two chip nodes; opening ``busy`` ones says EBUSY ``n`` times."""
        import errno
        from ray_tpu.accelerators import tpu
        state = {"busy": {}, "opened": []}

        def fake_open(path, flags):
            if state["busy"].get(path, 0) > 0:
                state["busy"][path] -= 1
                raise OSError(errno.EBUSY, "Device or resource busy")
            state["opened"].append(path)
            return 99

        monkeypatch.setattr(tpu.glob, "glob",
                            lambda pat: ["/dev/vfio/0", "/dev/vfio/1"])
        monkeypatch.setattr(tpu.os, "open", fake_open)
        monkeypatch.setattr(tpu.os, "close", lambda fd: None)
        monkeypatch.setattr("time.sleep", lambda s: None)
        return state

    def test_waits_until_a_busy_node_opens(self, nodes):
        from ray_tpu.accelerators.tpu import wait_for_chips
        nodes["busy"]["/dev/vfio/1"] = 3
        assert wait_for_chips([0, 1]) >= 0.0
        assert nodes["opened"] == ["/dev/vfio/0", "/dev/vfio/1"]
        assert nodes["busy"]["/dev/vfio/1"] == 0

    @pytest.mark.parametrize("granted", [None, [], [0]])
    def test_a_grant_of_part_of_the_host_waits_for_nothing(self, nodes,
                                                           granted):
        from ray_tpu.accelerators.tpu import wait_for_chips
        nodes["busy"]["/dev/vfio/1"] = 10 ** 9   # a sibling worker's chip
        assert wait_for_chips(granted) == 0.0
        assert nodes["opened"] == []

    def test_gives_up_at_its_limit(self, nodes):
        from ray_tpu.accelerators.tpu import wait_for_chips
        nodes["busy"]["/dev/vfio/0"] = 10 ** 9
        assert wait_for_chips([0, 1], timeout_s=0.05) >= 0.05
        assert nodes["opened"] == []             # the limit covers all


class TestNoFallback:
    def test_on_tpu_reads_the_platform_and_swallows_nothing(self,
                                                            monkeypatch):
        import importlib

        import jax
        mod = importlib.import_module("ray_tpu.ops.attention")
        assert mod._on_tpu() is False           # the CPU, really read

        def broken():
            raise RuntimeError("backend failed to initialize")
        monkeypatch.setattr(jax, "default_backend", broken)
        with pytest.raises(RuntimeError, match="failed to initialize"):
            mod._on_tpu()

    def test_chip_smoke_fails_without_an_accelerator(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            cwd=REPO, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout

    def test_chip_smoke_alone_in_a_directory_fails(self, tmp_path):
        import shutil
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout

    def test_chip_smoke_driver_never_imports_jax(self):
        import ast
        with open(os.path.join(REPO, "chip_smoke.py")) as f:
            tree = ast.parse(f.read())
        top = [n for n in tree.body
               if isinstance(n, (ast.Import, ast.ImportFrom))]
        names = {a.name.split(".")[0] for n in top
                 if isinstance(n, ast.Import) for a in n.names}
        names |= {(n.module or "").split(".")[0] for n in top
                  if isinstance(n, ast.ImportFrom)}
        assert not names & {"jax", "jaxlib", "numpy", "ray_tpu"}


class TestFlashOnAMesh:
    @pytest.fixture(scope="class")
    def qkv(self):
        import jax
        import jax.numpy as jnp
        ks = jax.random.split(jax.random.key(0), 3)
        return tuple(jax.random.normal(k, (4, 4, 128, 32), jnp.float32)
                     for k in ks)

    @pytest.mark.parametrize("spec", ["fsdp4", "dp2xfsdp2xtp2"])
    def test_island_matches_reference_forward_and_backward(self, qkv, spec):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from ray_tpu.ops.attention import attention, reference_attention
        from ray_tpu.parallel import build_mesh
        from ray_tpu.train import MeshConfig

        n = 4 if spec == "fsdp4" else 8
        mesh = build_mesh(MeshConfig.parse(spec).spec_for(n),
                          devices=jax.devices()[:n])

        def loss(fn):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

        got = jax.jit(jax.value_and_grad(loss(lambda q, k, v: attention(
            q, k, v, impl="flash_interpret", mesh=mesh)), (0, 1, 2)))(*qkv)
        want = jax.value_and_grad(loss(reference_attention), (0, 1, 2))(*qkv)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)

    def test_rows_call_on_a_mesh_is_turned_at_the_islands_edge(self):
        """The island takes head-major arrays (the models say ``rows`` on
        one device only); a call that says ``rows`` on a mesh has v turned
        at the edge and the result back, and every gradient in its
        operand's layout."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from ray_tpu.ops.attention import attention, reference_attention
        from ray_tpu.parallel import build_mesh
        from ray_tpu.train import MeshConfig

        ks = jax.random.split(jax.random.key(1), 3)
        q, k, v = (jax.random.normal(k, (4, 4, 128, 128), jnp.float32)
                   for k in ks)
        mesh = build_mesh(MeshConfig.parse("dp2xfsdp2xtp2").spec_for(8),
                          devices=jax.devices()[:8])
        turn = lambda x: jnp.swapaxes(x, 1, 2)

        def loss(fn):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

        got = jax.jit(jax.value_and_grad(loss(lambda q, k, v: attention(
            q, k, v, impl="flash_interpret", mesh=mesh, rows=True)),
            (0, 1, 2)))(q, k, turn(v))
        want = jax.value_and_grad(loss(reference_attention), (0, 1, 2))(
            q, k, v)
        np.testing.assert_allclose(got[0], want[0], rtol=2e-4)
        for g, w in zip((got[1][0], got[1][1], turn(got[1][2])), want[1]):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)

    def test_sequence_sharded_mesh_is_refused(self, qkv):
        import jax
        from ray_tpu.ops.attention import attention
        from ray_tpu.parallel import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(sp=2), devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="ring"):
            attention(*qkv, impl="flash_interpret", mesh=mesh)
