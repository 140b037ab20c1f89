"""JAX correctness/performance lint family (RT5xx) + host-sync tripwire:
per-rule true-positive/clean-negative/suppression triples, CFG taint
units, the runtime tripwire (injected sync, flight-recorder bundle, CLI
table), the rl hot-path sync regressions the rules caught, and the
TrackedFunction jit-kwarg forwarding."""

from __future__ import annotations

import ast
import json
import os

import numpy as np
import pytest

from ray_tpu.devtools import lint_source
from ray_tpu.devtools import syncdebug
from ray_tpu.devtools.rules_jax import _taint_with_cfg, traced_taint


def rule_ids(src, path="<snippet>"):
    return [f.rule for f in lint_source(src, path=path)]


# -- RT501: Python control flow on a traced value ---------------------------


class TestTracedControlFlowRT501:
    BAD = """
import jax

@jax.jit
def step(x):
    if x.sum() > 0:
        return x * 2
    return x
"""

    GOOD = """
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    return jnp.where(x.sum() > 0, x * 2, x)
"""

    def test_positive(self):
        findings = lint_source(self.BAD)
        assert [f.rule for f in findings] == ["RT501"]
        assert findings[0].line == 6

    def test_negative(self):
        assert rule_ids(self.GOOD) == []

    def test_shape_branch_is_static(self):
        # x.shape/x.ndim are trace-time constants: branching on them is
        # the blessed pattern, not a concretization.
        src = """
import jax

@jax.jit
def step(x):
    if x.ndim > 1:
        return x.reshape(-1)
    return x
"""
        assert rule_ids(src) == []

    def test_static_argnum_param_not_traced(self):
        src = """
from functools import partial

import jax

@partial(jax.jit, static_argnums=(1,))
def step(x, k):
    if k > 0:
        return x * k
    return x
"""
        assert rule_ids(src) == []

    def test_membership_test_is_static(self):
        # `in`/`is` compares resolve at trace time (dict keys, None
        # checks); only value comparisons concretize.
        src = """
import jax

@jax.jit
def step(batch):
    if "mask" in batch:
        return batch["x"] * batch["mask"]
    return batch["x"]
"""
        assert rule_ids(src) == []

    def test_while_on_traced_value(self):
        src = """
import jax

@jax.jit
def countdown(x):
    while x.sum() > 0:
        x = x - 1
    return x
"""
        assert rule_ids(src) == ["RT501"]

    def test_suppression(self):
        src = self.BAD.replace("if x.sum() > 0:",
                               "if x.sum() > 0:  # ray-tpu: noqa[RT501]")
        assert rule_ids(src) == []


class TestTracedTaintCfg:
    """Units for the may-be-traced CFG fixpoint RT501 runs on."""

    def _taint_entering(self, src, initial, stmt_src):
        fn = ast.parse(src).body[0]
        cfg, inset = _taint_with_cfg(fn, set(initial))
        for node in cfg.nodes:
            if node.stmt is not None and \
                    ast.get_source_segment(src, node.stmt) == stmt_src:
                return inset[node.idx]
        raise AssertionError(f"no CFG node for {stmt_src!r}")

    def test_branch_join_is_union(self):
        # z traced in ONE branch -> traced after the join (may-analysis).
        src = (
            "def f(x, y):\n"
            "    if y:\n"
            "        z = x * 2\n"
            "    else:\n"
            "        z = 1\n"
            "    w = z\n"
            "    return w\n")
        assert "z" in self._taint_entering(src, {"x"}, "w = z")
        assert "w" in self._taint_entering(src, {"x"}, "return w")

    def test_rebind_kills_taint(self):
        src = (
            "def f(x):\n"
            "    y = x + 1\n"
            "    x = 0\n"
            "    z = x\n"
            "    return z\n")
        entering_ret = self._taint_entering(src, {"x"}, "return z")
        assert "y" in entering_ret
        assert "x" not in entering_ret and "z" not in entering_ret

    def test_static_attrs_launder(self):
        # x.shape is a host int: assigning from it does NOT taint.
        src = (
            "def f(x):\n"
            "    n = x.shape[0]\n"
            "    return n\n")
        assert "n" not in self._taint_entering(src, {"x"}, "return n")

    def test_loop_carried_taint(self):
        # Taint introduced inside a loop body reaches the loop head on
        # the back edge (fixpoint, not single pass).
        src = (
            "def f(x, items):\n"
            "    acc = 0\n"
            "    for it in items:\n"
            "        acc = acc + x\n"
            "    return acc\n")
        assert "acc" in self._taint_entering(src, {"x"}, "return acc")

    def test_public_wrapper_shape(self):
        fn = ast.parse("def f(x):\n    return x\n").body[0]
        taint = traced_taint(fn, {"x"})
        assert isinstance(taint, dict)
        assert any("x" in s for s in taint.values())


# -- RT502: implicit host sync per loop iteration ---------------------------


class TestHostSyncRT502:
    BAD = """
import jax
import jax.numpy as jnp

def metrics_loop(batches, fn):
    out = []
    for b in batches:
        m = jnp.sum(fn(b))
        out.append(float(m))
    return out
"""

    GOOD = """
import jax
import jax.numpy as jnp

def metrics_loop(batches, fn):
    dev = [jnp.sum(fn(b)) for b in batches]
    host = jax.device_get(dev)
    return [float(v) for v in host]
"""

    def test_positive(self):
        findings = lint_source(self.BAD)
        assert [f.rule for f in findings] == ["RT502"]
        assert findings[0].line == 9
        assert "float" in findings[0].message

    def test_negative_batched_transfer(self):
        assert rule_ids(self.GOOD) == []

    def test_single_coercion_outside_loop_ok(self):
        # ONE sync per call is the blessed pattern; only per-iteration
        # coercions are the storm.
        src = """
import jax.numpy as jnp

def loss_value(fn, batch):
    return float(jnp.sum(fn(batch)))
"""
        assert rule_ids(src) == []

    def test_jitted_def_skipped(self):
        # Inside jit a float() raises TracerError -> RT501 territory,
        # not a runtime sync.
        src = """
import jax
import jax.numpy as jnp

@jax.jit
def step(xs):
    out = 0.0
    for i in range(4):
        out = out + jnp.sum(xs) * i
    return out
"""
        assert "RT502" not in rule_ids(src)

    def test_suppression(self):
        src = self.BAD.replace(
            "out.append(float(m))",
            "out.append(float(m))  # ray-tpu: noqa[RT502]")
        assert rule_ids(src) == []


# -- RT503: shape-unstable jit call site ------------------------------------


class TestShapeUnstableRT503:
    BAD = """
import jax
import jax.numpy as jnp

@jax.jit
def decode_fn(x):
    return x * 2

def run(stream):
    buf = []
    for tok in stream:
        buf.append(tok)
        logits = decode_fn(jnp.asarray(buf))
    return logits
"""

    GOOD = """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def decode_fn(x):
    return x * 2

def run(stream, max_len):
    buf = np.zeros((max_len,), np.int32)
    for i, tok in enumerate(stream):
        buf[i] = tok
        logits = decode_fn(jnp.asarray(buf))
    return logits
"""

    def test_positive(self):
        findings = lint_source(self.BAD)
        assert [f.rule for f in findings] == ["RT503"]
        assert findings[0].line == 13

    def test_negative_fixed_buffer(self):
        assert rule_ids(self.GOOD) == []

    def test_suppression(self):
        src = self.BAD.replace(
            "logits = decode_fn(jnp.asarray(buf))",
            "logits = decode_fn(jnp.asarray(buf))  "
            "# ray-tpu: noqa[RT503]")
        assert rule_ids(src) == []


# -- RT504: donated buffer read after the call ------------------------------


class TestDonatedReadRT504:
    BAD = """
import jax

step = jax.jit(lambda p, b: p, donate_argnums=(0,))

def train(params, batch):
    new_params = step(params, batch)
    norm = params["w"]
    return new_params, norm
"""

    GOOD = """
import jax

step = jax.jit(lambda p, b: p, donate_argnums=(0,))

def train(params, batch):
    params = step(params, batch)
    norm = params["w"]
    return params, norm
"""

    def test_positive(self):
        findings = lint_source(self.BAD)
        assert [f.rule for f in findings] == ["RT504"]
        assert findings[0].line == 8
        assert "params" in findings[0].message

    def test_negative_rebind_over_donation(self):
        assert rule_ids(self.GOOD) == []

    def test_suppression(self):
        src = self.BAD.replace(
            'norm = params["w"]',
            'norm = params["w"]  # ray-tpu: noqa[RT504]')
        assert rule_ids(src) == []


# -- RT505: PRNG key reuse --------------------------------------------------


class TestPrngReuseRT505:
    BAD = """
import jax

def sample(key, shape):
    a = jax.random.normal(key, shape)
    b = jax.random.normal(key, shape)
    return a + b
"""

    GOOD = """
import jax

def sample(key, shape):
    key, s1 = jax.random.split(key)
    a = jax.random.normal(s1, shape)
    key, s2 = jax.random.split(key)
    b = jax.random.normal(s2, shape)
    return a + b
"""

    def test_positive(self):
        findings = lint_source(self.BAD)
        assert [f.rule for f in findings] == ["RT505"]
        assert findings[0].line == 6

    def test_negative_split_between(self):
        assert rule_ids(self.GOOD) == []

    def test_loop_without_refresh(self):
        src = """
import jax

def rollout(key, n, shape):
    outs = []
    for _ in range(n):
        outs.append(jax.random.normal(key, shape))
    return outs
"""
        assert rule_ids(src) == ["RT505"]

    def test_loop_with_refresh_ok(self):
        src = """
import jax

def rollout(key, n, shape):
    outs = []
    for _ in range(n):
        key, sub = jax.random.split(key)
        outs.append(jax.random.normal(sub, shape))
    return outs
"""
        assert rule_ids(src) == []

    def test_suppression(self):
        src = self.BAD.replace(
            "b = jax.random.normal(key, shape)",
            "b = jax.random.normal(key, shape)  # ray-tpu: noqa[RT505]")
        assert rule_ids(src) == []


# -- RT506: op-by-op dispatch in a hot loop ---------------------------------


class TestOpByOpRT506:
    BAD = """
import jax.numpy as jnp

def fwd_loop(stream, w1, b1, w2):
    for batch in stream:
        h = jnp.dot(batch, w1)
        h = jnp.tanh(h + b1)
        out = jnp.dot(h, w2)
    return out
"""

    GOOD = """
import jax
import jax.numpy as jnp

@jax.jit
def fwd(batch, w1, b1, w2):
    return jnp.dot(jnp.tanh(jnp.dot(batch, w1) + b1), w2)

def fwd_loop(stream, w1, b1, w2):
    for batch in stream:
        out = fwd(batch, w1, b1, w2)
    return out
"""

    def test_positive(self):
        findings = lint_source(self.BAD)
        assert [f.rule for f in findings] == ["RT506"]
        assert findings[0].line == 5

    def test_negative_jitted(self):
        assert rule_ids(self.GOOD) == []

    def test_glue_ops_under_threshold_ok(self):
        # 1-2 ops around an already-jitted call is glue, not op-by-op.
        src = """
import jax.numpy as jnp

def loop(stream, fn):
    for batch in stream:
        out = fn(jnp.asarray(batch))
    return out
"""
        assert rule_ids(src) == []

    def test_suppression(self):
        src = self.BAD.replace("for batch in stream:",
                               "for batch in stream:  "
                               "# ray-tpu: noqa[RT506]")
        assert rule_ids(src) == []


# -- catalog / explain surfaces ---------------------------------------------


class TestRuleSurfaces:
    RULES = ("RT501", "RT502", "RT503", "RT504", "RT505", "RT506")

    def test_rules_in_catalog(self):
        from ray_tpu.devtools.lint import rule_catalog_text
        text = rule_catalog_text()
        for rid in self.RULES:
            assert rid in text

    def test_explain_has_rationale_and_examples(self):
        from ray_tpu.devtools.lint import explain_text
        for rid in self.RULES:
            text = explain_text(rid)
            assert text is not None, rid
            assert "noqa" in text, rid


# -- runtime tripwire -------------------------------------------------------


@pytest.fixture
def tripwire():
    syncdebug.install()
    assert syncdebug.is_installed()
    syncdebug.clear()
    yield syncdebug
    syncdebug.uninstall()
    syncdebug.clear()


class TestSyncTripwire:
    def test_records_and_attributes_syncs(self, tripwire):
        import jax.numpy as jnp
        x = jnp.arange(8.0)
        v = float(jnp.sum(x))        # injected implicit sync
        assert v == 28.0
        rep = tripwire.report()
        assert rep["installed"] is True
        assert rep["total_syncs"] >= 1
        mine = [r for r in rep["sites"]
                if r["site"].startswith(os.path.basename(__file__))]
        assert mine, rep["sites"]
        assert mine[0]["kind"] == "__float__"
        assert mine[0]["count"] == 1
        assert mine[0]["total_s"] > 0.0
        assert sum(mine[0]["hist"]) == 1
        assert len(rep["bucket_bounds_s"]) + 1 == len(mine[0]["hist"])

    def test_cached_value_takes_fast_path(self, tripwire):
        import jax.numpy as jnp
        s = jnp.sum(jnp.arange(4.0))
        float(s)                      # real sync caches _npy_value
        before = tripwire.report()
        float(s)                      # cached -> no new site count
        after = tripwire.report()
        assert after["total_syncs"] == before["total_syncs"]
        assert after["cached_fastpath"] > before["cached_fastpath"]

    def test_nested_coercion_counted_once(self, tripwire):
        import jax.numpy as jnp
        jnp.arange(4.0).tolist()      # tolist drives __array__ inside
        rep = tripwire.report()
        mine = [r for r in rep["sites"]
                if r["site"].startswith(os.path.basename(__file__))]
        assert len(mine) == 1
        assert mine[0]["kind"] == "tolist"
        assert mine[0]["count"] == 1

    def test_uninstall_restores_originals(self):
        from jax._src.array import ArrayImpl
        syncdebug.install()
        assert hasattr(ArrayImpl.__float__, "_ray_tpu_sync_orig")
        syncdebug.uninstall()
        assert not hasattr(ArrayImpl.__float__, "_ray_tpu_sync_orig")
        syncdebug.clear()

    def test_bundle_contains_sync_findings(self, tripwire, tmp_path):
        import jax.numpy as jnp
        from ray_tpu._private.diagnostics import write_debug_bundle

        float(jnp.sum(jnp.arange(4.0)))

        class _Rt:
            session_dir = str(tmp_path)
        path = write_debug_bundle(_Rt(), "sync_tripwire_test",
                                  capture_stacks=False)
        with open(os.path.join(path, "sync_findings.json")) as f:
            doc = json.load(f)
        assert doc["installed"] is True
        assert doc["total_syncs"] >= 1
        assert any(r["site"].startswith(os.path.basename(__file__))
                   for r in doc["sites"])
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert "sync_findings.json" in manifest["contents"]

    def test_format_and_cli_sync_report(self, tripwire, tmp_path):
        import jax.numpy as jnp
        float(jnp.sum(jnp.arange(4.0)))
        doc = tripwire.report()
        table = syncdebug.format_sync(doc)
        assert "site" in table and "__float__" in table

        from click.testing import CliRunner
        from ray_tpu.scripts.cli import cli
        p = tmp_path / "sync_findings.json"
        p.write_text(json.dumps(doc))
        r = CliRunner().invoke(cli, ["lint", "--sync-report", str(p)])
        assert r.exit_code == 0
        assert "__float__" in r.output
        r = CliRunner().invoke(cli, ["lint", "--sync-report",
                                     str(tmp_path / "missing.json")])
        assert r.exit_code == 2

    def test_empty_report_renders(self):
        out = syncdebug.format_sync({"installed": False, "sites": [],
                                     "cached_fastpath": 0})
        assert "no host syncs" in out


# -- rl hot-path regressions (the defects RT502 caught) ---------------------


class _LinMod:
    def init(self, key):
        import jax
        return {"w": jax.random.normal(key, (4,))}


def _lin_loss(module, params, batch):
    import jax.numpy as jnp
    pred = batch["x"] @ params["w"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, {"mse": loss}


_SCALAR_KINDS = {"__float__", "__int__", "__bool__", "__index__", "item"}


class TestRlSyncRegressions:
    def test_old_learner_pattern_still_flagged(self):
        # The pre-fix learner shape: per-metric float() on a device
        # dict inside the update loop.  The rule must keep catching it.
        src = """
import jax

step = jax.jit(lambda p, s, b: (p, s, {"loss": 0.0}))

def train_loop(params, opt_state, batches):
    history = []
    for batch in batches:
        params, opt_state, metrics = step(params, opt_state, batch)
        history.append({k: float(v) for k, v in metrics.items()})
    return history
"""
        assert "RT502" in rule_ids(src)

    def test_learner_update_is_one_batched_transfer(self, tripwire):
        from ray_tpu.rl.learner import JaxLearner
        learner = JaxLearner(_LinMod(), _lin_loss, learning_rate=1e-2)
        batch = {"x": np.ones((8, 4), np.float32),
                 "y": np.zeros((8,), np.float32)}
        learner.update(batch)          # compile outside the window
        tripwire.clear()
        metrics = learner.update(batch)
        assert all(isinstance(v, float) for v in metrics.values())
        rows = [r for r in tripwire.report()["sites"]
                if r["site"].startswith("learner.py")]
        # All learner syncs are the ONE device_get line (__array__ per
        # metric leaf); the old per-value float() storm would show up
        # as scalar-coercion kinds here.
        assert rows, "expected the batched device_get to be attributed"
        assert {r["kind"] for r in rows} == {"__array__"}
        assert len({r["site"] for r in rows}) == 1

    def test_env_runner_sample_no_scalar_syncs(self, tripwire):
        from ray_tpu.rl import CartPole, EnvRunner
        runner = EnvRunner(CartPole, num_envs=2, seed=0)
        runner.sample(4)               # compile outside the window
        tripwire.clear()
        batch = runner.sample(8)
        assert batch["obs"].shape[0] == 8
        rows = [r for r in tripwire.report()["sites"]
                if r["site"].startswith("env_runner.py")]
        # Pre-fix: 3 per-array np.asarray syncs per env step.  Fixed:
        # one batched device_get site, never a scalar coercion.
        assert rows
        assert not [r for r in rows if r["kind"] in _SCALAR_KINDS]
        assert len({r["site"] for r in rows}) == 1

    def test_fixed_rl_modules_lint_clean(self):
        # Source-level regression: the swept hot-path modules stay at
        # zero RT5xx findings.
        import ray_tpu.rl as rl
        pkg = os.path.dirname(os.path.abspath(rl.__file__))
        for mod in ("learner.py", "env_runner.py", "dqn.py", "sac.py",
                    "offline.py", "multi_agent.py"):
            path = os.path.join(pkg, mod)
            with open(path, encoding="utf-8") as f:
                findings = lint_source(f.read(), path=path,
                                       internal=True)
            rt5 = [f for f in findings if f.rule.startswith("RT5")]
            assert not rt5, f"{mod}: {[(f.rule, f.line) for f in rt5]}"


# -- TrackedFunction jit-kwarg forwarding -----------------------------------


@pytest.fixture
def recompile_detector():
    from ray_tpu.profiler import recompile
    recompile._reset_for_tests()
    recompile.install()
    yield recompile
    recompile.uninstall()
    recompile._reset_for_tests()


class TestTrackedJitKwargs:
    def test_static_argnums_forwarded(self, recompile_detector):
        import jax
        import jax.numpy as jnp

        def pow_fn(x, k):
            return x ** k
        f = recompile_detector.track(jax.jit(pow_fn, static_argnums=(1,)),
                                     static_argnums=(1,))
        assert isinstance(f, recompile_detector.TrackedFunction)
        assert f.static_argnums == (1,)
        f(jnp.ones((4,)), 2)
        f(jnp.ones((4,)), 2)           # cache hit -> warm
        f(jnp.ones((4,)), 3)           # static change -> recompile
        rep = recompile_detector.report()["pow_fn"]
        assert rep["static_argnums"] == [1]
        assert rep["recompiles"] == 1
        assert "static([1]=3)" in rep["last_signature"]
        # Static args are signature'd by VALUE, traced args by shape.
        assert rep["last_signature"].startswith("(float32[4])")

    def test_static_argnames_forwarded(self, recompile_detector):
        import jax
        import jax.numpy as jnp

        def mode_fn(x, mode=None):
            return x + (1 if mode == "a" else 2)
        g = recompile_detector.track(
            jax.jit(mode_fn, static_argnames=("mode",)),
            static_argnames=("mode",))
        assert g.static_argnames == ("mode",)
        g(jnp.ones((4,)), mode="a")
        rep = recompile_detector.report()["mode_fn"]
        assert rep["static_argnames"] == ["mode"]
        assert "static(mode='a')" in rep["last_signature"]

    def test_donate_argnums_forwarded(self, recompile_detector):
        import jax
        import jax.numpy as jnp

        def don_fn(x):
            return x * 2
        h = recompile_detector.track(jax.jit(don_fn, donate_argnums=(0,)),
                                     donate_argnums=(0,))
        assert h.donate_argnums == (0,)
        h(jnp.ones((4,)))
        assert recompile_detector.report()["don_fn"][
            "donate_argnums"] == [0]

    def test_static_change_warns_as_expected_recompile(
            self, recompile_detector, caplog):
        import logging

        import jax
        import jax.numpy as jnp

        def k_fn(x, k):
            return x * k
        f = recompile_detector.track(jax.jit(k_fn, static_argnums=(1,)),
                                     name="k_fn_site",
                                     static_argnums=(1,))
        f(jnp.ones((4,)), 2)
        f(jnp.ones((4,)), 2)
        with caplog.at_level(logging.WARNING, logger="ray_tpu.profiler"):
            f(jnp.ones((4,)), 5)
        assert any("STATIC argument" in r.message
                   for r in caplog.records), caplog.records
