"""``trinity-mini.train-moe8k``'s train step compiles for a described v5e,
without a chip.  A file a cell: ``--dist loadfile`` keeps a file on one
worker, and the step is compiled here and nowhere else.  The fixtures and
the readers of a compiled program's text are ``tests/v5e_compile.py``'s,
imported: describing the topology happens inside the fixture, in the worker
that is given THIS file, never while a module is imported.
"""

from __future__ import annotations

import pytest

from v5e_compile import (  # noqa: F401 (``topo`` is a fixture)
    _assert_q_and_k_cross_hbm_once,
    _assert_rows_leave_the_experts_buffer_by_the_rows_in_use,
    _assert_the_experts_buffer_has, _cell_step, _kernels, topo)


@pytest.fixture(scope="module")
def trinity_step(topo):
    """``trinity-mini.train-moe8k``'s step (9 layers, 16 of 128 experts, 4
    rows of 8,192, full remat, flash, Pallas grouped products)."""
    from benchmark.archs import afmoe
    return _cell_step(topo, afmoe, "trinity-mini.json", 8192,
                      moe_impl="gmm")


def test_trinity_train_step_compiles_at_the_cell_sizes(trinity_step, capsys):
    """The step compiles for one described v5e chip; its memory is stated
    (the temporaries over-state what the runtime reserves)."""
    from ray_tpu.parallel.spmd import StepState

    compiled, text = trinity_step["compiled"], trinity_step["text"]
    assert isinstance(trinity_step["state"], StepState)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\ntrinity-mini.train-moe8k step for a described v5e: "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"{_kernels(compiled)} kernels")
    for name in ("flash_fwd_w2048", "flash_bwd_w2048", "flash_fwd",
                 "flash_bwd", "gmm", "tgmm"):
        assert name in text, name
    # Full and window layers alike take the backward's one pass under the
    # group of eight (PR 60).
    assert "flash_dq" not in text and "flash_dkv" not in text
    # bf16 weights and two bf16 moments of 1,243 M parameters.
    assert 7.4e9 < mem.argument_size_in_bytes < 7.6e9
    # 9.69 GB of temporaries with the scatters (PR 29); 9.71 GB since the
    # sums over a token's rows are kernels (PR 45).
    assert mem.temp_size_in_bytes < 10.5e9
    # Rows and counts move by gathers and dense passes alone.  (Upstream's
    # grouped matmul builds its tiles' table with a scatter-add of 47
    # places, under ``experts``: not ours.)
    scatters = [line for line in text.splitlines()
                if " scatter(" in line and "block/moe" in line
                and "/experts/" not in line]
    assert not scatters, scatters[:2]


def test_q_and_k_cross_hbm_once_in_the_sparse_step(trinity_step):
    """Trinity's window layers, a row at a time: q [1, 32, 8192, 128], k
    [1, 4, 8192, 128] (the flash kernels' view [4, 8, 8192, 128]); its full
    layers have no positions and only turn q and k head-major."""
    _assert_q_and_k_cross_hbm_once(
        trinity_step["text"], ("1,32,8192,128", "4,8,8192,128",
                               "1,4,8192,128", "4,8192,128"),
        ("1,32,8192,64", "1,4,8192,64"))


@pytest.mark.parametrize("T,k,E", [(8192, 8, 2048)], ids=["trinity"])
def test_rows_leave_the_experts_buffer_by_the_rows_in_use(trinity_step, T, k,
                                                         E):
    """The sums over a token's rows in this cell's compiled step (what is
    asserted: the helper's docstring)."""
    _assert_rows_leave_the_experts_buffer_by_the_rows_in_use(
        trinity_step["text"], T, k, E)


@pytest.mark.parametrize("impl", ["gmm", "ragged_dot"])
def test_an_eighth_share_layer_traces_what_the_four_tiers_traced(
        trinity_step, impl, monkeypatch):
    """Where an eighth of the experts is held (16 of 128 here) the buffer's
    rule gives the four tiers every share had until PR 63: the layer's
    jaxpr at this cell's shapes, forward and backward, is letter for letter
    the one the fixed rule traces (PR 63 read both equal to the parent
    commit's too, 484,636 characters under the Pallas products), and the
    compiled step holds the 16,384 rows it held."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import moe

    T, E, M, Xh, X, k = 8192, 2048, 1024, 16, 128, 8
    assert moe.buffer_rows(T, k, Xh, X) == moe.buffer_rows(T, k) == 16384

    def layer(xt, rw, wg, wu, wd):
        routing = moe.sigmoid_routing(xt, rw, jnp.zeros((X,)), k, 2.5)
        out, stats = moe.dropless_experts(xt, routing, wg, wu, wd, 0, impl)
        return jnp.sum(out.astype(jnp.float32)), stats

    def traced():
        S, bf16 = jax.ShapeDtypeStruct, jnp.bfloat16
        return str(jax.make_jaxpr(jax.grad(
            layer, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                S((T, E), bf16), S((E, X), jnp.float32), S((Xh, E, M), bf16),
                S((Xh, E, M), bf16), S((Xh, M, E), bf16)))

    now = traced()
    monkeypatch.setattr(moe, "buffer_tiers", lambda T, held=None,
                        routed=None: 1 if T % 4 else 4)
    assert traced() == now and f"[{T * k // 4},{E}]" in now
    if impl == "gmm":
        _assert_the_experts_buffer_has(trinity_step["text"], 16384, 4096)
