"""SPMD training-step builder: model + mesh + rules -> compiled pjit step.

Replaces the reference's ``prepare_model`` DDP wrapping (reference:
python/ray/train/torch/train_loop_utils.py:153 wraps in
DistributedDataParallel over a NCCL process group) with the GSPMD recipe:
params/batch get NamedShardings from the logical-axis rules, the whole
fwd+bwd+update runs under one jit over the mesh, and XLA inserts the
gradient reduce-scatters/all-gathers implied by the layout — no explicit
collective calls in user code.
"""

from __future__ import annotations

import importlib
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

from ..util import telemetry
from .mesh import (AXIS_DATA, AXIS_FSDP, AXIS_SEQ, MeshSpec, build_mesh,
                   set_global_mesh)
from .sharding import (ShardingRules, default_rules, logical_to_pspec,
                       named_sharding)


def _mirror_param_shardings(opt_state_shape, params_shape,
                            param_shardings, mesh):
    """Sharding pytree for an optimizer state: each state leaf whose key
    path ends with a parameter's key path AND has that parameter's shape
    (optax's mu/nu mirror the param tree) takes the param's sharding;
    everything else — step counts, empty states, shape-reduced factored
    statistics like adafactor's v_row/v_col — replicates (a full-rank
    PartitionSpec pinned onto a reduced-rank leaf is a pjit error)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    flat, _ = jax.tree_util.tree_flatten_with_path(
        param_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    by_path = {tuple(str(k) for k in path): sh for path, sh in flat}
    pflat, _ = jax.tree_util.tree_flatten_with_path(params_shape)
    shape_by_path = {tuple(str(k) for k in path): leaf.shape
                     for path, leaf in pflat}

    def match(path, leaf):
        keys = tuple(str(k) for k in path)
        for start in range(len(keys)):
            sh = by_path.get(keys[start:])
            if sh is not None:
                if getattr(leaf, "shape", None) \
                        == shape_by_path.get(keys[start:]):
                    return sh
                return replicated
        return replicated

    return jax.tree_util.tree_map_with_path(match, opt_state_shape)


class StepState(NamedTuple):
    """The second argument of a step whose model carries state that no
    optimizer may touch (``init_state`` in its module): the optimizer's state
    and the model's, side by side.  What a step reports beside ``loss`` and
    ``grad_norm`` does not depend on it: that is the loss's to hand out
    (``loss_and_report``), with or without state."""
    opt: Any
    model: Any


def model_module(cfg):
    """The module that defines a model configuration's class, and with it
    the model: ``init_params``, ``param_logical_axes``, ``loss_fn``; where
    the loss hands out more than a scalar, ``loss_and_report(params, batch,
    cfg, state)`` -> (loss, report); and where the model carries state,
    ``init_state`` and ``update_state(state, report, cfg)`` -> (state,
    metrics) (models/llama.py, models/ouro.py, models/afmoe.py)."""
    return importlib.import_module(type(cfg).__module__)


def batch_pspec(mesh, rules: Optional[ShardingRules] = None):
    """Token batches: [B, S] -> (dp,fsdp) on batch, sp on seq."""
    import jax
    from jax.sharding import PartitionSpec as P
    rules = rules or default_rules()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    seq = AXIS_SEQ if axis_sizes.get(AXIS_SEQ, 1) > 1 else None
    return P((AXIS_DATA, AXIS_FSDP), seq)


def make_lm_train_step(cfg, mesh, *, rules: Optional[ShardingRules] = None,
                       optimizer=None, learning_rate: float = 3e-4,
                       donate: bool = True, param_dtype=None,
                       grad_accum: int = 1):
    """Build (init_fn, step_fn, place_batch) on ``mesh`` for the language
    model whose configuration ``cfg`` is: the model's ``init_params``,
    ``param_logical_axes`` and ``loss_fn`` are those of the module that
    defines ``cfg``'s class (``model_module``).

    init_fn(key) -> (params, opt_state) already sharded.
    step_fn(params, opt_state, batch) -> (params, opt_state, metrics).

    ``metrics`` holds ``loss``, ``grad_norm`` and whatever the model's loss
    reports: a module with ``loss_and_report`` returns (loss, report) from
    the compiled step's own forward pass, and the report's arrays join the
    metrics (ouro: ``loop_loss`` and ``loop_exit_share`` [loops],
    ``loop_exit_entropy``).  A model with state the optimizer must not touch
    (afmoe's selection bias: no gradient reaches it, and AdamW's weight
    decay would shrink it) goes the same way and gets ``StepState(opt,
    model)`` as its ``opt_state``: the step hands the model's part to the
    loss, and after the update the report to the model's ``update_state``,
    whose metrics are the ones that join (afmoe: the scalars
    ``moe_held_assignments``, ``moe_load_max_over_mean``, ``moe_dropped``,
    ``moe_sliced_calls`` and the routers' choices ``moe_choices`` [expert
    layers, tokens, k]).

    A batch is a dictionary of [B, S] arrays placed alike: ``tokens``, an
    optional ``loss_mask``, and since PR 65 an optional ``segment_ids`` (a
    packed row's documents, a run of equal ids one), which reaches the
    model's loss with the rest of the batch; a model that knows the key
    (models/granite_hybrid.py) stops its state, its convolution and its
    attention at a document's start, the others never read it.

    ``param_dtype`` overrides parameter (and hence optimizer-state)
    storage: bfloat16 halves the adamw footprint so ~1.5B params fit one
    v5e chip with remat (HBM budget: params+m+v at 2 bytes each).  Under
    ``remat=True`` / "full" a model's stack keeps the flash kernels' ``out``
    and ``lse`` of every attention call past the layers' recomputation where
    all of them together are at most a sixteenth of the device's
    ``bytes_limit``, and nothing but the layers' inputs otherwise
    (``models/_lm.flash_keep``; ``ray_tpu_remat_kept_total`` says which):
    a step that stood within a sixteenth of the limit while it recomputed
    everything can stop fitting, and fits again with fewer rows.

    ``grad_accum`` > 1 splits the batch's leading dim into that many
    microbatches, accumulating gradients in an f32 scan before ONE
    optimizer update — the effective batch is unchanged, but saved
    activations (and thus the remat policy's HBM bill) shrink by the
    same factor, which is what lets lighter-recompute policies like
    remat="mlp_only" fit a 16G chip at headline model sizes.  A loss that
    reports is refused with it: the accumulation sums losses and gradients,
    and knows no rule for a report.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    L = model_module(cfg)
    stateful = hasattr(L, "init_state")
    reports = hasattr(L, "loss_and_report")
    if reports and grad_accum > 1:
        raise NotImplementedError("grad_accum with a loss that reports "
                                  "metrics (ROADMAP)")

    rules = rules or default_rules()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if getattr(cfg, "pp_microbatches", 0) and axis_sizes.get("pp", 1) > 1:
        # Pipeline mode: shard the stacked layer axis over pp so each stage
        # holds its resident layers (see parallel/pipeline.py).
        rules = rules.replace(layers="pp")
    set_global_mesh(mesh)
    if optimizer is None:
        optimizer = optax.adamw(learning_rate, b1=0.9, b2=0.95,
                                weight_decay=0.1)

    logical = L.param_logical_axes(cfg)
    param_shardings = jax.tree.map(
        lambda ax: named_sharding(mesh, ax, rules), logical,
        is_leaf=lambda x: isinstance(x, tuple))
    bspec = batch_pspec(mesh, rules)
    bsharding = NamedSharding(mesh, bspec)

    def init_all(key):
        params = L.init_params(cfg, key) if param_dtype is None else \
            L.init_params(cfg, key, param_dtype=param_dtype)
        opt_state = optimizer.init(params)
        if stateful:
            opt_state = StepState(opt_state, L.init_state(cfg))
        return params, opt_state

    # Opt-state shardings are pinned EXPLICITLY to mirror the params
    # (mu/nu shard like their param — the ZeRO-style optimizer-state
    # sharding; scalars like adam's count replicate).  Leaving them to
    # GSPMD (out_shardings=None) lets init and step choose DIFFERENT
    # layouts, which breaks buffer donation at the first real
    # multi-device execution ("aliased input/output sub-shape size"
    # runtime errors) and silently double-materializes the state.
    params_shape, opt_state_shape = jax.eval_shape(
        init_all, jax.random.key(0))
    opt_shardings = _mirror_param_shardings(
        opt_state_shape, params_shape, param_shardings, mesh)
    _init_jit = jax.jit(init_all,
                        out_shardings=(param_shardings, opt_shardings))

    def init_fn(key):
        # Partitionable threefry for the sharded init only: the default
        # threefry lowering is NOT sharding-invariant under the SPMD
        # partitioner (the per-shard counter rewrite changes the bits),
        # so the same seed would yield different params on different
        # mesh shapes — an 8-way and a 1-device init must match.
        old = jax.config.jax_threefry_partitionable
        jax.config.update("jax_threefry_partitionable", True)
        try:
            return _init_jit(key)
        finally:
            jax.config.update("jax_threefry_partitionable", old)

    def train_step(params, opt_state, batch):
        model_state = None
        if stateful:
            opt_state, model_state = opt_state
        with jax.named_scope("forward_backward"):
            (loss, report), grads = loss_and_grads(params, model_state,
                                                   batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            gnorm = optax.global_norm(grads)
            if stateful:
                model_state, report = L.update_state(model_state, report,
                                                     cfg)
                opt_state = StepState(opt_state, model_state)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   **report}

    def loss_and_grads(params, model_state, batch):
        """((loss, what the loss reports), gradients)."""
        if reports:
            return jax.value_and_grad(L.loss_and_report, has_aux=True)(
                params, batch, cfg, model_state)
        if grad_accum > 1:
            def split(v):
                b = v.shape[0]
                assert b % grad_accum == 0, (b, grad_accum)
                return v.reshape((grad_accum, b // grad_accum)
                                 + v.shape[1:])

            micro = {k: split(v) for k, v in batch.items()}
            # Every microbatch normalizes by the FULL batch's unmasked
            # token count, so summed per-micro losses/grads equal the
            # unaccumulated step exactly even when masking is uneven
            # across microbatches.
            if "loss_mask" in batch:
                denom = jnp.maximum(
                    jnp.sum(batch["loss_mask"].astype(jnp.float32)), 1.0)
            else:
                t = batch["tokens"]
                denom = jnp.asarray(t.shape[0] * (t.shape[1] - 1),
                                    jnp.float32)
            micro["loss_denom"] = jnp.full((grad_accum,), denom)

            def acc_body(carry, mb):
                gsum, lsum = carry
                l, g = jax.value_and_grad(L.loss_fn)(params, mb, cfg)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), gsum, g)
                return (gsum, lsum + l), None

            # Accumulator in the params dtype: an f32 copy of a bf16
            # model's grads would cost 2 extra bytes/param of HBM — the
            # very budget grad_accum exists to free.
            gzero = jax.tree.map(jnp.zeros_like, params)
            (grads, loss), _ = jax.lax.scan(
                acc_body, (gzero, jnp.zeros((), jnp.float32)), micro)
            return (loss, {}), grads
        loss, grads = jax.value_and_grad(L.loss_fn)(params, batch, cfg)
        return (loss, {}), grads

    # The jitted function's name is the program's name in a device trace
    # (``jit_train_step``); the scopes inside are metadata only.
    step_fn = jax.jit(
        train_step,
        in_shardings=(param_shardings, opt_shardings, bsharding),
        out_shardings=(param_shardings, opt_shardings, None),
        donate_argnums=(0, 1) if donate else ())

    # The one call a step loop makes into the framework each step: its
    # spans, numbered from 0, give every step's period (start to start).
    # (Imported here: a line added above ``train_step`` moves the line
    # numbers that the kernels' compile-cache keys hold.)
    import itertools
    calls = itertools.count()

    def place_batch(batch: Dict[str, Any]):
        with telemetry.profile_span("train_place_batch", "train",
                                    extra={"step": next(calls)}):
            return {k: jax.device_put(v, bsharding)
                    for k, v in batch.items()}

    return init_fn, step_fn, place_batch


def make_lm_eval_step(cfg, mesh, *, rules: Optional[ShardingRules] = None):
    import jax
    from jax.sharding import NamedSharding

    L = model_module(cfg)

    rules = rules or default_rules()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if getattr(cfg, "pp_microbatches", 0) and axis_sizes.get("pp", 1) > 1:
        rules = rules.replace(layers="pp")
    set_global_mesh(mesh)
    logical = L.param_logical_axes(cfg)
    param_shardings = jax.tree.map(
        lambda ax: named_sharding(mesh, ax, rules), logical,
        is_leaf=lambda x: isinstance(x, tuple))
    bsharding = NamedSharding(mesh, batch_pspec(mesh, rules))

    def eval_step(params, batch):
        return L.loss_fn(params, batch, cfg)

    return jax.jit(eval_step, in_shardings=(param_shardings, bsharding))
