"""The ``deepseek_v3`` decoder (DeepSeek-V3's block; the defaults are
kanana-2-30b-a3b's published ``config.json``): latent attention on a plain
pre-norm stream, one leading dense SwiGLU layer, then sigmoid-routed
dropless experts (``noaux_tc`` with one group: the top k of score + bias,
weights ``score / sum x routed_scaling_factor``) beside the shared experts,
which the published code builds as ONE SwiGLU ``n_shared_experts`` times an
expert's width.

A thin front: the model is ``models/xing4.py``'s stack at one lane
(``hc_mult`` 1), without the prediction module (``mtp_layers`` 0) and, for
kanana, without the query bottleneck (``q_lora_rank`` None) or yarn.  Latent
attention (``xing4._mla``), the expert layer (``afmoe._moe``) and the
layer-rows loop are that module's, as they are: nothing is copied here.
What this module owns is the configuration, with no knob of the mechanisms
that DeepSeek-V3's block lacks, and the step's report, which carries the
experts' loads alone.  The selection bias is state
(``init_state`` / ``update_state``), a layer may hold a share of its experts
(``experts_held`` from ``held_start``), and a mesh of more than one device
is refused, as by the other sparse models.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import afmoe, xing4
from ..ops.rope import Yarn


@dataclass(frozen=True)
class DeepseekV3Config:
    """Defaults are kanana-2-30b-a3b-instruct-2601's ``config.json``."""
    vocab_size: int = 128256
    hidden: int = 2048
    layers: int = 48
    heads: int = 32
    q_lora_rank: Optional[int] = None   # None: one ``wq`` [E, H, 192]
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 6144                 # the dense layer's SwiGLU
    moe_mlp_dim: int = 768              # every routed expert's
    num_experts: int = 128              # the router's width
    experts_held: Optional[int] = None  # None = all of them
    held_start: int = 0
    top_k: int = 6
    num_shared_experts: int = 2         # one SwiGLU of 2 x 768
    num_dense_layers: int = 1           # ``first_k_dense_replace``
    route_scale: float = 2.448          # ``routed_scaling_factor``
    route_norm: bool = True             # ``norm_topk_prob``
    bias_update_rate: float = 1e-3
    rope_theta: float = 1000000.0
    yarn: Optional[Yarn] = None         # ``rope_scaling``: null
    norm_eps: float = 1e-6
    max_seq_len: int = 8192             # the rotary tables' rows
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"        # as Xing4Config's
    moe_impl: Optional[str] = None      # ops/moe.grouped_matmul
    remat: Any = True                   # _lm.remat
    layer_rows: Optional[int] = None    # as AfmoeConfig's
    loss_chunks: int = 0
    pp_microbatches: int = 0            # refused, as xing4's

    def replace(self, **kw) -> "DeepseekV3Config":
        return dataclasses.replace(self, **kw)

    @property
    def stack(self) -> xing4.Xing4Config:
        """The shared stack's configuration of this model: every field of
        this one, one lane and no prediction module."""
        return xing4.Xing4Config(hc_mult=1, mtp_layers=0, **{
            f.name: getattr(self, f.name) for f in dataclasses.fields(self)})

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_layers(self) -> int:
        return self.layers - self.num_dense_layers


def deepseek_v3_tiny(**kw) -> DeepseekV3Config:
    """A CPU-test size that keeps what the kernels must tell apart: head
    sizes 192 / 128 (128 + 64 rotary), 16 experts with 6 a token beside two
    shared, 1 dense + 2 expert layers."""
    return DeepseekV3Config(**{**dict(
        vocab_size=256, hidden=64, layers=3, heads=2, kv_lora_rank=32,
        mlp_dim=96, moe_mlp_dim=32, num_experts=16, max_seq_len=64,
        dtype=jnp.float32, attention_impl="reference", remat=False), **kw})


def param_shapes(cfg: DeepseekV3Config) -> Dict[str, Any]:
    return xing4.param_shapes(cfg.stack)


def param_logical_axes(cfg: DeepseekV3Config) -> Dict[str, Any]:
    return xing4.param_logical_axes(cfg.stack)


def init_params(cfg: DeepseekV3Config, key: jax.Array,
                param_dtype=jnp.float32) -> Dict[str, Any]:
    return xing4.init_params(cfg.stack, key, param_dtype)


def num_params(cfg: DeepseekV3Config) -> int:
    return xing4.num_params(cfg.stack)


def init_state(cfg: DeepseekV3Config) -> Dict[str, jax.Array]:
    """The routers' selection bias, float32 [expert layers, experts]."""
    return xing4.init_state(cfg.stack)


def forward(params, tokens, cfg: DeepseekV3Config, state=None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32."""
    return xing4.forward(params, tokens, cfg.stack, state)


def loss_and_report(params, batch, cfg: DeepseekV3Config, state=None):
    """What the train step differentiates (parallel.spmd): the masked mean
    next-token loss, no auxiliary term, and the expert layers' loads."""
    loss, report = xing4.loss_and_report(params, batch, cfg.stack, state)
    return loss, {k: report[k] for k in ("counts", "dropped", "sliced",
                                         "top")}


def loss_fn(params, batch, cfg: DeepseekV3Config, state=None) -> jax.Array:
    return loss_and_report(params, batch, cfg, state)[0]


def update_state(state, loads, cfg: DeepseekV3Config):
    """``afmoe.update_state``: the selection bias after the step, and the
    step's metrics of its experts' loads."""
    return afmoe.update_state(state, loads, cfg.stack)
