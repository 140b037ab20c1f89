"""Model zoo: pure-jax pytree models with logical-axis sharding annotations."""

from .llama import (LlamaConfig, init_params, forward, loss_fn,
                    param_logical_axes, llama_tiny, llama_125m, llama_1b,
                    llama_7b)
from .afmoe import AfmoeConfig
from .evabyte import EvaByteConfig, evabyte_tiny
from .xing4 import Xing4Config, xing4_tiny
from .deepseek_v3 import DeepseekV3Config, deepseek_v3_tiny
from .nemotron_h import NemotronHConfig, nemotron_h_tiny
from .lfm2 import Lfm2Config, lfm2_tiny
from .bailing_hybrid import BailingHybridConfig, bailing_hybrid_tiny
from .motif import MotifConfig, motif_tiny
from .mimo_v2 import MimoV2Config, mimo_v2_tiny
from .granite_hybrid import GraniteHybridConfig, granite_hybrid_tiny
from .mlp import MLPConfig, init_mlp, mlp_forward, mlp_loss

__all__ = [
    "LlamaConfig", "init_params", "forward", "loss_fn", "param_logical_axes",
    "llama_tiny", "llama_125m", "llama_1b", "llama_7b", "AfmoeConfig",
    "EvaByteConfig", "evabyte_tiny", "Xing4Config", "xing4_tiny",
    "DeepseekV3Config", "deepseek_v3_tiny",
    "NemotronHConfig", "nemotron_h_tiny", "Lfm2Config", "lfm2_tiny",
    "BailingHybridConfig", "bailing_hybrid_tiny",
    "MotifConfig", "motif_tiny",
    "MimoV2Config", "mimo_v2_tiny",
    "GraniteHybridConfig", "granite_hybrid_tiny",
    "MLPConfig", "init_mlp", "mlp_forward", "mlp_loss",
]
