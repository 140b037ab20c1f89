"""TPU ops layer: pallas kernels + SPMD attention/MoE primitives.

No reference analog (SURVEY §2.4: SP/CP/EP are absent in the reference,
delegated to vLLM/DeepSpeed).  Built natively here:

- ``attention``     — causal (GQA) attention; pallas flash kernel on TPU,
                      jnp reference elsewhere
- ``ring_attention``— context parallelism over an ICI ring
                      (K/V rotate via ppermute, online-softmax accumulation)
- ``ulysses``       — sequence<->head all-to-all context parallelism
- ``moe``           — mixture-of-experts: sigmoid scores with a selection
                      bias, dropless, over the experts held
- ``eva``           — EVA chunked linearized attention: a window's tokens
                      and 16-token summaries of every earlier window under
                      one softmax; pooling and two-operand flash kernels
- ``ssm``           — state-space layers (Mamba-2): a causal depthwise
                      convolution, the chunked scan of a recurrence whose
                      state is handed from chunk to chunk, and the gated
                      RMSNorm over groups of channels; LFM2's
                      double-gated short convolution
- ``norms``/``swiglu`` — fused-friendly elementwise building blocks
- ``rope``          — rotary embedding: the split rotation, and on the TPU a
                      rotate-and-place kernel pair between a projection
                      and the flash kernels
"""

from .norms import rms_norm
from .rope import (apply_rope, rope_frequencies, rope_lane_tables,
                   rotate_heads)
from .attention import attention, flash_attention, reference_attention
from .eva import eva_attention, eva_summaries
from .ring_attention import ring_attention
from .ssm import (causal_conv, gated_group_norm, gated_short_conv,
                  ssd_scan)
from .ulysses import ulysses_attention

__all__ = [
    "rms_norm", "apply_rope", "rope_frequencies", "rope_lane_tables",
    "rotate_heads",
    "attention", "flash_attention", "reference_attention",
    "eva_attention", "eva_summaries",
    "ring_attention", "ulysses_attention",
    "causal_conv", "ssd_scan", "gated_group_norm", "gated_short_conv",
]
