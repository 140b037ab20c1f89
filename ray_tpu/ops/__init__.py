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
- ``norms``/``rope``/``swiglu`` — fused-friendly elementwise building blocks
"""

from .norms import rms_norm
from .rope import apply_rope, rope_frequencies
from .attention import attention, flash_attention, reference_attention
from .ring_attention import ring_attention
from .ulysses import ulysses_attention

__all__ = [
    "rms_norm", "apply_rope", "rope_frequencies",
    "attention", "flash_attention", "reference_attention",
    "ring_attention", "ulysses_attention",
]
