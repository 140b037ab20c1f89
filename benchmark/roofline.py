"""Peaks of the chips the benchmark knows, and what each kernel must do.

The functions give the operations and bytes that the algorithm needs for
one call, from its shapes alone; a kernel's roofline share is the least
time the chip could take for them over the time the trace shows.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: device_kind -> peaks of ONE chip.  Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).  A kind that is not
#: here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"add it to benchmark/roofline.py with its source")
    return PEAKS[device_kind]


def least_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(ops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])


def _attn_matmul(batch, heads, seq, head_dim) -> float:
    """One causal [S,S]x[S,D]-sized matmul over all heads: 2*S*S*D
    multiply-adds counted as two operations, half of them masked away."""
    return 2.0 * batch * heads * seq * seq * head_dim / 2


def flash_attention_call(which: str, batch: int, heads: int, kv_heads: int,
                         seq: int, head_dim: int,
                         itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one flash-attention kernel call.

    ``fwd`` forms S=QK^T and PV; ``dq`` re-forms S, forms dP=dO V^T and
    dQ=dS K; ``dkv`` re-forms S and dP and forms dV=P^T dO and dK=dS^T Q.
    Bytes are each operand read once and each result written once.
    """
    matmuls = {"fwd": 2, "dq": 3, "dkv": 4}[which]
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    moved = {"fwd": 2 * q + 2 * kv + lse,            # q,k,v -> o,lse
             "dq": 3 * q + 2 * kv + 2 * lse + q,     # q,k,v,do,lse,di -> dq
             "dkv": 2 * q + 2 * kv + 2 * lse + 2 * kv}[which]
    return matmuls * _attn_matmul(batch, heads, seq, head_dim), float(moved)


def paged_attention_call(cache_tokens: float, slots: int, heads: int,
                         kv_heads: int, head_dim: int,
                         itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one decode call of paged attention in one
    layer: ``cache_tokens`` is the sum of the sequences' lengths.  Each
    cached key and value is read once; q and the output are [slots, H, D].
    """
    ops = 4.0 * heads * head_dim * cache_tokens          # qK^T and pV
    moved = (2.0 * kv_heads * head_dim * itemsize * cache_tokens
             + 2.0 * slots * heads * head_dim * itemsize)
    return ops, moved


def train_flops_per_token(num_params: int) -> float:
    """Model FLOPs a trained token needs, forward and backward (6*P):
    recomputation does not count, and attention's own term is left out,
    so the share of peak is a floor."""
    return 6.0 * num_params
