"""What the EVA kernels must do, from shapes alone: operations and bytes for
``roofline.least_seconds``.  The peaks stay in ``roofline.py``.

A query in window ``w`` of a row of ``n = seq / window`` windows sees the
tokens of its window up to itself and ``window / chunk`` summaries of every
earlier window."""

from __future__ import annotations

from typing import Tuple


def visible_pairs(seq: int, window: int, chunk: int) -> Tuple[float, float]:
    """(local, remote) score pairs a head: ``seq (window + 1) / 2`` tokens
    under the windows' triangles, and ``window^2 / chunk * n (n - 1) / 2``
    summaries (33.57 M and 31.46 M at 32,768 / 2,048 / 16)."""
    window = min(window, seq)
    n = seq // window
    return (seq * (window + 1) / 2.0,
            float(window) * window / chunk * n * (n - 1) / 2.0)


def eva_call(which: str, batch: int, heads: int, seq: int, head_dim: int,
             window: int, chunk: int, itemsize: int = 2
             ) -> Tuple[float, float]:
    """(operations, bytes) of one EVA attention kernel call.  Products a
    visible pair as ``roofline.flash_attention_call`` counts flash's: 2
    forward, 3 for dq, 4 for the two K-major kernels, ``dkv`` over the local
    pairs and ``dsum`` over the remote ones, each ``2 * head_dim`` operations.
    Bytes: every operand read once and every result written once."""
    local, remote = visible_pairs(seq, window, chunk)
    pairs = {"fwd": 2 * (local + remote), "dq": 3 * (local + remote),
             "dkv": 4 * local, "dsum": 4 * remote}[which]
    q = batch * heads * seq * head_dim * itemsize       # also k, v, o, do
    sums = q // chunk                                   # k~ or v~
    lse = batch * heads * seq * 4
    moved = {"fwd": 4 * q + 2 * sums + lse,             # q k v k~ v~ -> o lse
             "dq": 5 * q + 2 * sums + 2 * lse,          # + do, di -> dq
             "dkv": 6 * q + 2 * lse,                    # q k v do -> dk dv
             # q do k~ v~ -> dk~ dv~
             "dsum": 2 * q + 4 * sums + 2 * lse}[which]
    return 2.0 * batch * heads * pairs * head_dim, float(moved)


def pool_call(which: str, batch: int, heads: int, seq: int, head_dim: int,
              chunk: int, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one pooling kernel call, which the memory
    bounds: forward reads k and v and writes the summaries; backward reads
    k, v and the summaries' gradients and writes dk and dv.  Operations: a
    product with each vector and a weighted sum a position, and in the
    backward about three times that."""
    kv = batch * heads * seq * head_dim * itemsize
    moved = {"fwd": 2 * kv + 2 * kv // chunk,
             "bwd": 4 * kv + 2 * kv // chunk}[which]
    ops = {"fwd": 8.0, "bwd": 24.0}[which] * batch * heads * seq * head_dim
    return ops, float(moved)
