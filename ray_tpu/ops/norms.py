"""Normalization ops.

``rms_norm`` is XLA's elementwise chain (square, mean, rsqrt, two products,
a cast), and XLA fuses it well: in a compiled train step the sum of squares
rides out of the projection before it as that product's epilogue, the scale
rides into the next one, and where either stands alone it reads its stream
at 92 % of HBM pace (0.71 ms for 537 MB on a v5e; PERF.md, PR 39).  No
kernel is needed, and a Pallas pair measured no faster in the step.

What the chain cannot say is how its stream lies in memory.  A float32
residual stream of ONE long row (EvaByte's ``fp32_skip_add`` at
[1, 32768, 4096]) is kept by the TPU compiler with the sequence as its minor
dimension through the whole step, and the step's projections run 3 % slower
round it (52 ms of a 1,600 ms step).  So on a TPU a float32 input is pinned
row-major (``with_layout_constraint``): one hint, which turns the stream
round everywhere it goes.  bf16 streams, which every other model carries,
are left to the compiler and compile to what they compiled to.  Which way a
call went is counted in ``ray_tpu_norm_path_total``.

``poly_norm`` is PolyNorm (arXiv:2411.03884), an activation with weights of
its own: three powers of its input, each normed over the width, under three
learned weights and one bias.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..util import telemetry


def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm in fp32 accumulation, cast back to input dtype."""
    from .attention import _on_tpu          # at the call: tests steer it
    dtype = x.dtype
    row_major = dtype == jnp.float32 and x.ndim > 1 and _on_tpu()
    telemetry.inc("ray_tpu_norm_path_total", tags={
        "path": "row_major" if row_major else "xla",
        "rows": str(math.prod(x.shape[:-1]))})
    x32 = x.astype(jnp.float32)
    if row_major:
        x32 = with_layout_constraint(
            x32, Layout(major_to_minor=tuple(range(x.ndim))))
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def poly_norm(x, p, scale: float = 1.0, clamp: float = None,
              eps: float = 1e-6):
    """PolyNorm over the last axis: ``scale * (p[0] n(x^3) + p[1] n(x^2) +
    p[2] n(x) + clip(p[3], -clamp, clamp))`` with ``n(y) = y / rms(y)`` (the
    mean over the width, ``eps`` under the root); ``p`` [4] holds the three
    weights and the bias.  Float32 inside, the input's dtype out; the
    gradients of x and p are autodiff's of these lines."""
    with jax.named_scope("polynorm"):
        x32, p = x.astype(jnp.float32), p.astype(jnp.float32)
        bias = p[3] if clamp is None else jnp.clip(p[3], -clamp, clamp)
        out, power = bias, x32
        for weight in (p[2], p[1], p[0]):
            out = out + weight * power * jax.lax.rsqrt(
                jnp.mean(jnp.square(power), axis=-1, keepdims=True) + eps)
            power = power * x32
        return (scale * out).astype(x.dtype)
