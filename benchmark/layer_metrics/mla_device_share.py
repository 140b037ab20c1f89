"""Share of the device's busy time in the traced steps that latent attention
takes: every operation traced under the program's ``block/attn`` scope (the
four products under ``mla/q``, ``mla/kv_a``, ``mla/kv_b`` and ``mla/out``
with the latent's norm, the rotary passes under ``rope``, the three flash
kernels), forward, recomputed and backward, as the runner sums them with
``benchmark/scopes.py``; a path that JAX wrapped whole (``jvp(block/attn)``)
counts too (``conv_device_share.seconds_under``).  Where a stack's other
mixer shares ``block/attn`` (Ling-3.0-flash: six KDA layers, every one of
their operations under ``block/attn/kda``, beside the one latent layer),
what lies under ``block/attn/kda`` is taken off, and ``kda_device_share``
reads it: the rest is the latent layer's ``mla/..``, ``rope`` and flash
kernels.  The ``[scopes]`` line of a traced run tells the parts apart.  None
where the runner found no such scope, or the model is not a latent-attention
one (no ``dv`` among its sizes)."""

from benchmark.layer_metrics.conv_device_share import seconds_under


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes") \
            or "dv" not in arch.get("sizes", {}):
        return None
    seconds = seconds_under(arch["scopes"], "block/attn") \
        - seconds_under(arch["scopes"], "block/attn/kda")
    return 100.0 * seconds / t["busy_s"] if seconds else None
