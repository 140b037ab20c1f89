"""Share of the device's busy time in the traced steps that latent attention
takes: every operation traced under the program's ``block/attn`` scope (the
four products under ``mla/q``, ``mla/kv_a``, ``mla/kv_b`` and ``mla/out``
with the latent's norm, the rotary passes under ``rope``, the three flash
kernels), forward, recomputed and backward, as the runner sums them with
``benchmark/scopes.py``; a path that JAX wrapped whole (``jvp(block/attn)``)
counts too (``conv_device_share.seconds_under``).  The ``[scopes]`` line of
a traced run tells the parts apart.  None where the runner found no such
scope, or the model is not a latent-attention one (no ``dv`` among its
sizes)."""

from benchmark.layer_metrics.conv_device_share import seconds_under


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes") \
            or "dv" not in arch.get("sizes", {}):
        return None
    seconds = seconds_under(arch["scopes"], "block/attn")
    return 100.0 * seconds / t["busy_s"] if seconds else None
