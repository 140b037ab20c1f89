"""Share of the device's busy time in the traced steps that the attention
calls of a model with sink-biased window layers take: every operation traced
under the program's ``block/attn_window`` and ``block/attn_full`` scopes (the
partial rotary passes, the flash kernels, delta, the sum of a group's dk / dv
shares, and ``attn/sink_grad`` round db, which the runner prints beside it),
forward and backward, as the runner sums them with ``benchmark/scopes.py``; a
path that JAX wrapped whole counts too (``conv_device_share.seconds_under``).
The projections round the calls are not in it.  None where the runner found
no such scope, or the model's sizes name no window layers' key heads."""

from benchmark.layer_metrics.conv_device_share import seconds_under


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes") \
            or "Hskv" not in arch.get("sizes", {}):
        return None
    seconds = sum(seconds_under(arch["scopes"], scope)
                  for scope in ("block/attn_window", "block/attn_full"))
    return 100.0 * seconds / t["busy_s"] if seconds else None
