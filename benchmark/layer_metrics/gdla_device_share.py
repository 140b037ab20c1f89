"""Share of the device's busy time in the traced steps that grouped
differential attention takes: every operation traced under the program's
``block/attn`` scope (the products under ``mla/q``, ``mla/kv_a``,
``mla/kv_b`` and ``mla/out`` with the latents' norms, the rotary passes
under ``rope``, the flash kernels under ``block/attn_window`` and
``block/attn_full``, the differential combine under ``mla/diff`` and the
channel gate under ``mla/gate``), forward, recomputed and backward, as the
runner sums them with ``benchmark/scopes.py``; a path that JAX wrapped whole
counts too (``conv_device_share.seconds_under``).  None where the runner
found no such scope, or the model has no noise heads among its sizes."""

from benchmark.layer_metrics.conv_device_share import seconds_under


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes") \
            or "noise" not in arch.get("sizes", {}):
        return None
    seconds = seconds_under(arch["scopes"], "block/attn")
    return 100.0 * seconds / t["busy_s"] if seconds else None
