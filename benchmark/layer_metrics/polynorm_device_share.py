"""Share of the device's busy time in the traced steps that PolyNorm takes:
every operation traced under a ``polynorm`` scope (``ops/norms.poly_norm``'s
own, wherever it is called from: the dense layers' ``block/mlp/polynorm``,
the shared expert's ``block/moe/shared/polynorm``, the routed experts'
``block/moe/experts/polynorm``), forward, recomputed and backward, as the
runner sums them with ``benchmark/scopes.py``.  The compiler may fuse the
activation into the products on either side of it, whose seconds then lie
under the product's operation: the reading is what stayed a pass of its own.
None where the runner found no such scope."""

from benchmark.layer_metrics.conv_device_share import seconds_under


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes"):
        return None
    seconds = seconds_under(arch["scopes"], "polynorm")
    return 100.0 * seconds / t["busy_s"] if seconds else None
