"""Share of the device's busy time in the traced steps that the layers'
feed-forwards take: every operation traced under the program's ``block/mlp``
scope (the norm before it, the three products, the gate), forward,
recomputed and backward, as the runner sums them with
``benchmark/scopes.py``.  None where the runner found no such scope."""

from benchmark import scopes


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes"):
        return None
    seconds = scopes.seconds_under(arch["scopes"], "block/mlp")
    return 100.0 * seconds / t["busy_s"] if seconds else None
