"""Share of the device's busy time in the traced steps under the program's
``loss`` scope: the head's products, the log-sum-exp and whatever weighs
the per-token losses (a looped stack: one head a pass and the exit gate),
forward, recomputed and backward, as the runner sums them with
``benchmark/scopes.py``.  None where the runner found no scopes."""

from benchmark import scopes


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes"):
        return None
    seconds = scopes.seconds_under(arch["scopes"], "loss")
    return 100.0 * seconds / t["busy_s"] if seconds else None
