"""Share of the device's busy time in the traced steps that the expert
layers take: every operation traced under the program's ``block/moe`` scope
(``route``, ``shared``, ``dispatch``, ``experts`` with the grouped-matmul
kernels, ``combine``), forward, recomputed and backward, as the runner sums
them with ``benchmark/scopes.py``.  None where the runner found no scopes."""

from benchmark import scopes


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes"):
        return None
    seconds = scopes.seconds_under(arch["scopes"], "block/moe")
    return 100.0 * seconds / t["busy_s"] if seconds else None
