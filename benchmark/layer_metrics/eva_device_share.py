"""Share of the device's busy time in the traced steps that EVA takes: every
operation traced under the program's scopes ``block/attn/eva`` (the
attention over a window's tokens and the earlier windows' summaries) and
``block/attn/eva_pool`` (the chunks' summaries), forward, recomputed and
backward, as the runner sums them with ``benchmark/scopes.py``.  None where
the runner found no such scope."""

from benchmark import scopes


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes"):
        return None
    seconds = sum(scopes.seconds_under(arch["scopes"], scope)
                  for scope in ("block/attn/eva", "block/attn/eva_pool"))
    return 100.0 * seconds / t["busy_s"] if seconds else None
