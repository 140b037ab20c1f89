"""How late the open loop's generator sent requests: sent - due."""

from benchmark import traffic


def read(facts):
    late = [(r.sent - r.due) * 1e3 for r in facts.get("requests", ())
            if r.due is not None]
    return traffic.percentile(late, 99) if late else None
