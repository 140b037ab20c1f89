"""Output tokens that reached clients inside the window, per second."""


def read(facts):
    if "delivered_tokens" not in facts:
        return None
    lo, hi = facts["window"]
    return facts["delivered_tokens"] / (hi - lo)
