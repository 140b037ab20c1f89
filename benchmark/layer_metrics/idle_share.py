"""Share of the traced window in which no operation ran on the device."""


def read(facts):
    t = facts.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
