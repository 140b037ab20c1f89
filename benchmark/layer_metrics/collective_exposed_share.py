"""Share of the traced window in which a collective ran on a device and
nothing else did (averaged over the devices)."""


def read(facts):
    t = facts.get("trace")
    if not t or t.get("devices", 1) < 2 or not t.get("collective_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
