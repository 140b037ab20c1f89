"""Programs compiled (or fetched from the compile cache) inside the measured
window: ``xla_compile`` spans that start there.  Expected 0: everything
compiles in set-up (the check after the window compiles its own programs,
which do not count).  None where the program records no such span at all:
a run without one before the window has no listener."""

from benchmark import spans


def read(facts):
    compiles = spans.named(spans.load(facts), "xla_compile")
    if not compiles:
        return None
    return float(len(spans.inside(compiles, facts)))
