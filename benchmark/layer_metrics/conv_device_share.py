"""Share of the device's busy time in the traced steps that the gated short
convolution operators take: every operation traced under the program's
``block/conv`` scopes (``proj``: both projections; ``gate``: the convolution
and its two gates), forward, recomputed and backward, as the runner sums
them with ``benchmark/scopes.py``.  None where the runner found no such
scope.

``seconds_under`` also reads the paths of the first forward pass, which JAX
wraps whole (``jvp(block/conv/gate)/gated_conv_fwd``): ``benchmark/scopes.py``
takes a wrapper off a path a component at a time, and a scope's name with a
``/`` in it is several components, so such a path keeps its wrapper and
matches no scope.  The kernels' first forward lies there."""

import re

from benchmark import scopes

_WRAPPED = re.compile(r"\w+\(([^()]*)\)")


def seconds_under(by_scope, scope: str) -> float:
    """``scopes.seconds_under`` over the paths with every ``jvp(..)`` or
    ``transpose(..)`` round a scope's name taken off."""
    plain = {}
    for path, seconds in by_scope["scopes"].items():
        path = _WRAPPED.sub(r"\1", path)
        plain[path] = plain.get(path, 0.0) + seconds
    return scopes.seconds_under({"scopes": plain}, scope)


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not t.get("busy_s") or not arch or not arch.get("scopes"):
        return None
    seconds = seconds_under(arch["scopes"], "block/conv")
    return 100.0 * seconds / t["busy_s"] if seconds else None
