"""Device seconds by the program's named scopes, from a trace and the text
of the compiled program that ran.

A trace's operation events carry the instruction's text but not where in the
program it came from; the compiled program's text (``compiled.as_text()``)
has each instruction's ``op_name``: the path of ``jax.named_scope`` names it
was traced under, wrapped in what JAX's transformations add (``jit(..)``,
``jvp()``, ``transpose(jvp())``, ``while/body``, ``checkpoint``,
``rematted_computation``, ``cond/branch_1_fun``).  Joined by instruction
name, every operation of the trace gets the scopes it belongs to; a fusion
has the ``op_name`` of the instruction XLA named it after.
"""

from __future__ import annotations

import re
from typing import Any, Dict

from benchmark import trace

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?\bop_name="([^"]*)"', re.M)

#: path components that are JAX's and not a named scope (an einsum's
#: specification is one: ``bse,em->bsm/dot_general``)
_WRAPPER = re.compile(
    r"^(\w+\(.*\)|.*->.*|while|body|cond|closed_call|checkpoint|"
    r"rematted_computation|branch_\d+_fun|core_call|custom_[jv][jv]p_call|"
    r"pallas_call)$")


def scope_path(op_name: str) -> str:
    """``jit(step)/forward_backward/transpose(jvp())/while/body/checkpoint/
    block/moe/cond/branch_1_fun/dispatch/gather`` -> ``forward_backward/
    block/moe/dispatch``: the named scopes alone, without the primitive."""
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts if p and not _WRAPPER.match(p))


def op_names(hlo_text: str) -> Dict[str, str]:
    """instruction name -> its ``op_name``, for the instructions that have
    one."""
    return dict(_INSTRUCTION.findall(hlo_text))


def seconds_by_scope(loaded: Dict[str, Any], hlo_text: str) -> Dict[str, Any]:
    """{"scopes": {scope path: seconds}, "named_s": seconds of the
    operations found in the text, "ops_s": seconds of all operations}, on
    the first device of what ``trace.load`` returned; operations that only
    hold others are left out, as in ``trace.reduce``."""
    names = op_names(hlo_text)
    scopes: Dict[str, float] = {}
    named = total = 0.0
    devices = loaded["devices"]
    for name, a, b in devices[sorted(devices)[0]]["ops"] if devices else ():
        if trace.opcode(name) in trace.CONTAINERS:
            continue
        total += b - a
        op_name = names.get(name.split(" = ")[0].lstrip("%"))
        if op_name is None:
            continue
        named += b - a
        path = scope_path(op_name)
        scopes[path] = scopes.get(path, 0.0) + (b - a)
    return {"scopes": scopes, "named_s": named, "ops_s": total}


def seconds_under(by_scope: Dict[str, Any], scope: str) -> float:
    """Seconds of the operations whose path holds ``scope`` whole."""
    rx = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    return sum(v for k, v in by_scope["scopes"].items() if rx.search(k))
