"""The flash-attention kernels' share of their roofline in the traced steps:
the least time the chip could take for every forward and backward call the
trace shows (``flash_fwd``, and ``flash_bwd``, the one pass that PRs 54 and 60
put in the place of ``flash_dq`` and ``flash_dkv`` wherever the square is
causal; the pair where a call keeps it), over the time it shows for them.
Until PR 61 the backward's one pass matched nothing here and the reading was
the forward's alone."""

from benchmark import roofline

#: The kernels of ``ray_tpu/ops/attention.py`` by the names they give their
#: calls (``name=`` of the ``pallas_call``; until PR 61 by the result types at
#: the end of a label, from before they had names).  ``tests/test_tpu_compile``
#: holds these three to match their own kernel and no other, the rotary
#: kernels and ``flash_bwd`` among the others, so the one pass stands beside
#: them and not among them.
_NAME = r"flash_{}[_.\d]*<"
KERNELS = {kind: _NAME.format(kind) for kind in ("fwd", "dq", "dkv")}
ONE_PASS = "bwd"
_ANY = "/" + _NAME.format("(" + "|".join([*KERNELS, ONE_PASS]) + ")")


def read(facts):
    t = facts.get("trace")
    if not t or "rows" not in facts:
        return None
    s = facts["cell"]["sizes"]
    rows = facts["rows"] // facts["device"]["count"]
    return roofline.kernels_share(
        "flash_attn_roofline", t, facts["device"]["kind"], _ANY,
        lambda m: roofline.flash_attention_call(
            m.group(1), rows, s["H"], s["Hkv"], facts["seq_len"], s["D"]))
