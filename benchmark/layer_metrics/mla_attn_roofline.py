"""The latent-attention flash kernels' share of their roofline in the traced
steps: the least time the chip could take for every ``flash_fwd_d192v128``
and ``flash_bwd_d192v128`` call the trace shows (the backward's one pass:
counted since PR 61, the reading was the forward's alone from PR 54 until
then), and ``flash_dq_d192v128`` / ``flash_dkv_d192v128`` where a call keeps
the pair (operations over the visible triangle, scores over d_qk and values
over d_v; ``benchmark/roofline.flash_call``), over the time it shows for
them.  Keys and values are counted once a QUERY head: latent attention
expands one for each, and a model whose query heads share key heads (Motif)
has ``gdla_attn_roofline``.  The kernels are told by name (a trace's label
drops trailing digits: ``flash_fwd_d192v``).  A call holds the rows the
program gives a layer at a time.  None where the trace holds no such kernel,
as on a program without them."""

from benchmark import roofline


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or "dv" not in arch.get("sizes", {}):
        return None
    s = arch["sizes"]
    return roofline.kernels_share(
        "mla_attn_roofline", t, facts["device"]["kind"],
        r"/flash_(fwd|dq|dkv|bwd)_d\d+v\d*<",
        lambda m: roofline.flash_call(
            m.group(1), arch["rows_a_call"], s["H"], s["H"],
            facts["seq_len"], s["dn"] + s["dr"], s["dv"]))
