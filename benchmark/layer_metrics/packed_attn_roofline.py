"""The flash kernels' share of their roofline over PACKED rows: the least
time the chip could take for every ``flash_seg_fwd`` / ``flash_seg_dq`` /
``flash_seg_dkv`` (or ``flash_seg_bwd``) call the trace shows, operations
over the pairs INSIDE documents (sum of len^2 / 2 of the documents the
traced steps really held, which the runner keeps; bytes as
``roofline.flash_call`` counts them: ``benchmark/roofline_pack.py``), over
the time the trace shows for them.  Blocks whose documents cannot meet are
grid steps that compute nothing: their cost is in the denominator and not
in the numerator, so the share says what skipping leaves on the table.  At
a head size of 64 every product fills half of a pass of the MXU: a share
near half is the kernels' ceiling (``flash_d64_roofline``'s note).  The
kernels are told by name (a trace's label drops trailing digits:
``flash_seg_fwd_d``).  None where the trace holds no such kernel or the
runner kept no documents, as on a program without them."""

from benchmark import roofline, roofline_pack


def read(facts):
    t, arch = facts.get("trace"), facts.get("arch")
    if not t or not arch or not arch.get("pack_traced") \
            or "D" not in arch.get("sizes", {}):
        return None
    s, rows = arch["sizes"], arch["rows_a_call"]
    held = [row for step in arch["pack_traced"] for row in step["lengths"]]
    pairs = sum(map(roofline_pack.pairs_inside, held)) / len(held)
    return roofline.kernels_share(
        "packed_attn_roofline", t, facts["device"]["kind"],
        r"/flash_seg_(fwd|dq|dkv|bwd)_d\d*<",
        lambda m: roofline_pack.flash_seg_call(
            m.group(1), rows, s["H"], s["Hkv"], facts["seq_len"], s["D"],
            pairs * rows))
