"""The share of the causal square that attention inside documents keeps, in
the traced steps, as the program's own step reported it: sum of len^2 / S^2
over the rows of each traced step (``pack_pairs_share`` of the step's
metrics, computed on the device from the batch's segment ids; the gauge
``ray_tpu_pack_pairs_share`` holds the last reported step's), averaged.  What
part of the triangle a packed row keeps, and so what skipping can save.  1
where a row is one document.  None where the runner kept no such report."""


def read(facts):
    traced = (facts.get("arch") or {}).get("pack_traced")
    if not traced:
        return None
    return sum(step["pack_pairs_share"] for step in traced) / len(traced)
