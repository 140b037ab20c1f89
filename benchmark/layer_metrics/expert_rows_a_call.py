"""Mean rows a held expert got in one call of its grouped products in the
traced steps, from the step's own counts of its routers' choices
(``moe_held_assignments``, a layer-mean of the assignments a step routed to
the held experts): assignments / held experts / the calls a layer makes a
step (its rows over the rows it takes at a time).  Beside the load a
deployed expert would get, which the cell's ``why`` states.  None where no
step was traced or the model holds no experts."""


def read(facts):
    arch = facts.get("arch")
    if not arch or not arch.get("moe_traced") \
            or not arch.get("sizes", {}).get("Xh"):
        return None
    calls = facts["rows"] / facts["device"]["count"] / arch["rows_a_call"]
    steps = arch["moe_traced"]
    return sum(step["moe_held_assignments"] for step in steps) / len(steps) \
        / arch["sizes"]["Xh"] / calls
