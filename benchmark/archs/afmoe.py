"""Trinity's ``afmoe`` block for the benchmark: sizes from the config file,
the program's configuration, the layout of the weights (that of
``ray_tpu.models.afmoe``'s parameter tree: ``dense`` and ``moe`` stacks with a
leading layer axis), the judged norms, the counts, and the reference."""

from __future__ import annotations

from typing import Any, Dict

#: the six RMSNorm weights of a layer (the reference's ``NORMS``)
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
         "q_norm", "k_norm")


#: What the two post-norm weights (N2, N4) start at.  At 1, a branch's
#: normalised output is as large as the token's own embedding, and with
#: random weights it is nearly the same vector for every token of a row (a
#: near-uniform average over the window), so each row routes by its own
#: common direction.  Read on the chip at the cell's sizes (PERF.md section
#: 6, PR 29): with no selection bias the held experts' largest load is 2.2 to
#: 2.3 times their mean at 1 and 1.31 to 1.35 at 0.1 (3 seeds, 12 batches
#: each), nearer what a deployment's selection bias leaves, so the grouped
#: products see groups of a deployment's evenness.  What it costs: over the
#: same 12 seeds the int8 control's smallest gradient distance is 1.52 times
#: the program's largest where it is 1.64 times at 1 (routing: 2.3 against
#: 3.0 times).  A choice of the benchmark's weights, not of the model:
#: ``init_params`` starts every norm at 1.
POST_NORM_START = 0.1


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_afmoe
    return reference_afmoe


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """``num_experts`` counts the experts held here; the router's width is
    the published count, which the file states beside it."""
    share = config["share"]
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "Ld": config["num_dense_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "M": config["intermediate_size"],
            "Me": config["moe_intermediate_size"],
            "Ms": config["moe_intermediate_size"]
            * config["num_shared_experts"],
            "X": share["router_outputs"], "Xh": config["num_experts"],
            "held_start": share["held_start"],
            "k": config["num_experts_per_tok"],
            "route_scale": float(config["route_scale"]),
            "window": config["sliding_window"],
            "layer_types": tuple(
                config["layer_types"][:config["num_hidden_layers"]]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "bias_update_rate": float(config["load_balance_coeff"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.afmoe import AfmoeConfig
    return AfmoeConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"], heads=s["H"],
        kv_heads=s["Hkv"], head_dim=s["D"], mlp_dim=s["M"],
        moe_mlp_dim=s["Me"], num_experts=s["X"], experts_held=s["Xh"],
        held_start=s["held_start"], top_k=s["k"],
        num_shared_experts=s["Ms"] // s["Me"], num_dense_layers=s["Ld"],
        layer_types=s["layer_types"], sliding_window=s["window"],
        rope_theta=s["theta"], norm_eps=s["eps"],
        route_scale=s["route_scale"], bias_update_rate=s["bias_update_rate"],
        max_seq_len=max_seq_len, dtype=jnp.bfloat16, remat=opts["remat"],
        attention_impl=opts["attention"], loss_chunks=opts["loss_chunks"],
        layer_rows=opts["layer_rows"])


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    E, H, K, D, V = (s[k] for k in ("E", "H", "Hkv", "D", "V"))

    def attn(L):
        return {n: ((L, D if n in ("q_norm", "k_norm") else E), 0)
                for n in NORMS} | {
            "attn_post_norm": ((L, E), 0, POST_NORM_START),
            "mlp_post_norm": ((L, E), 0, POST_NORM_START)} | {
            "wq": ((L, E, H, D), E), "wk": ((L, E, K, D), E),
            "wv": ((L, E, K, D), E), "wg": ((L, E, H, D), E),
            "wo": ((L, H, D, E), H * D)}

    Ld, Lm = s["Ld"], s["L"] - s["Ld"]
    M, Me, Ms, X, Xh = (s[k] for k in ("M", "Me", "Ms", "X", "Xh"))
    return {
        "embed": ((V, E), E),
        "dense": {**attn(Ld), "w_gate": ((Ld, E, M), E),
                  "w_up": ((Ld, E, M), E), "w_down": ((Ld, M, E), M)},
        "moe": {**attn(Lm), "router": ((Lm, E, X), E),
                "shared_gate": ((Lm, E, Ms), E),
                "shared_up": ((Lm, E, Ms), E),
                "shared_down": ((Lm, Ms, E), Ms),
                "w_gate": ((Lm, Xh, E, Me), E), "w_up": ((Lm, Xh, E, Me), E),
                "w_down": ((Lm, Xh, Me, E), Me)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}


def norms_of(p):
    return {"final_norm": p["final_norm"],
            "dense": {n: p["dense"][n] for n in NORMS},
            "moe": {n: p["moe"][n] for n in NORMS}}


def with_norms(w, norms):
    """``w`` with its RMSNorm weights replaced by ``norms``."""
    return {**w, "final_norm": norms["final_norm"],
            "dense": {**w["dense"], **norms["dense"]},
            "moe": {**w["moe"], **norms["moe"]}}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``expert``: one routed
    expert's.  ``always``: what every token multiplies by, whatever its
    route: attention, the dense layers' MLP, shared experts, routers and the
    head (the embedding is a lookup)."""
    import math

    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        return math.prod(tree[0])

    tree = shapes(s)
    Lm = s["L"] - s["Ld"]
    expert = 3 * s["E"] * s["Me"]
    held = size(tree)
    return {"held": held, "expert": expert,
            "always": held - Lm * s["Xh"] * expert - s["V"] * s["E"]}
