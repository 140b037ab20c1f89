"""Xing4.0's ``xing4_0`` block for the benchmark: sizes from the config file,
the program's configuration, the layout of the weights (that of
``ray_tpu.models.xing4``'s parameter tree: ``dense`` and ``moe`` stacks with
a leading layer axis, and the prediction module under ``mtp``), the judged
weights, the counts, and the reference."""

from __future__ import annotations

from typing import Any, Dict

#: a layer's judged weights (the reference's ``JUDGED``): its four RMSNorm
#: weights and its two sublayers' hyper-connection weights
NORMS = ("attn_norm", "mlp_norm", "q_norm", "kv_norm")
MAPS = tuple(f"hc_{sub}_{part}" for sub in ("attn", "mlp")
             for part in ("phi", "b", "alpha"))
#: the prediction module's norms
MTP_NORMS = ("h_norm", "e_norm", "final_norm")

#: What a hyper-connection's gains start at in the benchmark's weights.  A
#: map is ``sigmoid(alpha m + b)`` with ``m = vec(X) / rms . phi``: phi's
#: columns are unit normal / sqrt(n C), so m is unit normal over the tokens,
#: and b is unit normal over the lanes (fan-in 1).  At 1 the maps differ
#: between tokens as much as between lanes (readings in PERF.md section 6,
#: PR 40), so no pass can take a map as a constant of the layer and be
#: right; the model's own ``init_params`` starts them at 0.01, where a map
#: is nearly its bias.  A choice of the benchmark's weights, not of the
#: model.
ALPHA_START = 1.0


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_xing4
    return reference_xing4


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """``n_routed_experts`` counts the experts held here; the router's width
    is the published count, which the file states under ``share``.  Every
    value is a number or a string, so that the reference can key its
    programs by them."""
    share, yarn = config["share"], config["rope_scaling"]
    if yarn["type"] != "yarn" or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["attention_bias"] or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] != 1 \
            or config["moe_layer_freq"] != 1 or not config["norm_topk_prob"]:
        raise ValueError("the block here is the one Xing4.0-29B-A4B's "
                         "config.json states; the file says otherwise")
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "Ld": config["first_k_dense_replace"],
            "H": config["num_attention_heads"],
            "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
            "dn": config["qk_nope_head_dim"],
            "dr": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "M": config["intermediate_size"],
            "Me": config["moe_intermediate_size"],
            "Ms": config["moe_intermediate_size"]
            * config["n_shared_experts"],
            "X": share["router_outputs"], "Xh": config["n_routed_experts"],
            "held_start": share["held_start"],
            "k": config["num_experts_per_tok"],
            "route_scale": float(config["routed_scaling_factor"]),
            "n": config["hc_mult"], "hc_iters": config["hc_sinkhorn_iters"],
            "hc_eps": float(config["hc_eps"]),
            "hc_lo": float(config["mhc_h_res_clamp_min"]),
            "hc_hi": float(config["mhc_h_res_clamp_max"]),
            "mtp_weight": float(config["train"]["mtp_loss_weight"]),
            "theta": float(config["rope_theta"]),
            "yarn_factor": float(yarn["factor"]),
            "yarn_original": yarn["original_max_position_embeddings"],
            "yarn_beta_fast": float(yarn["beta_fast"]),
            "yarn_beta_slow": float(yarn["beta_slow"]),
            "yarn_mscale": float(yarn["mscale"]),
            "yarn_mscale_all_dim": float(yarn["mscale_all_dim"]),
            "eps": float(config["rms_norm_eps"]),
            "bias_update_rate": float(config["train"]["bias_update_rate"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.xing4 import Xing4Config
    from ray_tpu.ops.rope import Yarn
    return Xing4Config(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"], heads=s["H"],
        q_lora_rank=s["rq"], kv_lora_rank=s["rkv"],
        qk_nope_head_dim=s["dn"], qk_rope_head_dim=s["dr"],
        v_head_dim=s["dv"], mlp_dim=s["M"], moe_mlp_dim=s["Me"],
        num_experts=s["X"], experts_held=s["Xh"], held_start=s["held_start"],
        top_k=s["k"], num_shared_experts=s["Ms"] // s["Me"],
        num_dense_layers=s["Ld"], route_scale=s["route_scale"],
        bias_update_rate=s["bias_update_rate"], hc_mult=s["n"],
        hc_sinkhorn_iters=s["hc_iters"], hc_eps=s["hc_eps"],
        hc_clamp=(s["hc_lo"], s["hc_hi"]), mtp_loss_weight=s["mtp_weight"],
        rope_theta=s["theta"],
        yarn=Yarn(s["yarn_factor"], s["yarn_original"], s["yarn_beta_fast"],
                  s["yarn_beta_slow"], s["yarn_mscale"],
                  s["yarn_mscale_all_dim"]),
        norm_eps=s["eps"], max_seq_len=max_seq_len, dtype=jnp.bfloat16,
        remat=opts["remat"], attention_impl=opts["attention"],
        loss_chunks=opts["loss_chunks"], layer_rows=opts["layer_rows"])


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant, 1 if not given])."""
    E, H, V, n = s["E"], s["H"], s["V"], s["n"]
    rq, rkv, dn, dr, dv = (s[k] for k in ("rq", "rkv", "dn", "dr", "dv"))
    width = 2 * n + n * n

    def layer(L):
        maps = {}
        for sub in ("attn", "mlp"):
            maps |= {f"hc_{sub}_phi": ((L, n * E, width), n * E),
                     f"hc_{sub}_b": ((L, width), 1),
                     f"hc_{sub}_alpha": ((L, 3), 0, ALPHA_START)}
        return {
            "attn_norm": ((L, E), 0), "mlp_norm": ((L, E), 0),
            "q_norm": ((L, rq), 0), "kv_norm": ((L, rkv), 0),
            "wq_a": ((L, E, rq), E), "wq_b": ((L, rq, H, dn + dr), rq),
            "wkv_a": ((L, E, rkv + dr), E),
            "wkv_b": ((L, rkv, H, dn + dv), rkv),
            "wo": ((L, H, dv, E), H * dv), **maps}

    M, Me, Ms, X, Xh = (s[k] for k in ("M", "Me", "Ms", "X", "Xh"))

    def moe_layer(L):
        return {**layer(L), "router": ((L, E, X), E),
                "shared_gate": ((L, E, Ms), E), "shared_up": ((L, E, Ms), E),
                "shared_down": ((L, Ms, E), Ms),
                "w_gate": ((L, Xh, E, Me), E), "w_up": ((L, Xh, E, Me), E),
                "w_down": ((L, Xh, Me, E), Me)}

    Ld = s["Ld"]
    return {
        "embed": ((V, E), E),
        "dense": {**layer(Ld), "w_gate": ((Ld, E, M), E),
                  "w_up": ((Ld, E, M), E), "w_down": ((Ld, M, E), M)},
        "moe": moe_layer(s["L"] - Ld),
        "mtp": {"h_norm": ((E,), 0), "e_norm": ((E,), 0),
                "proj": ((2 * E, E), 2 * E), "final_norm": ((E,), 0),
                "layer": moe_layer(1)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}


def _pick(p, names):
    return {"final_norm": p["final_norm"],
            "dense": {n: p["dense"][n] for n in names},
            "moe": {n: p["moe"][n] for n in names},
            "mtp": {**{n: p["mtp"][n] for n in MTP_NORMS},
                    "layer": {n: p["mtp"]["layer"][n] for n in names}}}


def norms_of(p):
    """The RMSNorm weights: four a layer, the final one, the module's
    three."""
    return _pick(p, NORMS)


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight and
    every hyper-connection's phi, b and gains."""
    return _pick(p, NORMS + MAPS)


def maps_of(p):
    """The hyper-connections' weights alone (of a judged tree too)."""
    return {"dense": {n: p["dense"][n] for n in MAPS},
            "moe": {n: p["moe"][n] for n in MAPS},
            "mtp": {n: p["mtp"]["layer"][n] for n in MAPS}}


def with_judged(w, judged):
    """``w`` with its judged weights replaced by ``judged``."""
    return {**w, "final_norm": judged["final_norm"],
            "dense": {**w["dense"], **judged["dense"]},
            "moe": {**w["moe"], **judged["moe"]},
            "mtp": {**w["mtp"],
                    **{n: judged["mtp"][n] for n in MTP_NORMS},
                    "layer": {**w["mtp"]["layer"],
                              **judged["mtp"]["layer"]}}}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``expert``: one routed
    expert's.  ``always``: what every token multiplies by, whatever its
    route: attention, the maps' thin products, the dense layers' SwiGLU,
    shared experts, routers, the module's projection and the head twice (the
    module's pass over it is a second product; the embedding is a
    lookup)."""
    import math

    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        return math.prod(tree[0])

    expert = 3 * s["E"] * s["Me"]
    held = size(shapes(s))
    layers = s["L"] - s["Ld"] + 1           # the module's layer too
    return {"held": held, "expert": expert,
            "always": held - layers * s["Xh"] * expert}
