"""Motif-3-Beta's ``Motif`` block for the benchmark: sizes from the config
file, the program's configuration, the layout of the weights (that of
``ray_tpu.models.motif``'s parameter tree: ``dense`` and ``moe`` stacks with
a leading layer axis, and the prediction module under ``mtp`` where the file
holds one), the judged weights, the counts, and the reference."""

from __future__ import annotations

from typing import Any, Dict

#: a layer's judged weights (the reference's ``JUDGED``): its four RMSNorm
#: weights, its two sublayers' hyper-connection weights, PolyNorm's numbers
#: and the differential pair's ``w_lambda``
NORMS = ("attn_norm", "mlp_norm", "q_norm", "kv_norm")
MAPS = tuple(f"hc_{sub}_{part}" for sub in ("attn", "mlp")
             for part in ("phi", "b", "alpha"))
POLYS = {"dense": ("mlp_poly",), "moe": ("shared_poly", "expert_poly")}
#: the prediction module's norms
MTP_NORMS = ("h_norm", "e_norm", "final_norm")

#: What PolyNorm's three weights and its bias are in the benchmark's weights,
#: in every module and on every seed: a weight of its own size and sign for
#: each power, so that none can be left out or swapped unseen, and a bias
#: past its clamp of 0.5, so that the clamp is seen (a clamped bias has no
#: gradient).  Not drawn from the seed: 32 numbers that each scale a whole
#: module's activation made the distances to the reference swing 2.3 x with
#: the seed (0.040 - 0.092 over 12 seeds where every other cell's swing
#: under 1.3 x; chip, PERF.md section 6, PR 57).  The model's own
#: ``init_params`` starts them at 1/3, 1/3, 1/3, 0.
POLY_START = (0.45, -0.35, 0.6, 0.8)

#: What a hyper-connection's gains start at in the benchmark's weights, as
#: ``archs/xing4_0.ALPHA_START``: at 1 the maps differ between tokens as
#: much as between lanes.  A choice of the benchmark's weights, not of the
#: model.
ALPHA_START = 1.0


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_motif
    return reference_motif


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """``num_experts`` counts the experts held here; the router's width is
    the published count, which the file states under ``share``.  Every value
    is a number or a string, so that the reference can key its programs by
    them."""
    share, yarn = config["share"], config["rope_scaling"]
    if config["attention_cls"] != "gdla" or not config["diff_v2"] \
            or not config["elementwise_attn_output_gate"] \
            or config["headwise_attn_output_gate"] \
            or config["hidden_act"] != "poly_norm" \
            or config["score_func"] != "sigmoid" or not config["route_norm"] \
            or config["score_before_experts"] or config["k_ratio"] != 1 \
            or config["interleave_moe_layer_step"] != 1 \
            or not config["mhc_enabled"] or not config["use_sliding_window"] \
            or config["sliding_window_pattern"] != "interleave" \
            or yarn["apply_yarn_scaling"] or config["mscale"] != 1 \
            or config["swa_rope_theta"] != config["rope_theta"] \
            or config["tie_word_embeddings"] \
            or config["polynorm_output_scale_per_layer"] \
            or config["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("the block here is the one Motif-3-Beta's "
                         "config.json states; the file says otherwise")
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "Ld": config["n_dense_first_layers"],
            "first_layer": share["first_layer"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"],
            "noise": config["num_noise_heads"],
            "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
            "dn": config["head_dim"] - config["qk_rope_head_dim"],
            "dr": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "W": config["sliding_window"],
            "period": config["sliding_window_period"],
            "M": config["intermediate_size"],
            "Me": config["moe_intermediate_size"],
            "Ms": config["moe_intermediate_size"]
            * config["num_shared_experts"],
            "X": share["router_outputs"], "Xh": config["num_experts"],
            "held_start": share["held_start"],
            "k": config["experts_top_k"],
            "route_scale": float(config["route_scale"]),
            "n": config["mhc_expansion_rate"],
            "hc_iters": config["mhc_sinkhorn_iters"],
            "hc_eps": float(config["train"]["hc_eps"]),
            # no clamp key is published: H_res' logits are not clamped
            "hc_lo": float("-inf"), "hc_hi": float("inf"),
            "poly_scale": float(config["polynorm_output_scale"]),
            "poly_clamp": float(config["polynorm_bias_clamp"]),
            "hidden_clamp": float(config["hidden_clamp"]),
            "mtp": config["num_nextn_predict_layers"],
            "mtp_weight": float(config["train"]["mtp_loss_weight"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "bias_update_rate": float(config["load_balance_coeff"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.motif import MotifConfig
    return MotifConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"],
        first_layer=s["first_layer"], heads=s["H"], kv_heads=s["Hkv"],
        num_noise_heads=s["noise"], q_lora_rank=s["rq"],
        kv_lora_rank=s["rkv"], qk_nope_head_dim=s["dn"],
        qk_rope_head_dim=s["dr"], v_head_dim=s["dv"],
        sliding_window=s["W"], sliding_window_period=s["period"],
        mlp_dim=s["M"], moe_mlp_dim=s["Me"], num_experts=s["X"],
        experts_held=s["Xh"], held_start=s["held_start"], top_k=s["k"],
        num_shared_experts=s["Ms"] // s["Me"], num_dense_layers=s["Ld"],
        route_scale=s["route_scale"], bias_update_rate=s["bias_update_rate"],
        polynorm_output_scale=s["poly_scale"],
        polynorm_bias_clamp=s["poly_clamp"], hidden_clamp=s["hidden_clamp"],
        hc_mult=s["n"], hc_sinkhorn_iters=s["hc_iters"], hc_eps=s["hc_eps"],
        hc_clamp=(s["hc_lo"], s["hc_hi"]), mtp_layers=s["mtp"],
        mtp_loss_weight=s["mtp_weight"], rope_theta=s["theta"],
        norm_eps=s["eps"], max_seq_len=max_seq_len, dtype=jnp.bfloat16,
        remat=opts["remat"], attention_impl=opts["attention"],
        loss_chunks=opts["loss_chunks"], layer_rows=opts["layer_rows"])


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant, 1 if not given]).  PolyNorm's four numbers are
    ``POLY_START``; ``w_lambda`` is a projection like any other, so that
    ``sigmoid(lambda)`` differs between tokens and heads (its logits are
    unit normal); the maps as ``archs/xing4_0``'s."""
    E, H, Hkv, V, n = s["E"], s["H"], s["Hkv"], s["V"], s["n"]
    rq, rkv, dn, dr, dv = (s[k] for k in ("rq", "rkv", "dn", "dr", "dv"))
    Hs, width = H - s["noise"], 2 * n + n * n
    import numpy as np
    poly = lambda L: ((L, 4), 0, np.asarray(POLY_START, np.float32))

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
            "wkv_b": ((L, rkv, Hkv, dn + dv), rkv),
            "w_lambda": ((L, E, Hs), E),
            "w_attn_gate": ((L, E, Hs * dv), E),
            "wo": ((L, Hs, dv, E), Hs * dv), **maps}

    M, Me, Ms, X, Xh = (s[k] for k in ("M", "Me", "Ms", "X", "Xh"))

    def moe_layer(L):
        return {**layer(L), "router": ((L, E, X), E),
                "shared_gate": ((L, E, Ms), E), "shared_up": ((L, E, Ms), E),
                "shared_down": ((L, Ms, E), Ms), "shared_poly": poly(L),
                "w_gate": ((L, Xh, E, Me), E), "w_up": ((L, Xh, E, Me), E),
                "w_down": ((L, Xh, Me, E), Me), "expert_poly": poly(L)}

    Ld = s["Ld"]
    tree = {
        "embed": ((V, E), E),
        "dense": {**layer(Ld), "w_gate": ((Ld, E, M), E),
                  "w_up": ((Ld, E, M), E), "w_down": ((Ld, M, E), M),
                  "mlp_poly": poly(Ld)},
        "moe": moe_layer(s["L"] - Ld),
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}
    if s["mtp"]:
        tree["mtp"] = {"h_norm": ((E,), 0), "e_norm": ((E,), 0),
                       "proj": ((2 * E, E), 2 * E), "final_norm": ((E,), 0),
                       "layer": moe_layer(1)}
    return tree


def _pick(p, names, polys=False, more=()):
    of = lambda part, kind: {n: part[n] for n in names + more + (
        POLYS[kind] if polys else ())}
    out = {"final_norm": p["final_norm"], "dense": of(p["dense"], "dense"),
           "moe": of(p["moe"], "moe")}
    if "mtp" in p:
        out["mtp"] = {**{n: p["mtp"][n] for n in MTP_NORMS},
                      "layer": of(p["mtp"]["layer"], "moe")}
    return out


def norms_of(p):
    """The RMSNorm weights: four a layer, the final one, the module's
    three."""
    return _pick(p, NORMS)


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight, every
    hyper-connection's phi, b and gains, PolyNorm's numbers and
    ``w_lambda``."""
    return _pick(p, NORMS + MAPS, polys=True, more=("w_lambda",))


def _parts(p, of):
    out = {"dense": of(p["dense"], "dense"), "moe": of(p["moe"], "moe")}
    if "mtp" in p:
        out["mtp"] = of(p["mtp"]["layer"], "moe")
    return out


def maps_of(p):
    """The hyper-connections' weights alone (of a judged tree too)."""
    return _parts(p, lambda part, _: {n: part[n] for n in MAPS})


def polys_of(p):
    """PolyNorm's numbers alone (of a judged tree too)."""
    return _parts(p, lambda part, kind: {n: part[n] for n in POLYS[kind]})


def lambdas_of(p):
    """``w_lambda`` alone (of a judged tree too)."""
    return _parts(p, lambda part, _: {"w_lambda": part["w_lambda"]})


def with_judged(w, judged):
    """``w`` with its judged weights replaced by ``judged``."""
    out = {**w, "final_norm": judged["final_norm"],
           "dense": {**w["dense"], **judged["dense"]},
           "moe": {**w["moe"], **judged["moe"]}}
    if "mtp" in w:
        out["mtp"] = {**w["mtp"],
                      **{n: judged["mtp"][n] for n in MTP_NORMS},
                      "layer": {**w["mtp"]["layer"],
                                **judged["mtp"]["layer"]}}
    return out


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``expert``: one routed
    expert's.  ``always``: what every token multiplies by, whatever its
    route: attention, the maps' thin products, the dense layers'
    feed-forward, shared experts, routers and the head (with a module its
    projection and the head a second time; the embedding is a lookup)."""
    import math

    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        return math.prod(tree[0])

    expert = 3 * s["E"] * s["Me"]
    held = size(shapes(s))
    layers = s["L"] - s["Ld"] + s["mtp"]
    embed = s["V"] * s["E"]
    return {"held": held, "expert": expert,
            "always": held - layers * s["Xh"] * expert
            - (0 if s["mtp"] else embed)}
