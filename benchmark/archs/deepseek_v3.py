"""DeepSeek-V3's ``deepseek_v3`` block (kanana-2-30b-a3b) for the benchmark:
sizes from the config file, the program's configuration, the layout of the
weights (that of ``ray_tpu.models.deepseek_v3``'s parameter tree: ``dense``
and ``moe`` stacks with a leading layer axis), the judged norms, the counts,
and the reference."""

from __future__ import annotations

import math
from typing import Any, Dict

#: a layer's RMSNorm weights (the reference's ``NORMS``); ``kv_norm``'s
#: gradient exists only through the latent path
NORMS = ("attn_norm", "mlp_norm", "kv_norm")

#: What the embedding starts at in the benchmark's weights: unit normal
#: (``nn.Embedding``'s default, as Nemotron's file), where the family's own
#: start is 0.02, a row 45 times smaller than a sublayer's output.  Attention's
#: output is nearly the same vector for every late token of a row, so with a
#: small embedding every row routes by its own common direction: read on the
#: chip at the cell's sizes (PERF.md section 6, PR 51) the held experts'
#: largest load was 3.5 - 3.8 times their mean and a layer call took the
#: buffer in slices; with the token's own row as large as the stream it is
#: 1.4 - 1.65.  The out-projections stay at their own fan-in: scaled down as
#: Nemotron's file scales them the int8 control comes within 1.21 of the
#: program (the config file's ``weights`` has the five starts read).  A
#: choice of the benchmark's weights, not of the model.
EMBED_FAN_IN = 1


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_deepseek_v3
    return reference_deepseek_v3


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """``n_routed_experts`` counts the experts held here; the router's width
    is the published count, which the file states under ``share``.  Every
    value is a number, a string or None, so that the reference can key its
    programs by them."""
    share = config["share"]
    if config["q_lora_rank"] is not None or config["rope_scaling"] is not None \
            or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["attention_bias"] or config["tie_word_embeddings"] \
            or config["moe_layer_freq"] != 1 or not config["norm_topk_prob"] \
            or config["hidden_act"] != "silu":
        raise ValueError("the block here is the one kanana-2-30b-a3b's "
                         "config.json states; the file says otherwise")
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "Ld": config["first_k_dense_replace"],
            "H": config["num_attention_heads"],
            "rkv": config["kv_lora_rank"],
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
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "bias_update_rate": float(config["train"]["bias_update_rate"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.deepseek_v3 import DeepseekV3Config
    return DeepseekV3Config(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"], heads=s["H"],
        q_lora_rank=None, kv_lora_rank=s["rkv"], qk_nope_head_dim=s["dn"],
        qk_rope_head_dim=s["dr"], v_head_dim=s["dv"], mlp_dim=s["M"],
        moe_mlp_dim=s["Me"], num_experts=s["X"], experts_held=s["Xh"],
        held_start=s["held_start"], top_k=s["k"],
        num_shared_experts=s["Ms"] // s["Me"], num_dense_layers=s["Ld"],
        route_scale=s["route_scale"], bias_update_rate=s["bias_update_rate"],
        rope_theta=s["theta"], yarn=None, norm_eps=s["eps"],
        max_seq_len=max_seq_len, dtype=jnp.bfloat16, remat=opts["remat"],
        attention_impl=opts["attention"], loss_chunks=opts["loss_chunks"],
        layer_rows=opts["layer_rows"])


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a norm weight, which starts at 1)."""
    E, H, V = s["E"], s["H"], s["V"]
    rkv, dn, dr, dv = (s[k] for k in ("rkv", "dn", "dr", "dv"))
    M, Me, Ms, X, Xh = (s[k] for k in ("M", "Me", "Ms", "X", "Xh"))

    def layer(L):
        return {"attn_norm": ((L, E), 0), "mlp_norm": ((L, E), 0),
                "kv_norm": ((L, rkv), 0),
                "wq": ((L, E, H, dn + dr), E),
                "wkv_a": ((L, E, rkv + dr), E),
                "wkv_b": ((L, rkv, H, dn + dv), rkv),
                "wo": ((L, H, dv, E), H * dv)}

    Ld, Lm = s["Ld"], s["L"] - s["Ld"]
    return {
        "embed": ((V, E), EMBED_FAN_IN),
        "dense": {**layer(Ld), "w_gate": ((Ld, E, M), E),
                  "w_up": ((Ld, E, M), E), "w_down": ((Ld, M, E), M)},
        "moe": {**layer(Lm), "router": ((Lm, E, X), E),
                "shared_gate": ((Lm, E, Ms), E),
                "shared_up": ((Lm, E, Ms), E),
                "shared_down": ((Lm, Ms, E), Ms),
                "w_gate": ((Lm, Xh, E, Me), E), "w_up": ((Lm, Xh, E, Me), E),
                "w_down": ((Lm, Xh, Me, E), Me)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E)}


def norms_of(p):
    """The RMSNorm weights: three a layer and the final one."""
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
    route: attention's projections, the dense layer's SwiGLU, the shared
    SwiGLU, routers and the head (the embedding is a lookup)."""
    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        return math.prod(tree[0])

    expert = 3 * s["E"] * s["Me"]
    held = size(shapes(s))
    return {"held": held, "expert": expert,
            "always": held - (s["L"] - s["Ld"]) * s["Xh"] * expert
            - s["V"] * s["E"]}
