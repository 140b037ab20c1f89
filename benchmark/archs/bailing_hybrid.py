"""Ling-3.0-flash's ``bailing_hybrid`` block for the benchmark: sizes from the
config file, the program's configuration, the layout of the weights (that of
``ray_tpu.models.bailing_hybrid``'s parameter tree: a list of layers, each a
dictionary of its own), Kimi Linear's start of the KDA layers' ``A_log`` and
``dt_bias`` (float32), the judged norms, the counts, and the reference."""

from __future__ import annotations

import math
from typing import Any, Dict

KDA, MLA = "kda", "mla"

#: What the embedding starts at in the benchmark's weights: unit normal, as
#: Kanana's and Nemotron's files (``archs/deepseek_v3.EMBED_FAN_IN`` has the
#: reading: with the family's small embedding every row routes by its own
#: common direction and the held experts' loads are uneven).
EMBED_FAN_IN = 1


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_bailing_hybrid
    return reference_bailing_hybrid


def kinds_of(first_layer: int, layers: int, group: int):
    """The mixer of each held layer, by its published index."""
    return tuple(MLA if (first_layer + i + 1) % group == 0 else KDA
                 for i in range(layers))


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """``num_experts`` counts the experts held here; the router's width is
    the published count, which the file states under ``share``.  Every value
    is a number, a string, a tuple of strings or None, so that the reference
    can key its programs by them."""
    share = config["share"]
    held = range(share["first_layer"],
                 share["first_layer"] + config["num_hidden_layers"])
    if config["q_lora_rank"] is not None or config["rope_scaling"] is not None \
            or config["score_function"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or not config["kda_safe_gate"] or not config["no_kda_lora"] \
            or config["use_kda_lora"] or not config["linear_silu"] \
            or not config["use_qk_norm"] or config["group_norm_size"] != 1 \
            or config["num_kv_heads_for_linear_attn"] != 0 \
            or config["gated_attention_proj_granularity_type"] != "head_wise" \
            or config["use_bias"] or config["use_qkv_bias"] \
            or config["tie_word_embeddings"] or not config["norm_topk_prob"] \
            or config["hidden_act"] != "silu" or config["value_norm"] \
            or config["up_proj_norm"] or config["use_nGPT"] \
            or config["scale_router_input"] \
            or config["mtp_loss_scaling_factor"] != 0 \
            or any(config[k][i] for i in held for k in (
                "expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list")):
        raise ValueError("the block here is the one Ling-3.0-flash's "
                         "config.json states for its layers without a "
                         "clamp; the file says otherwise")
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "Ld": config["first_k_dense_replace"],
            "kinds": kinds_of(share["first_layer"],
                              config["num_hidden_layers"],
                              config["layer_group_size"]),
            "H": config["num_attention_heads"], "D": config["head_dim"],
            "K": config["short_conv_kernel_size"],
            "bound": float(config["kda_lower_bound"]),
            "rkv": config["kv_lora_rank"],
            "dn": config["qk_nope_head_dim"],
            "dr": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "M": config["intermediate_size"],
            "Me": config["moe_intermediate_size"],
            "Ms": config["moe_shared_expert_intermediate_size"]
            * config["num_shared_experts"],
            "X": share["router_outputs"], "Xh": config["num_experts"],
            "held_start": share["held_start"],
            "k": config["num_experts_per_tok"],
            "n_group": config["n_group"], "topk_group": config["topk_group"],
            "route_scale": float(config["routed_scaling_factor"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "Q": config["train"]["kda_chunk"],
            "first_layer": share["first_layer"],
            "group": config["layer_group_size"],
            "dt_min": float(config["kda_start"]["time_step_min"]),
            "dt_max": float(config["kda_start"]["time_step_max"]),
            "dt_floor": float(config["kda_start"]["time_step_floor"]),
            "bias_update_rate": float(config["train"]["bias_update_rate"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.bailing_hybrid import BailingHybridConfig
    return BailingHybridConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"],
        first_layer=s["first_layer"], layer_group_size=s["group"],
        heads=s["H"], head_dim=s["D"], conv_kernel=s["K"],
        kda_lower_bound=s["bound"], kda_chunk=s["Q"],
        time_step_min=s["dt_min"], time_step_max=s["dt_max"],
        time_step_floor=s["dt_floor"], q_lora_rank=None,
        kv_lora_rank=s["rkv"], qk_nope_head_dim=s["dn"],
        qk_rope_head_dim=s["dr"], v_head_dim=s["dv"], mlp_dim=s["M"],
        moe_mlp_dim=s["Me"], num_experts=s["X"], experts_held=s["Xh"],
        held_start=s["held_start"], top_k=s["k"], n_group=s["n_group"],
        topk_group=s["topk_group"], num_shared_experts=s["Ms"] // s["Me"],
        num_dense_layers=s["Ld"], route_scale=s["route_scale"],
        bias_update_rate=s["bias_update_rate"], rope_theta=s["theta"],
        norm_eps=s["eps"], max_seq_len=max_seq_len, dtype=jnp.bfloat16,
        remat=opts["remat"], attention_impl=opts["attention"],
        loss_chunks=opts["loss_chunks"], layer_rows=opts["layer_rows"])


def _layer_shapes(s: Dict[str, Any], kind: str, sparse: bool):
    E, H, F = s["E"], s["H"], s["H"] * s["D"]
    shapes = {"attn_norm": ((E,), 0), "mlp_norm": ((E,), 0)}
    if kind == KDA:
        shapes |= {
            "w_qkv": ((E, 3 * F), E), "conv_w": ((s["K"], 3 * F), s["K"]),
            "w_a": ((E, F), E), "w_beta": ((E, H), E), "w_g": ((E, F), E),
            # finished by ``make_weights`` below, in float32
            "A_log": ((H,), 0), "dt_bias": ((F,), 0),
            "o_norm": ((s["D"],), 0), "wo": ((F, E), F)}
    else:
        rkv, dn, dr, dv = (s[k] for k in ("rkv", "dn", "dr", "dv"))
        shapes |= {
            "kv_norm": ((rkv,), 0), "wq": ((E, H, dn + dr), E),
            "wkv_a": ((E, rkv + dr), E), "wkv_b": ((rkv, H, dn + dv), rkv),
            "w_head_gate": ((E, H), E), "wo": ((H, dv, E), H * dv)}
    if sparse:
        Me, Ms, X, Xh = (s[k] for k in ("Me", "Ms", "X", "Xh"))
        shapes |= {"router": ((E, X), E), "shared_gate": ((E, Ms), E),
                   "shared_up": ((E, Ms), E), "shared_down": ((Ms, E), Ms),
                   "w_gate": ((Xh, E, Me), E), "w_up": ((Xh, E, Me), E),
                   "w_down": ((Xh, Me, E), Me)}
    else:
        shapes |= {"w_gate": ((E, s["M"]), E), "w_up": ((E, s["M"]), E),
                   "w_down": ((s["M"], E), s["M"])}
    return shapes


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant, 1 if not given])."""
    return {"embed": ((s["V"], s["E"]), EMBED_FAN_IN),
            "layers": [_layer_shapes(s, kind, i >= s["Ld"])
                       for i, kind in enumerate(s["kinds"])],
            "final_norm": ((s["E"],), 0),
            "lm_head": ((s["E"], s["V"]), s["E"])}


def finish(w, s: Dict[str, Any], seed: int):
    """``w`` with every KDA layer's ``A_log`` and ``dt_bias`` as Kimi Linear
    starts them, float32, from ``seed`` (``archs.make_weights`` knows a
    normal draw and a constant in bfloat16): ``A_log`` the log of a uniform
    draw from [1, 16] a head, ``dt_bias`` the inverse softplus of a
    log-uniform draw from [``time_step_min``, ``time_step_max``] floored at
    ``time_step_floor`` a channel.  The leaves replaced are deleted."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    lo, hi = math.log(s["dt_min"]), math.log(s["dt_max"])
    H, F = s["H"], s["H"] * s["D"]
    for i, kind in enumerate(s["kinds"]):
        if kind != KDA:
            continue
        ka, kd = jax.random.split(jax.random.fold_in(
            weights.seed_key(seed), 1000 + i))
        step = jnp.maximum(jnp.exp(jax.random.uniform(kd, (F,))
                                   * (hi - lo) + lo), s["dt_floor"])
        start = {"A_log": jnp.log(jax.random.uniform(
            ka, (H,), minval=1.0, maxval=16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step))}
        for name, value in start.items():
            old = w["layers"][i][name]
            w["layers"][i][name] = jax.device_put(
                value.astype(jnp.float32), old.sharding)
            old.delete()
    return w


def make_weights(s: Dict[str, Any], seed: int, shardings=None):
    """The benchmark's weights for sizes ``s`` from ``seed``: bfloat16, the
    KDA layers' ``A_log`` and ``dt_bias`` float32."""
    from benchmark import archs
    return finish(archs.make_weights(shapes(s), seed, shardings), s, seed)


def norms_of(p):
    """The RMSNorm weights: three a layer (a KDA layer's head norm, a latent
    layer's ``kv_norm``) and the final one."""
    return {"final_norm": p["final_norm"],
            "layers": [{n: layer[n] for n in ("attn_norm", "mlp_norm",
                                              "o_norm", "kv_norm")
                        if n in layer} for layer in p["layers"]]}


def own_norms_of(p, name: str):
    """One mixer's own norms alone (of a judged tree too): ``o_norm``, whose
    gradient exists only through the delta rule, or ``kv_norm``, only
    through the latent."""
    return [layer[name] for layer in p["layers"] if name in layer]


def with_norms(w, norms):
    """``w`` with its RMSNorm weights replaced by ``norms``."""
    return {**w, "final_norm": norms["final_norm"],
            "layers": [{**layer, **n}
                       for layer, n in zip(w["layers"], norms["layers"])]}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``expert``: one routed
    expert's.  ``always``: what every token multiplies by, whatever its
    route: the mixers' projections, the dense layer's SwiGLU, the shared
    SwiGLU, routers and the head (the embedding is a lookup)."""
    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        if isinstance(tree, list):
            return sum(size(v) for v in tree)
        return math.prod(tree[0])

    expert = 3 * s["E"] * s["Me"]
    held = size(shapes(s))
    return {"held": held, "expert": expert,
            "always": held - (s["L"] - s["Ld"]) * s["Xh"] * expert
            - s["V"] * s["E"]}
