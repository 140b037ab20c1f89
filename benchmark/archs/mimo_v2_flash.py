"""MiMo-V2-Flash's ``mimo_v2_flash`` stack for the benchmark: sizes from the
config file, the program's configuration, the layout of the weights (that of
``ray_tpu.models.mimo_v2``'s parameter tree: ``layers`` is a list with one
dictionary a layer, whose shapes depend on the layer's kind, a window layer
holding a ``sink``, and on whether its F is dense), the judged weights, the
counts, and the reference."""

from __future__ import annotations

import math
from typing import Any, Dict

#: ``hybrid_layer_pattern``'s entries as the letters the reference keys its
#: programs by: 0 a full layer, 1 a window layer
LETTER = {0: "f", 1: "w"}
#: a layer's judged weights, where it has them (the reference's ``JUDGED``)
JUDGED = ("attn_norm", "mlp_norm", "sink")


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_mimo_v2
    return reference_mimo_v2


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The four head counts and ``n_routed_experts`` count what is held
    here; the published counts are under ``share``.  The stack is
    ``hybrid_layer_pattern`` / ``moe_layer_freq`` from ``share.first_layer``
    on.  Every value is a number or a string, so that the reference can key
    its programs by them."""
    share = config["share"]
    if config["attention_bias"] or config["tie_word_embeddings"] \
            or not config["norm_topk_prob"] \
            or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or (config["n_group"], config["topk_group"]) != (1, 1) \
            or config["n_shared_experts"] is not None \
            or config["routed_scaling_factor"] is not None \
            or not config["add_swa_attention_sink_bias"] \
            or config["add_full_attention_sink_bias"] \
            or config["hidden_act"] != "silu" \
            or (config["swa_head_dim"], config["swa_v_head_dim"]) != (
                config["head_dim"], config["v_head_dim"]) \
            or config["sliding_window_size"] != config["sliding_window"] \
            or config["rms_norm_eps"] != config["layernorm_epsilon"] \
            or share["attention_heads"] != share["swa_attention_heads"]:
        raise ValueError("the stack here is the one MiMo-V2-Flash's "
                         "config.json states; the file says otherwise")
    L, first = config["num_hidden_layers"], share["first_layer"]
    kinds = config["hybrid_layer_pattern"][first:first + L]
    sparse = config["moe_layer_freq"][first:first + L]
    if len(kinds) != L or sorted(sparse) != sparse:
        raise ValueError(f"the patterns hold no {L} layers from {first} on "
                         "with the dense ones first")
    H, Hp = config["num_attention_heads"], share["attention_heads"]
    held = lambda published: max(published * H // Hp, 1)
    if (config["swa_num_attention_heads"] != H or Hp % H
            or config["num_key_value_heads"] != held(
                share["key_value_heads"])
            or config["swa_num_key_value_heads"] != held(
                share["swa_key_value_heads"])):
        raise ValueError("the head counts are not one share of the "
                         "published 64 : 4 and 64 : 8")
    return {"V": config["vocab_size"], "E": config["hidden_size"], "L": L,
            "Ld": L - sum(sparse),
            "kinds": "".join(LETTER[k] for k in kinds),
            "H": H, "Hkv": config["num_key_value_heads"],
            "Hskv": config["swa_num_key_value_heads"], "Hp": Hp,
            "Hkvp": share["key_value_heads"],
            "Hskvp": share["swa_key_value_heads"],
            "head_start": share["head_start"],
            "D": config["head_dim"], "Dv": config["v_head_dim"],
            "R": int(config["partial_rotary_factor"] * config["head_dim"]),
            "W": config["sliding_window"],
            "M": config["intermediate_size"],
            "Me": config["moe_intermediate_size"],
            "X": share["router_outputs"], "Xh": config["n_routed_experts"],
            "held_start": share["held_start"],
            "k": config["num_experts_per_tok"],
            "route_scale": 1.0,
            "route_eps": float(config["assumed_values"]["route_eps"]),
            "value_scale": float(config["attention_value_scale"]),
            "theta": float(config["rope_theta"]),
            "swa_theta": float(config["swa_rope_theta"]),
            "eps": float(config["layernorm_epsilon"]),
            "sink_start": float(config["assumed_values"]["sink_start"]),
            "bias_update_rate": float(config["train"]["bias_update_rate"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.mimo_v2 import FULL, WINDOW, MimoV2Config
    return MimoV2Config(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"],
        layer_types=tuple({"f": FULL, "w": WINDOW}[c] for c in s["kinds"]),
        heads=s["Hp"], kv_heads=s["Hkvp"], swa_heads=s["Hp"],
        swa_kv_heads=s["Hskvp"],
        heads_held=None if s["H"] == s["Hp"] else s["H"],
        head_start=s["head_start"], head_dim=s["D"], v_head_dim=s["Dv"],
        rotary_dim=s["R"], rope_theta=s["theta"],
        swa_rope_theta=s["swa_theta"], sliding_window=s["W"],
        value_scale=s["value_scale"], sink_start=s["sink_start"],
        mlp_dim=s["M"], moe_mlp_dim=s["Me"], num_experts=s["X"],
        experts_held=s["Xh"], held_start=s["held_start"], top_k=s["k"],
        num_dense_layers=s["Ld"], route_scale=s["route_scale"],
        route_eps=s["route_eps"], bias_update_rate=s["bias_update_rate"],
        norm_eps=s["eps"], max_seq_len=max_seq_len, dtype=jnp.bfloat16,
        remat=opts["remat"], attention_impl=opts["attention"],
        loss_chunks=opts["loss_chunks"], layer_rows=opts["layer_rows"])


def _layer_shapes(s: Dict[str, Any], kind: str, dense: bool) -> Dict[str, Any]:
    E, H, D, Dv = s["E"], s["H"], s["D"], s["Dv"]
    K = s["Hskv"] if kind == "w" else s["Hkv"]
    attn = {"wq": ((E, H, D), E), "wk": ((E, K, D), E),
            "wv": ((E, K, Dv), E), "wo": ((H, Dv, E), H * Dv)}
    if kind == "w":
        attn["sink"] = ((H,), 0, s["sink_start"])
    if dense:
        f = {"w_gate": ((E, s["M"]), E), "w_up": ((E, s["M"]), E),
             "w_down": ((s["M"], E), s["M"])}
    else:
        Me, Xh = s["Me"], s["Xh"]
        f = {"router": ((E, s["X"]), E), "w_gate": ((Xh, E, Me), E),
             "w_up": ((Xh, E, Me), E), "w_down": ((Xh, Me, E), Me)}
    return {"attn_norm": ((E,), 0), **attn, "mlp_norm": ((E,), 0), **f}


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in[, start]; fan-in 0 marks a weight that starts
    at a constant: a norm's at one, a sink at ``sink_start``)."""
    return {"embed": ((s["V"], s["E"]), s["E"]),
            "layers": [_layer_shapes(s, kind, i < s["Ld"])
                       for i, kind in enumerate(s["kinds"])],
            "final_norm": ((s["E"],), 0),
            "lm_head": ((s["E"], s["V"]), s["E"])}


def make_weights(s: Dict[str, Any], seed: int, shardings=None):
    """The benchmark's weights for sizes ``s`` from ``seed``."""
    from benchmark import archs
    return archs.make_weights(shapes(s), seed, shardings)


def _pick(p, names):
    return {"final_norm": p["final_norm"],
            "layers": [{n: layer[n] for n in names if n in layer}
                       for layer in p["layers"]]}


def norms_of(p):
    """The RMSNorm weights: every layer's two and the final one."""
    return _pick(p, JUDGED[:2])


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight and
    every window layer's sink (of a judged tree too)."""
    return _pick(p, JUDGED)


def sinks_of(p):
    """The window layers' sinks alone (of a judged tree too)."""
    return [layer["sink"] for layer in p["layers"] if "sink" in layer]


def with_judged(w, judged):
    """``w`` with its judged weights replaced by ``judged``."""
    return {**w, "final_norm": judged["final_norm"],
            "layers": [{**layer, **j}
                       for layer, j in zip(w["layers"], judged["layers"])]}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``expert``: one routed
    expert's (three matrices).  ``always``: what every token multiplies by,
    whatever its route: the held heads' projections, a dense F, the routers
    and the head (the embedding's lookup multiplies nothing)."""
    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        if isinstance(tree, list):
            return sum(size(v) for v in tree)
        return math.prod(tree[0])

    expert = 3 * s["E"] * s["Me"]
    held = size(shapes(s))
    return {"held": held, "expert": expert,
            "always": held - s["V"] * s["E"]
            - (s["L"] - s["Ld"]) * s["Xh"] * expert}
