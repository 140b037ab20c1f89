"""LFM2-24B-A2B's ``lfm2_moe`` stack for the benchmark: sizes from the config
file, the program's configuration, the layout of the weights (that of
``ray_tpu.models.lfm2``'s parameter tree: ``layers`` is a list with one
dictionary a layer, whose names depend on the layer's operator and on whether
its F is dense; no ``lm_head``: the head is the embedding's own matrix), the
judged weights, the counts, and the reference."""

from __future__ import annotations

import math
from typing import Any, Dict

#: ``layer_types``' entries as the letters the reference keys its programs by
LETTER = {"conv": "c", "full_attention": "a"}
#: a layer's judged weights, by its letter (the reference's ``JUDGED``)
JUDGED = {"c": ("op_norm", "ffn_norm", "conv_w"),
          "a": ("op_norm", "ffn_norm", "q_norm", "k_norm")}

#: What the embedding starts at in the benchmark's weights: its fan-in is the
#: hidden size (rows of norm one, 0.022 an element: the family's 0.02), NOT
#: the unit normal that ``archs/nemotron_h.EMBED_FAN_IN`` = 1 gives and that
#: ISSUE 47 asked for.  The matrix is the head too: at fan-in 1 the logits of
#: a unit-rms final stream have a spread of sqrt(hidden) = 45, the softmax is
#: one-hot, the loss some 190, and the gradient of a token is decided by
#: which logit is largest, which rounding flips: no comparison with a
#: reference holds there.  At the fan-in the logits' spread is 1 and the loss
#: starts at ln(8,192) + 0.5.  What fan-in 1 was for in Nemotron (a
#: sublayer's output with a part every token shares, from relu2's and
#: silu's positive means, which a small embedding lets every row route by)
#: has no counterpart here: the convolution operator's, attention's and a
#: gated expert's outputs are products with zero-mean factors.  The held
#: experts' loads over seeds are in PERF.md section 6, PR 47.
EMBED_FAN_IN = None         # None: the hidden size


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_lfm2
    return reference_lfm2


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """``num_experts`` counts the experts held here; the router's width is
    the published count, which the file states under ``share``.  The stack is
    ``layer_types`` from ``share.first_layer`` on.  Every value is a number
    or a string, so that the reference can key its programs by them."""
    share = config["share"]
    if config["conv_bias"] or not config["use_expert_bias"] \
            or not config["norm_topk_prob"] \
            or config["rope_parameters"]["rope_type"] != "default" \
            or config["rope_parameters"]["rope_theta"] != config["rope_theta"] \
            or config["norm_eps"] != config["rms_norm_eps"] \
            or config["head_dim"] * config["num_attention_heads"] \
            != config["hidden_size"]:
        raise ValueError("the stack here is the one LFM2-24B-A2B's "
                         "config.json states; the file says otherwise")
    L, first = config["num_hidden_layers"], share["first_layer"]
    kinds = config["layer_types"][first:first + L]
    if len(kinds) != L:
        raise ValueError(f"layer_types holds no {L} layers from {first} on")
    return {"V": config["vocab_size"], "E": config["hidden_size"], "L": L,
            "Ld": config["num_dense_layers"],
            "kinds": "".join(LETTER[k] for k in kinds),
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "K": config["conv_L_cache"], "M": config["intermediate_size"],
            "Me": config["moe_intermediate_size"],
            "X": share["router_outputs"], "Xh": config["num_experts"],
            "held_start": share["held_start"],
            "k": config["num_experts_per_tok"],
            "route_scale": float(config["routed_scaling_factor"]),
            "route_eps": float(config["assumed_values"]["route_eps"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["norm_eps"]),
            "bias_update_rate": float(config["train"]["bias_update_rate"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.lfm2 import CONV, FULL, Lfm2Config
    return Lfm2Config(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"],
        layer_types=tuple({"c": CONV, "a": FULL}[c] for c in s["kinds"]),
        heads=s["H"], kv_heads=s["Hkv"], head_dim=s["D"],
        conv_kernel=s["K"], mlp_dim=s["M"], moe_mlp_dim=s["Me"],
        num_experts=s["X"], experts_held=s["Xh"],
        held_start=s["held_start"], top_k=s["k"],
        num_dense_layers=s["Ld"], route_scale=s["route_scale"],
        route_eps=s["route_eps"], bias_update_rate=s["bias_update_rate"],
        rope_theta=s["theta"], norm_eps=s["eps"], max_seq_len=max_seq_len,
        dtype=jnp.bfloat16, remat=opts["remat"],
        attention_impl=opts["attention"], loss_chunks=opts["loss_chunks"],
        layer_rows=opts["layer_rows"])


def _layer_shapes(s: Dict[str, Any], kind: str, dense: bool) -> Dict[str, Any]:
    E = s["E"]
    if kind == "c":
        op = {"w_in": ((E, 3 * E), E), "conv_w": ((s["K"], E), s["K"]),
              "w_out": ((E, E), E)}
    else:
        H, K, D = s["H"], s["Hkv"], s["D"]
        op = {"wq": ((E, H, D), E), "wk": ((E, K, D), E),
              "wv": ((E, K, D), E), "q_norm": ((D,), 0), "k_norm": ((D,), 0),
              "wo": ((H, D, E), H * D)}
    if dense:
        f = {"w_gate": ((E, s["M"]), E), "w_up": ((E, s["M"]), E),
             "w_down": ((s["M"], E), s["M"])}
    else:
        Me, Xh = s["Me"], s["Xh"]
        f = {"router": ((E, s["X"]), E), "w_gate": ((Xh, E, Me), E),
             "w_up": ((Xh, E, Me), E), "w_down": ((Xh, Me, E), Me)}
    return {"op_norm": ((E,), 0), **op, "ffn_norm": ((E,), 0), **f}


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a norm weight, which starts at one)."""
    return {"embed": ((s["V"], s["E"]), EMBED_FAN_IN or s["E"]),
            "layers": [_layer_shapes(s, kind, i < s["Ld"])
                       for i, kind in enumerate(s["kinds"])],
            "final_norm": ((s["E"],), 0)}


def make_weights(s: Dict[str, Any], seed: int, shardings=None):
    """The benchmark's weights for sizes ``s`` from ``seed``."""
    from benchmark import archs
    return archs.make_weights(shapes(s), seed, shardings)


def _pick(p, names_of):
    return {"final_norm": p["final_norm"],
            "layers": [{n: layer[n] for n in names_of(layer)}
                       for layer in p["layers"]]}


def _letter(layer) -> str:
    return "c" if "conv_w" in layer else "a"


def norms_of(p):
    """The RMSNorm weights: every layer's two, an attention layer's q and k
    head norms, the final one."""
    return _pick(p, lambda layer: [n for n in JUDGED[_letter(layer)]
                                   if n != "conv_w"])


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight and
    every convolution's taps (of a judged tree too)."""
    return _pick(p, lambda layer: JUDGED[_letter(layer)])


def taps_of(p):
    """The convolutions' taps alone (of a judged tree too)."""
    return [layer["conv_w"] for layer in p["layers"] if "conv_w" in layer]


def with_judged(w, judged, embed=None):
    """``w`` with its judged weights replaced by ``judged`` (and its
    embedding by ``embed``, where given)."""
    return {**w, "final_norm": judged["final_norm"],
            "layers": [{**layer, **j}
                       for layer, j in zip(w["layers"], judged["layers"])],
            **({} if embed is None else {"embed": embed})}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip, the tied matrix once.
    ``expert``: one routed expert's (three matrices).  ``always``: what every
    token multiplies by, whatever its route: the operators, the dense F, the
    routers and the head, which is the embedding's matrix read a second time
    (the lookup itself multiplies nothing), so ``held`` less the experts."""
    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        if isinstance(tree, list):
            return sum(size(v) for v in tree)
        return math.prod(tree[0])

    expert = 3 * s["E"] * s["Me"]
    held = size(shapes(s))
    return {"held": held, "expert": expert,
            "always": held - (s["L"] - s["Ld"]) * s["Xh"] * expert}
