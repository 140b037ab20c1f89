"""Ouro's looped stack for the benchmark: sizes from the config file, the
program's configuration, the layout of the weights (that of
``ray_tpu.models.ouro``'s parameter tree: ``blocks`` with a leading layer
axis and four norms a layer, and the exit gate), the judged weights, the
counts, and the reference."""

from __future__ import annotations

from typing import Any, Dict

#: the four RMSNorm weights of a layer (the reference's ``NORMS``)
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_ouro
    return reference_ouro


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    if config["use_sliding_window"] or config["tie_word_embeddings"]:
        raise ValueError("the looped stack here has no window and an untied "
                         "head, as Ouro-2.6B's config.json states")
    if set(config["layer_types"]) != {"full_attention"}:
        raise ValueError(f"layer_types: {set(config['layer_types'])}")
    a = config["assumed"]
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "T": config["total_ut_steps"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "M": config["intermediate_size"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "beta": float(a["exit_entropy_coef"]["value"]),
            "post_norm_start": float(config["weights_start"]["post_norm"]),
            "gate_start": float(config["weights_start"]["exit_gate_w"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.ouro import OuroConfig
    return OuroConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"], heads=s["H"],
        kv_heads=s["Hkv"], head_dim=s["D"], mlp_dim=s["M"], loops=s["T"],
        rope_theta=s["theta"], norm_eps=s["eps"],
        exit_entropy_coef=s["beta"], max_seq_len=max_seq_len,
        dtype=jnp.bfloat16, remat=opts["remat"],
        attention_impl=opts["attention"], loss_chunks=opts["loss_chunks"])


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant, 1 if not given]).  The gate's ``w`` starts as a
    projection scaled by ``gate_start`` (truncated normal * gate_start /
    sqrt(E), stated as a fan-in of E / gate_start^2), so that the exit
    distribution differs by position and pass; its bias at 0.  Why both
    starts are under 1: the configuration's ``weights_start``."""
    L, E, H, K, D, M, V = (s[k] for k in ("L", "E", "H", "Hkv", "D", "M", "V"))
    post = s["post_norm_start"]
    gate_fan_in = round(E / s["gate_start"] ** 2)
    return {
        "embed": ((V, E), E),
        "blocks": {
            "attn_norm": ((L, E), 0), "attn_post_norm": ((L, E), 0, post),
            "mlp_norm": ((L, E), 0), "mlp_post_norm": ((L, E), 0, post),
            "wq": ((L, E, H, D), E), "wk": ((L, E, K, D), E),
            "wv": ((L, E, K, D), E), "wo": ((L, H, D, E), H * D),
            "w_gate": ((L, E, M), E), "w_up": ((L, E, M), E),
            "w_down": ((L, M, E), M)},
        "final_norm": ((E,), 0),
        "lm_head": ((E, V), E),
        "exit_gate": {"w": ((E,), gate_fan_in), "b": ((), 0, 0.0)}}


def norms_of(p):
    """The RMSNorm weights: four a layer and the final one."""
    return {"final_norm": p["final_norm"],
            "blocks": {n: p["blocks"][n] for n in NORMS}}


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight and the
    exit gate, whose gradient exists only through the weighing of the
    passes' losses."""
    return {**norms_of(p), "exit_gate": p["exit_gate"]}


def with_judged(w, judged):
    """``w`` with its judged weights replaced by ``judged``."""
    return {**w, "final_norm": judged["final_norm"],
            "blocks": {**w["blocks"], **judged["blocks"]},
            "exit_gate": judged["exit_gate"]}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``layer_products``: what a
    token multiplies by in one call of one layer (the projections; a norm's
    weight is no product).  ``head`` and ``gate``: what it multiplies by
    after each pass.  ``multiplied_a_token``: all of it as often as a token
    of a step meets it, ``T`` passes over ``L`` layers and ``T`` heads and
    gates (the embedding is a lookup)."""
    import math

    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        return math.prod(tree[0])

    E, T, L = s["E"], s["T"], s["L"]
    layer = 2 * E * s["H"] * s["D"] + 2 * E * s["Hkv"] * s["D"] \
        + 3 * E * s["M"]
    head, gate = E * s["V"], E
    return {"held": size(shapes(s)), "layer_products": layer, "head": head,
            "gate": gate,
            "multiplied_a_token": T * (L * layer + head + gate)}
