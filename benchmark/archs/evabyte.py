"""EvaByte's chunked-attention stack for the benchmark: sizes from the
config file, the program's configuration, the layout of the weights (that of
``ray_tpu.models.evabyte``'s parameter tree: ``blocks`` with a leading layer
axis, two offset norms and two pooling vectors a layer, and eight heads in
one ``lm_head``), the judged weights, the counts, and the reference."""

from __future__ import annotations

from typing import Any, Dict

#: a layer's judged weights (the reference's ``JUDGED``): its two RMSNorm
#: offsets and its two pooling vectors
NORMS = ("attn_norm", "mlp_norm")
POOLING = ("eva_mu", "eva_phi")


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_evabyte
    return reference_evabyte


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    if config["attention_class"] != "eva" or config["tie_word_embeddings"] \
            or config["attention_bias"]:
        raise ValueError("the stack here is EVA's with no bias and untied "
                         "heads, as EvaByte's config.json states")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("EVA has a key head a query head")
    if not (config["norm_add_unit_offset"] and config["fp32_skip_add"]
            and config["fp32_logits"]):
        raise ValueError("the program adds in float32, offsets its norms by "
                         "one and forms float32 logits; the config says "
                         "otherwise")
    return {"V": config["vocab_size"], "E": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "M": config["intermediate_size"],
            "window": config["window_size"], "chunk": config["chunk_size"],
            "J": config["num_pred_heads"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.evabyte import EvaByteConfig
    return EvaByteConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"], heads=s["H"],
        kv_heads=s["Hkv"], head_dim=s["D"], mlp_dim=s["M"],
        window=s["window"], chunk=s["chunk"], pred_heads=s["J"],
        rope_theta=s["theta"], norm_eps=s["eps"], max_seq_len=max_seq_len,
        dtype=jnp.bfloat16, remat=opts["remat"],
        attention_impl=opts["attention"], loss_chunks=opts["loss_chunks"])


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant]).  A norm's weight is its offset ``g`` and starts at 0,
    as the model's does; the pooling vectors stand where a query stands
    against a key and start at a query's size (unit normal: fan-in 1), so
    that a chunk's pooling is no plain mean and the two vectors' gradients
    are no rounding."""
    L, E, H, D, M, V, J = (s[k] for k in ("L", "E", "H", "D", "M", "V", "J"))
    return {
        "embed": ((V, E), E),
        "blocks": {
            "attn_norm": ((L, E), 0, 0.0), "mlp_norm": ((L, E), 0, 0.0),
            "wq": ((L, E, H, D), E), "wk": ((L, E, H, D), E),
            "wv": ((L, E, H, D), E), "wo": ((L, H, D, E), H * D),
            "eva_mu": ((L, H, D), 1), "eva_phi": ((L, H, D), 1),
            "w_gate": ((L, E, M), E), "w_up": ((L, E, M), E),
            "w_down": ((L, M, E), M)},
        "final_norm": ((E,), 0, 0.0),
        "lm_head": ((E, J, V), E)}


def norms_of(p):
    """The RMSNorm weights: two a layer and the final one."""
    return {"final_norm": p["final_norm"],
            "blocks": {n: p["blocks"][n] for n in NORMS}}


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight and the
    two pooling vectors of every layer, whose gradient exists only through
    the pooling's backward and the attention's second operand."""
    return {"final_norm": p["final_norm"],
            "blocks": {n: p["blocks"][n] for n in NORMS + POOLING}}


def with_judged(w, judged):
    """``w`` with its judged weights replaced by ``judged``."""
    return {**w, "final_norm": judged["final_norm"],
            "blocks": {**w["blocks"], **judged["blocks"]}}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``layer_products``: what a
    token multiplies by in one layer (the projections and the two pooling
    vectors; a norm's weight is no product).  ``heads``: the eight heads.
    ``multiplied_a_token``: all of it once a token of a step (the embedding
    is a lookup); attention's own products are not in it."""
    import math

    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        return math.prod(tree[0])

    E, H, D = s["E"], s["H"], s["D"]
    layer = 4 * E * H * D + 3 * E * s["M"] + 2 * H * D
    heads = s["J"] * E * s["V"]
    return {"held": size(shapes(s)), "layer_products": layer, "heads": heads,
            "multiplied_a_token": s["L"] * layer + heads}
