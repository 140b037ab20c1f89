"""Granite-4.0-H-Micro's ``granitemoehybrid`` stack (without experts) for the
benchmark: sizes from the config file, the program's configuration, the
layout of the weights (that of ``ray_tpu.models.granite_hybrid``'s parameter
tree: ``layers`` is a list with one dictionary a layer, whose names depend on
the layer's kind; no ``lm_head``), the start of the mixers' ``A_log`` and
``dt_bias``, the judged weights, the counts, and the reference."""

from __future__ import annotations

import math
from typing import Any, Dict

# What a Mamba-2 mixer's weights need whatever stack holds them: Mamba-2's
# start of ``A_log`` / ``dt_bias``, the mixers' judged weights and their
# names, a tree with its judged weights replaced.
from benchmark.archs.nemotron_h import (SSM, finish, ssm_of,  # noqa: F401
                                        with_judged)

NORMS = ("norm", "gate_norm", "mlp_norm")
#: ``layer_types``' words as the letters the ssm readers count (``M`` a mixer)
LETTER = {"mamba": "M", "attention": "*"}

#: Mamba-2's start of the time step, which config.json does not carry (the
#: ``granitemoehybrid`` modelling code's defaults; the file's ``assumed``)
TIME_STEP = {"dt_min": 0.001, "dt_max": 0.1, "dt_floor": 1e-4}


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_granite_hybrid
    return reference_granite_hybrid


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """Every value is a number or a string, so that the reference can key
    its programs by them; ``kinds`` a letter a layer."""
    if config["num_local_experts"] or config["num_experts_per_tok"]:
        raise ValueError("a granitemoehybrid file with experts: no router is "
                         "built here (num_local_experts must be 0)")
    if config["position_embedding_type"] != "nope" \
            or config["attention_bias"] or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] \
            or not config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm" \
            or config["mamba_n_groups"] != 1 \
            or config["intermediate_size"] \
            != config["shared_intermediate_size"] \
            or config["mamba_n_heads"] * config["mamba_d_head"] \
            != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("the stack here is the one Granite-4.0-H-Micro's "
                         "config.json states; the file says otherwise")
    # ``layer_types`` is kept whole, as published; the stack is its first
    # ``num_hidden_layers`` (as Nemotron's file keeps its pattern).
    L = config["num_hidden_layers"]
    kinds = "".join(LETTER[t] for t in config["layer_types"][:L])
    if len(kinds) != L:
        raise ValueError(f"layer_types names {len(kinds)} layers, "
                         f"num_hidden_layers {L}")
    return {"V": config["vocab_size"], "E": config["hidden_size"], "L": L,
            "kinds": kinds, "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "M": config["shared_intermediate_size"],
            "Hm": config["mamba_n_heads"], "P": config["mamba_d_head"],
            "N": config["mamba_d_state"], "G": config["mamba_n_groups"],
            "K": config["mamba_d_conv"],
            # the chunk the program's scan runs at (``train.chunk``; the
            # published ``mamba_chunk_size`` where the file names none)
            "Q": config.get("train", {}).get("chunk")
            or config["mamba_chunk_size"],
            **TIME_STEP, "eps": float(config["rms_norm_eps"]),
            "embedding_multiplier": float(config["embedding_multiplier"]),
            "residual_multiplier": float(config["residual_multiplier"]),
            "attention_multiplier": float(config["attention_multiplier"]),
            "logits_scaling": float(config["logits_scaling"])}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.granite_hybrid import (ATTENTION, MAMBA,
                                               GraniteHybridConfig)
    return GraniteHybridConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"],
        layer_types=tuple(MAMBA if k == "M" else ATTENTION
                          for k in s["kinds"]),
        heads=s["H"], kv_heads=s["Hkv"], head_dim=s["D"], mlp_dim=s["M"],
        mamba_heads=s["Hm"], mamba_head_dim=s["P"], ssm_state=s["N"],
        ssm_groups=s["G"], conv_kernel=s["K"], chunk_size=s["Q"],
        time_step_min=s["dt_min"], time_step_max=s["dt_max"],
        time_step_floor=s["dt_floor"],
        embedding_multiplier=s["embedding_multiplier"],
        residual_multiplier=s["residual_multiplier"],
        attention_multiplier=s["attention_multiplier"],
        logits_scaling=s["logits_scaling"], norm_eps=s["eps"],
        max_seq_len=max_seq_len, dtype=jnp.bfloat16, remat=opts["remat"],
        attention_impl=opts["attention"], loss_chunks=opts["loss_chunks"],
        layer_rows=opts["layer_rows"])


def _layer_shapes(s: Dict[str, Any], kind: str) -> Dict[str, Any]:
    E, M = s["E"], s["M"]
    if kind == "M":
        d, conv = s["Hm"] * s["P"], s["Hm"] * s["P"] + 2 * s["G"] * s["N"]
        mixer = {"w_in": ((E, d + conv + s["Hm"]), E),
                 "conv_w": ((s["K"], conv), s["K"]),
                 # not zero, so that a bias that is dropped or added twice
                 # shows: the scale a depthwise convolution's bias starts at
                 "conv_b": ((conv,), s["K"]),
                 # A_log and dt_bias are finished by ``make_weights`` below
                 "A_log": ((s["Hm"],), 0), "dt_bias": ((s["Hm"],), 0),
                 "D": ((s["Hm"],), 0), "gate_norm": ((d,), 0),
                 "w_out": ((d, E), d)}
    else:
        H, K, D = s["H"], s["Hkv"], s["D"]
        mixer = {"wq": ((E, H, D), E), "wk": ((E, K, D), E),
                 "wv": ((E, K, D), E), "wo": ((H, D, E), H * D)}
    return {"norm": ((E,), 0), **mixer, "mlp_norm": ((E,), 0),
            "w_gate": ((E, M), E), "w_up": ((E, M), E),
            "w_down": ((M, E), M)}


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant).
    The embedding at fan-in ``E``: rows of size 1 / sqrt(E) that the
    embedding multiplier of 12 brings to 0.27 an entry, the stream's size
    after a few layers of 0.22-scaled sublayers."""
    return {"embed": ((s["V"], s["E"]), s["E"]),
            "layers": [_layer_shapes(s, kind) for kind in s["kinds"]],
            "final_norm": ((s["E"],), 0)}


def make_weights(s: Dict[str, Any], seed: int, shardings=None):
    """The benchmark's weights for sizes ``s`` from ``seed``."""
    from benchmark import archs
    return finish(archs.make_weights(shapes(s), seed, shardings), s, seed)


def norms_of(p):
    """The RMSNorm weights: every layer's two, a mixer's gated norm, the
    final one."""
    return {"final_norm": p["final_norm"],
            "layers": [{n: layer[n] for n in NORMS if n in layer}
                       for layer in p["layers"]]}


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight and every
    mixer's ``A_log``, ``dt_bias``, ``D`` and convolution."""
    return {"final_norm": p["final_norm"],
            "layers": [{n: layer[n] for n in NORMS + SSM if n in layer}
                       for layer in p["layers"]]}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip, the tied table once.
    ``multiplied``: what a token multiplies by, which is ``held`` too: all
    but the table's look-up, and the tied head once."""
    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        if isinstance(tree, list):
            return sum(size(v) for v in tree)
        return math.prod(tree[0])

    held = size(shapes(s))
    return {"held": held, "multiplied": held}
