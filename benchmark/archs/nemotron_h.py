"""Nemotron-3-Nano's ``nemotron_h`` stack for the benchmark: sizes from the
config file, the program's configuration, the layout of the weights (that of
``ray_tpu.models.nemotron_h``'s parameter tree: ``layers`` is a list with one
dictionary a layer, whose names depend on the layer's letter), the start of
the mixers' ``A_log`` and ``dt_bias``, the judged weights, the counts, and
the reference."""

from __future__ import annotations

import math
from typing import Any, Dict

#: a mixer's weights that are judged beside the RMSNorm weights (the
#: reference's ``SSM``)
SSM = ("A_log", "dt_bias", "D", "conv_w", "conv_b")


#: What the embedding starts at in the benchmark's weights: unit normal
#: (``nn.Embedding``'s default; fan-in 1), where the family's own start is
#: 0.02, a row 52 times smaller than a sublayer's output.  A sublayer's
#: output has a part that every token of a row shares (silu and relu2 have
#: positive means, which the out-projection turns into one direction), so
#: with a small embedding every row routes by that direction: read on the
#: chip at the cell's sizes (PERF.md section 6, PR 43) the held experts'
#: largest load was 1.4 - 3.5 times their mean, their share of a step's
#: assignments moved with the seed (12,267 - 14,528 of an expected 12,288)
#: and tokens/s with it (0.4 % between the quartiles of five seeds).  With
#: the token's own row as large as the stream, and the out-projections
#: scaled as the config's ``rescale_prenorm_residual`` says (below), a
#: router sees mostly its token: 1.4 - 1.7 times the mean, 12,018 - 12,800
#: assignments, 0.17 % between the quartiles of four seeds, and gradient
#: distances an eighth of what they were (the same section).  A choice of
#: the benchmark's weights, not of the model, as Trinity-Mini's post-norm
#: start is.
EMBED_FAN_IN = 1


def reference():
    """The plain reference's module (it imports jax, which the benchmark's
    driver process may not)."""
    from benchmark import reference_nemotron_h
    return reference_nemotron_h


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """``n_routed_experts`` counts the experts held here; the router's width
    is the published count, which the file states under ``share``.  Every
    value is a number or a string, so that the reference can key its
    programs by them."""
    share = config["share"]
    if config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["attention_bias"] or config["mlp_bias"] \
            or config["mamba_proj_bias"] or not config["use_conv_bias"] \
            or config["tie_word_embeddings"] or config["residual_in_fp32"] \
            or config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu" \
            or config["n_shared_experts"] != 1 \
            or not config["norm_topk_prob"] \
            or config["norm_eps"] != config["layer_norm_epsilon"]:
        raise ValueError("the stack here is the one Nemotron-3-Nano-30B-A3B's "
                         "config.json states; the file says otherwise")
    L = config["num_hidden_layers"]
    return {"V": config["vocab_size"], "E": config["hidden_size"], "L": L,
            "kinds": config["hybrid_override_pattern"][:L],
            "pattern": config["hybrid_override_pattern"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "Hm": config["mamba_num_heads"], "P": config["mamba_head_dim"],
            "N": config["ssm_state_size"], "G": config["n_groups"],
            "K": config["conv_kernel"], "Q": config["chunk_size"],
            "dt_min": float(config["time_step_min"]),
            "dt_max": float(config["time_step_max"]),
            "dt_floor": float(config["time_step_floor"]),
            "Me": config["moe_intermediate_size"],
            "Ms": config["moe_shared_expert_intermediate_size"],
            "X": share["router_outputs"], "Xh": config["n_routed_experts"],
            "held_start": share["held_start"],
            "k": config["num_experts_per_tok"],
            "route_scale": float(config["routed_scaling_factor"]),
            "eps": float(config["layer_norm_epsilon"]),
            "bias_update_rate": float(config["train"]["bias_update_rate"]),
            # ``rescale_prenorm_residual``: every out-projection starts
            # 1 / sqrt(layers of the whole model) smaller
            "rescale": config["published"]["num_hidden_layers"]
            if config["rescale_prenorm_residual"] else 1}


def program_config(s: Dict[str, Any], max_seq_len: int, opts: Dict[str, Any]):
    import jax.numpy as jnp
    from ray_tpu.models.nemotron_h import NemotronHConfig
    return NemotronHConfig(
        vocab_size=s["V"], hidden=s["E"], layers=s["L"],
        pattern=s["pattern"], heads=s["H"], kv_heads=s["Hkv"],
        head_dim=s["D"], mamba_heads=s["Hm"], mamba_head_dim=s["P"],
        ssm_state=s["N"], ssm_groups=s["G"], conv_kernel=s["K"],
        chunk_size=s["Q"], time_step_min=s["dt_min"],
        time_step_max=s["dt_max"], time_step_floor=s["dt_floor"],
        moe_mlp_dim=s["Me"], shared_mlp_dim=s["Ms"], num_experts=s["X"],
        experts_held=s["Xh"], held_start=s["held_start"], top_k=s["k"],
        route_scale=s["route_scale"], bias_update_rate=s["bias_update_rate"],
        norm_eps=s["eps"], max_seq_len=max_seq_len, dtype=jnp.bfloat16,
        remat=opts["remat"], attention_impl=opts["attention"],
        loss_chunks=opts["loss_chunks"], layer_rows=opts["layer_rows"])


def _layer_shapes(s: Dict[str, Any], kind: str) -> Dict[str, Any]:
    E, n = s["E"], s["rescale"]
    if kind == "M":
        d, conv = s["Hm"] * s["P"], s["Hm"] * s["P"] + 2 * s["G"] * s["N"]
        return {"norm": ((E,), 0), "w_in": ((E, d + conv + s["Hm"]), E),
                "conv_w": ((s["K"], conv), s["K"]),
                # not zero, so that a bias that is dropped or added twice
                # shows: the scale a depthwise convolution's bias starts at
                "conv_b": ((conv,), s["K"]),
                # A_log and dt_bias are finished by ``make_weights`` below
                "A_log": ((s["Hm"],), 0), "dt_bias": ((s["Hm"],), 0),
                "D": ((s["Hm"],), 0), "gate_norm": ((d,), 0),
                "w_out": ((d, E), d * n)}
    if kind == "E":
        return {"norm": ((E,), 0), "router": ((E, s["X"]), E),
                "shared_up": ((E, s["Ms"]), E),
                "shared_down": ((s["Ms"], E), s["Ms"] * n),
                "w_up": ((s["Xh"], E, s["Me"]), E),
                "w_down": ((s["Xh"], s["Me"], E), s["Me"] * n)}
    H, K, D = s["H"], s["Hkv"], s["D"]
    return {"norm": ((E,), 0), "wq": ((E, H, D), E), "wk": ((E, K, D), E),
            "wv": ((E, K, D), E), "wo": ((H, D, E), H * D * n)}


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """leaf -> (shape, fan-in; 0 marks a weight that starts at a constant[,
    the constant, 1 if not given])."""
    return {"embed": ((s["V"], s["E"]), EMBED_FAN_IN),
            "layers": [_layer_shapes(s, kind) for kind in s["kinds"]],
            "final_norm": ((s["E"],), 0),
            "lm_head": ((s["E"], s["V"]), s["E"])}


def finish(w, s: Dict[str, Any], seed: int):
    """``w`` with every mixer's ``A_log`` and ``dt_bias`` as Mamba-2 starts
    them, from ``seed`` and the config's own keys (``archs.make_weights``
    knows a normal draw and a constant): A uniform in [1, 16], the time step
    log-uniform in [``time_step_min``, ``time_step_max``] floored at
    ``time_step_floor``, ``dt_bias`` its inverse softplus.  ``D`` stays at
    1.  The leaves replaced are deleted."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    lo, hi = math.log(s["dt_min"]), math.log(s["dt_max"])
    for i, kind in enumerate(s["kinds"]):
        if kind != "M":
            continue
        ka, kd = jax.random.split(jax.random.fold_in(
            weights.seed_key(seed), 1000 + i))
        step = jnp.maximum(jnp.exp(jax.random.uniform(kd, (s["Hm"],))
                                   * (hi - lo) + lo), s["dt_floor"])
        start = {"A_log": jnp.log(jax.random.uniform(
            ka, (s["Hm"],), minval=1.0, maxval=16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step))}
        for name, value in start.items():
            old = w["layers"][i][name]
            w["layers"][i][name] = jax.device_put(
                value.astype(old.dtype), old.sharding)
            old.delete()
    return w


def make_weights(s: Dict[str, Any], seed: int, shardings=None):
    """The benchmark's weights for sizes ``s`` from ``seed``."""
    from benchmark import archs
    return finish(archs.make_weights(shapes(s), seed, shardings), s, seed)


def norms_of(p):
    """The RMSNorm weights: every layer's, a mixer's gated norm, the final
    one."""
    return {"final_norm": p["final_norm"],
            "layers": [{n: layer[n] for n in ("norm", "gate_norm")
                        if n in layer} for layer in p["layers"]]}


def judged_of(p):
    """The weights whose gradients are judged: every RMSNorm weight and every
    mixer's ``A_log``, ``dt_bias``, ``D`` and convolution."""
    return {"final_norm": p["final_norm"],
            "layers": [{n: layer[n] for n in ("norm", "gate_norm") + SSM
                        if n in layer} for layer in p["layers"]]}


def ssm_of(p):
    """The mixers' own judged weights alone (of a judged tree too)."""
    return [{n: layer[n] for n in SSM} for layer in p["layers"]
            if "A_log" in layer]


def with_judged(w, judged):
    """``w`` with its judged weights replaced by ``judged``."""
    return {**w, "final_norm": judged["final_norm"],
            "layers": [{**layer, **j}
                       for layer, j in zip(w["layers"], judged["layers"])]}


def parameters(s: Dict[str, Any]) -> Dict[str, int]:
    """``held``: every parameter on this chip.  ``expert``: one routed
    expert's (two matrices: no gate).  ``always``: what every token
    multiplies by, whatever its route: mixers, attention, shared experts,
    routers and the head (the embedding is a lookup)."""
    def size(tree):
        if isinstance(tree, dict):
            return sum(size(v) for v in tree.values())
        if isinstance(tree, list):
            return sum(size(v) for v in tree)
        return math.prod(tree[0])

    expert = 2 * s["E"] * s["Me"]
    held = size(shapes(s))
    return {"held": held, "expert": expert,
            "always": held - s["kinds"].count("E") * s["Xh"] * expert
            - s["V"] * s["E"]}
