"""The dense decoder: pre-norm llama-style layers, every one alike (GQA
attention under the SALO window plus sinks, a SwiGLU MLP), RoPE, tied or
untied output head. ``smollm-135m`` names it.

An architecture module gives the benchmark everything it knows of one
kind of model, from a configuration's published keys:

  dims(spec)                   the sizes the benchmark's own code uses; at
                               least ``d`` (the output head's width) and
                               ``V`` (the vocabulary)
  model_config(spec)           the program's ``ModelConfig``
  param_shapes(spec), nest()   the weights' leaves and the program's tree
  served_gaps(...), follow()   the plain float32 references
  matmul_params_per_token(m)   weights one token multiplies by in its
                               layers (active experts only; the output
                               head apart)
  attention_layers(m)          [(count, H, Hkv, hd, window, sinks)], with
                               ``window=None`` for full causal attention
"""
from __future__ import annotations

from chipbench.reference import decoder
from chipbench.reference import train as ref_train


def dims(spec: dict) -> dict:
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    return {"L": spec["num_hidden_layers"], "d": d, "H": H,
            "Hkv": spec["num_key_value_heads"], "hd": d // H,
            "f": spec["intermediate_size"], "V": spec["vocab_size"],
            "eps": spec["rms_norm_eps"], "theta": spec["rope_theta"],
            "tied": bool(spec["tie_word_embeddings"]),
            "window": spec["salo_window"], "sinks": spec["salo_sinks"]}


def model_config(spec: dict):
    from repro.configs.base import ModelConfig, SALOConfig

    if spec["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {spec['hidden_act']!r}")
    dt = {"bfloat16": "bfloat16", "float32": "float32"}[spec["torch_dtype"]]
    m = dims(spec)
    return ModelConfig(
        name=spec["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["Hkv"], d_ff=m["f"],
        vocab_size=m["V"], act="swiglu", norm_eps=m["eps"],
        rope_theta=m["theta"], tie_embeddings=m["tied"],
        salo=SALOConfig(window=m["window"], n_global=m["sinks"]),
        param_dtype=dt, compute_dtype=dt)


def param_shapes(spec: dict) -> dict:
    """Leaf name -> (shape, fan-in or None for norm scales). Stacked
    layer leaves lead with the layer axis, as the program scans them."""
    m = dims(spec)
    L, d, H, Hkv, hd, f = m["L"], m["d"], m["H"], m["Hkv"], m["hd"], m["f"]
    leaves = {
        "embed/w": ((m["V"], d), d),
        "ln_f/scale": ((d,), None),
        "layers/ln1/scale": ((L, d), None),
        "layers/attn/wq": ((L, d, H * hd), d),
        "layers/attn/wk": ((L, d, Hkv * hd), d),
        "layers/attn/wv": ((L, d, Hkv * hd), d),
        "layers/attn/wo": ((L, H * hd, d), H * hd),
        "layers/ln2/scale": ((L, d), None),
        "layers/mlp/w_in": ((L, d, f), d),
        "layers/mlp/w_gate": ((L, d, f), d),
        "layers/mlp/w_out": ((L, f, d), f),
    }
    if not m["tied"]:
        leaves["lm_head/w"] = ((m["V"], d), d)
    return leaves


def nest(flat: dict) -> dict:
    """'layers/attn/wq' -> the program's {'seg0_attn_mlp': {'attn': ...}}."""
    tree: dict = {}
    for name, leaf in flat.items():
        parts = name.split("/")
        if parts[0] == "layers":
            parts[0] = "seg0_attn_mlp"
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


served_gaps = decoder.served_gaps
follow = ref_train.follow


def matmul_params_per_token(m: dict) -> int:
    """Attention projections and the gated MLP, in every layer."""
    d, H, Hkv, hd, f = m["d"], m["H"], m["Hkv"], m["hd"], m["f"]
    return m["L"] * (d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * f)


def attention_layers(m: dict) -> list:
    return [(m["L"], m["H"], m["Hkv"], m["hd"], m["window"], m["sinks"])]
