"""A configuration file as the program runs it, and its weights from a seed.

A configuration is a JSON file of published sizes (``configs/<name>.json``,
keys as in the model's own ``config.json``) plus the SALO window. This
module maps it onto the program's ``ModelConfig`` and makes the weights:
the benchmark makes them, in the tree the program's dense decoder takes,
so that the reference can make the very same ones from the same seed
without taking anything from the program.
"""
from __future__ import annotations

# Standard deviation of the random RMSNorm scales (stored as 1 + scale).
NORM_SCALE_STD = 0.1
# Gains over N(0, 1/fan_in). At unit gains a random decoder with tied
# embeddings puts the input token first at nearly every position whatever
# the context, so no fault in attention could move a served token. A small
# embedding keeps the input token from dominating the readout, and the
# served tokens then depend on the context that the cache holds. (Sharper
# queries and keys, at gain 2, made attention amplify rounding so much that
# bfloat16 read a third of what the fp8 control read.)
GAIN = {"embed/w": 0.3, "lm_head/w": 0.3}


def dims(spec: dict) -> dict:
    """The sizes the benchmark's own code uses, from a configuration."""
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    return {"L": spec["num_hidden_layers"], "d": d, "H": H,
            "Hkv": spec["num_key_value_heads"], "hd": d // H,
            "f": spec["intermediate_size"], "V": spec["vocab_size"],
            "eps": spec["rms_norm_eps"], "theta": spec["rope_theta"],
            "tied": bool(spec["tie_word_embeddings"]),
            "window": spec["salo_window"], "sinks": spec["salo_sinks"]}


def model_config(spec: dict):
    """The program's ``ModelConfig`` for a configuration."""
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


def seed_key(seed: int):
    """A PRNG key from any whole seed (64 bits and more fold in by
    32-bit words), without a compile per seed."""
    import jax
    import numpy as np

    key = jax.random.PRNGKey(0)
    seed = int(seed) % (1 << 64)
    for word in (seed & 0xFFFFFFFF, seed >> 32):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


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


def _nest(flat: dict) -> dict:
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


def make_params(spec: dict, seed: int):
    """All weights from the seed, on the device, in one jitted call: the
    matrices in the served dtype, the norm scales in float32."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(spec)
    dtype = jnp.dtype(spec["torch_dtype"])

    def make(key):
        flat = {}
        for i, (name, (shape, fan_in)) in enumerate(sorted(shapes.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if fan_in is None:
                flat[name] = x * NORM_SCALE_STD
            else:
                std = GAIN.get(name, 1.0) * fan_in ** -0.5
                flat[name] = (x * std).astype(dtype)
        return _nest(flat)

    return jax.jit(make)(seed_key(seed))


def check_tree(params, program_params_shape) -> None:
    """Raise unless the benchmark's weights have exactly the structure,
    shapes and dtypes of the program's own ``init``."""
    import jax

    got = jax.tree_util.tree_flatten_with_path(params)[0]
    want = jax.tree_util.tree_flatten_with_path(program_params_shape)[0]
    a = {jax.tree_util.keystr(k): (v.shape, str(v.dtype)) for k, v in got}
    b = {jax.tree_util.keystr(k): (v.shape, str(v.dtype)) for k, v in want}
    if a != b:
        raise ValueError(f"weights do not match the program's tree: "
                         f"benchmark {sorted(set(a.items()) - set(b.items()))}"
                         f" program {sorted(set(b.items()) - set(a.items()))}")
