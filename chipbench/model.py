"""A configuration's weights from a seed.

A configuration is a JSON file of published sizes (``configs/<name>.json``,
keys as in the model's own ``config.json``) plus the SALO window and
``"arch"``, the name of its architecture's module (``arch/<arch>.py``,
found by ``harness.arch``), which maps it onto the program's
``ModelConfig`` and lays out its weights. The benchmark makes the weights
itself, in the tree the program takes, so that the reference can make the
very same ones from the same seed without taking anything from the
program.
"""
from __future__ import annotations

from chipbench import harness

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


def make_params(spec: dict, seed: int):
    """All weights from the seed, on the device, in one jitted call: the
    matrices in the served dtype, the norm scales in float32."""
    import jax
    import jax.numpy as jnp

    arch = harness.arch(spec)
    shapes = arch.param_shapes(spec)
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
        return arch.nest(flat)

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
