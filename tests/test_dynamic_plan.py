"""Runtime ExecutionPlans (core/dynamic.py):
  * full keep reproduces the static fused path exactly — fwd AND grads —
    across window/sink, longformer-global and dilated patterns (the
    machinery-off invariant)
  * small keep equals a masked dense reference built from the implied
    token mask (selection is deterministic + stop-grad, so grads match
    the fixed-mask reference too)
  * the never-drop guarantee: causal-local and global tiles survive any
    keep; check_keep raises when keep can't cover them
  * emitted tables honor the plan contract (validate_tables accepts)
  * the Pallas table engine (interpret) matches the XLA scan twin
  * under shard_map: full-keep == static sharded == single-device fused,
    and small-keep sharded == small-keep single-device (per-shard top-k
    over the exchanged view is exhaustive for the rows a shard owns)
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import patterns as P
from repro.core.blockwise import blockwise_attention
from repro.core.dynamic import (DynamicConfig, check_keep, dynamic_attention,
                                dynamic_tables)
from repro.core.plan_contract import validate_tables

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PATTERNS = [
    ("window_sinks", P.causal_sliding_window(48, n_sinks=8)),
    ("longformer_global", P.longformer(32, n_global=8)),
    ("dilated", P.dilated_window(32, 2)),
]


def _data(rng, n=256, d=32, b=2, count=4):
    return tuple(jnp.asarray(rng.normal(size=(b, n, d)), jnp.float32)
                 for _ in range(count))


@pytest.mark.parametrize("name,pat", PATTERNS)
def test_full_keep_matches_static(name, pat):
    """keep >= max_steps selects every candidate step: outputs and all
    three gradients must match the static fused path to 1e-4."""
    q, k, v, cot = _data(np.random.default_rng(0))
    cfg = DynamicConfig(keep=10 ** 6)
    ref = blockwise_attention(q, k, v, pat, block_q=32, block_k=32)
    out = dynamic_attention(q, k, v, pat, cfg, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4, err_msg=name)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(blockwise_attention(
        a, b, c, pat, block_q=32, block_k=32) * cot),
        argnums=(0, 1, 2))(q, k, v)
    g_dyn = jax.grad(lambda a, b, c: jnp.sum(dynamic_attention(
        a, b, c, pat, cfg, block_q=32, block_k=32) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for gname, ga, gb in zip("qkv", g_ref, g_dyn):
        np.testing.assert_allclose(np.asarray(gb), np.asarray(ga),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}: d{gname}")


def test_small_keep_matches_masked_dense():
    """keep < max_steps: the executed computation must equal dense
    attention under the IMPLIED token mask (pattern mask restricted to
    the selected tiles). The selector is deterministic and gradient-free,
    so gradients match the fixed-mask dense reference as well."""
    pat = P.causal_sliding_window(64)
    N, BLK, KEEP = 256, 32, 3
    rng = np.random.default_rng(1)
    q, k, v, cot = _data(rng, n=N)
    cfg = DynamicConfig(keep=KEEP)
    plan, kvt, flg, _ = dynamic_tables(q, k, pat, cfg,
                                       block_q=BLK, block_k=BLK)
    # this reference construction assumes the working grid is the identity
    # (true for pure-window patterns)
    assert np.array_equal(plan.positions_padded(), np.arange(N))
    kvt, flg = np.asarray(kvt), np.asarray(flg)
    sel = np.zeros((N // BLK, N // BLK), bool)
    for i in range(N // BLK):
        sel[i, kvt[i][flg[i] != 0]] = True
    mask = np.asarray(pat.mask(N)) & np.repeat(
        np.repeat(sel, BLK, axis=0), BLK, axis=1)

    def dense_ref(a, b, c):
        s = jnp.einsum("bqd,bkd->bqk", a, b) * (32 ** -0.5)
        s = jnp.where(jnp.asarray(mask)[None], s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), c)

    out = dynamic_attention(q, k, v, pat, cfg, block_q=BLK, block_k=BLK)
    ref = dense_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # fewer tiles actually execute than the static plan carries
    assert (flg != 0).sum() < (plan.flags != 0).sum()
    g_ref = jax.grad(lambda a, b, c: jnp.sum(dense_ref(a, b, c) * cot),
                     argnums=(0, 1, 2))(q, k, v)
    g_dyn = jax.grad(lambda a, b, c: jnp.sum(dynamic_attention(
        a, b, c, pat, cfg, block_q=BLK, block_k=BLK) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for gname, ga, gb in zip("qkv", g_ref, g_dyn):
        np.testing.assert_allclose(np.asarray(gb), np.asarray(ga),
                                   rtol=1e-4, atol=2e-4,
                                   err_msg=f"d{gname}")


@pytest.mark.parametrize("name,pat", PATTERNS)
def test_never_drop_and_contract(name, pat):
    """Whatever the content says, every always-keep step (causal-local +
    global/sink tiles) appears in the selection, and the emitted tables
    pass the shared contract validator."""
    q, k, _, _ = _data(np.random.default_rng(2))
    cfg = DynamicConfig(keep=6)
    plan, kvt, flg, always = dynamic_tables(q, k, pat, cfg,
                                            block_q=32, block_k=32)
    kvt, flg = np.asarray(kvt), np.asarray(flg)
    validate_tables(kvt, flg, nkb=plan.nkb, name=f"dynamic[{name}]")
    for i in range(plan.nq):
        picked = set(kvt[i][flg[i] != 0].tolist())
        needed = set(plan.kv_blocks[i][always[i]].tolist())
        assert needed <= picked, \
            f"{name} row {i}: dropped always-keep tiles {needed - picked}"
        assert len(picked) <= 6


def test_check_keep_raises():
    """keep below the worst-case always-kept count must refuse loudly, not
    silently drop a correctness-critical tile."""
    q, k, _, _ = _data(np.random.default_rng(3))
    with pytest.raises(ValueError, match="always-kept"):
        dynamic_tables(q, k, P.causal_sliding_window(48, n_sinks=8),
                       DynamicConfig(keep=1), block_q=32, block_k=32)
    check_keep(3, np.ones((4, 3), bool)[:, :2])  # 3 >= 2: fine


def test_pallas_interpret_engine_parity():
    """The fused table kernel (interpret mode) under a dynamic table must
    match the XLA scan twin — fwd and grads."""
    pat = P.causal_sliding_window(48, n_sinks=8)
    q, k, v, cot = _data(np.random.default_rng(4))
    cfg = DynamicConfig(keep=5)
    ref = dynamic_attention(q, k, v, pat, cfg, block_q=32, block_k=32)
    out = dynamic_attention(q, k, v, pat, cfg, block_q=32, block_k=32,
                            impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(dynamic_attention(
        a, b, c, pat, cfg, block_q=32, block_k=32) * cot),
        argnums=(0, 1, 2))(q, k, v)
    g_pl = jax.grad(lambda a, b, c: jnp.sum(dynamic_attention(
        a, b, c, pat, cfg, block_q=32, block_k=32,
        impl="pallas_interpret") * cot), argnums=(0, 1, 2))(q, k, v)
    for gname, ga, gb in zip("qkv", g_ref, g_pl):
        np.testing.assert_allclose(np.asarray(gb), np.asarray(ga),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{gname}")


def test_hybrid_attention_dynamic_route():
    """plan="dynamic" on the public multi-head entry point routes through
    dynamic_attention; dense_ref and missing keep are rejected."""
    from repro.core.attention import hybrid_attention
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 2, 128, 16)), jnp.float32)
               for _ in range(3))
    pat = P.causal_sliding_window(32, n_sinks=4)
    ref = hybrid_attention(q, k, v, pat, block_q=16, block_k=16)
    full = hybrid_attention(q, k, v, pat, plan="dynamic",
                            dynamic_keep=10 ** 6, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    small = hybrid_attention(q, k, v, pat, plan="dynamic", dynamic_keep=4,
                             block_q=16, block_k=16)
    assert np.all(np.isfinite(np.asarray(small)))
    with pytest.raises(ValueError, match="dense_ref"):
        hybrid_attention(q, k, v, pat, plan="dynamic", dynamic_keep=4,
                         impl="dense_ref")
    with pytest.raises(ValueError, match="dynamic_keep"):
        hybrid_attention(q, k, v, pat, plan="dynamic")
    with pytest.raises(ValueError, match="plan"):
        hybrid_attention(q, k, v, pat, plan="adaptive")


def test_invalid_impl_rejected():
    q, k, v, _ = _data(np.random.default_rng(6), n=64)
    with pytest.raises(ValueError, match="table-driven"):
        dynamic_attention(q, k, v, P.causal_sliding_window(32),
                          DynamicConfig(keep=4), block_q=32, block_k=32,
                          impl="dense_ref")


def test_sharded_dynamic_parity():
    """Under an 8-device shard_map: full keep == the single-device STATIC
    fused path (fwd + grads), and small keep == the single-device DYNAMIC
    path — each shard's top-k over its exchanged [local|halo|global] view
    is exhaustive for the query rows it owns."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import patterns as P_
        from repro.core.blockwise import blockwise_attention
        from repro.core.dynamic import DynamicConfig, dynamic_attention
        from repro.dist.sharded_plan import sharded_attention
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        B, N, D = 2, 512, 16
        pat = P_.causal_sliding_window(48, n_sinks=8)
        q, k, v, cot = (jnp.asarray(rng.normal(size=(B, N, D)), jnp.float32)
                        for _ in range(4))

        full = DynamicConfig(keep=10 ** 6)
        ref = blockwise_attention(q, k, v, pat, block_q=16, block_k=16)
        g_ref = jax.grad(lambda a, b, c: jnp.sum(blockwise_attention(
            a, b, c, pat, block_q=16, block_k=16) * cot),
            argnums=(0, 1, 2))(q, k, v)
        with mesh:
            out = jax.jit(lambda a, b, c: sharded_attention(
                a, b, c, pat, mesh, block_q=16, block_k=16,
                dynamic=full))(q, k, v)
            g = jax.jit(jax.grad(lambda a, b, c: jnp.sum(sharded_attention(
                a, b, c, pat, mesh, block_q=16, block_k=16,
                dynamic=full) * cot), argnums=(0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        for name, ga, gb in zip("qkv", g_ref, g):
            np.testing.assert_allclose(np.asarray(gb), np.asarray(ga),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg="d" + name)
        print("FULL-KEEP-SHARDED-OK")

        small = DynamicConfig(keep=6)
        dref = dynamic_attention(q, k, v, pat, small,
                                 block_q=16, block_k=16)
        gd_ref = jax.grad(lambda a, b, c: jnp.sum(dynamic_attention(
            a, b, c, pat, small, block_q=16, block_k=16) * cot),
            argnums=(0, 1, 2))(q, k, v)
        with mesh:
            dout = jax.jit(lambda a, b, c: sharded_attention(
                a, b, c, pat, mesh, block_q=16, block_k=16,
                dynamic=small))(q, k, v)
            gd = jax.jit(jax.grad(lambda a, b, c: jnp.sum(sharded_attention(
                a, b, c, pat, mesh, block_q=16, block_k=16,
                dynamic=small) * cot), argnums=(0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(np.asarray(dout), np.asarray(dref),
                                   rtol=1e-4, atol=1e-4)
        for name, ga, gb in zip("qkv", gd_ref, gd):
            np.testing.assert_allclose(np.asarray(gb), np.asarray(ga),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg="d" + name)
        print("SMALL-KEEP-SHARDED-OK")
    """)
    r = subprocess.run([sys.executable, "-c", prog],
                       env={**os.environ, "PYTHONPATH": SRC},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "SMALL-KEEP-SHARDED-OK" in r.stdout
