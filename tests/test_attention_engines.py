"""Engine equivalence: blockwise / Pallas-interpret vs the dense oracle,
swept over patterns, shapes, dtypes, and block sizes (the per-kernel
allclose requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import patterns as P
from repro.core.attention import hybrid_attention
from repro.core.blockwise import blockwise_attention, decode_attention
from repro.kernels.ref import reference_attention
from repro.kernels.ops import salo_attention

RNG = np.random.default_rng(42)

PATTERNS = [
    ("causal_sw", P.causal_sliding_window(16)),
    ("causal_sw_sinks", P.causal_sliding_window(16, n_sinks=4)),
    ("longformer", P.longformer(32, n_global=2)),
    ("longformer_causal", P.longformer(32, n_global=2, causal=True)),
    ("dilated", P.dilated_window(8, 3)),
    ("dilated_causal", P.dilated_window(8, 3, causal=True)),
    ("dilated_sinks", P.causal_sliding_window(8, n_sinks=2, dilation=2)),
    ("vil_2d", P.vil((8, 9), (3, 5), n_global=2)),
    ("full_causal", P.full(causal=True)),
    ("asym", P.HybridSparsePattern(window=(-5, 3), n_global=3)),
]


def _qkv(n, d, dtype=jnp.float32, b=2):
    return tuple(jnp.asarray(RNG.normal(size=(b, n, d)), dtype)
                 for _ in range(3))


def _n_for(pat, default):
    return pat.seq_len() or default


@pytest.mark.parametrize("name,pat", PATTERNS)
def test_blockwise_matches_oracle(name, pat):
    n = _n_for(pat, 100)
    q, k, v = _qkv(n, 32)
    ref = reference_attention(q, k, v, pat)
    out = blockwise_attention(q, k, v, pat, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("name,pat", PATTERNS)
def test_pallas_interpret_matches_oracle(name, pat):
    n = _n_for(pat, 100)
    q, k, v = _qkv(n, 32)
    ref = reference_attention(q, k, v, pat)
    out = salo_attention(q, k, v, pat, 32, 32, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bq,bk", [(16, 16), (16, 64), (64, 16), (128, 128)])
def test_block_size_sweep(bq, bk):
    """Window splitting is exact for ANY tile geometry (paper Eq. 2)."""
    pat = P.causal_sliding_window(24, n_sinks=2)
    q, k, v = _qkv(200, 16)
    ref = reference_attention(q, k, v, pat)
    for impl in ("blockwise",):
        out = blockwise_attention(q, k, v, pat, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3, err_msg=impl)
    out = salo_attention(q, k, v, pat, bq, bk, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("d", [16, 64, 128, 256])
def test_dtype_headdim_sweep(dtype, tol, d):
    pat = P.causal_sliding_window(16, n_sinks=2)
    q, k, v = _qkv(64, d, dtype)
    ref = reference_attention(q, k, v, pat)
    out = salo_attention(q, k, v, pat, 32, 32, None, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_gqa_head_repeat():
    pat = P.causal_sliding_window(16)
    B, H, Hkv, N, D = 2, 8, 2, 64, 16
    q = jnp.asarray(RNG.normal(size=(B, H, N, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, N, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, N, D)), jnp.float32)
    out = hybrid_attention(q, k, v, pat)
    kr = jnp.repeat(k, H // Hkv, axis=1)
    vr = jnp.repeat(v, H // Hkv, axis=1)
    ref = hybrid_attention(q, kr, vr, pat, impl="dense_ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_platform_default_is_the_twin_off_tpu():
    """With no engine named, attention and ragged decode run the XLA twins
    on this (non-TPU) backend — and the default ``impl`` IS the blockwise
    engine, bit for bit."""
    from repro.core.attention import default_impl

    if jax.default_backend() == "tpu":
        pytest.skip("on a TPU the default is the compiled kernels")
    assert default_impl() == "blockwise"
    assert default_impl(decode=True) == "xla"
    pat = P.causal_sliding_window(16, n_sinks=2)
    q = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)), jnp.float32)
    out = hybrid_attention(q, q, q, pat)
    twin = hybrid_attention(q, q, q, pat, impl="blockwise")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(twin))


def test_decode_matches_full_forward_rows():
    """Decode step at position t == row t of the full-sequence attention."""
    pat = P.causal_sliding_window(12, n_sinks=2)
    n, d = 80, 16
    q, k, v = _qkv(n, d)
    full = reference_attention(q, k, v, pat)
    for t in (0, 5, 13, 79):
        out = decode_attention(q[:, t:t + 1], k, v, t, pat)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(full[:, t:t + 1]),
                                   rtol=2e-3, atol=2e-3, err_msg=str(t))


def test_ring_cache_decode_equivalence():
    """SALO ring cache (w+g slots) == full cache decode for the same pattern."""
    from repro.serve.kv_cache import (ring_init, ring_update,
                                      ring_positions_mask)
    w_, g = 8, 2
    pat = P.causal_sliding_window(w_, n_sinks=g)
    n, d, B = 40, 8, 2
    q, k, v = _qkv(n, d, b=B)
    cache = ring_init(B, w_, g, 1, d, jnp.float32)
    for t in range(n):
        cache = ring_update(cache, k[:, t:t + 1, None, :],
                            v[:, t:t + 1, None, :], t, w_, g)
        out_ring = decode_attention(
            q[:, t:t + 1], cache.k[:, :, 0], cache.v[:, :, 0], t, pat,
            cache_positions=ring_positions_mask(cache))
        out_full = decode_attention(q[:, t:t + 1], k[:, :t + 1],
                                    v[:, :t + 1], t, pat)
        np.testing.assert_allclose(np.asarray(out_ring),
                                   np.asarray(out_full),
                                   rtol=2e-3, atol=2e-3, err_msg=str(t))


def test_gradients_blockwise_vs_oracle():
    pat = P.causal_sliding_window(16, n_sinks=2)
    q, k, v = _qkv(64, 16)

    def loss_ref(q_, k_, v_):
        return jnp.sum(reference_attention(q_, k_, v_, pat) ** 2)

    def loss_blk(q_, k_, v_):
        return jnp.sum(blockwise_attention(q_, k_, v_, pat, block_q=32,
                                           block_k=32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=5e-3)


def test_dynamic_q8_roundtrip_per_tensor():
    """Per-tensor dynamic int8: round-trip error bounded by scale/2 (one
    rounding step) and the max-magnitude element is exactly representable."""
    from repro.core.quant import dequant, dynamic_q8
    x = jnp.asarray(RNG.normal(size=(4, 33, 7)) * 3.0, jnp.float32)
    q, scale = dynamic_q8(x)
    assert q.dtype == jnp.int8 and scale.ndim == 0
    back = dequant(q, scale)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(scale) / 2 + 1e-7)
    amax_idx = np.unravel_index(np.argmax(np.abs(np.asarray(x))), x.shape)
    assert abs(int(q[amax_idx])) == 127


def test_dynamic_q8_roundtrip_grouped_axis():
    """axis=reduced axes: one scale per remaining-axis group, each group's
    round-trip bounded by ITS scale (not the global amax)."""
    from repro.core.quant import dequant, dynamic_q8
    x = np.asarray(RNG.normal(size=(5, 16, 8)), np.float32)
    x[0] *= 100.0   # wildly different group magnitudes
    x[1] *= 0.01
    q, scale = dynamic_q8(jnp.asarray(x), axis=(1, 2))
    assert scale.shape == (5, 1, 1)
    back = np.asarray(dequant(q, scale))
    for g in range(5):
        bound = float(np.asarray(scale)[g, 0, 0]) / 2 + 1e-7
        assert np.max(np.abs(back[g] - x[g])) <= bound


def test_dynamic_q8_all_zero_and_denormal():
    """All-zero input survives (1e-8 amax floor, no div-by-zero NaNs) and
    denormal-range inputs quantize to finite values."""
    from repro.core.quant import dequant, dynamic_q8
    q, scale = dynamic_q8(jnp.zeros((3, 4)))
    assert float(scale) > 0.0 and not np.any(np.asarray(q))
    assert not np.any(np.isnan(np.asarray(dequant(q, scale))))
    tiny = jnp.full((2, 2), 1e-12, jnp.float32)  # below the 1e-8 floor
    q, scale = dynamic_q8(tiny)
    back = np.asarray(dequant(q, scale))
    assert np.all(np.isfinite(back)) and np.max(np.abs(back)) <= 1e-8


def test_group_q8_roundtrip_matches_page_layout():
    """group_q8 over the slab layout (L, P, page, Hkv, hd) with
    n_group_axes=2: one scale per (layer, page), group-wise round-trip
    bound, and group_dequant inverts to the requested dtype."""
    from repro.core.quant import group_dequant, group_q8
    x = jnp.asarray(RNG.normal(size=(2, 3, 4, 2, 8)), jnp.float32)
    q, scale = group_q8(x, 2)
    assert q.shape == x.shape and scale.shape == (2, 3)
    back = group_dequant(q, scale, dtype=jnp.bfloat16)
    assert back.dtype == jnp.bfloat16
    err = np.abs(np.asarray(back, np.float32) - np.asarray(x))
    bound = np.asarray(scale)[:, :, None, None, None] / 2 + 0.05
    assert np.all(err <= bound)


def test_quantized_attention_error_small():
    """Paper §6.4: int8(4-frac) QKV quantization has small output error."""
    from repro.core.quant import quantized_attention
    pat = P.longformer(32, n_global=1)
    q, k, v = _qkv(128, 32)
    q, k, v = q * 0.5, k * 0.5, v * 0.5  # typical activation scale
    ref = hybrid_attention(q[:, None], k[:, None], v[:, None], pat)[:, 0]
    out = quantized_attention(q[:, None], k[:, None], v[:, None],
                              pat, mode="fixed")[:, 0]
    err = float(jnp.mean(jnp.abs(out - ref)))
    assert err < 0.05, err
