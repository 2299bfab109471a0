"""Pallas decode kernels vs the jnp decode engine, swept over shapes/dtypes
(ring-cache layouts, ragged per-request positions, and the paged slab)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import patterns as P
from repro.core.attention import hybrid_decode_attention
from repro.kernels.salo_decode import salo_decode, salo_paged_decode

RNG = np.random.default_rng(3)


@pytest.mark.parametrize("H,Hkv,hd", [(8, 2, 32), (4, 4, 64), (6, 1, 128)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3),
                                       (jnp.bfloat16, 4e-2)])
def test_decode_kernel_full_cache(H, Hkv, hd, dtype, tol):
    pat = P.causal_sliding_window(24, n_sinks=3)
    B, S = 2, 100
    q = jnp.asarray(RNG.normal(size=(B, H, 1, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, hd)), dtype)
    pos = jnp.arange(S, dtype=jnp.int32)
    for t in (0, 30, 99):
        ref = hybrid_decode_attention(q, k, v, t, pat)
        out = salo_decode(q, k, v, pos, t, pattern=pat, block_s=32,
                          interpret=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol, err_msg=str(t))


def test_decode_kernel_ring_layout():
    """Kernel on a ring cache == jnp engine with the same slot positions."""
    from repro.serve.kv_cache import (ring_init, ring_update,
                                      ring_positions_mask)
    w_, g = 16, 2
    pat = P.causal_sliding_window(w_, n_sinks=g)
    B, Hkv, hd = 2, 2, 32
    H = 4
    n = 50
    q_all = jnp.asarray(RNG.normal(size=(B, H, n, hd)), jnp.float32)
    k_all = jnp.asarray(RNG.normal(size=(B, Hkv, n, hd)), jnp.float32)
    v_all = jnp.asarray(RNG.normal(size=(B, Hkv, n, hd)), jnp.float32)
    cache = ring_init(B, w_, g, Hkv, hd, jnp.float32)
    for t in range(n):
        cache = ring_update(cache,
                            k_all[:, :, t:t + 1].transpose(0, 2, 1, 3),
                            v_all[:, :, t:t + 1].transpose(0, 2, 1, 3),
                            t, w_, g)
        if t % 9 != 0:
            continue
        kc = cache.k.transpose(0, 2, 1, 3)
        vc = cache.v.transpose(0, 2, 1, 3)
        pos = ring_positions_mask(cache)
        ref = hybrid_decode_attention(q_all[:, :, t:t + 1], kc, vc, t, pat,
                                      cache_positions=pos)
        out = salo_decode(q_all[:, :, t:t + 1], kc, vc, pos, t,
                          pattern=pat, block_s=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3, err_msg=str(t))


# =================== ragged / paged continuous decode =================== #
def _rand_decode(B, H, Hkv, hd, S, dtype=jnp.float32, seed=11):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, 1, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, hd)), dtype)
    return q, k, v


@pytest.mark.parametrize("dilation", [1, 2])
def test_ragged_t_vector_one_launch(dilation):
    """ONE kernel launch with a per-request t vector == per-row lockstep
    reference calls — batch members at different positions (the continuous
    batching state), dilated windows included."""
    pat = P.causal_sliding_window(6, n_sinks=2, dilation=dilation)
    B, H, Hkv, hd, S = 4, 4, 2, 32, 64
    q, k, v = _rand_decode(B, H, Hkv, hd, S)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    tv = jnp.asarray([0, 7, 23, 63], jnp.int32)
    out = salo_decode(q, k, v, pos, tv, pattern=pat, block_s=16,
                      interpret=True)
    for b in range(B):
        ref = hybrid_decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                      int(tv[b]), pat)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]),
                                   rtol=2e-3, atol=2e-3, err_msg=str(b))


def test_per_request_positions():
    """Per-request slot->position tables (the paged view): each row's cache
    is scrambled differently; masks follow positions, not slots."""
    pat = P.causal_sliding_window(8, n_sinks=1)
    B, H, Hkv, hd, S = 3, 2, 1, 16, 32
    q, k, v = _rand_decode(B, H, Hkv, hd, S)
    rng = np.random.default_rng(5)
    pos = np.stack([rng.permutation(S) for _ in range(B)]).astype(np.int32)
    tv = jnp.asarray([9, 31, 14], jnp.int32)
    out = salo_decode(q, k, v, jnp.asarray(pos), tv, pattern=pat,
                      block_s=8, interpret=True)
    for b in range(B):
        ref = hybrid_decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                      int(tv[b]), pat,
                                      cache_positions=jnp.asarray(pos[b]))
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]),
                                   rtol=2e-3, atol=2e-3, err_msg=str(b))


def test_off_tpu_compiled_raises():
    """Compiled (non-interpret) decode kernels off the TPU raise — no
    silent swap to the XLA ragged twin — for the contiguous and the paged
    kernel alike."""
    if jax.default_backend() == "tpu":
        pytest.skip("compiled kernels run on the TPU")
    pat = P.causal_sliding_window(6, n_sinks=2)
    B, H, Hkv, hd, S = 2, 4, 2, 32, 40
    q, k, v = _rand_decode(B, H, Hkv, hd, S)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    tv = jnp.asarray([12, 39], jnp.int32)
    with pytest.raises(ValueError, match="interpret mode"):
        salo_decode(q, k, v, pos, tv, pattern=pat, block_s=8,
                    interpret=False)
    ks, vs, pt = _slabify(k, v, 8)
    with pytest.raises(ValueError, match="interpret mode"):
        salo_paged_decode(q, ks, vs, pt, pos, tv, pattern=pat,
                          interpret=False)


def _slabify(k, v, page):
    """Pack per-request contiguous caches into a pooled slab + page tables
    (page 0 reserved as the null page)."""
    B, Hkv, S, hd = k.shape
    npp = S // page
    n_pages = 1 + B * npp
    ks = np.zeros((n_pages, page, Hkv, hd), np.float32)
    vs = np.zeros((n_pages, page, Hkv, hd), np.float32)
    pt = np.zeros((B, npp), np.int32)
    for b in range(B):
        for g in range(npp):
            phys = 1 + b * npp + g
            pt[b, g] = phys
            ks[phys] = np.asarray(
                k[b, :, g * page:(g + 1) * page]).transpose(1, 0, 2)
            vs[phys] = np.asarray(
                v[b, :, g * page:(g + 1) * page]).transpose(1, 0, 2)
    return jnp.asarray(ks), jnp.asarray(vs), jnp.asarray(pt)


@pytest.mark.parametrize("block_s", [None, 8])
def test_paged_kernel_matches_contiguous(block_s):
    """salo_paged_decode chasing scalar-prefetched page tables == the
    contiguous-cache kernel on the same logical content."""
    pat = P.causal_sliding_window(10, n_sinks=2)
    B, H, Hkv, hd, S, page = 3, 4, 2, 32, 48, 16
    q, k, v = _rand_decode(B, H, Hkv, hd, S)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    tv = jnp.asarray([3, 30, 47], jnp.int32)
    ks, vs, pt = _slabify(k, v, page)
    ref = salo_decode(q, k, v, pos, tv, pattern=pat, block_s=16,
                      interpret=True)
    out = salo_paged_decode(q, ks, vs, pt, pos, tv, pattern=pat,
                            block_s=block_s, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
