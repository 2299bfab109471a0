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


# =========== the live-page walk: blocks of pages, live pages only =========== #
# smollm-135m's serving geometry: window 1024 + 4 sinks over 8-slot pages is
# one sink page and 128 ring pages, 129 a row; the kernel folds 16 pages a
# step, so the last block of a full row holds one page.
LIVE_PAT = P.causal_sliding_window(1024, n_sinks=4)
# one row of each kind: inactive, a first block cut short (6 pages), a
# whole number of blocks (32), a partial last block (39), a ring-wrapped
# full row (129); live counts from PagedLayout.pages_needed(t + 1)
LIVE_T = (0, 37, 250, 300, 2000)
LIVE_ACTIVE = (False, True, True, True, True)


def _live_state(int8: bool, seed: int = 0):
    from repro.core.scheduler import PAD_SENTINEL, ring_view_positions
    from repro.serve.paged_cache import layout_for_pattern

    lay = layout_for_pattern(LIVE_PAT, 8)
    B, H, Hkv, hd, npp = len(LIVE_T), 6, 2, 32, lay.pages_per_req
    assert npp == 129
    rng = np.random.default_rng(seed)
    n_pages = 1 + B * npp
    shape = (n_pages, lay.page, Hkv, hd)
    if int8:
        ks, vs = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                  for _ in range(2))
        ksc, vsc = (jnp.asarray(rng.uniform(0.01, 0.03, n_pages),
                                jnp.float32) for _ in range(2))
    else:
        ks, vs = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                  for _ in range(2))
        ksc = vsc = None
    pt = 1 + rng.permutation(B * npp).reshape(B, npp)
    t = np.asarray(LIVE_T, np.int32)
    active = np.asarray(LIVE_ACTIVE)
    pos = np.stack([ring_view_positions(int(x) + 1, lay.n_sink,
                                        lay.ring_cap, lay.n_global)
                    for x in t])
    pos = np.where(active[:, None], pos, PAD_SENTINEL).astype(np.int32)
    live = np.where(active, lay.pages_needed(t + 1), 0)
    assert live.tolist() == [0, 6, 32, 39, 129]
    q = jnp.asarray(rng.normal(size=(B, H, 1, hd)), jnp.bfloat16)
    return dict(q=q, ks=ks, vs=vs, ksc=ksc, vsc=vsc, pt=jnp.asarray(pt),
                pos=jnp.asarray(pos), t=jnp.asarray(t), live=live,
                active=active)


def _walk(s, ks=None, vs=None, ksc=None, vsc=None, live=True, **kw):
    return salo_paged_decode(
        s["q"], s["ks"] if ks is None else ks, s["vs"] if vs is None else vs,
        s["pt"], s["pos"], s["t"], pattern=LIVE_PAT, interpret=True,
        k_scale=s["ksc"] if ksc is None else ksc,
        v_scale=s["vsc"] if vsc is None else vsc,
        live_pages=jnp.asarray(s["live"]) if live else None, **kw)


def _twin(s, **kw):
    """The XLA decode twin over each row's whole gathered view."""
    from repro.serve.paged_cache import gather_view

    quant = s["ksc"] is not None
    k, v = gather_view(s["ks"], s["vs"], s["pt"],
                       *((s["ksc"], s["vsc"], jnp.bfloat16) if quant
                         else ()))
    return hybrid_decode_attention(
        s["q"], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), s["t"],
        LIVE_PAT, cache_positions=s["pos"], **kw)


def _close(a, b, tol=4e-2, rows=None):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if rows is not None:
        a, b = a[rows], b[rows]
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("mode", ["out", "state", "page_stats"])
def test_live_walk_matches_twin(int8, mode):
    """The walk over each row's live pages, 16 to a step, == the XLA twin
    over every page: inactive rows, a short first block, whole blocks, a
    partial last block and a ring-wrapped row of 129 pages (not a multiple
    of the block), for bf16 and int8 slabs, plain, with (m, l) partials,
    and with per-page max scores."""
    s = _live_state(int8)
    act = s["active"]
    if mode == "out":
        _close(_walk(s), _twin(s), rows=act)
        # an inactive row folds nothing: the empty row's output
        assert not np.asarray(_walk(s), np.float32)[~act].any()
    elif mode == "state":
        out, m, l = _walk(s, return_state=True)
        ro, rm, rl = _twin(s, return_state=True)
        _close(out, ro, rows=act)
        _close(m, rm, rows=act)
        np.testing.assert_allclose(np.asarray(l)[act], np.asarray(rl)[act],
                                   rtol=4e-2)
        # no owned live slot: the (0, NEG_INF, 0) merge identity
        assert not np.asarray(out)[~act].any()
        assert (np.asarray(m)[~act] <= -1e29).all()
        assert not np.asarray(l)[~act].any()
    else:
        out, pm = _walk(s, return_page_stats=True)
        ro, slot_m = _twin(s, return_slot_m=True)
        _close(out, ro, rows=act)
        ref_pm = np.asarray(slot_m).reshape(len(LIVE_T), -1, 8).max(-1)
        pm = np.asarray(pm)
        for b, n in enumerate(s["live"]):
            _close(pm[b, :n], ref_pm[b, :n])
            assert (pm[b, n:] <= -1e29).all(), b   # never fetched


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_live_walk_never_reads_past_the_bound(int8):
    """Every page past a row's live count (and every page of an inactive
    row) made poison: NaN K/V in a bf16 slab, a NaN scale in an int8 one.
    With the live bound the output is finite and equals the twin on the
    clean slab; without it (every page walked) the poison reaches the
    output, so the bound is what keeps it out."""
    s = _live_state(int8)
    live_pages = np.zeros(s["ks"].shape[0], bool)
    for b, n in enumerate(s["live"]):
        live_pages[np.asarray(s["pt"])[b, :n]] = True
    dead = jnp.asarray(~live_pages)
    if int8:
        nan = jnp.float32(np.nan)
        bad = dict(ksc=jnp.where(dead, nan, s["ksc"]),
                   vsc=jnp.where(dead, nan, s["vsc"]))
    else:
        d4 = dead[:, None, None, None]
        bad = dict(ks=jnp.where(d4, jnp.nan, s["ks"]).astype(jnp.bfloat16),
                   vs=jnp.where(d4, jnp.nan, s["vs"]).astype(jnp.bfloat16))
    out, pm = _walk(s, return_page_stats=True, **bad)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    _close(out, _twin(s), rows=s["active"])
    assert not np.isnan(np.asarray(pm)).any()
    walked_all = _walk(s, live=False, **bad)
    assert np.isnan(np.asarray(walked_all, np.float32)).any()


def test_live_walk_grid():
    """The kernel's step tables: one step per live block, row after row;
    the walk is the live pages rounded up to whole blocks; a dead tile
    repeats its input's page, so the pipeline copies each live page once
    and no other."""
    from repro.kernels.salo_decode import (_walk as kernel_walk,
                                           pages_per_block, paged_decode_walk)

    npp, ppb = 129, pages_per_block(8, 129)
    assert ppb == 16 and pages_per_block(8, 5) == 5
    assert pages_per_block(12, 129) == 11      # 132 slots: a lane row
    live = np.asarray([0, 6, 32, 39, 129, 200])
    assert paged_decode_walk(live, 8, npp).tolist() \
        == [0, 16, 32, 48, 129, 129]
    n = np.minimum(live, npp)
    rng = np.random.default_rng(1)
    pt = jnp.asarray(1 + rng.permutation(6 * npp).reshape(6, npp))
    steps, row, blk, tiles = kernel_walk(pt, jnp.asarray(n), 8, 1)
    nb = [0, 1, 2, 3, 9, 9]
    assert int(steps) == sum(nb)
    want = [(b, j) for b in range(6) for j in range(nb[b])]
    assert list(zip(np.asarray(row)[:int(steps)].tolist(),
                    np.asarray(blk)[:int(steps)].tolist())) == want
    tiles = np.asarray(tiles).reshape(-1, ppb)[:int(steps)]
    # the first step copies every input, a later one each input it changes
    assert ppb + (tiles[1:] != tiles[:-1]).sum() == n.sum()
    for s, (b, j) in enumerate(want):
        for u in range(ppb):
            if j * ppb + u < n[b]:
                assert tiles[s, u] == pt[b, j * ppb + u]
