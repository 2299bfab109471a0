"""SALO ragged decode kernels (Pallas, TPU target).

One new token per request against the SALO cache, **one launch for the whole
continuous batch**: the per-request position vector ``t`` rides in via
scalar prefetch (``PrefetchScalarGridSpec``), so batch members at different
depths — the normal state of a continuous-batching engine — share a single
kernel launch instead of a lockstep scalar ``t``. Two cache layouts:

* :func:`salo_decode` — per-request contiguous caches ``(B, Hkv, S, hd)``
  (dense baseline or the legacy ring layout). Per-request slot-position
  tiles make ring indexing transparent, exactly like the jnp engine.
* :func:`salo_paged_decode` — the pooled paged ring-cache slab
  ``(n_pages, page, Hkv, hd)`` shared by every request
  (:mod:`repro.serve.paged_cache`): the per-request **page table** is the
  second scalar-prefetch operand, and the BlockSpec index map chases it so
  each grid step DMAs exactly one physical page tile — no per-request
  gather ever materializes in HBM. int8 slabs additionally prefetch the
  per-page f32 scales (operands 3/4) and dequantize each tile in VMEM;
  ``return_page_stats`` emits per-(request, page) max masked scores for
  the engine's stats-driven page-keep mask.

Both kernels stream cache tiles through VMEM past the resident grouped
query (GQA: rep = H/Hkv query rows share each KV head — no KV repeat), with
the usual online-softmax scratch. Masks are evaluated on original positions
(``scheduler.causal_step_mask`` semantics, inlined below).

Grids: ``(B, Hkv, n_slot_tiles)`` for the contiguous kernel, ``(B,
n_slot_tiles)`` for the paged one (each step holds a page tile of every KV
head) — last dim sequential. Compiled mode runs on a TPU only; the XLA
ragged decode twin (:func:`repro.core.attention.hybrid_decode_attention`)
is the engine elsewhere, chosen by the caller. Validated in interpret mode
in tests/test_decode_kernel.py and compiled for a v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.patterns import HybridSparsePattern
from repro.core.scheduler import PAD_SENTINEL

NEG_INF = -1e30
LANES = 128


def _decode_mask(pattern: HybridSparsePattern, pos_k, t):
    """``causal_step_mask`` with both flags, inlined for one query at
    position ``t`` against a (1, Bs) row of slot positions (no in-range
    guard needed: PAD_SENTINEL slots fail the window by distance and
    pos_k <= t)."""
    a, _ = pattern.window
    g = pattern.n_global
    rel = pos_k - t
    mask = (rel >= a) & (rel <= 0)
    if pattern.dilation > 1:
        mask = mask & (rel % pattern.dilation == 0)
    if g > 0:
        mask = mask | (pos_k < g)
    return mask & (pos_k <= t)


def _online_update(scores, mask, v, acc, m_prev, l_prev):
    """Fold one masked (rep, Bs) score tile into the online-softmax state
    ``(acc (rep, hd), m (rep, 1), l (rep, 1))``; returns the new state."""
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(scores - shift), 0.0)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - shift))
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return (acc * corr + pv, m_new,
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True))


def _masked_scores(q, k, mask, scale):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(mask, s, NEG_INF)                  # (rep, Bs)


def _normalized(acc, l):
    return acc / jnp.where(l == 0.0, 1.0, l)


def _ragged_kernel(t_ref, q_ref, k_ref, v_ref, pos_ref, out_ref,
                   acc_ref, m_scr, l_scr, *, pattern: HybridSparsePattern,
                   steps: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    mask = _decode_mask(pattern, pos_ref[0], t_ref[b])      # (1, Bs)
    scores = _masked_scores(q_ref[0, 0], k_ref[0, 0], mask, scale)
    acc, m, l = _online_update(scores, mask, v_ref[0, 0], acc_ref[...],
                               m_scr[:, :1], l_scr[:, :1])
    acc_ref[...] = acc
    m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(s == steps - 1)
    def _fin():
        out_ref[0, 0] = _normalized(acc_ref[...],
                                    l_scr[:, :1]).astype(out_ref.dtype)


def _make_paged_kernel(*, pattern: HybridSparsePattern, steps: int,
                       scale: float, npp: int, tpp: int, n_kv: int,
                       quant: bool, want_state: bool, want_pm: bool,
                       compute_dtype):
    """Paged-decode kernel for any combination of the static features.

    One grid step holds one page tile of ALL ``n_kv`` KV heads — the
    slab's (Hkv, hd) minor dims stay whole, as the TPU tiling requires —
    and folds it into each head's online-softmax state. ``quant``
    dequantizes the int8 tile by its page's scalar-prefetched scale (no
    fp slab ever exists in HBM), ``want_state`` emits the (m, l) row
    stats, ``want_pm`` emits the tile's max masked score over all heads.
    Refs arrive positionally (prefetch, ins, outs, scratch) so the one
    body parses them by the same flags."""

    def kern(*refs):
        t_ref, pt_ref = refs[0], refs[1]
        i = 2
        if quant:
            ks_ref, vs_ref = refs[2], refs[3]
            i = 4
        q_ref, k_ref, v_ref, pos_ref = refs[i:i + 4]
        i += 4
        out_ref = refs[i]
        i += 1
        if want_state:
            m_ref, l_ref = refs[i], refs[i + 1]
            i += 2
        if want_pm:
            pm_ref = refs[i]
            i += 1
        acc_ref, m_scr, l_scr = refs[i:i + 3]
        b = pl.program_id(0)
        s = pl.program_id(1)

        @pl.when(s == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)

        mask = _decode_mask(pattern, pos_ref[0], t_ref[b])  # (1, Bs)
        if quant:
            pg = pt_ref[b * npp + s // tpp]
            k_sc, v_sc = ks_ref[pg], vs_ref[pg]
        tile_max = None
        for h in range(n_kv):
            k = k_ref[0, :, h, :]                           # (Bs, hd)
            v = v_ref[0, :, h, :]
            if quant:
                k = (k.astype(jnp.float32) * k_sc).astype(compute_dtype)
                v = (v.astype(jnp.float32) * v_sc).astype(compute_dtype)
            scores = _masked_scores(q_ref[0, h], k, mask, scale)
            if want_pm:
                hmax = jnp.max(jnp.max(scores, axis=1, keepdims=True),
                               axis=0, keepdims=True)       # (1, 1)
                tile_max = hmax if tile_max is None else jnp.maximum(
                    tile_max, hmax)
            acc, m, l = _online_update(scores, mask, v, acc_ref[h],
                                       m_scr[h][:, :1], l_scr[h][:, :1])
            acc_ref[h] = acc
            m_scr[h] = jnp.broadcast_to(m, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l, l_scr.shape[1:])
        if want_pm:
            # one row of the resident (8, LANES) block per step
            pm_ref[0, pl.ds(s % 8, 1), :] = jnp.broadcast_to(
                tile_max, (1, LANES))

        @pl.when(s == steps - 1)
        def _fin():
            for h in range(n_kv):
                out_ref[0, h] = _normalized(
                    acc_ref[h], l_scr[h][:, :1]).astype(out_ref.dtype)
            if want_state:
                m_ref[0] = m_scr[...]
                l_ref[0] = l_scr[...]

    return kern


@functools.partial(jax.jit, static_argnames=("pattern", "block_s", "scale",
                                             "interpret"))
def salo_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                positions: jax.Array, t, *, pattern: HybridSparsePattern,
                block_s: int = 128, scale: Optional[float] = None,
                interpret: bool = False) -> jax.Array:
    """q: (B, H, 1, hd); caches: (B, Hkv, S, hd); positions: (S,) shared or
    (B, S) per-request absolute position per slot (huge sentinel = empty);
    ``t``: scalar (lockstep) or (B,) per-request position — one launch
    serves a ragged continuous batch. Returns (B, H, 1, hd)."""
    B, H, _, hd = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    scale_ = (hd ** -0.5) if scale is None else scale
    t_arr = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))
    S_pad = -(-S // block_s) * block_s
    if S_pad != S:
        padc = ((0, 0), (0, 0), (0, S_pad - S), (0, 0))
        k_cache = jnp.pad(k_cache, padc)
        v_cache = jnp.pad(v_cache, padc)
        pos = jnp.pad(pos, ((0, 0), (0, S_pad - S)),
                      constant_values=PAD_SENTINEL)
    steps = S_pad // block_s
    qg = q.reshape(B, Hkv, rep, hd)
    pos_rows = pos.reshape(B * steps, 1, block_s)

    kern = functools.partial(_ragged_kernel, pattern=pattern, steps=steps,
                             scale=scale_)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                                # t vector
        grid=(B, Hkv, steps),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda b, h, s, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b, h, s, t: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b, h, s, t: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s),
                         lambda b, h, s, t: (b * steps + s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b, h, s, t: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep, LANES), jnp.float32),
            pltpu.VMEM((rep, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="salo_decode",
    )(t_arr, qg, k_cache, v_cache, pos_rows)
    return out.reshape(B, H, 1, hd)


def paged_decode_grid(rows: int, pages_per_req: int, page: int,
                      block_s: Optional[int] = None) -> tuple:
    """Grid ``(rows, steps)`` of :func:`salo_paged_decode`: one sequential
    step per ``block_s``-slot tile of each row's pages, over every row and
    page whatever the row holds (dead rows and unwritten pages included)."""
    bs = page if block_s is None else block_s
    return rows, pages_per_req * page // bs


@functools.partial(jax.jit, static_argnames=("pattern", "block_s", "scale",
                                             "interpret", "return_state",
                                             "return_page_stats"))
def salo_paged_decode(q: jax.Array, k_slab: jax.Array, v_slab: jax.Array,
                      page_tables: jax.Array, positions: jax.Array, t, *,
                      pattern: HybridSparsePattern,
                      block_s: Optional[int] = None,
                      scale: Optional[float] = None,
                      interpret: bool = False,
                      return_state: bool = False,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None,
                      return_page_stats: bool = False):
    """Ragged decode straight off the pooled paged slab.

    q: (B, H, 1, hd); slabs: (n_pages, page, Hkv, hd) shared by ALL
    requests; page_tables: (B, pages_per_req) int32 physical page per
    logical page; positions: (B, S_req) absolute position per logical slot
    (S_req = pages_per_req * page); ``t``: (B,) per-request position. The
    page table is scalar-prefetched, so the BlockSpec index map resolves
    logical tile -> physical page before each DMA — the kernel never sees a
    gathered copy of the cache. Returns (B, H, 1, hd).

    **int8 slab**: pass the layer's per-page ``k_scale``/``v_scale``
    (n_pages,) f32 — they ride as scalar-prefetch operands 3/4 next to
    the page table and each tile is dequantized in VMEM right after its
    DMA (the fp cache never materializes anywhere).

    ``return_page_stats=True`` additionally emits ``page_m`` (B, npp): the
    max masked score each request produced against each of its logical
    pages this step (NEG_INF for fully-masked pages) — the statistic the
    engine's Salca-style page-keep mask accumulates. Composes with
    ``return_state``; outputs are ``out[, m, l][, page_m]`` in that order.

    Under sequence-parallel serving each shard runs this launch over its
    OWN page tables / slot positions (its slice of the paged slab) and
    ``return_state=True`` makes the kernel also emit the online-softmax row
    stats ``(m, l)`` as (B, H, 1) — the per-shard partial the masked-psum
    merge combines across the "seq" axis. Requests with no owned live slot
    finalize to the (0, NEG_INF, 0) merge identity."""
    B, H, _, hd = q.shape
    n_pages, page, Hkv, _ = k_slab.shape
    npp = page_tables.shape[1]
    S_req = npp * page
    assert positions.shape == (B, S_req), (positions.shape, B, S_req)
    quant = k_scale is not None
    rep = H // Hkv
    scale_ = (hd ** -0.5) if scale is None else scale
    t_arr = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    bs = page if block_s is None else block_s
    assert page % bs == 0, f"block_s {bs} must divide page {page}"
    tpp = page // bs                       # tiles per page
    grid = paged_decode_grid(B, npp, page, block_s)
    steps = grid[1]
    qg = q.reshape(B, Hkv, rep, hd)
    pos_rows = positions.astype(jnp.int32).reshape(B * steps, 1, bs)
    pt_flat = page_tables.astype(jnp.int32).reshape(-1)
    n_pref = 4 if quant else 2

    def kv_idx(b, s, t_ref, pt_ref, *_):
        return (pt_ref[b * npp + s // tpp], s % tpp, 0, 0)

    def req_idx(b, s, *_):
        return (b, 0, 0, 0)

    kern = _make_paged_kernel(pattern=pattern, steps=steps, scale=scale_,
                              npp=npp, tpp=tpp, n_kv=Hkv, quant=quant,
                              want_state=return_state,
                              want_pm=return_page_stats,
                              compute_dtype=q.dtype)
    out_specs = [pl.BlockSpec((1, Hkv, rep, hd), req_idx)]
    # state mode emits the out partial in f32: the cross-shard merge
    # rounds to q.dtype once, after combining (per-shard rounding would
    # diverge from the single-device round-once numerics)
    out_shape = [jax.ShapeDtypeStruct(
        (B, Hkv, rep, hd), jnp.float32 if return_state else q.dtype)]
    if return_state:
        # m/l ride full LANES-wide blocks (every lane equal) so the output
        # keeps the TPU-native tiling; callers read lane 0.
        stat_spec = pl.BlockSpec((1, Hkv, rep, LANES), req_idx)
        stat_shape = jax.ShapeDtypeStruct((B, Hkv, rep, LANES), jnp.float32)
        out_specs += [stat_spec, stat_spec]
        out_shape += [stat_shape, stat_shape]
    if return_page_stats:
        # one LANES-wide row per sequential step (lanes equal), in (8,
        # LANES) blocks that stay resident for 8 steps; the host reduces
        # tiles -> pages below.
        n_rows = -(-steps // 8) * 8
        out_specs.append(pl.BlockSpec((1, 8, LANES),
                                      lambda b, s, *_: (b, s // 8, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((B, n_rows, LANES), jnp.float32))
    single = len(out_specs) == 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,   # t, page tables[, k_scale, v_scale]
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, rep, hd), req_idx),          # q
            pl.BlockSpec((1, bs, Hkv, hd), kv_idx),            # k slab
            pl.BlockSpec((1, bs, Hkv, hd), kv_idx),            # v slab
            pl.BlockSpec((1, 1, bs),
                         lambda b, s, *_: (b * steps + s, 0, 0)),  # pos
        ],
        out_specs=out_specs[0] if single else tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rep, hd), jnp.float32),
            pltpu.VMEM((Hkv, rep, LANES), jnp.float32),
            pltpu.VMEM((Hkv, rep, LANES), jnp.float32),
        ],
    )
    pref = (t_arr, pt_flat) + (
        (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
        if quant else ())
    res = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape[0] if single else tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="salo_paged_decode",
    )(*pref, qg, k_slab, v_slab, pos_rows)
    res = (res,) if single else list(res)
    out = res[0].reshape(B, H, 1, hd)
    rest = []
    if return_state:
        m, l = res[1], res[2]
        rest += [m[..., 0].reshape(B, H, 1), l[..., 0].reshape(B, H, 1)]
    if return_page_stats:
        pm = res[-1][:, :steps, 0]                 # (B, steps)
        rest.append(pm.reshape(B, npp, tpp).max(axis=-1))
    return (out, *rest) if rest else out
