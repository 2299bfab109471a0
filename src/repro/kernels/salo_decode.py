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
  (:mod:`repro.serve.paged_cache`): it walks only each request's live
  pages (the prefix its position can attend), a block of
  :func:`pages_per_block` pages per grid step, each page an input whose
  BlockSpec index map reads the physical page from a scalar-prefetched
  table — no per-request gather ever materializes in HBM. int8 slabs
  additionally prefetch the per-page f32 scales and dequantize each tile
  in VMEM; ``return_page_stats`` emits per-(request, page) max masked
  scores for the engine's stats-driven page-keep mask.

Both kernels stream cache tiles through VMEM past the resident grouped
query (GQA: rep = H/Hkv query rows share each KV head — no KV repeat), with
the usual online-softmax scratch. Masks are evaluated on original positions
(``scheduler.causal_step_mask`` semantics, inlined below).

Grids: ``(B, Hkv, n_slot_tiles)`` for the contiguous kernel, last dim
sequential; for the paged one a single sequential dim of one step per live
block, sized at run time (each step holds a block of every KV head).
Compiled mode runs on a TPU only; the XLA ragged decode twin
(:func:`repro.core.attention.hybrid_decode_attention`) is the engine
elsewhere, chosen by the caller. Validated in interpret mode
in tests/test_decode_kernel.py and compiled for a v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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


def _make_paged_kernel(*, pattern: HybridSparsePattern, scale: float,
                       bsb: int, units: int, upp: int, ppb: int, n_kv: int,
                       quant: bool, want_state: bool, want_pm: bool,
                       compute_dtype):
    """Paged-decode kernel for any combination of the static features.

    Grid step ``s`` folds block ``j = blk[s]`` of row ``b = row[s]``:
    ``bsb`` slots (``ppb`` pages) that arrive as ``units`` tiles, one
    input per tile (``upp`` tiles per page), each a tile of ALL ``n_kv``
    KV heads — the slab's (Hkv, hd) minor dims stay whole, as the TPU
    tiling requires. The tiles are joined into one (bsb, hd) block per
    head and folded into that head's online-softmax state; a row's steps
    are consecutive, so the state lives in scratch from its first block
    to its last. Tiles past the row's live pages are zeroed (their
    buffers hold whatever an earlier step fetched) and their slots
    masked. ``quant`` dequantizes each tile by its page's
    scalar-prefetched scale (no fp slab ever exists in HBM),
    ``want_state`` emits the (m, l) row stats, ``want_pm`` emits each
    slot's max masked score over all heads. Refs arrive positionally
    (prefetch, ins, outs, scratch) so the one body parses them by the
    same flags."""
    page = bsb // ppb

    def kern(*refs):
        n_ref, t_ref, row_ref, blk_ref, tbl_ref = refs[:5]
        i = 5
        if quant:
            ks_ref, vs_ref = refs[5:7]
            i = 7
        q_ref, pos_ref = refs[i:i + 2]
        i += 2
        k_refs, v_refs = refs[i:i + units], refs[i + units:i + 2 * units]
        i += 2 * units
        out_ref = refs[i]
        i += 1
        if want_state:
            m_ref, l_ref = refs[i:i + 2]
            i += 2
        if want_pm:
            pm_ref = refs[i]
            i += 1
        acc_ref, m_scr, l_scr = refs[i:i + 3]
        s = pl.program_id(0)
        b, j = row_ref[s], blk_ref[s]
        n = n_ref[b]
        lim = n * page - j * bsb              # live slots of this block

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            if want_pm:
                pm_ref[...] = jnp.full_like(pm_ref, NEG_INF)

        slots = jax.lax.broadcasted_iota(jnp.int32, (1, bsb), 1)
        mask = _decode_mask(pattern, pos_ref[0], t_ref[b]) \
            & (slots < lim)                             # (1, bsb)
        # per tile: live?, and the scale its f32 values take (0 when
        # dead, so stale int8 reads as 0; stale fp is selected away)
        live = [u // upp * page < lim for u in range(units)]
        if quant:
            pg = [tbl_ref[s * units + u] for u in range(units)]
            k_sc = [jnp.where(live[u], ks_ref[pg[u]], 0.0)
                    for u in range(units)]
            v_sc = [jnp.where(live[u], vs_ref[pg[u]], 0.0)
                    for u in range(units)]

        def joined(refs, sc, h):
            tiles = []
            for u in range(units):
                x = refs[u][0, :, h, :].astype(jnp.float32)
                tiles.append(x * sc[u] if quant
                             else jnp.where(live[u], x, 0.0))
            return jnp.concatenate(tiles, axis=0).astype(compute_dtype)

        slot_max = None
        for h in range(n_kv):
            k = joined(k_refs, k_sc if quant else None, h)  # (bsb, hd)
            v = joined(v_refs, v_sc if quant else None, h)
            scores = _masked_scores(q_ref[0, h], k, mask, scale)
            if want_pm:
                hmax = jnp.max(scores, axis=0, keepdims=True)
                slot_max = hmax if slot_max is None else jnp.maximum(
                    slot_max, hmax)
            acc, m, l = _online_update(scores, mask, v, acc_ref[h],
                                       m_scr[h][:, :1], l_scr[h][:, :1])
            acc_ref[h] = acc
            m_scr[h] = jnp.broadcast_to(m, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l, l_scr.shape[1:])
        if want_pm:
            pm_ref[0, pl.ds(j, 1), :] = slot_max

        @pl.when(lim <= bsb)                 # the row's last block
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


def pages_per_block(page: int, npp: int) -> int:
    """Pages one grid step of :func:`salo_paged_decode` folds: the fewest
    that fill a lane row of scores (at least ``LANES`` slots), and no more
    than a row has — 16 at the 8-slot page."""
    return min(-(-LANES // page), npp)


def paged_decode_walk(live_pages, page: int, npp: int, xp=np):
    """Pages the walk of :func:`salo_paged_decode` covers in a row with
    ``live_pages`` live: its live pages rounded up to whole blocks of
    :func:`pages_per_block`, at most ``npp`` (0 for a row with none).
    The kernel folds every slot of those blocks, masked past the live
    pages, and copies only the live pages among them. The kernel sizes its
    walk by this rule (``xp=jnp``); the engine's decode counter reads it
    (``xp=np``)."""
    ppb = pages_per_block(page, npp)
    live = xp.clip(live_pages, 0, npp)
    return xp.minimum(-(-live // ppb) * ppb, npp)


def _walk(page_tables, n, page: int, upp: int):
    """The kernel's walk over the live blocks of every row, as scalar
    tables in grid order: its length ``steps`` (the dynamic grid), and for
    each step (at most ``B * n_blocks``; the tail unused) its row, its
    block, and the physical page of each of its ``ppb * upp`` tile inputs.
    A live tile reads its page; a dead one repeats what that input read at
    its last live step, or before any, what it reads at its first (page 0
    for an input never live). The pipeline copies only when an input's
    block index changes, so it fetches each live page once and nothing
    else."""
    B, npp = page_tables.shape
    ppb = pages_per_block(page, npp)
    n_blocks = -(-npp // ppb)
    units = ppb * upp
    nb = -(-paged_decode_walk(n, page, npp, jnp) // ppb)   # live blocks
    ends = jnp.cumsum(nb)
    step = jnp.arange(B * n_blocks, dtype=jnp.int32)
    # one compare per (step, row): a single fusion, where the default
    # binary search is a while loop on the device
    row = jnp.minimum(jnp.searchsorted(ends, step, side="right",
                                       method="compare_all"),
                      B - 1).astype(jnp.int32)
    blk = step - (ends - nb)[row]
    lp = blk[:, None] * ppb + jnp.arange(units)[None, :] // upp
    live = (lp < n[row][:, None]) & (step < ends[-1])[:, None]
    phys = jnp.take_along_axis(page_tables[row], jnp.minimum(lp, npp - 1),
                               axis=1)
    last = jax.lax.cummax(jnp.where(live, step[:, None], -1), axis=0)
    first = jnp.argmax(live, axis=0)[None, :]
    held = jnp.take_along_axis(phys, jnp.where(last >= 0, last, first),
                               axis=0)
    tiles = jnp.where(live.any(axis=0)[None, :], held, 0).reshape(-1)
    return ends[-1], row, blk, tiles


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
                      return_page_stats: bool = False,
                      live_pages: Optional[jax.Array] = None):
    """Ragged decode straight off the pooled paged slab.

    q: (B, H, 1, hd); slabs: (n_pages, page, Hkv, hd) shared by ALL
    requests; page_tables: (B, pages_per_req) int32 physical page per
    logical page; positions: (B, S_req) absolute position per logical slot
    (S_req = pages_per_req * page); ``t``: (B,) per-request position.
    Returns (B, H, 1, hd).

    ``live_pages``: (B,) int32, the number of leading logical pages of
    each row that can hold an attended key (0 for an inactive row;
    default every page). Only the blocks that hold them are folded
    (:func:`paged_decode_walk`), and only those pages copied: the grid
    has one step per live block of
    :func:`pages_per_block` pages, row after row (:func:`_walk`), so a
    row costs what it holds, not what its table could hold. Each page of
    a block is an input of its own whose BlockSpec index map reads the
    physical page from a scalar-prefetched table: the slab is read page
    by page, with no gathered copy in HBM. ``block_s`` (a divisor of the
    page, default the page) is the slots one input tile holds.

    **int8 slab**: pass the layer's per-page ``k_scale``/``v_scale``
    (n_pages,) f32 — they ride as scalar-prefetch operands next to the
    tile table and each tile is dequantized in VMEM right after its DMA
    (the fp cache never materializes anywhere).

    ``return_page_stats=True`` additionally emits ``page_m`` (B, npp): the
    max masked score each request produced against each of its logical
    pages this step (NEG_INF for fully-masked and never-fetched pages) —
    the statistic the engine's Salca-style page-keep mask accumulates.
    Composes with ``return_state``; outputs are ``out[, m, l][, page_m]``
    in that order.

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
    n = (jnp.full((B,), npp, jnp.int32) if live_pages is None
         else jnp.clip(jnp.asarray(live_pages, jnp.int32), 0, npp))
    bs = page if block_s is None else block_s
    assert page % bs == 0, f"block_s {bs} must divide page {page}"
    upp = page // bs                       # tiles per page
    ppb = pages_per_block(page, npp)
    n_blocks = -(-npp // ppb)
    bsb, units = ppb * page, ppb * upp
    pos = positions.astype(jnp.int32)
    if n_blocks * bsb != S_req:
        pos = jnp.pad(pos, ((0, 0), (0, n_blocks * bsb - S_req)),
                      constant_values=PAD_SENTINEL)
    pos = pos.reshape(B * n_blocks, 1, bsb)
    qg = q.reshape(B, Hkv, rep, hd)
    steps, rows, blks, tiles = _walk(page_tables.astype(jnp.int32), n, page,
                                     upp)

    def row(s, n_ref, t_ref, row_ref, *_):
        return (row_ref[s], 0, 0, 0)

    def pos_idx(s, n_ref, t_ref, row_ref, blk_ref, *_):
        return (row_ref[s] * n_blocks + blk_ref[s], 0, 0)

    def tile_idx(u):
        def idx(s, n_ref, t_ref, row_ref, blk_ref, tile_ref, *_):
            return (tile_ref[s * units + u], u % upp, 0, 0)
        return idx

    kern = _make_paged_kernel(pattern=pattern, scale=scale_, bsb=bsb,
                              units=units, upp=upp, ppb=ppb, n_kv=Hkv,
                              quant=quant,
                              want_state=return_state,
                              want_pm=return_page_stats,
                              compute_dtype=q.dtype)
    out_specs = [pl.BlockSpec((1, Hkv, rep, hd), row)]
    # state mode emits the out partial in f32: the cross-shard merge
    # rounds to q.dtype once, after combining (per-shard rounding would
    # diverge from the single-device round-once numerics)
    out_shape = [jax.ShapeDtypeStruct(
        (B, Hkv, rep, hd), jnp.float32 if return_state else q.dtype)]
    if return_state:
        # m/l ride full LANES-wide blocks (every lane equal) so the output
        # keeps the TPU-native tiling; callers read lane 0.
        stat_spec = pl.BlockSpec((1, Hkv, rep, LANES), row)
        stat_shape = jax.ShapeDtypeStruct((B, Hkv, rep, LANES), jnp.float32)
        out_specs += [stat_spec, stat_spec]
        out_shape += [stat_shape, stat_shape]
    if return_page_stats:
        # a row's per-slot maxima, one row per block, resident over the
        # row's steps; reduced to pages below
        out_specs.append(pl.BlockSpec((1, n_blocks, bsb),
                                      lambda s, *r: (row(s, *r)[0], 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((B, n_blocks, bsb), jnp.float32))
    single = len(out_specs) == 1
    tile_specs = [pl.BlockSpec((1, bs, Hkv, hd), tile_idx(u))
                  for u in range(units)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # live pages, t, the walk[, k_scale, v_scale]
        num_scalar_prefetch=7 if quant else 5,
        # at least one step: a launch with no live row folds nothing
        grid=(jnp.maximum(steps, 1),),
        in_specs=[pl.BlockSpec((1, Hkv, rep, hd), row),          # q
                  pl.BlockSpec((1, 1, bsb), pos_idx)]            # pos
        + tile_specs + tile_specs,                               # k, v
        out_specs=out_specs[0] if single else tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rep, hd), jnp.float32),
            pltpu.VMEM((Hkv, rep, LANES), jnp.float32),
            pltpu.VMEM((Hkv, rep, LANES), jnp.float32),
        ],
    )
    pref = (n, t_arr, rows, blks, tiles) + (
        (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
        if quant else ())
    res = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape[0] if single else tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="salo_paged_decode",
    )(*pref, qg, pos, *([k_slab] * units), *([v_slab] * units))
    res = (res,) if single else list(res)
    # the grid never visits a row with no live page: its outputs are the
    # empty row's (out 0, m NEG_INF, l 0, every page NEG_INF)
    visited = (n > 0)[:, None, None]
    out = jnp.where(visited[..., None], res[0].reshape(B, H, 1, hd), 0)
    rest = []
    if return_state:
        m, l = (res[i][..., 0].reshape(B, H, 1) for i in (1, 2))
        rest += [jnp.where(visited, m, NEG_INF), jnp.where(visited, l, 0.0)]
    if return_page_stats:
        pm = res[-1].reshape(B, n_blocks * bsb)[:, :S_req]
        pm = pm.reshape(B, npp, page).max(axis=-1)
        rest.append(jnp.where(visited[:, 0], pm, NEG_INF))
    return (out, *rest) if rest else out
