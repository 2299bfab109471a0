"""ShardedPlan: the ExecutionPlan sliced per sequence shard — SALO's
hierarchical window splitting at datacenter scale.

The paper's data scheduler splits a sliding window so a PE array only ever
sees neighboring tiles; across arrays the same argument says a sequence
shard only needs **neighbor** KV tiles (the band reach) plus the tiny
global-token set — a halo exchange, not an all-gather. This module lowers
that into the ExecutionPlan IR:

* ``shard_plan(plan, n_shards)`` slices the plan's step tables by owner
  query block. Every KV tile a shard's rows reference is classified as
  **local** (owned), **halo** (owned by a shard at signed distance ``δ``,
  fetched by one ``ppermute`` per distinct distance — distance sets beyond
  ±1 arise from 2-D ViL bands or windows wider than a shard), or
  **global** (a tile holding global-prefix keys, broadcast once by a
  masked ``psum`` — so ``n_global`` may exceed a shard's length, which the
  retired prototype silently truncated). The tables are remapped onto each
  shard's **local view** ``[local | halo groups | global slots]`` and
  stacked per shard; at run time each device selects its slice by
  ``axis_index`` and feeds it to the *existing fused engines* — the Pallas
  scalar-prefetch kernels or their XLA scan twins — via the table-driven
  entry points (``salo_table_attention`` & co.).

* Because every row's full step set executes on its owner device, the
  windowed + global-column output is already normalized — no cross-device
  softmax merge. Only global *rows* (global queries attending everything)
  need cross-shard state, and they are the same tiny dense epilogue the
  single-device wrapper uses, computed on the original (globally sharded)
  arrays.

* The backward reuses :func:`repro.core.blockwise.plan_backward` — ONE
  contract with the single-device engines — with shard-mapped gradient
  passes: dQ replays the local tables against the re-exchanged view;
  dK/dV walks the shard's PACKED transposed tables over the view, then
  halo-tile gradients ride the *reverse* ``ppermute`` back to their owners
  and global-slot gradients a ``psum``, scatter-added into the owner's
  local dK/dV — the exact adjoint of the forward exchange.

Traffic per device per layer: ``(halo_tiles * Bk + n_global_tiles * Bk) *
d`` — independent of sequence length — vs ``(n_shards - 1) * n_local * d``
for all-gather ring attention (quantified in ``benchmarks/dist_stats.py``
-> ``BENCH_dist.json``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.blockwise import (_global_rows, plan_backward,
                                  table_attention_scan, table_dkv_scan,
                                  table_dkv_scatter_scan, table_dq_scan,
                                  undo_working, working_stream)
from repro.core.patterns import HybridSparsePattern
from repro.core.scheduler import (PAD_SENTINEL, ExecutionPlan, build_plan,
                                  pack_rows, schedule)


# ---------------------------------------------------------------------- #
# The ShardedPlan IR
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True, eq=False)
class ShardedPlan:
    """Static per-shard slicing of an ExecutionPlan (pure numpy metadata).

    Stacked arrays carry one row per shard; device ``s`` selects row
    ``axis_index`` at run time. View-tile indices live in
    ``[0, view_tiles)`` over the local layout
    ``[nkb_l local | halo group per distance | n_gt global slots]``.
    """
    plan: ExecutionPlan
    n_shards: int
    nq_l: int                     # query blocks per shard
    nkb_l: int                    # owned KV tiles per shard
    gtiles: Tuple[int, ...]       # global-key tiles (global tile order)
    halo_dists: Tuple[int, ...]   # distinct signed owner distances
    halo_counts: Tuple[int, ...]  # per distance: padded slot count T_δ
    halo_real: Tuple[int, ...]    # per shard: real (unpadded) halo tiles
    view_tiles: int               # nkb_l + sum(halo_counts) + n_gt
    tables: np.ndarray            # (n_shards, nq_l, W) view-tile ids
    flags: np.ndarray             # (n_shards, nq_l, W) step flags
    view_map: np.ndarray          # (n_shards, view_tiles) global tile each
    #                               view slot holds after the exchange (-1 =
    #                               padded halo slot, never referenced) — the
    #                               repro.analysis exchange-soundness hook
    send_idx: Tuple[np.ndarray, ...]  # per distance: (n_shards, T_δ) local
    #                                   tile indices each shard SENDS (pad 0)
    g_owner_idx: np.ndarray       # (n_shards, n_gt) local idx of owned gtile
    g_owned: np.ndarray           # (n_shards, n_gt) bool ownership mask
    pos_q: np.ndarray             # (n_shards, nq_l, block_q) positions
    pos_k: np.ndarray             # (n_shards, view_tiles, block_k) positions
    t_row_tile: np.ndarray        # (n_shards, R) packed dK/dV owner tiles
    t_q_blocks: np.ndarray        # (n_shards, R, Wt) packed local q blocks
    t_flags: np.ndarray           # (n_shards, R, Wt)

    @property
    def n_gt(self) -> int:
        return len(self.gtiles)

    # ------------------------------------------------------------------ #
    def stats(self, d: int, dtype_bytes: int = 2) -> dict:
        """Per-device per-layer collective bytes (the paper's halo claim).

        ``halo_tiles``/``halo_bytes`` count what ``_build_views`` actually
        TRANSMITS: every shard sends the padded ``sum(halo_counts)`` slots
        per direction (SPMD buffers are padded to the worst shard per
        distance, wrap sends included); ``halo_tiles_real`` is the worst
        shard's unpadded need, for reference. ``bcast_bytes`` is the
        global-tile psum, vs the all-gather ring baseline that cycles
        every other shard's full KV through each device."""
        bk = self.plan.block_k
        halo_tiles = sum(self.halo_counts)
        halo_bytes = halo_tiles * bk * d * dtype_bytes * 2
        bcast_bytes = self.n_gt * bk * d * dtype_bytes * 2
        allgather_bytes = ((self.n_shards - 1) * self.nkb_l * bk * d
                           * dtype_bytes * 2)
        return dict(
            n_shards=self.n_shards,
            n_local=self.nkb_l * bk,
            halo_tiles=halo_tiles,
            halo_tiles_real=max(self.halo_real) if self.halo_real else 0,
            global_tiles=self.n_gt,
            halo_bytes=halo_bytes,
            bcast_bytes=bcast_bytes,
            exchange_bytes=halo_bytes + bcast_bytes,
            allgather_bytes=allgather_bytes,
            bytes_ratio=(halo_bytes + bcast_bytes)
            / max(allgather_bytes, 1),
        )


@functools.lru_cache(maxsize=64)
def shard_plan(plan: ExecutionPlan, n_shards: int) -> ShardedPlan:
    """Slice ``plan`` into per-shard step tables + exchange metadata."""
    nq, nkb = plan.nq, plan.nkb
    if nq % n_shards or nkb % n_shards:
        raise ValueError(
            f"plan grid ({nq} q blocks, {nkb} KV tiles) must be divisible "
            f"by n_shards={n_shards}; build the plan with pad_multiple="
            f"n_shards * lcm(block_q, block_k)")
    nq_l, nkb_l = nq // n_shards, nkb // n_shards
    bq, bk = plan.block_q, plan.block_k
    pos = plan.positions_padded()
    g = plan.sched.n_global

    if g > 0:
        gtiles = [int(t) for t in np.nonzero(
            (pos.reshape(nkb, bk) < g).any(axis=1))[0]]
    else:
        gtiles = []
    gset = set(gtiles)
    g_index = {t: i for i, t in enumerate(gtiles)}
    n_gt = len(gtiles)

    # Referenced non-local, non-global tiles per shard, grouped by the
    # signed owner distance δ (owner = shard + δ).
    halo = []
    for s in range(n_shards):
        tiles = set()
        for i in range(s * nq_l, (s + 1) * nq_l):
            for st in range(int(plan.num_steps[i])):
                tiles.add(int(plan.kv_blocks[i, st]))
        halo.append(sorted(t for t in tiles
                           if t // nkb_l != s and t not in gset))
    dists = sorted({t // nkb_l - s for s in range(n_shards)
                    for t in halo[s]})
    need = {d: [[t for t in halo[s] if t // nkb_l - s == d]
                for s in range(n_shards)] for d in dists}
    counts = [max(len(need[d][s]) for s in range(n_shards)) for d in dists]
    view_tiles = nkb_l + sum(counts) + n_gt

    # Group base offsets in the view + per-shard view index of each tile.
    group_off = {}
    off = nkb_l
    for d, T in zip(dists, counts):
        group_off[d] = off
        off += T
    g_base = off
    view_of = []   # per shard: {global tile -> view tile}
    for s in range(n_shards):
        m = {}
        for t in range(s * nkb_l, (s + 1) * nkb_l):
            m[t] = t - s * nkb_l
        for d in dists:
            for slot, t in enumerate(need[d][s]):
                m[t] = group_off[d] + slot
        for t in gtiles:
            m.setdefault(t, g_base + g_index[t])
        view_of.append(m)

    # Remapped step tables (values -> view tiles), stacked per shard.
    W = plan.max_steps
    tables = np.zeros((n_shards, nq_l, W), dtype=np.int32)
    flags = np.zeros((n_shards, nq_l, W), dtype=np.int32)
    for s in range(n_shards):
        for i_l in range(nq_l):
            i = s * nq_l + i_l
            for st in range(int(plan.num_steps[i])):
                tables[s, i_l, st] = view_of[s][int(plan.kv_blocks[i, st])]
                flags[s, i_l, st] = int(plan.flags[i, st])

    # What each view slot physically holds after _build_views runs: the
    # local region is the shard's own tiles, each halo group slot the tile
    # its need-list ordered there, each global slot its gtile. Padded halo
    # slots (beyond a shard's need, up to the SPMD-common T_δ) carry -1:
    # they receive whatever the sender's slot-0 default gathers, are
    # referenced by no table, and keep PAD_SENTINEL positions. This map is
    # what repro.analysis.plan_verify proves the tables + send schedule
    # against.
    view_map = np.full((n_shards, view_tiles), -1, dtype=np.int32)
    for s in range(n_shards):
        view_map[s, :nkb_l] = np.arange(s * nkb_l, (s + 1) * nkb_l)
        for d in dists:
            for slot, t in enumerate(need[d][s]):
                view_map[s, group_off[d] + slot] = t
        for gi, t in enumerate(gtiles):
            view_map[s, g_base + gi] = t

    # What each shard SENDS per distance: the tiles its receiver (shard
    # s - δ, which fetches from owner s) listed, as local tile indices.
    send_idx = []
    for d, T in zip(dists, counts):
        arr = np.zeros((n_shards, T), dtype=np.int32)
        for j in range(n_shards):
            r = j - d
            if 0 <= r < n_shards:
                for slot, t in enumerate(need[d][r]):
                    arr[j, slot] = t - j * nkb_l
        send_idx.append(arr)

    g_owner_idx = np.zeros((n_shards, max(n_gt, 1)), dtype=np.int32)
    g_owned = np.zeros((n_shards, max(n_gt, 1)), dtype=bool)
    for gi, t in enumerate(gtiles):
        o = t // nkb_l
        g_owner_idx[o, gi] = t - o * nkb_l
        g_owned[o, gi] = True
    g_owner_idx = g_owner_idx[:, :n_gt]
    g_owned = g_owned[:, :n_gt]

    # Static positions: local queries; the view's local/halo/global slots.
    pos_q = pos.reshape(n_shards, nq_l, bq).copy()
    pos_k = np.full((n_shards, view_tiles, bk), PAD_SENTINEL, dtype=np.int32)
    pos_t = pos.reshape(nkb, bk)
    for s in range(n_shards):
        pos_k[s, :nkb_l] = pos_t[s * nkb_l: (s + 1) * nkb_l]
        for d in dists:
            for slot, t in enumerate(need[d][s]):
                pos_k[s, group_off[d] + slot] = pos_t[t]
        for gi, t in enumerate(gtiles):
            pos_k[s, g_base + gi] = pos_t[t]

    # Packed local transposed tables (dK/dV): per shard, per VIEW tile, the
    # local query blocks that visit it — one common packed width so the
    # stacked arrays stay rectangular across shards.
    rows_per_shard = []
    all_lens = []
    for s in range(n_shards):
        rows = [[] for _ in range(view_tiles)]
        for i_l in range(nq_l):
            i = s * nq_l + i_l
            for st in range(int(plan.num_steps[i])):
                fl = int(plan.flags[i, st])
                if fl:
                    rows[int(tables[s, i_l, st])].append((i_l, fl))
        rows_per_shard.append(rows)
        all_lens.extend(len(r) for r in rows if r)
    lens = np.asarray(all_lens if all_lens else [1])
    width = max(1, int(np.ceil(np.percentile(lens, 95))))
    packed = [pack_rows(rows, width) for rows in rows_per_shard]
    R = max(p[0].shape[0] for p in packed)
    t_row_tile = np.zeros((n_shards, R), dtype=np.int32)
    t_q_blocks = np.zeros((n_shards, R, width), dtype=np.int32)
    t_flags = np.zeros((n_shards, R, width), dtype=np.int32)
    for s, (rt, qb, fl, _ns, _w) in enumerate(packed):
        r = rt.shape[0]
        t_row_tile[s, :r] = rt
        t_q_blocks[s, :r] = qb
        t_flags[s, :r] = fl

    return ShardedPlan(
        plan=plan, n_shards=n_shards, nq_l=nq_l, nkb_l=nkb_l,
        gtiles=tuple(gtiles), halo_dists=tuple(dists),
        halo_counts=tuple(counts),
        halo_real=tuple(len(h) for h in halo), view_tiles=view_tiles,
        tables=tables, flags=flags, view_map=view_map,
        send_idx=tuple(send_idx),
        g_owner_idx=g_owner_idx, g_owned=g_owned, pos_q=pos_q, pos_k=pos_k,
        t_row_tile=t_row_tile, t_q_blocks=t_q_blocks, t_flags=t_flags)


# ---------------------------------------------------------------------- #
# Cross-shard softmax merge for the sharded SERVING engines
# ---------------------------------------------------------------------- #
def masked_psum_merge(out: jax.Array, m: jax.Array, l: jax.Array,
                      axis: str) -> jax.Array:
    """Combine per-shard finalized attention partials across a mesh axis.

    The serving-side counterpart of the training path's halo exchange: the
    sharded paged slab gives each shard of the "seq" axis a disjoint slice
    of every request's cache, so decode / chunked prefill run ONE launch
    per shard over the owned slots and the partials are merged here — the
    cross-device instance of :func:`repro.core.renorm.merge`, applied to
    finalized triples. ``out``: (..., d) = acc / l (guarded); ``m``/``l``:
    (...) row stats. Each shard's contribution is weighted by
    ``c = l * exp(m - M)`` with ``M = pmax(m)``; the
    ``renorm.PartialState`` empty-row identity ``(0, NEG_INF, 0)`` gives
    ``c == 0``, which is what makes the psum *masked*: shards holding no
    valid slot for a row (inactive request, slot owned elsewhere, ring not
    yet reaching this shard) contribute exactly nothing, with no explicit
    mask traffic.
    """
    from repro.core.renorm import NEG_INF

    M = jax.lax.pmax(m, axis)
    shift = jnp.where(M <= NEG_INF / 2, 0.0, M)
    c = l * jnp.exp(m - shift)       # m <= M; empty rows: l == 0 -> c == 0
    num = jax.lax.psum(out.astype(jnp.float32) * c[..., None], axis)
    den = jax.lax.psum(c, axis)
    return (num / jnp.where(den == 0.0, 1.0, den)[..., None]).astype(
        out.dtype)


# ---------------------------------------------------------------------- #
# The halo/broadcast exchange and its exact adjoint
# ---------------------------------------------------------------------- #
def _build_views(sp: ShardedPlan, axis: str, idx, k_l, v_l):
    """Local KV -> full local view: one ppermute per halo distance (K and V
    ride one stacked buffer) + one masked psum for the global tiles."""
    B, _, D = k_l.shape
    bk = sp.plan.block_k
    kv = jnp.stack([k_l.reshape(B, sp.nkb_l, bk, D),
                    v_l.reshape(B, sp.nkb_l, bk, D)])
    parts = [kv]
    for d_i, (delta, T) in enumerate(zip(sp.halo_dists, sp.halo_counts)):
        sel = jnp.take(jnp.asarray(sp.send_idx[d_i]), idx, axis=0)
        buf = jnp.take(kv, sel, axis=2)                   # (2, B, T, bk, D)
        perm = [(j, (j - delta) % sp.n_shards) for j in range(sp.n_shards)]
        parts.append(jax.lax.ppermute(buf, axis, perm))
    if sp.n_gt:
        gsel = jnp.take(jnp.asarray(sp.g_owner_idx), idx, axis=0)
        gown = jnp.take(jnp.asarray(sp.g_owned), idx, axis=0)
        contrib = jnp.where(gown[None, None, :, None, None],
                            jnp.take(kv, gsel, axis=2),
                            jnp.zeros((), kv.dtype))
        parts.append(jax.lax.psum(contrib, axis))
    view = jnp.concatenate(parts, axis=2)       # (2, B, view_tiles, bk, D)
    return (view[0].reshape(B, sp.view_tiles * bk, D),
            view[1].reshape(B, sp.view_tiles * bk, D))


def _return_views(sp: ShardedPlan, axis: str, idx, dk_view, dv_view):
    """Adjoint of :func:`_build_views`: halo-slot gradients ride the
    REVERSE ppermute back to their owner shard; global-slot gradients are
    psum'd and claimed by each tile's owner. Padded slots are never
    referenced by any table, so their gradients are exactly zero and the
    scatter-adds of the padding lanes are no-ops."""
    B, _, D = dk_view.shape
    bk = sp.plan.block_k
    dkv = jnp.stack([dk_view.reshape(B, sp.view_tiles, bk, D),
                     dv_view.reshape(B, sp.view_tiles, bk, D)])
    dloc = dkv[:, :, : sp.nkb_l]
    off = sp.nkb_l
    for d_i, (delta, T) in enumerate(zip(sp.halo_dists, sp.halo_counts)):
        buf = dkv[:, :, off: off + T]
        off += T
        perm = [(j, (j + delta) % sp.n_shards) for j in range(sp.n_shards)]
        back = jax.lax.ppermute(buf, axis, perm)
        sel = jnp.take(jnp.asarray(sp.send_idx[d_i]), idx, axis=0)
        dloc = dloc.at[:, :, sel].add(back)
    if sp.n_gt:
        dg = jax.lax.psum(dkv[:, :, off: off + sp.n_gt], axis)
        gsel = jnp.take(jnp.asarray(sp.g_owner_idx), idx, axis=0)
        gown = jnp.take(jnp.asarray(sp.g_owned), idx, axis=0)
        dloc = dloc.at[:, :, gsel].add(
            jnp.where(gown[None, None, :, None, None], dg,
                      jnp.zeros((), dg.dtype)))
    return (dloc[0].reshape(B, sp.nkb_l * bk, D),
            dloc[1].reshape(B, sp.nkb_l * bk, D))


# ---------------------------------------------------------------------- #
# Shard-local engines (the existing fused kernels / their XLA twins)
# ---------------------------------------------------------------------- #
def _resolve_engine(impl: str):
    """(engine, interpret): the fused kernel for the Pallas impls, the XLA
    scan twin for "blockwise"."""
    if impl in ("pallas", "pallas_interpret"):
        return "pallas", impl == "pallas_interpret"
    return "blockwise", False


def _shard_tables(sp: ShardedPlan, idx):
    tbl = jnp.take(jnp.asarray(sp.tables), idx, axis=0)     # (nq_l, W)
    flg = jnp.take(jnp.asarray(sp.flags), idx, axis=0)
    pq = jnp.take(jnp.asarray(sp.pos_q), idx, axis=0)       # (nq_l, bq)
    pk = jnp.take(jnp.asarray(sp.pos_k), idx, axis=0)       # (view, bk)
    return tbl, flg, pq, pk


@functools.lru_cache(maxsize=64)
def _sharded_always_keep(sp: ShardedPlan, local_window: int) -> np.ndarray:
    """Per-shard never-drop masks over the candidate tables: the dynamic
    selection runs on each shard's [local | halo | global] view, and the
    causal-local/global exemptions are decided on ORIGINAL positions — the
    view remap is transparent. Stacked (n_shards, nq_l, W) bool."""
    from repro.core.dynamic import always_keep_mask
    out = np.zeros(sp.tables.shape, dtype=bool)
    for s in range(sp.n_shards):
        out[s] = always_keep_mask(sp.tables[s], sp.flags[s], sp.pos_q[s],
                                  sp.pos_k[s], local_window,
                                  sp.plan.sched.causal)
    return out


def _dyn_select(sp: ShardedPlan, dyn, idx, q_l, k_view, tbl, flg, pq, pk,
                scale: float):
    """Per-shard top-k over the traced candidate slice: same selector as
    the single-device path, run INSIDE the shard_map region after the view
    exchange — the ppermute/psum schedule stays static while the executed
    steps are content-chosen. Deterministic in (q_l, k_view), so forward
    and backward replay the identical table."""
    from repro.core.dynamic import _resolve_window, select_steps
    lw = _resolve_window(dyn, sp.plan.block_q, sp.plan.block_k)
    ak = jnp.take(jnp.asarray(_sharded_always_keep(sp, lw)), idx, axis=0)
    keep = min(int(dyn.keep), sp.tables.shape[2])
    return select_steps(q_l, k_view, tbl, flg, pq, pk, ak, keep, scale,
                        dyn.pool_k)


def _make_local_fwd(sp: ShardedPlan, axis: str, scale: float, impl: str,
                    dyn=None):
    engine, interpret = _resolve_engine(impl)
    sched = sp.plan.sched
    bq, bk = sp.plan.block_q, sp.plan.block_k

    def local(q_l, k_l, v_l):
        idx = jax.lax.axis_index(axis)
        tbl, flg, pq, pk = _shard_tables(sp, idx)
        k_view, v_view = _build_views(sp, axis, idx, k_l, v_l)
        if dyn is not None:
            tbl, flg = _dyn_select(sp, dyn, idx, q_l, k_view, tbl, flg,
                                   pq, pk, scale)
        if engine == "pallas":
            from repro.kernels.salo_attention import salo_table_attention
            return salo_table_attention(
                q_l, k_view, v_view, pq, pk, tbl.reshape(-1),
                flg.reshape(-1), sched=sched, block_q=bq, block_k=bk,
                scale=scale, interpret=interpret)
        return table_attention_scan(q_l, k_view, v_view, pq, pk, tbl, flg,
                                    sched, scale)

    return local


def _make_local_bwd(sp: ShardedPlan, axis: str, scale: float, impl: str,
                    dyn=None):
    """ONE shard-local backward: a single view exchange feeds BOTH the dQ
    pass (local forward tables) and the dK/dV pass (packed transposed
    tables) — separate shard_map regions would each re-run the halo
    ppermutes + global psum (collectives don't CSE across regions).

    Dynamic plans replay the forward's selection from (q_l, k_view)
    (gradient-free, deterministic) and swap the packed-transposed dK/dV
    walk — a host-built artifact that cannot exist for runtime tables —
    for the scatter twin over the view."""
    engine, interpret = _resolve_engine(impl)
    sched = sp.plan.sched
    bq, bk = sp.plan.block_q, sp.plan.block_k

    def local(dout, delta, m, l, q_l, k_l, v_l):
        idx = jax.lax.axis_index(axis)
        tbl, flg, pq, pk = _shard_tables(sp, idx)
        rt = jnp.take(jnp.asarray(sp.t_row_tile), idx, axis=0)
        qbt = jnp.take(jnp.asarray(sp.t_q_blocks), idx, axis=0)
        tfl = jnp.take(jnp.asarray(sp.t_flags), idx, axis=0)
        k_view, v_view = _build_views(sp, axis, idx, k_l, v_l)
        if dyn is not None:
            tbl, flg = _dyn_select(sp, dyn, idx, q_l, k_view, tbl, flg,
                                   pq, pk, scale)
            if engine == "pallas":
                from repro.kernels.salo_backward import \
                    salo_table_backward_dq
                dq = salo_table_backward_dq(
                    dout, delta, m, l, q_l, k_view, v_view, pq, pk,
                    tbl.reshape(-1), flg.reshape(-1), sched=sched,
                    block_q=bq, block_k=bk, scale=scale,
                    interpret=interpret)
            else:
                dq = table_dq_scan(dout, delta, m, l, q_l, k_view, v_view,
                                   pq, pk, tbl, flg, sched, scale)
            dk_view, dv_view = table_dkv_scatter_scan(
                dout, delta, m, l, q_l, k_view, v_view, pq, pk, tbl, flg,
                sched, scale)
            dk_l, dv_l = _return_views(sp, axis, idx, dk_view, dv_view)
            return dq, dk_l, dv_l
        if engine == "pallas":
            from repro.kernels.salo_backward import (salo_table_backward_dq,
                                                     salo_table_backward_dkv)
            dq = salo_table_backward_dq(
                dout, delta, m, l, q_l, k_view, v_view, pq, pk,
                tbl.reshape(-1), flg.reshape(-1), sched=sched, block_q=bq,
                block_k=bk, scale=scale, interpret=interpret)
            dk_view, dv_view = salo_table_backward_dkv(
                dout, delta, m, l, q_l, k_view, v_view, pq, pk, rt,
                qbt.reshape(-1), tfl.reshape(-1), sched=sched, block_q=bq,
                block_k=bk, nkb=sp.view_tiles, scale=scale,
                interpret=interpret)
        else:
            dq = table_dq_scan(dout, delta, m, l, q_l, k_view, v_view, pq,
                               pk, tbl, flg, sched, scale)
            dk_view, dv_view = table_dkv_scan(
                dout, delta, m, l, q_l, k_view, v_view, pq, pk, rt, qbt,
                tfl, sched, scale)
        dk_l, dv_l = _return_views(sp, axis, idx, dk_view, dv_view)
        return dq, dk_l, dv_l

    return local


# ---------------------------------------------------------------------- #
# The sharded attention entry point (custom VJP over shard_map passes)
# ---------------------------------------------------------------------- #
def _sharded_forward(q, k, v, sp, mesh, axis, scale, impl, dyn=None):
    plan, sched = sp.plan, sp.plan.sched
    N = q.shape[1]
    qw = working_stream(q, sched, plan)
    kw = working_stream(k, sched, plan)
    vw = working_stream(v, sched, plan)
    fn = jax.shard_map(_make_local_fwd(sp, axis, scale, impl, dyn),
                       mesh=mesh, in_specs=(P(None, axis, None),) * 3,
                       out_specs=(P(None, axis, None), P(None, axis),
                                  P(None, axis)),
                       check_vma=False)
    out_w, m, l = fn(qw, kw, vw)
    out_w = out_w.astype(q.dtype)
    out = undo_working(out_w, sched, N)
    if sched.n_global > 0 and sched.global_rows:
        rows = _global_rows(q, k, v, sched, scale, q.dtype)
        # concatenate, NOT out.at[:, :g].set(rows): a dynamic-update-slice
        # into the seq-sharded shard_map output miscompiles on the forced-
        # host-device CPU backend (update lands at per-shard offsets).
        out = jnp.concatenate([rows, out[:, sched.n_global:]], axis=1)
    return out, (out_w, m, l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _sharded(q, k, v, sp, mesh, axis, scale, impl, dyn):
    out, _ = _sharded_forward(q, k, v, sp, mesh, axis, scale, impl, dyn)
    return out


def _sharded_fwd(q, k, v, sp, mesh, axis, scale, impl, dyn):
    out, (out_w, m, l) = _sharded_forward(q, k, v, sp, mesh, axis, scale,
                                          impl, dyn)
    return out, (q, k, v, out_w, m, l)


def _sharded_bwd(sp, mesh, axis, scale, impl, dyn, res, g):
    q, k, v, out_w, m, l = res

    # plan_backward invokes dq_engine then dkv_engine with identical
    # arguments; both answers come from ONE combined shard_map region
    # (single view exchange), stashed across the two calls.
    stash = {}

    def dq_engine(dout, delta, m_, l_, qw, kw, vw, pos):
        fn = jax.shard_map(_make_local_bwd(sp, axis, scale, impl, dyn),
                           mesh=mesh,
                           in_specs=(P(None, axis, None), P(None, axis),
                                     P(None, axis), P(None, axis),
                                     P(None, axis, None), P(None, axis, None),
                                     P(None, axis, None)),
                           out_specs=(P(None, axis, None),
                                      P(None, axis, None),
                                      P(None, axis, None)), check_vma=False)
        dq, dk, dv = fn(dout, delta, m_, l_, qw, kw, vw)
        stash["dkv"] = (dk, dv)
        return dq

    def dkv_engine(dout, delta, m_, l_, qw, kw, vw, pos):
        return stash.pop("dkv")

    return plan_backward(g, q, k, v, out_w, m, l, sp.plan, scale,
                         dq_engine, dkv_engine)


_sharded.defvjp(_sharded_fwd, _sharded_bwd)


def _auto_block(n_work: int, n_shards: int, requested: Optional[int]) -> int:
    """Largest power-of-two block <= min(128, the shard's slot count) —
    keeps pad_multiple (= n_shards * lcm of the blocks) from inflating
    n_pad far past the sequence on small shards."""
    b = 8
    while b * 2 <= min(128, max(8, n_work // n_shards)):
        b *= 2
    return min(requested, b) if requested else b


def sharded_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      pattern: HybridSparsePattern, mesh: Mesh,
                      axis: str = "data", *,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      scale: Optional[float] = None,
                      impl: Optional[str] = None,
                      dynamic=None) -> jax.Array:
    """Sequence-parallel hybrid sparse attention over ``mesh[axis]``.

    q/k/v: (B, N, D) with N sharded over ``axis`` (B typically folds
    batch*heads). Supports everything the single-device plan supports —
    dilation > 1 (the stride permutation runs on the global arrays before
    the shard_map region; XLA lowers it to an all-to-all, a one-off
    activation-sized reshuffle), 2-D ViL bands, reordered global tiles,
    causal and bidirectional windows (halos on both sides), and windows
    wider than a shard (multi-hop halo distances). Differentiable: the
    backward is the shared ``plan_backward`` contract with shard-mapped
    dQ/dK/dV passes and reverse-ppermute gradient returns.

    ``impl`` picks the shard-local engine: "blockwise" (XLA scan twin),
    "pallas"/"pallas_interpret" (the fused scalar-prefetch kernels via
    their table-driven entry points; compiled mode runs on a TPU only).

    ``dynamic`` (a :class:`repro.core.dynamic.DynamicConfig`) turns on
    content-based selection: each shard top-k's its own candidate steps
    over the exchanged [local | halo | global] view, so the collective
    schedule stays static while the executed tiles are data-dependent.
    """
    from repro.core.attention import default_impl
    B, N, D = q.shape
    impl = impl or default_impl()
    n_shards = int(mesh.shape[axis])
    sched = schedule(pattern, N)
    bq = _auto_block(sched.n_work, n_shards, block_q)
    bk = _auto_block(sched.n_work, n_shards, block_k)
    plan = build_plan(sched, bq, bk, n_shards * math.lcm(bq, bk))
    sp = shard_plan(plan, n_shards)
    scale_ = (D ** -0.5) if scale is None else scale
    if dynamic is not None:
        from repro.core.dynamic import (_account_build, _resolve_window,
                                        check_keep)
        lw = _resolve_window(dynamic, bq, bk)
        check_keep(min(int(dynamic.keep), sp.tables.shape[2]),
                   _sharded_always_keep(sp, lw), what="sharded plan")
        _account_build(sp.flags.reshape(-1, sp.tables.shape[2]),
                       min(int(dynamic.keep), sp.tables.shape[2]))
    return _sharded(q, k, v, sp, mesh, axis, scale_, impl, dynamic)
