"""Plan-driven blockwise attention in pure JAX — SALO's schedule on XLA.

This is the *algorithmic twin* of the Pallas kernel: it walks the SAME
:class:`repro.core.scheduler.ExecutionPlan` step tables with the SAME
per-step masks (``plan.step_mask``), folded through the same renormalized
online-softmax state. It exists because

1. training needs a CPU/XLA path (the backward here is the plan-driven
   custom VJP below, not autodiff through the scan),
2. the CPU-only dry-run must lower something honest for roofline analysis
   (Pallas TPU kernels cannot be lowered by the CPU backend).

One ``lax.scan`` over ``plan.max_steps`` executes every band AND the global
column — overlapping KV tiles deduplicated to one visit, no per-band passes,
no separate global partial. Global rows (global queries attend everything)
are a dense g-row epilogue shared with the kernel wrapper.

**Backward contract (shared with kernels/ops.py).** Both engines save the
forward's already-computed partial triple ``(out, m, l)`` as residuals and
recompute the attention probabilities ``p = exp(s - m) / l`` tile-by-tile in
the backward — flash-style, no O(n^2) residuals, no forward re-run. The dQ
pass replays the forward tables; the dK/dV pass walks
``plan.transposed()`` (the exact adjoint regrouping of the same
deduplicated visits). :func:`plan_backward` owns the host-step adjoints
(global-rows epilogue, reorder, pad, the ``delta = sum(dout * out)``
precompute) and is parameterized over the two gradient passes, so the
Pallas kernels (kernels/salo_backward.py) and the scan engines here
(:func:`bwd_dq_scan`, :func:`bwd_dkv_scan`) execute ONE contract.

Shapes: q, k, v are ``(B, N, D)`` where ``B`` folds batch*heads. The public
model-facing API lives in :mod:`repro.core.attention`.

Complexity: O(N * deduped_tiles_per_block * block_k * D) — linear in N for
banded patterns, the paper's claim, and strictly fewer tiles than the
per-band walk whenever bands overlap (ViL).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import renorm
from repro.core.scheduler import (BIG, STEP_GLOBAL, STEP_WINDOW,
                                  BandSchedule, ExecutionPlan, _round_up,
                                  causal_step_mask, schedule)
from repro.core.patterns import HybridSparsePattern


def _dot(a, b):
    return jnp.einsum("...qd,...kd->...qk", a, b,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------- #
# Working-stream host steps (shared by both engines, forward AND backward)
# ---------------------------------------------------------------------- #
def working_stream(x: jax.Array, sched: BandSchedule,
                   plan: ExecutionPlan) -> jax.Array:
    """Original order -> working layout: dilation reorder + pad to n_pad.

    ``x``: (B, N, ...) along axis 1. The reorder is a permutation, so this
    transform is also the ADJOINT of the forward's output un-reordering —
    the same function maps inputs forward and output-cotangents backward.
    """
    N = x.shape[1]
    if sched.reordered:
        perm = jnp.asarray(sched.perm)
        take = jnp.clip(perm, 0, N - 1)
        valid = (perm < N).reshape((1, -1) + (1,) * (x.ndim - 2))
        x = jnp.where(valid, jnp.take(x, take, axis=1), 0)
    pad = plan.n_pad - x.shape[1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x


def undo_working(x_w: jax.Array, sched: BandSchedule, n: int) -> jax.Array:
    """Working layout -> original order (inverse of :func:`working_stream`)."""
    if sched.reordered:
        return jnp.take(x_w, jnp.asarray(sched.inverse_perm()), axis=1)
    return x_w[:, :n]


def _plan_partial(state: renorm.PartialState, q_blk, k_pad, v_pad, pos_pad,
                  plan: ExecutionPlan, scale: float) -> renorm.PartialState:
    """Fold the WHOLE plan (all bands + global column) into the state.

    q_blk: (B, nq, Bq, D); k_pad/v_pad: (B, n_pad, D); pos_pad: (n_pad,).
    state: PartialState over (B, nq, Bq). One scan step = one table column:
    every query block gathers its step-``s`` KV tile and applies the
    flag-gated union mask. Padding steps (flags == 0) mask to nothing.
    """
    B, nq, Bq, D = q_blk.shape
    bk = plan.block_k
    nkb = plan.nkb
    pos_q = pos_pad.reshape(nq, Bq)

    # Fast path (single band, no global, Bq == Bk): the plan's tile walk is
    # the affine shift ``i + c0 + s`` — a CONSTANT shift per step — so the
    # banded walk is a sliced view of the padded KV stream, not a gather.
    # No per-block index materialization; XLA fuses the slice into the
    # matmul operand (measured on the gemma/prefill_32k dry-run cell;
    # see benchmarks/roofline_report.py). Out-of-range
    # tiles carry PAD_SENTINEL positions and mask to nothing.
    sched = plan.sched
    if len(sched.bands) == 1 and sched.n_global == 0 and Bq == bk:
        import math as _math
        band = sched.bands[0]
        steps = band.kv_steps(Bq, bk)
        c0 = _math.floor(band.lo / bk)
        c1 = c0 + steps - 1
        lpad = max(0, -c0) * bk
        rpad = max(0, c1) * bk
        n_pad = k_pad.shape[1]
        k_w = jnp.pad(k_pad, ((0, 0), (lpad, rpad), (0, 0)))
        v_w = jnp.pad(v_pad, ((0, 0), (lpad, rpad), (0, 0)))
        pos_w = jnp.pad(pos_pad, (lpad, rpad), constant_values=BIG)

        def body(st, s):
            start = (c0 + s) * bk + lpad     # >= 0 by construction
            k_blk = jax.lax.dynamic_slice_in_dim(
                k_w, start, n_pad, axis=1).reshape(B, nq, bk, D)
            v_blk = jax.lax.dynamic_slice_in_dim(
                v_w, start, n_pad, axis=1).reshape(B, nq, bk, D)
            pos_k = jax.lax.dynamic_slice_in_dim(
                pos_w, start, n_pad).reshape(nq, bk)
            scores = _dot(q_blk, k_blk) * scale
            mask = plan.step_mask(pos_q[:, :, None], pos_k[:, None, :],
                                  STEP_WINDOW)
            return renorm.update(st, scores, v_blk, mask[None]), ()

        state, _ = jax.lax.scan(body, state,
                                jnp.arange(steps, dtype=jnp.int32))
        return state

    # General path: gather each step's KV tile by the plan table — the
    # same scan body as table_attention_scan (ONE copy, _table_fold).
    return _table_fold(state, q_blk, k_pad.reshape(B, nkb, bk, D),
                       v_pad.reshape(B, nkb, bk, D), pos_q,
                       pos_pad.reshape(nkb, bk),
                       jnp.asarray(plan.kv_blocks),
                       jnp.asarray(plan.flags), plan.sched, scale)


def _table_fold(state, q_blk, k_r, v_r, pos_q, pos_k, kv_blocks, flags,
                sched: BandSchedule, scale: float):
    """Fold step tables into a renorm state: one lax.scan over the table
    width, gathering each step's KV tile — THE table walk shared by the
    plan-driven general path and the (sharded) table-driven entry point.

    q_blk: (B, nq, Bq, D); k_r/v_r: (B, nkb, Bk, D); pos_q: (nq, Bq);
    pos_k: (nkb, Bk); kv_blocks/flags: (nq, W) — table values may be
    traced (per-device slices under shard_map).
    """
    def body(st, s):
        blk = jax.lax.dynamic_index_in_dim(kv_blocks, s, axis=1,
                                           keepdims=False)      # (nq,)
        fl = jax.lax.dynamic_index_in_dim(flags, s, axis=1,
                                          keepdims=False)       # (nq,)
        k_blk = jnp.take(k_r, blk, axis=1)                      # (B,nq,Bk,D)
        v_blk = jnp.take(v_r, blk, axis=1)
        pos_kb = jnp.take(pos_k, blk, axis=0)                   # (nq, Bk)
        scores = _dot(q_blk, k_blk) * scale
        mask = sched.step_mask(pos_q[:, :, None], pos_kb[:, None, :],
                               fl[:, None, None])
        return renorm.update(st, scores, v_blk, mask[None]), ()

    state, _ = jax.lax.scan(body, state,
                            jnp.arange(kv_blocks.shape[1], dtype=jnp.int32))
    return state


def table_attention_scan(q, k, v, pos_q, pos_k, kv_blocks, flags,
                         sched: BandSchedule, scale: float):
    """Generic table-driven forward on XLA: one ``lax.scan`` over step
    tables whose *values* may be traced (the sharded per-device tables are
    selected by ``axis_index`` at run time) and whose q/KV sides may have
    different lengths (the sharded local view).

    q: (B, nq*Bq, D); k/v: (B, nkb*Bk, D); pos_q: (nq, Bq); pos_k:
    (nkb, Bk) ORIGINAL positions; kv_blocks/flags: (nq, W). Returns the
    normalized partial triple ``(out, m, l)`` — the same contract as
    :func:`repro.kernels.salo_attention.salo_plan_attention`.
    """
    B, nQ, D = q.shape
    nq, _W = kv_blocks.shape
    bq = nQ // nq
    nkb, bk = pos_k.shape
    st = renorm.empty_state((B, nq, bq), D)
    st = _table_fold(st, q.reshape(B, nq, bq, D),
                     k.reshape(B, nkb, bk, D), v.reshape(B, nkb, bk, D),
                     pos_q, pos_k, kv_blocks, flags, sched, scale)
    out = renorm.finalize(st, q.dtype).reshape(B, nQ, D)
    return out, st.m.reshape(B, nQ), st.l.reshape(B, nQ)


def _global_rows(q_orig, k_orig, v_orig, sched: BandSchedule, scale: float,
                 out_dtype):
    """Global-row pass: the first n_global queries attend ALL keys (original
    order) — SALO's global PE row. Returns (B, g, D)."""
    g = sched.n_global
    n = sched.n
    qg = q_orig[:, :g]
    scores = _dot(qg, k_orig[:, :n]) * scale      # (B, g, n)
    if sched.causal:
        mask = (jnp.arange(n)[None, :] <= jnp.arange(g)[:, None])[None]
        scores = jnp.where(mask, scores, renorm.NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p,
                      v_orig[:, :n].astype(p.dtype)).astype(out_dtype)


def _blockwise_forward(q, k, v, pattern, block_q, block_k, scale,
                       return_state=False):
    """Plan walk + host steps. Returns ``(out, (out_w, m, l))`` — the
    working-space partial triple doubles as the backward's residuals —
    or the raw PartialState when ``return_state``."""
    B, N, D = q.shape
    scale = (D ** -0.5) if scale is None else scale
    sched = schedule(pattern, N)
    plan = sched.plan(block_q, block_k)
    out_dtype = q.dtype

    # --- data reordering (dilation) + padding to the tile grid ---------- #
    qw = working_stream(q, sched, plan)
    kw = working_stream(k, sched, plan)
    vw = working_stream(v, sched, plan)
    pos = jnp.asarray(plan.positions_padded())

    nq = plan.nq
    q_blk = qw.reshape(B, nq, block_q, D)

    state = renorm.empty_state((B, nq, block_q), D)
    state = _plan_partial(state, q_blk, kw, vw, pos, plan, scale)

    if return_state:
        return state

    out_w = renorm.finalize(state, out_dtype).reshape(B, plan.n_pad, D)
    m = state.m.reshape(B, plan.n_pad)
    l = state.l.reshape(B, plan.n_pad)

    # --- undo reordering / padding -------------------------------------- #
    out = undo_working(out_w, sched, N)

    # --- global rows (paper's global PE row) ----------------------------- #
    if sched.n_global > 0 and sched.global_rows:
        rows = _global_rows(q, k, v, sched, scale, out_dtype)
        out = out.at[:, : sched.n_global].set(rows)
    return out, (out_w, m, l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _blockwise(q, k, v, pattern, block_q, block_k, scale):
    out, _ = _blockwise_forward(q, k, v, pattern, block_q, block_k, scale)
    return out


def _blockwise_fwd(q, k, v, pattern, block_q, block_k, scale):
    out, (out_w, m, l) = _blockwise_forward(q, k, v, pattern, block_q,
                                            block_k, scale)
    return out, (q, k, v, out_w, m, l)


def _blockwise_bwd(pattern, block_q, block_k, scale, res, g):
    q, k, v, out_w, m, l = res
    B, N, D = q.shape
    scale_ = (D ** -0.5) if scale is None else scale
    plan = schedule(pattern, N).plan(block_q, block_k)
    return plan_backward(
        g, q, k, v, out_w, m, l, plan, scale_,
        functools.partial(bwd_dq_scan, plan=plan, scale=scale_),
        functools.partial(bwd_dkv_scan, plan=plan, scale=scale_))


_blockwise.defvjp(_blockwise_fwd, _blockwise_bwd)


@functools.partial(jax.jit, static_argnames=("pattern", "block_q", "block_k",
                                             "scale", "return_state"))
def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        pattern: HybridSparsePattern, *,
                        block_q: int = 128, block_k: int = 128,
                        scale: Optional[float] = None,
                        return_state: bool = False):
    """Hybrid sparse attention via the SALO ExecutionPlan. q,k,v: (B, N, D).

    Differentiating through this uses the plan-driven custom VJP (dQ over
    the forward tables, dK/dV over the transposed tables, ``p`` recomputed
    from the saved ``(out, m, l)``) — NOT autodiff through the scan, which
    would re-run the forward sequentially and materialize per-step
    residuals. ``return_state=True`` returns the raw PartialState (for
    cross-device merges) and bypasses the custom VJP.
    """
    if return_state:
        return _blockwise_forward(q, k, v, pattern, block_q, block_k, scale,
                                  return_state=True)
    return _blockwise(q, k, v, pattern, block_q, block_k, scale)


# ---------------------------------------------------------------------- #
# The backward contract: shared host steps + the XLA gradient engines
# ---------------------------------------------------------------------- #
def p_from_stats(scores, mask, m, l):
    """Recompute normalized attention probabilities from saved row stats:
    ``p = exp(s - m) / l`` where ``s`` is the masked scaled score.

    Empty rows — every step masked; the forward emitted ``(out=0,
    m=NEG_INF, l=0)``, see :class:`repro.core.renorm.PartialState` — take
    the guarded branch (shift 0, l 1) and end at exactly ``p == 0`` via the
    mask, so their gradients vanish identically in every engine.
    """
    l_safe = jnp.where(l == 0.0, 1.0, l)
    shift = jnp.where(m <= renorm.NEG_INF / 2, 0.0, m)
    p = jnp.exp(scores - shift[..., None]) / l_safe[..., None]
    return jnp.where(mask, p, 0.0)


def table_dq_scan(dout, delta, m, l, q, k, v, pos_q, pos_k, kv_blocks,
                  flags, sched: BandSchedule, scale: float) -> jax.Array:
    """dQ pass: one scan over (possibly dynamic) FORWARD step tables.

    ds = p * (dout.v - delta);  dq_i += scale * sum_j ds_ij k_j

    Generic over table *values* and over asymmetric q/KV lengths (the
    sharded local view streams ``nkb_view`` tiles past ``nq_local`` query
    blocks): q-side arrays (dout/delta/m/l/q) are (B, nq*Bq, ...), KV-side
    (k/v) are (B, nkb*Bk, D); pos_q: (nq, Bq); pos_k: (nkb, Bk);
    kv_blocks/flags: (nq, W). Returns (B, nq*Bq, D) f32.
    """
    B, nQ, D = q.shape
    nq, W = kv_blocks.shape
    bq = nQ // nq
    nkb, bk = pos_k.shape
    q_blk = q.reshape(B, nq, bq, D)
    do_blk = dout.reshape(B, nq, bq, D)
    m_blk = m.reshape(B, nq, bq)
    l_blk = l.reshape(B, nq, bq)
    dl_blk = delta.reshape(B, nq, bq)
    k_r = k.reshape(B, nkb, bk, D)
    v_r = v.reshape(B, nkb, bk, D)

    def body(dq, s):
        blk = jax.lax.dynamic_index_in_dim(kv_blocks, s, 1, keepdims=False)
        fl = jax.lax.dynamic_index_in_dim(flags, s, 1, keepdims=False)
        k_b = jnp.take(k_r, blk, axis=1)                       # (B,nq,Bk,D)
        v_b = jnp.take(v_r, blk, axis=1)
        pos_kb = jnp.take(pos_k, blk, axis=0)                  # (nq, Bk)
        scores = _dot(q_blk, k_b) * scale
        mask = sched.step_mask(pos_q[:, :, None], pos_kb[:, None, :],
                               fl[:, None, None])[None]
        p = p_from_stats(scores, mask, m_blk, l_blk)
        ds = p * (_dot(do_blk, v_b) - dl_blk[..., None])
        dq = dq + jnp.einsum("bnqk,bnkd->bnqd", ds,
                             k_b.astype(jnp.float32)) * scale
        return dq, ()

    dq0 = jnp.zeros((B, nq, bq, D), jnp.float32)
    dq, _ = jax.lax.scan(body, dq0, jnp.arange(W, dtype=jnp.int32))
    return dq.reshape(B, nQ, D)


def bwd_dq_scan(dout, delta, m, l, qw, kw, vw, pos, *,
                plan: ExecutionPlan, scale: float) -> jax.Array:
    """Plan-driven dQ (the single-device engine): replay the forward
    tables. All arrays working-space padded; returns (B, n_pad, D) f32."""
    pos_q = pos.reshape(plan.nq, plan.block_q)
    pos_k = pos.reshape(plan.nkb, plan.block_k)
    return table_dq_scan(dout, delta, m, l, qw, kw, vw, pos_q, pos_k,
                         jnp.asarray(plan.kv_blocks),
                         jnp.asarray(plan.flags), plan.sched, scale)


def table_dkv_scan(dout, delta, m, l, q, k, v, pos_q, pos_k, row_tile,
                   q_blocks, flags, sched: BandSchedule, scale: float):
    """dK/dV pass over PACKED transposed tables: each packed row keeps its
    owner KV tile (``row_tile``) resident while its slice of visiting query
    blocks streams past; per-row partials are scatter-added per owner tile
    (rows split from one ragged transposed row recombine here).

    dv_j += sum_i p_ij dout_i;  dk_j += scale * sum_i ds_ij q_i

    Shapes as :func:`table_dq_scan`, plus row_tile: (R,), q_blocks/flags:
    (R, W). Returns ``(dk, dv)`` both (B, nkb*Bk, D) f32.
    """
    B, nQ, D = q.shape
    nq, bq = pos_q.shape
    nkb, bk = pos_k.shape
    R, W = q_blocks.shape
    q_r = q.reshape(B, nq, bq, D)
    do_r = dout.reshape(B, nq, bq, D)
    m_r = m.reshape(B, nq, bq)
    l_r = l.reshape(B, nq, bq)
    dl_r = delta.reshape(B, nq, bq)
    k_rt = jnp.take(k.reshape(B, nkb, bk, D), row_tile, axis=1)  # (B,R,Bk,D)
    v_rt = jnp.take(v.reshape(B, nkb, bk, D), row_tile, axis=1)
    pos_kr = jnp.take(pos_k, row_tile, axis=0)                   # (R, Bk)

    def body(carry, s):
        dk, dv = carry
        qb = jax.lax.dynamic_index_in_dim(q_blocks, s, 1, keepdims=False)
        fl = jax.lax.dynamic_index_in_dim(flags, s, 1, keepdims=False)
        q_b = jnp.take(q_r, qb, axis=1)                        # (B,R,Bq,D)
        do_b = jnp.take(do_r, qb, axis=1)
        m_b = jnp.take(m_r, qb, axis=1)
        l_b = jnp.take(l_r, qb, axis=1)
        dl_b = jnp.take(dl_r, qb, axis=1)
        pos_qb = jnp.take(pos_q, qb, axis=0)                   # (R, Bq)
        scores = _dot(q_b, k_rt) * scale
        mask = sched.step_mask(pos_qb[:, :, None], pos_kr[:, None, :],
                               fl[:, None, None])[None]
        p = p_from_stats(scores, mask, m_b, l_b)
        ds = p * (_dot(do_b, v_rt) - dl_b[..., None])
        dv = dv + jnp.einsum("bnqk,bnqd->bnkd", p, do_b)
        dk = dk + jnp.einsum("bnqk,bnqd->bnkd", ds,
                             q_b.astype(jnp.float32)) * scale
        return (dk, dv), ()

    z = jnp.zeros((B, R, bk, D), jnp.float32)
    (dk_r, dv_r), _ = jax.lax.scan(body, (z, z),
                                   jnp.arange(W, dtype=jnp.int32))
    zt = jnp.zeros((B, nkb, bk, D), jnp.float32)
    dk = zt.at[:, row_tile].add(dk_r).reshape(B, nkb * bk, D)
    dv = zt.at[:, row_tile].add(dv_r).reshape(B, nkb * bk, D)
    return dk, dv


def table_dkv_scatter_scan(dout, delta, m, l, q, k, v, pos_q, pos_k,
                           kv_blocks, flags, sched: BandSchedule,
                           scale: float):
    """dK/dV pass over (possibly runtime-valued) FORWARD step tables.

    The static engines walk ``plan.transposed_packed()`` — a host-built
    regrouping that cannot exist for tables computed on device
    (:mod:`repro.core.dynamic`, per-shard dynamic selection). This twin
    walks the forward table width instead: at step ``s`` every query block
    computes its (dk, dv) contribution to its step-``s`` tile, scatter-added
    into the tile's slot (``.at[].add`` — duplicate tile indices across
    query blocks accumulate, the runtime mirror of the transposed
    regrouping). Same visits, same masks, same p recompute; padding steps
    (flags 0) mask to nothing and add zeros to tile 0.

    Shapes as :func:`table_dq_scan`. Returns ``(dk, dv)``
    (B, nkb*Bk, D) f32.
    """
    B, nQ, D = q.shape
    nq, W = kv_blocks.shape
    bq = nQ // nq
    nkb, bk = pos_k.shape
    q_blk = q.reshape(B, nq, bq, D)
    do_blk = dout.reshape(B, nq, bq, D)
    m_blk = m.reshape(B, nq, bq)
    l_blk = l.reshape(B, nq, bq)
    dl_blk = delta.reshape(B, nq, bq)
    k_r = k.reshape(B, nkb, bk, D)
    v_r = v.reshape(B, nkb, bk, D)

    def body(carry, s):
        dk, dv = carry
        blk = jax.lax.dynamic_index_in_dim(kv_blocks, s, 1, keepdims=False)
        fl = jax.lax.dynamic_index_in_dim(flags, s, 1, keepdims=False)
        k_b = jnp.take(k_r, blk, axis=1)                       # (B,nq,Bk,D)
        v_b = jnp.take(v_r, blk, axis=1)
        pos_kb = jnp.take(pos_k, blk, axis=0)                  # (nq, Bk)
        scores = _dot(q_blk, k_b) * scale
        mask = sched.step_mask(pos_q[:, :, None], pos_kb[:, None, :],
                               fl[:, None, None])[None]
        p = p_from_stats(scores, mask, m_blk, l_blk)
        ds = p * (_dot(do_blk, v_b) - dl_blk[..., None])
        dv = dv.at[:, blk].add(jnp.einsum("bnqk,bnqd->bnkd", p, do_blk))
        dk = dk.at[:, blk].add(jnp.einsum("bnqk,bnqd->bnkd", ds,
                                          q_blk.astype(jnp.float32)) * scale)
        return (dk, dv), ()

    z = jnp.zeros((B, nkb, bk, D), jnp.float32)
    (dk, dv), _ = jax.lax.scan(body, (z, z), jnp.arange(W, dtype=jnp.int32))
    return dk.reshape(B, nkb * bk, D), dv.reshape(B, nkb * bk, D)


def bwd_dkv_scan(dout, delta, m, l, qw, kw, vw, pos, *,
                 plan: ExecutionPlan, scale: float):
    """Plan-driven dK/dV (the single-device engine): walk
    ``plan.transposed_packed()`` — the exact adjoint regrouping of the
    forward's deduplicated visits, packed so global-column tiles' ragged
    rows don't inflate everyone's padding."""
    pk = plan.transposed_packed()
    pos_q = pos.reshape(plan.nq, plan.block_q)
    pos_k = pos.reshape(plan.nkb, plan.block_k)
    return table_dkv_scan(dout, delta, m, l, qw, kw, vw, pos_q, pos_k,
                          jnp.asarray(pk.row_tile),
                          jnp.asarray(pk.q_blocks), jnp.asarray(pk.flags),
                          plan.sched, scale)


def plan_backward(g, q, k, v, out_w, m, l, plan: ExecutionPlan, scale: float,
                  dq_engine, dkv_engine):
    """THE backward contract of both engines: host-step adjoints around two
    plan-walking gradient passes.

    ``kernels/ops.py`` passes the Pallas launchers (kernels/salo_backward),
    the blockwise custom VJP passes :func:`bwd_dq_scan`/:func:`bwd_dkv_scan`
    — everything else (global-rows epilogue VJP, cotangent reorder/pad, the
    ``delta`` precompute, gradient un-reordering) is this one code path.

    Engines take ``(dout, delta, m, l, qw, kw, vw, pos)`` in the padded
    working layout and return working-layout gradients.
    """
    sched = plan.sched
    B, N, D = q.shape
    # 1. Global-rows epilogue: the forward overwrote rows [:g] with the
    #    dense g-row pass on ORIGINAL-order tensors; its VJP is dense but
    #    tiny (g rows), and those rows' main-path cotangent is zeroed.
    if sched.n_global > 0 and sched.global_rows:
        ng = sched.n_global
        _, rows_vjp = jax.vjp(
            lambda q_, k_, v_: _global_rows(q_, k_, v_, sched, scale,
                                            g.dtype), q, k, v)
        dq_rows, dk_rows, dv_rows = rows_vjp(g[:, :ng])
        # concatenate, NOT g.at[:, :ng].set(0): under sequence sharding the
        # dynamic-update-slice is partitioned per shard and zeroes the
        # first ng rows of EVERY shard (the forward's epilogue avoids it
        # the same way, dist/sharded_plan.py).
        g = jnp.concatenate([jnp.zeros_like(g[:, :ng]), g[:, ng:]], axis=1)
    else:
        dq_rows = dk_rows = dv_rows = None
    # 2. The output reorder is a permutation: the cotangent takes the SAME
    #    working-stream transform as the inputs did.
    dout = working_stream(g, sched, plan).astype(jnp.float32)
    qw = working_stream(q, sched, plan)
    kw = working_stream(k, sched, plan)
    vw = working_stream(v, sched, plan)
    pos = jnp.asarray(plan.positions_padded())
    # 3. delta = rowwise dout . out — the flash-backward precompute.
    delta = jnp.sum(dout * out_w.astype(jnp.float32), axis=-1)
    # 4. The two plan walks.
    dq_w = dq_engine(dout, delta, m, l, qw, kw, vw, pos)
    dk_w, dv_w = dkv_engine(dout, delta, m, l, qw, kw, vw, pos)
    # 5. Back to original order (+ the epilogue contributions).
    dq = undo_working(dq_w, sched, N)
    dk = undo_working(dk_w, sched, N)
    dv = undo_working(dv_w, sched, N)
    if dq_rows is not None:
        dq = dq + dq_rows
        dk = dk + dk_rows
        dv = dv + dv_rows
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------- #
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     t: jax.Array, pattern: HybridSparsePattern, *,
                     scale: Optional[float] = None,
                     cache_positions: Optional[jax.Array] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None) -> jax.Array:
    """One-token decode against a KV cache (serve_step path) — RAGGED aware.

    q: (B, 1, D); caches: (B, S, D); ``t`` = current absolute position:
    a scalar (lockstep batch) OR a (B,) vector — one position per request,
    the continuous-batching decode twin. ``cache_positions``: (S,) or
    (B, S) absolute position per cache slot (defaults to arange — the dense
    baseline cache); ring/paged caches pass their slot->position maps here
    and everything still works because masks are position-based
    (``scheduler.causal_step_mask``).

    int8 caches pass per-slot dequant scales ``k_scale``/``v_scale``
    ((S,) or (B, S) f32 — a paged caller expands its per-page scales
    page->slots): slots are dequantized to ``q.dtype`` before the score
    matmul, mirroring the in-kernel dequant of the Pallas paged path.
    """
    B, S, D = k_cache.shape
    scale = (D ** -0.5) if scale is None else scale
    if k_scale is not None:
        sk = jnp.broadcast_to(jnp.asarray(k_scale, jnp.float32), (B, S))
        sv = jnp.broadcast_to(jnp.asarray(v_scale, jnp.float32), (B, S))
        k_cache = (k_cache.astype(jnp.float32)
                   * sk[..., None]).astype(q.dtype)
        v_cache = (v_cache.astype(jnp.float32)
                   * sv[..., None]).astype(q.dtype)
    pos_k = (jnp.arange(S, dtype=jnp.int32) if cache_positions is None
             else cache_positions.astype(jnp.int32))
    pos_k = jnp.broadcast_to(pos_k, (B, S))
    pos_i = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))

    m = causal_step_mask(pattern, pos_i[:, None], pos_k,
                         STEP_WINDOW | STEP_GLOBAL)           # (B, S)
    scores = _dot(q, k_cache) * scale            # (B, 1, S)
    scores = jnp.where(m[:, None, :], scores, renorm.NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqs,bsd->bqd", w,
                      v_cache.astype(w.dtype)).astype(q.dtype)


def chunk_attention(q: jax.Array, k_view: jax.Array, v_view: jax.Array,
                    pos_q: jax.Array, pos_k: jax.Array,
                    kv_blocks: jax.Array, flags: jax.Array,
                    pattern: HybridSparsePattern, *,
                    scale: Optional[float] = None,
                    return_state: bool = False):
    """Plan-driven chunked-prefill attention: ONE table-driven pass.

    q: (B, Cp, D) chunk queries; k_view/v_view: (B, Vp, D) the request's
    paged KV view (sinks + ring) with the fresh chunk appended; pos_q:
    (B, Cp) and pos_k: (B, Vp) ORIGINAL positions (``BIG`` = empty/pad);
    kv_blocks/flags: (nq, W) ChunkPlan step tables (dynamic arrays — the
    same compiled step serves every chunk of a request). One ``lax.scan``
    over W table columns folds the whole causal hybrid pattern through the
    renormalized online softmax — the serving twin of ``_plan_partial``.

    ``return_state=True`` additionally returns the finalized partial triple
    ``(out, m, l)`` with m/l (B, Cp) — what a sequence shard feeds the
    cross-shard masked-psum merge (a chunk row whose every step is masked
    on this shard carries the ``(0, NEG_INF, 0)`` identity).
    """
    B, Cp, D = q.shape
    nq, W = kv_blocks.shape
    block = Cp // nq
    Vp = k_view.shape[1]
    nkb = Vp // block
    q_blk = q.reshape(B, nq, block, D)
    k_r = k_view.reshape(B, nkb, block, D)
    v_r = v_view.reshape(B, nkb, block, D)
    pos_qb = pos_q.reshape(B, nq, block)
    pos_kr = pos_k.reshape(B, nkb, block)
    scale_ = (D ** -0.5) if scale is None else scale

    def body(st, s):
        blk = jax.lax.dynamic_index_in_dim(kv_blocks, s, axis=1,
                                           keepdims=False)     # (nq,)
        fl = jax.lax.dynamic_index_in_dim(flags, s, axis=1,
                                          keepdims=False)      # (nq,)
        k_blk = jnp.take(k_r, blk, axis=1)                     # (B,nq,Bk,D)
        v_blk = jnp.take(v_r, blk, axis=1)
        pos_kb = jnp.take(pos_kr, blk, axis=1)                 # (B,nq,Bk)
        scores = _dot(q_blk, k_blk) * scale_
        mask = causal_step_mask(pattern, pos_qb[:, :, :, None],
                                pos_kb[:, :, None, :],
                                fl[None, :, None, None])
        return renorm.update(st, scores, v_blk, mask), ()

    state = renorm.empty_state((B, nq, block), D)
    state, _ = jax.lax.scan(body, state, jnp.arange(W, dtype=jnp.int32))
    if return_state:
        # f32 partial: the cross-shard merge rounds to the compute dtype
        # once, AFTER combining (single-device round-once numerics)
        return (renorm.finalize(state).reshape(B, Cp, D),
                state.m.reshape(B, Cp), state.l.reshape(B, Cp))
    return renorm.finalize(state, q.dtype).reshape(B, Cp, D)
