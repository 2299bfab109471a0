"""Fused table-driven backward: flash-style dQ and dK/dV Pallas kernels.

The gradient counterpart of :mod:`repro.kernels.salo_attention` — SALO's
data-scheduler insight applied symmetrically to training. Exactly TWO
scalar-prefetch launches per backward, both recomputing the attention
probabilities from the forward's saved partial triple ``(out, m, l)``
(``p = exp(s - m) / l``) instead of re-running the forward:

* **dQ kernel** — replays the FORWARD plan (grid ``(B, nq, max_steps)``):
  the query tile, its cotangent and row stats stay resident while the
  plan's deduplicated KV tiles stream past, accumulating
  ``dq_i += scale * sum_j ds_ij k_j`` with ``ds = p * (dout.v - delta)``.
* **dK/dV kernel** — walks the PACKED transposed plan
  (:meth:`ExecutionPlan.transposed_packed`, grid ``(B, n_rows, width)``):
  each packed row keeps its owner KV tile resident while its slice of
  visiting query blocks streams past, accumulating
  ``dv_j += sum_i p_ij dout_i`` and ``dk_j += scale * sum_i ds_ij q_i``;
  per-row partials are scatter-added per owner tile afterwards. The
  transposed tables are the exact adjoint regrouping of the forward's
  deduplicated visits — same total tiles, no extra work — and packing
  keeps global-column patterns (whose global KV tile is visited by every
  query block) from padding every other row to that ragged width.

The ``delta = sum(dout * out)`` rowwise precompute and every host-step
adjoint (global rows, reorder, pad) live in
:func:`repro.core.blockwise.plan_backward` — ONE backward contract shared
with the XLA scan engines; these kernels are its Pallas instantiation
(wired up in :mod:`repro.kernels.ops`).

Masking/padding follow the forward contract: per-step flags gate the union
mask, ``flags == 0`` steps (table padding) mask to nothing and leave the
accumulators untouched, and empty rows (``l == 0``, ``m == NEG_INF`` —
see :class:`repro.core.renorm.PartialState`) produce exactly zero
gradients via the guarded ``p`` recompute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.renorm import NEG_INF
from repro.core.scheduler import BandSchedule, ExecutionPlan

LANES = 128  # TPU vector lane count; dQ's stat columns are lane-replicated


def _p_ds(scores, mask, m_row, l_row, dp, delta):
    """Recomputed probabilities + score gradient (the in-kernel twin of
    ``core.blockwise.p_from_stats``). The stats arrive already shaped to
    broadcast against ``scores`` (a (Bq, 1) column or a (1, Bq) row).
    Guarded so empty rows (l == 0, m == NEG_INF) contribute exactly zero."""
    l_safe = jnp.where(l_row == 0.0, 1.0, l_row)
    shift = jnp.where(m_row <= NEG_INF / 2, 0.0, m_row)
    p = jnp.exp(scores - shift) / l_safe
    p = jnp.where(mask, p, 0.0)
    ds = p * (dp - delta)
    return p, ds


def _dq_kernel(kvt_ref, flg_ref,                                # prefetch
               pos_q_ref, pos_k_ref, q_ref, k_ref, v_ref,       # inputs
               do_ref, m_ref, l_ref, delta_ref,
               dq_ref,                                          # output
               acc_ref, m_col, l_col, d_col,                    # scratch
               *, sched: BandSchedule, steps: int, scale: float):
    i = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the resident query block's (1, Bq) stat rows -> lane-replicated
        # (Bq, LANES) columns, once per query block
        for row_ref, col_ref in ((m_ref, m_col), (l_ref, l_col),
                                 (delta_ref, d_col)):
            col_ref[...] = jnp.broadcast_to(row_ref[0, 0],
                                            col_ref.shape[::-1]).T

    q = q_ref[0]                                     # (Bq, D)
    k = k_ref[0]                                     # (Bk, D)
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)               # (Bq, D)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (Bq, Bk)

    fl = flg_ref[i * steps + s]
    mask = sched.step_mask(pos_q_ref[0], pos_k_ref[0], fl)
    dp = jax.lax.dot_general(
        do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Bq, Bk)
    _, ds = _p_ds(scores, mask, m_col[:, :1], l_col[:, :1], dp,
                  d_col[:, :1])

    acc_ref[...] += jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (Bq, D)

    @pl.when(s == steps - 1)
    def _fin():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(rt_ref, qbt_ref, flg_ref,                       # prefetch
                pos_k_ref, pos_q_ref, q_ref, k_ref, v_ref,      # inputs
                do_ref, m_ref, l_ref, delta_ref,
                dk_ref, dv_ref,                                 # outputs
                dk_acc, dv_acc,                                 # scratch
                *, sched: BandSchedule, steps: int, scale: float):
    """Runs in the transposed (Bk, Bq) orientation: the streaming query
    block's stats then broadcast as (1, Bq) rows and both accumulations
    are plain (Bk, Bq) x (Bq, D) products."""
    r = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0]                                     # (Bq, D)
    k = k_ref[0]                                     # (Bk, D) resident
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)
    scores_t = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (Bk, Bq)

    fl = flg_ref[r * steps + s]
    mask_t = sched.step_mask(pos_q_ref[0], pos_k_ref[0], fl)
    dp_t = jax.lax.dot_general(
        v.astype(jnp.float32), do, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Bk, Bq)
    p_t, ds_t = _p_ds(scores_t, mask_t, m_ref[0, 0], l_ref[0, 0], dp_t,
                      delta_ref[0, 0])

    dv_acc[...] += jax.lax.dot_general(
        p_t, do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Bk, D)
    dk_acc[...] += jax.lax.dot_general(
        ds_t, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(s == steps - 1)
    def _fin():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sched", "block_q", "block_k",
                                             "scale", "interpret"))
def salo_table_backward_dq(dout, delta, m, l, q, k, v, pos_q, pos_k,
                           kvt, flg, *, sched: BandSchedule, block_q: int,
                           block_k: int, scale: float,
                           interpret: bool = False) -> jax.Array:
    """dQ in ONE launch over forward step tables passed as traced operands
    (the ShardedPlan per-device slice under ``shard_map``, or the plan's
    own tables via :func:`salo_plan_backward_dq`). The q side
    (q/dout/delta/m/l, length nq*block_q) and KV side (k/v, length
    nkb*block_k) may differ; kvt/flg: (nq*steps,) int32.
    """
    B, nQ, D = q.shape
    bq, bk = block_q, block_k
    nq = nQ // bq
    steps = kvt.shape[0] // nq

    def q_idx(b, i, s, kvt_ref, flg_ref):
        return (b, i, 0)

    def kv_idx(b, i, s, kvt_ref, flg_ref):
        return (b, kvt_ref[i * steps + s], 0)

    def row_idx(b, i, s, kvt_ref, flg_ref):
        return (b, i, 0, 0)

    # Block layouts as in the forward: (Bq, 1) position columns, (1, Bk)
    # position rows, (1, Bq) stat rows of (B, nq, 1, Bq) arrays.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nq, steps),
        in_specs=[
            pl.BlockSpec((1, bq, 1),
                         lambda b, i, s, kvt_ref, flg_ref: (i, 0, 0)),  # pos_q
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, s, kvt_ref, flg_ref:
                         (kvt_ref[i * steps + s], 0, 0)),               # pos_k
            pl.BlockSpec((1, bq, D), q_idx),                         # q
            pl.BlockSpec((1, bk, D), kv_idx),                        # k
            pl.BlockSpec((1, bk, D), kv_idx),                        # v
            pl.BlockSpec((1, bq, D), q_idx),                         # dout
            pl.BlockSpec((1, 1, 1, bq), row_idx),                    # m
            pl.BlockSpec((1, 1, 1, bq), row_idx),                    # l
            pl.BlockSpec((1, 1, 1, bq), row_idx),                    # delta
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_idx),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)]
        + [pltpu.VMEM((bq, LANES), jnp.float32)] * 3,
    )

    kern = functools.partial(_dq_kernel, sched=sched, steps=steps,
                             scale=scale)
    rows = [a.reshape(B, nq, 1, bq) for a in (m, l, delta)]
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nQ, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="salo_plan_backward_dq",
    )(kvt, flg, pos_q.reshape(nq, bq, 1), pos_k.reshape(-1, 1, bk), q, k,
      v, dout, *rows)


def salo_plan_backward_dq(dout, delta, m, l, q, k, v, pos, *,
                          plan: ExecutionPlan, scale: float,
                          interpret: bool = False) -> jax.Array:
    """dQ in ONE launch over the forward plan. All arrays working-space
    padded: q/k/v/dout (B, n_pad, D); delta/m/l (B, n_pad); pos (n_pad,).
    """
    B, n_pad, D = q.shape
    assert n_pad == plan.n_pad, (n_pad, plan.n_pad)
    return salo_table_backward_dq(
        dout, delta, m, l, q, k, v,
        pos.reshape(plan.nq, plan.block_q),
        pos.reshape(plan.nkb, plan.block_k),
        jnp.asarray(plan.kv_blocks.reshape(-1)),
        jnp.asarray(plan.flags.reshape(-1)),
        sched=plan.sched, block_q=plan.block_q, block_k=plan.block_k,
        scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sched", "block_q", "block_k",
                                             "nkb", "scale", "interpret"))
def salo_table_backward_dkv(dout, delta, m, l, q, k, v, pos_q, pos_k,
                            row_tile, qbt, flg, *, sched: BandSchedule,
                            block_q: int, block_k: int, nkb: int,
                            scale: float, interpret: bool = False):
    """dK and dV in ONE launch over PACKED transposed tables.

    Grid row ``r`` keeps KV tile ``row_tile[r]`` resident while its slice
    of visiting query blocks streams past; per-row partials land in a
    (B, n_rows*block_k, D) buffer and are scatter-added per owner tile on
    the host side (rows split from one ragged transposed row — the
    global-column tile that every query block visits — recombine there).
    row_tile: (R,); qbt/flg: (R*W,) int32 flattened. Returns ``(dk, dv)``,
    both (B, nkb*block_k, D) float32.
    """
    B, nQ, D = q.shape
    bq, bk = block_q, block_k
    R = row_tile.shape[0]
    steps = qbt.shape[0] // R
    nq = nQ // bq

    def kv_idx(b, r, s, rt_ref, qbt_ref, flg_ref):
        return (b, r, 0)

    def q_idx(b, r, s, rt_ref, qbt_ref, flg_ref):
        return (b, qbt_ref[r * steps + s], 0)

    def row_idx(b, r, s, rt_ref, qbt_ref, flg_ref):
        return (b, qbt_ref[r * steps + s], 0, 0)

    def owner_idx(b, r, s, rt_ref, qbt_ref, flg_ref):
        return (b, rt_ref[r], 0)

    # Transposed orientation: KV positions as (Bk, 1) columns, the
    # streaming query block's positions and stats as (1, Bq) rows.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, R, steps),
        in_specs=[
            pl.BlockSpec((1, bk, 1),
                         lambda b, r, s, rt_ref, qbt_ref, flg_ref:
                         (rt_ref[r], 0, 0)),                         # pos_k
            pl.BlockSpec((1, 1, bq),
                         lambda b, r, s, rt_ref, qbt_ref, flg_ref:
                         (qbt_ref[r * steps + s], 0, 0)),            # pos_q
            pl.BlockSpec((1, bq, D), q_idx),                         # q
            pl.BlockSpec((1, bk, D), owner_idx),                     # k
            pl.BlockSpec((1, bk, D), owner_idx),                     # v
            pl.BlockSpec((1, bq, D), q_idx),                         # dout
            pl.BlockSpec((1, 1, 1, bq), row_idx),                    # m
            pl.BlockSpec((1, 1, 1, bq), row_idx),                    # l
            pl.BlockSpec((1, 1, 1, bq), row_idx),                    # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),        # dk accumulator
            pltpu.VMEM((bk, D), jnp.float32),        # dv accumulator
        ],
    )

    kern = functools.partial(_dkv_kernel, sched=sched, steps=steps,
                             scale=scale)
    rows = [a.reshape(B, nq, 1, bq) for a in (m, l, delta)]
    dk_r, dv_r = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, R * bk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, R * bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="salo_plan_backward_dkv",
    )(row_tile, qbt, flg, pos_k.reshape(-1, bk, 1),
      pos_q.reshape(nq, 1, bq), q, k, v, dout, *rows)
    z = jnp.zeros((B, nkb, bk, D), jnp.float32)
    dk = z.at[:, row_tile].add(dk_r.reshape(B, R, bk, D))
    dv = z.at[:, row_tile].add(dv_r.reshape(B, R, bk, D))
    return dk.reshape(B, nkb * bk, D), dv.reshape(B, nkb * bk, D)


def salo_plan_backward_dkv(dout, delta, m, l, q, k, v, pos, *,
                           plan: ExecutionPlan, scale: float,
                           interpret: bool = False):
    """dK and dV in ONE launch over the packed transposed plan. Returns
    ``(dk, dv)``, both (B, n_pad, D) working-space padded."""
    B, n_pad, D = q.shape
    assert n_pad == plan.n_pad, (n_pad, plan.n_pad)
    pk = plan.transposed_packed()
    return salo_table_backward_dkv(
        dout, delta, m, l, q, k, v,
        pos.reshape(plan.nq, plan.block_q),
        pos.reshape(plan.nkb, plan.block_k),
        jnp.asarray(pk.row_tile),
        jnp.asarray(pk.q_blocks.reshape(-1)),
        jnp.asarray(pk.flags.reshape(-1)),
        sched=plan.sched, block_q=plan.block_q, block_k=plan.block_k,
        nkb=plan.nkb, scale=scale, interpret=interpret)
