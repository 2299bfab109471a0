"""SALO hybrid sparse attention as ONE table-driven Pallas TPU kernel.

The TPU-native incarnation of the paper's spatial accelerator (DESIGN.md §2),
driven by the :class:`repro.core.scheduler.ExecutionPlan` IR:

* The MXU plays the 32x32 PE systolic array: each grid step multiplies a
  resident (block_q, D) query tile against a streamed (block_k, D) K tile and
  the matching V tile — stage 1 and stage 5 of the paper's 5-stage PE pipeline
  collapse into two MXU contractions.
* The paper's data scheduler becomes the plan's **step table**, streamed in
  via scalar prefetch (``PrefetchScalarGridSpec``): step ``s`` of query block
  ``i`` fetches KV tile ``kv_blocks[i, s]`` HBM->VMEM. The table is the union
  of every band's walk plus the global-key tiles, deduplicated — overlapping
  bands (ViL's 15) share one visit per tile, and global attention rides the
  same stream ("simultaneously with the same input vectors", paper §5.2)
  instead of a separate pass. One ``pallas_call`` per forward, period.
* The paper's window splitting + weighted-sum module (Eq. 2) is the online
  softmax accumulator in VMEM scratch: (acc, m, l) updated once per visited
  tile — no per-band partials, no inter-launch merges.
* Masks come from *original token positions* streamed as int32 tiles plus the
  plan's per-step flags, so dilation-reordered inputs, 2-D grids, global
  columns, and padding are all the same code path (core/scheduler.py).
  (Global *rows* — global queries attending everything — are a tiny dense
  epilogue over g rows in ops.py, not a kernel launch.)

Grid: ``(B, num_q_blocks, plan.max_steps)``; the last dimension is
sequential ("arbitrary"), the first two parallel. Padding steps (flags == 0)
mask to nothing and leave the accumulator untouched.

The kernel emits the *partial state* (normalized out, m, l) so cross-device
sequence parallelism can still merge outputs with `core.renorm.merge` AND so
the fused backward (kernels/salo_backward.py) can recompute attention
probabilities from it instead of re-running the forward. Empty rows follow
the renorm.PartialState contract: (out=0, m=NEG_INF, l=0) — the merge
identity, and exactly zero gradient through the backward's guards.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scheduler import BandSchedule, ExecutionPlan

NEG_INF = -1e30
LANES = 128  # TPU vector lane count; m/l scratch is lane-replicated


def _kernel(kvt_ref, flg_ref,                           # scalar prefetch
            pos_q_ref, pos_k_ref, q_ref, k_ref, v_ref,  # inputs
            out_ref, m_ref, l_ref,                      # outputs
            acc_ref, m_scr, l_scr,                      # VMEM scratch
            *, sched: BandSchedule, steps: int, scale: float):
    i = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                                     # (Bq, D)
    k = k_ref[0]                                     # (Bk, D)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (Bq, Bk)

    # ---- plan mask: window | global column, gated by the step flags ---- #
    fl = flg_ref[i * steps + s]                      # int32 scalar
    pos_q = pos_q_ref[0]                             # (Bq, 1) int32 column
    pos_k = pos_k_ref[0]                             # (1, Bk) int32 row
    mask = sched.step_mask(pos_q, pos_k, fl)

    scores = jnp.where(mask, scores, NEG_INF)

    # ---- online softmax update (paper Eq. 2, stabilized) ---------------- #
    m_prev = m_scr[...][:, :1]                        # (Bq, 1)
    m_tile = jnp.max(scores, axis=-1, keepdims=True)  # (Bq, 1)
    m_new = jnp.maximum(m_prev, m_tile)
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - shift)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - shift))

    v = v_ref[0]                                      # (Bk, D)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (Bq, D)
    acc_ref[...] = acc_ref[...] * corr + pv
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    # ---- finalize on the last sequential step ---------------------------- #
    @pl.when(s == steps - 1)
    def _fin():
        # Empty-row contract (shared with renorm.PartialState): a row whose
        # EVERY step masked to nothing — tile-grid padding, or a pattern
        # row with no reachable key — emits exactly (out=0, m=NEG_INF,
        # l=0), the identity element of renorm.merge. The l == 0 guard
        # below only protects the normalization; m is deliberately left at
        # NEG_INF so merges keep zero weight and the fused backward's
        # p-recompute / delta term (kernels/salo_backward.py) sees the
        # same guarded branch and yields exactly zero gradients.
        l = l_scr[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_ref[...] / l_safe).astype(out_ref.dtype)
        # lane-replicated (Bq, LANES) columns -> (1, Bq) rows
        m_ref[0, 0] = m_scr[...].T[:1]
        l_ref[0, 0] = l_scr[...].T[:1]


@functools.partial(jax.jit, static_argnames=("sched", "block_q", "block_k",
                                             "scale", "interpret"))
def salo_table_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         pos_q: jax.Array, pos_k: jax.Array,
                         kvt: jax.Array, flg: jax.Array, *,
                         sched: BandSchedule, block_q: int, block_k: int,
                         scale: float, interpret: bool = False):
    """The table-driven launch with the step tables as *traced operands*.

    The tables only reach the kernel through scalar prefetch, so their
    values may be runtime data — e.g. a per-device slice of the
    ShardedPlan's stacked tables selected by ``axis_index`` under
    ``shard_map``. The q side and KV side may differ in length (the sharded
    local view streams ``nkb_view`` tiles past ``nq_local`` query blocks).

    q: (B, nq*block_q, D); k/v: (B, nkb*block_k, D); pos_q: (nq, block_q);
    pos_k: (nkb, block_k); kvt/flg: (nq*steps,) int32 flattened tables.
    Returns (out, m, l) exactly like :func:`salo_plan_attention`.
    """
    B, nQ, D = q.shape
    assert nQ % block_q == 0 and k.shape[1] % block_k == 0, \
        (nQ, block_q, k.shape[1], block_k)
    nq = nQ // block_q
    steps = kvt.shape[0] // nq

    def kv_idx(b, i, s, kvt_ref, flg_ref):
        return (b, kvt_ref[i * steps + s], 0)

    def q_idx(b, i, s, kvt_ref, flg_ref):
        return (b, i, 0)

    def row_idx(b, i, s, kvt_ref, flg_ref):
        return (b, i, 0, 0)

    # Every block's last two dims equal the array's (Mosaic tiling rule):
    # positions ride as (Bq, 1) columns / (1, Bk) rows and the row stats
    # as (1, Bq) rows of a (B, nq, 1, Bq) array.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nq, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, 1),
                         lambda b, i, s, kvt_ref, flg_ref: (i, 0, 0)),  # pos_q
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, s, kvt_ref, flg_ref:
                         (kvt_ref[i * steps + s], 0, 0)),               # pos_k
            pl.BlockSpec((1, block_q, D), q_idx),                       # q
            pl.BlockSpec((1, block_k, D), kv_idx),                      # k
            pl.BlockSpec((1, block_k, D), kv_idx),                      # v
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), q_idx),
            pl.BlockSpec((1, 1, 1, block_q), row_idx),
            pl.BlockSpec((1, 1, 1, block_q), row_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),      # acc
            pltpu.VMEM((block_q, LANES), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((block_q, LANES), jnp.float32),  # l
        ],
    )

    kern = functools.partial(_kernel, sched=sched, steps=steps, scale=scale)
    out, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nQ, D), q.dtype),
            jax.ShapeDtypeStruct((B, nq, 1, block_q), jnp.float32),
            jax.ShapeDtypeStruct((B, nq, 1, block_q), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="salo_plan_attention",
    )(kvt, flg, pos_q.reshape(nq, block_q, 1),
      pos_k.reshape(-1, 1, block_k), q, k, v)
    return out, m.reshape(B, nQ), l.reshape(B, nQ)


def salo_plan_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        pos: jax.Array, *, plan: ExecutionPlan,
                        scale: Optional[float] = None,
                        interpret: bool = False):
    """The whole hybrid pattern (all bands + global column) in ONE launch.

    q/k/v: (B, n_pad, D) padded working-space inputs; pos: (n_pad,) original
    positions. Returns (out, m, l): normalized output and softmax stats — a
    mergeable partial (out*l rebuilds `renorm.PartialState.acc`).
    """
    B, n_pad, D = q.shape
    assert n_pad == plan.n_pad, (n_pad, plan.n_pad)
    scale = (D ** -0.5) if scale is None else scale
    return salo_table_attention(
        q, k, v,
        pos.reshape(plan.nq, plan.block_q),
        pos.reshape(plan.nkb, plan.block_k),
        jnp.asarray(plan.kv_blocks.reshape(-1)),
        jnp.asarray(plan.flags.reshape(-1)),
        sched=plan.sched, block_q=plan.block_q, block_k=plan.block_k,
        scale=scale, interpret=interpret)
