"""Runtime ExecutionPlans: content-based step tables built on device.

The static scheduler hand-designs *which* KV tiles each query block visits
from the pattern alone. This module predicts it from the *content*
(Dynamic Sparse Attention, arXiv:2110.11299; the estimator follows SEA's
pooled-score idea): estimate every (q-block, kv-tile) pair's attention
mass from pooled q·k scores, keep the top-``keep`` tiles per query block,
and emit ``(kv_blocks, flags)`` as traced jnp arrays honoring the exact
contract of :mod:`repro.core.plan_contract` — so every table consumer
(fused Pallas kernels, the XLA scan twins, ShardedPlan's per-shard slices)
runs query-adaptive sparsity without changing a line.

Three load-bearing properties:

* **Selection is a subset of the static plan's visits.** Candidates are
  the static plan's steps, and a selected step keeps its ORIGINAL flags —
  so ``step_mask`` applies the same union mask it always did, full keep
  (``keep >= max_steps``) reproduces the static walk step-for-step (the
  machinery-off invariant), and the dedup/padding contract is inherited.
* **The never-drop guarantee.** Steps whose tile is causal-local to the
  row (within ``local_window`` original positions) or carries global/sink
  columns (``STEP_GLOBAL``) get ``+inf`` selection score: correctness-
  critical tiles can never be dropped, whatever the content says.
  ``keep`` must cover the worst-case always-kept count (checked, raises).
* **The selector is gradient-free.** q/k enter the estimator under
  ``lax.stop_gradient``; training treats the selected table like the
  static one (a constant of the step), and the backward replays the
  SAME selection deterministically from the saved residuals — dQ over the
  forward tables (:func:`repro.core.blockwise.table_dq_scan` or the
  Pallas table kernel), dK/dV through the runtime scatter twin
  (:func:`repro.core.blockwise.table_dkv_scatter_scan`), since the
  host-packed transposed walk cannot exist for device-built tables.

Selected rows are re-sorted into ascending-tile order with right-aligned
padding — matching the static builder's layout, so engines see an
identically-shaped, identically-ordered table whose *values* happen to be
traced.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blockwise import (_global_rows, table_attention_scan,
                                  table_dkv_scatter_scan, table_dq_scan,
                                  plan_backward, undo_working,
                                  working_stream)
from repro.core.patterns import HybridSparsePattern
from repro.core.plan_contract import (PAD_SENTINEL, STEP_GLOBAL,
                                      validate_tables)
from repro.core.scheduler import ExecutionPlan, schedule
from repro.obs.metrics import global_registry


@dataclasses.dataclass(frozen=True)
class DynamicConfig:
    """How to select: ``keep`` tiles per query block.

    ``local_window``: original-position distance under which a tile is
    causal-local and therefore always kept (default: one tile span,
    ``max(block_q, block_k)``). ``pool_k``: key-side pooling granularity
    for the mass estimator — keys are mean-pooled per ``pool_k``-slot
    group and groups reduce by logsumexp, so ``None`` (= whole tile) is
    the cheapest plain block-mean while small values track a tile's
    exp-mass closely (e.g. hot single keys); must divide ``block_k``.
    """
    keep: int
    local_window: Optional[int] = None
    pool_k: Optional[int] = None


def _resolve_window(cfg: DynamicConfig, block_q: int, block_k: int) -> int:
    if cfg.local_window is not None:
        return int(cfg.local_window)
    return max(block_q, block_k)


def always_keep_mask(kv_blocks: np.ndarray, flags: np.ndarray,
                     pos_q: np.ndarray, pos_k: np.ndarray,
                     local_window: int, causal: bool) -> np.ndarray:
    """The never-drop set, statically: which steps of a candidate table are
    exempt from selection. A step is always kept when its tile carries
    global columns (``STEP_GLOBAL``) or is local to the row — the tile's
    original-position range overlaps ``[row_min - local_window, row_max]``
    (``row_max + local_window`` when not causal). Ranges are taken over
    valid (non-``PAD_SENTINEL``) slots; all-padding tiles/rows never
    match. Returns a boolean (nq, W) mask; padding steps are False.
    """
    kv_blocks = np.asarray(kv_blocks)
    flags = np.asarray(flags)
    vq = pos_q < PAD_SENTINEL
    pq = pos_q.astype(np.int64)
    qlo = np.where(vq, pq, np.iinfo(np.int64).max).min(axis=1)
    qhi = np.where(vq, pq, -1).max(axis=1)
    vk = pos_k < PAD_SENTINEL
    pk = pos_k.astype(np.int64)
    klo = np.where(vk, pk, np.iinfo(np.int64).max).min(axis=1)
    khi = np.where(vk, pk, -1).max(axis=1)

    tlo = klo[kv_blocks]                                   # (nq, W)
    thi = khi[kv_blocks]
    lo_q = qlo[:, None]
    hi_q = qhi[:, None]
    reach = hi_q if causal else hi_q + local_window
    local = (thi >= lo_q - local_window) & (tlo <= reach)
    local &= (thi >= 0) & (hi_q >= 0)      # all-padding tile / row: never
    keep = ((flags & STEP_GLOBAL) != 0) | local
    return keep & (flags != 0)


@functools.lru_cache(maxsize=256)
def _plan_always_keep(plan: ExecutionPlan, local_window: int) -> np.ndarray:
    pos = plan.positions_padded()
    return always_keep_mask(
        plan.kv_blocks, plan.flags,
        pos.reshape(plan.nq, plan.block_q),
        pos.reshape(plan.nkb, plan.block_k),
        local_window, plan.sched.causal)


def plan_always_keep(plan: ExecutionPlan, local_window: int) -> np.ndarray:
    """Public analyzer hook: the (nq, max_steps) never-drop mask for a
    static plan — what :mod:`repro.analysis.plan_verify` proves global /
    sink / causal-local tiles can never be dropped against."""
    return _plan_always_keep(plan, int(local_window))


def check_keep(keep: int, always: np.ndarray, what: str = "plan") -> None:
    """The never-drop guarantee needs room: ``keep`` must cover the largest
    per-row always-kept count, else top-k would be forced to drop a
    correctness-critical tile. Static check — raises ValueError."""
    need = int(np.asarray(always).sum(axis=-1).max()) if always.size else 0
    if keep < need:
        raise ValueError(
            f"dynamic keep={keep} is below the {what}'s worst-case "
            f"always-kept count {need} (causal-local + global tiles); "
            f"raise keep or shrink local_window")


def _account_build(flags, keep: int) -> None:
    """Trace-time keep-ratio accounting (host-side: static table shapes and
    the static candidate flags — zero traced operands, zero cost when the
    registry is disabled; the same pattern as ops._trace_accounting)."""
    real = (np.asarray(flags) != 0).sum(axis=-1)
    total = int(real.sum())
    kept = int(np.minimum(real, keep).sum())
    reg = global_registry()
    reg.inc("dynamic_plan_builds")
    reg.observe("dynamic_plan_keep_ratio", kept / max(total, 1))


def block_scores(q, k, pos_q, pos_k, scale: float,
                 pool_k: Optional[int] = None):
    """Pooled per-(q-block, kv-tile) attention-mass estimate, (nq, nkb) f32.

    Queries are mean-pooled per block over valid slots — by linearity the
    pooled score IS the exact mean of the block's pairwise scores. Keys
    are mean-pooled per ``pool_k``-slot group and the groups reduce by
    logsumexp (with the whole tile as one group this is the plain block
    mean; finer groups approximate ``log`` of the tile's exp-mass, which
    is what top-k should rank). Batch/head reduce by mean. Cost is
    ``N^2 D / (block_q * pool_k)`` — ``block_q``x (or more) below the
    attention it prices.
    """
    B, nQ, D = q.shape
    nq, bq = pos_q.shape
    nkb, bk = pos_k.shape
    pk = bk if pool_k is None else int(pool_k)
    if bk % pk:
        raise ValueError(f"pool_k={pk} must divide block_k={bk}")
    S = bk // pk
    vq = jnp.asarray(pos_q) < PAD_SENTINEL                      # (nq, bq)
    vk = (jnp.asarray(pos_k) < PAD_SENTINEL).reshape(nkb, S, pk)
    qf = q.astype(jnp.float32).reshape(B, nq, bq, D)
    kf = k.astype(jnp.float32).reshape(B, nkb, S, pk, D)
    qp = (qf * vq[None, :, :, None]).sum(2) \
        / jnp.maximum(vq.sum(1), 1)[None, :, None]              # (B, nq, D)
    kcnt = vk.sum(2)                                            # (nkb, S)
    kp = (kf * vk[None, :, :, :, None]).sum(3) \
        / jnp.maximum(kcnt, 1)[None, :, :, None]                # (B,nkb,S,D)
    s = jnp.einsum("bqd,bksd->bqks", qp, kp) * scale
    s = jnp.where((kcnt > 0)[None, None], s, -jnp.inf)
    est = jax.nn.logsumexp(s, axis=-1)                          # (B,nq,nkb)
    return est.mean(0)


def select_steps(q, k, kv_blocks, flags, pos_q, pos_k, always, keep: int,
                 scale: float, pool_k: Optional[int] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Top-``keep`` content selection over a candidate step table.

    Works identically on static (numpy) and traced (per-shard slice)
    candidate tables. The selector sees q/k through ``stop_gradient``;
    ``always`` steps score ``+inf`` (never dropped), padding steps
    ``-inf`` (picked only when a row has fewer than ``keep`` real steps,
    and re-emitted as contract padding: flags 0, tile 0). Output rows are
    ascending-tile, right-padded — the static builder's layout. Returns
    ``(kv_blocks, flags)`` int32 (nq, keep).
    """
    q = jax.lax.stop_gradient(q)
    k = jax.lax.stop_gradient(k)
    est = block_scores(q, k, pos_q, pos_k, scale, pool_k)      # (nq, nkb)
    kvb = jnp.asarray(kv_blocks)
    flg = jnp.asarray(flags)
    step_est = jnp.take_along_axis(est, kvb, axis=1)           # (nq, W)
    score = jnp.where(jnp.asarray(always), jnp.inf, step_est)
    score = jnp.where(flg != 0, score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, keep)
    sel_f = jnp.where(vals > -jnp.inf,
                      jnp.take_along_axis(flg, idx, axis=1), 0)
    sel_t = jnp.where(sel_f != 0,
                      jnp.take_along_axis(kvb, idx, axis=1), 0)
    order = jnp.argsort(
        jnp.where(sel_f != 0, sel_t, jnp.iinfo(jnp.int32).max), axis=1)
    sel_t = jnp.take_along_axis(sel_t, order, axis=1)
    sel_f = jnp.take_along_axis(sel_f, order, axis=1)
    return sel_t.astype(jnp.int32), sel_f.astype(jnp.int32)


def _prep(pattern: HybridSparsePattern, N: int, cfg: DynamicConfig,
          block_q: int, block_k: int):
    sched = schedule(pattern, N)
    plan = sched.plan(block_q, block_k)
    always = _plan_always_keep(plan, _resolve_window(cfg, block_q, block_k))
    keep = min(int(cfg.keep), plan.max_steps)
    check_keep(keep, always)
    return sched, plan, always, keep


def dynamic_tables(q, k, pattern: HybridSparsePattern, cfg: DynamicConfig,
                   *, block_q: int = 128, block_k: int = 128,
                   scale: Optional[float] = None):
    """Materialize the selected tables for inspection (tests, benchmarks,
    recall measurement). q/k: (B, N, D) flat. Returns ``(plan, kv_blocks,
    flags, always)`` with tables (nq, keep) on the plan's working grid —
    concrete when called outside jit."""
    B, N, D = q.shape
    scale = (D ** -0.5) if scale is None else scale
    sched, plan, always, keep = _prep(pattern, N, cfg, block_q, block_k)
    qw = working_stream(q, sched, plan)
    kw = working_stream(k, sched, plan)
    pos = plan.positions_padded()
    kvt, flg = select_steps(
        qw, kw, plan.kv_blocks, plan.flags,
        pos.reshape(plan.nq, plan.block_q),
        pos.reshape(plan.nkb, plan.block_k),
        always, keep, scale, cfg.pool_k)
    return plan, kvt, flg, always


def _dyn_forward(q, k, v, pattern, cfg, block_q, block_k, scale, impl):
    B, N, D = q.shape
    scale = (D ** -0.5) if scale is None else scale
    sched, plan, always, keep = _prep(pattern, N, cfg, block_q, block_k)
    out_dtype = q.dtype

    qw = working_stream(q, sched, plan)
    kw = working_stream(k, sched, plan)
    vw = working_stream(v, sched, plan)
    pos = jnp.asarray(plan.positions_padded())
    pos_q = pos.reshape(plan.nq, plan.block_q)
    pos_k = pos.reshape(plan.nkb, plan.block_k)

    kvt, flg = select_steps(qw, kw, plan.kv_blocks, plan.flags, pos_q,
                            pos_k, always, keep, scale, cfg.pool_k)
    validate_tables(kvt, flg, nkb=plan.nkb, name="dynamic tables")
    _account_build(plan.flags, keep)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.salo_attention import salo_table_attention
        out_w, m, l = salo_table_attention(
            qw, kw, vw, pos_q, pos_k, kvt.reshape(-1), flg.reshape(-1),
            sched=sched, block_q=block_q, block_k=block_k, scale=scale,
            interpret=impl == "pallas_interpret")
    else:
        out_w, m, l = table_attention_scan(qw, kw, vw, pos_q, pos_k, kvt,
                                           flg, sched, scale)

    out = undo_working(out_w, sched, N)
    if sched.n_global > 0 and sched.global_rows:
        rows = _global_rows(q, k, v, sched, scale, out_dtype)
        out = out.at[:, : sched.n_global].set(rows)
    return out, (out_w, m, l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _dynamic(q, k, v, pattern, cfg, block_q, block_k, scale, impl):
    out, _ = _dyn_forward(q, k, v, pattern, cfg, block_q, block_k, scale,
                          impl)
    return out


def _dynamic_fwd(q, k, v, pattern, cfg, block_q, block_k, scale, impl):
    out, (out_w, m, l) = _dyn_forward(q, k, v, pattern, cfg, block_q,
                                      block_k, scale, impl)
    return out, (q, k, v, out_w, m, l)


def _dynamic_bwd(pattern, cfg, block_q, block_k, scale, impl, res, g):
    q, k, v, out_w, m, l = res
    B, N, D = q.shape
    scale_ = (D ** -0.5) if scale is None else scale
    sched, plan, always, keep = _prep(pattern, N, cfg, block_q, block_k)
    pos_np = plan.positions_padded()
    pos_q = jnp.asarray(pos_np.reshape(plan.nq, plan.block_q))
    pos_k = jnp.asarray(pos_np.reshape(plan.nkb, plan.block_k))

    # The selector is deterministic in (q, k): replaying it from the saved
    # residuals reproduces the forward's table exactly, once, shared by
    # both gradient walks.
    stash = {}

    def tables(qw, kw):
        if not stash:
            stash["t"] = select_steps(qw, kw, plan.kv_blocks, plan.flags,
                                      pos_q, pos_k, always, keep, scale_,
                                      cfg.pool_k)
        return stash["t"]

    def dq_engine(dout, delta, m_, l_, qw, kw, vw, pos):
        kvt, flg = tables(qw, kw)
        if impl in ("pallas", "pallas_interpret"):
            from repro.kernels.salo_backward import salo_table_backward_dq
            return salo_table_backward_dq(
                dout, delta, m_, l_, qw, kw, vw, pos_q, pos_k,
                kvt.reshape(-1), flg.reshape(-1), sched=sched,
                block_q=block_q, block_k=block_k, scale=scale_,
                interpret=impl == "pallas_interpret")
        return table_dq_scan(dout, delta, m_, l_, qw, kw, vw, pos_q,
                             pos_k, kvt, flg, sched, scale_)

    def dkv_engine(dout, delta, m_, l_, qw, kw, vw, pos):
        # dK/dV cannot walk the host-packed transposed tables (the table
        # is runtime data): the scatter twin regroups at run time.
        kvt, flg = tables(qw, kw)
        return table_dkv_scatter_scan(dout, delta, m_, l_, qw, kw, vw,
                                      pos_q, pos_k, kvt, flg, sched,
                                      scale_)

    return plan_backward(g, q, k, v, out_w, m, l, plan, scale_, dq_engine,
                         dkv_engine)


_dynamic.defvjp(_dynamic_fwd, _dynamic_bwd)


@functools.partial(jax.jit, static_argnames=("pattern", "cfg", "block_q",
                                             "block_k", "scale", "impl"))
def dynamic_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      pattern: HybridSparsePattern, cfg: DynamicConfig, *,
                      block_q: int = 128, block_k: int = 128,
                      scale: Optional[float] = None,
                      impl: Optional[str] = None) -> jax.Array:
    """Content-based dynamically-sparse attention. q/k/v: (B, N, D).

    The static plan supplies the candidate visits and masks; per query
    block only the ``cfg.keep`` highest estimated-mass tiles execute
    (never dropping causal-local/global tiles). Differentiable through the
    shared ``plan_backward`` contract with a gradient-free selector — see
    the module docstring.
    """
    from repro.core.attention import default_impl
    impl = impl or default_impl()
    if impl not in ("blockwise", "pallas", "pallas_interpret"):
        raise ValueError(
            f"plan='dynamic' needs a table-driven engine, got impl={impl!r}")
    return _dynamic(q, k, v, pattern, cfg, block_q, block_k, scale, impl)
