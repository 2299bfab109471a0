"""Public hybrid-sparse-attention API: one entry point, many engines.

``hybrid_attention(q, k, v, pattern, impl=...)`` with q/k/v ``(B, H, N, D)``
(batch, heads, seq, head_dim — the model-facing layout).

Every sparse engine executes the same lowering pipeline (core/scheduler.py):

    HybridSparsePattern --schedule()--> BandSchedule --plan()--> ExecutionPlan

The ExecutionPlan is the single source of truth for the tile walk and the
per-step masks: flat per-query-block step tables covering the union of all
bands plus the global-key tiles, deduplicated to one visit per KV tile.

Engines:
  * ``dense_ref``          O(n^2) masked oracle (tests/small shapes)
  * ``blockwise``          the plan on XLA: one lax.scan over the step table
                           [default off the TPU]
  * ``pallas``             the plan on TPU: ONE table-driven pallas_call,
                           step table streamed via scalar prefetch
                           [default on the TPU; raises elsewhere]
  * ``pallas_interpret``   same kernel, interpret mode (CPU numerics check)

:func:`default_impl` is the one place the platform picks the engine.

All engines are drop-in equivalent (tested to tolerance), forward AND
backward: both differentiable engines install a plan-driven custom VJP that
reuses the forward's saved ``(out, m, l)`` partials — ``blockwise`` as two
table-walking scans, ``pallas`` as two flash-style kernel launches (dQ over
the forward tables, dK/dV over the transposed tables; see
kernels/salo_backward.py) — same residuals, same contract.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.patterns import HybridSparsePattern
from repro.core.blockwise import blockwise_attention
from repro.obs.metrics import global_registry

IMPLS = ("dense_ref", "blockwise", "pallas", "pallas_interpret")


def default_impl(decode: bool = False) -> str:
    """The engine this platform runs when the caller names none: the
    compiled Pallas kernels on a TPU, the XLA twin elsewhere (``blockwise``
    for attention and its gradient, ``xla`` for ragged decode)."""
    if jax.default_backend() == "tpu":
        return "pallas"
    return "xla" if decode else "blockwise"


def hybrid_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pattern: HybridSparsePattern, *,
                     impl: Optional[str] = None,
                     block_q: int = 128, block_k: int = 128,
                     scale: Optional[float] = None,
                     plan: str = "static",
                     dynamic_keep: Optional[int] = None,
                     dynamic_local_window: Optional[int] = None,
                     dynamic_pool_k: Optional[int] = None) -> jax.Array:
    """Hybrid sparse attention. q: (B, H, N, D); k/v: (B, Hkv, N, D).

    GQA: if Hkv < H, KV heads are repeated to match (H % Hkv == 0).
    ``impl`` names the engine; ``None`` takes :func:`default_impl`.

    ``plan`` selects how step tables are built: ``"static"`` lowers the
    pattern alone (the default ExecutionPlan path); ``"dynamic"`` routes
    through :mod:`repro.core.dynamic` — per query block only the
    ``dynamic_keep`` highest estimated-mass candidate tiles execute
    (causal-local and global/sink tiles are never dropped; see the
    DynamicConfig knobs ``dynamic_local_window`` / ``dynamic_pool_k``).
    Dynamic plans need a table-driven engine (any ``impl`` but
    ``dense_ref``) and compose with sequence parallelism: the selection
    happens per shard over its [local | halo | global] view while the
    exchange schedule stays static.
    """
    impl = impl or default_impl()
    if plan not in ("static", "dynamic"):
        raise ValueError(f"unknown plan {plan!r}; choose static or dynamic")
    dcfg = None
    if plan == "dynamic":
        if impl == "dense_ref":
            raise ValueError("plan='dynamic' needs a table-driven engine "
                             "(impl != 'dense_ref')")
        if dynamic_keep is None:
            raise ValueError("plan='dynamic' requires dynamic_keep")
        from repro.core.dynamic import DynamicConfig
        dcfg = DynamicConfig(keep=int(dynamic_keep),
                             local_window=dynamic_local_window,
                             pool_k=dynamic_pool_k)
    B, H, N, D = q.shape
    Hkv = k.shape[1]
    if Hkv != H:
        assert H % Hkv == 0, f"GQA heads {H} not divisible by kv heads {Hkv}"
        rep = H // Hkv
        # broadcast_to + reshape, NOT jnp.repeat: XLA keeps the expand as a
        # no-copy broadcast fused into the consumer (repeat materializes the
        # KV stream rep x in HBM).
        k = jnp.broadcast_to(k[:, :, None], (B, Hkv, rep, N, D))
        v = jnp.broadcast_to(v[:, :, None], (B, Hkv, rep, N, D))
        k = k.reshape(B, H, N, D)
        v = v.reshape(B, H, N, D)

    qf = q.reshape(B * H, N, D)
    kf = k.reshape(B * H, N, D)
    vf = v.reshape(B * H, N, D)
    assert qf.shape == kf.shape == vf.shape == (B * H, N, D), \
        "engines (incl. pallas) require the flat (B*H, N, D) layout"

    # Trace-time call accounting (host-side, once per compilation — the
    # dispatch-level complement of the per-launch accounting in
    # kernels/ops.py; zero traced operands).
    global_registry().inc("attention_trace_calls", impl=impl)

    # Sequence parallelism: when the active sharding rules map the "seq"
    # logical axis onto a mesh axis (long-context cells turn this on in
    # launch.specs.cell_rules), run the ShardedPlan path — the same fused
    # engines under shard_map with ppermute halo exchange — instead of
    # letting pjit all-gather K/V.
    if impl in ("blockwise", "pallas", "pallas_interpret"):
        from repro.dist.sharding import sequence_mesh_axis
        seq = sequence_mesh_axis()
        if seq is not None:
            from repro.dist.sharded_plan import sharded_attention
            mesh, ax = seq
            out = sharded_attention(qf, kf, vf, pattern, mesh, ax,
                                    block_q=block_q, block_k=block_k,
                                    scale=scale, impl=impl, dynamic=dcfg)
            return out.reshape(B, H, N, D)

    if dcfg is not None:
        from repro.core.dynamic import dynamic_attention
        out = dynamic_attention(qf, kf, vf, pattern, dcfg, block_q=block_q,
                                block_k=block_k, scale=scale, impl=impl)
        return out.reshape(B, H, N, D)

    if impl == "dense_ref":
        from repro.kernels.ref import reference_attention
        out = reference_attention(qf, kf, vf, pattern, scale=scale)
    elif impl == "blockwise":
        out = blockwise_attention(qf, kf, vf, pattern, block_q=block_q,
                                  block_k=block_k, scale=scale)
    elif impl in ("pallas", "pallas_interpret"):
        from repro.kernels.ops import salo_attention
        out = salo_attention(qf, kf, vf, pattern, block_q=block_q,
                             block_k=block_k, scale=scale,
                             interpret=(impl == "pallas_interpret"))
    else:
        raise ValueError(f"unknown impl {impl!r}; choose from {IMPLS}")
    return out.reshape(B, H, N, D)


def hybrid_decode_attention(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, t, pattern, *,
                            scale: Optional[float] = None,
                            cache_positions=None,
                            slice_window: bool = False,
                            return_state: bool = False,
                            return_slot_m: bool = False):
    """Single-token decode — ragged aware. q: (B, H, 1, D); caches:
    (B, Hkv, S, D); ``t``: scalar position (lockstep batch) OR a (B,)
    vector — one position per request, so a single call serves a
    continuous batch whose members sit at different depths.
    ``cache_positions``: (S,) shared slots or (B, S) per-request slots
    (the paged ring-cache view).

    GQA is computed with a grouped einsum — KV heads are NEVER repeated
    (a `jnp.repeat` materializes rep x the cache and breaks seq-sharding
    propagation under pjit).

    ``slice_window=True`` (SALO windowed decode): read only the last
    ``window`` cache slots + the global-token prefix instead of the whole
    sequence — O(w) instead of O(n) HBM traffic per step, the serving-side
    payoff of the paper's pattern. Requires the slot==position cache layout
    (``cache_positions is None``) and a lockstep scalar ``t``.

    ``return_state=True`` returns the finalized partial triple
    ``(out, m, l)`` with m/l (B, H, 1) instead of the softmaxed output —
    what a sequence shard contributes to the cross-shard masked-psum merge
    over its owned cache slots. A request with no valid slot on this shard
    yields the ``(0, NEG_INF, 0)`` identity (renorm.PartialState contract).
    Incompatible with ``slice_window`` (the sharded slab path passes
    ``cache_positions``, which already disables the slice).

    ``return_slot_m=True`` appends ``slot_m`` (B, S) — each request's max
    masked score against each cache slot (NEG_INF where masked), the raw
    per-slot statistic the paged engine reduces to per-page maxima for
    its stats-driven page-keep mask. Composes with ``return_state``;
    incompatible with ``slice_window`` (slot order would be scrambled).
    """
    from repro.core import renorm
    from repro.core.scheduler import (STEP_GLOBAL, STEP_WINDOW,
                                      causal_step_mask)

    B, H, _, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    scale_ = (D ** -0.5) if scale is None else scale
    qg = q.reshape(B, Hkv, rep, D)
    p = pattern
    a, _b = p.window
    g = p.n_global
    ragged_t = jnp.ndim(t) > 0

    def grouped(kc, vc, pos_k, extra_mask=None):
        """kc/vc: (B, Hkv, L, D); pos_k: (L,) or (B, L) -> masked scores."""
        s = jnp.einsum("bgrd,bgsd->bgrs", qg, kc,
                       preferred_element_type=jnp.float32) * scale_
        L = kc.shape[2]
        pos_i = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
        pos_kb = jnp.broadcast_to(jnp.asarray(pos_k, jnp.int32), (B, L))
        m = causal_step_mask(p, pos_i[:, None], pos_kb,
                             STEP_WINDOW | STEP_GLOBAL)        # (B, L)
        if extra_mask is not None:
            m = m & extra_mask
        return jnp.where(m[:, None, None, :], s, renorm.NEG_INF)

    if return_state:
        pos_k = (jnp.arange(S, dtype=jnp.int32) if cache_positions is None
                 else cache_positions.astype(jnp.int32))
        s = grouped(k_cache, v_cache, pos_k)          # (B, Hkv, rep, S)
        slot_m = jnp.max(s, axis=(1, 2)) if return_slot_m else None
        m = jnp.max(s, axis=-1)
        # masked entries sit at NEG_INF: exp(NEG_INF - shift) underflows to
        # exactly 0, and an all-masked row keeps (0, NEG_INF, 0).
        shift = jnp.where(m <= renorm.NEG_INF / 2, 0.0, m)
        p = jnp.exp(s - shift[..., None])
        l = jnp.sum(p, axis=-1)
        # f32 contraction AND an f32 partial: the cross-shard merge
        # re-weights partials, so the round to the compute dtype must
        # happen ONCE, after the merge — per-shard bf16 rounding here
        # would diverge from the single-device round-once numerics
        acc = jnp.einsum("bgrs,bgsd->bgrd", p, v_cache.astype(p.dtype))
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        res = (out.reshape(B, H, 1, D),
               m.reshape(B, H, 1), l.reshape(B, H, 1))
        return (*res, slot_m) if return_slot_m else res

    if return_slot_m:
        pos_k = (jnp.arange(S, dtype=jnp.int32) if cache_positions is None
                 else cache_positions.astype(jnp.int32))
        s = grouped(k_cache, v_cache, pos_k)
        wts = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bgrs,bgsd->bgrd", wts, v_cache.astype(wts.dtype))
        return (out.astype(q.dtype).reshape(B, H, 1, D),
                jnp.max(s, axis=(1, 2)))

    if slice_window and cache_positions is None and a > -(1 << 29) \
            and not ragged_t:
        w = -a + 1
        L = min(S, w)
        start = jnp.clip(jnp.asarray(t, jnp.int32) - (L - 1), 0, S - L)
        k_win = jax.lax.dynamic_slice_in_dim(k_cache, start, L, axis=2)
        v_win = jax.lax.dynamic_slice_in_dim(v_cache, start, L, axis=2)
        pos_win = start + jnp.arange(L, dtype=jnp.int32)
        parts_v, parts_s = [v_win], []
        s_win = grouped(k_win, v_win, pos_win)
        parts_s.append(s_win)
        if g > 0:
            gp = min(g, S)
            k_sink = k_cache[:, :, :gp]
            v_sink = v_cache[:, :, :gp]
            pos_sink = jnp.arange(gp, dtype=jnp.int32)
            # exclude sink slots already inside the window slice
            s_sink = grouped(k_sink, v_sink, pos_sink,
                             extra_mask=pos_sink < start)
            parts_s.insert(0, s_sink)
            parts_v.insert(0, v_sink)
        s = jnp.concatenate(parts_s, axis=-1)
        vc = jnp.concatenate(parts_v, axis=2)
    else:
        pos_k = (jnp.arange(S, dtype=jnp.int32) if cache_positions is None
                 else cache_positions.astype(jnp.int32))
        s = grouped(k_cache, v_cache, pos_k)
        vc = v_cache
    wts = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bgsd->bgrd", wts, vc.astype(wts.dtype))
    return out.astype(q.dtype).reshape(B, H, 1, D)


def hybrid_chunk_attention(q: jax.Array, k_view: jax.Array,
                           v_view: jax.Array, pos_q: jax.Array,
                           pos_k: jax.Array, kv_blocks: jax.Array,
                           flags: jax.Array, pattern, *,
                           scale: Optional[float] = None,
                           return_state: bool = False):
    """Chunked-prefill attention (model-facing layout): one fused pass of a
    prompt chunk against the request's paged KV view + the chunk itself.

    q: (B, H, Cp, D); k_view/v_view: (B, Hkv, Vp, D); pos_q: (B, Cp);
    pos_k: (B, Vp) original positions; kv_blocks/flags: (nq, W) ChunkPlan
    step tables. GQA via no-copy broadcast (same rule as the training
    path). Returns (B, H, Cp, D), plus (m, l) of shape (B, H, Cp) when
    ``return_state`` (the per-shard partial for the cross-shard merge).
    """
    from repro.core.blockwise import chunk_attention

    B, H, Cp, D = q.shape
    Hkv, Vp = k_view.shape[1], k_view.shape[2]
    rep = H // Hkv
    if Hkv != H:
        k_view = jnp.broadcast_to(k_view[:, :, None],
                                  (B, Hkv, rep, Vp, D)).reshape(B, H, Vp, D)
        v_view = jnp.broadcast_to(v_view[:, :, None],
                                  (B, Hkv, rep, Vp, D)).reshape(B, H, Vp, D)
    qf = q.reshape(B * H, Cp, D)
    kf = k_view.reshape(B * H, Vp, D)
    vf = v_view.reshape(B * H, Vp, D)
    pos_qf = jnp.broadcast_to(pos_q[:, None], (B, H, Cp)).reshape(B * H, Cp)
    pos_kf = jnp.broadcast_to(pos_k[:, None], (B, H, Vp)).reshape(B * H, Vp)
    res = chunk_attention(qf, kf, vf, pos_qf, pos_kf, kv_blocks, flags,
                          pattern, scale=scale, return_state=return_state)
    if return_state:
        out, m, l = res
        return (out.reshape(B, H, Cp, D), m.reshape(B, H, Cp),
                l.reshape(B, H, Cp))
    return res.reshape(B, H, Cp, D)
