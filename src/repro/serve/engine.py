"""Serving engines: the lockstep baseline and the continuous-batching engine.

``ServeEngine`` (lockstep): prefill a rectangular batch token-by-token, then
step the decode loop in lockstep — every sequence at the same position. The
correctness baseline, and the thing the continuous engine is measured
against.

``ContinuousEngine``: the production-style path. Requests of different
lengths enter a scheduler (:mod:`repro.serve.batcher`), share ONE pooled
paged ring-cache slab (:mod:`repro.serve.paged_cache`), prefill in
plan-driven chunks (``ChunkPlan`` — ``ceil(P/chunk)`` fused passes instead
of ``P`` sequential decode steps), and decode ragged: one launch per step
serves every in-flight request at its own position via the per-request
``t`` vector / page tables of :mod:`repro.kernels.salo_decode`. Greedy
outputs match the lockstep baseline token-for-token
(tests/test_serve_continuous.py).
"""
from __future__ import annotations

import dataclasses
import json
import time
from collections.abc import MutableMapping
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import default_impl
from repro.ft.faults import ResourceExhausted
from repro.models.model import Model
from repro.obs import Observability


class CountersView(MutableMapping):
    """The old ``ContinuousEngine.counters`` dict, now a live view over
    registry counters (``serve_<key>``). Every historical access pattern
    keeps working — ``counters["x"] += 1``, ``dict(counters)``,
    ``counters.update(snapshot)`` — while the values live in the metrics
    registry alongside everything else observability collects."""

    KEYS = ("prefill_launches", "decode_launches", "prefill_tokens",
            "decode_tokens", "decode_pages_read", "decode_pages_total",
            "prefill_pages_read", "prefill_pages_total", "engine_steps",
            "decode_pages_walked", "decode_pages_live")

    def __init__(self, registry):
        self._reg = registry

    def __getitem__(self, key: str) -> int:
        if key not in self.KEYS:
            raise KeyError(key)
        return int(self._reg.value("serve_" + key))

    def __setitem__(self, key: str, value) -> None:
        if key not in self.KEYS:
            raise KeyError(key)
        self._reg.set_counter("serve_" + key, int(value))

    def __delitem__(self, key: str) -> None:
        raise TypeError("engine counters are a fixed set")

    def __iter__(self):
        return iter(self.KEYS)

    def __len__(self) -> int:
        return len(self.KEYS)

    def __repr__(self) -> str:
        return repr(dict(self))


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int
    temperature: float = 0.0   # 0 = greedy
    seed: int = 0


class ServeEngine:
    def __init__(self, model: Model, scfg: ServeConfig):
        self.model = model
        self.scfg = scfg
        self._decode = jax.jit(model.decode_step)

    def prefill(self, params, prompts: jax.Array):
        """prompts: (B, P). Returns (cache, last_logits) after P steps.

        Token-by-token prefill through decode_step — exercises exactly the
        decode path (production engines fuse this; the framework keeps it
        simple and correct, and the dry-run lowers the fused full-sequence
        forward separately)."""
        B, P = prompts.shape
        cache = self.model.init_cache(B, self.scfg.max_len)

        def body(carry, t):
            cache = carry
            logits, cache = self.model.decode_step(
                params, cache, {"tokens": jax.lax.dynamic_slice_in_dim(
                    prompts, t, 1, axis=1)}, t)
            return cache, logits

        cache, logits = jax.lax.scan(body, cache, jnp.arange(P))
        return cache, logits[-1][:, -1, :]   # (B, V) at the last position

    def generate(self, params, prompts: jax.Array, n_new: int):
        """Greedy/temperature generation. Returns (B, n_new) tokens."""
        B, P = prompts.shape
        cache, logits = self.prefill(params, prompts)
        rng = jax.random.PRNGKey(self.scfg.seed)

        def sample(logits, rng):  # logits: (B, V)
            if self.scfg.temperature == 0.0:
                return jnp.argmax(logits, axis=-1)
            return jax.random.categorical(
                rng, logits / self.scfg.temperature, axis=-1)

        def body(carry, i):
            cache, logits, rng = carry
            rng, sub = jax.random.split(rng)
            tok = sample(logits, sub)
            new_logits, cache = self.model.decode_step(
                params, cache, {"tokens": tok[:, None]}, P + i)
            return (cache, new_logits[:, -1, :], rng), tok

        (_, _, _), toks = jax.lax.scan(
            body, (cache, logits, rng), jnp.arange(n_new))
        return toks.T  # (B, n_new)


# ====================================================================== #
# Continuous batching
# ====================================================================== #
@dataclasses.dataclass(frozen=True)
class ContinuousConfig:
    """Knobs of the continuous-batching engine.

    ``n_pages`` sizes the pooled slab (page 0 is reserved); ``chunk`` is
    the prefill chunk length (one fused launch each); ``max_batch`` the
    engine rows (max concurrent requests); ``decode_impl`` selects the
    ragged decode engine: ``xla`` (gather + ragged twin), ``pallas`` (the
    paged kernel; TPU only) or ``pallas_interpret`` (CPU numerics check of
    the kernel); ``None`` takes the platform's engine
    (:func:`repro.core.attention.default_impl`).

    ``seq_shards > 1`` shards the engine over the "seq" mesh axis
    (sequence-parallel serving): each shard holds its OWN ``n_pages``-page
    slab pool covering the request slots it owns (contiguous page
    striping — see :class:`repro.serve.paged_cache.PagedLayout`), chunked
    prefill and ragged decode run one launch per shard over per-shard step
    tables / page tables / slot maps, and per-layer partials combine by a
    masked psum. Greedy output stays token-exact vs ``seq_shards=1``.

    ``kv_dtype``: ``"compute"`` stores the slab at the model's compute
    dtype; ``"int8"`` stores it quantized with per-(layer, page) scales
    (paper §6.4 deployment numerics — ~4x less resident KV HBM).

    ``page_sparsity_threshold``: ``None`` disables the stats machinery
    entirely (dense reads, no per-page score tracking). A float enables
    Salca-style page-skip: each decode step every request's per-page max
    attention score (log-space, relative to its row max) updates a
    decayed historical max, and pages whose history falls below the
    threshold are routed to the null page for the next launch — sink
    pages and the current write page are always kept. ``-inf`` keeps the
    machinery on but skips nothing (token-identical to ``None``).
    ``page_stat_decay`` is the per-step additive log-space decay
    (``hist = max(rel_score, hist - decay)``); 0 = pure historical max.

    ``max_queue`` bounds the admission queue (``submit`` raises
    :class:`~repro.ft.faults.QueueFull` beyond it — backpressure); ``None``
    is unbounded. ``preempt`` enables page-pressure preemption: when the
    queue head cannot get pages, the youngest strictly-lower-priority
    decoding request is evicted and later recovered by chunked re-prefill
    (see :meth:`repro.serve.batcher.Batcher.maybe_preempt`)."""
    n_pages: int
    page: int = 8
    chunk: int = 16
    max_batch: int = 4
    decode_impl: Optional[str] = None
    seq_shards: int = 1
    kv_dtype: str = "compute"
    page_sparsity_threshold: Optional[float] = None
    page_stat_decay: float = 0.0
    max_queue: Optional[int] = None
    preempt: bool = True


class ContinuousEngine:
    """Continuous-batching serving over the paged ring-cache slab.

    Greedy decoding only (temperature sampling needs per-request RNG
    streams — a scheduler policy, not an engine limitation). Supports every
    attention-block architecture with a causal 1-D SALO pattern; SSM /
    recurrent / encoder-decoder programs keep the lockstep path.
    """

    def __init__(self, model: Model, ccfg: ContinuousConfig, mesh=None,
                 seq_axis: str = "seq",
                 clock: Optional[Callable[[], float]] = None,
                 obs: Optional[Observability] = None):
        from repro.models import layers as L
        from repro.models import transformer as T
        from repro.serve.batcher import Batcher
        from repro.serve.paged_cache import layout_for_pattern, slab_init

        cfg = model.cfg
        if cfg.mrope_sections is not None or cfg.encoder_decoder:
            raise NotImplementedError("continuous serving: text-only LMs")
        for kind, _ in model.program:
            if kind not in T.ATTN_KINDS:
                raise NotImplementedError(
                    f"continuous serving needs attention blocks, got {kind}")
        self.model = model
        self.ccfg = ccfg
        self.n_shards = ccfg.seq_shards
        self.mesh, self.seq_axis = mesh, seq_axis
        if self.n_shards > 1:
            if mesh is None or dict(zip(mesh.axis_names, mesh.devices.shape)
                                    ).get(seq_axis, 0) != self.n_shards:
                raise ValueError(
                    f"seq_shards={self.n_shards} needs a mesh with a "
                    f"{seq_axis!r} axis of that size, got {mesh}")
        if ccfg.kv_dtype not in ("compute", "int8"):
            raise ValueError(f"kv_dtype must be 'compute' or 'int8', got "
                             f"{ccfg.kv_dtype!r}")
        self.quantized = ccfg.kv_dtype == "int8"
        self.decode_impl = ccfg.decode_impl or default_impl(decode=True)
        self.track_stats = ccfg.page_sparsity_threshold is not None
        self.pattern = L.salo_pattern(cfg, causal=True)
        if self.pattern.is_2d or not self.pattern.causal:
            raise NotImplementedError("continuous serving: causal 1-D only")
        # Observability: registry always live (the engine counters ARE
        # registry counters), tracing opt-in. All hooks are host-side —
        # see the zero-jitted-operand contract in repro.obs.
        self.obs = obs if obs is not None else Observability()
        self.tracer = self.obs.tracer
        self.registry = self.obs.registry
        self.layout = layout_for_pattern(self.pattern, ccfg.page,
                                         shards=self.n_shards)
        self.batcher = Batcher(self.layout, ccfg.n_pages, ccfg.max_batch,
                               max_queue=ccfg.max_queue,
                               clock=clock or time.monotonic, obs=self.obs)
        self.batcher.on_finish = self._release_hook

        lay = self.layout
        self.chunk_pad = -(-max(ccfg.chunk, 1) // ccfg.page) * ccfg.page
        self.nq = self.chunk_pad // ccfg.page
        self.ctx_len = lay.n_sink + lay.ring_cap
        # step-table width: per shard under SP (owned ctx tiles + chunk),
        # the full view on a single device — one compiled step per engine
        self.table_w = (self.ctx_len // self.n_shards
                        + self.chunk_pad) // ccfg.page

        dtype = jnp.dtype(cfg.compute_dtype)
        shard_dims = (self.n_shards,) if self.n_shards > 1 else ()
        self.slabs = {
            f"seg{i}_{kind}": slab_init(n, ccfg.n_pages, ccfg.page,
                                        cfg.n_kv_heads, cfg.hd, dtype,
                                        lead=shard_dims,
                                        quantized=self.quantized)
            for i, (kind, n) in enumerate(model.program)}
        # Per-(request row, logical page) decayed historical max score
        # (log-space, relative to the row max). 0 = "hot" — fresh pages
        # start kept; fully-masked/skipped pages only ever decay.
        self.page_hist = np.zeros(
            (ccfg.max_batch, self.layout.pages_per_req), np.float64)
        from repro.core.scheduler import PAD_SENTINEL
        if self.n_shards > 1:
            self.slot_pos = jnp.full(
                (self.n_shards, ccfg.max_batch, lay.slots_per_shard),
                PAD_SENTINEL, jnp.int32)
            self._shard_state()
        else:
            from repro.serve.paged_cache import empty_positions
            self.slot_pos = empty_positions(ccfg.max_batch, lay)
        self.page_tables = np.zeros((ccfg.max_batch, lay.pages_per_req),
                                    np.int32)
        self.counters = CountersView(self.registry)
        for key in CountersView.KEYS:
            self.registry.counter("serve_" + key)
        # Pages one decode launch walks over all layers (and all shards):
        # the paged kernel's grid runs over every row and page whatever a
        # row holds, and the XLA twin gathers the same pages.
        from repro.kernels.salo_decode import paged_decode_grid
        self.n_layers = sum(n for _, n in model.program)
        rows, steps = paged_decode_grid(ccfg.max_batch, lay.pages_per_shard,
                                        ccfg.page)
        self.decode_pages_walked = self.n_layers * self.n_shards * rows * steps
        # Quantization effectiveness as a registry gauge (once, at init —
        # int8 slabs show ~4x fewer resident bytes than the compute dtype).
        self.registry.set("serve_slab_resident_bytes",
                          self.slab_resident_bytes())
        if self.n_shards > 1:
            self._chunk_jit = jax.jit(self._chunk_sharded)
            self._decode_jit = jax.jit(self._decode_sharded)
        else:
            self._chunk_jit = jax.jit(self._chunk_fn)
            self._decode_jit = jax.jit(self._decode_fn)

    def _shard_state(self):
        """Pin the stacked (shard-leading) device state to the mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P(self.seq_axis))
        self.slabs = jax.device_put(self.slabs, sh)
        self.slot_pos = jax.device_put(self.slot_pos, sh)

    # -------------------------- jitted steps --------------------------- #
    def _run_lm(self, params, slabs, x, seg_step):
        """THE model core shared by the four engine steps (single/sharded
        x chunk/decode): run every stacked segment through ``seg_step``,
        then the final norm + logits head. ``x``: embedded inputs."""
        from repro.models import layers as L

        cfg = self.model.cfg
        new_slabs = {}
        for i, (kind, n) in enumerate(self.model.program):
            key = f"seg{i}_{kind}"
            x, new_slabs[key] = seg_step(kind, params[key], slabs[key], x)
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = L.logits_apply(params["embed"], params.get("lm_head"),
                                x, cfg)
        return logits, new_slabs

    def _chunk_core(self, params, slabs, page_table, ctx_pos, pos_q,
                    tokens, kv_blocks, flags, phys_w, off_w, axis=None):
        """One plan-driven prefill chunk for ONE request (all layers).

        All operands are fixed-shape (chunk padded to ``chunk_pad``, tables
        to ``table_w``), so every chunk of every request reuses one
        compilation. Returns (chunk logits (Cp, V), new slabs). ``axis``:
        running as one shard of the "seq" mesh (per-shard operands,
        cross-shard attention merge)."""
        from repro.models import transformer as T

        cfg = self.model.cfg
        x = self.model._embed_inputs(params, {"tokens": tokens[None]})
        logits, new_slabs = self._run_lm(
            params, slabs, x,
            lambda kind, p, s, x: T.segment_chunk_prefill(
                p, s, x, page_table, ctx_pos[None], pos_q[None], kv_blocks,
                flags, phys_w, off_w, cfg, kind, self.pattern, axis=axis))
        return logits[0], new_slabs

    def _decode_core(self, params, slabs, page_tables, slot_pos, tokens,
                     t_vec, phys_w, off_w, axis=None):
        """One ragged decode step for the WHOLE cohort, write targets
        already resolved (null page for dropped writes). Returns
        (logits (R, V), new slabs, page_m) — ``page_m`` (R, npp), the max
        per-(request, page) score over ALL layers of ALL segments when
        page stats are tracked, else None."""
        from repro.models import transformer as T

        cfg = self.model.cfg
        x = self.model._embed_inputs(params, {"tokens": tokens[:, None]})
        pms = []

        def seg_step(kind, p, s, x):
            res = T.segment_decode_paged(
                p, s, x, page_tables, slot_pos, t_vec, phys_w, off_w, cfg,
                kind, self.pattern, self.decode_impl, axis=axis,
                want_page_stats=self.track_stats)
            if self.track_stats:
                x, new_slab, pm = res
                pms.append(pm)
                return x, new_slab
            return res

        logits, new_slabs = self._run_lm(params, slabs, x, seg_step)
        page_m = jnp.max(jnp.stack(pms), axis=0) if pms else None
        return logits[:, 0, :], new_slabs, page_m

    def _chunk_fn(self, params, slabs, page_table, ctx_pos, pos_q, tokens,
                  kv_blocks, flags, phys_w, off_w):
        return self._chunk_core(params, slabs, page_table, ctx_pos, pos_q,
                                tokens, kv_blocks, flags, phys_w, off_w)

    def _decode_fn(self, params, slabs, page_tables, slot_pos, tokens,
                   t_vec, active, page_keep=None):
        """Every in-flight request advances one token at its own position.
        Inactive rows write to the null page; their logits are discarded.

        ``page_keep`` (R, npp) bool (page-sparsity mode only): pages the
        stats history says to read this step. Dropped pages are routed to
        the null page AND their slots' read positions masked to PAD — the
        persisted ``slot_pos``/page tables are untouched, so a page that
        would come back above threshold later would simply be read again."""
        from repro.core.scheduler import PAD_SENTINEL

        R = tokens.shape[0]
        lay = self.layout
        slot = lay.slot(t_vec)
        phys_w, off_w = lay.write_target(jnp.asarray(page_tables), t_vec,
                                         keep=active)
        rows = jnp.arange(R)
        slot_pos = slot_pos.at[rows, slot].set(
            jnp.where(active, t_vec, slot_pos[rows, slot]))
        pt_read, pos_read = jnp.asarray(page_tables), slot_pos
        if page_keep is not None:
            pt_read = jnp.where(page_keep, pt_read, 0)
            pos_read = jnp.where(jnp.repeat(page_keep, lay.page, axis=1),
                                 slot_pos, PAD_SENTINEL)
        logits, new_slabs, page_m = self._decode_core(
            params, slabs, pt_read, pos_read, tokens, t_vec, phys_w, off_w)
        if self.track_stats:
            return logits, new_slabs, slot_pos, page_m
        return logits, new_slabs, slot_pos

    # --------------------- sharded (seq-parallel) steps ----------------- #
    def _chunk_sharded(self, params, slabs, page_table, ctx_pos, pos_q,
                       tokens, kv_blocks, flags, phys_w, off_w):
        """One prefill chunk under sequence parallelism: ONE launch per
        shard over per-shard tables, per-layer masked-psum merge.

        Shard-leading operands (sharded over the "seq" axis): ``slabs``
        (S, L, n_pages, page, Hkv, hd), ``page_table`` (S, npp_s),
        ``ctx_pos`` (S, S_s), ``kv_blocks``/``flags`` (S, nq, W_s),
        ``phys_w``/``off_w`` (S, Cp) — non-owned chunk positions already
        routed to the null page. ``pos_q``/``tokens`` (Cp,) replicated."""
        from jax.sharding import PartitionSpec as P

        ax = self.seq_axis

        def local(params, slabs, page_table, ctx_pos, kv_blocks, flags,
                  phys_w, off_w, pos_q, tokens):
            slabs = jax.tree.map(lambda a: a[0], slabs)
            logits, new_slabs = self._chunk_core(
                params, slabs, page_table[0], ctx_pos[0], pos_q, tokens,
                kv_blocks[0], flags[0], phys_w[0], off_w[0], axis=ax)
            return logits, jax.tree.map(lambda a: a[None], new_slabs)

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(ax), P(ax), P(ax), P(ax), P(ax), P(ax), P(ax),
                      P(), P()),
            out_specs=(P(), P(ax)), check_vma=False)
        return fn(params, slabs, page_table, ctx_pos, kv_blocks, flags,
                  phys_w, off_w, pos_q, tokens)

    def _decode_sharded(self, params, slabs, page_tables, slot_pos, tokens,
                        t_vec, active, page_keep=None):
        """One ragged decode step under sequence parallelism: each shard
        attends its owned slots (per-shard page tables + slot map), the
        new KV is written only by the written slot's owner, and per-layer
        (out, m, l) partials combine by masked psum — the sharded decode
        slot map. ``page_tables`` (S, R, npp_s), ``slot_pos`` (S, R, S_s);
        tokens/t_vec/active replicated. ``page_keep`` (S, R, npp_s) —
        the host-built keep mask striped like the page tables; each shard
        masks its own reads (writes are never masked). Page stats come
        back shard-stacked (S, R, npp_s); the host re-assembles the
        logical (R, npp) view."""
        from jax.sharding import PartitionSpec as P
        from repro.core.scheduler import PAD_SENTINEL

        ax, lay = self.seq_axis, self.layout
        R = tokens.shape[0]
        page = self.ccfg.page
        sparse = page_keep is not None

        def local(params, slabs, page_tables, slot_pos, tokens, t_vec,
                  active, *rest):
            slabs = jax.tree.map(lambda a: a[0], slabs)
            page_tables, slot_pos = page_tables[0], slot_pos[0]
            idx = jax.lax.axis_index(ax)
            keep, local_slot, phys, off = sharded_write_target(
                lay, page_tables, t_vec, active, idx)
            rows = jnp.arange(R)
            slot_pos = slot_pos.at[rows, local_slot].set(
                jnp.where(keep, t_vec, slot_pos[rows, local_slot]))
            pt_read, pos_read = page_tables, slot_pos
            if sparse:
                pk = rest[0][0]                        # (R, npp_s)
                pt_read = jnp.where(pk, pt_read, 0)
                pos_read = jnp.where(jnp.repeat(pk, page, axis=1),
                                     slot_pos, PAD_SENTINEL)
            logits, new_slabs, page_m = self._decode_core(
                params, slabs, pt_read, pos_read, tokens, t_vec, phys,
                off, axis=ax)
            out = (logits, jax.tree.map(lambda a: a[None], new_slabs),
                   slot_pos[None])
            return out + ((page_m[None],) if self.track_stats else ())

        specs = [P(), P(ax), P(ax), P(ax), P(), P(), P()]
        args = [params, slabs, page_tables, slot_pos, tokens, t_vec, active]
        if sparse:
            specs.append(P(ax))
            args.append(page_keep)
        out_specs = (P(), P(ax), P(ax)) + ((P(ax),) if self.track_stats
                                           else ())
        fn = jax.shard_map(local, mesh=self.mesh, in_specs=tuple(specs),
                           out_specs=out_specs, check_vma=False)
        return fn(*args)

    # --------------------------- host driving -------------------------- #
    def submit(self, prompt, max_new: int, priority: int = 0,
               deadline_s: Optional[float] = None) -> int:
        return self.batcher.submit(prompt, max_new, priority=priority,
                                   deadline_s=deadline_s)

    def _release_hook(self, row: int, pages: np.ndarray):
        """Batcher completion callback: retire the row's page stats and
        (int8 slabs) zero the recycled pages' scales in every slab, so a
        reused page starts from a fresh quantization grid instead of the
        old request's amax."""
        self.page_hist[row] = 0.0
        if not self.quantized:
            return
        S = self.n_shards
        if S > 1:
            p2d = jnp.asarray(pages.reshape(S, self.layout.pages_per_shard))
            idx = jnp.arange(S)[:, None]
            self.slabs = {
                k: s._replace(k_scale=s.k_scale.at[idx, :, p2d].set(0.0),
                              v_scale=s.v_scale.at[idx, :, p2d].set(0.0))
                for k, s in self.slabs.items()}
        else:
            from repro.serve.paged_cache import reset_page_scales
            self.slabs = {
                k: s._replace(k_scale=reset_page_scales(s.k_scale, pages),
                              v_scale=reset_page_scales(s.v_scale, pages))
                for k, s in self.slabs.items()}

    def _admit(self):
        from repro.core.scheduler import PAD_SENTINEL

        for req in self.batcher.admit():
            self.page_tables[req.row] = req.pages
            self.page_hist[req.row] = 0.0
            if self.n_shards > 1:
                self.slot_pos = self.slot_pos.at[:, req.row].set(
                    PAD_SENTINEL)
            else:
                self.slot_pos = self.slot_pos.at[req.row].set(PAD_SENTINEL)

    def _advance_prefill(self, params, req):
        """Run the request's next chunk: ONE fused table-driven pass
        (one per shard under sequence parallelism).

        A fresh request prefills its prompt; a preemption-resumed request
        prefills ``prompt + out[:-1]`` (``req.prefill_tokens``) — the exact
        token stream the evicted KV was built from — through this same
        chunked path, then rejoins decode at its old position without
        re-emitting anything."""
        from repro.core.scheduler import ring_view_positions

        lay, S, trc = self.layout, self.n_shards, self.tracer
        P = req.prefill_len
        c0 = req.prefilled
        clen = min(self.ccfg.chunk, P - c0)
        c1 = c0 + clen
        with trc.span("chunk_plan"):
            plan, pages_read, operands = self._chunk_operands(req, c0, clen)
        with trc.span("chunk_launch"):
            logits, self.slabs = self._chunk_jit(
                params, self.slabs, *(jnp.asarray(a) for a in operands))
        npp = lay.pages_per_req
        self.counters["prefill_launches"] += 1
        self.counters["prefill_tokens"] += clen
        self.counters["prefill_pages_read"] += pages_read
        self.counters["prefill_pages_total"] += npp
        self.registry.inc("serve_prefill_tiles",
                          plan.stats()["executed_tiles"])
        req.prefilled = c1
        if c1 == P:
            with trc.span("first_token"):
                first = int(np.argmax(np.asarray(logits[clen - 1])))
                rvp = ring_view_positions(P, lay.n_sink, lay.ring_cap,
                                          lay.n_global)
                if S > 1:
                    self.slot_pos = self.slot_pos.at[:, req.row].set(
                        jnp.asarray(rvp.reshape(S, lay.slots_per_shard)))
                else:
                    self.slot_pos = self.slot_pos.at[req.row].set(
                        jnp.asarray(rvp))
            self.batcher.to_decode(req, first)

    def _chunk_operands(self, req, c0: int, clen: int):
        """Host half of one prefill chunk: (plan, context pages read, the
        chunk program's operands after ``params`` and the slabs, as host
        arrays)."""
        from repro.core.scheduler import BIG, build_chunk_plan

        lay, page, S = self.layout, self.ccfg.page, self.n_shards
        src = req.prefill_tokens
        c1 = c0 + clen
        plan = build_chunk_plan(self.pattern, c0, clen, n_sink=lay.n_sink,
                                ring_cap=lay.ring_cap, block=page,
                                chunk_pad=self.chunk_pad)
        ctx_pos = plan.view_positions[: self.ctx_len]
        Cp = self.chunk_pad
        pos_q = np.full(Cp, BIG, np.int32)
        pos_q[:clen] = np.arange(c0, c1, dtype=np.int32)
        tokens = np.zeros(Cp, np.int32)
        tokens[:clen] = src[c0:c1]
        # Slab write targets: ring-overwritten positions (chunk longer than
        # the ring) and padded rows route to the null page.
        pos = np.arange(c0, c0 + Cp, dtype=np.int64)
        keep = (np.arange(Cp) < clen) & (
            (pos < lay.n_global) | (pos + lay.ring_cap >= c1))
        slot = np.where(pos < lay.n_global, pos,
                        lay.n_sink + (pos - lay.n_global) % lay.ring_cap)
        # Stats-driven ctx-page skipping for the chunk's READ of the paged
        # context — the chunked-prefill twin of the decode page-keep mask
        # (same history, same Salca rule): pages whose decayed max-score
        # history fell below the threshold are routed to the null page and
        # their positions to PAD_SENTINEL; sink pages and pages the chunk
        # WRITES are unconditionally kept. Fresh/just-admitted requests
        # have an all-zero (hot) history, so plain prefill is untouched —
        # the mask only bites when a request re-prefills with accumulated
        # stats (preemption resume) or the threshold is driven externally.
        npp = lay.pages_per_req
        pt_read, ctx_read = req.pages, ctx_pos
        pages_read = npp
        if self.track_stats:
            rkeep = self.page_hist[req.row] \
                >= self.ccfg.page_sparsity_threshold
            rkeep[: lay.sink_pages] = True
            rkeep[np.unique(slot[keep] // page)] = True
            pages_read = int(rkeep.sum())
            pt_read = np.where(rkeep, req.pages, 0).astype(np.int32)
            ctx_read = np.where(np.repeat(rkeep, page), ctx_pos,
                                BIG).astype(np.int32)
        if S > 1:
            kv, fl = plan.sharded_tables(S, self.nq, self.table_w)
            owner = slot // lay.slots_per_shard
            local = slot % lay.slots_per_shard
            keep_s = keep[None] & (owner[None] == np.arange(S)[:, None])
            phys = np.where(keep_s,
                            req.pages.reshape(S, lay.pages_per_shard)[
                                np.arange(S)[:, None], local[None] // page],
                            0).astype(np.int32)
            off = np.where(keep_s, local[None] % page, 0).astype(np.int32)
            pt_read = pt_read.reshape(S, lay.pages_per_shard)
            ctx_read = ctx_read.reshape(S, lay.slots_per_shard)
        else:
            kv, fl = plan.padded_tables(self.nq, self.table_w)
            phys = np.where(keep, req.pages[slot // page], 0).astype(np.int32)
            off = np.where(keep, slot % page, 0).astype(np.int32)
        return plan, pages_read, (pt_read, ctx_read, pos_q, tokens, kv, fl,
                                  phys, off)

    def _page_keep_mask(self, t_vec, active) -> np.ndarray:
        """(R, npp) bool: pages each request reads this step. History at or
        above the threshold keeps a page; sink pages and the page being
        written are unconditionally kept (Salca's rule: never starve the
        global prefix or the live write point); inactive rows keep-all
        (their reads are already null-routed)."""
        lay = self.layout
        R = self.ccfg.max_batch
        keep = self.page_hist >= self.ccfg.page_sparsity_threshold
        keep[:, :lay.sink_pages] = True
        p = np.asarray(t_vec, np.int64)
        slot = np.where(p < lay.n_global, p,
                        lay.n_sink + (p - lay.n_global) % lay.ring_cap)
        keep[np.arange(R), slot // lay.page] = True
        keep[~np.asarray(active, bool)] = True
        return keep

    def _update_page_stats(self, page_m: np.ndarray, active) -> None:
        """Fold one step's per-page max scores into the decayed history.
        ``rel`` is log-relative to the request's row max, so the history
        is softmax-shift invariant; fully-masked/skipped pages carry
        NEG_INF and therefore only decay."""
        pm = np.asarray(page_m, np.float64)
        rowmax = pm.max(axis=1, keepdims=True)
        rel = pm - np.where(rowmax <= -1e29, 0.0, rowmax)
        upd = np.maximum(rel, self.page_hist - self.ccfg.page_stat_decay)
        act = np.asarray(active, bool)[:, None]
        self.page_hist = np.where(act, upd, self.page_hist)

    def _advance_decode(self, params, reqs):
        R, S = self.ccfg.max_batch, self.n_shards
        lay, trc = self.layout, self.tracer
        with trc.span("ragged_decode", cohort=len(reqs)):
            tokens = np.zeros(R, np.int32)
            t_vec = np.zeros(R, np.int32)
            active = np.zeros(R, bool)
            for req in reqs:
                tokens[req.row] = req.out[-1]
                t_vec[req.row] = req.t_next
                active[req.row] = True
            # pages holding a key each live row attends at this step
            pages_live = self.n_layers * int(
                lay.pages_needed(t_vec[active] + 1).sum())
            with trc.span("decode_launch", rows_live=len(reqs),
                          pages_walked=self.decode_pages_walked,
                          pages_live=pages_live):
                page_tables = (self.page_tables.reshape(
                    R, S, lay.pages_per_shard).transpose(1, 0, 2).copy()
                    if S > 1 else self.page_tables.copy())
                args = [params, self.slabs, page_tables, self.slot_pos,
                        jnp.asarray(tokens), jnp.asarray(t_vec),
                        jnp.asarray(active)]
                if self.track_stats:
                    keep = self._page_keep_mask(t_vec, active)
                    keep_dev = (keep.reshape(R, S, lay.pages_per_shard)
                                .transpose(1, 0, 2).copy() if S > 1
                                else keep)
                    args.append(jnp.asarray(keep_dev))
                out = self._decode_jit(*args)
                logits, self.slabs, self.slot_pos = out[:3]
            with trc.span("logits_to_host"):
                logits = np.asarray(logits)
        if self.track_stats:
            with trc.span("page_stats_fold"):
                page_m = out[3]
                if S > 1:
                    page_m = np.asarray(page_m).transpose(1, 0, 2).reshape(
                        R, lay.pages_per_req)
                self._update_page_stats(np.asarray(page_m), active)
            pages_read = int(keep[active].sum())
        else:
            pages_read = len(reqs) * lay.pages_per_req
        self.counters["decode_launches"] += 1
        self.counters["decode_tokens"] += len(reqs)
        self.counters["decode_pages_read"] += pages_read
        self.counters["decode_pages_total"] += len(reqs) * lay.pages_per_req
        self.counters["decode_pages_walked"] += self.decode_pages_walked
        self.counters["decode_pages_live"] += pages_live
        with trc.span("sample", cohort=len(reqs)):
            for req in reqs:
                self.batcher.record_token(req,
                                          int(np.argmax(logits[req.row])))

    def slab_resident_bytes(self) -> int:
        """Actual bytes of the pooled KV slabs (all segments, K+V, plus
        the per-(layer, page) scale arrays for int8 slabs) — what the
        quantized-serving benchmark reports as resident KV footprint."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(self.slabs))

    def step(self, params) -> bool:
        """One engine iteration: expire overdue requests, admit (preempting
        lower-priority decoders on page pressure), advance every prefilling
        request by one chunk, run one ragged decode step for the decoding
        cohort. Returns True while work remains.

        Truly-oversized requests are rejected at ``submit``, so a stalled
        queue here means transient pressure: if nothing at all is in
        flight and the head still cannot get pages (e.g. an injected
        exhaustion window), the step raises the RECOVERABLE
        :class:`~repro.ft.faults.ResourceExhausted` — the supervisor
        retries instead of the old drain-time dead-end ``RuntimeError``."""
        trc = self.tracer
        with trc.span("engine.step", step=self.counters["engine_steps"]):
            with trc.span("assemble"):
                self.batcher.expire()
                self._admit()
                if self.batcher.queue and self.ccfg.preempt \
                        and self.batcher.maybe_preempt():
                    self._admit()
                pre, dec = self.batcher.assemble()
            if not pre and not dec:
                if self.batcher.queue:
                    raise ResourceExhausted(
                        "admission stalled with nothing in flight: head of "
                        f"queue needs {self.batcher._shard_needs(self.batcher.queue[0])} "
                        f"pages per shard, free "
                        f"{[a.n_free for a in self.batcher.allocs]}")
                return False
            for req in pre:
                with trc.span("chunk_prefill", rid=req.rid,
                              prefilled=req.prefilled):
                    self._advance_prefill(params, req)
            if dec:
                self._advance_decode(params, dec)
            self.counters["engine_steps"] += 1
        return not self.batcher.idle

    def run(self, params) -> Dict[int, np.ndarray]:
        """Drive all submitted requests to completion; returns
        {rid: generated tokens}."""
        while self.step(params):
            pass
        return self.batcher.results()

    # --------------------------- snapshotting --------------------------- #
    def state_dict(self) -> dict:
        """Full serving state as a checkpointable pytree: the KV slabs
        (payload + int8 scales), the device slot map, the host page
        tables / page-stats history, and ONE variable-length uint8 leaf of
        JSON bytes carrying all control-plane state (the metrics registry —
        engine counters included — plus the batcher's entire request
        lifecycle, see ``Batcher.state_dict``). Encoding the control plane
        as bytes keeps
        the tree STRUCTURE fixed (a ``ft.checkpoint.restore`` requirement)
        while its shape tracks queue depth. Host arrays are copied so an
        in-flight snapshot cannot be torn by subsequent steps; a snapshot
        is only taken at step boundaries, where device + host state are
        mutually consistent."""
        ctl = {"counters": dict(self.counters),
               "batcher": self.batcher.state_dict(),
               "metrics": self.registry.state_dict()}
        blob = np.frombuffer(json.dumps(ctl).encode("utf-8"),
                             np.uint8).copy()
        return {"slabs": self.slabs,
                "slot_pos": self.slot_pos,
                "page_tables": self.page_tables.copy(),
                "page_hist": self.page_hist.copy(),
                "control": blob}

    def load_state(self, tree: dict) -> None:
        """Wholesale state replacement from a :meth:`state_dict` image
        (same model + config; the mesh may be a different physical mesh of
        the same "seq" extent — checkpoints are host numpy, re-placed
        here). After this the engine continues exactly where the snapshot
        was taken: greedy outputs match an uninterrupted run token-for-
        token (exactly-once emission; tests/test_serve_ft.py)."""
        slabs, slot_pos = tree["slabs"], tree["slot_pos"]
        if self.n_shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(self.mesh, P(self.seq_axis))
            slabs = jax.device_put(
                jax.tree.map(jnp.asarray, slabs), sh)
            slot_pos = jax.device_put(jnp.asarray(slot_pos), sh)
        else:
            slabs = jax.tree.map(jnp.asarray, slabs)
            slot_pos = jnp.asarray(slot_pos)
        self.slabs = slabs
        self.slot_pos = slot_pos
        self.page_tables = np.asarray(tree["page_tables"],
                                      np.int32).copy()
        self.page_hist = np.asarray(tree["page_hist"], np.float64).copy()
        ctl = json.loads(bytes(np.asarray(tree["control"],
                                          np.uint8)).decode("utf-8"))
        self.counters.update(ctl["counters"])
        if "metrics" in ctl:   # full-registry image; absent in pre-obs
            self.registry.load_state(ctl["metrics"])   # snapshots, whose
        self.batcher.load_state(ctl["batcher"])        # counters loaded above


# ---------------------------------------------------------------------- #
# Decode write routing under sequence parallelism — module-level so the
# static analyzer can probe it over every (position, shard) pair without
# building an engine (repro.analysis.jaxpr_lint.check_write_ownership).
# ---------------------------------------------------------------------- #
def sharded_write_target(lay, page_tables, t_vec, active, idx):
    """Per-shard decode write target: each new token's KV lands on the
    writing shard ONLY if that shard owns the token's logical slot; every
    other shard (and every inactive row) routes the write to the reserved
    null page 0. ``page_tables``: (R, pages_per_shard) this shard's stripe;
    ``t_vec``: (R,) positions; ``idx``: this shard's "seq" axis index.
    Returns ``(keep, local_slot, phys, off)``.
    """
    slot = lay.slot(t_vec)
    keep = active & (lay.slot_owner(slot) == idx)
    local_slot = lay.slot_local(slot)
    phys = jnp.take_along_axis(
        page_tables, (local_slot // lay.page)[:, None], axis=1)[:, 0]
    phys = jnp.where(keep, phys, 0)
    off = jnp.where(keep, local_slot % lay.page, 0)
    return keep, local_slot, phys, off
