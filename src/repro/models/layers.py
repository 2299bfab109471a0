"""Shared layer library: norms, RoPE/M-RoPE, projections, MLPs, attention.

Pure-functional modules: ``*_init(rng, ...) -> params dict`` and
``*_apply(params, x, ...) -> y``. Parameter key names are load-bearing — the
path-regex sharding rules in :mod:`repro.dist.sharding` match on them.

The attention layer is where the paper's technique enters every model: QKV
projection -> RoPE -> :func:`repro.core.hybrid_attention` with the arch's
:class:`SALOConfig` pattern -> output projection.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SALOConfig
from repro.core import (HybridSparsePattern, causal_sliding_window,
                        hybrid_attention, hybrid_decode_attention, longformer,
                        full)
from repro.core.attention import default_impl, hybrid_chunk_attention
from repro.core.scheduler import PAD_SENTINEL
from repro.dist.sharding import constrain


def dt(cfg: ModelConfig, kind: str = "param"):
    return jnp.dtype(cfg.param_dtype if kind == "param" else cfg.compute_dtype)


# --------------------------- init helpers ------------------------------ #
def dense_init(rng, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out)) * std).astype(dtype)


# ------------------------------ norms ---------------------------------- #
def rmsnorm_init(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}  # gemma-style (1 + scale)


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
    return y.astype(x.dtype)


# ------------------------------- RoPE ----------------------------------- #
def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         sections: Optional[tuple] = None) -> jax.Array:
    """Rotary embedding. x: (B, S, H, D); positions: (B, S) or (3, B, S) for
    M-RoPE with ``sections=(t, h, w)`` splitting D//2 frequency pairs."""
    B, S, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if sections is None:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    else:
        t, h, w = sections
        assert t + h + w == half, (sections, half)
        # Each frequency pair uses the position component of its section.
        sec = jnp.concatenate([jnp.zeros(t, jnp.int32),
                               jnp.ones(h, jnp.int32),
                               jnp.full((w,), 2, jnp.int32)])
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32).transpose(1, 2, 0),  # (B,S,3)
            jnp.broadcast_to(sec, (B, S, half)).astype(jnp.int32), axis=-1)
        ang = pos * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------- MLPs ----------------------------------- #
def mlp_init(rng, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {"w_in": dense_init(ks[0], d, f, dt(cfg)),
         "w_out": dense_init(ks[1], f, d, dt(cfg))}
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[2], d, f, dt(cfg))
    return p


def mlp_apply(p, x, cfg: ModelConfig):
    h = x @ p["w_in"].astype(x.dtype)
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * h
    elif cfg.act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"].astype(x.dtype)) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", "seq", "ffn")
    return h @ p["w_out"].astype(x.dtype)


# ---------------------------- attention --------------------------------- #
def salo_pattern(cfg: ModelConfig, causal: bool = True,
                 salo: Optional[SALOConfig] = None) -> HybridSparsePattern:
    """The pattern this architecture's attention layers run (DESIGN.md §5)."""
    s = salo or cfg.salo
    if not s.enabled:
        return full(causal=causal)
    if s.bidirectional and not causal:
        return longformer(s.window, n_global=s.n_global)
    return causal_sliding_window(s.window, n_sinks=s.n_global,
                                 dilation=s.dilation)


def attn_init(rng, cfg: ModelConfig):
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(rng, 4)
    return {"wq": dense_init(ks[0], d, H * hd, dt(cfg)),
            "wk": dense_init(ks[1], d, Hkv * hd, dt(cfg)),
            "wv": dense_init(ks[2], d, Hkv * hd, dt(cfg)),
            "wo": dense_init(ks[3], H * hd, d, dt(cfg))}


def attn_qkv(p, x, cfg: ModelConfig, positions, mrope=None):
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, Hkv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, Hkv, hd)
    q = rope(q, positions, cfg.rope_theta, mrope)
    k = rope(k, positions, cfg.rope_theta, mrope)
    return q, k, v


def attn_apply(p, x, cfg: ModelConfig, pattern: HybridSparsePattern,
               positions=None, mrope=None, kv=None):
    """Full-sequence attention (train / prefill).

    kv: optional externally-provided (k, v) — used for cross-attention.
    Returns (out, (k, v)) so prefill can populate caches.
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = attn_qkv(p, x, cfg, positions, mrope)
    if kv is not None:
        k, v = kv
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    # When the cell rules map "seq" to a mesh axis (long-context SP),
    # hybrid_attention routes to the ShardedPlan shard_map path — the same
    # fused engines with ppermute halo exchange instead of a K/V
    # all-gather (repro.dist.sharded_plan).
    out = hybrid_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), pattern, impl=cfg.salo.impl,
        block_q=cfg.salo.block_q, block_k=cfg.salo.block_k)
    out = out.transpose(0, 2, 1, 3)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    out = out.reshape(B, S, cfg.n_heads * cfg.hd)
    return out @ p["wo"].astype(x.dtype), (k, v)


def attn_decode(p, x_t, cache_k, cache_v, t, cfg: ModelConfig,
                pattern: HybridSparsePattern, cache_positions=None,
                positions=None, mrope=None):
    """One-token decode. x_t: (B, 1, d); caches: (B, S, Hkv, hd); t scalar.

    Writes the new KV at slot ``t`` (full-cache baseline) unless the caller
    manages slots itself (SALO ring cache passes ``cache_positions``)."""
    B = x_t.shape[0]
    if positions is None:
        # M-RoPE text decode: all three components advance together.
        shape = (3, B, 1) if mrope is not None else (B, 1)
        positions = jnp.full(shape, t, jnp.int32)
    q, k, v = attn_qkv(p, x_t, cfg, positions, mrope)
    if cfg.salo.ring_cache and cache_positions is None:
        # SALO ring cache: slots = [sinks | ring of
        # size w]; slot j >= g holds the most recent position p <= t with
        # (p - g) mod w == j - g.
        w_, g_ = cfg.salo.window, max(cfg.salo.n_global, 0)
        S_slots = cache_k.shape[1]
        tt = jnp.asarray(t, jnp.int32)
        slot = jnp.where(tt < g_, tt, g_ + (tt - g_) % w_)
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot,
                                                      axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot,
                                                      axis=1)
        j = jnp.arange(S_slots, dtype=jnp.int32)
        pos_ring = tt - ((tt - j) % w_)
        pos = jnp.where(j < g_, j, pos_ring)
        # unwritten ring slots (pos < g) mask out via the padding sentinel
        cache_positions = jnp.where((j >= g_) & (pos < g_),
                                    jnp.int32(PAD_SENTINEL), pos)
    elif cache_positions is None:  # full cache: slot == position
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, t, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, t, axis=1)
    out = hybrid_decode_attention(
        q.transpose(0, 2, 1, 3), cache_k.transpose(0, 2, 1, 3),
        cache_v.transpose(0, 2, 1, 3), t, pattern,
        cache_positions=cache_positions,
        slice_window=cfg.salo.decode_slice and not cfg.salo.ring_cache)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * cfg.hd)
    return out @ p["wo"].astype(x_t.dtype), cache_k, cache_v


# ------------------- continuous-batching serve paths -------------------- #
def attn_chunk_prefill(p, x_chunk, ctx_k, ctx_v, ctx_pos, pos_q, kv_blocks,
                       flags, cfg: ModelConfig,
                       pattern: HybridSparsePattern, axis=None):
    """One prompt chunk through a layer's attention (plan-driven prefill).

    x_chunk: (1, Cp, d) chunk activations; ctx_k/ctx_v: (1, S_req, Hkv, hd)
    the request's paged KV view (sinks + ring); ctx_pos: (1, S_req) live
    slot positions; pos_q: (1, Cp) chunk positions (PAD_SENTINEL on padded
    rows); kv_blocks/flags: (nq, W) ChunkPlan step tables. Returns
    (out, k_chunk, v_chunk) — the fresh chunk KV for the caller's slab
    write-back (the paper's window stream, cached as it flows by).

    ``axis``: sequence-parallel serving — this shard's ctx view/positions
    and per-shard tables cover only the slots it owns (plus the replicated
    chunk on the chunk-owner shard); the partial (out, m, l) is merged
    across the mesh axis before the output projection."""
    B, Cp, _ = x_chunk.shape
    rope_pos = jnp.where(pos_q < PAD_SENTINEL, pos_q, 0)
    q, k, v = attn_qkv(p, x_chunk, cfg, rope_pos)
    k_view = jnp.concatenate([ctx_k.astype(k.dtype), k], axis=1)
    v_view = jnp.concatenate([ctx_v.astype(v.dtype), v], axis=1)
    pos_k = jnp.concatenate([ctx_pos, pos_q], axis=1)
    if axis is None:
        out = hybrid_chunk_attention(
            q.transpose(0, 2, 1, 3), k_view.transpose(0, 2, 1, 3),
            v_view.transpose(0, 2, 1, 3), pos_q, pos_k, kv_blocks, flags,
            pattern)
    else:
        from repro.dist.sharded_plan import masked_psum_merge
        out, m, l = hybrid_chunk_attention(
            q.transpose(0, 2, 1, 3), k_view.transpose(0, 2, 1, 3),
            v_view.transpose(0, 2, 1, 3), pos_q, pos_k, kv_blocks, flags,
            pattern, return_state=True)
        # partials are f32; ONE round to the compute dtype, post-merge
        out = masked_psum_merge(out, m, l, axis).astype(x_chunk.dtype)
    out = out.transpose(0, 2, 1, 3).reshape(B, Cp, cfg.n_heads * cfg.hd)
    return out @ p["wo"].astype(x_chunk.dtype), k, v


def attn_decode_paged(p, x_t, k_slab, v_slab, page_tables, slot_pos, t_vec,
                      phys_w, off_w, cfg: ModelConfig,
                      pattern: HybridSparsePattern,
                      impl: Optional[str] = None,
                      axis=None, k_scale=None, v_scale=None,
                      want_page_stats: bool = False, live_pages=None):
    """Ragged one-token decode against ONE layer's pooled paged slab.

    x_t: (R, 1, d) — one token per engine row; k_slab/v_slab:
    (n_pages, page, Hkv, hd); page_tables: (R, npp); slot_pos: (R, S_req)
    live positions (already updated for this step's writes); t_vec: (R,)
    per-request positions; phys_w/off_w: (R,) slab write targets (null page
    for inactive rows). Returns
    ``(out, k_slab, v_slab, k_scale, v_scale, page_m)``.

    ``k_scale``/``v_scale``: the layer's per-page (n_pages,) f32 dequant
    scales — present iff the slab is int8. The fresh token KV is
    quantized into its page (:func:`~repro.serve.paged_cache
    .quant_slab_write`, monotone scale growth) and reads dequantize
    per page — in-kernel for the Pallas impls, via the dequantizing
    ``gather_view`` for the XLA twin. Returned ``k_scale``/``v_scale``
    are the updated vectors (``None`` for fp slabs).

    ``want_page_stats=True`` makes ``page_m`` (R, npp) the max masked
    score this request produced against each of its logical pages
    (NEG_INF for fully-masked pages) — the engine's page-sparsity
    statistic; otherwise ``page_m`` is ``None``.

    ``live_pages``: (R,) int32, the leading logical pages of each row
    that can hold an attended key (0 for inactive rows; default all): the
    Pallas kernel fetches and folds only those. The XLA twin gathers
    every page whatever it holds.

    ``axis``: sequence-parallel serving — slab/page_tables/slot_pos are
    this shard's slice (npp = pages_per_shard; non-owned writes already
    routed to the null page via phys_w), so the decode launch covers only
    the owned slots and the (out, m, l) partial is merged across the mesh
    axis (one ragged launch per shard, masked-psum combine)."""
    from repro.serve.paged_cache import (gather_view, quant_slab_write,
                                         slab_write)

    R = x_t.shape[0]
    quant = k_scale is not None
    impl = impl or default_impl(decode=True)
    q, k, v = attn_qkv(p, x_t, cfg, t_vec[:, None])
    if quant:
        k_slab, v_slab, k_scale, v_scale = quant_slab_write(
            k_slab, v_slab, k_scale, v_scale, phys_w, off_w, k[:, 0], v[:, 0])
    else:
        k_slab, v_slab = slab_write(k_slab, v_slab, phys_w, off_w,
                                    k[:, 0], v[:, 0])
    qt = q.transpose(0, 2, 1, 3)                       # (R, H, 1, hd)
    state = axis is not None
    page_m = None
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.salo_decode import salo_paged_decode
        res = salo_paged_decode(qt, k_slab, v_slab, page_tables, slot_pos,
                                t_vec, pattern=pattern,
                                interpret=(impl == "pallas_interpret"),
                                return_state=state, k_scale=k_scale,
                                v_scale=v_scale,
                                return_page_stats=want_page_stats,
                                live_pages=live_pages)
        if want_page_stats:
            res, page_m = res[:-1], res[-1]
            res = res if state else res[0]
    else:
        k_req, v_req = gather_view(
            k_slab, v_slab, page_tables,
            *((k_scale, v_scale, x_t.dtype) if quant else ()))
        res = hybrid_decode_attention(
            qt, k_req.transpose(0, 2, 1, 3), v_req.transpose(0, 2, 1, 3),
            t_vec, pattern, cache_positions=slot_pos, return_state=state,
            return_slot_m=want_page_stats)
        if want_page_stats:
            res, slot_m = (res[:-1], res[-1])
            res = res if state else res[0]
            page = k_slab.shape[1]
            npp = page_tables.shape[1]
            page_m = slot_m.reshape(R, npp, page).max(axis=-1)
    if state:
        from repro.dist.sharded_plan import masked_psum_merge
        out, m, l = res
        # partials are f32; ONE round to the compute dtype, post-merge
        out = masked_psum_merge(out, m, l, axis).astype(x_t.dtype)
    else:
        out = res
    out = out.transpose(0, 2, 1, 3).reshape(R, 1, cfg.n_heads * cfg.hd)
    return (out @ p["wo"].astype(x_t.dtype), k_slab, v_slab,
            k_scale, v_scale, page_m)


# ------------------------------ embedding -------------------------------- #
def embed_init(rng, cfg: ModelConfig):
    # std 1/sqrt(d): embed_apply rescales by sqrt(d) to unit variance, and
    # the (tied) readout keeps logits O(1) at init.
    std = cfg.d_model ** -0.5
    w = (jax.random.normal(rng, (cfg.vocab_size, cfg.d_model)) * std)
    return {"w": w.astype(dt(cfg))}


def embed_apply(p, tokens, cfg: ModelConfig):
    x = jnp.take(p["w"], tokens, axis=0).astype(dt(cfg, "compute"))
    # NB: python float (weak type) — a numpy scalar would promote bf16->f32.
    return x * float(np.sqrt(cfg.d_model))  # gemma-style scaling


def logits_apply(p_embed, p_head, x, cfg: ModelConfig):
    w = (p_embed["w"] if cfg.tie_embeddings else p_head["w"]).astype(x.dtype)
    logits = x @ w.T
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def cross_entropy(logits, targets, mask=None):
    """logits (B,S,V), targets (B,S) int32. Mean NLL over mask."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------- cross attention ----------------------------- #
def cross_attn_apply(p, x, enc_out, cfg: ModelConfig):
    """Encoder-decoder cross attention (dense over the encoder sequence —
    n_enc is short for the audio stub; no RoPE, whisper-style).

    Rectangular (S_q != S_kv), so computed directly rather than through the
    square-pattern SALO engines."""
    B, S, _ = x.shape
    Se = enc_out.shape[1]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, hd)
    k = (enc_out @ p["wk"].astype(x.dtype)).reshape(B, Se, Hkv, hd)
    v = (enc_out @ p["wv"].astype(x.dtype)).reshape(B, Se, Hkv, hd)
    kr, vr = k, v
    if Hkv != H:
        kr = jnp.repeat(k, H // Hkv, axis=2)
        vr = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, vr.astype(w.dtype))
    out = out.astype(x.dtype).reshape(B, S, H * hd)
    return out @ p["wo"].astype(x.dtype), (k, v)


def cross_attn_decode(p, x_t, k_enc, v_enc, cfg: ModelConfig):
    """Decode-time cross attention with precomputed encoder K/V."""
    B = x_t.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x_t @ p["wq"].astype(x_t.dtype)).reshape(B, 1, H, hd)
    if Hkv != H:
        k_enc = jnp.repeat(k_enc, H // Hkv, axis=2)
        v_enc = jnp.repeat(v_enc, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_enc,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v_enc.astype(w.dtype))
    out = out.astype(x_t.dtype).reshape(B, 1, H * hd)
    return out @ p["wo"].astype(x_t.dtype)


def sinusoidal_pos(S: int, d: int, dtype) -> jnp.ndarray:
    """Whisper-style sinusoidal positional embedding (S, d)."""
    half = d // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    ang = np.arange(S)[:, None] * freqs[None, :]
    pe = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(pe, dtype)
