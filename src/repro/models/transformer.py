"""Block assembly and the unified causal LM.

An architecture is a *program*: a list of (block_kind, count) segments. Each
segment's layer params are stacked on a leading layer axis and executed with
``jax.lax.scan`` (+ configurable remat) — HLO stays O(1) in depth, which is
what keeps 61-layer/1T-param dry-runs compilable.

Block kinds:
  attn_mlp        pre-norm attention + MLP           (dense archs, whisper enc)
  attn_moe        attention + MoE FFN                (kimi)
  attn_moe_dense  attention + dense MLP + MoE in parallel (arctic)
  ssm             Mamba2 SSD block                   (mamba2)
  rec_mlp         RG-LRU recurrent block + MLP       (recurrentgemma)
  griffin         (rec_mlp, rec_mlp, attn_mlp) supergroup, scanned as one

Decode caches are pytrees stacked the same way, scanned alongside params.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import ssm as SSM


# ===================== per-kind init / apply / decode ==================== #
def block_init(rng, cfg: ModelConfig, kind: str):
    ks = jax.random.split(rng, 6)
    if kind in ("attn_mlp", "attn_mlp_local"):
        return {"ln1": L.rmsnorm_init(cfg.d_model),
                "attn": L.attn_init(ks[0], cfg),
                "ln2": L.rmsnorm_init(cfg.d_model),
                "mlp": L.mlp_init(ks[1], cfg)}
    if kind == "attn_moe":
        return {"ln1": L.rmsnorm_init(cfg.d_model),
                "attn": L.attn_init(ks[0], cfg),
                "ln2": L.rmsnorm_init(cfg.d_model),
                "moe": MOE.moe_init(ks[1], cfg)}
    if kind == "attn_moe_dense":
        return {"ln1": L.rmsnorm_init(cfg.d_model),
                "attn": L.attn_init(ks[0], cfg),
                "ln2": L.rmsnorm_init(cfg.d_model),
                "mlp": L.mlp_init(ks[1], cfg),
                "moe": MOE.moe_init(ks[2], cfg)}
    if kind == "ssm":
        return {"ln1": L.rmsnorm_init(cfg.d_model),
                "ssm": SSM.ssm_init(ks[0], cfg)}
    if kind == "rec_mlp":
        return {"ln1": L.rmsnorm_init(cfg.d_model),
                "rec": RG.rglru_init(ks[0], cfg),
                "ln2": L.rmsnorm_init(cfg.d_model),
                "mlp": L.mlp_init(ks[1], cfg)}
    if kind == "griffin":
        return {"r1": block_init(ks[0], cfg, "rec_mlp"),
                "r2": block_init(ks[1], cfg, "rec_mlp"),
                "a": block_init(ks[2], cfg, "attn_mlp_local")}
    if kind == "xattn":  # whisper decoder block: self + cross + mlp
        return {"ln1": L.rmsnorm_init(cfg.d_model),
                "attn": L.attn_init(ks[0], cfg),
                "ln_x": L.rmsnorm_init(cfg.d_model),
                "xattn": L.attn_init(ks[1], cfg),
                "ln2": L.rmsnorm_init(cfg.d_model),
                "mlp": L.mlp_init(ks[2], cfg)}
    raise ValueError(kind)


def _patterns(cfg: ModelConfig, causal: bool = True):
    import dataclasses

    main = L.salo_pattern(cfg, causal=causal)
    if cfg.recurrent is not None:  # recurrentgemma local-attention third
        local = dataclasses.replace(cfg.salo,
                                    window=cfg.recurrent.local_window)
        localp = L.salo_pattern(cfg, causal=causal, salo=local)
        return {"attn_mlp": main, "attn_mlp_local": localp}
    return {"attn_mlp": main, "attn_mlp_local": main}


def block_apply(p, x, cfg: ModelConfig, kind: str, pattern, positions=None,
                mrope=None, enc_out=None):
    """Full-sequence block. Returns (x, aux) where aux holds MoE losses."""
    aux = {}
    x = constrain(x, "batch", "seq", "embed")
    if kind == "xattn":
        h, _ = L.attn_apply(p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps),
                            cfg, pattern, positions=positions)
        x = x + h
        hx, _ = L.cross_attn_apply(
            p["xattn"], L.rmsnorm(p["ln_x"], x, cfg.norm_eps), enc_out, cfg)
        x = x + hx
        x = x + L.mlp_apply(p["mlp"],
                            L.rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        return x, aux
    if kind == "griffin":
        pats = _patterns(cfg)
        x, a1 = block_apply(p["r1"], x, cfg, "rec_mlp", pattern, positions)
        x, a2 = block_apply(p["r2"], x, cfg, "rec_mlp", pattern, positions)
        x, a3 = block_apply(p["a"], x, cfg, "attn_mlp_local",
                            pats["attn_mlp_local"], positions)
        return x, aux
    if kind in ("attn_mlp", "attn_mlp_local", "attn_moe", "attn_moe_dense"):
        h, _ = L.attn_apply(p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps),
                            cfg, pattern, positions=positions, mrope=mrope)
        x = x + h
        h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "attn_mlp" or kind == "attn_mlp_local":
            x = x + L.mlp_apply(p["mlp"], h2, cfg)
        elif kind == "attn_moe":
            y, aux = MOE.moe_apply(p["moe"], h2, cfg)
            x = x + y
        else:  # arctic: dense residual MLP in parallel with MoE
            y, aux = MOE.moe_apply(p["moe"], h2, cfg)
            x = x + y + L.mlp_apply(p["mlp"], h2, cfg)
        return x, aux
    if kind == "ssm":
        x = x + SSM.ssm_apply(p["ssm"], L.rmsnorm(p["ln1"], x, cfg.norm_eps),
                              cfg)
        return x, aux
    if kind == "rec_mlp":
        x = x + RG.rglru_apply(p["rec"],
                               L.rmsnorm(p["ln1"], x, cfg.norm_eps), cfg)
        x = x + L.mlp_apply(p["mlp"],
                            L.rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        return x, aux
    raise ValueError(kind)


# --------------------------- decode caches ------------------------------ #
def block_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype):
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    if cfg.salo.ring_cache:  # SALO ring cache: O(window) slots
        max_len = min(max_len, cfg.salo.window + cfg.salo.n_global)
    if kind == "griffin":
        return {"r1": block_cache_init(cfg, "rec_mlp", batch, max_len, dtype),
                "r2": block_cache_init(cfg, "rec_mlp", batch, max_len, dtype),
                "a": block_cache_init(cfg, "attn_mlp_local", batch,
                                      max_len, dtype)}
    if kind == "xattn":
        return {"k": jnp.zeros((batch, max_len, Hkv, hd), dtype),
                "v": jnp.zeros((batch, max_len, Hkv, hd), dtype),
                # cross K/V filled at prefill from the encoder output
                "xk": jnp.zeros((batch, cfg.n_audio_frames, Hkv, hd), dtype),
                "xv": jnp.zeros((batch, cfg.n_audio_frames, Hkv, hd), dtype)}
    if kind.startswith("attn"):
        return {"k": jnp.zeros((batch, max_len, Hkv, hd), dtype),
                "v": jnp.zeros((batch, max_len, Hkv, hd), dtype)}
    if kind == "ssm":
        d_inner, H, N, P = SSM._dims(cfg)
        W = cfg.ssm.conv_width
        return {"conv": jnp.zeros((batch, W - 1, d_inner + 2 * N), dtype),
                "state": jnp.zeros((batch, H, N, P), jnp.float32)}
    if kind == "rec_mlp":
        dr = RG._d_rnn(cfg)
        W = cfg.recurrent.conv_width
        return {"conv": jnp.zeros((batch, W - 1, dr), dtype),
                "state": jnp.zeros((batch, dr), jnp.float32)}
    raise ValueError(kind)


def block_decode(p, cache, x_t, t, cfg: ModelConfig, kind: str, pattern,
                 positions=None, mrope=None):
    """One-token decode. Returns (x_t, cache)."""
    if kind == "xattn":
        h, ck, cv = L.attn_decode(p["attn"],
                                  L.rmsnorm(p["ln1"], x_t, cfg.norm_eps),
                                  cache["k"], cache["v"], t, cfg, pattern,
                                  positions=positions)
        x_t = x_t + h
        x_t = x_t + L.cross_attn_decode(
            p["xattn"], L.rmsnorm(p["ln_x"], x_t, cfg.norm_eps),
            cache["xk"], cache["xv"], cfg)
        x_t = x_t + L.mlp_apply(p["mlp"],
                                L.rmsnorm(p["ln2"], x_t, cfg.norm_eps), cfg)
        return x_t, {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}
    if kind == "griffin":
        pats = _patterns(cfg)
        x_t, c1 = block_decode(p["r1"], cache["r1"], x_t, t, cfg, "rec_mlp",
                               pattern)
        x_t, c2 = block_decode(p["r2"], cache["r2"], x_t, t, cfg, "rec_mlp",
                               pattern)
        x_t, c3 = block_decode(p["a"], cache["a"], x_t, t, cfg,
                               "attn_mlp_local", pats["attn_mlp_local"])
        return x_t, {"r1": c1, "r2": c2, "a": c3}
    if kind.startswith("attn"):
        h, ck, cv = L.attn_decode(p["attn"],
                                  L.rmsnorm(p["ln1"], x_t, cfg.norm_eps),
                                  cache["k"], cache["v"], t, cfg, pattern,
                                  positions=positions, mrope=mrope)
        return _ffn_residual(p, x_t + h, cfg, kind), {"k": ck, "v": cv}
    if kind == "ssm":
        y, conv, st = SSM.ssm_decode(p["ssm"],
                                     L.rmsnorm(p["ln1"], x_t, cfg.norm_eps),
                                     cache["conv"], cache["state"], cfg)
        return x_t + y, {"conv": conv, "state": st}
    if kind == "rec_mlp":
        y, conv, st = RG.rglru_decode(p["rec"],
                                      L.rmsnorm(p["ln1"], x_t, cfg.norm_eps),
                                      cache["conv"], cache["state"], cfg)
        x_t = x_t + y
        x_t = x_t + L.mlp_apply(p["mlp"],
                                L.rmsnorm(p["ln2"], x_t, cfg.norm_eps), cfg)
        return x_t, {"conv": conv, "state": st}
    raise ValueError(kind)


# ----------------- continuous-batching serve block paths ---------------- #
ATTN_KINDS = ("attn_mlp", "attn_mlp_local", "attn_moe", "attn_moe_dense")


def _ffn_residual(p, x, cfg: ModelConfig, kind: str):
    """The post-attention FFN residual shared by every attn block kind
    (MoE aux losses are dropped — serving never backprops)."""
    h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if kind in ("attn_mlp", "attn_mlp_local"):
        return x + L.mlp_apply(p["mlp"], h2, cfg)
    if kind == "attn_moe":
        y, _ = MOE.moe_apply(p["moe"], h2, cfg)
        return x + y
    if kind == "attn_moe_dense":
        y, _ = MOE.moe_apply(p["moe"], h2, cfg)
        return x + y + L.mlp_apply(p["mlp"], h2, cfg)
    raise ValueError(f"continuous serving supports attention block kinds "
                     f"{ATTN_KINDS}, got {kind!r}")


def block_chunk_prefill(p, x, ctx_k, ctx_v, ctx_pos, pos_q, kv_blocks,
                        flags, cfg: ModelConfig, kind: str, pattern,
                        axis=None):
    """One prompt chunk through one block. Returns (x, k_chunk, v_chunk)."""
    h, k_c, v_c = L.attn_chunk_prefill(
        p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), ctx_k, ctx_v,
        ctx_pos, pos_q, kv_blocks, flags, cfg, pattern, axis=axis)
    return _ffn_residual(p, x + h, cfg, kind), k_c, v_c


def block_decode_paged(p, x_t, k_slab, v_slab, page_tables, slot_pos, t_vec,
                       phys_w, off_w, cfg: ModelConfig, kind: str, pattern,
                       impl: str, axis=None, k_scale=None, v_scale=None,
                       want_page_stats: bool = False, live_pages=None):
    """Ragged one-token decode through one block against the paged slab.
    Returns (x, k_slab, v_slab, k_scale, v_scale, page_m) — scales/stats
    ``None`` unless the slab is int8 / stats were requested."""
    h, k_slab, v_slab, k_scale, v_scale, page_m = L.attn_decode_paged(
        p["attn"], L.rmsnorm(p["ln1"], x_t, cfg.norm_eps), k_slab, v_slab,
        page_tables, slot_pos, t_vec, phys_w, off_w, cfg, pattern, impl,
        axis=axis, k_scale=k_scale, v_scale=v_scale,
        want_page_stats=want_page_stats, live_pages=live_pages)
    return (_ffn_residual(p, x_t + h, cfg, kind), k_slab, v_slab,
            k_scale, v_scale, page_m)


def segment_chunk_prefill(params, slab, x, page_table, ctx_pos, pos_q,
                          kv_blocks, flags, phys_w, off_w, cfg: ModelConfig,
                          kind: str, pattern, axis=None):
    """Scan one stacked segment over a prompt chunk, writing the slab.

    ``slab``: :class:`repro.serve.paged_cache.PagedSlab` with leading layer
    axis; ``page_table``: (npp,) the request's pages; ``phys_w``/``off_w``:
    (Cp,) precomputed slab write targets for the chunk positions (ring-
    overwritten and padded positions already routed to the null page).
    Returns (x, new slab).

    ``axis``: sequence-parallel serving — the slab / page table / ctx
    positions / step tables / write targets are this shard's slice, the
    chunk activations and fresh chunk KV are replicated, and each layer's
    attention merges its partial across the mesh axis (one cross-shard
    combine per layer inside the scan).

    int8 slabs (``slab.quantized``) thread each layer's per-page scales
    through the scan: the ctx view is dequantized at the gather and the
    fresh chunk KV is quantized at the write-back (monotone per-page
    scale growth).
    """
    from repro.serve.paged_cache import (PagedSlab, gather_view,
                                         quant_slab_write)

    npp = page_table.shape[0]
    page = slab.k.shape[2]
    quant = slab.quantized

    def body(carry, inp):
        x = carry
        if quant:
            layer_params, (k_l, v_l, ks_l, vs_l) = inp
            ctx_k, ctx_v = gather_view(k_l, v_l, page_table[None],
                                       ks_l, vs_l, x.dtype)
        else:
            layer_params, (k_l, v_l) = inp
            Hkv, hd = k_l.shape[-2], k_l.shape[-1]
            ctx_k = k_l[page_table].reshape(1, npp * page, Hkv, hd)
            ctx_v = v_l[page_table].reshape(1, npp * page, Hkv, hd)
        x, k_c, v_c = block_chunk_prefill(
            layer_params, x, ctx_k, ctx_v, ctx_pos, pos_q, kv_blocks,
            flags, cfg, kind, pattern, axis=axis)
        if quant:
            k_l, v_l, ks_l, vs_l = quant_slab_write(
                k_l, v_l, ks_l, vs_l, phys_w, off_w, k_c[0], v_c[0])
            return x, (k_l, v_l, ks_l, vs_l)
        k_l = k_l.at[phys_w, off_w].set(k_c[0].astype(k_l.dtype))
        v_l = v_l.at[phys_w, off_w].set(v_c[0].astype(v_l.dtype))
        return x, (k_l, v_l)

    xs = ((params, (slab.k, slab.v, slab.k_scale, slab.v_scale)) if quant
          else (params, (slab.k, slab.v)))
    x, new = jax.lax.scan(body, x, xs)
    return x, PagedSlab(*new)


def segment_decode_paged(params, slab, x_t, page_tables, slot_pos, t_vec,
                         phys_w, off_w, cfg: ModelConfig, kind: str,
                         pattern, impl: str, axis=None,
                         want_page_stats: bool = False, live_pages=None):
    """Scan one stacked segment for one ragged decode step. Returns
    (x_t, new slab) — plus ``page_m`` (R, npp), the max masked score over
    the segment's layers per (request, logical page), when
    ``want_page_stats`` (the engine's page-sparsity statistic). int8
    slabs thread per-layer scales through the scan exactly like
    :func:`segment_chunk_prefill`. ``axis``: sequence-parallel serving
    (per-shard slab slice + cross-shard partial merge per layer, see
    :func:`repro.models.layers.attn_decode_paged`, which also defines
    ``live_pages``)."""
    from repro.core.renorm import NEG_INF
    from repro.serve.paged_cache import PagedSlab

    quant = slab.quantized

    def body(carry, inp):
        x_t, pm_acc = carry
        if quant:
            layer_params, (k_l, v_l, ks_l, vs_l) = inp
        else:
            layer_params, (k_l, v_l) = inp
            ks_l = vs_l = None
        x_t, k_l, v_l, ks_l, vs_l, pm = block_decode_paged(
            layer_params, x_t, k_l, v_l, page_tables, slot_pos, t_vec,
            phys_w, off_w, cfg, kind, pattern, impl, axis=axis,
            k_scale=ks_l, v_scale=vs_l, want_page_stats=want_page_stats,
            live_pages=live_pages)
        if want_page_stats:
            pm_acc = jnp.maximum(pm_acc, pm)
        return ((x_t, pm_acc),
                (k_l, v_l, ks_l, vs_l) if quant else (k_l, v_l))

    R, npp = page_tables.shape
    pm0 = jnp.full((R, npp), NEG_INF, jnp.float32)
    xs = ((params, (slab.k, slab.v, slab.k_scale, slab.v_scale)) if quant
          else (params, (slab.k, slab.v)))
    (x_t, pm), new = jax.lax.scan(body, (x_t, pm0), xs)
    slab = PagedSlab(*new)
    return (x_t, slab, pm) if want_page_stats else (x_t, slab)


# ========================= programs & segments ========================== #
def make_program(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """(block_kind, count) segments; each segment is one lax.scan."""
    if cfg.family == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        n_groups, rem = divmod(cfg.n_layers, 3)
        prog = [("griffin", n_groups)]
        if rem:
            prog.append(("rec_mlp", rem))
        return prog
    if cfg.encoder_decoder:
        return [("xattn", cfg.n_layers)]   # decoder stack; encoder separate
    if cfg.family == "moe":
        m = cfg.moe
        prog = []
        if m.first_k_dense:
            prog.append(("attn_mlp", m.first_k_dense))
        kind = "attn_moe_dense" if m.dense_residual else "attn_moe"
        prog.append((kind, cfg.n_layers - m.first_k_dense))
        return prog
    return [("attn_mlp", cfg.n_layers)]  # dense / vlm / audio backbones


def segment_init(rng, cfg: ModelConfig, kind: str, n: int):
    rngs = jax.random.split(rng, n)
    return jax.vmap(lambda r: block_init(r, cfg, kind))(rngs)


def _remat(f, cfg: ModelConfig):
    if cfg.remat == "none":
        return f
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(f, policy=policy)
    return jax.checkpoint(f)


def segment_apply(params, x, cfg: ModelConfig, kind: str, pattern,
                  positions=None, mrope=None, enc_out=None):
    """Scan a stacked segment. Returns (x, summed aux).

    Activations stay constrained to ("batch", "seq", "embed") through every
    block, so under a long-context cell's rules (``"seq"`` mapped to a mesh
    axis) the whole stack runs sequence-parallel: norms/MLPs shard
    elementwise and attention takes the ShardedPlan halo-exchange path
    inside :func:`repro.models.layers.attn_apply`.
    """
    def body(carry, layer_params):
        y, aux = block_apply(layer_params, carry, cfg, kind, pattern,
                             positions=positions, mrope=mrope,
                             enc_out=enc_out)
        return y, aux

    body = _remat(body, cfg)
    x, auxs = jax.lax.scan(body, x, params)
    aux = jax.tree.map(lambda a: jnp.sum(a), auxs) if auxs else {}
    return x, aux


def segment_decode(params, caches, x_t, t, cfg: ModelConfig, kind: str,
                   pattern, positions=None, mrope=None):
    def body(carry, inp):
        layer_params, layer_cache = inp
        y, new_cache = block_decode(layer_params, layer_cache, carry, t, cfg,
                                    kind, pattern, positions=positions,
                                    mrope=mrope)
        return y, new_cache

    x_t, new_caches = jax.lax.scan(body, x_t, (params, caches))
    return x_t, new_caches
