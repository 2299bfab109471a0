"""Fixed-point quantization simulation (paper §6.4).

SALO quantizes Q, K, V to **int8 with 4 fractional bits** (scale 2^-4, range
[-8, 7.9375]) and produces 16-bit outputs; the paper shows accuracy within
noise of fp32 after quantization-aware finetuning (Table 3).

We simulate the exact fixed-point grid (not per-tensor dynamic scaling — the
ASIC's format is static) plus an optional dynamic per-tensor variant that a
TPU int8 path would use. ``quantized_attention`` runs any attention engine
on the quantized grid to measure the end-to-end output error (Table 3 analog
in ``benchmarks/paper_claims.py::table3_quantization``).

STE (straight-through estimator) gradients make the simulation usable inside
quantization-aware finetuning, mirroring the paper's QAT setup.

The serving stack stores the paged KV slab in this int8 format with
*per-page* dynamic scales (:func:`group_q8` / :func:`group_dequant`, used by
``repro.serve.paged_cache``) — the deployment-side counterpart of the
paper's Table-3 numerics: one f32 scale per (layer, page) rides next to the
page table, and decode dequantizes page tiles on the fly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

FRAC_BITS = 4
SCALE = 2.0 ** FRAC_BITS  # paper: 4-bit fraction
QMIN, QMAX = -128, 127


@jax.custom_vjp
def fixed_point_q8(x: jax.Array) -> jax.Array:
    """Round to the int8(4-frac) fixed-point grid. Shape-preserving."""
    q = jnp.clip(jnp.round(x * SCALE), QMIN, QMAX)
    return (q / SCALE).astype(x.dtype)


def _fp_fwd(x):
    return fixed_point_q8(x), ()


def _fp_bwd(_, g):
    return (g,)  # STE


fixed_point_q8.defvjp(_fp_fwd, _fp_bwd)


def dynamic_q8(x: jax.Array, axis=None):
    """Per-tensor (or grouped) dynamic int8: returns ``(int8, scale)``.

    ``axis`` semantics: ``None`` (default) computes ONE scale for the whole
    tensor (scalar scale, per-tensor quantization). An int or tuple of ints
    names the axes *reduced away* when computing the scale — every other
    axis indexes an independent quantization group, and ``scale`` comes
    back with the reduced axes kept as size-1 (``keepdims``) so it
    broadcasts directly against ``q`` in :func:`dequant`. E.g. for a slab
    ``(n_pages, page, Hkv, hd)``, ``axis=(1, 2, 3)`` is per-page
    quantization with ``scale: (n_pages, 1, 1, 1)``.

    The ``1e-8`` floor on the group amax keeps all-zero (and denormal-ish)
    groups from producing a zero or subnormal divisor — such groups
    quantize to all-zero ints and dequantize to exact zeros.
    """
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), QMIN, QMAX).astype(jnp.int8)
    return q, scale


def dequant(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return q.astype(dtype) * scale


def group_q8(x: jax.Array, n_group_axes: int):
    """Leading-axis-grouped int8: one scale per leading-axes group.

    ``x``'s first ``n_group_axes`` axes index quantization groups; the
    trailing axes are reduced into each group's scale. Returns
    ``(q int8 like x, scale f32 of shape x.shape[:n_group_axes])`` — the
    per-(layer, page) layout the quantized KV slab stores: for a slab
    ``(L, n_pages, page, Hkv, hd)``, ``n_group_axes=2`` yields one scale
    per (layer, page)."""
    assert 0 < n_group_axes < x.ndim, (n_group_axes, x.shape)
    axes = tuple(range(n_group_axes, x.ndim))
    q, scale = dynamic_q8(x.astype(jnp.float32), axis=axes)
    return q, scale.reshape(x.shape[:n_group_axes])


def group_dequant(q: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`group_q8`: ``scale`` broadcasts over the trailing
    (non-group) axes of ``q``."""
    expand = scale.reshape(scale.shape + (1,) * (q.ndim - scale.ndim))
    return (q.astype(jnp.float32) * expand).astype(dtype)


def quantized_attention(q, k, v, pattern, *, impl: Optional[str] = None,
                        mode: str = "fixed", **kw):
    """Attention on the quantized grid (paper's deployment numerics).

    mode='fixed'   int8 with 4-bit fraction (the ASIC's format)
    mode='dynamic' per-tensor dynamic int8 (TPU-style)
    """
    from repro.core.attention import hybrid_attention

    if mode == "fixed":
        qq, kq, vq = fixed_point_q8(q), fixed_point_q8(k), fixed_point_q8(v)
    elif mode == "dynamic":
        qq = dequant(*dynamic_q8(q), dtype=q.dtype)
        kq = dequant(*dynamic_q8(k), dtype=k.dtype)
        vq = dequant(*dynamic_q8(v), dtype=v.dtype)
    else:
        raise ValueError(mode)
    return hybrid_attention(qq, kq, vq, pattern, impl=impl, **kw)
