"""Paged ring-cache slab: ONE pooled KV allocation shared by all requests.

The serving-side mirror of the paper's hybrid sparse pattern, upgraded from
the per-batch :class:`repro.serve.kv_cache.RingCache` to a production-style
paged pool (vLLM-style paging x SALO's O(window) live set):

* **One slab per model segment** — ``(n_layers, n_pages, page, Hkv, hd)``
  for K and V. No per-request allocation ever happens after engine init;
  admission just hands out pages, completion recycles them.
* **Per-request page table** — each request owns ``sink_pages`` pages
  pinned to the global/sink prefix plus ``ring_pages`` pages forming a ring
  over the window lookback. Under dilation ``d`` the ring spans the full
  dilated lookback ``(w - 1) * d + 1`` positions (the legacy ring kept only
  ``w`` slots, silently dropping dilated keys — see
  tests/test_serve_continuous.py::test_dilated_decode_parity).
* **Per-request positions** — ``(R, slots_per_req)`` absolute position per
  logical slot (``PAD_SENTINEL`` = empty), fixing the legacy cache's
  batch-shared ``positions: (g + w,)``: a continuous batch's members sit at
  different depths, so slot->position maps cannot be shared.

Page 0 is reserved as the **null page**: inactive batch rows and dropped
writes are routed there, which keeps every scatter shape-static under jit
without masking logic in the hot path.

Slot map (logical, per request): position ``p < g`` lives at slot ``p``
inside the sink region ``[0, n_sink)``; position ``p >= g`` lives at slot
``n_sink + (p - g) % ring_cap``. Masks downstream are position-based
(:func:`repro.core.scheduler.causal_step_mask`), so the scrambled ring
order is transparent — exactly the legacy ring-cache argument, per request.

Cache footprint accounting lives in :func:`slab_bytes` and feeds
``benchmarks/serve_stats.py`` (BENCH_serve.json).

**Quantized slab** (``kv_dtype="int8"``): K/V are stored int8 with one f32
scale per (layer, page) riding next to the page tables
(:class:`PagedSlab` ``k_scale``/``v_scale``). :func:`quant_slab_write`
grows a page's scale monotonically as hotter rows land in it (rescaling
the already-resident int8 payload by the old/new ratio — exact where the
ratio is 1) and forces the null page's scale to 0, so inactive-row
scatters stay harmless AND dequantize to exact zeros. Reads dequantize
per page tile — :func:`gather_view` for the XLA twin, scalar-prefetched
scales inside the Pallas kernel. Recycled pages get their scales reset to
0 on admission (the position map, not the scale, is the validity source
of truth; the reset just stops stale amaxes from inflating the grid).
"""
from __future__ import annotations

import dataclasses
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import PAD_SENTINEL
from repro.ft.faults import ResourceExhausted


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static per-request geometry of the paged ring cache.

    ``shards > 1`` is the sequence-parallel serving layout: the request's
    logical pages are striped contiguously over the ``shards`` devices of
    the "seq" mesh axis — logical page ``j`` (and every slot inside it) is
    owned by shard ``j // pages_per_shard``, so sink/global pages land on
    the shards covering their positions and the ring pages are row-sharded
    across the rest. ``ring_pages`` absorbs the alignment padding (a ring
    larger than the dilated lookback is semantically identity: positions
    older than the lookback are masked out by the window term regardless of
    whether a slot still holds them).
    """
    page: int
    window: int
    n_global: int
    dilation: int = 1
    shards: int = 1

    def __post_init__(self):
        if self.page < 1 or self.window < 1 or self.dilation < 1 \
                or self.shards < 1:
            raise ValueError(f"bad paged layout {self}")
        if self.window > 1 << 28:
            raise ValueError("paged serving needs a bounded window "
                             "(salo pattern disabled / dense?)")

    @property
    def span(self) -> int:
        """Positions the ring must retain: the full dilated lookback."""
        return (self.window - 1) * self.dilation + 1

    @property
    def sink_pages(self) -> int:
        return _ceil_div(self.n_global, self.page) if self.n_global else 0

    @property
    def ring_pages(self) -> int:
        base = _ceil_div(self.span, self.page)
        # shard alignment: total pages padded so every shard owns the same
        # number of whole pages (padding slots stay PAD and mask to nothing)
        pad = -(self.sink_pages + base) % self.shards
        return base + pad

    @property
    def n_sink(self) -> int:
        return self.sink_pages * self.page

    @property
    def ring_cap(self) -> int:
        return self.ring_pages * self.page

    @property
    def pages_per_req(self) -> int:
        return self.sink_pages + self.ring_pages

    @property
    def slots_per_req(self) -> int:
        return self.pages_per_req * self.page

    # ---------------------- sequence-parallel view --------------------- #
    @property
    def pages_per_shard(self) -> int:
        assert self.pages_per_req % self.shards == 0
        return self.pages_per_req // self.shards

    @property
    def slots_per_shard(self) -> int:
        return self.pages_per_shard * self.page

    def slot_owner(self, s):
        """Shard owning logical slot ``s`` (contiguous page striping)."""
        return jnp.asarray(s, jnp.int32) // self.slots_per_shard

    def slot_local(self, s):
        """Shard-local slot index of logical slot ``s``."""
        return jnp.asarray(s, jnp.int32) % self.slots_per_shard

    # ------------------------- variable footprint ---------------------- #
    def _pages_needed(self, t, xp):
        need = xp.where(
            t <= self.n_global, -(-t // self.page),
            xp.minimum(self.sink_pages + -(-(t - self.n_global) // self.page),
                       self.pages_per_req))
        return xp.where(t <= 0, 0, need)

    def pages_needed(self, total_positions):
        """Physical pages a request writing positions ``[0, total)`` ever
        touches. Touched logical slots are a PREFIX of the slot space
        (positions below ``n_global`` map to slot ``p``; later positions
        fill the ring in order until it wraps), so a short request —
        ``total <= n_global + ring_cap`` — needs strictly fewer pages than
        :attr:`pages_per_req`. This is what admission actually allocates;
        the page table's unneeded tail entries stay on the null page.

        A scalar gives an ``int``; an array gives the count per element."""
        need = self._pages_needed(np.asarray(total_positions, np.int64), np)
        return int(need) if need.ndim == 0 else need

    def pages_live(self, total_positions):
        """:meth:`pages_needed` as a traced int32 array, for jitted code:
        the leading logical pages a row at ``total - 1`` can attend."""
        return self._pages_needed(jnp.asarray(total_positions, jnp.int32),
                                  jnp)

    def stripe(self, need, shard, xp=np):
        """Shard ``shard``'s part of a touched-page prefix of ``need``
        pages: shard ``s`` owns logical pages ``[s*pps, (s+1)*pps)``, and
        the prefix intersects each stripe in a prefix."""
        pps = self.pages_per_shard
        return xp.clip(need - shard * pps, 0, pps)

    def pages_needed_per_shard(self, total_positions: int) -> List[int]:
        """Split :meth:`pages_needed` over the contiguous page striping
        (:meth:`stripe`)."""
        need = self.pages_needed(total_positions)
        return [int(self.stripe(need, s)) for s in range(self.shards)]

    # ------------------------------------------------------------------ #
    def slot(self, p):
        """Logical slot of absolute position ``p`` (jnp-compatible)."""
        p = jnp.asarray(p, jnp.int32)
        g = self.n_global
        return jnp.where(p < g, p, self.n_sink + (p - g) % self.ring_cap)

    def write_target(self, page_table, p, keep=None):
        """(physical page, offset) for writing position ``p``.

        ``page_table``: (..., pages_per_req) int32; ``p``: (...) positions
        (leading dims must match). ``keep``: optional bool mask — False
        routes the write to the reserved null page 0 (inactive rows,
        ring-overwritten chunk positions). Returns (phys, off).
        """
        s = self.slot(p)
        pg = s // self.page
        off = s % self.page
        phys = jnp.take_along_axis(page_table, pg[..., None],
                                   axis=-1)[..., 0]
        if keep is not None:
            phys = jnp.where(keep, phys, 0)
            off = jnp.where(keep, off, 0)
        return phys, off


def layout_for_pattern(pattern, page: int, shards: int = 1) -> PagedLayout:
    """THE layout derivation — engine and pool-sizing callers share it, so
    ``n_pages = 1 + max_batch * layout.pages_per_req`` (or
    ``pages_per_shard`` per shard pool under sequence parallelism) always
    matches what admission will actually request."""
    if pattern.is_2d or not pattern.causal:
        raise ValueError(f"paged serving needs a causal 1-D pattern: "
                         f"{pattern}")
    return PagedLayout(page=page, window=pattern.window_size(),
                       n_global=pattern.n_global, dilation=pattern.dilation,
                       shards=shards)


class PagedSlab(NamedTuple):
    """Pooled KV for one model segment: (n_layers, n_pages, page, Hkv, hd).

    Layer ``i`` of the segment's stacked scan uses slab row ``i``; all
    layers of all segments share the SAME page tables (a request's page p
    means page p in every layer — the standard paged-KV invariant).

    ``k_scale``/``v_scale`` are ``None`` for fp slabs; for int8 slabs they
    are f32 ``(n_layers, n_pages)`` per-(layer, page) dequant scales
    (``lead`` dims prepended under sharding, striping with their pages).
    Scale 0 marks an empty page — in particular the null page 0, always."""
    k: jax.Array
    v: jax.Array
    k_scale: jax.Array = None
    v_scale: jax.Array = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def slab_init(n_layers: int, n_pages: int, page: int, n_kv_heads: int,
              head_dim: int, dtype, lead: tuple = (),
              quantized: bool = False) -> PagedSlab:
    """``lead``: extra leading dims — ``(n_shards,)`` stacks one per-shard
    pool per sequence shard (row s lives on shard s of the "seq" axis).
    ``quantized=True`` allocates int8 K/V (``dtype`` then only names the
    compute dtype readers dequantize to) plus zeroed per-(layer, page)
    scale arrays."""
    shape = (*lead, n_layers, n_pages, page, n_kv_heads, head_dim)
    if not quantized:
        return PagedSlab(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
    sshape = (*lead, n_layers, n_pages)
    return PagedSlab(k=jnp.zeros(shape, jnp.int8),
                     v=jnp.zeros(shape, jnp.int8),
                     k_scale=jnp.zeros(sshape, jnp.float32),
                     v_scale=jnp.zeros(sshape, jnp.float32))


def slab_write(k_slab: jax.Array, v_slab: jax.Array, phys: jax.Array,
               off: jax.Array, k_t: jax.Array, v_t: jax.Array):
    """Scatter per-request new KV into ONE layer's slab.

    k_slab/v_slab: (n_pages, page, Hkv, hd); phys/off: (B,) from
    :meth:`PagedLayout.write_target`; k_t/v_t: (B, Hkv, hd). Rows routed to
    the null page collide harmlessly (page 0 is never read)."""
    return (k_slab.at[phys, off].set(k_t.astype(k_slab.dtype)),
            v_slab.at[phys, off].set(v_t.astype(v_slab.dtype)))


def _quant_write_one(slab: jax.Array, scale: jax.Array, phys: jax.Array,
                     off: jax.Array, x: jax.Array):
    """int8 scatter of ``x`` into one layer's slab with per-page scales.

    slab: (n_pages, page, Hkv, hd) int8; scale: (n_pages,) f32; phys/off:
    (...,) write targets; x: (..., Hkv, hd) new rows. Page scales grow
    MONOTONICALLY (scatter-max of the incoming rows' amax/127): growth
    rescales the page's resident int8 payload by old/new — exactly 1.0
    (bit-identical payload) for untouched pages — and the null page's
    scale is pinned to 0 so routed-away writes quantize to zeros."""
    x = x.astype(jnp.float32)
    row_scale = jnp.max(jnp.abs(x), axis=(-2, -1)) / 127.0      # (...,)
    new_scale = scale.at[phys].max(row_scale).at[0].set(0.0)
    ratio = jnp.where(new_scale > 0.0,
                      scale / jnp.maximum(new_scale, 1e-30), 1.0)
    slab = jnp.clip(jnp.round(slab.astype(jnp.float32)
                              * ratio[:, None, None, None]),
                    -128, 127).astype(jnp.int8)
    s = new_scale[phys][..., None, None]                        # (...,1,1)
    q = jnp.where(s > 0.0,
                  jnp.clip(jnp.round(x / jnp.maximum(s, 1e-30)), -128, 127),
                  0.0).astype(jnp.int8)
    return slab.at[phys, off].set(q), new_scale


def quant_slab_write(k_slab: jax.Array, v_slab: jax.Array,
                     k_scale: jax.Array, v_scale: jax.Array,
                     phys: jax.Array, off: jax.Array,
                     k_t: jax.Array, v_t: jax.Array):
    """Quantizing twin of :func:`slab_write` for int8 slabs.

    Same write targets/contract, plus the per-(page,) scale vectors for
    the layer being written; returns (k_slab, v_slab, k_scale, v_scale)."""
    k_slab, k_scale = _quant_write_one(k_slab, k_scale, phys, off, k_t)
    v_slab, v_scale = _quant_write_one(v_slab, v_scale, phys, off, v_t)
    return k_slab, v_slab, k_scale, v_scale


def reset_page_scales(scale: jax.Array, pages: np.ndarray) -> jax.Array:
    """Zero the scales of freshly (re)allocated pages, all layers at once.

    scale: (..., n_layers, n_pages); pages: (n,) physical page ids. Called
    on admission so a recycled page's stale amax can't inflate the new
    request's quantization grid."""
    return scale.at[..., jnp.asarray(pages, jnp.int32)].set(0.0)


def gather_view(k_slab: jax.Array, v_slab: jax.Array,
                page_tables: jax.Array, k_scale: jax.Array = None,
                v_scale: jax.Array = None, dtype=None):
    """Materialize per-request logical KV views (the XLA decode twin path;
    the Pallas kernel chases the page table instead and never does this).

    k_slab/v_slab: (n_pages, page, Hkv, hd); page_tables: (B, npp).
    For int8 slabs pass the layer's ``k_scale``/``v_scale`` (n_pages,)
    and the compute ``dtype``: each gathered page tile is dequantized by
    its own scale. Returns (B, npp * page, Hkv, hd) x 2."""
    B, npp = page_tables.shape
    _, page, Hkv, hd = k_slab.shape
    kv = k_slab[page_tables]                     # (B, npp, page, Hkv, hd)
    vv = v_slab[page_tables]
    if k_scale is not None:
        sk = k_scale[page_tables][:, :, None, None, None]
        sv = v_scale[page_tables][:, :, None, None, None]
        kv = (kv.astype(jnp.float32) * sk).astype(dtype)
        vv = (vv.astype(jnp.float32) * sv).astype(dtype)
    return (kv.reshape(B, npp * page, Hkv, hd),
            vv.reshape(B, npp * page, Hkv, hd))


def empty_positions(n_requests: int, layout: PagedLayout) -> jax.Array:
    """Per-request slot->position table, all-empty (PAD_SENTINEL)."""
    return jnp.full((n_requests, layout.slots_per_req), PAD_SENTINEL,
                    jnp.int32)


# ---------------------------------------------------------------------- #
class PageAllocator:
    """Free-list page allocator over the pooled slab (host-side).

    Page 0 is reserved as the null page and never handed out. Admission
    calls :meth:`alloc`; completion calls :meth:`release` — recycled pages
    go straight back to the free list (no zeroing needed: positions are the
    validity source of truth, stale KV in a reused page is masked out by
    its PAD positions until overwritten)."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, 0, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> np.ndarray:
        if not self.can_alloc(n):
            raise ResourceExhausted(
                f"page pool exhausted ({n} > {self.n_free})")
        pages = [self._free.pop() for _ in range(n)]
        return np.asarray(pages, dtype=np.int32)

    def release(self, pages) -> None:
        for p in np.asarray(pages).tolist():
            assert 0 < p < self.n_pages, p
            assert p not in self._free, f"double free of page {p}"
            self._free.append(p)


# ---------------------------------------------------------------------- #
def slab_bytes(n_layers_total: int, n_pages: int, page: int,
               n_kv_heads: int, head_dim: int, dtype_bytes: int = 2,
               with_scales: bool = False) -> int:
    """Total pooled slab footprint (all segments' layers, K+V).

    ``with_scales`` adds the int8 slab's per-(layer, page) f32 scale
    arrays (K and V) — the honest footprint the quantized-serving
    benchmark compares against the fp slab."""
    base = 2 * n_layers_total * n_pages * page * n_kv_heads * head_dim \
        * dtype_bytes
    if with_scales:
        base += 2 * n_layers_total * n_pages * 4
    return base


def full_cache_bytes(n_layers_total: int, batch: int, max_len: int,
                     n_kv_heads: int, head_dim: int,
                     dtype_bytes: int = 2) -> int:
    """What the lockstep dense baseline allocates for the same traffic."""
    return 2 * n_layers_total * batch * max_len * n_kv_heads * head_dim \
        * dtype_bytes
