"""The main path's Pallas kernels compile for a TPU v5e at smollm-135m
widths (hd 64, 9/3 heads, window 1024 + 4 sinks, bf16, 256-row blocks,
8-token pages), at 2048 tokens and at a long context of 8192 — without a
chip: the TPU compiler is handed a described ``v5e:2x2`` topology and
abstract shapes, so Mosaic's tiling, VMEM and SMEM checks run exactly as
they would before a launch on the device.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and test collection happens in
every worker."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.patterns import causal_sliding_window
from repro.core.scheduler import schedule
from repro.kernels.salo_attention import salo_plan_attention
from repro.kernels.salo_backward import (salo_plan_backward_dkv,
                                         salo_plan_backward_dq)
from repro.kernels.salo_decode import salo_paged_decode

PATTERN = causal_sliding_window(1024, n_sinks=4)   # smollm-135m SALO
CONTEXTS = (2048, 8192)
BH, HD, BLOCK = 4 * 9, 64, 256
R, H, HKV, PAGE = 8, 9, 3, 8
# A request's page table holds its window's pages whatever its length
# (the ring wraps), so smollm decodes over 136 pages at any context. At
# 8192 tokens the table is widest when the window spans the context:
# 8192 // PAGE ring pages + one sink page, a 9x larger grid and SMEM table.
DECODE_CASES = {2048: (PATTERN, 136),
                8192: (causal_sliding_window(8192, n_sinks=4),
                       8192 // PAGE + 1)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(name, f, *shapes):
    text = jax.jit(f).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in the HLO"
    assert name in text, f"the kernel in the HLO is not {name}"
    return text


@pytest.fixture(scope="module", params=CONTEXTS)
def attn(request, one_chip):
    plan = schedule(PATTERN, request.param).plan(BLOCK, BLOCK)
    pos = jnp.asarray(plan.positions_padded())
    x = jax.ShapeDtypeStruct((BH, plan.n_pad, HD), jnp.bfloat16,
                             sharding=one_chip)
    row = jax.ShapeDtypeStruct((BH, plan.n_pad), jnp.float32,
                               sharding=one_chip)
    return plan, pos, x, row


def test_forward_compiles(attn):
    plan, pos, x, _ = attn
    _compile("salo_plan_attention",
             lambda q, k, v: salo_plan_attention(q, k, v, pos, plan=plan),
             x, x, x)


def test_backward_dq_compiles(attn):
    plan, pos, x, row = attn
    _compile("salo_plan_backward_dq",
             lambda do, de, m, l, q, k, v: salo_plan_backward_dq(
                 do, de, m, l, q, k, v, pos, plan=plan, scale=HD ** -0.5),
             x, row, row, row, x, x, x)


def test_backward_dkv_compiles(attn):
    plan, pos, x, row = attn
    _compile("salo_plan_backward_dkv",
             lambda do, de, m, l, q, k, v: salo_plan_backward_dkv(
                 do, de, m, l, q, k, v, pos, plan=plan, scale=HD ** -0.5),
             x, row, row, row, x, x, x)


def _decode_shapes(one_chip, slab_dtype, pages_per_req):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    n_pages = 1 + R * pages_per_req
    slab = s((n_pages, PAGE, HKV, HD), slab_dtype)
    return [s((R, H, 1, HD), jnp.bfloat16), slab, slab,
            s((R, pages_per_req), jnp.int32),
            s((R, pages_per_req * PAGE), jnp.int32), s((R,), jnp.int32),
            s((n_pages,), jnp.float32)]


@pytest.mark.parametrize("context", CONTEXTS)
def test_paged_decode_bf16_compiles(one_chip, context):
    pattern, pages_per_req = DECODE_CASES[context]
    args = _decode_shapes(one_chip, jnp.bfloat16, pages_per_req)[:6]
    _compile("salo_paged_decode",
             lambda q, ks, vs, pt, p, t: salo_paged_decode(
                 q, ks, vs, pt, p, t, pattern=pattern), *args)
    # the sequence-parallel variant: f32 partials + (m, l) + page stats
    _compile("salo_paged_decode",
             lambda q, ks, vs, pt, p, t: salo_paged_decode(
                 q, ks, vs, pt, p, t, pattern=pattern, return_state=True,
                 return_page_stats=True), *args)


@pytest.mark.parametrize("context", CONTEXTS)
def test_paged_decode_int8_compiles(one_chip, context):
    pattern, pages_per_req = DECODE_CASES[context]
    args = _decode_shapes(one_chip, jnp.int8, pages_per_req)
    _compile("salo_paged_decode",
             lambda q, ks, vs, pt, p, t, sc: salo_paged_decode(
                 q, ks, vs, pt, p, t, pattern=pattern, k_scale=sc,
                 v_scale=sc, return_page_stats=True), *args)
