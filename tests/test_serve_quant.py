"""Quantized int8 KV slab + stats-driven page-sparse decode, pinned
against the full-precision continuous engine:
  * int8 engine greedy parity vs the fp engine across ring wraparound
    (t >> window), dilation > 1, page-recycling waves, and the paged
    decode kernel (pallas_interpret)
  * quant_slab_write -> gather_view round-trip at the slab level
  * int8 slab resident footprint ~4x under the f32 slab
  * page_sparsity_threshold=-inf (stats machinery ON, keep everything)
    token-identical to the machinery being off — the read-masking-only
    invariant
  * a finite threshold actually skips page reads (counters) at parity
  * the 8-shard int8 + page-sparse engine matches its single-device twin
    (subprocess with 8 forced host devices)
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.models.model import build_model
from repro.serve.engine import ContinuousConfig, ContinuousEngine

RNG = np.random.default_rng(11)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _engine(cfg, model, *, page=8, chunk=8, max_batch=4, decode_impl="xla",
            kv_dtype="compute", thr=None, decay=0.0):
    from repro.models.layers import salo_pattern
    from repro.serve.paged_cache import layout_for_pattern

    lay = layout_for_pattern(salo_pattern(cfg, causal=True), page)
    return ContinuousEngine(model, ContinuousConfig(
        n_pages=1 + max_batch * lay.pages_per_req, page=page, chunk=chunk,
        max_batch=max_batch, decode_impl=decode_impl, kv_dtype=kv_dtype,
        page_sparsity_threshold=thr, page_stat_decay=decay))


def _prompts(cfg, lens):
    return [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


def _run(eng, params, prompts, n_new):
    rids = [eng.submit(p, n_new) for p in prompts]
    res = eng.run(params)
    return [res[r] for r in rids]


def _assert_parity(a_toks, b_toks):
    for i, (a, b) in enumerate(zip(a_toks, b_toks)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


# ==================== int8 engine vs fp engine parity ================== #
def test_int8_parity_ring_wraparound():
    """t >> window: many full ring revolutions re-quantize every ring page
    over and over (monotone per-page scale growth + whole-slab rescale);
    greedy tokens stay identical to the fp engine."""
    cfg = get_smoke("smollm-135m")
    cfg = dataclasses.replace(cfg, salo=dataclasses.replace(
        cfg.salo, window=8))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _prompts(cfg, (21, 6))
    n_new = 40  # final t = 60 -> 7+ revolutions past window=8
    ref = _run(_engine(cfg, model, max_batch=2), params, prompts, n_new)
    out = _run(_engine(cfg, model, max_batch=2, kv_dtype="int8"),
               params, prompts, n_new)
    _assert_parity(out, ref)


def test_int8_parity_dilated():
    """dilation > 1: the quantized ring spans the full dilated lookback
    and dequantized reads stay greedy-exact vs the fp engine."""
    cfg = get_smoke("smollm-135m")
    cfg = dataclasses.replace(cfg, salo=dataclasses.replace(
        cfg.salo, window=4, dilation=2, n_global=2))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = _prompts(cfg, (11, 17))
    ref = _run(_engine(cfg, model, max_batch=2), params, prompts, 10)
    out = _run(_engine(cfg, model, max_batch=2, kv_dtype="int8"),
               params, prompts, 10)
    _assert_parity(out, ref)


def test_int8_parity_page_recycling_waves():
    """More requests than rows: finished requests hand their pages (and
    rows) to waiting ones; recycled pages' scales reset to 0 so the new
    tenant starts on a fresh quantization grid."""
    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    prompts = _prompts(cfg, (9, 26, 5, 14, 22, 7))
    ref = _run(_engine(cfg, model, max_batch=2), params, prompts, 8)
    eng = _engine(cfg, model, max_batch=2, kv_dtype="int8")
    out = _run(eng, params, prompts, 8)
    _assert_parity(out, ref)
    # the waves really happened: 6 requests through 2 rows
    assert len(eng.batcher.finished) == 6


def test_int8_parity_pallas_interpret():
    """The paged decode kernel (scales scalar-prefetched next to the page
    table, int8 dequantized in-kernel) matches the fp XLA engine."""
    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    prompts = _prompts(cfg, (7, 12))
    ref = _run(_engine(cfg, model, max_batch=2), params, prompts, 6)
    out = _run(_engine(cfg, model, max_batch=2, kv_dtype="int8",
                       decode_impl="pallas_interpret"),
               params, prompts, 6)
    _assert_parity(out, ref)


# ======================= slab-level invariants ========================= #
def test_quant_slab_write_gather_roundtrip():
    """quant_slab_write (one layer's slab) then a dequantizing gather_view
    approximates the fp slab within the per-page scale bound, and the null
    page reads back exactly zero (scale pinned to 0)."""
    from repro.serve.paged_cache import gather_view, quant_slab_write

    n_pages, page, Hkv, hd = 5, 4, 2, 8
    shape = (n_pages, page, Hkv, hd)
    k8 = jnp.zeros(shape, jnp.int8)
    v8 = jnp.zeros(shape, jnp.int8)
    ks = jnp.zeros((n_pages,), jnp.float32)
    vs = jnp.zeros((n_pages,), jnp.float32)
    fp_k = np.zeros(shape, np.float32)
    fp_v = np.zeros(shape, np.float32)
    writes = ((1, 0), (1, 1), (2, 3), (4, 2), (0, 0))  # incl. null route
    for phys, off in writes:
        k_t = RNG.normal(size=(Hkv, hd)).astype(np.float32) * 2.0
        v_t = RNG.normal(size=(Hkv, hd)).astype(np.float32)
        k8, v8, ks, vs = quant_slab_write(
            k8, v8, ks, vs, jnp.asarray([phys], jnp.int32),
            jnp.asarray([off], jnp.int32), jnp.asarray(k_t)[None],
            jnp.asarray(v_t)[None])
        if phys != 0:  # the null page swallows routed-away writes
            fp_k[phys, off] = k_t
            fp_v[phys, off] = v_t
    pt = jnp.asarray([[0, 1, 2, 4]], jnp.int32)  # null + written pages
    got_k, got_v = gather_view(k8, v8, pt, ks, vs, dtype=jnp.float32)
    want_k, want_v = gather_view(jnp.asarray(fp_k), jnp.asarray(fp_v), pt)
    # per-page bound: scale/2 rounding plus one re-rescale rounding step
    bound = float(jnp.maximum(jnp.max(ks), jnp.max(vs))) + 1e-6
    assert float(jnp.max(jnp.abs(got_k - want_k))) <= bound
    assert float(jnp.max(jnp.abs(got_v - want_v))) <= bound
    assert not np.any(np.asarray(got_k[:, :page]))  # null page all-zero


def test_int8_slab_resident_footprint():
    """int8 slab (K/V int8 + per-(layer, page) f32 scales) sits ~4x under
    the f32 compute-dtype slab for the same pool."""
    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    fp = _engine(cfg, model).slab_resident_bytes()
    q8 = _engine(cfg, model, kv_dtype="int8").slab_resident_bytes()
    assert fp / q8 >= 3.5, (fp, q8)


# ==================== stats-driven page sparsity ======================= #
def test_keepall_threshold_exact_vs_none():
    """threshold=-inf turns the stats machinery ON but keeps every page:
    reads are masked (not state), so tokens are bit-identical to
    threshold=None and no page read is ever skipped."""
    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    prompts = _prompts(cfg, (9, 26, 5, 14))
    ref = _run(_engine(cfg, model, kv_dtype="int8"), params, prompts, 10)
    eng = _engine(cfg, model, kv_dtype="int8", thr=float("-inf"),
                  decay=0.5)
    out = _run(eng, params, prompts, 10)
    _assert_parity(out, ref)
    assert (eng.counters["decode_pages_read"]
            == eng.counters["decode_pages_total"] > 0)


def test_page_skip_engages_at_parity():
    """A finite threshold with decay > 0 skips real page reads (counters
    prove it) while this workload's greedy tokens stay identical to the
    dense-read int8 engine."""
    cfg = get_smoke("smollm-135m")
    cfg = dataclasses.replace(cfg, salo=dataclasses.replace(
        cfg.salo, window=64))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    prompts = _prompts(cfg, (24, 17, 9, 30))
    ref = _run(_engine(cfg, model, kv_dtype="int8"), params, prompts, 24)
    eng = _engine(cfg, model, kv_dtype="int8", thr=-3.0, decay=0.3)
    out = _run(eng, params, prompts, 24)
    _assert_parity(out, ref)
    read = eng.counters["decode_pages_read"]
    total = eng.counters["decode_pages_total"]
    assert 0 < read < total, (read, total)


def test_page_skip_zero_decay_never_skips():
    """decay=0 can never skip a page: the history init (0) is the maximum
    possible relative score, so nothing ever falls below a threshold <= 0
    without decay pulling it down."""
    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    prompts = _prompts(cfg, (9, 14))
    eng = _engine(cfg, model, max_batch=2, kv_dtype="int8", thr=-0.1,
                  decay=0.0)
    _run(eng, params, prompts, 8)
    assert (eng.counters["decode_pages_read"]
            == eng.counters["decode_pages_total"] > 0)


# ========================= sharded (8 devices) ========================= #
def test_sharded_int8_page_sparse_matches_single_device():
    """8-shard engine, int8 slab + page sparsity: scales stripe with the
    pages, the keep mask comes from merged shard stats, and greedy tokens
    match the single-device engine token-for-token (with pages actually
    skipped on both sides). Subprocess: 8 forced host devices."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.configs import get_smoke
        from repro.models.model import build_model
        from repro.models.layers import salo_pattern
        from repro.serve.engine import ContinuousConfig, ContinuousEngine
        from repro.serve.paged_cache import layout_for_pattern

        cfg = get_smoke("smollm-135m")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (24, 17, 9, 30)]
        pat = salo_pattern(cfg, causal=True)
        quant = dict(kv_dtype="int8", page_sparsity_threshold=-0.5,
                     page_stat_decay=0.3)
        l1 = layout_for_pattern(pat, 8)
        e1 = ContinuousEngine(model, ContinuousConfig(
            n_pages=1 + 4 * l1.pages_per_req, page=8, chunk=8,
            max_batch=4, **quant))
        r1 = [e1.submit(p, 8) for p in prompts]
        ref = e1.run(params)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("seq",))
        l8 = layout_for_pattern(pat, 8, shards=8)
        e8 = ContinuousEngine(model, ContinuousConfig(
            n_pages=1 + 4 * l8.pages_per_shard, page=8, chunk=8,
            max_batch=4, seq_shards=8, **quant), mesh=mesh)
        r8 = [e8.submit(p, 8) for p in prompts]
        out = e8.run(params)
        for a, b in zip(r1, r8):
            np.testing.assert_array_equal(ref[a], out[b])
        assert e1.counters["decode_pages_read"] < \\
            e1.counters["decode_pages_total"]
        assert e8.counters["decode_pages_read"] < \\
            e8.counters["decode_pages_total"]
        print("QUANT-SHARD-OK")
    """)
    r = subprocess.run([sys.executable, "-c", prog],
                       env={**os.environ, "PYTHONPATH": SRC},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "QUANT-SHARD-OK" in r.stdout


# ================== stats-driven chunked-prefill reads ================== #
def test_prefill_keepall_counters_and_parity():
    """threshold=-inf through MULTI-CHUNK prefill: the ctx-read mask is on
    but keeps every page — tokens identical to the machinery being off,
    and the prefill page-read counters prove no read was skipped."""
    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    prompts = _prompts(cfg, (21, 30))          # > chunk: several chunks each
    ref = _run(_engine(cfg, model, kv_dtype="int8"), params, prompts, 6)
    eng = _engine(cfg, model, kv_dtype="int8", thr=float("-inf"), decay=0.5)
    out = _run(eng, params, prompts, 6)
    _assert_parity(out, ref)
    assert (eng.counters["prefill_pages_read"]
            == eng.counters["prefill_pages_total"] > 0)


def test_prefill_page_skip_engages():
    """Chunked prefill actually skips ctx-page reads once a row's history
    falls below the threshold (driven directly here — fresh requests are
    admitted hot, the PR-6 decode stats populate the history in service):
    the skipped chunk reads only sink + chunk-written pages, and the
    request still completes."""
    cfg = get_smoke("smollm-135m")
    cfg = dataclasses.replace(cfg, salo=dataclasses.replace(
        cfg.salo, window=64))                  # ring spans several pages
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(8))
    eng = _engine(cfg, model, thr=-0.1, decay=0.3, max_batch=1)
    prompt = RNG.integers(0, cfg.vocab_size, (40,)).astype(np.int32)
    rid = eng.submit(prompt, 4)
    eng.step(params)                           # admit + first chunk (hot)
    r0, t0 = (eng.counters["prefill_pages_read"],
              eng.counters["prefill_pages_total"])
    assert r0 == t0 > 0                        # all-zero history: no skip
    req = next(r for r in eng.batcher.rows if r is not None)
    eng.page_hist[req.row, :] = -1.0           # below threshold everywhere
    eng.step(params)                           # next chunk: mask bites
    r1, t1 = (eng.counters["prefill_pages_read"],
              eng.counters["prefill_pages_total"])
    assert r1 - r0 < t1 - t0, (r1 - r0, t1 - t0)
    res = eng.run(params)
    assert res[rid].shape[0] == 4              # completes, emits max_new
