"""repro.analysis: the prover proves builder plans sound, and every
seeded mutation class is caught with a specific counterexample."""
import dataclasses
import warnings

import numpy as np
import pytest

import repro.core.patterns as P
from repro.analysis import Finding, plan_verify as pv, render
from repro.analysis.code_lint import lint_paths, lint_source
from repro.analysis.registry import chunk_targets, plan_targets
from repro.core.scheduler import build_chunk_plan, build_plan, schedule

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False


def _plan(pattern=None, n=256, bq=32, bk=32, pad=None):
    pattern = pattern or P.longformer(64, n_global=8)
    sched = schedule(pattern, n)
    if pad is not None:
        return build_plan(sched, bq, bk, pad)
    return sched.plan(bq, bk)


# ---------------------------------------------------------------------- #
# The builder's plans prove sound
# ---------------------------------------------------------------------- #
def test_registry_targets_prove_sound():
    for t in plan_targets()[:3]:
        plan = schedule(t.pattern, t.n).plan(t.block_q, t.block_k)
        assert pv.verify_plan(plan, t.name) == []


def test_sharded_and_never_drop_prove_sound():
    plan = _plan(pad=2 * 32)
    assert pv.verify_sharded(plan, 2) == []
    assert pv.verify_never_drop(_plan(P.causal_sliding_window(
        32, n_sinks=8), 256), local_window=32) == []


def test_chunk_slices_prove_sound():
    t = chunk_targets()[0]
    from repro.serve.paged_cache import layout_for_pattern
    lay = layout_for_pattern(t.pattern, t.page)
    c0 = 0
    while c0 < t.prompt:
        clen = min(t.chunk, t.prompt - c0)
        cp = build_chunk_plan(t.pattern, c0, clen, n_sink=lay.n_sink,
                              ring_cap=lay.ring_cap, block=t.page)
        assert pv.verify_chunk(cp, n_shards=t.n_shards) == []
        c0 += clen


def test_dynamic_full_keep_matches_static():
    plan = _plan(P.causal_sliding_window(32, n_sinks=8), 256)
    assert pv.verify_dynamic_full_keep(plan) == []


# ---------------------------------------------------------------------- #
# Seeded mutations: each class caught, with the offending tile named
# ---------------------------------------------------------------------- #
def _drop_covering_step(plan):
    """Zero out a step that really covers pairs (the diagonal tile —
    boundary tiles can be conservatively scheduled yet pair-empty)."""
    kv, fl = plan.kv_blocks.copy(), plan.flags.copy()
    i, s = next((i, s) for i in range(plan.nq)
                for s in range(int(plan.num_steps[i]))
                if kv[i, s] == i and fl[i, s] != 0)
    kv[i, s] = 0
    fl[i, s] = 0
    return dataclasses.replace(plan, kv_blocks=kv, flags=fl), i


def test_mutation_dropped_tile():
    plan = _plan()
    mut, i = _drop_covering_step(plan)
    findings = pv.verify_coverage(mut, "mut")
    assert findings, "dropped tile not caught"
    f = findings[0]
    assert "missing" in f.message and f.q_block == i
    assert f"q_block={f.q_block}" in f.counterexample()


def test_mutation_duplicated_tile():
    plan = _plan()
    kv, fl = plan.kv_blocks.copy(), plan.flags.copy()
    r = int(np.nonzero(plan.num_steps < plan.max_steps)[0][0])
    ns = int(plan.num_steps[r])
    kv[r, ns], fl[r, ns] = kv[r, 0], fl[r, 0]
    mut = dataclasses.replace(plan, kv_blocks=kv, flags=fl)
    findings = pv.verify_coverage(mut, "mut")
    assert findings and "double-counted" in findings[0].message
    assert findings[0].q_block == r


def test_mutation_wrong_flag():
    plan = _plan()
    kv, fl = plan.kv_blocks.copy(), plan.flags.copy()
    i, s = (int(x) for x in np.argwhere(fl == 1)[0])   # window-only step
    fl[i, s] = 2                                       # -> global-only
    mut = dataclasses.replace(plan, kv_blocks=kv, flags=fl)
    findings = pv.verify_coverage(mut, "mut")
    assert findings and "missing" in findings[0].message
    assert findings[0].q_block == i


def test_mutation_transposed_row_swap():
    plan = _plan()
    tp = plan.transposed()
    qb, fl, ns = (tp.q_blocks.copy(), tp.flags.copy(), tp.num_steps.copy())
    qb[[0, 1]], fl[[0, 1]], ns[[0, 1]] = qb[[1, 0]], fl[[1, 0]], ns[[1, 0]]
    mut = dataclasses.replace(tp, q_blocks=qb, flags=fl, num_steps=ns)
    findings = pv.verify_transposed(plan, mut, "mut")
    assert findings and "transposed walk" in findings[0].message


def test_mutation_broken_halo_hop():
    from repro.dist.sharded_plan import shard_plan
    plan = _plan(pad=2 * 32)
    sp = shard_plan(plan, 2)
    assert sp.halo_dists, "config must produce halo traffic"
    vm = np.asarray(sp.view_map)
    send = tuple(a.copy() for a in sp.send_idx)
    off = sp.nkb_l
    hop = None
    for d_i, (delta, T) in enumerate(zip(sp.halo_dists, sp.halo_counts)):
        for s in range(sp.n_shards):
            for slot in range(T):
                gt = int(vm[s, off + slot])
                if gt >= 0:
                    hop = (d_i, gt // sp.nkb_l, slot, gt)
                    break
            if hop:
                break
        if hop:
            break
        off += T
    d_i, owner, slot, gt = hop
    send[d_i][owner, slot] = (send[d_i][owner, slot] + 1) % sp.nkb_l
    mut = dataclasses.replace(sp, send_idx=send)
    findings = pv.verify_sharded(plan, 2, mut, "mut")
    assert findings
    assert any("no scheduled ppermute hop delivers" in f.message
               and f.kv_block == gt for f in findings)


def test_mutation_unfilled_view_slot():
    from repro.dist.sharded_plan import shard_plan
    plan = _plan(pad=2 * 32)
    sp = shard_plan(plan, 2)
    vm = np.asarray(sp.view_map).copy()
    used = np.unique(np.asarray(sp.tables)[np.asarray(sp.flags) != 0])
    vt = int(used[-1])
    vm[:, vt] = -1                       # exchange never fills this slot
    mut = dataclasses.replace(sp, view_map=vm)
    findings = pv.verify_sharded(plan, 2, mut, "mut")
    assert any("no exchange ever fills" in f.message for f in findings)


# ---------------------------------------------------------------------- #
# Finding plumbing + the gate's report
# ---------------------------------------------------------------------- #
def test_finding_counterexample_and_render():
    f = Finding("coverage", "t", "msg", q_block=3, kv_block=7)
    assert "(q_block=3, kv_block=7)" in f.counterexample()
    assert Finding(**f.as_dict()) == f
    assert "coverage" in render([f])
    assert render([]) == ""


# ---------------------------------------------------------------------- #
# Code lint: repo sources clean, synthetic violations caught
# ---------------------------------------------------------------------- #
def test_code_lint_repo_clean():
    assert lint_paths(["src", "tests", "benchmarks"]) == []


def test_code_lint_catches_violations():
    src = (
        "import os\n"
        "from typing import List\n"
        "def f(x=[]):\n"
        "    try:\n"
        "        pass\n"
        "    except:\n"
        "        pass\n"
        "list = 3\n"
    )
    msgs = [f.message for f in lint_source(src, "x.py")]
    assert any("unused import 'os'" in m for m in msgs)
    assert any("unused import 'List'" in m for m in msgs)
    assert any("mutable default" in m for m in msgs)
    assert any("bare 'except:'" in m for m in msgs)
    assert any("shadows builtin 'list'" in m for m in msgs)


def test_code_lint_allows_reexport_idiom():
    src = "from a import X as X\nfrom __future__ import annotations\n"
    assert lint_source(src, "x.py") == []


# ---------------------------------------------------------------------- #
# Jaxpr lint (cheap checks only — the gate runs the full set)
# ---------------------------------------------------------------------- #
def test_jaxpr_lint_negative_checks():
    import jax
    import jax.numpy as jnp

    from repro.analysis import jaxpr_lint as jl

    tr = jax.make_jaxpr(
        lambda x, i, u: x.at[i].add(u, unique_indices=True))(
            jnp.zeros(8), jnp.array([1, 1]), jnp.ones(2))
    assert any("write-write race" in f.message
               for f in jl.check_scatter_modes(tr, "t"))

    tr2 = jax.make_jaxpr(
        lambda x8: x8.astype(jnp.float32) + x8.astype(jnp.float32))(
            jnp.zeros(4, jnp.int8))
    assert any("double-dequant" in f.message
               for f in jl.check_double_dequant(tr2, "t"))


def test_jaxpr_lint_launch_contract_and_twins():
    from repro.analysis import jaxpr_lint as jl

    pat = P.longformer(32, n_global=4)
    assert jl.check_launch_contract(pat, 128, 32, 32, "t") == []
    assert jl.lint_traced(jl.trace_dkv_scatter(pat, 128, 32, 32), "t") == []
    assert jl.lint_traced(jl.trace_masked_psum_merge(), "t") == []


def test_write_ownership_probe():
    from repro.analysis import jaxpr_lint as jl
    from repro.serve.paged_cache import layout_for_pattern

    for shards in (1, 2):
        lay = layout_for_pattern(P.causal_sliding_window(16, n_sinks=2), 8,
                                 shards=shards)
        assert jl.check_write_ownership(lay, "t") == []


def test_vmem_estimates_within_budget():
    from repro.analysis import jaxpr_lint as jl

    plan = _plan(n=1024, bq=128, bk=128)
    assert jl.check_vmem(plan, d=64, decode=jl.SERVING_DECODE) == []
    f32, int8 = (jl.decode_vmem_bytes(**jl.SERVING_DECODE[k])
                 for k in ("paged_decode", "paged_decode_int8"))
    # 3 KV-head rows pad to a whole sublane tile in either dtype (8 f32,
    # 32 int8 rows); with 32 heads the int8 tiles are a quarter the size
    assert int8 == f32
    wide = {k: dict(v, n_kv=32) for k, v in jl.SERVING_DECODE.items()}
    assert (jl.decode_vmem_bytes(**wide["paged_decode_int8"])
            < jl.decode_vmem_bytes(**wide["paged_decode"]))
    big = dict(jl.SERVING_DECODE["paged_decode_int8"], block_s=8192)
    assert jl.check_vmem(plan, d=64, decode={"int8": big}), \
        "an oversized int8 decode launch must be flagged"
    huge = _plan(P.longformer(2048, n_global=8), 4096, 2048, 2048)
    assert jl.check_vmem(huge, d=256), "oversized blocks must be flagged"


# ---------------------------------------------------------------------- #
# Deprecation pin (satellite: legacy lockstep cache)
# ---------------------------------------------------------------------- #
def test_ring_init_deprecation_warning():
    import jax.numpy as jnp

    from repro.serve.kv_cache import ring_init

    with pytest.warns(DeprecationWarning, match="LOCKSTEP"):
        ring_init(1, 8, 2, 1, 4, jnp.float32)
    # paged path warns nothing
    from repro.serve.paged_cache import layout_for_pattern
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        layout_for_pattern(P.causal_sliding_window(16, n_sinks=2), 8)


# ---------------------------------------------------------------------- #
# Property tests (hypothesis is an optional dependency)
# ---------------------------------------------------------------------- #
@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="hypothesis not installed")
def test_property_random_patterns_prove_sound():
    @settings(max_examples=15, deadline=None)
    @given(window=st.integers(4, 24), n_global=st.integers(0, 6),
           causal=st.booleans(), dilation=st.sampled_from([1, 2]),
           block=st.sampled_from([8, 16]))
    def inner(window, n_global, causal, dilation, block):
        if dilation > 1:
            pat = P.causal_sliding_window(window, n_sinks=n_global,
                                          dilation=dilation)
        else:
            pat = P.longformer(2 * window, n_global=n_global,
                               causal=causal)
        plan = schedule(pat, 96).plan(block, block)
        assert pv.verify_coverage(plan) == []
        assert pv.verify_transposed(plan) == []
        assert pv.verify_packed(plan) == []
    inner()


@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="hypothesis not installed")
def test_property_random_step_drop_is_caught():
    @settings(max_examples=10, deadline=None)
    @given(row=st.integers(0, 7))
    def inner(row):
        plan = _plan()
        r = row % plan.nq
        try:
            mut, i = _drop_covering_step(
                dataclasses.replace(plan))
        except StopIteration:
            return
        assert pv.verify_coverage(mut)
    inner()
