"""Fused plan-driven backward: gradient parity + transposed-plan contract.

The backward is a first-class ExecutionPlan consumer (two flash-style
passes: dQ over the forward tables, dK/dV over the transposed tables,
``p`` recomputed from the saved ``(out, m, l)``), so these tests pin:

  * gradient parity of BOTH differentiable engines (pallas_interpret and
    blockwise) against dense_ref autodiff, <= 1e-4, across the four
    pattern families (Longformer window+global, ViL 2-D multi-band,
    dilated/reordered, reordered+global sinks);
  * exactly TWO backward kernel launches and ZERO forward kernel
    launches inside the VJP (no full-forward recompute);
  * the transposed plan is the EXACT adjoint of the forward coverage
    (same visits, same flags, dedup preserved — equal tile totals);
  * the empty-row contract: rows that attend nothing emit
    (out=0, m=NEG_INF, l=0) and get exactly zero gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import patterns as P
from repro.core.attention import hybrid_attention
from repro.core.scheduler import build_plan, schedule

# The four pattern families named by the training configs (scaled down so
# interpret-mode gradients stay fast): Longformer-4k window+global, ViL 2-D
# multi-band, dilated (data-reordered), and reordered-global (sinks).
GRAD_CASES = [
    ("longformer", P.longformer(8, n_global=2), 37, 8, 8),
    ("longformer_causal", P.longformer(8, n_global=2, causal=True), 37, 8, 8),
    ("vil_2d", P.vil((5, 7), (3, 3), n_global=2), None, 8, 8),
    ("vil_2d_overlap", P.vil((5, 4), (3, 5), n_global=1), None, 8, 8),
    ("dilated", P.dilated_window(4, 3), 29, 8, 8),
    ("reordered_global", P.causal_sliding_window(5, n_sinks=2, dilation=2),
     31, 8, 8),
]


def _qkv_cot(n, d=16, b=1, h=2, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v, cot = (jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
                    for _ in range(4))
    return q, k, v, cot


def _grads(impl, pat, n, bq, bk, q, k, v, cot):
    def loss(q_, k_, v_):
        out = hybrid_attention(q_, k_, v_, pat, impl=impl, block_q=bq,
                               block_k=bk)
        return jnp.sum(out * cot)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("impl", ["pallas_interpret", "blockwise"])
@pytest.mark.parametrize("name,pat,n,bq,bk", GRAD_CASES)
def test_gradient_parity_vs_dense_ref(impl, name, pat, n, bq, bk):
    """dQ/dK/dV through the fused plan backward == dense_ref autodiff."""
    n = n if n is not None else pat.seq_len()
    q, k, v, cot = _qkv_cot(n)
    g_ref = _grads("dense_ref", pat, n, bq, bk, q, k, v, cot)
    g_out = _grads(impl, pat, n, bq, bk, q, k, v, cot)
    for gname, a, b in zip("qkv", g_ref, g_out):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4,
            err_msg=f"{name}/{impl}: d{gname} mismatch")


def test_gqa_gradient_parity():
    """GQA (broadcast KV, no repeat-copy) keeps fwd+bwd parity."""
    pat = P.longformer(8, n_global=1)
    n, d, b, h, hkv = 24, 8, 2, 4, 2
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, hkv, n, d)), jnp.float32)
            for _ in range(2))
    cot = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)

    def loss(impl):
        def f(q_, k_, v_):
            out = hybrid_attention(q_, k_, v_, pat, impl=impl, block_q=8,
                                   block_k=8)
            return jnp.sum(out * cot)
        return f

    g_ref = jax.grad(loss("dense_ref"), argnums=(0, 1, 2))(q, k, v)
    for impl in ("blockwise", "pallas_interpret"):
        g_out = jax.grad(loss(impl), argnums=(0, 1, 2))(q, k, v)
        for gname, a, b in zip("qkv", g_ref, g_out):
            assert a.shape == b.shape  # KV grads stay (B, Hkv, N, D)
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4,
                err_msg=f"{impl}: d{gname}")


# ---------------- launch accounting: 2 bwd, 0 fwd-recompute ------------- #
def test_backward_is_two_launches_no_forward_recompute(monkeypatch):
    # salo_attention and salo_backward share the one pallas module object,
    # so patch it once and classify launches by kernel name.
    from repro.kernels import salo_attention as sa
    from repro.kernels.ops import salo_attention

    jax.clear_caches()  # launch counts are per-trace; force fresh traces
    launches = []
    real = sa.pl.pallas_call

    def counting(*args, **kwargs):
        launches.append(kwargs.get("name", "?"))
        return real(*args, **kwargs)

    monkeypatch.setattr(sa.pl, "pallas_call", counting)

    pat = P.vil((5, 7), (3, 3), n_global=2)
    n = pat.seq_len()
    rng = np.random.default_rng(0)
    q, k, v, cot = (jnp.asarray(rng.normal(size=(2, n, 16)), jnp.float32)
                    for _ in range(4))
    out, vjp = jax.vjp(
        lambda q_, k_, v_: salo_attention(q_, k_, v_, pat, 8, 8, None, True),
        q, k, v)
    assert launches == ["salo_plan_attention"], launches
    dq, dk, dv = vjp(cot)
    jax.block_until_ready((dq, dk, dv))
    bwd = launches[1:]
    assert sorted(bwd) == ["salo_plan_backward_dkv",
                           "salo_plan_backward_dq"], \
        f"want exactly dQ + dK/dV and NO forward recompute, got {launches}"


def test_compiled_pallas_off_tpu_raises():
    """impl="pallas" asks for the compiled kernels: off the TPU the forward
    AND the gradient refuse to lower instead of swapping in a stand-in
    (interpret mode and the XLA twin are only ever chosen explicitly)."""
    from repro.kernels.ops import salo_attention

    if jax.default_backend() == "tpu":
        pytest.skip("compiled kernels run on the TPU")
    pat = P.longformer(8, n_global=2)
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 26, 8)), jnp.float32)
               for _ in range(3))
    with pytest.raises(ValueError, match="interpret mode"):
        salo_attention(q, k, v, pat, 8, 8, None, False)
    with pytest.raises(ValueError, match="interpret mode"):
        jax.grad(lambda q_: jnp.sum(
            salo_attention(q_, k, v, pat, 8, 8, None, False)))(q)


# ---------------------- transposed-plan contract ------------------------ #
TP_CASES = [
    ("longformer", P.longformer(8, n_global=2), 37, 8, 8),
    ("vil_2d", P.vil((5, 7), (3, 3), n_global=2), None, 8, 8),
    ("dilated_sinks", P.causal_sliding_window(5, n_sinks=2, dilation=2),
     31, 8, 8),
    ("asym_blocks", P.causal_sliding_window(7), 33, 8, 16),
]


@pytest.mark.parametrize("name,pat,n,bq,bk", TP_CASES)
def test_transposed_plan_exact_adjoint(name, pat, n, bq, bk):
    """Transposed tables = the forward visit set with (i, j) swapped —
    same flags, each visit once, dedup preserved (equal totals)."""
    n = n if n is not None else pat.seq_len()
    plan = build_plan(schedule(pat, n), bq, bk)
    tp = plan.transposed()

    fwd = {(i, int(plan.kv_blocks[i, s])): int(plan.flags[i, s])
           for i in range(plan.nq) for s in range(int(plan.num_steps[i]))}
    bwd = {(int(tp.q_blocks[j, s]), j): int(tp.flags[j, s])
           for j in range(plan.nkb) for s in range(int(tp.num_steps[j]))}
    assert fwd == bwd, f"{name}: transposed walk is not the exact adjoint"
    # dedup preserved: identical tile totals (so within any 1.1x budget)
    assert int(tp.num_steps.sum()) == int(plan.num_steps.sum())
    # same padding contract: flags 0 beyond num_steps, ascending q order
    for j in range(plan.nkb):
        ns = int(tp.num_steps[j])
        assert (tp.flags[j, ns:] == 0).all()
        assert (tp.q_blocks[j, ns:] == 0).all()
        row = tp.q_blocks[j, :ns]
        assert (np.diff(row) > 0).all(), f"{name}: row {j} not deduped/sorted"


def test_transposed_plan_cached_and_in_stats():
    pat = P.vil((5, 7), (3, 3), 1)
    plan = build_plan(schedule(pat, pat.seq_len()), 8, 8)
    assert plan.transposed() is plan.transposed()  # lru-cached
    stats = plan.stats()
    assert stats["bwd_dq_tiles"] == stats["executed_tiles"]
    assert stats["bwd_dkv_tiles"] == stats["executed_tiles"]
    assert stats["bwd_launches"] == 2


# ------------------------- empty-row contract --------------------------- #
def test_dead_rows_emit_merge_identity_and_zero_grads():
    """Rows with no reachable key: (out=0, m=NEG_INF, l=0) from the kernel,
    and exactly zero (finite!) gradients through the fused backward."""
    from repro.core.blockwise import working_stream
    from repro.core.renorm import NEG_INF
    from repro.kernels.salo_attention import salo_plan_attention

    pat = P.HybridSparsePattern(window=(2, 5))  # rows >= n-2 attend nothing
    n, d = 16, 8
    sched = schedule(pat, n)
    plan = sched.plan(8, 8)
    rng = np.random.default_rng(4)
    q, k, v, cot = (jnp.asarray(rng.normal(size=(1, n, d)), jnp.float32)
                    for _ in range(4))
    empty = ~pat.mask(n).any(axis=1)
    assert empty.sum() >= 2

    qw = working_stream(q, sched, plan)
    kw = working_stream(k, sched, plan)
    vw = working_stream(v, sched, plan)
    pos = jnp.asarray(plan.positions_padded())
    out_w, m, l = salo_plan_attention(qw, kw, vw, pos, plan=plan,
                                      scale=d ** -0.5, interpret=True)
    np.testing.assert_array_equal(np.asarray(l)[0, :n][empty], 0.0)
    np.testing.assert_array_equal(np.asarray(m)[0, :n][empty],
                                  np.float32(NEG_INF))
    np.testing.assert_array_equal(np.asarray(out_w)[0, :n][empty], 0.0)

    for impl in ("pallas_interpret", "blockwise"):
        def loss(q_, k_, v_):
            out = hybrid_attention(q_[None], k_[None], v_[None], pat,
                                   impl=impl, block_q=8, block_k=8)[0]
            return jnp.sum(out * cot)
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g in (dq, dk, dv):
            assert np.isfinite(np.asarray(g)).all(), impl
        np.testing.assert_array_equal(np.asarray(dq)[0, empty], 0.0,
                                      err_msg=impl)
