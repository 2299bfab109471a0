"""Multi-device tests (8 forced host devices, run in a subprocess so the
rest of the suite keeps its single-device view):
  * ShardedPlan sequence-parallel attention: fwd + bwd parity vs the
    single-device fused path across every supported pattern family
    (longformer bidirectional + global rows, dilated/reordered-global,
    ViL 2-D multi-band, window == n_local boundary, g > n_local), with
    both shard-local engines (XLA scan twin and the Pallas table kernels)
  * a model forward under live "seq" rules takes the sharded route and
    matches the unsharded logits
  * the retired sequence_parallel_attention entry point still answers
    (now a shim over the ShardedPlan engine)
  * input_sharding drops absent / non-dividing mesh axes (_mesh_clean)
  * pjit'd train step runs under a (2, 4) mesh with the production rules
  * elastic rescale: checkpoint from mesh A restores onto mesh B
  * int8-compressed gradient psum convergence
  * compress_grads wires compressed_psum into the pod/data reduce INSIDE
    train_step (shard_map), error feedback converging on the int8 wire
  * sequence-parallel continuous serving: the 8-shard engine (sharded
    paged slab + distributed ragged decode) emits greedy tokens identical
    to the single-device ContinuousEngine across ragged batches, page
    recycling, ring wraparound across shard boundaries, dilation > 1, and
    the paged decode kernel inside shard_map
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", prog],
                       env={**os.environ, "PYTHONPATH": SRC},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sequence_parallel_attention_matches_oracle():
    """sharded_attention keeps the retired prototype's contract on the
    patterns the prototype supported (its shim was deleted — this is the
    direct entry point)."""
    _run("""
        from repro.core import patterns as P_
        from repro.dist.sharded_plan import sharded_attention
        from repro.kernels.ref import reference_attention
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        B, N, D = 2, 128, 16
        q, k, v = (jnp.asarray(rng.normal(size=(B, N, D)), jnp.float32)
                   for _ in range(3))
        for pat in (P_.causal_sliding_window(12, n_sinks=3),
                    P_.longformer(8, n_global=2),
                    P_.causal_sliding_window(16)):
            ref = reference_attention(q, k, v, pat)
            with mesh:
                out = jax.jit(lambda a, b, c: sharded_attention(
                    a, b, c, pat, mesh, "data"))(q, k, v)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)
        print("SP-ATTN-OK")
    """)


# --------------------- ShardedPlan fwd + bwd parity --------------------- #
_PARITY_PRELUDE = """
        from repro.core import patterns as P_
        from repro.core.blockwise import blockwise_attention
        from repro.dist.sharded_plan import sharded_attention
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)

        def check(name, pat, N, impl):
            B, D = 2, 16
            q, k, v, cot = (jnp.asarray(rng.normal(size=(B, N, D)), jnp.float32)
                            for _ in range(4))
            # single-device fused-path twin (same plan IR, same backward)
            ref = blockwise_attention(q, k, v, pat, block_q=16, block_k=16)
            g_ref = jax.grad(lambda a, b, c: jnp.sum(blockwise_attention(
                a, b, c, pat, block_q=16, block_k=16) * cot),
                argnums=(0, 1, 2))(q, k, v)
            with mesh:
                out = jax.jit(lambda a, b, c: sharded_attention(
                    a, b, c, pat, mesh, impl=impl))(q, k, v)
                g = jax.jit(jax.grad(lambda a, b, c: jnp.sum(sharded_attention(
                    a, b, c, pat, mesh, impl=impl) * cot),
                    argnums=(0, 1, 2)))(q, k, v)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4, err_msg=name)
            for gname, a, b in zip("qkv", g_ref, g):
                np.testing.assert_allclose(
                    np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4,
                    err_msg=f"{name}: d{gname}")
            print("ok", name, impl)

"""

_PARITY_RUN = """
        for case in CASES:
            check(*case)
        print("SHARDED-PARITY-OK")
"""


def test_sharded_plan_parity_pattern_families():
    """Sharded fwd+bwd == single-device fused path across the supported
    families: longformer (bidirectional window + global rows => both-side
    halos + psum merge), dilated (data reordering), reordered-global
    (dilated sinks), ViL 2-D multi-band, and the window == n_local
    boundary."""
    _run(_PARITY_PRELUDE + """
        CASES = [
            ("longformer", P_.longformer(8, n_global=2), 128, "blockwise"),
            ("longformer_causal",
             P_.longformer(8, n_global=2, causal=True), 128, "blockwise"),
            ("dilated", P_.dilated_window(4, 3), 128, "blockwise"),
            ("reordered_global",
             P_.causal_sliding_window(5, n_sinks=2, dilation=2), 128,
             "blockwise"),
            ("vil_2d", P_.vil((16, 16), (5, 5), 1), 257, "blockwise"),
            ("window_eq_nlocal", P_.causal_sliding_window(16), 128,
             "blockwise"),
        ]
    """ + _PARITY_RUN)


def test_sharded_plan_parity_pallas_engine():
    """The fused Pallas kernels (table-driven entry points, interpret mode
    on CPU) execute inside shard_map with the same parity."""
    _run(_PARITY_PRELUDE + """
        CASES = [
            ("sinks_pallas", P_.causal_sliding_window(12, n_sinks=3), 128,
             "pallas_interpret"),
            ("vil_pallas", P_.vil((8, 9), (3, 5), 1), 73,
             "pallas_interpret"),
            ("longformer_pallas", P_.longformer(8, n_global=2), 128,
             "pallas_interpret"),
        ]
    """ + _PARITY_RUN)


def test_sharded_plan_global_exceeds_shard():
    """Regression for the retired prototype's silent truncation: with
    g > N // n_shards the global prefix spans multiple shards; the
    owner-keyed psum broadcast must still deliver every global tile."""
    _run(_PARITY_PRELUDE + """
        CASES = [
            ("g_gt_nlocal", P_.causal_sliding_window(8, n_sinks=24), 128,
             "blockwise"),
            ("g_gt_nlocal_rows", P_.longformer(8, n_global=24), 128,
             "blockwise"),
        ]
    """ + _PARITY_RUN)


def test_sharded_route_via_seq_rules_in_model():
    """A model forward under live "seq" rules takes the ShardedPlan route
    through layers.attn_apply and matches the unsharded logits."""
    _run("""
        from repro.configs import get_smoke
        from repro.dist import sharding as shlib
        from repro.dist import sharded_plan as spm
        from repro.models.model import build_model
        cfg = get_smoke("smollm-135m")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, 64)))}
        base = model.forward(params, batch)

        calls = []
        orig = spm.sharded_attention
        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)
        spm.sharded_attention = spy

        mesh = make_mesh((8,), ("data",))
        rules = dict(shlib.DEFAULT_RULES)
        rules.update(batch=None, seq=("data",))
        def fwd(p, b):
            with shlib.axis_rules(rules, mesh):
                return model.forward(p, b)
        with mesh:
            out = jax.jit(fwd)(params, batch)
        assert calls, "seq rules did not engage the sharded route"
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=2e-3, atol=2e-3)
        print("SEQ-RULES-ROUTE-OK", len(calls))
    """)


def test_input_sharding_mesh_clean():
    """input_sharding must produce VALID NamedShardings when a rule names a
    mesh axis that is absent or doesn't divide the dim (the bug
    launch/specs.py used to work around with a duplicated _divisible)."""
    _run("""
        from repro.dist.sharding import input_sharding
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = {"batch": ("pod", "data"), "seq": None, "vocab": ("model",)}
        # "pod" doesn't exist on this mesh: must be dropped, "data" kept.
        sh = input_sharding(mesh, rules, "batch", "seq",
                            shape=(4, 64))
        x = jax.device_put(jnp.zeros((4, 64)), sh)      # must not raise
        assert sh.spec == P(("data",), None), sh.spec
        # 63 % 4 != 0: the vocab axis must be dropped for an argument
        # sharding (pjit rejects non-dividing argument shardings).
        sh2 = input_sharding(mesh, rules, "vocab", shape=(63,))
        assert sh2.spec == P(None), sh2.spec
        jax.device_put(jnp.zeros((63,)), sh2)
        # without a shape the membership check still applies
        sh3 = input_sharding(mesh, rules, "batch")
        assert sh3.spec == P(("data",)), sh3.spec
        # one mesh axis may shard at most one dim
        sh4 = input_sharding(mesh, {"a": ("model",), "b": ("model",)},
                             "a", "b", shape=(8, 8))
        assert sh4.spec == P(("model",), None), sh4.spec
        print("INPUT-SHARDING-OK")
    """)


def test_pjit_train_step_under_mesh():
    _run("""
        from repro.configs import get_smoke
        from repro.configs.base import ShapeCell
        from repro.launch.specs import build_cell
        import dataclasses
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke("smollm-135m")
        shape = ShapeCell("t", 64, 4, "train")
        fn, args, in_sh, out_sh, rules = build_cell(cfg, shape, mesh)
        from repro.models.model import build_model
        from repro.optim import adamw
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tcfg_opt = adamw.AdamWConfig()
        opt = adamw.init(tcfg_opt, params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64))),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)))}
        with mesh:
            params = jax.device_put(params, in_sh[0])
            opt = jax.device_put(opt, jax.tree.map(lambda s: s, in_sh[1],
                                 is_leaf=lambda x: hasattr(x, "spec")))
            step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            p2, o2, metrics = step(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))
        print("PJIT-TRAIN-OK", float(metrics["loss"]))
    """)


def test_elastic_rescale_8_to_4():
    _run("""
        import tempfile
        from repro.ft import checkpoint as ck
        tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
        mesh8 = make_mesh((8,), ("data",))
        sh8 = {"w": NamedSharding(mesh8, P("data", None))}
        placed = jax.device_put(tree, sh8)
        d = tempfile.mkdtemp()
        ck.save(d, placed, 1)
        # restore onto a 4-device mesh (elastic shrink)
        mesh4 = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        sh4 = {"w": NamedSharding(mesh4, P("data", None))}
        restored = ck.restore(d, tree, shardings=sh4)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding.num_devices == 4
        print("ELASTIC-OK")
    """)


def test_compressed_psum_in_train_step_pod_axis():
    """compress_grads=True wires compressed_psum into the pod/data-axis
    reduce INSIDE train_step (shard_map over both axes): the first step's
    loss matches the pjit fp32 path exactly (loss is computed before the
    reduce), error feedback keeps convergence on top of the int8 wire, and
    the per-participant residual state is threaded with the fixed 4-tuple
    arity."""
    _run("""
        from repro.configs import get_smoke
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.dist import sharding as shlib
        from repro.models.model import build_model
        from repro.optim import adamw
        from repro.train.trainer import TrainConfig, make_train_step
        cfg = get_smoke("smollm-135m")
        model = build_model(cfg)
        params0 = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((2, 4), ("pod", "data"))
        rules = dict(shlib.DEFAULT_RULES, batch=("pod", "data"), fsdp=None)
        ds = SyntheticLM(cfg, DataConfig(seq_len=64, global_batch=8))

        def run(compress, steps):
            tcfg = TrainConfig(
                optimizer=adamw.AdamWConfig(lr=1e-2, grad_clip=1.0),
                compress_grads=compress)
            raw = make_train_step(model, tcfg)
            def fn(p, o, b, ef):
                with shlib.axis_rules(rules, mesh):
                    return raw(p, o, b, ef)
            step = jax.jit(fn)
            params, opt, ef = params0, adamw.init(tcfg.optimizer,
                                                  params0), None
            losses = []
            with mesh:
                for i in range(steps):
                    batch = {k: jnp.asarray(v)
                             for k, v in ds.batch(i % 4).items()}
                    params, opt, metrics, ef = step(params, opt, batch, ef)
                    losses.append(float(metrics["loss"]))
            return params, losses, ef

        p_ref, l_ref, ef_ref = run(False, 25)
        p_c, l_c, ef_c = run(True, 25)
        assert ef_ref is None
        leaf = jax.tree.leaves(ef_c)[0]
        assert leaf.shape[0] == 8, leaf.shape  # 2 pod x 4 data participants
        # first-step loss is pre-reduce: must agree exactly
        assert abs(l_c[0] - l_ref[0]) < 1e-5, (l_c[0], l_ref[0])
        # error feedback: int8 wire converges alongside fp32
        assert l_c[-1] < l_c[0] - 0.5, l_c[::6]
        assert abs(l_c[-1] - l_ref[-1]) < 0.3, (l_c[-1], l_ref[-1])
        print("COMPRESSED-TRAIN-STEP-OK", l_c[-1])
    """)


def test_compressed_psum_across_shards():
    _run("""
        from repro.dist.compression import compressed_psum
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
        def f(x):
            return compressed_psum(x[0], "data")[None]
        with mesh:
            out = jax.jit(jax.shard_map(f, mesh=mesh,
                                        in_specs=(P("data", None),),
                                        out_specs=P("data", None)))(g)
        ref = jnp.sum(g, axis=0)
        rel = float(jnp.max(jnp.abs(out[0] - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 0.05, rel
        print("COMPRESSED-PSUM-OK", rel)
    """)


# ----------------- sequence-parallel continuous serving ----------------- #
_SERVE_PRELUDE = """
        import dataclasses
        from repro.configs import get_smoke
        from repro.models.model import build_model
        from repro.models.layers import salo_pattern
        from repro.serve.engine import ContinuousConfig, ContinuousEngine
        from repro.serve.paged_cache import layout_for_pattern
        mesh = make_mesh((8,), ("seq",))
        rng = np.random.default_rng(3)

        def pair(cfg, lens, n_new, max_batch, impl="xla", seed=1):
            '''Greedy tokens of the 8-shard engine must equal the
            single-device ContinuousEngine token-for-token.'''
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(seed))
            prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
                       for L in lens]
            pat = salo_pattern(cfg, causal=True)
            l1 = layout_for_pattern(pat, 8)
            e1 = ContinuousEngine(model, ContinuousConfig(
                n_pages=1 + max_batch * l1.pages_per_req, page=8, chunk=8,
                max_batch=max_batch, decode_impl=impl))
            r1 = [e1.submit(p, n_new) for p in prompts]
            ref = e1.run(params)
            l8 = layout_for_pattern(pat, 8, shards=8)
            e8 = ContinuousEngine(model, ContinuousConfig(
                n_pages=1 + max_batch * l8.pages_per_shard, page=8, chunk=8,
                max_batch=max_batch, decode_impl=impl, seq_shards=8),
                mesh=mesh)
            r8 = [e8.submit(p, n_new) for p in prompts]
            out = e8.run(params)
            for a, b in zip(r1, r8):
                np.testing.assert_array_equal(ref[a], out[b])
            # per-shard pools fully recycled on completion
            for al in e8.batcher.allocs:
                assert al.n_free == e8.ccfg.n_pages - 1
            return e8
"""


def test_sharded_serving_ragged_and_recycling():
    """8-shard continuous engine == single-device engine token-for-token on
    a ragged batch with more requests than rows (page-recycling waves over
    the per-shard pools), and with the paged decode KERNEL inside
    shard_map (pallas_interpret partial-state path)."""
    _run(_SERVE_PRELUDE + """
        cfg = get_smoke("smollm-135m")
        pair(cfg, (5, 11, 7, 9, 6), 4, 2)
        print("RAGGED-RECYCLE-OK")
        pair(cfg, (7, 12), 4, 2, impl="pallas_interpret")
        print("SHARDED-KERNEL-OK")
        # bf16 compute: partials stay f32 until ONE post-merge round, so
        # the low-precision dtype must not break token-exactness either
        cfgb = dataclasses.replace(cfg, compute_dtype="bfloat16")
        pair(cfgb, (9, 14), 6, 2, seed=2)
        print("SHARDED-BF16-OK")
    """)


def test_sharded_serving_ring_wraparound_and_dilation():
    """Ring wraparound ACROSS shard boundaries: window=8 with 8 shards puts
    each shard's slice at a couple of ring slots, and t >> window drives
    many revolutions through all of them; dilation > 1 exercises the
    dilated-lookback ring under the sharded slot map."""
    _run(_SERVE_PRELUDE + """
        cfg = get_smoke("smollm-135m")
        cfgw = dataclasses.replace(cfg, salo=dataclasses.replace(
            cfg.salo, window=8))
        pair(cfgw, (21, 6), 40, 2)
        print("SHARD-WRAP-OK")
        cfgd = dataclasses.replace(cfg, salo=dataclasses.replace(
            cfg.salo, window=4, dilation=2, n_global=2))
        pair(cfgd, (11, 17), 10, 2)
        print("SHARD-DILATED-OK")
    """)


def test_multipod_mesh_shape():
    _run("""
        # 8 devices reshaped as a miniature (pod, data, model) mesh to prove
        # the 3-axis sharding rules compose (full 512-chip version runs in
        # the dry-run).
        from repro.configs import get_smoke
        from repro.configs.base import ShapeCell
        from repro.launch.specs import build_cell
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_smoke("arctic-480b")  # MoE: exercises EP rules too
        shape = ShapeCell("t", 64, 4, "train")
        fn, args, in_sh, out_sh, rules = build_cell(cfg, shape, mesh)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            compiled = lowered.compile()
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        print("MULTIPOD-SMOKE-OK")
    """)
