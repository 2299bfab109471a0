"""Smoke run of the main path on a TPU: smollm-135m at its published widths
(30 layers, d 576, 9/3 heads, hd 64, vocab 49152, window 1024 + 4 sinks,
bf16) through the compiled SALO kernels, random weights from ``--seed``.

  python chip_smoke.py             # phases a-c on one chip
  python chip_smoke.py --chips 4   # only the cross-chip path, on four chips

One chip:
  a. kernel parity: ``hybrid_attention(impl="pallas")`` at B*H = 4*9,
     N 4096 — forward against ``dense_ref`` in f32, the full gradient
     (1 forward + 2 backward launches) against the ``blockwise`` engine.
  b. serving: the continuous engine built by ``launch/serve.py``'s own
     setup (int8 slab, the platform's decode engine), 8 requests with
     prompts spread over 512-4096 tokens (the ring wraps past the 1024
     window), 32 new tokens each; every request must finish, and the
     greedy tokens must equal those of the XLA decode twin on the same
     requests. Where bf16 rounding flips a near-tie of the random-weight
     logits, the comparison is repeated in f32 compute and must then be
     exact.
  c. training: 10 steps of ``launch/train.py``'s step at seq 2048, batch 8;
     the loss is finite at every step and lower at the end.

Four chips (``--chips 4``): ``ContinuousEngine(seq_shards=4)`` against
``seq_shards=1`` on the phase-b requests, and ``sharded_attention``
forward + gradient on 4 shards against one device at the phase-a shapes,
with every array's placement printed.

Every phase checks that its jitted program holds the named Pallas kernels
as ``tpu_custom_call``s. Any failure exits non-zero. Without a TPU the
script exits 2 before running anything. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Runs from the repository root, one process, no ``PYTHONPATH`` needed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
PAGE, CHUNK, NEW_TOKENS = 8, 512, 32
PROMPT_RANGE, N_REQUESTS = (512, 4096), 8
ATTN_SHAPE = dict(B=4, N=4096)            # heads/hd from the model config
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 2048, 8, 10
FWD_TOL, GRAD_TOL = 2e-2, 2e-2            # max |err| / max |reference|

FWD_KERNEL = "salo_plan_attention"
GRAD_KERNELS = (FWD_KERNEL, "salo_plan_backward_dq", "salo_plan_backward_dkv")
DECODE_KERNEL = "salo_paged_decode"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits of this process,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.cache_hits = 0.0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def require_kernels(hlo_text: str, names, what: str) -> None:
    missing = [n for n in names if n not in hlo_text]
    if "tpu_custom_call" not in hlo_text or missing:
        raise AssertionError(f"{what}: compiled kernels missing from the "
                             f"HLO: {missing or 'no tpu_custom_call'}")
    log(f"  {what}: HLO holds {', '.join(names)} as tpu_custom_call")


def rel_err(a, b) -> tuple:
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    err = float(np.max(np.abs(a - b)))
    return err, err / max(float(np.max(np.abs(b))), 1e-30)


def check_close(name: str, a, b, tol: float) -> None:
    err, rel = rel_err(a, b)
    log(f"  {name}: max|err| {err:.6g}, max|err|/max|ref| {rel:.6g} "
        f"(tol {tol})")
    if not rel <= tol:
        raise AssertionError(f"{name}: error {rel:.6g} over tolerance {tol}")


# --------------------------------------------------------------------- #
# a. kernel parity
# --------------------------------------------------------------------- #
def attention_inputs(cfg, B: int, N: int, seed: int):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jax.random.normal(ks[0], (B, H, N, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Hkv, N, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Hkv, N, D), jnp.bfloat16)
    cot = jax.random.normal(ks[3], (B, H, N, D), jnp.bfloat16)
    return q, k, v, cot


def phase_kernels(cfg, B: int, N: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.attention import hybrid_attention
    from repro.models.layers import salo_pattern

    pat = salo_pattern(cfg, causal=True)
    blocks = dict(block_q=cfg.salo.block_q, block_k=cfg.salo.block_k)
    q, k, v, cot = attention_inputs(cfg, B, N, seed)

    def fwd(impl):
        return lambda q_, k_, v_: hybrid_attention(q_, k_, v_, pat,
                                                   impl=impl, **blocks)

    def grad(impl):
        def loss(q_, k_, v_):
            out = fwd(impl)(q_, k_, v_)
            return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    fwd_c = jax.jit(fwd("pallas")).lower(q, k, v).compile()
    grad_c = grad("pallas").lower(q, k, v).compile()
    require_kernels(fwd_c.as_text(), (FWD_KERNEL,), "attention forward")
    require_kernels(grad_c.as_text(), GRAD_KERNELS, "attention gradient")
    out = fwd_c(q, k, v)
    # dense f32 oracle, one batch row at a time (B*H*N^2 f32 scores at
    # once would not fit next to everything else)
    dense = jax.jit(fwd("dense_ref"))
    up = lambda x: x.astype(jnp.float32)  # noqa: E731
    ref = jnp.concatenate([dense(up(q[b:b + 1]), up(k[b:b + 1]),
                                 up(v[b:b + 1])) for b in range(B)])
    check_close("forward vs dense_ref(f32)", out, ref, FWD_TOL)
    g_k = grad_c(q, k, v)
    g_t = grad("blockwise")(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_k, g_t):
        check_close(f"{name} vs blockwise", a, b, GRAD_TOL)
    return {"fwd_rel_err": rel_err(out, ref)[1],
            "grad_rel_err": max(rel_err(a, b)[1] for a, b in zip(g_k, g_t))}


# --------------------------------------------------------------------- #
# b. serving
# --------------------------------------------------------------------- #
def make_prompts(cfg, seed: int):
    """``N_REQUESTS`` ragged prompt lengths spread evenly over
    ``PROMPT_RANGE`` (each pulled down by up to 60 tokens, never below its
    low end), random token ids."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_RANGE
    lens = (np.linspace(lo, hi, N_REQUESTS).astype(int)
            - rng.integers(0, 61, N_REQUESTS))
    lens = np.clip(lens, lo, hi)
    return [rng.integers(0, cfg.vocab_size, (int(L),)) for L in lens]


def serve(cfg, prompts, *, seed: int, decode_impl=None, seq_shards=1):
    """Run ``prompts`` to completion on a continuous engine set up exactly
    as ``launch/serve.py`` sets it up. Returns (engine, params, tokens)."""
    import jax
    import numpy as np

    from repro.launch.serve import continuous_setup
    from repro.models.model import build_model
    from repro.serve.engine import ContinuousEngine

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    ccfg, mesh = continuous_setup(cfg, max_batch=len(prompts), page=PAGE,
                                  chunk=CHUNK, seq_shards=seq_shards,
                                  kv_dtype="int8")
    if decode_impl is not None:
        ccfg = dataclasses.replace(ccfg, decode_impl=decode_impl)
    eng = ContinuousEngine(model, ccfg, mesh=mesh)
    rids = [eng.submit(p, NEW_TOKENS) for p in prompts]
    res = eng.run(params)
    failures = eng.batcher.failures()
    if failures or sorted(res) != sorted(rids):
        raise AssertionError(f"requests did not finish: {failures}")
    toks = np.stack([np.asarray(res[r]) for r in rids])
    if toks.shape != (len(prompts), NEW_TOKENS):
        raise AssertionError(f"token shape {toks.shape}")
    return eng, params, toks


def decode_hlo(eng, params) -> str:
    """Lowered HLO of the engine's decode step (not re-compiled)."""
    import jax.numpy as jnp
    import numpy as np

    R = eng.ccfg.max_batch
    pt = eng.page_tables
    if eng.n_shards > 1:
        pt = pt.reshape(R, eng.n_shards, -1).transpose(1, 0, 2)
    z = jnp.zeros(R, jnp.int32)
    return eng._decode_jit.lower(params, eng.slabs, np.ascontiguousarray(pt),
                                 eng.slot_pos, z, z,
                                 jnp.zeros(R, bool)).as_text()


def first_divergence(a, b):
    import numpy as np

    rows, steps = np.nonzero(a != b)
    if rows.size == 0:
        return None
    i = int(np.argmin(steps * a.shape[0] + rows))
    return int(rows[i]), int(steps[i])


def token_parity(cfg, label: str, run_pair) -> str:
    """``run_pair(cfg) -> (tokens under test, reference tokens)`` must give
    identical greedy tokens. If they differ at bf16 compute, both runs are
    repeated in f32 compute, where they must then agree exactly: bf16
    rounding may flip a near-tie of the random-weight logits, a wrong
    kernel also breaks f32. Returns the compute dtype that agreed."""
    import numpy as np

    for dtype in ("bfloat16", "float32"):
        c = cfg if dtype == cfg.compute_dtype else dataclasses.replace(
            cfg, compute_dtype=dtype, param_dtype=dtype)
        test, ref = run_pair(c)
        div = first_divergence(test, ref)
        if div is None:
            log(f"  {label} [{dtype} compute]: greedy tokens identical "
                f"({test.size} tokens)")
            return dtype
        log(f"  {label} [{dtype} compute]: tokens agree "
            f"{float(np.mean(test == ref)):.4f}; first divergence request "
            f"{div[0]} step {div[1]} ({test[div]} vs {ref[div]})")
        if dtype == "float32":
            raise AssertionError(f"{label}: tokens differ in f32 compute")
        log(f"  {label}: repeating the comparison in f32 compute")
    raise AssertionError("unreachable")


def phase_serve(cfg, seed: int, prompts) -> dict:
    log(f"  prompt lengths {[len(p) for p in prompts]}, {NEW_TOKENS} new "
        f"tokens each, page {PAGE}, chunk {CHUNK}, int8 slab")

    def run_pair(c):
        eng, params, toks = serve(c, prompts, seed=seed)
        log(f"  decode engine {eng.decode_impl} [{c.compute_dtype}]: "
            f"{len(prompts)} requests finished, counters "
            f"{dict(eng.counters)}")
        require_kernels(decode_hlo(eng, params), (DECODE_KERNEL,),
                        "serving decode step")
        return toks, serve(c, prompts, seed=seed, decode_impl="xla")[2]

    dtype = token_parity(cfg, "kernel vs xla decode", run_pair)
    return {"requests": len(prompts), "parity_compute_dtype": dtype}


# --------------------------------------------------------------------- #
# c. training
# --------------------------------------------------------------------- #
def phase_train(cfg, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_train_step, train_config
    from repro.models.model import build_model
    from repro.optim import adamw

    model = build_model(cfg)
    mesh = make_host_mesh(1, 1)
    tcfg = train_config(lr=3e-3, steps=TRAIN_STEPS)
    params = model.init(jax.random.PRNGKey(seed))
    opt = adamw.init(tcfg.optimizer, params)
    ds = SyntheticLM(cfg, DataConfig(TRAIN_SEQ, TRAIN_BATCH, seed=seed))
    losses = []
    with mesh:
        batch0 = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
        step = build_train_step(model, tcfg, mesh).lower(
            params, opt, batch0, None).compile()
        require_kernels(step.as_text(), GRAD_KERNELS, "train step")
        for i in range(TRAIN_STEPS):
            b = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
            params, opt, metrics, _ = step(params, opt, b, None)
            losses.append(float(metrics["loss"]))
            log(f"  step {i}: loss {losses[-1]:.6f}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")
    return {"loss_first": losses[0], "loss_last": losses[-1]}


# --------------------------------------------------------------------- #
# four chips: the cross-chip path
# --------------------------------------------------------------------- #
def placement(name: str, arr) -> None:
    """Print an array's sharding and bytes per device; fail if it sits on
    fewer devices than its sharding spans."""
    per_dev = {}
    for s in arr.addressable_shards:
        per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    log(f"  {name}: {arr.shape} {arr.dtype} {arr.sharding} "
        f"bytes/device {per_dev}")
    if len(per_dev) != len(arr.sharding.device_set):
        raise AssertionError(f"{name} is not on every device of its mesh")


def phase_sharded_serve(cfg, seed: int, prompts, shards: int) -> dict:
    import jax

    def run_pair(c):
        eng, params, toks = serve(c, prompts, seed=seed, seq_shards=shards)
        require_kernels(decode_hlo(eng, params), (DECODE_KERNEL,),
                        f"seq_shards={shards} decode step")
        slab = next(iter(eng.slabs.values()))
        for name, leaf in zip(slab._fields, slab):
            placement(f"slab.{name}", leaf)
        placement("slot_pos", eng.slot_pos)
        for a in jax.tree.leaves(eng.slabs) + [eng.slot_pos]:
            if len(a.sharding.device_set) != shards:
                raise AssertionError("serving state not spread over the "
                                     f"{shards}-device mesh: {a.sharding}")
        return toks, serve(c, prompts, seed=seed)[2]

    dtype = token_parity(cfg, f"seq_shards={shards} vs 1", run_pair)
    return {"parity_compute_dtype": dtype}


def phase_sharded_attention(cfg, B: int, N: int, seed: int,
                            shards: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.attention import hybrid_attention
    from repro.dist.sharded_plan import sharded_attention
    from repro.launch.mesh import make_mesh
    from repro.models.layers import salo_pattern

    pat = salo_pattern(cfg, causal=True)
    q, k, v, cot = attention_inputs(cfg, B, N, seed)
    rep = cfg.n_heads // cfg.n_kv_heads
    # (B*H, N, D) streams, KV heads repeated for GQA
    flat = lambda x: x.reshape(-1, N, x.shape[-1])  # noqa: E731
    kr, vr = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    q3, k3, v3, c3 = map(flat, (q, kr, vr, cot))
    mesh = make_mesh((shards,), ("seq",), devices=jax.devices()[:shards])
    sh = NamedSharding(mesh, P(None, "seq", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q3, k3, v3))

    def loss_sharded(a, b, c):
        out = sharded_attention(a, b, c, pat, mesh, axis="seq", impl="pallas")
        return jnp.sum(out.astype(jnp.float32) * c3.astype(jnp.float32)), out

    def loss_single(a, b, c):
        out = hybrid_attention(a[None], b[None], c[None], pat, impl="pallas",
                               block_q=cfg.salo.block_q,
                               block_k=cfg.salo.block_k)[0]
        return jnp.sum(out.astype(jnp.float32) * c3.astype(jnp.float32)), out

    vg = jax.value_and_grad(loss_sharded, argnums=(0, 1, 2), has_aux=True)
    sharded_c = jax.jit(vg).lower(qs, ks, vs).compile()
    require_kernels(sharded_c.as_text(), GRAD_KERNELS,
                    f"sharded attention on {shards} chips")
    (_, out_s), g_s = sharded_c(qs, ks, vs)
    one = jax.devices()[0]
    q1, k1, v1 = (jax.device_put(x, one) for x in (q3, k3, v3))
    (_, out_1), g_1 = jax.jit(jax.value_and_grad(
        loss_single, argnums=(0, 1, 2), has_aux=True))(q1, k1, v1)
    placement("q (input)", qs)
    placement("out", out_s)
    for name, g in zip(("dq", "dk", "dv"), g_s):
        placement(name, g)
    check_close(f"{shards}-shard forward vs one device", out_s, out_1,
                FWD_TOL)
    for name, a, b in zip(("dq", "dk", "dv"), g_s, g_1):
        check_close(f"{shards}-shard {name} vs one device", a, b, GRAD_TOL)
    return {"fwd_rel_err": rel_err(out_s, out_1)[1]}


# --------------------------------------------------------------------- #
def run_phase(results: dict, name: str, fn, *args, **kw) -> None:
    log(f"== phase {name}")
    t0 = time.perf_counter()
    results[name] = fn(*args, **kw)
    log(f"== phase {name}: ok ({time.perf_counter() - t0:.1f}s wall, "
        f"compile included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip path, on four chips")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2
    from repro.configs import get_config

    cfg = get_config("smollm-135m")
    clock = CompileClock()
    log(f"# device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}"
        f", compile cache {cache_dir}")
    log(f"# {cfg.name}: {cfg.n_layers} layers, d {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads}, hd {cfg.hd}, vocab "
        f"{cfg.vocab_size}, window {cfg.salo.window} + {cfg.salo.n_global}"
        f" sinks, {cfg.compute_dtype}")
    results: dict = {}
    prompts = make_prompts(cfg, args.seed)
    if args.chips == 1:
        run_phase(results, "a-kernels", phase_kernels, cfg,
                  ATTN_SHAPE["B"], ATTN_SHAPE["N"], args.seed)
        run_phase(results, "b-serving", phase_serve, cfg, args.seed,
                  prompts)
        run_phase(results, "c-training", phase_train, cfg, args.seed)
    else:
        run_phase(results, "sharded-serving", phase_sharded_serve, cfg,
                  args.seed, prompts, args.chips)
        run_phase(results, "sharded-attention", phase_sharded_attention,
                  cfg, ATTN_SHAPE["B"], ATTN_SHAPE["N"], args.seed,
                  args.chips)
    log(f"# compile: {clock.seconds:.1f}s backend compile, "
        f"{clock.cache_hits} persistent-cache hits")
    log(f"# results {json.dumps(results)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
