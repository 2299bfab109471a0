"""A training cell: ``launch/train.py``'s jitted step on packed documents.

Set-up builds one object, the compiled step with its state (the weights
from the seed, AdamW as the traffic file states it), and drives it through
its first ``check_steps`` steps on batches from the seed; those steps warm
the one program the window runs. The window then keeps stepping that same
object: each step's batch is made on the host while the device runs the
step before, and a step counts once ``block_until_ready`` on its loss
returns inside the window.

  train_tokens_per_s   tokens of the steps completed in the window / the
                       seconds from the window's start to the return of
                       the last completed step's ``block_until_ready``
                       (all the work over its own time, not stepped by
                       whole steps against the window's length)

``correct``: the architecture's float32 reference (``reference/train.py``
for ``dense_decoder``) follows the first steps from the same weights and
batches, and three numbers are compared, each by its worst case:
  loss_rel_gap     |program loss - reference loss| / reference loss, per step
  grad_norm_gap    per leaf, the first step's clipped gradient as the
                   optimizer got it (its first moment / (1 - b1)) against
                   the reference's, as a gap of norms over the larger of the
                   reference leaf's norm and the median leaf's
  update_norm_gap  per leaf, the change of the (master) weights over the
                   first steps, measured the same way; leaves whose
                   reference gradient is under a thousandth of the median
                   leaf's move by round-off alone and are left out
"""
from __future__ import annotations

import dataclasses
import gc
import time
import types

import numpy as np

from chipbench import gen, harness, model

TINY_GRAD = 1e-3     # of the median leaf's gradient norm


def leaf_norms(tree) -> dict:
    """{path: L2 norm} of a pytree's leaves, on the host."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get([jnp.linalg.norm(v.astype(jnp.float32).ravel())
                            for _, v in flat])
    return {jax.tree_util.keystr(k): float(n) for (k, _), n in
            zip(flat, norms)}


def build(spec: dict, traffic: dict, seed: int):
    """(step, params, opt, mesh) as ``launch/train.py`` builds them, with
    the optimizer the traffic file states."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_train_step, train_config
    from repro.models.model import build_model
    from repro.optim import adamw

    o = traffic["optimizer"]
    tcfg = train_config(lr=o["lr"], steps=o["total_steps"])
    tcfg = dataclasses.replace(tcfg, optimizer=dataclasses.replace(
        tcfg.optimizer, use_master=o["master_weights"]))
    opt_cfg, sched = tcfg.optimizer, tcfg.schedule
    stated = (o["b1"], o["b2"], o["eps"], o["weight_decay"], o["grad_clip"],
              o["warmup_steps"], o["min_lr_ratio"], "cosine")
    runs = (opt_cfg.b1, opt_cfg.b2, opt_cfg.eps, opt_cfg.weight_decay,
            opt_cfg.grad_clip, sched.warmup_steps, sched.min_ratio,
            sched.kind)
    if stated != runs:
        raise ValueError(f"traffic file states the optimizer {stated}, the "
                         f"program runs {runs}")
    prog = build_model(harness.arch(spec).model_config(spec))
    params = model.make_params(spec, seed)
    model.check_tree(params, jax.eval_shape(prog.init,
                                            jax.random.PRNGKey(0)))
    mesh = make_host_mesh(1, 1)
    opt = adamw.init(opt_cfg, params)
    if opt.master is not None:
        # float32 weights would share their buffers with the master copy,
        # and the step donates both
        opt = opt._replace(master=jax.tree.map(jnp.copy, opt.master))
    return build_train_step(prog, tcfg, mesh), params, opt, mesh


def first_steps(step, params, opt, traffic: dict, seed: int, vocab: int):
    """Drive the step through the checked first steps. Returns (params,
    opt, program readings)."""
    import jax
    import jax.numpy as jnp

    b1 = traffic["optimizer"]["b1"]
    p0 = jax.tree.map(lambda a: jnp.array(a, jnp.float32, copy=True), params)
    losses, grad = [], None
    for k in range(traffic["check_steps"]):
        batch = gen.train_tokens(traffic, seed, k, vocab)
        params, opt, metrics, _ = step(params, opt, {
            "tokens": jnp.asarray(batch[:, :-1]),
            "labels": jnp.asarray(batch[:, 1:])}, None)
        losses.append(float(metrics["loss"]))
        if grad is None:
            grad = {k_: v / (1 - b1) for k_, v in leaf_norms(opt.m).items()}
    now = opt.master if opt.master is not None else params
    change = leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                                     now, p0))
    return params, opt, {"losses": losses, "grad": grad, "change": change}


def compare(prog: dict, ref: dict) -> dict:
    """The three compared numbers from program and reference readings."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"]))
    g_ref = ref["grad"]
    g_med = float(np.median(list(g_ref.values())))
    grad = max(abs(prog["grad"][k] - g_ref[k]) / max(g_ref[k], g_med)
               for k in g_ref)
    moved = [k for k in g_ref if g_ref[k] >= TINY_GRAD * g_med]
    c_ref = ref["change"]
    c_med = float(np.median([c_ref[k] for k in moved]))
    change = max(abs(prog["change"][k] - c_ref[k]) / max(c_ref[k], c_med)
                 for k in moved)
    return {"loss_rel_gap": loss, "grad_norm_gap": grad,
            "update_norm_gap": change}


def reference_readings(spec: dict, traffic: dict, seed: int,
                       mode: str = "f32", rows: int = 0) -> dict:
    """The reference's readings over the checked first steps; ``rows``
    keeps only the first rows of each batch (the fault of a step that
    leaves half of its batch out)."""
    import jax
    import jax.numpy as jnp

    params = model.make_params(spec, seed)
    batches = [gen.train_tokens(traffic, seed, k, spec["vocab_size"])
               [:rows or None] for k in range(traffic["check_steps"])]
    o = traffic["optimizer"]
    arch = harness.arch(spec)
    losses, first, p = arch.follow(arch.dims(spec), params, batches, o, mode)
    p0 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return {"losses": losses, "grad": leaf_norms(first),
            "change": leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))}


def drive(step, params, opt, traffic: dict, seed: int, vocab: int,
          seconds: float, prof):
    """The measured window. Returns (params, opt, steps completed inside
    the window, the seconds from its start to the last completion, steps
    dispatched, steps run while ``prof`` profiled)."""
    import jax

    k = traffic["check_steps"]
    host = gen.train_tokens(traffic, seed, k, vocab)
    pending, last = None, [None]
    done = dispatched = counted = 0
    last_done = 0.0
    on = prof.on
    drain = lambda: last[0] is not None and last[0].block_until_ready()  # noqa: E731,E501
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        prof.tick(time.perf_counter() - t0, drain)
        with harness.annotate(on, "train.step"):
            batch = jax.device_put({"tokens": host[:, :-1],
                                    "labels": host[:, 1:]})
            params, opt, metrics, _ = step(params, opt, batch, None)
        dispatched += 1
        counted += prof.active
        last[0] = metrics["loss"]
        k += 1
        with harness.annotate(on, "data"):
            host = gen.train_tokens(traffic, seed, k, vocab)
        if pending is not None:
            with harness.annotate(on, "wait"):
                pending.block_until_ready()
            now = time.perf_counter()
            if now > t_end:
                break
            done += 1
            last_done = now - t0
        pending = last[0]
        if time.perf_counter() >= t_end:
            break
    prof.close(drain)
    drain()
    return params, opt, done, last_done, dispatched, counted


def run(cell: dict, spec: dict, traffic: dict, check: dict, seed: int,
        seconds: float, trace: bool, t_start: float, bench: dict,
        device: dict) -> None:
    clock = harness.CompileClock()
    vocab = spec["vocab_size"]
    step, params, opt, mesh = build(spec, traffic, seed)
    with mesh:
        params, opt, prog = first_steps(step, params, opt, traffic, seed,
                                        vocab)
        prof = harness.Profile(trace, seconds)
        setup_s = time.perf_counter() - t_start
        compiles0 = clock.compiles
        params, opt, done, span, dispatched, counted = drive(
            step, params, opt, traffic, seed, vocab, seconds, prof)
    harness.log(f"window: {done} steps completed, compilations inside the "
                f"window {clock.compiles - compiles0}, set-up compile "
                f"{clock.seconds:.1f}s, persistent-cache hits "
                f"{clock.cache_hits}")
    tokens_per_step = traffic["batch"] * traffic["seq"]
    device = {**device, "memory_peak_bytes": harness.memory_peak_bytes()}
    result = {"correct": False, "attempted": dispatched, "failed": 0}
    if trace:
        harness.read_trace(prof, bench, cell["name"], spec, device, result,
                           engine=traffic, work=types.SimpleNamespace(
                               steps=counted))
    else:
        result["metrics"] = {
            "train_tokens_per_s": {
                "value": done * tokens_per_step / span if done else 0.0,
                "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device
    del step, params, opt
    gc.collect()
    numbers = compare(prog, reference_readings(spec, traffic, seed))
    result["correct"] = all(numbers[k] <= check[k] for k in numbers)
    harness.emit(result, {k: {"value": v, "limit": check[k]}
                          for k, v in numbers.items()})
