"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
      --steps 300 --seq 512 --batch 8 [--smoke] [--ckpt DIR] [--resume]

Runs on whatever devices exist (`--data/--model` mesh dims), with the full
production stack: SALO attention, sharding rules, grad clip + schedule,
checkpoint manager (atomic/keep-k/async), straggler watchdog, restart-safe
data stream.

``--trace-out trace.json`` records per-step spans (+ checkpoint/straggler
instants) and writes Chrome trace-event JSON at exit; ``--metrics-out``
dumps the metrics registry (step-time histogram, token/step counters,
kernel trace-time launch accounting).
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist import sharding as shlib
from repro.ft.checkpoint import CheckpointManager
from repro.ft.manager import StragglerWatchdog
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.obs import Observability
from repro.obs.metrics import global_registry
from repro.optim import adamw
from repro.optim.schedule import Schedule
from repro.train.trainer import TrainConfig, make_train_step


def train_config(lr: float, steps: int, microbatches: int = 1,
                 compress_grads: bool = False) -> TrainConfig:
    """AdamW at peak ``lr`` with a warmup of max(10, steps/20) steps into
    a schedule that ends at ``steps``."""
    return TrainConfig(
        optimizer=adamw.AdamWConfig(lr=lr),
        schedule=Schedule(warmup_steps=max(10, steps // 20),
                          total_steps=steps),
        microbatches=microbatches, compress_grads=compress_grads)


def build_train_step(model, tcfg: TrainConfig, mesh):
    """The jitted training step ``(params, opt, batch, ef) -> (params, opt,
    metrics, ef)`` under the data-parallel sharding rules on ``mesh``.
    Params, optimizer state and the error-feedback residual are donated
    (under --compress-grads ef is a params-sized f32 tree per participant,
    replaced wholesale every step; None when off — donating an empty
    pytree is a no-op)."""
    rules = dict(shlib.DEFAULT_RULES, batch=("data",), fsdp=None)
    raw_step = make_train_step(model, tcfg)

    def fn(p, o, b, ef):
        with shlib.axis_rules(rules, mesh):
            return raw_step(p, o, b, ef)

    return jax.jit(fn, donate_argnums=(0, 1, 3))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 error-feedback gradient all-reduce over the "
                         "data/pod mesh axes")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-branch", type=int, default=16)
    ap.add_argument("--data-docs", type=int, default=64)
    ap.add_argument("--trace-out", default=None,
                    help="write Chrome trace-event JSON of the step "
                         "timeline here at exit (chrome://tracing/Perfetto)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the full metrics-registry JSON here at exit")
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = make_host_mesh(args.data, args.model)

    tcfg = train_config(args.lr, args.steps, args.microbatches,
                        args.compress_grads)

    params = model.init(jax.random.PRNGKey(args.seed))
    opt = adamw.init(tcfg.optimizer, params)
    n_par = sum(x.size for x in jax.tree.leaves(params))
    print(f"# arch={cfg.name} params={n_par/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"window={cfg.salo.window} sinks={cfg.salo.n_global}")

    mgr = CheckpointManager(args.ckpt, keep=3) if args.ckpt else None
    start = 0
    if mgr and args.resume:
        restored, step0 = mgr.restore_latest({"params": params, "opt": opt})
        if restored is not None:
            params, opt = restored["params"], restored["opt"]
            start = step0
            print(f"# resumed from step {start}")

    step = build_train_step(model, tcfg, mesh)
    ef = None   # error-feedback residual, threaded through every step
    ds = SyntheticLM(cfg, DataConfig(args.seq, args.batch, seed=args.seed,
                                     branch=args.data_branch,
                                     n_docs=args.data_docs))
    wd = StragglerWatchdog()
    obs = Observability(tracing=bool(args.trace_out))
    reg = obs.registry

    with mesh:
        for i in range(start, args.steps):
            t0 = time.perf_counter()
            with obs.tracer.span("train.step", track="train", step=i):
                batch = {k: jnp.asarray(v)
                         for k, v in ds.batch(i).items()}
                params, opt, metrics, ef = step(params, opt, batch, ef)
                loss = float(metrics["loss"])   # host sync inside the span
            dt = time.perf_counter() - t0
            reg.inc("train_steps")
            reg.inc("train_tokens", args.batch * args.seq)
            reg.observe("train_step_s", dt)
            straggler = wd.observe(dt)
            if straggler:
                reg.inc("ft_straggler_events")
                obs.tracer.instant("ft.straggler", track="ft", step=i,
                                   step_time_s=round(dt, 6))
            if i % args.log_every == 0 or i == args.steps - 1:
                toks = args.batch * args.seq / dt
                print(f"step {i:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"{dt*1e3:7.1f} ms {toks/1e3:7.1f} ktok/s"
                      + (" [straggler]" if straggler else ""), flush=True)
            if mgr and (i + 1) % args.ckpt_every == 0:
                mgr.save({"params": params, "opt": opt}, i + 1)
                obs.tracer.instant("ft.snapshot", track="ft", step=i + 1)
    if mgr:
        mgr.save({"params": params, "opt": opt}, args.steps)
        mgr.wait()
    if args.trace_out:
        obs.write_trace(args.trace_out)
        print(f"# trace: {args.trace_out} ({len(obs.tracer)} events)",
              file=sys.stderr)
    if args.metrics_out:
        # Fold in the process-wide kernel trace-time launch accounting so
        # the dump is the complete picture for this run.
        reg.merge(global_registry().snapshot())
        obs.write_metrics(args.metrics_out)
        print(f"# metrics: {args.metrics_out}", file=sys.stderr)
    st = reg.percentiles("train_step_s")
    print(f"# done: final loss {loss:.4f}, straggler events {wd.events}, "
          f"step p50 {st['p50'] * 1e3:.1f} ms")
    return loss


if __name__ == "__main__":
    main()
