"""Serving driver: lockstep baseline OR the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \\
      --batch 4 --prompt-len 32 --new-tokens 32
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \\
      --engine continuous --batch 4 --prompt-len 32 --new-tokens 16 \\
      --chunk 16 --page 8

``--engine continuous`` submits a RAGGED batch (prompt lengths spread
around ``--prompt-len``) to the paged-slab engine and reports launch
counters alongside throughput.

``--seq-shards N`` shards the continuous engine over an N-way "seq" mesh
axis (sequence-parallel serving: per-shard slab pools, sharded decode slot
map, masked-psum partial combine). Needs >= N devices — on a CPU host set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before launching.

``--snapshot-dir DIR`` runs the continuous engine under the fault-tolerant
:class:`~repro.ft.manager.ServeSupervisor`: full engine snapshots (slabs,
page tables, request lifecycle) every ``--snapshot-every`` steps through
the atomic keep-k writer, bounded restarts on recoverable faults. Token
output is exactly-once across kill/resume. ``--inject-crash-at`` takes a
comma list of step attempts to crash (fault-injection demo):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \\
      --engine continuous --snapshot-dir /tmp/snap --inject-crash-at 3,7

``--trace-out trace.json`` records the engine's step-phase spans and every
request's lifecycle events and writes Chrome trace-event JSON at exit
(open in chrome://tracing or https://ui.perfetto.dev); ``--metrics-out``
dumps the full metrics registry; ``--summary-every N`` prints a one-line
stderr summary (steps, launches, TTFT/TPOT p50) every N engine steps.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.launch.compile_cache import setup_compile_cache
from repro.models.model import build_model
from repro.obs import Observability, summary_line
from repro.serve.engine import (ContinuousConfig, ContinuousEngine,
                                ServeConfig, ServeEngine)


def _ragged_lengths(base: int, batch: int, rng) -> list:
    """Prompt lengths spread around ``base`` (min 2) — continuous batching
    exists precisely because real traffic is ragged."""
    return [max(2, int(l)) for l in
            rng.integers(max(2, base // 2), base + 1, batch)]


def continuous_setup(cfg, *, max_batch: int, page: int, chunk: int,
                     seq_shards: int = 1, kv_dtype: str = "compute",
                     page_sparsity_threshold=None,
                     page_stat_decay: float = 0.0, max_queue=None):
    """``(ContinuousConfig, mesh)`` of the continuous engine: a slab pool
    that holds ``max_batch`` full-footprint requests per shard, and a
    ``"seq"`` mesh over the first ``seq_shards`` devices when sharded
    (``None`` otherwise). The decode engine is the platform's (compiled
    paged kernel on a TPU, the XLA twin elsewhere)."""
    from repro.models.layers import salo_pattern
    from repro.serve.paged_cache import layout_for_pattern

    mesh = None
    if seq_shards > 1:
        if len(jax.devices()) < seq_shards:
            raise ValueError(
                f"seq_shards={seq_shards} needs that many devices (have "
                f"{len(jax.devices())}; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={seq_shards})")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((seq_shards,), ("seq",),
                         devices=jax.devices()[:seq_shards])
    lay = layout_for_pattern(salo_pattern(cfg, causal=True), page,
                             shards=seq_shards)
    ccfg = ContinuousConfig(
        n_pages=1 + max_batch * lay.pages_per_shard, page=page,
        chunk=chunk, max_batch=max_batch, seq_shards=seq_shards,
        kv_dtype=kv_dtype, page_sparsity_threshold=page_sparsity_threshold,
        page_stat_decay=page_stat_decay, max_queue=max_queue)
    return ccfg, mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("lockstep", "continuous"),
                    default="lockstep")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--page", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=0,
                    help="engine rows (0 = --batch)")
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="sequence-parallel serving shards (continuous "
                         "engine; needs a 'seq' mesh of that many devices)")
    ap.add_argument("--kv-dtype", choices=("compute", "int8"),
                    default="compute",
                    help="paged-slab storage dtype (continuous engine): "
                         "'int8' stores K/V quantized per (layer, page) "
                         "with f32 scales, dequantized in-kernel")
    ap.add_argument("--page-sparsity-threshold", type=float, default=None,
                    help="continuous engine: skip reading pages whose "
                         "historical max attention score (log-space, "
                         "relative to the row max) fell below this; sink "
                         "and write pages are always read. Unset = dense "
                         "reads; -inf = track stats but keep everything")
    ap.add_argument("--page-stat-decay", type=float, default=0.0,
                    help="per-step decay of the per-page score history; "
                         "must be > 0 for --page-sparsity-threshold to "
                         "ever skip a page")
    ap.add_argument("--snapshot-dir", default=None,
                    help="continuous engine: run under the ServeSupervisor "
                         "with engine snapshots in this directory "
                         "(fault-tolerant serving)")
    ap.add_argument("--snapshot-every", type=int, default=4,
                    help="engine steps between snapshots")
    ap.add_argument("--max-restarts", type=int, default=4,
                    help="restart budget before RestartsExhausted")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue (submit raises "
                         "QueueFull beyond it); unset = unbounded")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline in seconds; overdue "
                         "requests fail with a reason and free their pages")
    ap.add_argument("--inject-crash-at", default=None,
                    help="comma list of step attempts at which to inject "
                         "a StepCrash (needs --snapshot-dir)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of engine phases "
                         "+ request lifecycle here at exit (continuous "
                         "engine; open in chrome://tracing / Perfetto)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the full metrics-registry JSON here at exit")
    ap.add_argument("--summary-every", type=int, default=0,
                    help="print a one-line metrics summary to stderr every "
                         "N engine steps (0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    if args.engine != "continuous" and (args.trace_out or args.metrics_out
                                        or args.summary_every):
        ap.error("--trace-out/--metrics-out/--summary-every need "
                 "--engine continuous (the instrumented engine)")

    if args.engine == "continuous":
        if args.temperature != 0.0:
            ap.error("--engine continuous is greedy-only "
                     "(temperature sampling needs per-request RNG streams)")
        try:
            ccfg, mesh = continuous_setup(
                cfg, max_batch=args.max_batch or args.batch, page=args.page,
                chunk=args.chunk, seq_shards=args.seq_shards,
                kv_dtype=args.kv_dtype,
                page_sparsity_threshold=args.page_sparsity_threshold,
                page_stat_decay=args.page_stat_decay,
                max_queue=args.max_queue)
        except ValueError as e:
            ap.error(str(e))
        lens = _ragged_lengths(args.prompt_len, args.batch, rng)
        prompts = [rng.integers(0, cfg.vocab_size, (L,)) for L in lens]
        # ONE obs bundle shared by the engine, the batcher, and the
        # supervisor — and across supervisor restarts — so the exported
        # trace holds the whole timeline including kills and restores.
        obs = Observability(tracing=bool(args.trace_out))

        def summarize(reg):
            if args.summary_every and \
                    reg.total("serve_engine_steps") % args.summary_every == 0:
                print(f"# {summary_line(reg)}", file=sys.stderr, flush=True)

        def make_engine():
            eng = ContinuousEngine(model, ccfg, mesh=mesh, obs=obs)
            for p in prompts:
                eng.submit(p, args.new_tokens, deadline_s=args.deadline_s)
            return eng

        t0 = time.perf_counter()
        if args.snapshot_dir:
            from repro.ft import FaultInjector, FaultPlan, ServeSupervisor
            injector = None
            if args.inject_crash_at:
                injector = FaultInjector(FaultPlan(crash_steps=frozenset(
                    int(s) for s in args.inject_crash_at.split(","))))
            sup = ServeSupervisor(
                make_engine, params, args.snapshot_dir,
                checkpoint_every=args.snapshot_every,
                max_restarts=args.max_restarts, injector=injector, obs=obs,
                on_step=lambda eng, hist: summarize(obs.registry))
            eng, history = sup.run()
            results = eng.batcher.results()
            print(f"# supervisor: {history}")
            if eng.batcher.failures():
                print(f"# failed: {eng.batcher.failures()}")
        else:
            if args.inject_crash_at:
                ap.error("--inject-crash-at needs --snapshot-dir")
            eng = make_engine()
            while eng.step(params):
                summarize(obs.registry)
            results = eng.batcher.results()
        if args.trace_out:
            obs.write_trace(args.trace_out)
            print(f"# trace: {args.trace_out} "
                  f"({len(obs.tracer)} events)", file=sys.stderr)
        if args.metrics_out:
            obs.write_metrics(args.metrics_out)
            print(f"# metrics: {args.metrics_out}", file=sys.stderr)
        rids = sorted(results)
        dt = time.perf_counter() - t0
        total_new = args.batch * args.new_tokens
        print(f"# arch={cfg.name} engine=continuous batch={args.batch} "
              f"prompts={lens} new={args.new_tokens} chunk={args.chunk} "
              f"page={args.page} seq_shards={args.seq_shards} "
              f"kv_dtype={args.kv_dtype} "
              f"page_thr={args.page_sparsity_threshold}")
        print(f"# {dt:.2f}s total, {total_new/dt:.1f} tok/s "
              f"(includes compile); counters={eng.counters}")
        for rid in rids[:2]:
            print(f"sample[{rid}]: {results[rid][:16].tolist()}")
        return results

    max_len = args.prompt_len + args.new_tokens
    eng = ServeEngine(model, ServeConfig(max_len=max_len,
                                         temperature=args.temperature,
                                         seed=args.seed))
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (args.batch, args.prompt_len)))
    t0 = time.perf_counter()
    toks = jax.block_until_ready(eng.generate(params, prompts,
                                              args.new_tokens))
    dt = time.perf_counter() - t0
    total_new = args.batch * args.new_tokens
    print(f"# arch={cfg.name} batch={args.batch} "
          f"prompt={args.prompt_len} new={args.new_tokens}")
    print(f"# {dt:.2f}s total, {total_new/dt:.1f} tok/s "
          f"(includes compile)")
    for b in range(min(args.batch, 2)):
        print(f"sample[{b}]: {np.asarray(toks[b])[:16].tolist()}")
    return toks


if __name__ == "__main__":
    main()
