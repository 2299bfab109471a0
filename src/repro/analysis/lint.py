"""The static soundness gate: ``python -m repro.analysis.lint``.

Runs all three analysis passes over every registered config/pattern and
exits nonzero on any error finding:

1. the plan soundness prover (:mod:`repro.analysis.plan_verify`) over the
   registry's plan targets — coverage, adjoint, per-shard exchange,
   never-drop for the dynamic targets, dynamic full-keep replay — and
   over every prefill chunk slice of the chunk targets;
2. the jaxpr effect linter (:mod:`repro.analysis.jaxpr_lint`) over the
   traced entry points — forward/backward launch contract, the dK/dV
   scatter twin, the masked psum merge, the engine's ragged-decode step —
   plus the decode write-ownership probe and per-launch VMEM estimates;
3. the stdlib AST code lint (:mod:`repro.analysis.code_lint`) over
   ``src``, ``tests`` and ``benchmarks`` (CI additionally runs ruff).

``--out report.json`` writes the machine-readable report
(``{"targets": [...], "findings": [...], "summary": {...}}``) that
``benchmarks/verify_stats.py`` gates on.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from typing import List

from repro.analysis import Finding, render


def run_plan_pass(findings: List[Finding], targets: List[str]) -> None:
    from repro.analysis import plan_verify as pv
    from repro.analysis.registry import chunk_targets, plan_targets
    from repro.core.scheduler import build_chunk_plan, build_plan, schedule

    for t in plan_targets():
        sched = schedule(t.pattern, t.n)
        plan = sched.plan(t.block_q, t.block_k)
        findings += pv.verify_plan(plan, t.name, never_drop=t.dynamic,
                                   local_window=t.local_window)
        if t.dynamic:
            findings += pv.verify_dynamic_full_keep(plan, t.name)
        for S in t.n_shards:
            padded = build_plan(sched, t.block_q, t.block_k,
                                S * math.lcm(t.block_q, t.block_k))
            findings += pv.verify_plan(padded, t.name, n_shards=(S,))
        targets.append(t.name)

    from repro.serve.paged_cache import layout_for_pattern
    for ct in chunk_targets():
        lay = layout_for_pattern(ct.pattern, ct.page)
        c0 = 0
        while c0 < ct.prompt:
            clen = min(ct.chunk, ct.prompt - c0)
            cp = build_chunk_plan(ct.pattern, c0, clen, n_sink=lay.n_sink,
                                  ring_cap=lay.ring_cap, block=ct.page)
            findings += pv.verify_chunk(
                cp, f"{ct.name}[{c0}:{c0 + clen}]", n_shards=ct.n_shards)
            c0 += clen
        targets.append(ct.name)


def run_jaxpr_pass(findings: List[Finding], targets: List[str],
                   engine: bool = True) -> None:
    import repro.core.patterns as P
    from repro.analysis import jaxpr_lint as jl
    from repro.core.scheduler import schedule
    from repro.serve.paged_cache import layout_for_pattern

    pat = P.longformer(32, n_global=4)
    findings += jl.check_launch_contract(pat, 128, 32, 32, "kernels.ops")
    findings += jl.lint_traced(jl.trace_dkv_scatter(pat, 128, 32, 32),
                               "table_dkv_scatter_scan")
    findings += jl.lint_traced(jl.trace_masked_psum_merge(),
                               "masked_psum_merge")
    findings += jl.check_vmem(schedule(pat, 1024).plan(128, 128), d=64,
                              target="kernels.salo_attention",
                              decode=jl.SERVING_DECODE)
    targets += ["kernels.ops", "table_dkv_scatter_scan",
                "masked_psum_merge"]

    for shards in (1, 2):
        lay = layout_for_pattern(P.causal_sliding_window(16, n_sinks=2), 8,
                                 shards=shards)
        findings += jl.check_write_ownership(
            lay, f"paged_layout@{shards}shards")
        targets.append(f"paged_layout@{shards}shards")

    if engine:
        import jax

        from repro.configs import get_smoke
        from repro.models.layers import salo_pattern
        from repro.models.model import build_model
        from repro.serve.engine import ContinuousConfig, ContinuousEngine

        cfg = get_smoke("smollm-135m")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        lay = layout_for_pattern(salo_pattern(cfg, causal=True), 8)
        eng = ContinuousEngine(model, ContinuousConfig(
            n_pages=1 + 4 * lay.pages_per_req, page=8, chunk=8,
            max_batch=4))
        findings += jl.lint_traced(jl.trace_engine_decode(eng, params),
                                   "engine.decode")
        targets.append("engine.decode")


def run_code_pass(findings: List[Finding], targets: List[str],
                  paths: List[str]) -> None:
    from repro.analysis.code_lint import lint_paths
    findings += lint_paths(paths)
    targets += paths


def collect(engine: bool = True,
            paths: List[str] = ("src", "tests", "benchmarks")) -> dict:
    """Run every pass; the report dict the CLI and benchmark share."""
    findings: List[Finding] = []
    targets: List[str] = []
    run_plan_pass(findings, targets)
    run_jaxpr_pass(findings, targets, engine=engine)
    run_code_pass(findings, targets, list(paths))
    errors = [f for f in findings if f.severity == "error"]
    by_pass: dict = {}
    for f in findings:
        by_pass[f.pass_name] = by_pass.get(f.pass_name, 0) + 1
    return {
        "targets": targets,
        "findings": [f.as_dict() for f in findings],
        "summary": {
            "targets_checked": len(targets),
            "findings": len(findings),
            "errors": len(errors),
            "by_pass": by_pass,
            "plans_sound": 1.0 if not errors else 0.0,
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="static soundness gate: plan prover + jaxpr effect "
                    "lint + code lint")
    ap.add_argument("--out", default=None,
                    help="write the JSON report here")
    ap.add_argument("--skip-engine", action="store_true",
                    help="skip the (slow) serving-engine decode trace")
    ap.add_argument("--paths", nargs="*",
                    default=["src", "tests", "benchmarks"],
                    help="roots for the code lint pass")
    args = ap.parse_args(argv)

    report = collect(engine=not args.skip_engine, paths=args.paths)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
    findings = [Finding(**d) for d in report["findings"]]
    print(render(findings))
    s = report["summary"]
    print(f"checked {s['targets_checked']} targets: "
          f"{s['errors']} errors, {s['findings']} findings")
    return 1 if s["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
