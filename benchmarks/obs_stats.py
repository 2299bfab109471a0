"""Observability benchmark -> BENCH_obs.json.

The observability layer ships with two hard promises, and this benchmark
is where they are enforced rather than asserted in prose:

* **zero cost on the jitted hot path when disabled** (static, always
  collected):

  - *jaxpr identity* — the ragged-decode step function of an engine built
    with full tracing enabled lowers to the character-for-character same
    jaxpr as the default engine's: instrumentation lives host-side around
    the jitted calls and adds ZERO traced operands;
  - *launch identity* — two engines (obs on/off) serving the identical
    ragged workload issue exactly the same number of prefill/decode
    launches and emit token-identical results;

* **negligible cost when enabled** (``measure``): warm traced vs untraced
  wall-clock per engine step (min over alternating repetitions), gated
  ``<= 1.05`` — a full trace of every span/instant may cost at most 5 %.

On top of the contract checks, the traced run itself is summarized
(section ``latency``): TTFT / per-output-token latency / queue-wait
percentiles from the registry's log-bucketed histograms, the exported
Chrome trace is schema-validated (``validate_chrome_trace``) and its event
census reported — one ``engine.step`` span per engine step, request
lifecycle instants for every submitted request.

Used by ``python -m benchmarks.run`` (section ``obs/``) and standalone via
``python -m benchmarks.obs_stats``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

PROMPT_LENS = (24, 17, 9, 30)
PRIORITIES = (0, 1, 0, 1)
N_NEW = 8
CHUNK = 8
PAGE = 8
OVERHEAD_REPS = 5
OVERHEAD_GATE = 1.05


def _engine(cfg, model, obs=None):
    from repro.models.layers import salo_pattern
    from repro.serve.engine import ContinuousConfig, ContinuousEngine
    from repro.serve.paged_cache import layout_for_pattern

    lay = layout_for_pattern(salo_pattern(cfg, causal=True), PAGE)
    return ContinuousEngine(model, ContinuousConfig(
        n_pages=1 + len(PROMPT_LENS) * lay.pages_per_req, page=PAGE,
        chunk=CHUNK, max_batch=len(PROMPT_LENS)), obs=obs)


def _run(eng, params, prompts):
    rids = [eng.submit(p, N_NEW, priority=pr)
            for p, pr in zip(prompts, PRIORITIES)]
    res = eng.run(params)
    return [res[r] for r in rids]


def _decode_jaxpr(eng, params) -> str:
    """The ragged-decode step's jaxpr, from the engine's live state — the
    string the zero-traced-operand check compares."""
    R = eng.ccfg.max_batch
    z = jnp.zeros(R, jnp.int32)
    return str(jax.make_jaxpr(eng._decode_fn)(
        params, eng.slabs, eng.page_tables.copy(), eng.slot_pos,
        z, z, jnp.zeros(R, bool)))


def _hist_summary(reg, name) -> dict:
    h = reg.merged_hist(name)
    if not h.count:
        return {"count": 0}
    return {"count": h.count, "mean": h.sum / h.count,
            "p50": h.percentile(0.5), "p99": h.percentile(0.99),
            "min": h.min, "max": h.max}


def collect(measure: bool = True) -> dict:
    from repro.configs import get_smoke
    from repro.models.model import build_model
    from repro.obs import Observability, validate_chrome_trace

    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in PROMPT_LENS]

    # --- plain engine (obs default: metrics only, tracer disabled) ------- #
    plain = _engine(cfg, model)
    plain_toks = _run(plain, params, prompts)

    # --- fully traced engine, identical workload ------------------------- #
    obs = Observability(tracing=True)
    traced = _engine(cfg, model, obs=obs)
    traced_toks = _run(traced, params, prompts)

    # --- zero-cost contract ---------------------------------------------- #
    jaxpr_equal = (_decode_jaxpr(plain, params)
                   == _decode_jaxpr(traced, params))
    launch_equal = all(plain.counters[k] == traced.counters[k]
                       for k in ("prefill_launches", "decode_launches",
                                 "prefill_tokens", "decode_tokens",
                                 "engine_steps"))
    token_equal = all(np.array_equal(a, b)
                      for a, b in zip(plain_toks, traced_toks))

    # --- the traced run's own story -------------------------------------- #
    reg = obs.registry
    doc = obs.tracer.to_chrome_trace()
    validate_chrome_trace(doc)
    census: dict = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] != "M":
            census[ev["name"]] = census.get(ev["name"], 0) + 1
    steps = traced.counters["engine_steps"]
    latency = {
        "ttft_s": _hist_summary(reg, "serve_ttft_s"),
        "tpot_s": _hist_summary(reg, "serve_tpot_s"),
        "queue_wait_s": _hist_summary(reg, "serve_queue_wait_s"),
        "prefill_tiles": reg.total("serve_prefill_tiles"),
    }

    data = {
        "workload": {"arch": cfg.name, "prompt_lens": list(PROMPT_LENS),
                     "priorities": list(PRIORITIES), "n_new": N_NEW,
                     "chunk": CHUNK, "page": PAGE},
        "zero_cost": {
            "decode_jaxpr_identical": float(jaxpr_equal),
            "launch_counts_identical": float(launch_equal),
            "token_parity": float(token_equal),
        },
        "latency": latency,
        "trace": {
            "events": sum(census.values()),
            "census": dict(sorted(census.items())),
            "step_spans": census.get("engine.step", 0),
            "engine_steps": steps,
            "lifecycle_complete": float(
                census.get("request.submitted", 0) == len(PROMPT_LENS)
                and census.get("request.finished", 0) == len(PROMPT_LENS)
                and census.get("request.first_token", 0)
                == len(PROMPT_LENS)),
        },
    }

    if measure:
        # Warm traced vs untraced step time: both engines already compiled
        # above; alternate full re-runs of the identical workload and take
        # the min (noise floor) of each side.
        t_plain, t_traced = [], []
        for _ in range(OVERHEAD_REPS):
            t0 = time.perf_counter()
            _run(plain, params, prompts)
            t_plain.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            _run(traced, params, prompts)
            t_traced.append(time.perf_counter() - t0)
        data["overhead"] = {
            "reps": OVERHEAD_REPS,
            "untraced_wall_s": min(t_plain),
            "traced_wall_s": min(t_traced),
            "traced_over_untraced": min(t_traced) / min(t_plain),
            "gate": OVERHEAD_GATE,
        }
    return data


def obs_benchmark(rows, measure: bool = True,
                  out_path: str = "BENCH_obs.json") -> dict:
    """benchmarks.run section: report + write BENCH_obs.json."""
    data = collect(measure=measure)
    zc, tr, lat = data["zero_cost"], data["trace"], data["latency"]
    rows.append(("obs/decode_jaxpr_identical", zc["decode_jaxpr_identical"],
                 "obs_on_vs_off_zero_traced_operands"))
    rows.append(("obs/launch_counts_identical",
                 zc["launch_counts_identical"],
                 "same_launches_either_way"))
    rows.append(("obs/token_parity", zc["token_parity"],
                 "traced_engine==plain_engine_tokens"))
    rows.append(("obs/trace_step_spans", float(tr["step_spans"]),
                 f"engine_steps={tr['engine_steps']}"))
    rows.append(("obs/trace_lifecycle_complete", tr["lifecycle_complete"],
                 f"submitted=finished=first_token={len(PROMPT_LENS)}"))
    if lat["ttft_s"]["count"]:
        rows.append(("obs/ttft_p50_s", lat["ttft_s"]["p50"],
                     f"n={lat['ttft_s']['count']}"))
    if lat["tpot_s"]["count"]:
        rows.append(("obs/tpot_p50_s", lat["tpot_s"]["p50"],
                     f"n={lat['tpot_s']['count']}"))
    if "overhead" in data:
        ov = data["overhead"]
        rows.append(("obs/traced_overhead", ov["traced_over_untraced"],
                     f"traced={ov['traced_wall_s']:.4f}s_untraced="
                     f"{ov['untraced_wall_s']:.4f}s_min_of_"
                     f"{ov['reps']}"))
        with open(out_path, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
    return data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_obs.json")
    ap.add_argument("--no-measure", action="store_true",
                    help="static contract checks only (no wall-time; does "
                         "NOT rewrite the committed JSON)")
    args = ap.parse_args()
    rows = []
    obs_benchmark(rows, measure=not args.no_measure, out_path=args.out)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.6g},{derived}")
    if not args.no_measure:
        print(f"# wrote {args.out}")
    d = {name: value for name, value, _ in rows}
    bad = []
    for k in ("obs/decode_jaxpr_identical", "obs/launch_counts_identical",
              "obs/token_parity", "obs/trace_lifecycle_complete"):
        if d[k] != 1.0:
            bad.append((k, d[k], "== 1.0"))
    if d["obs/trace_step_spans"] <= 0:
        bad.append(("obs/trace_step_spans", d["obs/trace_step_spans"],
                    "> 0"))
    if "obs/traced_overhead" in d and d["obs/traced_overhead"] > OVERHEAD_GATE:
        bad.append(("obs/traced_overhead", d["obs/traced_overhead"],
                    f"<= {OVERHEAD_GATE}"))
    if bad:
        for b in bad:
            print(f"CHECK-FAILED: {b}", file=sys.stderr)
        raise SystemExit(1)
    print("# observability contract gates hold")


if __name__ == "__main__":
    main()
