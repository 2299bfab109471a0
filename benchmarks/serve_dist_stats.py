"""Sequence-parallel continuous-serving benchmark -> BENCH_serve_dist.json.

Quantifies what sharding the paged slab over the "seq" mesh axis buys —
the 500k+-context serving regime where one chip's HBM caps the paged pool:

  * **per-shard slab bytes** — each device's slab pool under ``seq_shards=N``
    vs the whole pool replicated-per-device (what a single-device engine
    pins in HBM for the same traffic). The ratio approaches ``1/N`` (page-
    striping alignment padding is the only overhead), which is exactly the
    context-length headroom gained per chip;
  * **decode exchange bytes** — the masked-psum combine of per-shard
    ``(out, m, l)`` partials (R·H·(hd+2)·4 bytes per device per layer per
    step — independent of context length) vs all-gathering the other
    shards' KV view slices ((N-1)·R·S_shard·Hkv·hd·K+V bytes — linear in
    context), per decode step per layer;
  * **greedy parity** — the 8-shard engine's tokens vs the single-device
    ``ContinuousEngine``, token-for-token on a ragged batch over an
    8-forced-host-device mesh (subprocess, same pattern as
    ``benchmarks/dist_stats.py``), gated ``== 1.0``.

Used by ``python -m benchmarks.run`` (section ``serve_dist/``) and writable
standalone via ``python -m benchmarks.serve_dist_stats``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

from repro.core import patterns as P
from repro.serve.paged_cache import layout_for_pattern, slab_bytes

N_SHARDS = 8
DTYPE_BYTES = 2     # bf16 KV at scale

# (name, pattern, page, max_batch, n_layers, n_heads, n_kv_heads, head_dim)
WORKLOADS = [
    ("long_512k_w4096",
     P.causal_sliding_window(4096, n_sinks=4), 128, 8, 32, 64, 8, 128),
    ("long_64k_w1024_d4",
     P.causal_sliding_window(1024, n_sinks=4, dilation=4), 64, 16, 32, 64,
     8, 128),
    ("smoke_w16",
     P.causal_sliding_window(16, n_sinks=2), 8, 4, 2, 3, 1, 16),
]


def _accounting() -> dict:
    out = {}
    for name, pat, page, B, L, H, Hkv, hd in WORKLOADS:
        lay1 = layout_for_pattern(pat, page)
        layN = layout_for_pattern(pat, page, shards=N_SHARDS)
        # per-device slab pool: 1 null page + max_batch full page sets
        rep = slab_bytes(L, 1 + B * lay1.pages_per_req, page, Hkv, hd,
                         DTYPE_BYTES)
        shard = slab_bytes(L, 1 + B * layN.pages_per_shard, page, Hkv, hd,
                           DTYPE_BYTES)
        # decode exchange, per step per layer per device
        psum = B * H * (hd + 2) * 4                      # (out, m, l) f32
        allgather = ((N_SHARDS - 1) * B * layN.slots_per_shard * Hkv * hd
                     * 2 * DTYPE_BYTES)                  # K + V view slices
        out[name] = dict(
            n_shards=N_SHARDS,
            slots_per_request=layN.slots_per_req,
            replicated_slab_bytes=rep,
            shard_slab_bytes=shard,
            slab_bytes_ratio=shard / rep,
            decode_psum_bytes=psum,
            decode_allgather_bytes=allgather,
            decode_bytes_ratio=psum / allgather,
        )
    return out


_PARITY_PROG = """
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
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (24, 17, 9, 30)]
    pat = salo_pattern(cfg, causal=True)
    l1 = layout_for_pattern(pat, 8)
    e1 = ContinuousEngine(model, ContinuousConfig(
        n_pages=1 + 4 * l1.pages_per_req, page=8, chunk=8, max_batch=4))
    r1 = [e1.submit(p, 8) for p in prompts]
    ref = e1.run(params)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("seq",))
    l8 = layout_for_pattern(pat, 8, shards=8)
    e8 = ContinuousEngine(model, ContinuousConfig(
        n_pages=1 + 4 * l8.pages_per_shard, page=8, chunk=8, max_batch=4,
        seq_shards=8), mesh=mesh)
    r8 = [e8.submit(p, 8) for p in prompts]
    out = e8.run(params)
    match = all(np.array_equal(ref[a], out[b]) for a, b in zip(r1, r8))
    print("PARITY", 1.0 if match else 0.0)
"""


def _measure_parity() -> dict:
    """Greedy token parity of the 8-shard engine vs single-device, via a
    subprocess with 8 forced host devices (the running process already
    initialized jax with 1), pinned to the CPU (the parent may hold the
    chip)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_PARITY_PROG)],
        env={**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"parity subprocess failed:\n{r.stderr[-2000:]}")
    parity = float(r.stdout.strip().split("PARITY")[-1])
    return {"greedy_token_match": parity, "n_shards": N_SHARDS}


def collect(measure: bool = True) -> dict:
    data = {"workloads": _accounting()}
    if measure:
        data["parity"] = _measure_parity()
    return data


def _write_json(data, out_path, measure):
    if not measure:
        return
    with open(out_path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)


def serve_dist_benchmark(rows, measure: bool = True,
                         out_path: str = "BENCH_serve_dist.json") -> dict:
    """benchmarks.run section: report + write BENCH_serve_dist.json."""
    data = collect(measure=measure)
    for name, st in data["workloads"].items():
        rows.append((f"serve_dist/{name}/slab_bytes_ratio",
                     st["slab_bytes_ratio"],
                     f"shard={st['shard_slab_bytes']}_replicated="
                     f"{st['replicated_slab_bytes']}"))
        rows.append((f"serve_dist/{name}/decode_bytes_ratio",
                     st["decode_bytes_ratio"],
                     f"psum={st['decode_psum_bytes']}_allgather="
                     f"{st['decode_allgather_bytes']}"))
    if "parity" in data:
        rows.append(("serve_dist/parity",
                     data["parity"]["greedy_token_match"],
                     "8shard_vs_single_device_greedy_tokens"))
    _write_json(data, out_path, measure)
    return data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve_dist.json")
    ap.add_argument("--no-measure", action="store_true",
                    help="static byte accounting only (skips the 8-device "
                         "parity subprocess; does NOT rewrite the "
                         "committed JSON)")
    args = ap.parse_args()
    rows = []
    serve_dist_benchmark(rows, measure=not args.no_measure,
                         out_path=args.out)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.6g},{derived}")
    if not args.no_measure:
        print(f"# wrote {args.out}")
    # standalone gates (benchmarks.run applies the same ones)
    d = {name: value for name, value, _ in rows}
    bad = [(k, v) for k, v in d.items()
           if k.endswith("bytes_ratio") and v >= 1.0]
    if "serve_dist/parity" in d and d["serve_dist/parity"] != 1.0:
        bad.append(("serve_dist/parity", d["serve_dist/parity"]))
    if bad:
        for k, v in bad:
            print(f"CHECK-FAILED: {k} = {v}", file=sys.stderr)
        raise SystemExit(1)
    print("# serve_dist gates hold")


if __name__ == "__main__":
    main()
