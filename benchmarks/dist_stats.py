"""Sequence-parallel ShardedPlan benchmark -> BENCH_dist.json.

Quantifies the paper's hierarchical-splitting claim at datacenter scale:
a sequence shard only exchanges its **halo** (the band reach, ``(w + Bk)·d``
bytes — independent of sequence length) plus the tiny global-tile psum,
versus all-gather ring attention cycling every other shard's full KV
through each device (``(n_shards - 1)·n_local·d`` bytes):

  * static per-layer collective-byte accounting from the ShardedPlan
    metadata (``ShardedPlan.stats``) for the paper's workloads — gated in
    ``benchmarks/run.py`` as ``bytes_ratio < 1`` per workload;
  * measured parity: sharded fwd+bwd vs the single-device fused path on an
    8-device forced-host mesh (subprocess, same pattern as
    tests/test_distributed.py), reported as ``dist/parity`` and gated
    ``== 1.0``.

Used by ``python -m benchmarks.run`` (section ``dist/``) and writable as a
standalone JSON via ``python -m benchmarks.dist_stats``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

from repro.core import patterns as P
from repro.core.scheduler import build_plan, schedule
from repro.dist.sharded_plan import shard_plan

N_SHARDS = 8
HEAD_DIM = 64
DTYPE_BYTES = 2     # bf16 activations at scale

# (name, pattern, n, block) — longformer-4k and a long_64k window stand in
# for the paper's 1-D workloads; vil_64x64 for the 2-D multi-band case.
WORKLOADS = [
    ("longformer_4k", P.longformer(512, n_global=1), 4096, 128),
    ("long_64k_w4096", P.causal_sliding_window(4096, n_sinks=4), 65536, 128),
    ("dilated_64k_w1024_d4",
     P.causal_sliding_window(1024, n_sinks=4, dilation=4), 65536, 128),
    ("vil_64x64", P.vil((64, 64), (15, 15), 1), None, 128),
]


def _accounting() -> dict:
    out = {}
    for name, pat, n, blk in WORKLOADS:
        n = n if n is not None else pat.seq_len()
        sched = schedule(pat, n)
        plan = build_plan(sched, blk, blk, N_SHARDS * blk)
        sp = shard_plan(plan, N_SHARDS)
        out[name] = sp.stats(HEAD_DIM, DTYPE_BYTES)
    return out


_PARITY_PROG = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import patterns as P_
    from repro.core.blockwise import blockwise_attention
    from repro.dist.sharded_plan import sharded_attention
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    worst = 0.0
    for pat, N in ((P_.longformer(8, n_global=2), 128),
                   (P_.causal_sliding_window(5, n_sinks=2, dilation=2), 128),
                   (P_.vil((16, 16), (5, 5), 1), 257)):
        q, k, v, cot = (jnp.asarray(rng.normal(size=(2, N, 16)), jnp.float32)
                        for _ in range(4))
        ref = blockwise_attention(q, k, v, pat, block_q=16, block_k=16)
        g_ref = jax.grad(lambda a, b, c: jnp.sum(blockwise_attention(
            a, b, c, pat, block_q=16, block_k=16) * cot),
            argnums=(0, 1, 2))(q, k, v)
        with mesh:
            out = jax.jit(lambda a, b, c: sharded_attention(
                a, b, c, pat, mesh))(q, k, v)
            g = jax.jit(jax.grad(lambda a, b, c: jnp.sum(sharded_attention(
                a, b, c, pat, mesh) * cot), argnums=(0, 1, 2)))(q, k, v)
        worst = max(worst, float(jnp.max(jnp.abs(out - ref))))
        for a, b in zip(g_ref, g):
            worst = max(worst, float(jnp.max(jnp.abs(a - b))))
    print("WORST_ERR", worst)
"""


def _measure_parity() -> dict:
    """Max |sharded - single-device| over fwd + all grads, via a subprocess
    with 8 forced host devices (the running process already initialized
    jax with 1). The child is pinned to the CPU: it is a CPU parity check,
    and on a TPU host the parent already holds the chip."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_PARITY_PROG)],
        env={**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"parity subprocess failed:\n{r.stderr[-2000:]}")
    worst = float(r.stdout.strip().split("WORST_ERR")[-1])
    return {"worst_abs_err": worst,
            "parity": 1.0 if worst <= 1e-4 else 0.0,
            "n_shards": N_SHARDS, "tol": 1e-4}


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


def dist_benchmark(rows, measure: bool = True,
                   out_path: str = "BENCH_dist.json") -> dict:
    """benchmarks.run section: report + write BENCH_dist.json."""
    data = collect(measure=measure)
    for name, st in data["workloads"].items():
        rows.append((f"dist/{name}/exchange_bytes", st["exchange_bytes"],
                     f"halo={st['halo_bytes']}_bcast={st['bcast_bytes']}"))
        rows.append((f"dist/{name}/allgather_bytes", st["allgather_bytes"],
                     f"ring_{st['n_shards']}x{st['n_local']}"))
        rows.append((f"dist/{name}/bytes_ratio", st["bytes_ratio"],
                     f"halo_tiles={st['halo_tiles']}"
                     f"_gtiles={st['global_tiles']}"))
    if "parity" in data:
        p = data["parity"]
        rows.append(("dist/parity", p["parity"],
                     f"worst_err={p['worst_abs_err']:.2e}_8dev_fwd+bwd"))
    _write_json(data, out_path, measure)
    return data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_dist.json")
    ap.add_argument("--no-measure", action="store_true",
                    help="static halo accounting only (skips the 8-device "
                         "parity subprocess; does NOT rewrite the "
                         "committed JSON)")
    args = ap.parse_args()
    rows = []
    dist_benchmark(rows, measure=not args.no_measure, out_path=args.out)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.6g},{derived}")
    if not args.no_measure:
        print(f"# wrote {args.out}")
    # standalone gates (benchmarks.run applies the same ones): the halo
    # exchange must beat the all-gather ring on every workload, and the
    # sharded engines must match the single-device fused path exactly.
    d = {name: value for name, value, _ in rows}
    bad = [(k, v) for k, v in d.items()
           if k.endswith("bytes_ratio") and v >= 1.0]
    if "dist/parity" in d and d["dist/parity"] != 1.0:
        bad.append(("dist/parity", d["dist/parity"]))
    if bad:
        for k, v in bad:
            print(f"CHECK-FAILED: {k} = {v}", file=sys.stderr)
        raise SystemExit(1)
    print("# dist gates hold")


if __name__ == "__main__":
    main()
