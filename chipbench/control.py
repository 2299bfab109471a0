"""Readings that set a cell's correctness limits.

  python chipbench/control.py --workload NAME --seconds S --seeds N1 N2 ...

One process on the chip, one JSON line per seed. A serving cell serves its
traffic, at its own sizes and rate, for ``seconds``, samples served
requests as a run does, and reads on that sample:

  program  the widest gap of the served tokens under the float32 reference
  control  the widest gap of the tokens the fp8 control (the reference with
           every matrix product in float8 e4m3) puts first at the same
           positions

A training cell reads its three numbers (``train.compare``) for the fp8
control and for the half-batch fault, each put in the program's place
against the float32 reference; its program readings come from its runs.

The limits in ``checks/<cell>.json`` lie above the largest program reading
and below the smallest control or fault reading (PERF.md gives both).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(spec: dict, traffic: dict, check: dict, seed: int,
             seconds: float) -> dict:
    from chipbench import harness, serve

    eng, params, tracked, live, arrivals = serve.open_session(
        spec, traffic, seed, seconds, False)
    serve.drive(eng, params, tracked, live, arrivals, seconds,
                harness.Profile(False, seconds))
    sample = serve.sample_served(tracked, seed, check["sample_tokens"])
    del eng, params, tracked, live
    gc.collect()
    if not sample:
        return {"seed": seed, "requests": 0}
    return {"seed": seed, "requests": len(sample),
            "tokens": sum(len(o) for _, o in sample),
            "program": serve.widest_gap(spec, seed, sample),
            "control": serve.widest_gap(spec, seed, sample, control=True)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness

    _, cell, spec, traffic, check = harness.cell_files(args.workload)
    harness.open_chip(cell)
    for seed in args.seeds:
        if traffic["kind"] == "train":
            from chipbench import train

            ref = train.reference_readings(spec, traffic, seed)
            line = {"seed": seed, **{
                name: train.compare(
                    train.reference_readings(spec, traffic, seed, **kw), ref)
                for name, kw in (("control", {"mode": "fp8"}),
                                 ("half_batch",
                                  {"rows": traffic["batch"] // 2}))}}
        else:
            line = readings(spec, traffic, check, seed, args.seconds)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
