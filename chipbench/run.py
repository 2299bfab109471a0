"""Run one benchmark cell on the chip and print its result line.

  python chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout, one process, no PYTHONPATH. The cell's
configuration, traffic mix, correctness limits and per-layer metric readers
are found by name: ``BENCHMARK.json`` names them, ``chipbench/configs``,
``traffic``, ``checks`` and ``metrics`` hold them. Set-up (loading, weights,
compilation or the persistent cache, warm-up) is timed from the start of
this process. With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from a profiler trace of
the window. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero before measuring anything and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KINDS = {"serve": "chipbench.serve", "train": "chipbench.train"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import importlib

    from chipbench import harness

    bench, cell, spec, traffic, check = harness.cell_files(args.workload)
    device = harness.open_chip(cell)
    driver = importlib.import_module(KINDS[traffic["kind"]])
    driver.run(cell, spec, traffic, check, args.seed, args.seconds,
               bool(args.trace), T_START, bench, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
