"""The trace reduction on small traces recorded on a v5e (one traced
second of ``smollm-serve-short`` and of ``smollm-train-8k``)."""
import gzip
from pathlib import Path

import pytest

from chipbench import reduce

DATA = Path(__file__).resolve().parent / "data"


def trace(name, tmp_path):
    raw = tmp_path / f"{name}.xplane.pb"
    raw.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz")
                                    .read_bytes()))
    return reduce.load(raw)


def test_names():
    assert reduce.op_name("%fusion.12 = bf16[2]{0} fusion(x)") == "fusion.12"
    assert reduce.base("salo_paged_decode.7") == "salo_paged_decode"
    assert reduce.base("jit_fn") == "jit_fn"
    ev = [("while", 0, 10), ("a", 1, 4), ("b", 5, 9), ("c", 11, 12)]
    assert [leaf for *_, leaf in reduce._leaves(ev)] == [False, True, True,
                                                         True]


def test_serving_trace(tmp_path):
    tr = trace("tiny_serve", tmp_path)
    assert tr.n_devices == 1
    assert 0 < tr.busy_s() <= tr.window_s
    calls = tr.op_calls("salo_paged_decode")
    launches, seconds = tr.module_runs("_decode_fn")
    assert calls > 0 and calls % 30 == 0 and launches == calls // 30
    assert 0 < tr.op_seconds("salo_paged_decode") < seconds <= tr.window_s
    top = tr.top_ops(10)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert reduce.base(top[0][0]) == "salo_paged_decode"
    gaps = tr.gaps(0, 10)
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert sum(s for _, s in tr.gaps(0, 10**9)) == pytest.approx(
        tr.window_s - tr.busy_s(), rel=1e-9)


def test_training_trace(tmp_path):
    tr = trace("tiny_train", tmp_path)
    dq = tr.op_calls("salo_plan_backward_dq")
    assert dq > 0 and dq % 30 == 0
    assert tr.op_calls("salo_plan_backward_dkv") == dq
    # full remat runs the forward kernel again in the backward pass
    assert tr.op_calls("salo_plan_attention") == 2 * dq
    assert tr.busy_s() / tr.window_s > 0.9
