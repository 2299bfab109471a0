"""The readers of the engine's phase spans and decode-grid counts, on
hand-made spans and a hand-made reduced trace."""
import importlib.util
import types
from pathlib import Path

import pytest

from chipbench import reduce

METRICS = Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000                         # nanoseconds


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, dur=0.0, **args):
    return {"ph": "X", "name": name, "track": "engine", "ts": 0.0,
            "dur": dur, "depth": 2, "args": args or None}


def trace(host, busy=((0, 20), (30, 60), (70, 100)), n_devices=1):
    ops = [(f"fusion.{i}", s * MS, e * MS, 0, True)
           for i, (s, e) in enumerate(busy)]
    host = [("window", 0, 100 * MS, 0)] + [
        (n, s * MS, e * MS, d) for n, s, e, d in host]
    return reduce.Trace((0, 100 * MS), n_devices, ops, [], host)


def ctx(spans=(), host=(), **kw):
    return types.SimpleNamespace(spans=list(spans), trace=trace(host, **kw))


def test_chunk_plan_ms_per_chunk():
    read = reader("chunk_plan_ms_per_chunk")
    spans = [span("chunk_plan", 0.1), span("chunk_launch", 5.0),
             span("chunk_plan", 0.3)]
    assert read(ctx(spans)) == pytest.approx(200.0)
    assert read(ctx()) is None
    assert read(ctx([span("chunk_launch", 1.0)])) is None


def test_decode_pages_live_share():
    read = reader("decode_pages_live_share")
    spans = [span("decode_launch", rows_live=2, pages_walked=24,
                  pages_live=10),
             span("logits_to_host", 0.01),
             span("decode_launch", rows_live=1, pages_walked=24,
                  pages_live=6)]
    assert read(ctx(spans)) == pytest.approx(100.0 * 16 / 48)
    assert read(ctx()) is None
    assert read(ctx([span("sample", 0.01)])) is None


def test_logits_idle_ms_per_step():
    """Device busy 0-20, 30-60, 70-100 ms. The first step's copy (15-35)
    holds the idle 20-30 and its sample (35-40) none; the second step's
    copy (62-68) is idle throughout, its sample (68-75) idle for 68-70.
    The outer ``engine.step`` (10-80) holds the idle 60-62 too, which
    counts for nothing: 18 ms over two steps."""
    read = reader("logits_idle_ms_per_step")
    host = [("engine.step", 10, 80, 1),
            ("logits_to_host", 15, 35, 3), ("sample", 35, 40, 2),
            ("logits_to_host", 62, 68, 3), ("sample", 68, 75, 2)]
    assert read(ctx(host=host)) == pytest.approx(9.0)
    # no copies in the trace, or no device: nothing to read
    assert read(ctx(host=[("sample", 35, 40, 2)])) is None
    assert read(ctx(host=host, n_devices=0)) is None
    # a device that never ran: the copies and samples are idle throughout
    assert read(ctx(host=host, busy=())) == pytest.approx(
        (20 + 5 + 6 + 7) / 2)
