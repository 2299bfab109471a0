"""A whole serving run at a smoke size on the CPU, with the look for a chip
skipped, and ``correct`` under the faults a serving cell can have."""
import contextlib
import io
import json
import time

import numpy as np
import pytest

from chipbench import harness, serve
from chipbench.tests.smoke import CHECK, SPEC, TRAFFIC

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def run_cell(seed=1234567890123, seconds=3.0, trace=False):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        serve.run({"name": "smollm-serve-long"}, SPEC, TRAFFIC, CHECK, seed,
                  seconds, trace, time.perf_counter(), harness.benchmark(),
                  DEVICE)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct():
    res = run_cell()
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] > TRAFFIC["backlog"]
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "check"
    assert res["check"]["max_logit_gap"]["value"] <= CHECK["max_logit_gap"]


def test_traced_run_is_correct_and_reads_host_spans():
    res = run_cell(seed=77, trace=True)
    assert res["correct"]
    assert "sample_ms_per_step" in res["metrics"]
    assert "output_tokens_per_s" not in res["metrics"]
    assert res["device"]["window_s"] > 0


def test_altered_token_is_caught(monkeypatch):
    from repro.serve.batcher import Batcher

    record = Batcher.record_token
    monkeypatch.setattr(Batcher, "record_token", lambda self, req, tok:
                        record(self, req, (tok + 1) % SPEC["vocab_size"]))
    assert not run_cell()["correct"]


def test_decode_that_leaves_its_state_unchanged_is_caught(monkeypatch):
    from repro.serve.engine import ContinuousEngine

    decode = ContinuousEngine._decode_fn

    def stale(self, params, slabs, page_tables, slot_pos, *rest):
        logits, _, _ = decode(self, params, slabs, page_tables, slot_pos,
                              *rest)
        return logits, slabs, slot_pos

    monkeypatch.setattr(ContinuousEngine, "_decode_fn", stale)
    assert not run_cell()["correct"]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_fp8_control_is_not_correct(seed):
    from chipbench import control

    r = control.readings(SPEC, TRAFFIC, CHECK, seed, 3.0)
    assert r["program"] <= CHECK["max_logit_gap"] < r["control"]
    assert np.isfinite(r["control"])


def test_window_opens_on_a_full_engine():
    from chipbench import gen

    eng, params, tracked, live, arrivals = serve.open_session(
        SPEC, TRAFFIC, 11, 3.0, False)
    rows = [q for q in eng.batcher.rows if q is not None]
    assert len(rows) == TRAFFIC["engine"]["max_batch"]
    assert all(q.state == "decode" and q.out for q in rows)
    assert len(tracked) == TRAFFIC["backlog"]
    assert all(r.due_s > 0 for r in arrivals)
    assert len(tracked) + len(arrivals) == len(
        gen.serve_requests(TRAFFIC, 11, 3.0, SPEC["vocab_size"]))


def test_output_rate_is_over_the_time_to_the_last_step():
    """Tokens over the seconds to the return of the last step that
    delivered any, not over the window's length."""
    import types

    done = types.SimpleNamespace(state="done")
    tracked = [types.SimpleNamespace(req=done, times=[-0.5, 0.4, 0.8, 1.6]),
               types.SimpleNamespace(req=done, times=[0.8, 1.6, 2.05])]
    rate, failed = serve.end_to_end(tracked, 2.0)
    assert rate == 5 / 1.6 and failed == 0
