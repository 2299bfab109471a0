"""A whole training run at a smoke size on the CPU, with the look for a
chip skipped, and ``correct`` under the faults a training cell can have."""
import contextlib
import io
import json
import time

from chipbench import harness, train
from chipbench.tests.smoke import SPEC, TRAIN, TRAIN_CHECK

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def run_cell(seed=98765432109, seconds=1.0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        train.run({"name": "smollm-train-8k"}, SPEC, TRAIN, TRAIN_CHECK,
                  seed, seconds, False, time.perf_counter(),
                  harness.benchmark(), DEVICE)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct():
    res = run_cell()
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "check"


def test_step_that_leaves_its_state_unchanged_is_caught(monkeypatch):
    from repro.optim import adamw

    monkeypatch.setattr(adamw, "update", lambda cfg, state, params, grads,
                        lr_scale=1.0: (params, state, {"grad_norm": 0.0,
                                                       "lr": 0.0}))
    res = run_cell()
    assert not res["correct"]
    assert res["check"]["update_norm_gap"]["value"] > 0.9


def test_half_the_batch_left_out_is_caught(monkeypatch):
    from repro.models.model import Model

    loss = Model.loss

    def half(self, params, batch):
        b = batch["tokens"].shape[0] // 2
        return loss(self, params, {k: v[:b] for k, v in batch.items()})

    monkeypatch.setattr(Model, "loss", half)
    assert not run_cell()["correct"]


def test_fp8_control_is_not_correct():
    ref = train.reference_readings(SPEC, TRAIN, 5)
    ctl = train.reference_readings(SPEC, TRAIN, 5, mode="fp8")
    numbers = train.compare(ctl, ref)
    assert any(numbers[k] > TRAIN_CHECK[k] for k in numbers), numbers
