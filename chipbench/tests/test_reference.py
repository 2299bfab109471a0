"""The plain reference against the program's own forward pass with dense
masked attention, in float32 at a smoke size."""
import dataclasses

import jax
import numpy as np
import pytest

from chipbench import harness, model
from chipbench.reference import decoder
from chipbench.tests.smoke import SPEC

ARCH = harness.arch(SPEC)


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_program_dense_forward(tied):
    from repro.models.model import build_model

    spec = dict(SPEC, tie_word_embeddings=tied)
    cfg = ARCH.model_config(spec)
    cfg = dataclasses.replace(cfg, salo=dataclasses.replace(
        cfg.salo, impl="dense_ref"))
    params = model.make_params(spec, 11)
    prog = build_model(cfg)
    model.check_tree(params, jax.eval_shape(prog.init, jax.random.PRNGKey(0)))
    tokens = np.random.default_rng(0).integers(0, 256, 70, np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(prog.forward(params, {"tokens": tokens[None]}))[0]
    rows = np.arange(70)
    got = decoder.logits_at(ARCH.dims(spec), params, tokens, rows)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_fp8_control_departs_from_the_reference():
    params = model.make_params(SPEC, 5)
    tokens = np.random.default_rng(1).integers(0, 256, 40, np.int32)
    rows = np.arange(40)
    m = ARCH.dims(SPEC)
    ref = decoder.logits_at(m, params, tokens, rows)
    ctl = decoder.logits_at(m, params, tokens, rows, "fp8")
    err = np.max(np.abs(ctl - ref)) / np.max(np.abs(ref))
    assert 1e-3 < err < 0.5


def test_reference_loss_and_gradient_match_program():
    from repro.models.model import build_model

    from chipbench import gen
    from chipbench.reference import train as ref_train
    from chipbench.tests.smoke import TRAIN

    cfg = ARCH.model_config(SPEC)
    cfg = dataclasses.replace(cfg, salo=dataclasses.replace(
        cfg.salo, impl="dense_ref"), remat="none")
    params = model.make_params(SPEC, 12)
    tokens = gen.train_tokens(TRAIN, 12, 0, SPEC["vocab_size"])
    prog = build_model(cfg)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    with jax.default_matmul_precision("highest"):
        (want_loss, _), want = jax.value_and_grad(prog.loss, has_aux=True)(
            params, batch)
    got_loss, got = ref_train.loss_and_grad(params, tokens,
                                            decoder._M(ARCH.dims(SPEC)))
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5 * float(np.abs(w).max()) + 1e-9,
                                   rtol=1e-3)


def test_reference_adamw_schedule():
    from chipbench.reference import train as ref_train
    from chipbench.tests.smoke import TRAIN

    o = TRAIN["optimizer"]
    assert [ref_train.lr_at(o, s) for s in (0, 1, 2)] == pytest.approx(
        [0.0, 3e-4, 6e-4])
    assert ref_train.lr_at(o, o["total_steps"]) == pytest.approx(3e-4)
