"""The dense decoder's weights, reference gaps and work counts, pinned to
what commit db9ad4645781 gave before the architecture became a module of
its own (``arch/dense_decoder.py``): a move of code must change no number
that a cell prints or compares."""
import importlib.util
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import harness, model
from chipbench.tests.smoke import SPEC

HERE = Path(__file__).resolve().parents[1]

# leaf -> (dtype, shape, float64 sum, first three entries), untied bf16
# smoke weights from the seed 2**33 + 7
LEAVES = {
    "['embed']['w']": ("bfloat16", (1024, 128), 6.231610618531704,
                       [0.0244140625, -0.00714111328125, 0.048828125]),
    "['lm_head']['w']": ("bfloat16", (1024, 128), -9.563232474494725,
                         [0.0157470703125, -0.03564453125,
                          -0.01092529296875]),
    "['ln_f']['scale']": ("float32", (128,), 2.0574436120223254,
                          [0.016836700960993767, -0.06104276701807976,
                           0.15067344903945923]),
    "['seg0_attn_mlp']['attn']['wk']": (
        "bfloat16", (4, 128, 64), 18.940651513636112,
        [0.0125732421875, 0.00579833984375, -0.0137939453125]),
    "['seg0_attn_mlp']['attn']['wo']": (
        "bfloat16", (4, 128, 128), 45.52795138955116,
        [-0.216796875, -0.08251953125, -0.10546875]),
    "['seg0_attn_mlp']['attn']['wq']": (
        "bfloat16", (4, 128, 128), 23.140356473624706,
        [-0.029052734375, -0.13671875, -0.154296875]),
    "['seg0_attn_mlp']['attn']['wv']": (
        "bfloat16", (4, 128, 64), -5.704368829727173,
        [-0.0191650390625, -0.00157928466796875, -0.00396728515625]),
    "['seg0_attn_mlp']['ln1']['scale']": (
        "float32", (4, 128), 1.368328234353612,
        [-0.0704246237874031, 0.05398295447230339, 0.04741479083895683]),
    "['seg0_attn_mlp']['ln2']['scale']": (
        "float32", (4, 128), 3.6106508418451995,
        [-0.030071964487433434, -0.034657713025808334, 0.04729054123163223]),
    "['seg0_attn_mlp']['mlp']['w_gate']": (
        "bfloat16", (4, 128, 256), -13.071171708405018,
        [0.1259765625, -0.125, -0.07470703125]),
    "['seg0_attn_mlp']['mlp']['w_in']": (
        "bfloat16", (4, 128, 256), 2.780435960739851,
        [-0.08203125, 0.11328125, -0.007049560546875]),
    "['seg0_attn_mlp']['mlp']['w_out']": (
        "bfloat16", (4, 256, 128), -17.72631051391363,
        [0.09765625, -0.09375, -0.09619140625]),
}


def test_weights_are_the_parents():
    spec = dict(SPEC, tie_word_embeddings=False, torch_dtype="bfloat16")
    flat = jax.tree_util.tree_flatten_with_path(
        model.make_params(spec, 2**33 + 7))[0]
    got = {}
    for path, leaf in flat:
        a = np.asarray(leaf.astype(np.float32), np.float64).ravel()
        got[jax.tree_util.keystr(path)] = (str(leaf.dtype), leaf.shape,
                                           float(a.sum()), a[:3].tolist())
    assert got == LEAVES


def test_served_gaps_are_the_parents():
    arch = harness.arch(SPEC)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 1024, 20, np.int32)
    out = rng.integers(0, 1024, 6, np.int32)
    gaps = arch.served_gaps(arch.dims(SPEC), model.make_params(SPEC, 5),
                            prompt, out)
    np.testing.assert_allclose(
        gaps, [1.611960530281067, 1.0750164985656738, 0.8619294762611389,
               1.3321795463562012, 0.6326367855072021, 0.7376911640167236],
        rtol=1e-6)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "pinned_" + name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def smollm_ctx(kv_dtype):
    """A hand-made traced window of the smollm-135m cells: kernel seconds
    and calls, decode positions on both sides of the window and past 32k,
    prefill chunks."""
    spec = json.loads((HERE / "configs" / "smollm-135m.json").read_text())
    arch = harness.arch(spec)
    seconds = {"salo_paged_decode": 0.178, "salo_plan_attention": 1.69,
               "salo_plan_backward_dq": 0.61, "salo_plan_backward_dkv": 0.73}
    calls = {"salo_paged_decode": 1320, "salo_plan_attention": 300,
             "salo_plan_backward_dq": 150, "salo_plan_backward_dkv": 150}
    trace = types.SimpleNamespace(n_devices=1, window_s=3.97,
                                  op_seconds=seconds.get, op_calls=calls.get)
    work = types.SimpleNamespace(
        decode_positions=np.array([40, 700, 1023, 1024, 1500, 5000, 33000]),
        prefill_chunks=[(0, 512), (512, 1024), (7680, 8192), (0, 300)],
        head_rows=9, steps=5)
    return types.SimpleNamespace(
        trace=trace, peaks=harness.peaks("TPU v5 lite"), arch=arch,
        dims=arch.dims(spec), work=work,
        engine={"page": 8, "kv_dtype": kv_dtype, "seq": 8192, "batch": 2})


@pytest.mark.parametrize("name,kv_dtype,want", [
    ("serve_step_mfu", "int8", 0.0598431643130586),
    ("salo_paged_decode_roofline", "int8", 0.047234802650532995),
    ("salo_paged_decode_roofline", "bfloat16", 0.09389406099518459),
    ("train_step_mfu", "int8", 10.544503079983123),
    ("salo_plan_attention_roofline", "int8", 3.2775573349352713),
    ("salo_plan_backward_roofline", "int8", 5.16704467914236),
])
def test_readers_are_the_parents(name, kv_dtype, want):
    assert reader(name)(smollm_ctx(kv_dtype)) == want
