"""Work counts against brute force over the pattern's own mask."""
import numpy as np
import pytest

from chipbench import work
from repro.core.patterns import causal_sliding_window

CASES = [(16, 2, 4, 80), (8, 4, 8, 40), (5, 0, 4, 30), (1024, 4, 8, 1100),
         (12, 3, 4, 50)]


def brute_pages(mask_row, window, sinks, page):
    pos = np.nonzero(mask_row)[0]
    n_sink = -(-sinks // page) * page
    cap = -(-window // page) * page
    slot = np.where(pos < sinks, pos, n_sink + (pos - sinks) % cap)
    return len(np.unique(slot // page))


@pytest.mark.parametrize("window,sinks,page,n", CASES)
def test_attended_matches_mask(window, sinks, page, n):
    mask = causal_sliding_window(window, n_sinks=sinks).mask(n)
    np.testing.assert_array_equal(
        work.attended(np.arange(n), window, sinks), mask.sum(1))
    assert work.pairs_causal_prefix(n, window, sinks) == mask.sum()


@pytest.mark.parametrize("window,sinks,page,n", CASES)
def test_ring_pages_match_brute_force(window, sinks, page, n):
    mask = causal_sliding_window(window, n_sinks=sinks).mask(n)
    want = [brute_pages(mask[t], window, sinks, page) for t in range(n)]
    np.testing.assert_array_equal(
        work.ring_pages(np.arange(n), window, sinks, page), want)


def test_ring_layout_is_the_programs():
    from repro.serve.paged_cache import layout_for_pattern

    for window, sinks, page, _ in CASES:
        lay = layout_for_pattern(causal_sliding_window(window, n_sinks=sinks),
                                 page)
        assert lay.n_sink == -(-sinks // page) * page
        assert lay.ring_cap == -(-window // page) * page


def test_forward_flops_attention_term():
    from chipbench import harness

    dense = harness.arch({"arch": "dense_decoder"})
    m = {"L": 3, "d": 8, "H": 4, "Hkv": 2, "hd": 2, "f": 16, "V": 10,
         "window": 16, "sinks": 2}
    n = 70
    mask = causal_sliding_window(16, n_sinks=2).mask(n)
    want = 2 * dense.matmul_params_per_token(m) * n \
        + 4 * 3 * 4 * 2 * mask.sum() + 2 * 8 * 10 * 5
    assert work.forward_flops(dense, m, np.arange(n), 5) == want
    assert dense.matmul_params_per_token(m) == 3 * (
        8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)


def test_decode_roofline_work():
    import importlib.util
    from pathlib import Path

    path = (Path(__file__).resolve().parents[1] / "metrics"
            / "salo_paged_decode_roofline.py")
    spec = importlib.util.spec_from_file_location("roof", path)
    roof = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roof)
    layers = [(2, 4, 2, 8, 16, 2)]
    eng = {"page": 4, "kv_dtype": "int8"}
    pos = np.array([0, 3, 17, 40, 41])
    mask = causal_sliding_window(16, n_sinks=2).mask(50)
    pages = sum(brute_pages(mask[t], 16, 2, 4) for t in pos)
    ops, nbytes = roof.operations_and_bytes(layers, eng, pos)
    assert ops == 2 * 4 * 4 * 8 * mask[pos].sum()
    assert nbytes == 2 * (pages * (2 * 4 * 2 * 8 + 8) + 5 * 2 * 4 * 8 * 2)
