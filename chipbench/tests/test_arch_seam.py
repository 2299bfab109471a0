"""A new architecture is a new file: a toy module with a windowed and a
full-attention layer kind, in an ``arch`` directory of its own, is found by
the name its configuration gives, and the work counts sum over its kinds as
a brute-force count over each layer's mask does."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, work
from repro.core.patterns import causal_sliding_window

HERE = Path(__file__).resolve().parents[1]

TOY = '''
def dims(spec):
    return {"d": 16, "V": 50, "H": 4, "Hkv": 2, "hd": 8, "window": 16,
            "sinks": 2, "swa": 2, "full": 1}


def matmul_params_per_token(m):
    return 1000


def attention_layers(m):
    return [(m["swa"], m["H"], m["Hkv"], m["hd"], m["window"], m["sinks"]),
            (m["full"], m["H"], m["Hkv"], m["hd"], None, 0)]
'''

# per layer, in order: window (None for full causal attention), sinks
LAYERS = [(16, 2), (None, 0), (16, 2)]


@pytest.fixture
def toy_tree(tmp_path, monkeypatch):
    """A checkout with two cells, whose configurations name the
    architectures ``toy`` (there) and ``toy2`` (not there)."""
    bench = tmp_path / "chipbench"
    for d in ("arch", "configs", "traffic", "checks"):
        (bench / d).mkdir(parents=True)
    (bench / "arch" / "toy.py").write_text(TOY)
    (bench / "traffic" / "t.json").write_text(json.dumps({"kind": "serve"}))
    cells = []
    for config, name in (("toy-1", "toy"), ("other-1", "toy2")):
        (bench / "configs" / f"{config}.json").write_text(
            json.dumps({"name": config, "arch": name}))
        (bench / "checks" / f"{config}-cell.json").write_text("{}")
        cells.append({"name": f"{config}-cell", "config": config,
                      "traffic": "t", "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({"workloads":
                                                         cells}))
    monkeypatch.setattr(harness, "HERE", bench)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    return bench


def masks(n):
    full = np.tril(np.ones((n, n), bool))
    return [full if w is None else causal_sliding_window(w, n_sinks=g).mask(n)
            for w, g in LAYERS]


def flat_pages(row, page):
    """Pages that hold an attended key where position ``p`` sits on page
    ``p // page`` (full attention; windowed layers take ``test_work``'s
    ring layout)."""
    return len(np.unique(np.nonzero(row)[0] // page))


def test_cell_files_resolves_a_new_architecture(toy_tree):
    _, cell, spec, traffic, _ = harness.cell_files("toy-1-cell")
    assert cell["config"] == "toy-1" and traffic == {"kind": "serve"}
    arch = harness.arch(spec)
    assert Path(arch.__file__) == toy_tree / "arch" / "toy.py"
    assert [c for c, *_ in arch.attention_layers(arch.dims(spec))] == [2, 1]


def test_forward_flops_sum_over_layer_kinds(toy_tree):
    arch = harness.arch({"arch": "toy"})
    m = arch.dims({})
    n, head_rows = 70, 5
    pairs = sum(int(mask.sum()) for mask in masks(n))
    want = 2 * 1000 * n + 4 * 4 * 8 * pairs + 2 * 16 * 50 * head_rows
    assert work.forward_flops(arch, m, np.arange(n), head_rows) == want


def test_decode_roofline_work_sums_over_layer_kinds(toy_tree):
    from chipbench.tests.test_work import brute_pages as ring_pages

    spec = importlib.util.spec_from_file_location(
        "seam_roof", HERE / "metrics" / "salo_paged_decode_roofline.py")
    roof = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roof)
    arch = harness.arch({"arch": "toy"})
    pos = np.array([0, 3, 17, 40, 41])
    ms = masks(50)
    keys = sum(int(mask[pos].sum()) for mask in ms)
    pages = sum(ring_pages(mask[t], 16, 2, 4) if w is not None
                else flat_pages(mask[t], 4)
                for mask, (w, _) in zip(ms, LAYERS) for t in pos)
    ops, nbytes = roof.operations_and_bytes(
        arch.attention_layers(arch.dims({})), {"page": 4, "kv_dtype": "int8"},
        pos)
    assert ops == 4 * 4 * 8 * keys
    assert nbytes == pages * (2 * 4 * 2 * 8 + 8) + 3 * 5 * 2 * 4 * 8 * 2


def test_cell_of_an_unknown_architecture_exits_before_set_up(toy_tree):
    with pytest.raises(SystemExit, match=r"'toy2'; chipbench/arch has "
                                         r"\['toy'\]"):
        harness.cell_files("other-1-cell")


@pytest.mark.parametrize("spec", [{"name": "c", "arch": "no_such_arch"},
                                  {"name": "c"}])
def test_unknown_architecture_names_those_there(spec):
    with pytest.raises(SystemExit, match=r"chipbench/arch has "
                                         r"\['dense_decoder'\]"):
        harness.arch(spec)
