"""The lower-precision control comes out NOT correct (toy size; the same
comparison ran on the chip at the cells' own sizes, see PERF.md)."""

import json
import os

import numpy as np
import pytest

from conftest import TOY


def _cell(workload):
    from chipbench import harness

    return harness.load_cell(workload, TOY)


def test_serving_control_fp8_fails_the_gap_limit():
    from chipbench import reference
    from chipbench.drivers import serve

    dims = _cell("m7b-serve-chat")["dims"]
    rng = np.random.default_rng(0)
    toks = rng.integers(0, dims["vocab_size"], (3, 64)).astype(np.int32)
    plens, nserved = [20, 30, 40], [24, 24, 24]
    ref, served, valid = reference.served_logits(
        3, dims, "float32", toks, plens, nserved, q_block=32)
    # the sound path: the reference's own picks have gap 0
    gap, _ = reference.gaps_from_logits(ref, ref.argmax(-1), valid)
    assert np.nanmax(gap) == 0.0
    low, _, _ = reference.served_logits(
        3, dims, "float32", toks, plens, nserved, mode="fp8", q_block=32)
    cgap, _ = reference.gaps_from_logits(ref, low.argmax(-1), valid)
    assert np.nanmax(cgap) > dims["check"]["served_gap_limit"]
    assert serve.pick_sample([], 0, 4, 128) == []


def test_training_control_fp8_fails_a_limit():
    from chipbench.drivers import train

    cell = _cell("m7b-train-1chip")
    ref = train.reference_steps(cell, 5, 2, 3)
    low = train.reference_steps(cell, 5, 2, 3, mode="fp8")
    rows, ok = train.compare(low, ref, cell["dims"]["check"])
    assert not ok
    assert train.compare(ref, ref, cell["dims"]["check"])[1]


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    from chipbench.drivers import train

    ref = {"a": 1.0, "b": 1.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 1.0, "c": 3e-9}   # c is all but zero
    worst, leaf = train.worst_leaf_gap(prog, ref)
    assert leaf == "a" and abs(worst - 0.1) < 1e-9


def test_real_configs_keep_published_widths():
    here = os.path.dirname(os.path.abspath(__file__))
    for name in ("mistral-7b-v0.3-serve", "mistral-7b-v0.3-train"):
        with open(os.path.join(here, "..", "configs", name + ".json")) as f:
            d = json.load(f)
        assert (d["hidden_size"], d["intermediate_size"], d["head_dim"],
                d["num_attention_heads"], d["num_key_value_heads"],
                d["vocab_size"]) == (4096, 14336, 128, 32, 8, 32768)
        assert d["reduced"] == ["num_hidden_layers"]


TOY_TREES = ("toy", "toy_latent", "toy_patterned", "toy_sparse")


@pytest.mark.parametrize("tree", TOY_TREES)
def test_toy_benchmark_lists_the_real_metrics(tree):
    """A toy tree the CPU tests run is the real BENCHMARK.json with toy
    configurations, cut to the tree's cells: its ``per_layer`` is exactly
    the real entries whose ``workloads`` name one of its cells, each list
    cut to those cells; its end-to-end metrics, bounds and cells are the
    real ones.  The next cell cannot let a tree drift."""
    from conftest import ROOT

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(os.path.dirname(TOY), tree,
                           "BENCHMARK.json")) as f:
        toy = json.load(f)
    cells = [w["name"] for w in toy["workloads"]]

    def cut(metrics):
        out = []
        for m in metrics:
            if "workloads" not in m:
                out.append(m)
                continue
            here = [c for c in m["workloads"] if c in cells]
            if here:
                out.append(dict(m, workloads=here))
        return out

    assert toy["per_layer"] == cut(real["per_layer"])
    assert toy["end_to_end"] == cut(real["end_to_end"])
    for key in ("command", "paths", "run_seconds"):
        assert toy[key] == real[key]
    assert [(w["name"], w["traffic"], w["chips"]) for w in toy["workloads"]] \
        == [(w["name"], w["traffic"], w["chips"]) for w in real["workloads"]
            if w["name"] in cells]
