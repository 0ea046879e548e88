"""The ``serve_sparse`` kind end to end at a toy size on the CPU (its own
toy tree, ``toy_sparse/``: the cell's name and metric list are the real
benchmark's, the model ``toy_latent``'s with 4 index heads of 16 and
``index_topk`` 24 under contexts of 20-92): the flow of a run through
the new driver, the comparison that decides ``correct`` — sound, altered
underneath, with the mechanism changed, and under BOTH controls — and
that the real tree's files are whole."""

import json
import os
import time

import pytest

from conftest import ROOT

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_sparse")
CELL = "dsv32-serve-deepctx"


def _run(seed=11, seconds=2.0, control=False):
    from chipbench import harness

    lines = []
    rc = harness.run_cell(CELL, seed, seconds, False, t0=time.monotonic(),
                          root=TOY, need_chip=False, control=control,
                          out=lines.append)
    assert rc == 0
    return json.loads(lines[-1])


def test_toy_cell_runs_and_is_correct_but_prints_no_device_metric():
    line = _run(seed=2**31 + 5)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}      # a CPU time is never a device metric


@pytest.mark.parametrize("what", ["indexer_ignored", "unbiased_router"])
def test_the_mechanism_changed_is_not_correct(monkeypatch, what):
    """A program that keeps the FIRST positions whatever their scores,
    and one that routes without the bias: each serves another model's
    tokens."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T
    from horovod_tpu.ops import paged_attention as PA

    if what == "indexer_ignored":
        real = PA.select_topk
        monkeypatch.setattr(
            PA, "select_topk", lambda scores, n_valid, k, width=0: real(
                -jnp.broadcast_to(jnp.arange(scores.shape[1],
                                             dtype=jnp.float32),
                                  scores.shape), n_valid, k, width))
    else:
        monkeypatch.setattr(T, "_routing", lambda p, cfg: cfg.moe_routing)
    assert _run()["correct"] is False


def test_both_controls_fail_the_toy_limit(capfd):
    _run(control=True)
    out = capfd.readouterr().out
    for mode in ("fp8", "dense"):
        gap = float(out.split(f"CONTROL {mode} mean gap ")[1].split(" ")[0])
        assert gap > 1e-5, (mode, gap)


def test_the_parent_program_fails_cleanly_on_the_configuration(monkeypatch):
    """A program whose ``TransformerConfig`` has no indexer fields fails
    in ``build_cfg``, with a ``TypeError``, before any weight is made."""
    import dataclasses

    from chipbench import harness
    from chipbench.drivers import serve_sparse

    def old_replace(cfg, **kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'moe_score_bias'")

    monkeypatch.setattr(dataclasses, "replace", old_replace)
    with pytest.raises(TypeError, match="moe_score_bias"):
        serve_sparse.build_cfg(harness.load_cell(CELL, ROOT)["dims"])


def test_the_real_cells_files_are_whole():
    """Every metric the real ``BENCHMARK.json`` lists for the cell has
    its data file and names a reader that exists; the configuration
    holds the catalog's widths and the stated cut; the traffic is the
    issue's."""
    import importlib

    from chipbench import harness

    cell = harness.load_cell(CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"] == "deepctx-answer"
    assert "0.75 rows an expert" in cell["why"] and len(cell["why"]) <= 200
    for which in ("end_to_end", "per_layer"):
        for name, spec in harness.metric_specs(cell, which).items():
            importlib.import_module(f"chipbench.readers.{spec['reader']}")
    per_layer = harness.metric_specs(cell, "per_layer")
    assert len(per_layer) >= 34     # a later cell's PR may add, not take
    assert {"dsa_score_roofline_pct.dsv32", "dsa_attend_roofline_pct.dsv32",
            "moe_experts_roofline_pct", "dsa_selected_pct.dsv32",
            "kv_index_bytes_per_token.dsv32", "kv_latent_bytes_per_token",
            "moe_rows_here_pct", "mla_expand_ms_per_tick"} <= set(per_layer)
    assert "serve_tokens_per_s" in harness.metric_specs(cell, "end_to_end")
    d = cell["dims"]
    assert (d["hidden_size"], d["num_attention_heads"], d["q_lora_rank"],
            d["kv_lora_rank"], d["qk_nope_head_dim"], d["qk_rope_head_dim"],
            d["v_head_dim"]) == (7168, 128, 1536, 512, 128, 64, 128)
    assert (d["index_n_heads"], d["index_head_dim"], d["index_topk"]) == (
        64, 128, 2048)
    assert (d["intermediate_size"], d["moe_intermediate_size"],
            d["num_experts_per_tok"], d["n_group"], d["topk_group"],
            d["n_shared_experts"], d["topk_method"]) == (
        18432, 2048, 8, 8, 4, 1, "noaux_tc")
    assert d["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size"]
    assert (d["num_hidden_layers"], d["first_k_dense_replace"],
            d["n_routed_experts"], d["vocab_size"], d["router_outputs"]) \
        == (5, 1, 8, 16160, 256)
    assert d["published"]["n_routed_experts"] == 256
    assert d["published"]["num_hidden_layers"] == 61
    assert d["published"]["first_k_dense_replace"] == 3
    t = cell["traffic_params"]
    assert (t["generator"], t["clients"], t["strata"],
            t["requests_per_client"]) == ("closed_loop", 24, 24, 8)
    assert (t["prompt"], t["output"]) == (
        {"dist": "loguniform", "min": 8192, "max": 28672},
        {"dist": "loguniform", "min": 1024, "max": 4096})
    assert (t["first_token_grace_s"], t["request_timeout_s"],
            t["trace_seconds"], t["stream"]) == (60, 900, 4.0, True)
    from chipbench.drivers import serve_sparse

    cfg = serve_sparse.build_cfg(d)
    assert cfg.sparse and cfg.latent_row == 640 and cfg.moe_score_bias
    assert cfg.mla_scale == pytest.approx(0.135234, rel=1e-5)
    assert (cfg.n_experts, cfg.experts_held, cfg.held_offset) == (256, 8, 0)
    assert (cfg.n_heads, cfg.n_dense_layers, cfg.n_layers) == (128, 1, 5)
    e = d["engine"]
    assert e["n_pages"] * e["page_size"] == e["n_slots"] * e["max_len"]
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"]


def test_the_weights_weigh_what_the_configuration_says():
    """3.226 B parameters: the tree's leaves, counted by shape."""
    import numpy as np

    from chipbench import harness, weights_sparse

    d = harness.load_cell(CELL, ROOT)["dims"]

    def count(dense):
        return sum(int(np.prod(s)) for s, _ in
                   weights_sparse.layer_shapes(d, dense).values())

    idx = sum(int(np.prod(weights_sparse.layer_shapes(d, True)[n][0]))
              for n in ("wi_q", "wi_k", "wi_w"))
    assert idx == 13_959_168
    total = count(True) + 4 * count(False) \
        + 2 * d["vocab_size"] * d["hidden_size"] + d["hidden_size"]
    assert abs(total - 3.226e9) < 1e6, total
    assert abs(count(False) - 599.3e6) < 1e5 and abs(
        count(True) - 597.4e6) < 1e5


def test_costs_hand_worked():
    from chipbench import costs_sparse as C

    d = {"hidden_size": 4, "moe_intermediate_size": 3, "kv_lora_rank": 8,
         "qk_rope_head_dim": 2, "num_attention_heads": 3,
         "num_hidden_layers": 2, "index_n_heads": 5, "index_head_dim": 6}
    # 31 scored tokens through 2 layers: a 6-wide key in 2 B; 5 heads'
    # dots of 6
    assert C.index_score_bytes(d, 31) == 31 * 2 * 6 * 2
    assert C.index_score_flops(d, 31) == 31 * 2 * 2 * 5 * 6
    # 7 selected rows: 10 values in 2 B; 3 heads x 2 x (10 + 8)
    assert C.selected_attend_bytes(d, 7) == 7 * 2 * 10 * 2
    assert C.selected_attend_flops(d, 7) == 7 * 2 * 2 * 3 * 18
    assert C.held_expert_flops(d, 7) == 7 * 3 * 2 * 4 * 3


def test_the_new_counters_readers_on_hand_made_observations():
    from chipbench.readers import stats_diff, stats_last

    picked = {"num": ["dsa_selected_tokens_total"], "scale": 100.0,
              "den": ["dsa_scored_tokens_total"]}
    obs = {"stats0": {"dsa_selected_tokens_total": 10,
                      "dsa_scored_tokens_total": 100},
           "stats1": {"dsa_selected_tokens_total": 30,
                      "dsa_scored_tokens_total": 500}}
    assert stats_diff.read(obs, picked) == pytest.approx(5.0)
    # the parent program has neither counter nor key: nothing to read
    old = {"stats0": {"decode_ticks": 1}, "stats1": {"decode_ticks": 9}}
    assert stats_diff.read(old, picked) is None
    assert stats_last.read(old, {"key": "kv_index_bytes_per_token"}) is None


@pytest.mark.parametrize("kw", [{}, {"select": False}],
                         ids=["selected", "dense"])
@pytest.mark.parametrize("blocks", [(16, 16, 2, 32, 16), (32, 8, 4, 64, 32)],
                         ids=["4bands", "2bands"])
def test_the_reference_is_the_same_whatever_divides_it(blocks, kw):
    """Bands, blocks and head groups divide the reference's work in
    memory and time only: the logits are one whole band's, and what lies
    in a row beyond the sequence's own length reaches nothing."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import harness, reference_sparse

    d = harness.load_cell(CELL, TOY)["dims"]
    rng = np.random.default_rng(3)
    toks = rng.integers(0, d["vocab_size"], (3, 128)).astype(np.int32)
    plens, served = [40, 70, 100], [20, 50, 28]
    whole, s0, v0 = reference_sparse.served_logits(
        5, d, jnp.float32, toks, plens, served, **kw)
    other = toks.copy()
    for i, (p, m) in enumerate(zip(plens, served)):
        other[i, p + m:] = rng.integers(0, d["vocab_size"], 128 - p - m)
    cut, s1, v1 = reference_sparse.served_logits(
        5, d, jnp.float32, other, plens, served, blocks=blocks, **kw)
    assert (s0 == s1)[v0].all() and (v0 == v1).all()
    assert np.isfinite(cut[v0]).all()
    np.testing.assert_allclose(cut[v0], whole[v0], atol=2e-5, rtol=0)


def test_the_references_pairs_hand_worked():
    from chipbench.reference_sparse import pairs

    # 4 bands of 8: rows of a band see the keys up to the band's end;
    # 11 tokens are computed as 12 (whole blocks of 4)
    assert pairs(11, 32, band=8, step=4) == 8 * 8 + 4 * 16
    assert pairs(32, 32, band=8, step=4) == 8 * (8 + 16 + 24 + 32)
    assert pairs(1, 32, band=8, step=4) == 4 * 8
    assert pairs(32768, 32768) == 8192 * 81920


def test_the_sample_stays_inside_the_references_budget():
    """The seeded order, the request with the most served tokens first;
    one whose pairs would pass ``check.reference_pairs`` is passed over,
    the first is taken whatever it costs."""
    from chipbench.drivers import serve_sparse

    def rec(i, plen, n):
        return {"id": f"r{i}", "prompt_len": plen, "tokens": [1] * n,
                "error": None, "finish": "length"}

    recs = [rec(0, 100, 5), rec(1, 20, 28), rec(2, 60, 4), rec(3, 30, 3),
            rec(4, 120, 3), rec(5, 10, 2)]
    dims = {"engine": {"max_len": 128},
            "check": {"sample": 4, "reference_pairs": 10 ** 9}}
    every = serve_sparse.pick_sample(recs, 7, dims)
    assert len(every) == 4 and every[0]["id"] == "r1"
    # one band of 128 keys: a request costs 128 a row of its 128-wide block
    dims["check"]["reference_pairs"] = 128 * 128
    assert [r["id"] for r in serve_sparse.pick_sample(recs, 7, dims)] \
        == ["r1"]
    dims["check"]["reference_pairs"] = 0
    assert [r["id"] for r in serve_sparse.pick_sample(recs, 7, dims)] \
        == ["r1"]
    assert serve_sparse.pick_sample([], 7, dims) == []
