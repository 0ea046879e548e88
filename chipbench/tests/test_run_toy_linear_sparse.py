"""The ``serve_linear_sparse`` kind end to end at a toy size on the CPU
(its own toy tree, ``toy_linear_sparse/``: the cell's name and metric
list are the real benchmark's, the model published layers 1-4 of six —
sparse, linear, linear, sparse — of hidden 64, 4 query / 2 KV heads of 16,
windows of 8 keys every 4, blocks of 8, top-2, the switch at 24 tokens,
under prompts of 20-80 in chunks of 32): the flow of a run through the
new driver, the comparison that decides ``correct`` — sound, altered
underneath, with each mechanism changed, and under ALL THREE controls —
that the benchmark's reference is the program's plain reference, and that
the real tree's files are whole."""

import json
import os
import time

import numpy as np
import pytest

from conftest import ROOT

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_linear_sparse")
CELL = "sala-serve-longreason"


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


@pytest.mark.parametrize("what", ["state_lost_between_chunks",
                                  "state_lost_at_a_tick",
                                  "compressed_keys_never_written",
                                  "the_selection_left_out",
                                  "a_scale_left_out"])
def test_the_mechanism_changed_is_not_correct(monkeypatch, what):
    """A program that hands a prompt's next chunk zeros for the matrix
    state the last one left, one whose tick reads its slots' states as
    zeros, one whose tick scores compressed keys that were never
    written, one that attends everything, and one that leaves the
    residual scale off the mixers: each serves another model's tokens."""
    import dataclasses

    import jax.numpy as jnp

    from chipbench.drivers import serve_linear_sparse
    from horovod_tpu.models import transformer as T
    from horovod_tpu.serving.cache import PagedSlotCache

    def rebuilt(**over):
        real = serve_linear_sparse.build_cfg
        monkeypatch.setattr(
            serve_linear_sparse, "build_cfg",
            lambda dims: dataclasses.replace(real(dims), **over))

    if what == "state_lost_between_chunks":
        real = PagedSlotCache.slot_state
        monkeypatch.setattr(
            PagedSlotCache, "slot_state",
            lambda self, slot, name=None: jnp.zeros_like(
                real(self, slot, name)))
    elif what == "state_lost_at_a_tick":
        real = T._lin_decode
        monkeypatch.setattr(
            T, "_lin_decode",
            lambda q, k, v, p, states, layer, active, kernel: real(
                q, k, v, p, jnp.zeros_like(states), layer, active, kernel))
    elif what == "compressed_keys_never_written":
        real = T._bsa_window_mean
        monkeypatch.setattr(
            T, "_bsa_window_mean",
            lambda prev, this, cfg: 0.0 * real(prev, this, cfg))
    elif what == "the_selection_left_out":
        rebuilt(bsa_dense_len=10 ** 6)
    else:
        rebuilt(attn_out_multiplier=1.0)
    assert _run()["correct"] is False


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from horovod_tpu.serving import engine as E

    real = E.InferenceEngine._emit

    def emit(self, slot, tok):
        return real(self, slot, (tok + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(E.InferenceEngine, "_emit", emit)
    assert _run()["correct"] is False


def test_all_three_controls_fail_the_toy_limit(capfd):
    """Each control goes through the comparison that decides ``correct``
    and is refused by it; the run stays ``correct`` (served tokens pass,
    no control does)."""
    assert _run(control=True)["correct"] is True
    out = capfd.readouterr().out
    for mode in ("fp8", "lost_state", "dense"):
        line = out.split(f"CONTROL {mode}: mean gap ")[1].split("\n")[0]
        assert "-> correct False" in line, line


def test_a_limit_that_lets_a_control_pass_fails_the_control_run(monkeypatch):
    from chipbench.drivers import serve_linear_sparse

    real = serve_linear_sparse.judged
    monkeypatch.setattr(
        serve_linear_sparse, "judged",
        lambda chk, dims, prefix="": prefix == "dense_" or real(
            chk, dims, prefix))
    assert _run(control=True)["correct"] is False


def test_the_benchmarks_reference_is_the_programs_plain_reference():
    """``chipbench/reference_linear_sparse.py`` (rows in blocks, one
    width, a loop to the sequence's own length) against
    ``plain_reference.sala_forward`` on the benchmark's own weights."""
    import jax.numpy as jnp

    from chipbench import harness, reference_linear_sparse
    from chipbench import weights_linear_sparse
    from horovod_tpu.models import plain_reference

    dims = harness.load_cell(CELL, TOY)["dims"]
    params = weights_linear_sparse.make_params(3, dims, jnp.float32)
    plain = dict(dims, sparse_config=dims["assumed"]["sparse_config"],
                 mixer_types=dims["mixer_types"][1:5])
    toks = np.random.default_rng(1).integers(0, 128, (2, 64)).astype(np.int32)
    plens, ns = [30, 20], [20, 30]
    got, served, valid = reference_linear_sparse.served_logits(
        3, dims, jnp.float32, toks, plens, ns, q_block=16)
    for i, (p, n) in enumerate(zip(plens, ns)):
        want = np.asarray(plain_reference.sala_forward(
            params, jnp.asarray(toks[i, :p + n]), plain))
        np.testing.assert_allclose(got[i, :n], want[p - 1:p - 1 + n],
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_array_equal(served[i, :n], toks[i, p:p + n])
        assert valid[i].sum() == n


def test_the_parent_program_fails_cleanly_on_the_configuration(monkeypatch):
    """A program whose ``TransformerConfig`` has no block-sparse fields
    fails in ``build_cfg``, with a ``TypeError``, before any weight is
    made."""
    from chipbench import harness
    from chipbench.drivers import serve_linear_sparse
    from horovod_tpu.models import transformer as T

    def old_config(**kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'bsa_kernel'")

    monkeypatch.setattr(T, "TransformerConfig", old_config)
    with pytest.raises(TypeError, match="bsa_kernel"):
        serve_linear_sparse.build_cfg(harness.load_cell(CELL, ROOT)["dims"])


def test_the_toy_tree_lists_the_real_metrics():
    """``test_control.py``'s rule for the standing trees, for this one:
    the real ``BENCHMARK.json`` cut to the tree's cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        toy = json.load(f)

    def cut(metrics):
        return [dict(m, workloads=[CELL]) if "workloads" in m else m
                for m in metrics if CELL in m.get("workloads", [CELL])]

    assert toy["per_layer"] == cut(real["per_layer"])
    assert toy["end_to_end"] == cut(real["end_to_end"])
    for key in ("command", "paths", "run_seconds"):
        assert toy[key] == real[key]
    assert [(w["name"], w["traffic"], w["chips"], w["why"])
            for w in toy["workloads"]] == [
        (w["name"], w["traffic"], w["chips"], w["why"])
        for w in real["workloads"] if w["name"] == CELL]


def test_the_real_cells_files_are_whole():
    """Every metric the real ``BENCHMARK.json`` lists for the cell has
    its data file and names a reader that exists; the configuration
    holds EVERY key of the catalog's config but the stated cut (depth);
    the traffic is the issue's."""
    import importlib

    from chipbench import harness

    cell = harness.load_cell(CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-reason"
    assert len(cell["why"]) <= 200
    for which in ("end_to_end", "per_layer"):
        for name, spec in harness.metric_specs(cell, which).items():
            importlib.import_module(f"chipbench.readers.{spec['reader']}")
    per_layer = set(harness.metric_specs(cell, "per_layer"))
    assert {"lin_proj_ms_per_tick", "bsa_score_ms_per_tick",
            "bsa_score_roofline_pct", "bsa_select_ms_per_tick",
            "bsa_attend_ms_per_tick", "bsa_attend_roofline_pct",
            "bsa_selected_pct", "lin_state_bytes_per_slot",
            "kv_compressed_bytes_per_page", "ssm_update_ms_per_tick",
            "lin_update_roofline_pct", "ssm_scan_ms_per_tick",
            "bsa_chunk_select_ms_per_tick",
            "landed_gather_ms_per_tick", "prefill_padding_pct.longdoc",
            "dense_mlp_ms_per_tick", "device_idle_pct.tput",
            "device_unscoped_pct.tput"} <= per_layer
    # the state-space mixer's shares count ITS need (costs_hybrid): the
    # lightning layers' are entries of their own over the same scopes
    assert not {"ssm_update_roofline_pct", "ssm_scan_roofline_pct"} & per_layer
    assert not [n for n in per_layer if n.startswith(("moe_", "conv_",
                                                      "dsa_", "paged_"))]
    assert "serve_tokens_per_s" in harness.metric_specs(cell, "end_to_end")
    d = cell["dims"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert d["source"] == row["source_url"]
    published = {k: v for k, v in row["config"].items()
                 if k != "num_hidden_layers"}
    assert {k: d[k] for k in published} == published
    assert d["reduced"] == ["num_hidden_layers"]
    assert (d["num_hidden_layers"], d["first_layer"]) == (12, 16)
    assert d["published"]["num_hidden_layers"] == 32 == len(d["mixer_types"])
    assert "THREE" in d["deployment"]
    assert d["assumed"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "window_size": 2048, "init_blocks": 1, "dense_len": 8192}
    for key in ("lightning_decay", "norm_places", "gates", "rope_pairing",
                "mup", "state_dtype", "initialisation", "window_as_blocks",
                "dense_switch"):
        assert d["assumed"][key]
    t = cell["traffic_params"]
    assert (t["generator"], t["clients"], t["strata"],
            t["requests_per_client"]) == ("closed_loop", 48, 48, 4)
    assert t["prompt"] == {"dist": "loguniform", "min": 8192, "max": 16384}
    assert t["output"] == {"dist": "loguniform", "min": 4096, "max": 12288}
    assert (t["stream"], t["first_token_grace_s"], t["request_timeout_s"],
            t["trace_seconds"]) == (True, 60, 1200, 4.0)
    from chipbench.drivers import serve_linear_sparse

    cfg = serve_linear_sparse.build_cfg(d)
    assert cfg.layer_kinds == ("block_sparse",) * 2 + ("linear",) * 4 + (
        "block_sparse",) + ("linear",) * 5
    assert (cfg.head_dim, cfg.kv_heads, cfg.bsa_blocks_max,
            cfg.ssm_chunk) == (128, 2, 128, 128)
    assert abs(cfg.attn_out_multiplier - 1.4 / 32 ** 0.5) < 1e-12
    assert (cfg.embed_multiplier, cfg.head_multiplier) == (12.0, 1 / 16)
    assert (cfg.layers_with("k"), cfg.layers_with("ck"),
            cfg.layers_with("lin")) == (3, 3, 9)
    e = d["engine"]
    # 48, not the issue's 64: its stated fallback (a warm run of 64 took
    # 339 s in all, over 300: PERF.md section 6)
    assert e["n_slots"] == t["clients"] == 48 and not e["speculative"]
    assert (e["max_len"], e["page_size"], e["n_pages"],
            e["prefill_chunk_tokens"]) == (28672, 16, 81920, 512)
    assert e["page_size"] == cfg.bsa_stride
    # the longest standing context and every request fit a slot
    assert t["prompt"]["max"] + t["output"]["max"] <= e["max_len"]


def test_the_weights_weigh_what_the_configuration_says():
    """3.93 B parameters = 7.86 GB in bf16: the issue's count of the
    matrices, and the norms and decays; the decay is the schedule at the
    PUBLISHED layer index."""
    from chipbench import harness, weights_linear_sparse as W

    d = harness.load_cell(CELL, ROOT)["dims"]
    assert W.param_count(d) == 3_929_973_152

    def count(kind, *names):
        shapes = W.layer_shapes(d, kind)
        return sum(int(np.prod(shapes[n][0])) for n in names or shapes)

    assert count("linear", "lin_q", "lin_k", "lin_v", "lin_g",
                 "lin_o") == 5 * 16_777_216
    assert count("block_sparse", "wq", "wo", "wg", "wk",
                 "wv") == 3 * 16_777_216 + 2 * 1_048_576
    assert count("linear", "w_gate", "w_up", "w_down") == 201_326_592
    got = np.asarray(W.decay(16, d))
    h = np.arange(1, 33)
    want = -(2.0 ** (-8.0 * h / 32)) * (1 - 16 / 31 + 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert 0.9994 < np.exp(np.asarray(W.decay(27, d))).max() < 0.9996
    assert W.residual_scale(d) == 1.4 / np.sqrt(32)
    assert W.logit_scale(d) == 256 / 4096


def test_costs_hand_worked():
    from chipbench import costs_linear_sparse as C

    d = {"num_hidden_layers": 4, "first_layer": 1,
         "mixer_types": ["lightning-attn", "minicpm4", "lightning-attn",
                         "lightning-attn", "minicpm4", "lightning-attn"],
         "num_key_value_heads": 2, "head_dim": 4, "lightning_nh": 3,
         "lightning_head_dim": 4}
    # layers 1-4: two minicpm4, two lightning
    assert C.kv_bytes_per_token(d) == 2 * 2 * 2 * 4 * 2 == 64
    assert C.compressed_bytes_per_page(d) == 2 * 2 * 4 * 2 == 32
    assert C.lin_state_bytes_per_layer(d) == 3 * 4 * 4 * 4 == 192
    assert C.lin_state_bytes_per_slot(d) == 384
    # 10 (slot, layer) pairs: each state read once and written once
    assert C.lin_update_bytes(d, 10) == 10 * 2 * 192
    # chunk 8: 3 heads x (2 x 8 x 4 + 2 x 8 x 4 + 2 x 16 + 2 x 16)
    assert C.lin_scan_flops_per_token(d, chunk=8) == 3 * (64 + 64 + 64)
    assert C.lin_scan_flops(d, 5) == 5 * C.lin_scan_flops_per_token(d)
    assert C.bsa_score_bytes(d, 7) == 7 * 4 * 2
    # a token a slot and layer: K and V of both KV heads
    assert C.bsa_attend_bytes(d, 9) == 9 * 2 * 2 * 4 * 2
    real = {"num_hidden_layers": 12, "first_layer": 16,
            "mixer_types": ["minicpm4"] * 18 + ["lightning-attn"] * 4
            + ["minicpm4"] + ["lightning-attn"] * 9,
            "num_key_value_heads": 2, "head_dim": 128, "lightning_nh": 32,
            "lightning_head_dim": 128}
    assert C.kv_bytes_per_token(real) == 3072
    assert C.compressed_bytes_per_page(real) == 1536
    assert C.lin_state_bytes_per_layer(real) == 2_097_152
    assert C.lin_state_bytes_per_slot(real) == 18_874_368
    assert C.lin_scan_flops_per_token(real) == 4_194_304
