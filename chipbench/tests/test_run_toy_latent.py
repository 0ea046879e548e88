"""The ``serve_latent`` kind end to end at a toy size on the CPU (its own
toy tree, ``toy_latent/``: the cell's name and metric list are the real
benchmark's, the model three layers of hidden 64 holding 4 of 16
experts): the flow of a run through the new driver, the comparison that
decides ``correct`` — sound, altered underneath, with the mechanism
changed, and under the lower-precision control — and that the real
tree's files are whole."""

import json
import os
import time

import pytest

from conftest import ROOT

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_latent")
CELL = "axk1-serve-longctx"


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


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from horovod_tpu.serving import engine as E

    real = E.InferenceEngine._emit

    def emit(self, slot, tok):
        return real(self, slot, (tok + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(E.InferenceEngine, "_emit", emit)
    assert _run()["correct"] is False


@pytest.mark.parametrize("what", ["scale_without_m2", "another_share"])
def test_the_mechanism_changed_is_not_correct(monkeypatch, what):
    """The softmax scale without YaRN's ``m^2``, and the experts of
    another chip's share: each serves another model's tokens."""
    from horovod_tpu.models import transformer as T

    if what == "scale_without_m2":
        monkeypatch.setattr(
            T.TransformerConfig, "mla_scale", property(
                lambda self: (self.qk_nope_head_dim
                              + self.qk_rope_head_dim) ** -0.5))
    else:
        monkeypatch.setattr(T.TransformerConfig, "held_offset",
                            property(lambda self: 8))
    assert _run()["correct"] is False


def test_control_precision_fails_the_toy_limit(capfd):
    _run(control=True)
    out = capfd.readouterr().out
    gap = float(out.split("CONTROL fp8 mean gap ")[1].split(" ")[0])
    assert gap > 1e-5


def test_the_real_cells_files_are_whole():
    """Every metric the real ``BENCHMARK.json`` lists for the cell has
    its data file and names a reader that exists; the configuration
    holds the published widths and the stated cut; the traffic is the
    issue's."""
    import importlib

    from chipbench import harness

    cell = harness.load_cell(CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"] == "longctx-answer"
    assert "more than its share" in cell["why"]
    for which in ("end_to_end", "per_layer"):
        for name, spec in harness.metric_specs(cell, which).items():
            importlib.import_module(f"chipbench.readers.{spec['reader']}")
    per_layer = harness.metric_specs(cell, "per_layer")
    assert len(per_layer) >= 28     # a later cell's PR may add, not take
    assert {"mla_decode_roofline_pct.axk1", "moe_experts_roofline_pct",
            "device_unscoped_pct.tput"} <= set(per_layer)
    assert "serve_tokens_per_s" in harness.metric_specs(cell, "end_to_end")
    d = cell["dims"]
    assert (d["hidden_size"], d["num_attention_heads"], d["q_lora_rank"],
            d["kv_lora_rank"], d["qk_nope_head_dim"], d["qk_rope_head_dim"],
            d["v_head_dim"]) == (7168, 64, 1536, 512, 128, 64, 128)
    assert (d["intermediate_size"], d["moe_intermediate_size"],
            d["num_experts_per_tok"], d["n_group"], d["topk_group"],
            d["n_shared_experts"]) == (18432, 2048, 8, 8, 4, 1)
    assert d["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert (d["num_hidden_layers"], d["n_routed_experts"], d["vocab_size"],
            d["router_outputs"]) == (5, 12, 20480, 192)
    assert d["published"]["n_routed_experts"] == 192
    assert d["published"]["num_hidden_layers"] == 61
    t = cell["traffic_params"]
    assert (t["clients"], t["strata"], t["requests_per_client"]) == (
        32, 32, 32)
    assert (t["prompt"]["min"], t["prompt"]["max"], t["output"]["min"],
            t["output"]["max"]) == (2048, 16384, 512, 2048)
    from chipbench.drivers import serve_latent

    cfg = serve_latent.build_cfg(d)
    assert cfg.latent and cfg.latent_width == 576 and cfg.latent_row == 640
    assert cfg.mla_scale == pytest.approx(0.130861, rel=1e-5)
    assert cfg.rope_yarn[4] == 1.0 and cfg.n_dense_layers == 1
    assert (cfg.n_experts, cfg.experts_held, cfg.held_offset) == (192, 12, 0)
    e = d["engine"]
    assert e["n_pages"] * e["page_size"] == e["n_slots"] * e["max_len"]


def test_costs_hand_worked():
    from chipbench import costs_latent as C

    d = {"hidden_size": 4, "moe_intermediate_size": 3, "kv_lora_rank": 8,
         "qk_rope_head_dim": 2, "num_attention_heads": 3,
         "num_hidden_layers": 2, "n_routed_experts": 3,
         "router_outputs": 12}
    assert C.latent_values(d) == 10
    # contexts 6 and 25 through 2 layers: 31 rows of 10 values in 2 B,
    # and a slot and layer 3 heads x (10 in + 8 out) x 2 B
    assert C.mla_decode_bytes(d, [6, 25]) == 2 * (31 * 20 + 2 * 3 * 18 * 2)
    assert C.mla_decode_flops(d, [6, 25]) == 2 * 31 * 2 * 3 * 18
    assert C.expert_weight_bytes(d) == 3 * 4 * 3 * 2
    assert C.held_expert_bytes(d, 5, 7) == 5 * 72 + 7 * 2 * 4 * 2
    assert C.held_expert_flops(d, 7) == 7 * 3 * 2 * 4 * 3
    assert C.rows_here_share(d) == 0.25


def test_the_new_counters_reader_on_hand_made_observations():
    from chipbench.readers import stats_diff, stats_last

    here = {"num": ["moe_rows_total"], "scale": 100.0,
            "den": ["moe_rows_total", "moe_rows_routed_away_total"]}
    obs = {"stats0": {"moe_rows_total": 10, "moe_rows_routed_away_total": 90},
           "stats1": {"moe_rows_total": 30, "moe_rows_routed_away_total": 400}}
    assert stats_diff.read(obs, here) == pytest.approx(100 * 20 / 330)
    # a program that counts rows but holds every expert: all of them here
    old = {"stats0": {"moe_rows_total": 10}, "stats1": {"moe_rows_total": 30}}
    assert stats_diff.read(old, here) == 100.0
    assert stats_last.read(old, {"key": "kv_latent_bytes_per_token"}) is None
