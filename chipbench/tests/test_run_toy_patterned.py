"""The ``serve_patterned`` kind end to end at a toy size on the CPU (its
own toy tree, ``toy_patterned/``: the cell's name and metric list are the
real benchmark's, the model four layers of hidden 64): the flow of a run
through the new driver, the comparison that decides ``correct`` — sound,
altered underneath, and under the lower-precision control — and that the
real tree's files are whole."""

import json
import os
import time

import pytest

from conftest import ROOT

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_patterned")
CELL = "mellum2-serve-ide"


def _run(seed=11, seconds=2.0, control=False, log=None):
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


def test_a_window_layer_that_sees_everything_is_not_correct(monkeypatch):
    """The mechanism itself left out: with the window's lower bound
    dropped from the decode path the served tokens are another model's
    once a context passes the window."""
    from horovod_tpu.models import transformer as T

    real = T._paged_decode_attend

    def no_bound(*args):
        return real(*args[:-1], None)

    monkeypatch.setattr(T, "_paged_decode_attend", no_bound)
    assert _run()["correct"] is False


def test_control_precision_fails_the_toy_limit(capfd):
    _run(control=True)
    out = capfd.readouterr().out
    gap = float(out.split("CONTROL fp8 mean gap ")[1].split(" ")[0])
    assert gap > 1e-5


def test_the_real_cells_files_are_whole():
    """Every metric the real ``BENCHMARK.json`` lists for the cell has
    its data file and names a reader that exists; the configuration
    holds the published widths; the traffic is the issue's."""
    import importlib

    from chipbench import harness

    cell = harness.load_cell(CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"] == "ide-assist"
    for which in ("end_to_end", "per_layer"):
        for name, spec in harness.metric_specs(cell, which).items():
            importlib.import_module(f"chipbench.readers.{spec['reader']}")
    per_layer = harness.metric_specs(cell, "per_layer")
    assert len(per_layer) >= 27 and "moe_experts_roofline_pct" in per_layer
    d = cell["dims"]
    assert (d["hidden_size"], d["num_attention_heads"],
            d["num_key_value_heads"], d["head_dim"]) == (2304, 32, 4, 128)
    assert (d["num_experts"], d["num_experts_per_tok"],
            d["moe_intermediate_size"]) == (64, 8, 896)
    assert (d["sliding_window"], d["vocab_size"]) == (1024, 98304)
    assert d["reduced"] == ["num_hidden_layers"]
    assert d["num_hidden_layers"] == 8 == len(d["layer_types"])
    assert d["published"]["num_hidden_layers"] == 28
    t = cell["traffic_params"]
    assert (t["clients"], t["prompt"]["max"], t["output"]["max"]) == (
        32, 8192, 1024)
    from chipbench.drivers import serve_patterned

    cfg = serve_patterned.build_cfg(d)
    assert cfg.layer_pattern == ("sliding", "sliding", "sliding", "full")
    assert cfg.head_dim == 128 and cfg.expert_width == 896
    assert cfg.rope_yarn[0] == 16 and cfg.rope_yarn[4] == pytest.approx(
        1.2772588722239782)


def test_costs_hand_worked():
    from chipbench import costs_patterned as C

    d = {"hidden_size": 4, "moe_intermediate_size": 3,
         "num_key_value_heads": 2, "num_attention_heads": 4, "head_dim": 8,
         "sliding_window": 10,
         "layer_types": ["sliding_attention", "full_attention"]}
    assert C.expert_weight_bytes(d) == 3 * 4 * 3 * 2
    # 5 experts touched, 7 rows: 5 x 72 + 7 x 2 x 4 x 2
    assert C.moe_expert_bytes(d, 5, 7) == 5 * 72 + 112
    assert C.moe_expert_flops(d, 7) == 7 * 3 * 2 * 4 * 3
    # contexts 6 and 25: the full layer 6 + 25 tokens, the sliding one
    # 6 + 10; K and V of 2 heads of 8 in 2 bytes = 64 B a token; queries
    # and outputs 2 layers x 2 x 4 x 8 x 2 = 256 B a slot
    assert C.windowed_decode_bytes(d, [6, 25]) == (31 + 16) * 64 + 2 * 256


def test_new_readers_on_hand_made_observations():
    from chipbench.readers import stats_last

    obs = {"stats1": {"kv_window_pages_per_slot_max": 65.0}}
    assert stats_last.read(obs, {"key": "kv_window_pages_per_slot_max"}) \
        == 65.0
    assert stats_last.read({"stats1": {}}, {"key": "absent"}) is None
    assert stats_last.read({}, {"key": "absent"}) is None
    from chipbench.readers import trace_scope_roofline

    assert trace_scope_roofline.read(
        {}, {"scope": "hvd_moe_experts"}) is None   # no trace: nothing
