"""The ``serve_conv`` kind end to end at a toy size on the CPU (its own
toy tree, ``toy_conv/``: the cell's name and metric list are the real
benchmark's, the model six layers of hidden 128 — conv, conv dense, then
full, conv, conv, conv with 2 of 8 experts — under prompts of 20-80 in
chunks of 32): the flow of a run through the new driver, the comparison
that decides ``correct`` — sound, altered underneath, with the mechanism
changed, and under BOTH controls — that the benchmark's reference is the
program's plain reference, and that the real tree's files are whole."""

import json
import os
import time

import numpy as np
import pytest

from conftest import ROOT

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_conv")
CELL = "lfm2-serve-generate"


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
                                  "unbiased_router"])
def test_the_mechanism_changed_is_not_correct(monkeypatch, what):
    """A program that hands a prompt's next chunk zeros for the state
    the last one left, one whose tick reads its slots' state as zeros,
    and one that routes without the expert bias: each serves another
    model's tokens."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T
    from horovod_tpu.serving.cache import PagedSlotCache

    if what == "state_lost_between_chunks":
        real = PagedSlotCache.slot_state
        monkeypatch.setattr(PagedSlotCache, "slot_state",
                            lambda self, slot: jnp.zeros_like(
                                real(self, slot)))
    elif what == "state_lost_at_a_tick":
        real = T._conv_decode
        monkeypatch.setattr(
            T, "_conv_decode", lambda x, p, cfg, states, layer, active:
            real(x, p, cfg, jnp.zeros_like(states), layer, active))
    else:
        monkeypatch.setattr(T, "_routing", lambda p, cfg: cfg.moe_routing)
    assert _run()["correct"] is False


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from horovod_tpu.serving import engine as E

    real = E.InferenceEngine._emit

    def emit(self, slot, tok):
        return real(self, slot, (tok + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(E.InferenceEngine, "_emit", emit)
    assert _run()["correct"] is False


def test_both_controls_fail_the_toy_limit(capfd):
    _run(control=True)
    out = capfd.readouterr().out
    for mode in ("fp8", "zero_taps"):
        gap = float(out.split(f"CONTROL {mode} mean gap ")[1].split(" ")[0])
        assert gap > 1e-5, (mode, gap)


def test_the_parent_program_fails_cleanly_on_the_configuration(monkeypatch):
    """A program whose ``TransformerConfig`` has no conv fields fails in
    ``build_cfg``, with a ``TypeError``, before any weight is made."""
    from chipbench import harness
    from chipbench.drivers import serve_conv
    from horovod_tpu.models import transformer as T

    def old_config(**kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'conv_kernel'")

    monkeypatch.setattr(T, "TransformerConfig", old_config)
    with pytest.raises(TypeError, match="conv_kernel"):
        serve_conv.build_cfg(harness.load_cell(CELL, ROOT)["dims"])


def test_the_toy_tree_lists_the_real_metrics():
    """``test_control.py``'s rule for the four standing trees, for this
    one: the real ``BENCHMARK.json`` cut to the tree's cell."""
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
    holds the catalog's widths and the stated cut (depth alone); the
    traffic is the issue's."""
    import importlib

    from chipbench import harness

    cell = harness.load_cell(CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"] == "agent-generate"
    assert len(cell["why"]) <= 200
    for which in ("end_to_end", "per_layer"):
        for name, spec in harness.metric_specs(cell, which).items():
            importlib.import_module(f"chipbench.readers.{spec['reader']}")
    per_layer = set(harness.metric_specs(cell, "per_layer"))
    assert {"conv_update_ms_per_tick", "conv_proj_ms_per_tick",
            "conv_scan_ms_per_tick", "conv_state_bytes_per_slot",
            "moe_experts_roofline_pct", "paged_attn_roofline_pct.ide",
            "device_idle_pct.tput", "device_unscoped_pct.tput"} <= per_layer
    assert "serve_tokens_per_s" in harness.metric_specs(cell, "end_to_end")
    d = cell["dims"]
    assert (d["hidden_size"], d["intermediate_size"],
            d["moe_intermediate_size"], d["num_attention_heads"],
            d["num_key_value_heads"], d["vocab_size"]) == (
        2048, 11776, 1536, 32, 8, 65536)
    assert (d["num_experts"], d["num_experts_per_tok"], d["num_dense_layers"],
            d["conv_L_cache"], d["conv_bias"], d["norm_eps"],
            d["max_position_embeddings"]) == (64, 4, 2, 3, False, 1e-5,
                                              128000)
    assert (d["norm_topk_prob"], d["use_expert_bias"],
            d["routed_scaling_factor"]) == (True, True, 1)
    assert d["rope_parameters"] == {"rope_theta": 1000000,
                                    "rope_type": "default"}
    assert d["reduced"] == ["num_hidden_layers"]
    # the FIRST ten of the published forty, in their order
    assert d["num_hidden_layers"] == 10 == len(d["layer_types"])
    assert d["layer_types"] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2
    assert d["published"]["num_hidden_layers"] == 40
    t = cell["traffic_params"]
    assert (t["generator"], t["clients"], t["strata"]) == (
        "closed_loop", 64, 64)
    assert t["prompt"] == t["output"] == {
        "dist": "loguniform", "min": 1024, "max": 4096}
    assert t["stream"] is True
    from chipbench.drivers import serve_conv

    cfg = serve_conv.build_cfg(d)
    assert cfg.layer_pattern == ("conv", "conv", "full", "conv")
    assert cfg.layer_kinds == tuple(
        "conv" if k == "conv" else "full" for k in d["layer_types"])
    assert (cfg.head_dim, cfg.kv_pack, cfg.conv_taps, cfg.n_dense_layers,
            cfg.tie_embeddings) == (64, 2, 2, 2, True)
    assert cfg.moe_routing == {"score": "sigmoid", "norm_eps": 1e-6}
    e = d["engine"]
    assert e["n_slots"] == t["clients"] == 64 and not e["speculative"]
    assert e["n_pages"] * e["page_size"] == e["n_slots"] * e["max_len"]
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"]


def test_the_weights_weigh_what_the_configuration_says():
    """5.267 B parameters = 10.53 GB in bf16 (the issue's count): the
    tree's leaves, counted by shape."""
    from chipbench import harness, weights_conv

    d = harness.load_cell(CELL, ROOT)["dims"]
    assert weights_conv.param_count(d) == 5_267_090_176

    def count(kind, dense):
        return sum(int(np.prod(s)) for s, _ in
                   weights_conv.layer_shapes(d, kind, dense).values())

    # W_in, the kernel, W_out; two norms; the SwiGLU of 11 776
    assert count("conv", True) == (2048 * 6144 + 2048 * 3 + 2048 * 2048
                                   + 2 * 2048 + 3 * 2048 * 11776)
    assert abs(count("conv", False) - 620.9e6) < 1e5
    assert abs(count("full_attention", False) - 614.6e6) < 1e5


def test_costs_hand_worked():
    from chipbench import costs_conv as C

    d = {"hidden_size": 8, "num_attention_heads": 4,
         "num_key_value_heads": 2, "conv_L_cache": 3,
         "moe_intermediate_size": 3,
         "layer_types": ["conv", "full_attention", "conv", "conv",
                         "full_attention"]}
    # K and V of 2 heads of 2 in 2 B through the 2 attention layers
    assert C.kv_bytes_per_token(d) == 2 * 2 * 2 * 2 * 2 == 32
    # contexts 6 and 25; queries in and outputs out: 2 layers x 2 x 4
    # heads x 2 x 2 B = 64 B a slot
    assert C.paged_decode_bytes(d, [6, 25]) == 31 * 32 + 2 * 64
    assert C.conv_state_bytes_per_slot(d) == 3 * 2 * 8 * 2
    assert C.moe_expert_flops(d, 7) == 7 * 3 * 2 * 8 * 3
    assert C.moe_expert_bytes(d, 5, 7) == 5 * 3 * 8 * 3 * 2 + 7 * 2 * 8 * 2
    real = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "lfm2-24b-a2b-serve.json")))
    assert C.kv_bytes_per_token(real) == 4096
    assert C.conv_state_bytes_per_slot(real) == 65536


def test_the_new_counter_reads_or_reads_nothing():
    from chipbench import harness
    from chipbench.readers import stats_last

    args = harness.load_json("layer_metrics",
                             "conv_state_bytes_per_slot.json")["args"]
    assert stats_last.read(
        {"stats1": {"conv_state_bytes_per_slot": 65536}}, args) == 65536.0
    # the parent program has no such key: nothing to read, no error
    assert stats_last.read({"stats1": {"decode_ticks": 9}}, args) is None


def _toy_case():
    import jax.numpy as jnp

    from chipbench import harness, weights_conv

    d = harness.load_cell(CELL, TOY)["dims"]
    rng = np.random.default_rng(3)
    toks = rng.integers(0, d["vocab_size"], (3, 128)).astype(np.int32)
    return d, rng, toks, [40, 70, 100], [20, 50, 28], \
        weights_conv.make_params(5, d, jnp.float32)


@pytest.mark.parametrize("q_block", [16, 64])
def test_the_reference_is_the_same_whatever_divides_it(q_block):
    """Blocks of rows divide the reference's work in memory and time
    only, and what lies in a row beyond the sequence's own length
    reaches nothing (the length is a traced scalar: one executable)."""
    import jax.numpy as jnp

    from chipbench import reference_conv

    d, rng, toks, plens, served, _ = _toy_case()
    whole, s0, v0 = reference_conv.served_logits(
        5, d, jnp.float32, toks, plens, served, q_block=128)
    other = toks.copy()
    for i, (p, m) in enumerate(zip(plens, served)):
        other[i, p + m:] = rng.integers(0, d["vocab_size"], 128 - p - m)
    cut, s1, v1 = reference_conv.served_logits(
        5, d, jnp.float32, other, plens, served, q_block=q_block)
    assert (s0 == s1)[v0].all() and (v0 == v1).all()
    np.testing.assert_allclose(cut[v0], whole[v0], atol=2e-5, rtol=0)


@pytest.mark.parametrize("zero_taps", [False, True])
def test_the_benchmarks_reference_is_the_programs_plain_reference(
        zero_taps):
    """``chipbench/reference_conv.py`` (blocks at one width, a layer's
    weights at a time) against ``horovod_tpu/models/plain_reference.py``
    (``conv_forward``: one sequence whole) on the same seeded weights:
    both float32 at ``highest``, so they differ by the order of sums
    alone (2e-5 on logits of std ~1) — the model and the zeroed-taps
    control alike."""
    import jax.numpy as jnp

    from chipbench import reference_conv
    from horovod_tpu.models import plain_reference as R

    d, _, toks, plens, served, params = _toy_case()
    got, _, valid = reference_conv.served_logits(
        5, d, jnp.float32, toks, plens, served, zero_taps=zero_taps)
    for i, (p, m) in enumerate(zip(plens, served)):
        want = np.asarray(R.conv_forward(
            params, jnp.asarray(toks[i, :p + m]), d, zero_taps=zero_taps))
        np.testing.assert_allclose(got[i, :m], want[p - 1:p - 1 + m],
                                   atol=2e-5, rtol=0)
    assert valid.sum() == sum(served)


def test_the_reference_in_bfloat16_fails_the_tolerance():
    """The 2e-5 that holds the two references together is tight enough
    for the precision: with every matmul's operands rounded to bfloat16
    the benchmark's reference leaves its own float32 logits by a
    hundred times that."""
    import jax.numpy as jnp

    from chipbench import reference_conv

    d, _, toks, plens, served, _ = _toy_case()
    f32, _, valid = reference_conv.served_logits(
        5, d, jnp.float32, toks, plens, served)
    low, _, _ = reference_conv.served_logits(
        5, d, jnp.float32, toks, plens, served, mode="bf16")
    assert np.abs(low - f32)[valid].max() > 100 * 2e-5
