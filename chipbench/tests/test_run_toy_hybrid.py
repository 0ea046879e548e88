"""The ``serve_hybrid`` kind end to end at a toy size on the CPU (its own
toy tree, ``toy_hybrid/``: the cell's name and metric list are the real
benchmark's, the model three layers of hidden 64 — 10 query / 2 KV heads
of 16 beside a mixer of 4 heads of 8, state 16, 2 groups, 4 taps over 96
columns, every multiplier set — under prompts of 20-80 in chunks of 32):
the flow of a run through the new driver, the comparison that decides
``correct`` — sound, altered underneath, with the mechanism changed, and
under BOTH controls — that the benchmark's reference is the program's
plain reference, and that the real tree's files are whole."""

import json
import os
import time

import numpy as np
import pytest

from conftest import ROOT

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_hybrid")
CELL = "falconh1-serve-longform"


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
                                  "taps_lost_between_chunks",
                                  "state_lost_at_a_tick",
                                  "a_multiplier_left_out"])
def test_the_mechanism_changed_is_not_correct(monkeypatch, what):
    """A program that hands a prompt's next chunk zeros for the matrix
    state (or the taps) the last one left, one whose tick reads its
    slots' matrix states as zeros, and one that leaves the key's
    multiplier out: each serves another model's tokens."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T
    from horovod_tpu.serving.cache import PagedSlotCache

    if what.endswith("between_chunks"):
        real = PagedSlotCache.slot_state
        lost = "ssm" if what.startswith("state") else "conv"

        def slot_state(self, slot, name="conv"):
            got = real(self, slot, name)
            return jnp.zeros_like(got) if name == lost else got

        monkeypatch.setattr(PagedSlotCache, "slot_state", slot_state)
    elif what == "state_lost_at_a_tick":
        real = T._ssm_decode
        monkeypatch.setattr(
            T, "_ssm_decode",
            lambda n, p, cfg, taps, states, layer, active, kernel:
            real(n, p, cfg, taps, jnp.zeros_like(states), layer, active,
                 kernel))
    else:
        import dataclasses

        from chipbench.drivers import serve_hybrid

        real = serve_hybrid.build_cfg
        monkeypatch.setattr(
            serve_hybrid, "build_cfg",
            lambda dims: dataclasses.replace(real(dims), key_multiplier=1.0))
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
    for mode in ("fp8", "lost_state"):
        gap = float(out.split(f"CONTROL {mode} mean gap ")[1].split(" ")[0])
        assert gap > 1e-5, (mode, gap)


def test_the_parent_program_fails_cleanly_on_the_configuration(monkeypatch):
    """A program whose ``TransformerConfig`` has no state-space fields
    fails in ``build_cfg``, with a ``TypeError``, before any weight is
    made."""
    from chipbench import harness
    from chipbench.drivers import serve_hybrid
    from horovod_tpu.models import transformer as T

    def old_config(**kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'ssm_heads'")

    monkeypatch.setattr(T, "TransformerConfig", old_config)
    with pytest.raises(TypeError, match="ssm_heads"):
        serve_hybrid.build_cfg(harness.load_cell(CELL, ROOT)["dims"])


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
    holds EVERY number of the catalog's config but the stated cut (depth
    and vocabulary rows); the traffic is the issue's."""
    import importlib

    from chipbench import harness

    cell = harness.load_cell(CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"] == "assistant-longform"
    assert len(cell["why"]) <= 200
    for which in ("end_to_end", "per_layer"):
        for name, spec in harness.metric_specs(cell, which).items():
            importlib.import_module(f"chipbench.readers.{spec['reader']}")
    per_layer = set(harness.metric_specs(cell, "per_layer"))
    assert {"ssm_update_ms_per_tick", "ssm_update_roofline_pct",
            "ssm_scan_ms_per_tick", "ssm_scan_roofline_pct",
            "ssm_proj_ms_per_tick", "ssm_conv_ms_per_tick",
            "ssm_state_bytes_per_slot", "conv_state_bytes_per_slot",
            "paged_attn_roofline_pct.ide", "dense_mlp_ms_per_tick",
            "device_idle_pct.tput", "device_unscoped_pct.tput"} <= per_layer
    assert not [n for n in per_layer if n.startswith(("moe_", "conv_"))
                and n != "conv_state_bytes_per_slot"]
    assert "serve_tokens_per_s" in harness.metric_specs(cell, "end_to_end")
    d = cell["dims"]
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_key_value_heads": 4, "num_logits_to_keep": 1,
        "projectors_bias": False, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False}
    assert {k: d[k] for k in published} == published
    assert d["reduced"] == ["num_hidden_layers", "vocab_size"]
    # nine of the 72 identical layers; an eighth of the vocabulary's rows
    assert (d["num_hidden_layers"], d["vocab_size"]) == (9, 32640)
    assert d["published"]["num_hidden_layers"] == 72 == 8 * 9
    assert d["published"]["vocab_size"] == 261120 == 8 * 32640
    assert "EIGHT" in d["deployment"]
    t = cell["traffic_params"]
    assert (t["generator"], t["clients"], t["strata"]) == (
        "closed_loop", 64, 64)
    assert t["prompt"] == {"dist": "loguniform", "min": 256, "max": 1024}
    assert t["output"] == {"dist": "loguniform", "min": 512, "max": 2048}
    assert t["stream"] is True
    from chipbench.drivers import serve_hybrid

    cfg = serve_hybrid.build_cfg(d)
    assert cfg.layer_kinds == ("hybrid",) * 9
    assert (cfg.head_dim, cfg.n_heads // cfg.kv_heads, cfg.conv_taps,
            cfg.ssm_inner, cfg.ssm_conv_width, cfg.ssm_chunk) == (
        128, 5, 3, 4096, 5120, 128)
    assert (cfg.layers_with("k"), cfg.layers_with("ssm"),
            cfg.layers_with("conv")) == (9, 9, 9)
    e = d["engine"]
    assert e["n_slots"] == t["clients"] == 64 and not e["speculative"]
    assert (e["max_len"], e["page_size"], e["n_pages"],
            e["prefill_chunk_tokens"]) == (4096, 16, 8192, 512)
    # the longest standing context and every request fit a slot
    assert t["prompt"]["max"] + t["output"]["max"] <= e["max_len"]


def test_the_weights_weigh_what_the_configuration_says():
    """4.205 B parameters = 8.41 GB in bf16 (the issue's count): the
    tree's leaves, counted by shape."""
    from chipbench import harness, weights_hybrid

    d = harness.load_cell(CELL, ROOT)["dims"]
    assert weights_hybrid.param_count(d) == 4_205_319_008
    shapes = weights_hybrid.layer_shapes(d)

    def count(*names):
        return sum(int(np.prod(shapes[n][0])) for n in names)

    assert count("wq", "wk", "wv", "wo") == 31_457_280
    assert count(*(n for n in shapes if n.startswith("ssm_"))) == 68_351_072
    assert count("w_gate", "w_up", "w_down") == 330_301_440
    assert count(*shapes) == 430_120_032
    assert weights_hybrid.in_width(d) == 9248
    mup = weights_hybrid.mup_vector(d)
    assert mup.shape == (9248,) and mup[4096 + 4096] == np.float32(
        0.1767766952966369) and mup[-1] == mup[0]


def test_costs_hand_worked():
    from chipbench import costs_hybrid as C

    d = {"num_hidden_layers": 3, "num_attention_heads": 10,
         "num_key_value_heads": 2, "head_dim": 4, "mamba_d_ssm": 12,
         "mamba_n_heads": 6, "mamba_d_head": 2, "mamba_d_state": 5,
         "mamba_n_groups": 3, "mamba_d_conv": 4, "mamba_chunk_size": 8}
    # K and V of 2 heads of 4 in 2 B through the 3 layers
    assert C.kv_bytes_per_token(d) == 3 * 2 * 2 * 4 * 2 == 96
    # contexts 6 and 25; queries in and outputs out: 3 layers x 2 x 10
    # heads x 4 x 2 B = 480 B a slot
    assert C.paged_decode_bytes(d, [6, 25]) == 31 * 96 + 2 * 480
    assert C.ssm_state_bytes_per_layer(d) == 6 * 2 * 5 * 2 == 120
    assert C.ssm_state_bytes_per_slot(d) == 360
    # three taps of 12 + 2 x 3 x 5 = 42 columns, three layers
    assert C.conv_state_bytes_per_slot(d) == 3 * 3 * 42 * 2
    # 7 (slot, layer) pairs, each state read once and written once
    assert C.ssm_update_bytes(d, 7) == 7 * 2 * 120
    # scores a group 2 x 8 x 5; a head 2 x 8 x 2 + 2 x 2 x 2 x 5
    assert C.ssm_scan_flops_per_token(d) == 3 * 80 + 6 * (32 + 40) == 672
    assert C.ssm_scan_flops(d, 11) == 11 * 672
    real = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "falcon-h1-34b-serve.json")))
    assert C.kv_bytes_per_token(real) == 18432
    assert C.ssm_state_bytes_per_slot(real) == 18874368
    assert C.conv_state_bytes_per_slot(real) == 276480
    assert C.ssm_scan_flops_per_token(real) == 5373952
    # a full tick's update: 64 slots x 9 layers x 2 x 2 MiB = 2.42 GB
    assert C.ssm_update_bytes(real, 64 * 9) == 2415919104


def test_the_new_counters_read_or_read_nothing():
    from chipbench import harness
    from chipbench.readers import stats_last, trace_scope_roofline

    args = harness.load_json("layer_metrics",
                             "ssm_state_bytes_per_slot.json")["args"]
    assert stats_last.read(
        {"stats1": {"ssm_state_bytes_per_slot": 18874368}}, args) == 18874368.0
    # the parent program has no such key: nothing to read, no error
    assert stats_last.read({"stats1": {"decode_ticks": 9}}, args) is None
    # ... and no trace, nothing of a roofline
    for name in ("ssm_update_roofline_pct", "ssm_scan_roofline_pct"):
        args = harness.load_json("layer_metrics", name + ".json")["args"]
        assert trace_scope_roofline.read({}, args) is None


def _toy_case():
    import jax.numpy as jnp

    from chipbench import harness, weights_hybrid

    d = harness.load_cell(CELL, TOY)["dims"]
    rng = np.random.default_rng(3)
    toks = rng.integers(0, d["vocab_size"], (3, 128)).astype(np.int32)
    return d, rng, toks, [40, 70, 100], [20, 50, 28], \
        weights_hybrid.make_params(5, d, jnp.float32)


@pytest.mark.parametrize("q_block", [16, 64])
def test_the_reference_is_the_same_whatever_divides_it(q_block):
    """Blocks of rows divide the reference's work in memory and time
    only, and what lies in a row beyond the sequence's own length
    reaches nothing (the length is a traced scalar: one executable)."""
    import jax.numpy as jnp

    from chipbench import reference_hybrid

    d, rng, toks, plens, served, _ = _toy_case()
    whole, s0, v0 = reference_hybrid.served_logits(
        5, d, jnp.float32, toks, plens, served, q_block=128)
    other = toks.copy()
    for i, (p, m) in enumerate(zip(plens, served)):
        other[i, p + m:] = rng.integers(0, d["vocab_size"], 128 - p - m)
    cut, s1, v1 = reference_hybrid.served_logits(
        5, d, jnp.float32, other, plens, served, q_block=q_block)
    assert (s0 == s1)[v0].all() and (v0 == v1).all()
    np.testing.assert_allclose(cut[v0], whole[v0], atol=2e-5, rtol=0)


@pytest.mark.parametrize("lose_state", [False, True])
def test_the_benchmarks_reference_is_the_programs_plain_reference(
        lose_state):
    """``chipbench/reference_hybrid.py`` (blocks at one width, a layer's
    weights at a time) against ``horovod_tpu/models/plain_reference.py``
    (``hybrid_forward``: one sequence whole) on the same seeded weights:
    both float32 at ``highest``, both stepping the recurrence token by
    token, so they differ by the order of sums alone (2e-5 on logits of
    std ~1) — the model and the lost-state control alike."""
    import jax.numpy as jnp

    from chipbench import reference_hybrid
    from horovod_tpu.models import plain_reference as R

    d, _, toks, plens, served, params = _toy_case()
    got, _, valid = reference_hybrid.served_logits(
        5, d, jnp.float32, toks, plens, served, lose_state=lose_state)
    for i, (p, m) in enumerate(zip(plens, served)):
        reset = jnp.asarray(reference_hybrid.lost_state(
            p + m, p, d["engine"]["prefill_chunk_tokens"])) \
            if lose_state else None
        want = np.asarray(R.hybrid_forward(
            params, jnp.asarray(toks[i, :p + m]), d, reset))
        np.testing.assert_allclose(got[i, :m], want[p - 1:p - 1 + m],
                                   atol=2e-5, rtol=0)
    assert valid.sum() == sum(served)


def test_the_lost_state_control_loses_it_where_a_program_would():
    from chipbench import reference_hybrid

    lost = reference_hybrid.lost_state(12, 7, 3)
    assert lost.tolist() == [False, False, False, True, False, False, True,
                             True, True, True, True, True]


def test_the_reference_in_bfloat16_fails_the_tolerance():
    """The 2e-5 that holds the two references together is tight enough
    for the precision: with every matmul's operands rounded to bfloat16
    the benchmark's reference leaves its own float32 logits by a
    hundred times that."""
    import jax.numpy as jnp

    from chipbench import reference_hybrid

    d, _, toks, plens, served, _ = _toy_case()
    f32, _, valid = reference_hybrid.served_logits(
        5, d, jnp.float32, toks, plens, served)
    low, _, _ = reference_hybrid.served_logits(
        5, d, jnp.float32, toks, plens, served, mode="bf16")
    assert np.abs(low - f32)[valid].max() > 100 * 2e-5
