"""PR 21 bring-up guards: the chip smoke's phases at toy size, and the
rules that keep a run from quietly leaving the device.

Everything here runs on CPU in seconds.  ``chip_smoke.py`` itself only
passes on a TPU; its phases are plain functions of a ``SmokeConfig``, so
the same code is exercised here with the library interpreting the same
kernel bodies.
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

import horovod_tpu
from horovod_tpu import serving
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import _pallas_util
from horovod_tpu.ops import paged_attention as PA
from horovod_tpu.runner import chips

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _spawn(code_or_script, env_overrides, *, script=False):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO
    env.update(env_overrides)
    argv = ([sys.executable, code_or_script] if script
            else [sys.executable, "-c", code_or_script])
    return subprocess.Popen(argv, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def test_smoke_phases_toy_size_on_cpu():
    """(a) every phase of the smoke — kernels vs references, the DP
    trainer over the virtual devices, the HTTP server, tp serving — at
    toy size, kernels interpreted (``expect_compiled=False`` is the only
    difference from the chip run; the script has no such mode)."""
    smoke = chip_smoke.SmokeConfig(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=128, seq=64, dtype="float32", batch_per_chip=1,
        train_steps=3, learning_rate=1e-2, n_slots=2, serve_max_len=48,
        serve_n_pages=6,
        prompt_lens=(3, 17), max_new_tokens=4, logit_tol=1e-3,
        expect_compiled=False)
    report = chip_smoke.run(smoke, tp=2)
    assert report["train"]["losses"][-1] < report["train"]["losses"][0]
    assert report["train"]["chips"] == horovod_tpu.size() > 1
    for key in ("serve", "serve_tp"):
        assert report[key]["paged_kernel_engaged"] is True
        assert report[key]["oracle_positions_checked"] > 0
    assert "tp=2" in report["serve_tp"]["mesh"]


def test_smoke_compiled_proof_rejects_an_interpreted_run():
    """The chip run's proof is not vacuous: on this CPU backend the same
    check (expect_compiled=True) refuses an executable without the
    Mosaic custom call."""
    smoke = chip_smoke.SmokeConfig()
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreted"):
        chip_smoke._require_compiled(smoke, "HloModule m", 1, "probe")


def test_no_chip_means_nonzero_exit_and_no_result():
    """(b) with the platform pinned to a TPU this sandbox does not have,
    ``hvd.init()``, ``chip_smoke.py`` and ``bench.py`` each exit non-zero
    within seconds, print no result line, and never continue on CPU."""
    tpu = {"JAX_PLATFORMS": "tpu"}
    procs = {
        "init": _spawn("import horovod_tpu as hvd; hvd.init(); "
                       "print('RESULT', hvd.size())", tpu),
        "smoke": _spawn("chip_smoke.py", tpu, script=True),
        "bench": _spawn("bench.py", tpu, script=True),
        # no platform pinned: JAX finds only the CPU — still no result
        "smoke_cpu": _spawn("chip_smoke.py", {"JAX_PLATFORMS": "cpu"},
                            script=True),
        "bench_cpu": _spawn("bench.py", {"JAX_PLATFORMS": "cpu"},
                            script=True),
    }
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        assert proc.returncode != 0, (name, out, err)
        assert "falling back" not in (out + err).lower(), (name, err)
        assert "RESULT" not in out and '"ok"' not in out \
            and '"metric"' not in out, (name, out)


def test_compile_cache_placed_from_outside_or_at_one_fixed_path(tmp_path):
    """(c) with JAX_COMPILATION_CACHE_DIR set the program sets no
    directory in code; without it, two fresh processes agree on one
    path inside the checkout."""
    code = ("import jax, horovod_tpu as hvd; "
            "before = jax.config.jax_compilation_cache_dir; "
            "print(hvd.place_compile_cache()); "
            "print(before); print(jax.config.jax_compilation_cache_dir)")
    outside = str(tmp_path / "placed")
    procs = [_spawn(code, {"JAX_PLATFORMS": "cpu"}),
             _spawn(code, {"JAX_PLATFORMS": "cpu"}),
             _spawn(code, {"JAX_PLATFORMS": "cpu",
                           "JAX_COMPILATION_CACHE_DIR": outside})]
    outs = []
    for proc in procs:
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err
        outs.append(out.split())
    inside = os.path.join(REPO, ".jax_cache")
    assert outs[0] == outs[1] == [inside, "None", inside]
    # placed from outside: JAX read the variable itself, and the call
    # changed nothing
    assert outs[2] == [outside, outside, outside]


@pytest.fixture()
def toy_model():
    cfg = T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_seq=48, dtype="float32", n_kv_heads=2)
    import jax

    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def test_kernel_gate_is_the_compilers_rule():
    assert PA.kernel_supported("bfloat16", 16, 128)
    assert PA.kernel_supported("int8", 32, 128)
    assert PA.kernel_supported("float32", 8, 256)
    # the engine default page_size=16 with int8 storage: int8 tiles are
    # 32 sublanes deep
    assert not PA.kernel_supported("int8", 16, 128)
    assert not PA.kernel_supported("bfloat16", 16, 64)
    assert not PA.kernel_supported("bfloat16", 8, 128)


def test_engine_kernel_engagement_where_it_would_be_compiled(
        toy_model, monkeypatch):
    """(d) where the kernel would be COMPILED, engagement follows the
    compiler's gate on the real pool layout: explicit True on a rejected
    layout is a typed error at construction, auto takes the unfused tick
    and /stats says what ran."""
    params, cfg = toy_model  # head_dim 8: nothing the TPU can tile
    monkeypatch.setattr(_pallas_util, "use_interpret", lambda: False)
    with pytest.raises(serving.UnsupportedPagedLayoutError,
                       match="head_dim=8"):
        serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, paged_kernel=True))
    engine = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(n_slots=2))  # auto
    assert engine.stats()["paged_kernel_engaged"] is False
    # ... and the unfused tick it resolved to serves requests
    fut = engine.submit([1, 2, 3], max_new_tokens=3)
    while not fut.done():
        engine.step()
    want = np.asarray(T.greedy_decode(
        params, np.asarray([[1, 2, 3]], np.int32), 3, cfg))[0].tolist()
    assert fut.result() == want
    assert engine.stats()["paged_kernel_engaged"] is False


def test_pallas_interprets_on_cpu_only(monkeypatch):
    import jax

    assert _pallas_util.use_interpret() is True  # this suite runs on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _pallas_util.use_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not supported"):
        _pallas_util.use_interpret()


class TestChipEnv:
    """(e) which chips a child owns, as a pure function."""

    def test_ranks_of_one_job_get_one_chip_each(self):
        envs = [chips.chip_env(i, 4, one_job=True) for i in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        # every rank names the same four addresses, and listens on its own
        addrs = {e["TPU_PROCESS_ADDRESSES"] for e in envs}
        assert len(addrs) == 1 and len(addrs.pop().split(",")) == 4
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
        assert envs == [chips.chip_env(i, 4, one_job=True)
                        for i in range(4)]  # pure

    def test_replicas_are_slices_of_their_own(self):
        a = chips.chip_env(0, 2, chips_per_proc=2, one_job=False)
        b = chips.chip_env(1, 2, chips_per_proc=2, one_job=False)
        assert (a["TPU_VISIBLE_CHIPS"], b["TPU_VISIBLE_CHIPS"]) == \
            ("0,1", "2,3")
        for e in (a, b):
            assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
            assert e["CLOUD_TPU_TASK_ID"] == "0"
            assert e["TPU_PROCESS_ADDRESSES"] == \
                f"localhost:{e['TPU_PROCESS_PORT']}"
        assert a["TPU_PROCESS_PORT"] != b["TPU_PROCESS_PORT"]
        one = chips.chip_env(3, 4, one_job=False)  # tp=1 replicas too
        assert one["TPU_VISIBLE_CHIPS"] == "3"

    def test_refuses_what_it_cannot_partition(self, monkeypatch):
        with pytest.raises(chips.ChipPartitionError):
            chips.chip_env(0, 3, one_job=True)
        with pytest.raises(chips.ChipPartitionError):
            chips.chip_env(0, 2, chips_per_proc=2, one_job=True)
        # a four-chip host: -np 8 is refused before anything is spawned,
        # naming the single-process shape
        monkeypatch.setattr(chips, "local_tpu_chips", lambda: 4)
        with pytest.raises(chips.ChipPartitionError, match="-np 1"):
            chips.local_rank_envs(8, {})
        assert [e["TPU_VISIBLE_CHIPS"]
                for e in chips.local_rank_envs(4, {})] == ["0", "1", "2", "3"]
        # nothing to partition: one rank owns every chip; a CPU-pinned
        # job opens none
        assert chips.local_rank_envs(1, {}) == [{}]
        assert chips.local_rank_envs(4, {"JAX_PLATFORMS": "cpu"}) == [{}] * 4
        assert chips.usable_chips({"JAX_PLATFORMS": "tpu,cpu"}) == 4

    def test_launcher_hands_each_local_rank_its_chip(self, monkeypatch):
        """The launcher's spawn path: four local ranks on a (pretend)
        four-chip host each start with a different chip."""
        from horovod_tpu.runner import launch
        from horovod_tpu.runner.hosts import HostSpec, allocate

        monkeypatch.setattr(chips, "local_tpu_chips", lambda: 4)
        seen = {}

        def fake_exec(cmd, env, **kw):
            seen[env["HOROVOD_RANK"]] = env.get("TPU_VISIBLE_CHIPS")
            return 0

        threads, codes = launch.spawn_ranks(
            ["true"], allocate([HostSpec("localhost", 0)] * 4), {},
            "127.0.0.1", 1, _executor=fake_exec)
        for t in threads:
            t.join(10)
        assert codes == [0, 0, 0, 0]
        assert seen == {"0": "0", "1": "1", "2": "2", "3": "3"}
        with pytest.raises(chips.ChipPartitionError):
            launch.spawn_ranks(
                ["true"], allocate([HostSpec("localhost", 0)] * 8), {},
                "127.0.0.1", 1, _executor=fake_exec)


_HLO = """HloModule jit__tick, is_scheduled=true

%fused_computation.5 (param_0.1: bf16[2,65,4,16,128], param_1.2: s32[4,2]) -> bf16[2,65,4,16,128] {
  %param_0.1 = bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.1 = bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)} scatter(%param_0.1, %param_1.2), to_apply=%region
}

%body (arg: (s32[], bf16[2,65,4,16,128], /*index=2*/f32[4,8])) -> (s32[], bf16[2,65,4,16,128], f32[4,8]) {
  %gte = bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %fusion.9 = bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%gte, %idx), kind=kCustom, calls=%fused_computation.5, metadata={op_name="jit(_tick)/kv_write/scatter"}, backend_config={"aliasing_operands":{"lists":[{"indices":["0","2"]}]}}
  %copy.110 = bf16[65,4,16,128]{3,1,2,0:T(8,128)(2,1)} copy(%slice), metadata={op_name="jit(_tick)/layer_scan/while/body/kv_write/scatter"}
  %small = f32[4,8]{1,0:T(4,128)} fusion(%x), kind=kLoop, calls=%fused_computation.6, backend_config={"aliasing_operands":{"lists":[]}}
  ROOT %tuple.1 = (s32[], bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)}, f32[4,8]{1,0}) tuple(%i, %fusion.9, %small)
}

ENTRY %main (p: bf16[2,65,4,16,128]) -> bf16[2,65,4,16,128] {
  %p = bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.133 = bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%p)
  %while.1 = (s32[], bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)}, f32[4,8]{1,0}) while(%t), condition=%cond, body=%body
  ROOT %out = bf16[2,65,4,16,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=1
}
"""


def test_pool_sized_results_reads_a_compiled_program():
    """The compiled-program check of the served tick and landing: the
    pool passing through (parameter, while, tuple, get-tuple-element)
    and a write the compiler aliased to its operand are the pool's own;
    a copy of the pool, or of one layer in another layout, is an
    offender; what sits inside a fused computation has no buffer."""
    layer = 65 * 4 * 16 * 128
    offenders, largest = chip_smoke.pool_sized_results(_HLO, layer)
    assert [(o[1], o[2]) for o in offenders] == [
        ("copy", "copy.133"), ("copy", "copy.110")]
    assert offenders[1][3].endswith("kv_write/scatter")
    assert [x[2] for x in largest] == ["fusion.9", "copy.133", "copy.110",
                                       "small"]
    smoke = chip_smoke.SmokeConfig()
    with pytest.raises(chip_smoke.SmokeFailure, match="size of a layer"):
        chip_smoke._require_pool_in_place(smoke, _HLO, layer, "probe")
    chip_smoke._require_pool_in_place(
        smoke, _HLO.replace("copy(%p)", "bitcast(%p)").replace(
            "%copy.110 = bf16[65,4,16,128]", "%copy.110 = bf16[1,4,16,128]"),
        layer, "probe")


_HLO_PREFETCH = """HloModule jit__tick, is_scheduled=true

ENTRY %main (w: bf16[3,32,128,4096]) -> bf16[3,32,128,4096] {
  %w = bf16[3,32,128,4096]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %slice-start.1 = ((bf16[3,32,128,4096]{3,2,1,0:T(8,128)(2,1)}), bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%w), slice={[0:1], [0:32], [0:128], [0:4096]}
  %slice-done.1 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.1)
  ROOT %custom-call.7 = bf16[3,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(%slice-done.1, %slice-done.1, %slice-done.1), custom_call_target="ConcatBitcast", backend_config={"aliasing_operands":{"lists":[]}}
}
"""


@pytest.mark.parametrize("sliced,offends", [(1, False), (2, True)])
def test_pool_sized_results_reads_an_asynchronous_slice_by_its_result(
        sliced, offends):
    """A weight stack prefetched a layer at a time (the sala tick since
    PR 48: the fast memory the gathered rows held went to ``wo``): an
    asynchronous slice's type restates its OPERAND — the whole stack —
    before its result, and the slices' results are seen as one array
    through a ``ConcatBitcast``; neither is a result of the stack's
    size.  A slice whose own result is that large still is."""
    text = _HLO_PREFETCH.replace("bf16[1,32", f"bf16[{sliced},32")
    offenders, largest = chip_smoke.pool_sized_results(
        text, 2 * 32 * 128 * 4096)
    assert [o[2] for o in offenders] == (
        ["slice-start.1", "slice-done.1"] if offends else [])
    assert largest[0][:3] == (sliced * 32 * 128 * 4096, "slice-start",
                              "slice-start.1")


_HLO_PICK = """HloModule jit__tick, is_scheduled=true

%compare (a: f32[], b: f32[]) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

%region_6.8 (div.0: (f32[32,512])) -> (f32[32,512]) {
  ROOT %div.0 = (f32[32,512]{1,0:T(8,128)}) parameter(0)
}

%helper (x: f32[32,512]) -> f32[32,512] {
  %sort.9 = (f32[32,512]{1,0:T(8,128)S(1)}, s32[32,512]{1,0:T(8,128)}) sort(%x, %iota.12), dimensions={1}, is_stable=true, to_apply=%compare
  ROOT %g = f32[32,512]{1,0:T(8,128)} get-tuple-element(%sort.9), index=0
}

%region_7.15 (arg_tuple.2: (f32[32,512], s32[32])) -> (f32[32,512]) {
  %sort.6 = (f32[32,512]{1,0:T(8,128)S(1)}, s32[32,512]{1,0:T(8,128)}) sort(%copy-done, %iota.11), dimensions={1}, is_stable=true, to_apply=%compare
  %call.1 = f32[32,512]{1,0:T(8,128)} call(%y), to_apply=%helper
  ROOT %t = (f32[32,512]{1,0:T(8,128)}) tuple(%call.1)
}

ENTRY %main (p: f32[32,512]) -> s32[32] {
  %p = f32[32,512]{1,0:T(8,128)} parameter(0)
  %conditional = (f32[32,512]{1,0:T(8,128)}) conditional(%pred.5, %tuple.14, %tuple.15), branch_computations={%region_6.8, %region_7.15}, metadata={op_name="jit(_tick)/sample/cond"}
  %sort.2 = (f32[32,64]{1,0:T(8,128)}, s32[32,64]{1,0:T(8,128)}) sort(%scores, %iota.2), dimensions={1}, to_apply=%compare, metadata={op_name="jit(_tick)/hvd_moe_route/sort"}
  ROOT %out = s32[32]{0:T(128)} fusion(%conditional), kind=kLoop, calls=%fused_computation.45
}
"""


def test_sorts_by_conditional_reads_a_compiled_program():
    """The compiled-program check of the pick's gates: a sort in a
    conditional's branch, or in a computation a branch calls, runs only
    when the branch is taken; one in the entry computation runs every
    tick; with the conditional flattened away every sort does."""
    assert chip_smoke.sorts_by_conditional(_HLO_PICK) == (
        1, ["sort.6", "sort.9"], ["sort.2"])
    smoke = chip_smoke.SmokeConfig()
    with pytest.raises(chip_smoke.SmokeFailure, match="not gated"):
        chip_smoke._require_sorts_gated(smoke, _HLO_PICK, "probe")
    gated = "\n".join(line for line in _HLO_PICK.splitlines()
                      if "%sort.2" not in line)
    chip_smoke._require_sorts_gated(smoke, gated, "probe")
    flat = gated.replace(" conditional(", " select(")
    assert chip_smoke.sorts_by_conditional(flat) == (
        0, [], ["sort.6", "sort.9"])
    with pytest.raises(chip_smoke.SmokeFailure, match="not gated"):
        chip_smoke._require_sorts_gated(smoke, flat, "probe")


_HLO_TRAIN = """HloModule jit__step, is_scheduled=true

%fused_computation.29 (a: bf16[24576,4096], b: bf16[24576,32768]) -> (f32[4096,32768], f32[4096,32768], f32[4096,32768]) {
  %convolution.7 = f32[4096,32768]{1,0:T(8,128)} convolution(%a, %b), dim_labels=fb_io->bf
  ROOT %tuple.3 = (f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}) tuple(%sub.1, %add.2, %add.3)
}

%fused_computation.52 (a: bf16[6,4096,4096]) -> (f32[6,4096], bf16[6,4096,4096], bf16[6,4096,4096]) {
  %convolution.9 = f32[6,4096,4096]{2,1,0:T(8,128)} convolution(%a, %w), dim_labels=b0f_0io->b0f
  ROOT %tuple.4 = (f32[6,4096]{1,0:T(8,128)}, bf16[6,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[6,4096,4096]{2,1,0:T(8,128)(2,1)}) tuple(%r, %c, %d)
}

%fused_computation.60 (g: f32[4096,32768]) -> (f32[4096,32768], f32[4096,32768], f32[4096,32768]) {
  ROOT %tuple.5 = (f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}) tuple(%p, %m, %n)
}

ENTRY %main (p: f32[4096,32768]) -> f32[4096,32768] {
  %fusion.52 = (f32[6,4096]{1,0:T(8,128)}, bf16[6,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[6,4096,4096]{2,1,0:T(8,128)(2,1)}) fusion(%x), kind=kOutput, calls=%fused_computation.52
  %fusion.29 = (f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}) fusion(%h, %dlogits), kind=kOutput, calls=%fused_computation.29
  %multiply_add_fusion = (f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)}) fusion(%g), kind=kLoop, calls=%fused_computation.60
}
"""


def test_products_carrying_an_update_reads_a_compiled_program():
    """The compiled-program check of the train step: a fusion that holds
    a matmul and writes a parameter's worth of float32 three times is a
    weight gradient's product with AdamW in its epilogue; an activation
    written twice is not, nor is an update with no product."""
    import jax
    import jax.numpy as jnp

    params = {"head": jax.ShapeDtypeStruct((4096, 32768), jnp.float32),
              "norm": jax.ShapeDtypeStruct((4096,), jnp.float32)}
    assert sorted(chip_smoke.product_fusions(_HLO_TRAIN)) == [
        "fusion.29", "fusion.52"]
    assert list(chip_smoke.products_carrying_an_update(
        _HLO_TRAIN, params)) == ["fusion.29"]
    alone = _HLO_TRAIN.replace(
        "ROOT %tuple.3 = (f32[4096,32768]{1,0:T(8,128)}, "
        "f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)})",
        "ROOT %tuple.3 = (f32[4096,32768]{1,0:T(8,128)})").replace(
        "%fusion.29 = (f32[4096,32768]{1,0:T(8,128)}, "
        "f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]{1,0:T(8,128)})",
        "%fusion.29 = f32[4096,32768]{1,0:T(8,128)}")
    assert chip_smoke.products_carrying_an_update(alone, params) == {}


_HLO_PACKED = """HloModule jit__step, is_scheduled=true

%fused_computation.1 (g: f32[58720256]) -> (f32[58720256], f32[58720256]) {
  ROOT %tuple.1 = (f32[58720256]{0:T(1024)}, f32[58720256]{0:T(1024)}) tuple(%m, %n)
}

ENTRY %main (p: f32[1,4096,14336]) -> f32[1,4096,14336] {
  %copy.243 = f32[512,112,8,128]{3,1,2,0:T(8,128)} copy(%fusion.87)
  %copy.185 = f32[6,32,4096,128]{2,3,1,0:T(8,128)} copy(%dq), metadata={op_name="jit(_step)/shard_map/attn_qkv/convert_element_type"}
  %psum.82 = f32[58720256]{0:T(1024)} all-reduce(%bitcast.9), metadata={op_name="jit(_step)/shard_map/grad_allreduce/psum"}
  %broadcast_multiply_fusion.2 = (f32[58720256]{0:T(1024)}, f32[58720256]{0:T(1024)}) fusion(%psum.82), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/shard_map/opt_update/mul"}
  %slice_multiply_fusion = (f32[4096]{0:T(1024)}, f32[4096]{0:T(1024)}) fusion(%psum.9), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/shard_map/opt_update/mul"}
  %mul.1406 = f32[1,4096,14336]{2,1,0:T(8,128)} reshape(%get-tuple-element.1), metadata={op_name="jit(_step)/shard_map/opt_update/mul"}
  %mul.1409 = f32[1,4096,14336]{2,1,0:T(8,128)} reshape(%get-tuple-element.2), metadata={op_name="jit(_step)/shard_map/opt_update/mul"}
  %copy.244 = f32[512,32,8,128]{3,1,2,0:T(8,128)} copy(%fusion.91)
  %multiply_add_fusion.6 = (f32[1,4096,14336]{2,1,0:T(8,128)}, f32[1,4096,14336]{2,1,0:T(8,128)}, f32[1,4096,14336]{2,1,0:T(8,128)}) fusion(%p, %mul.1406, %mul.1409), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_step)/shard_map/add"}
}
"""


def test_leaves_relaid_for_a_bucket_reads_a_compiled_program():
    """The compiled-program check of a leaf reduced alone: a ``copy`` or
    ``reshape`` of as many elements as a big leaf is its relayout to the
    flat buffer or back, whatever dimensions the compiler gave it, and a
    fusion under ``opt_update`` with two flat outputs of its size the
    moments' own pass; an activation's copy, a small leaf's packing and
    the one pass that writes parameter, ``mu`` and ``nu`` in the leaf's
    shape are not."""
    import jax
    import jax.numpy as jnp

    params = {"w_up": jax.ShapeDtypeStruct((1, 4096, 14336), jnp.float32),
              "wo": jax.ShapeDtypeStruct((1, 32, 128, 4096), jnp.float32),
              "norm": jax.ShapeDtypeStruct((4096,), jnp.float32)}
    names = [n for n, *_ in chip_smoke.entry_instructions(_HLO_PACKED)]
    assert names[0] == "copy.243" and names[-1] == "multiply_add_fusion.6"
    relaid, passes = chip_smoke.leaves_relaid_for_a_bucket(
        _HLO_PACKED, params, 50_000_000)
    assert {n: op for n, (op, _) in relaid.items()} == {
        "copy.243": "copy", "mul.1406": "reshape", "mul.1409": "reshape"}
    assert list(passes) == ["broadcast_multiply_fusion.2"]
    relaid, _ = chip_smoke.leaves_relaid_for_a_bucket(
        _HLO_PACKED, params, 16_000_000)
    assert "copy.244" in relaid and "copy.185" not in relaid
    alone = "\n".join(
        line for line in _HLO_PACKED.splitlines()
        if not re.match(r"\s*%(copy\.24|mul\.|broadcast_multiply)", line))
    assert chip_smoke.leaves_relaid_for_a_bucket(
        alone, params, 16_000_000) == ({}, {})
