"""The TPU compiler's own verdict on the serving programs, with no chip:
``jax.experimental.topologies`` describes a v5e and XLA:TPU / Mosaic
compile for it here (nothing runs).  What is held: the paged decode tick
and the landing of a prefill write the KV pool IN PLACE — layout
assignment is the TPU compiler's, so the CPU tests of
``tests/test_paged.py`` cannot see it, and ``chip_smoke.py`` sees it
only on the chip; the tick's next-token pick keeps its conditionals
(a compiler that ran both branches and selected would sort every tick);
the training step under remat ``dots`` runs the flash forward kernel
once, for the two arrays it saves; and the step the benchmark's training
cells build hands its optimizer VALUES: no weight-gradient product of
the one-chip program carries AdamW in its epilogue, and the four-chip
program is the one it was.

Keep every such compile in THIS file (one process may hold libtpu), and
describe the topology only inside the fixture ``v5e`` below.
"""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from horovod_tpu.models import transformer as T  # noqa: E402
from horovod_tpu.ops import attention as ATT  # noqa: E402
from horovod_tpu.ops import moe as MOE  # noqa: E402
from horovod_tpu.ops import paged_attention as PA  # noqa: E402
from horovod_tpu.serving import cache as C  # noqa: E402
from horovod_tpu.serving.engine import InferenceEngine  # noqa: E402

pytestmark = [pytest.mark.serving, pytest.mark.paged]

S, PS, PAGES, MAX_LEN = 8, 16, 2048, 512


@pytest.fixture(scope="module")
def v5e():
    """The described ``v5e:2x2``: its four devices, none attached."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile can be written to the persistent cache but never
    # read back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _cfg(**kw):
    return T.TransformerConfig(
        vocab_size=512, d_model=256, n_heads=2, n_kv_heads=2, d_ff=512,
        max_seq=MAX_LEN, dtype=jnp.bfloat16, attention_impl="reference",
        **kw)


def _on(sharding, tree):
    """``tree``'s shapes, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _params(sharding, cfg):
    """The model's parameters as shapes on the chip, f32 leaves in the
    configuration's dtype (as a server holds them)."""
    return _on(sharding, jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(cfg.dtype) if a.dtype == jnp.float32 else a,
        T.init_params(jax.random.PRNGKey(0), cfg))))


CASES = {
    "uniform": _cfg(n_layers=2),
    "patterned": _cfg(n_layers=4, window=64,
                      layer_pattern=("sliding", "full")),
    # latent attention over ONE pool array (rows of 128 + 64 in 256
    # lanes), a leading dense layer, a share of sigmoid-routed experts
    "latent": _cfg(n_layers=3, n_dense_layers=1, q_lora_rank=128,
                   kv_lora_rank=128, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128,
                   rope_yarn=(4.0, 64.0, 32.0, 1.0, 1.0, 1.0),
                   n_experts=8, n_experts_held=4, expert_offset=4,
                   n_experts_per_tok=2, d_expert=256, n_shared_experts=1,
                   moe_score="sigmoid", n_group=2, topk_group=1,
                   norm_topk_prob=True, routed_scaling_factor=2.5,
                   moe_impl="dropless"),
}
# ... and the latent case with a lightning indexer (4 index heads of
# 128, index_topk 128 under a table of 512): TWO pool arrays under one
# table, the index walk and the selected attend in the tick
CASES["sparse"] = dataclasses.replace(
    CASES["latent"], index_n_heads=4, index_head_dim=128, index_topk=128,
    moe_score_bias=True)


@pytest.mark.parametrize("what", ["tick", "landing"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_tpu_compiler_writes_the_pool_in_place(one_chip, monkeypatch,
                                                   case, what):
    """Compiled for the v5e, neither the tick (fused kernel, the pools
    the layer scan's carry) nor the landing has an instruction with a
    result the size of one layer of a pool, other than the pool passing
    through and the writes the compiler aliased to it; and both need
    next to no temporary memory beside the pool."""
    monkeypatch.setattr(PA, "use_interpret", lambda: False)
    monkeypatch.setattr(MOE, "use_interpret", lambda: False)
    cfg = CASES[case]

    def on_chip(tree):
        return _on(one_chip, tree)

    pool = C.init_page_pool(cfg, S, PAGES + 1, PS, None,
                            cfg.kind_count("full"))
    if cfg.has_window:
        w = C.init_page_pool(cfg, S, PAGES // 4 + 1, PS, None,
                             cfg.kind_count("sliding"))
        pool = {**pool, "wk": w["k"], "wv": w["v"]}
    pool = on_chip(jax.eval_shape(lambda: pool))
    layer = min(a.size // a.shape[0] for n, a in pool.items() if n != "pos")
    table = on_chip(jax.ShapeDtypeStruct((S, MAX_LEN // PS), jnp.int32))
    if what == "tick":
        params = _params(one_chip, cfg)
        compiled = jax.jit(
            lambda p, tok, act, t, wt, pl: T.decode_step_paged(
                p, tok, pl, t, cfg, act, kernel=True,
                wtable=wt if cfg.has_window else None),
            donate_argnums=(5,)).lower(
                params, on_chip(jax.ShapeDtypeStruct((S,), jnp.int32)),
                on_chip(jax.ShapeDtypeStruct((S,), jnp.bool_)), table,
                table, pool).compile()
        assert "tpu_custom_call" in compiled.as_text()
        if cfg.sparse:
            # the index walk and the selected attend went through
            # Mosaic; the dense latent walk is not in this tick; and the
            # selection compiled to NO sort (the router's small top-k is
            # the tick's only one: none lies under hvd_dsa_select)
            text = compiled.as_text()
            assert PA.INDEX_KERNEL_NAME in text
            assert PA.SELECT_ATTEND_NAME in text
            assert PA.MLA_KERNEL_NAME not in text
            assert not [l for l in text.splitlines()
                        if re.search(r"= \S+ sort\(", l)
                        and "hvd_dsa_select" in l]
        elif cfg.latent:    # the latent walk is the kernel in the tick
            assert PA.MLA_KERNEL_NAME in compiled.as_text()
    else:
        full = {n: a for n, a in pool.items() if n not in ("wk", "wv")}
        blk = on_chip(jax.ShapeDtypeStruct(
            (cfg.kind_count("full"), 2) + pool["k"].shape[2:3] + (128,)
            + pool["k"].shape[4:], cfg.dtype))
        i32 = lambda *shape: on_chip(  # noqa: E731
            jax.ShapeDtypeStruct(shape, jnp.int32))
        ik = on_chip(jax.ShapeDtypeStruct(
            blk.shape[:-1] + (cfg.index_head_dim,), cfg.dtype))
        compiled = jax.jit(C.paged_insert, donate_argnums=(0,)).lower(
            full, i32(2), i32(2), i32(2, C.landing_pages(128, PS)), i32(),
            i32(2), {n: ik if n == "ik" else blk
                     for n in full if n != "pos"}).compile()
    offenders, largest = chip_smoke.pool_sized_results(compiled.as_text(),
                                                       layer)
    assert offenders == [], (offenders, largest)
    # the pool itself is argument and aliased result; beside it the
    # program holds less than one layer of it
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer * 2, mem


@pytest.mark.parametrize("what", ["index_walk", "select", "attend",
                                  "chunk_scores"])
def test_sparse_attentions_parts_compile_at_the_published_sizes(
        one_chip, monkeypatch, what):
    """DeepSeek-V3.2-Exp's own sizes (64 index heads of 128, 128 heads
    over rows of 640, ``index_topk`` 2048, 24 slots of 32 768 in pages of
    16): each part of a tick's sparse attention, and a chunk's scores,
    through XLA:TPU / Mosaic for the v5e."""
    monkeypatch.setattr(PA, "use_interpret", lambda: False)
    S_, ML, K = 24, 32768, 2048
    bf, f32 = jnp.bfloat16, jnp.float32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table, lim = sds((S_, ML // PS), jnp.int32), sds((S_,), jnp.int32)
    if what == "index_walk":
        args = (sds((S_, 64, 128), bf), sds((S_, 64), f32),
                sds((5, S_ * ML // PS + 1, 1, PS, 128), bf), table, lim,
                sds((), jnp.int32))
        fn = lambda q, w, pool, t, l, i: PA.index_scores(  # noqa: E731
            q, w, pool, t, l, layer=i)
    elif what == "select":
        args = (sds((S_, ML), f32), lim)
        fn = lambda sc, l: PA.select_topk(sc, l, K)  # noqa: E731
    elif what == "attend":
        args = (sds((S_, 128, 640), bf), sds((S_, K, 640), bf), lim)
        fn = lambda q, rows, l: PA.selected_attend(  # noqa: E731
            q, rows, l, v_dim=512, sm_scale=0.135, kernel=True)
    else:
        args = (sds((512, 64, 128), bf), sds((512, 64), f32),
                sds((ML + 512, 128), bf))
        fn = lambda q, w, keys: PA.index_scores_rows(  # noqa: E731
            q, w, keys, kernel=True)
    text = jax.jit(fn).lower(*args).compile().as_text()
    if what == "select":
        assert not re.search(r"= \S+ sort\(", text)
        assert "tpu_custom_call" not in text
    else:
        assert "tpu_custom_call" in text
        assert (PA.SELECT_ATTEND_NAME if what == "attend"
                else PA.INDEX_KERNEL_NAME) in text


@pytest.mark.parametrize("K,N", [(7168, 2048), (2048, 7168)])
def test_the_grouped_product_compiles_with_its_runs_held_still(
        one_chip, monkeypatch, K, N):
    """A.X-K1's tick (256 rows over 12 held experts, a matrix of 29 MB in
    runs of 4 MB): ``grouped_matmul``'s index maps choose the run by a
    prefetched scalar (``MOE._run``: a skipped item names the block
    already resident), and Mosaic takes that inside the 48 MB of VMEM
    the call asks for."""
    monkeypatch.setattr(MOE, "use_interpret", lambda: False)
    assert K // MOE._k_tile(K, N, 2) > 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(MOE.grouped_matmul).lower(
        sds((256, K), jnp.bfloat16), sds((4, 12, K, N), jnp.bfloat16),
        sds((), jnp.int32), sds((12,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and MOE.EXPERTS_NAME in text
    # the stack is read where it lies: no copy of it, nor of a layer
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 12 * K * N * 2, mem


@pytest.mark.parametrize("lookup", ["product", "scalar_gather"])
def test_the_served_sparse_tick_fits_the_chip_and_writes_in_place(
        one_chip, monkeypatch, lookup):
    """The benchmark's own configuration (`deepseek-v3.2-exp-serve`: 24
    slots of 32 768, 49 152 pages) as the engine's tick, compiled for
    the v5e from shapes alone: 3.226 B parameters and 6.04 GB of pool
    are its arguments, both pool arrays come back aliased, no
    instruction has a result the size of a layer of the index-key array
    (the smaller of the two), and the temporaries — the scores, the
    selection's one-hots, 24 x 2048 gathered rows — stay under 0.2
    GB.  Each pick's PAGE comes out of a product (``PA.pages_of``):
    with ``jnp.take_along_axis`` patched back in (``scalar_gather``:
    the form until PR 44) each layer program holds a gather of 49 152
    single int32s."""
    import json

    from chipbench.drivers import serve_sparse

    monkeypatch.setattr(PA, "use_interpret", lambda: False)
    monkeypatch.setattr(MOE, "use_interpret", lambda: False)
    if lookup == "scalar_gather":
        monkeypatch.setattr(
            PA, "pages_of", lambda table, idx, ps: jnp.take_along_axis(
                table, idx // ps, axis=1))
    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench",
                           "configs", "deepseek-v3.2-exp-serve.json")) as f:
        dims = json.load(f)
    cfg, eng = serve_sparse.build_cfg(dims), dims["engine"]
    params = _params(one_chip, cfg)
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert abs(n_params - 3.226e9) < 1e6, n_params
    pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
        cfg, eng["n_slots"], eng["n_pages"] + 1, eng["page_size"])))
    S_ = eng["n_slots"]
    compiled = jax.jit(
        lambda p, tok, act, t, pl: T.decode_step_paged(
            p, tok, pl, t, cfg, act, kernel=True, return_moe_load=True),
        donate_argnums=(4,)).lower(
            params, _on(one_chip, jax.ShapeDtypeStruct((S_,), jnp.int32)),
            _on(one_chip, jax.ShapeDtypeStruct((S_,), jnp.bool_)),
            _on(one_chip, jax.ShapeDtypeStruct(
                (S_, eng["max_len"] // eng["page_size"]), jnp.int32)),
            pool).compile()
    text = compiled.as_text()
    assert PA.INDEX_KERNEL_NAME in text and PA.SELECT_ATTEND_NAME in text
    layer = pool["ik"].size // pool["ik"].shape[0]
    offenders, largest = chip_smoke.pool_sized_results(text, layer)
    assert offenders == [], (offenders, largest)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * (pool["k"].size + pool["ik"].size)
    assert abs(pool_bytes - 6.04e9) < 2e7
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.2e9, mem
    assert mem.argument_size_in_bytes < 12.6e9, mem
    # two layer programs (the dense stack's scan, the expert stack's):
    # each gathers its 24 x 2048 rows once, and none of them an int32 a
    # pick (a transfer an ELEMENT) unless the old lookup is patched in
    lines = text.splitlines()
    rows = [l for l in lines if re.search(
        r"= bf16\[49152,640\]\S* fusion\(.*kind=kCustom", l)]
    assert len(rows) == 2 and all("gather" in l for l in rows), rows
    ids = [l for l in lines if re.search(
        r"= s32\[49152\]\S* fusion\(.*kind=kCustom", l)]
    assert len(ids) == (2 if lookup == "scalar_gather" else 0), ids
    assert all("take_along_axis" in l and "gather" in l for l in ids), ids
    assert (lookup == "scalar_gather") == bool(re.search(
        r"= s32\[24,2048\]\S* gather\(", text))


@pytest.mark.parametrize("half", ["attend", "feed"])
def test_the_sparse_cells_reference_fits_the_chip_in_one_width(one_chip,
                                                               half):
    """The benchmark's own float32 reference of `deepseek-v3.2-exp-serve`
    (``chipbench/reference_sparse.py``), a sequence of any length laid in
    the engine's 32 768 rows: each half of a layer compiles for the v5e
    with its temporaries well inside the chip (the engine is gone by
    then), its length a traced scalar — one executable whatever the seed
    draws."""
    import json

    from chipbench import reference_sparse as R
    from chipbench import weights_sparse as W

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench",
                           "configs", "deepseek-v3.2-exp-serve.json")) as f:
        dims = json.load(f)
    att, ffn = R._layer_fns(R._freeze({k: dims[k] for k in R._LAYER_KEYS}),
                            "f32", True, ())
    w = _on(one_chip, jax.eval_shape(lambda: W._layer(
        jax.random.key(0), 1, dims, jnp.bfloat16, False)))
    wa = {k: w.pop(k) for k in R.ATTENTION_LEAVES}
    x = _on(one_chip, jax.ShapeDtypeStruct(
        (dims["engine"]["max_len"], dims["hidden_size"]), jnp.float32))
    n = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        compiled = (att.lower(x, wa, n) if half == "attend"
                    else ffn.lower(x, w, n)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (9e9 if half == "attend" else 2e9), mem
    assert "while" in compiled.as_text()    # the rows below n, no more


def test_the_tpu_compiler_keeps_the_picks_sorts_under_a_conditional(
        one_chip, monkeypatch):
    """The chat-shaped tick (32 slots, the Mistral vocabulary) with the
    engine's pick, compiled for the v5e: the conditionals of
    ``sample_token_rows`` survive, both full-vocabulary sorts lie under a
    branch and none outside, and the branches took nothing of the pool
    along — it is still written in place."""
    monkeypatch.setattr(PA, "use_interpret", lambda: False)
    slots = 32
    cfg = T.TransformerConfig(
        vocab_size=32768, d_model=256, n_heads=2, n_kv_heads=2, d_ff=512,
        n_layers=2, max_seq=MAX_LEN, dtype=jnp.bfloat16,
        attention_impl="reference")

    def col(dtype, *more):
        return _on(one_chip, jax.ShapeDtypeStruct((slots,) + more, dtype))

    params = _params(one_chip, cfg)
    pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
        cfg, slots, PAGES + 1, PS, None, cfg.n_layers)))
    layer = min(a.size // a.shape[0] for n, a in pool.items() if n != "pos")

    def tick(p, tok, act, table, pl, s_t, s_k, s_p, s_key):
        logits, out = T.decode_step_paged(p, tok, pl, table, cfg, act,
                                          kernel=True)
        return InferenceEngine._pick(logits, pl["pos"], act, s_t, s_k,
                                     s_p, s_key), out

    compiled = jax.jit(tick, donate_argnums=(4,)).lower(
        params, col(jnp.int32), col(jnp.bool_),
        col(jnp.int32, MAX_LEN // PS), pool, col(jnp.float32),
        col(jnp.int32), col(jnp.float32), col(jnp.uint32, 2)).compile()
    text = compiled.as_text()
    conditionals, inside, outside = chip_smoke.sorts_by_conditional(text)
    assert conditionals >= 1 and len(inside) == 2 and outside == [], (
        conditionals, inside, outside)
    offenders, largest = chip_smoke.pool_sized_results(text, layer)
    assert offenders == [], (offenders, largest)


@pytest.mark.parametrize("policy,forwards", [("dots", 1), ("full", 2)])
def test_the_train_step_runs_the_flash_forward_once_under_dots(
        one_chip, monkeypatch, policy, forwards):
    """One layer's AdamW step at a small tileable shape (2 rows of 2048,
    8 heads of 128), compiled for the v5e: under ``"dots"`` the program
    holds ONE ``hvd_flash_fwd`` custom call — the backward pass reads the
    saved output and log-sum-exp — and under ``"full"`` two.  What
    ``"dots"`` holds for it: no more temporary memory over the form that
    saved matmul outputs alone (the parent's) than the two arrays, with a
    tenth of room for the allocator's packing (it read +0.7 % here, and
    +0.1 % at the benchmark's shape)."""
    monkeypatch.setattr(ATT, "_use_interpret", lambda: False)
    rows, seq = 2, 2048
    cfg = T.TransformerConfig(
        vocab_size=4096, d_model=1024, n_heads=8, n_kv_heads=8, d_ff=2048,
        n_layers=1, max_seq=seq, dtype=jnp.bfloat16, attention_impl="flash",
        remat=True, remat_policy=policy)
    params = _on(one_chip, jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    opt = optax.adamw(3e-4)
    opt_state = _on(one_chip, jax.eval_shape(opt.init, params))
    batch = _on(one_chip, {k: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
                           for k in ("tokens", "targets")})

    def compiled():
        def step(params, opt_state, batch):   # a new function: traced anew
            loss, grads = jax.value_and_grad(
                lambda p: T.loss_fn(p, batch, cfg))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        return jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt_state, batch).compile()

    program = compiled()
    text = program.as_text()
    calls = {k: chip_smoke.kernel_calls(text, k) for k in (
        "hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")}
    assert calls == {"hvd_flash_fwd": forwards * cfg.n_layers,
                     "hvd_flash_bwd_dq": 1, "hvd_flash_bwd_dkv": 1}, calls
    if policy != "dots":
        return
    # the parent's form of "dots": matmul outputs alone
    monkeypatch.setattr(T, "_remat", lambda layer, cfg: jax.checkpoint(
        layer, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable))
    before = compiled()
    assert chip_smoke.kernel_calls(before.as_text(), "hvd_flash_fwd") == 2
    saved = (rows * seq * cfg.d_model * 2          # o, bf16
             + rows * cfg.n_heads * seq * 4)       # lse, f32
    grew = (program.memory_analysis().temp_size_in_bytes
            - before.memory_analysis().temp_size_in_bytes)
    assert 0 < grew <= saved * 1.1, (grew, saved)


# Mistral-7B-v0.3's widths at one layer and 6 rows of 4096 a chip (the
# training cells'), and the smallest widths heads of 128 allow: the
# compiler fuses an update into its product at both.
TRAIN_WIDTHS = {
    "published": dict(d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336,
                      vocab_size=32768, max_seq=4096, rows=6),
    "smallest": dict(d_model=256, n_heads=2, n_kv_heads=1, d_ff=512,
                     vocab_size=1024, max_seq=1024, rows=2),
}


def _cell_train_step(v5e, n_devices, widths):
    """The step ``chipbench/drivers/train.py`` builds —
    ``value_and_grad(T.loss_fn)``, then
    ``hvd.DistributedOptimizer(optax.adamw(..)).update``, under
    ``shard_map`` — compiled for ``n_devices`` of the described v5e:
    ``(HLO text, parameter shapes)``.  Traced anew at every call."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import spmd

    widths = dict(widths)
    rows = widths.pop("rows") * n_devices
    cfg = T.TransformerConfig(
        n_layers=1, rope_theta=1e6, dtype=jnp.bfloat16,
        attention_impl="flash", remat=True, remat_policy="dots", **widths)
    mesh = Mesh(np.array(v5e.devices[:n_devices]), (hvd.AXIS,))
    repl = NamedSharding(mesh, P())
    params = _on(repl, jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4, weight_decay=1e-4))
    opt_state = _on(repl, jax.eval_shape(opt.init, params))
    batch = _on(NamedSharding(mesh, P(hvd.AXIS)), {
        k: jax.ShapeDtypeStruct((rows, cfg.max_seq), jnp.int32)
        for k in ("tokens", "targets")})

    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, hvd.AXIS))

    step = jax.jit(spmd.shard(
        _step, in_specs=(P(), P(), P(hvd.AXIS)),
        out_specs=(P(), P(), P()), mesh=mesh), donate_argnums=(0, 1))
    return step.lower(params, opt_state, batch).compile().as_text(), params


def _gradients_as_they_come(monkeypatch):
    """The parent's form: nothing between a gradient and its reduction."""
    from horovod_tpu import optim

    monkeypatch.setattr(optim, "_as_values", lambda grads: grads)


@pytest.mark.parametrize("widths", sorted(TRAIN_WIDTHS))
def test_no_weight_gradient_product_carries_the_update_on_one_chip(
        v5e, monkeypatch, widths):
    """On ONE device the reduction emits no operation, and XLA:TPU then
    writes AdamW's three outputs from the epilogue of the matmul that
    makes the gradient — ``fusion.29`` / ``.85`` / ``.87`` / ``.89`` of
    ``m7b-train-1chip``, at 41-52 % of the MXU (ledger, PR 40).  The
    optimizer receives values (``optim._as_values``): no fusion that
    holds a ``convolution`` writes a parameter's worth of float32 more
    than once."""
    monkeypatch.setattr(ATT, "_use_interpret", lambda: False)
    text, params = _cell_train_step(v5e, 1, TRAIN_WIDTHS[widths])
    assert chip_smoke.kernel_calls(text, "hvd_flash_fwd") == 1
    carrying = chip_smoke.products_carrying_an_update(text, params)
    assert carrying == {}, carrying


def test_the_compiler_still_fuses_an_update_into_a_bare_gradients_product(
        v5e, monkeypatch):
    """What the test above guards against is still what this compiler
    does: with the gradients handed on as they come, all eight matrices
    (head, three of the MLP, ``wq`` / ``wk`` / ``wv`` / ``wo``) are
    written three times over by their gradient's product.  When this
    fails the compiler has changed its mind, and ``_as_values`` can be
    measured again."""
    monkeypatch.setattr(ATT, "_use_interpret", lambda: False)
    _gradients_as_they_come(monkeypatch)
    text, params = _cell_train_step(v5e, 1, TRAIN_WIDTHS["smallest"])
    carrying = chip_smoke.products_carrying_an_update(text, params)
    assert len(carrying) == 8, carrying


def _entry_schedule(text):
    """The entry computation's collectives, products and custom calls in
    the order the compiler scheduled them."""
    products = chip_smoke.product_fusions(text)
    kinds = []
    for name, _, opcode, line in chip_smoke.entry_instructions(text):
        if name in products:
            kinds.append("product")
        elif opcode == "custom-call":
            kinds.append(re.search(
                r'custom_call_target="([^"]+)"', line).group(1))
        elif opcode.startswith(chip_smoke._HLO_COLLECTIVES):
            kinds.append(opcode)
    return kinds


def test_the_four_chip_step_is_the_program_it_was(v5e, monkeypatch):
    """On the four devices the allreduce already stood between a gradient
    and its update, and a barrier BEFORE it changes nothing: at the
    published widths (a schedule read at toy widths says nothing about
    the cell: ROADMAP S11) the entry computation's collectives, products
    and custom calls are the parent's in kind and order — seven
    ``all-reduce``s, each right behind the product that makes its
    gradient — and no product carries an update on either side."""
    monkeypatch.setattr(ATT, "_use_interpret", lambda: False)
    text, params = _cell_train_step(v5e, 4, TRAIN_WIDTHS["published"])
    schedule = _entry_schedule(text)
    assert schedule.count("all-reduce") == 7, schedule
    assert schedule.count("tpu_custom_call") == 3, schedule
    assert chip_smoke.products_carrying_an_update(text, params) == {}
    _gradients_as_they_come(monkeypatch)
    parent, _ = _cell_train_step(v5e, 4, TRAIN_WIDTHS["published"])
    assert chip_smoke.products_carrying_an_update(parent, params) == {}
    assert schedule == _entry_schedule(parent)


def test_the_four_chip_step_relays_no_leaf_that_is_reduced_alone(
        v5e, monkeypatch):
    """A leaf that fills a bucket alone goes into its ``all-reduce`` as it
    lies (``ops/fusion.py``): at the published widths the four-device
    step's entry computation holds no ``copy`` and no ``reshape`` of a
    whole leaf of 50 M float32 elements or more (the embedding, the
    head, the MLP's three), and AdamW no pass of its own over their flat
    form — it runs in the ONE pass it takes on one chip — under the same
    seven ``all-reduce``s.  (The attention leaves, 4-17 M elements, keep
    copies of their own on both sides and are left out.)  With every
    bucket packed, as before PR 43, the same reader finds a copy in
    front of each of the five reductions, two reshapes behind each and
    the moments' pass between (twelve reshapes counting ``wo``'s): when
    THAT fails the compiler has changed its mind about a ravel."""
    from conftest import every_bucket_packed

    monkeypatch.setattr(ATT, "_use_interpret", lambda: False)
    floor = 50_000_000
    text, params = _cell_train_step(v5e, 4, TRAIN_WIDTHS["published"])
    assert chip_smoke.leaves_relaid_for_a_bucket(
        text, params, floor) == ({}, {})
    assert _entry_schedule(text).count("all-reduce") == 7

    every_bucket_packed(monkeypatch)
    before, _ = _cell_train_step(v5e, 4, TRAIN_WIDTHS["published"])
    assert _entry_schedule(before).count("all-reduce") == 7
    relayouts, passes = chip_smoke.leaves_relaid_for_a_bucket(
        before, params, floor)
    opcodes = [opcode for opcode, _ in relayouts.values()]
    assert (opcodes.count("copy"), opcodes.count("reshape"),
            len(passes)) == (5, 10, 5), (relayouts, passes)
    # ... and wo's (16.8 M elements: a bucket of its own at 64 MiB)
    relayouts, passes = chip_smoke.leaves_relaid_for_a_bucket(
        before, params, 16_000_000)
    assert [opcode for opcode, _ in relayouts.values()].count(
        "reshape") == 12, relayouts
    assert len(passes) == 7, passes


@pytest.mark.parametrize("tree", ["engine", "checkpoint"])
@pytest.mark.parametrize("what", ["tick", "chunk"])
def test_the_layer_scan_copies_no_projection_leaf_of_an_engines_tree(
        one_chip, monkeypatch, what, tree):
    """The benchmark's Mistral configuration, its tick and a chunk's
    program (``prefill_with_prefix``: 512 tokens behind 1024 landed),
    compiled for the v5e.  With the ENGINE's tree
    (``T.lay_out_projections``: ``wq``/``wk``/``wv`` stored ``(L, D, H *
    Dh)``) no operation of the scan's own — under ``layer_scan`` and
    under no scope of the layer's — has a result the size of one
    layer's ``wq`` or ``wk``: the products read their leaves where they
    lie.  With a CHECKPOINT's tree (``(L, D, H, Dh)``: what every tick
    and chunk ran before ISSUE 37) the same reading finds each of the
    three leaves cut out AND copied — the control that says the reading
    would see such a copy."""
    import json

    from chipbench.drivers import serve

    monkeypatch.setattr(PA, "use_interpret", lambda: False)
    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench",
                           "configs", "mistral-7b-v0.3-serve.json")) as f:
        dims = json.load(f)
    cfg, eng = serve.build_cfg(dims), dims["engine"]
    params = _params(one_chip, cfg)
    if tree == "engine":
        params = _on(one_chip, jax.eval_shape(
            lambda p: T.lay_out_projections(p)[0], params))
        assert params["layers"]["wq"].shape == (
            cfg.n_layers, cfg.d_model, cfg.n_heads * cfg.head_dim)

    def sds(shape, dtype):
        return _on(one_chip, jax.ShapeDtypeStruct(shape, dtype))

    S_, L = eng["n_slots"], cfg.n_layers
    if what == "tick":
        pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
            cfg, S_, eng["n_pages"] + 1, eng["page_size"])))
        compiled = jax.jit(
            lambda p, tok, act, t, pl: T.decode_step_paged(
                p, tok, pl, t, cfg, act, kernel=True),
            donate_argnums=(4,)).lower(
                params, sds((S_,), jnp.int32), sds((S_,), jnp.bool_),
                sds((S_, eng["max_len"] // eng["page_size"]), jnp.int32),
                pool).compile()
    else:
        landed = sds((L, cfg.kv_heads, 1024, cfg.head_dim), cfg.dtype)
        compiled = jax.jit(
            lambda p, tok, lens, pk, pv, p0: T.prefill_with_prefix(
                p, tok, {"k": pk, "v": pv}, p0, cfg, true_len=lens)).lower(
                    params, sds((1, eng["prefill_chunk_tokens"]), jnp.int32),
                    sds((1,), jnp.int32), landed, landed,
                    sds((), jnp.int32)).compile()
    leaf = {cfg.d_model * h * cfg.head_dim
            for h in (cfg.n_heads, cfg.kv_heads)}
    found, _ = chip_smoke.pool_sized_results(compiled.as_text(), min(leaf))
    scans_own = [
        f for f in found if f[0] in leaf and [
            c for c in f[3].split("/") if c in T.DEVICE_SCOPES
        ][-1:] == ["layer_scan"]]
    if tree == "engine":
        assert scans_own == [], scans_own
    else:
        assert len(scans_own) >= 3, (scans_own, found)


def _lfm2():
    import json

    from chipbench.drivers import serve_conv

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench",
                           "configs", "lfm2-24b-a2b-serve.json")) as f:
        dims = json.load(f)
    return dims, serve_conv.build_cfg(dims)


@pytest.mark.parametrize("what", ["tick", "chunk", "prompt"])
def test_the_served_conv_programs_compile_at_the_published_widths(
        one_chip, monkeypatch, what):
    """The benchmark's own configuration (`lfm2-24b-a2b-serve`: 64 slots
    of 8192, 32 768 pages, heads of 64 two to a stored row) as the
    engine's three programs, compiled for the v5e from shapes alone.
    5 267 090 176 parameters = 10.53 GB in bf16 are their argument (the
    issue's count, from an engine's own tree).  The TICK holds the fused
    paged kernel — at rows of 128 lanes, the kernel heads of 128 run —
    and the grouped expert product, takes 2.15 GB of pool and 4 MB of
    conv state and gives ALL of it back aliased (the state is written in
    place like the pages), with no result the size of a layer of ``k``
    and 5 MB of temporaries.  A CHUNK of 512 against 4096 landed tokens
    (prefix as the pool stores it, state as the chunk before left it)
    and a PROMPT of 512 — through the flash forward at heads of 64, a
    Mosaic call and not the XLA form — compile inside 0.7 GB of
    temporaries."""
    for mod, name in ((PA, "use_interpret"), (MOE, "use_interpret"),
                      (ATT, "_use_interpret")):
        monkeypatch.setattr(mod, name, lambda: False)
    dims, cfg = _lfm2()
    eng = dims["engine"]
    assert (cfg.head_dim, cfg.kv_pack, cfg.n_dense_layers) == (64, 2, 2)
    params = _on(one_chip, jax.eval_shape(
        lambda: T.lay_out_projections(jax.tree_util.tree_map(
            lambda a: a.astype(cfg.dtype),
            T.init_params(jax.random.PRNGKey(0), cfg)))[0]))
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 5_267_090_176 and "head" not in params
    weights = 2 * n_params

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    La, Lc = cfg.kind_count("full"), cfg.kind_count("conv")
    if what == "tick":
        S_ = eng["n_slots"]
        pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
            cfg, S_, eng["n_pages"] + 1, eng["page_size"], None, La)))
        assert pool["k"].shape == (2, 32769, 4, 16, 128)
        assert pool["conv"].shape == (8, 64, 2, 2048)
        compiled = jax.jit(
            lambda p, tok, act, t, pl: T.decode_step_paged(
                p, tok, pl, t, cfg, act, kernel=True, return_moe_load=True),
            donate_argnums=(4,)).lower(
                params, sds((S_,), jnp.int32), sds((S_,), jnp.bool_),
                sds((S_, eng["max_len"] // eng["page_size"]), jnp.int32),
                pool).compile()
        text = compiled.as_text()
        assert chip_smoke.kernel_calls(text, PA.KERNEL_NAME) == 1
        assert chip_smoke.kernel_calls(text, MOE.EXPERTS_NAME) >= 1
        layer = pool["k"].size // pool["k"].shape[0]
        offenders, largest = chip_smoke.pool_sized_results(text, layer)
        assert offenders == [], (offenders, largest)
        mem = compiled.memory_analysis()
        pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())
        assert abs(pool_bytes - 2.152e9) < 1e6
        assert mem.alias_size_in_bytes >= pool_bytes     # the state too
        assert mem.temp_size_in_bytes < 0.05e9, mem
        assert mem.argument_size_in_bytes < weights + pool_bytes + 1e6
        return
    ids, lens = sds((1, 512), jnp.int32), sds((1,), jnp.int32)
    if what == "chunk":
        pk = sds((La, cfg.kv_heads // cfg.kv_pack, 4096,
                  cfg.head_dim * cfg.kv_pack), cfg.dtype)
        state = sds((Lc, 1, cfg.conv_taps, cfg.d_model), cfg.dtype)
        compiled = jax.jit(
            lambda p, suf, k, v, p0, n, st: T.prefill_with_prefix(
                p, suf, {"k": k, "v": v, "conv": st}, p0, cfg,
                true_len=n)).lower(
                    params, ids, pk, pk, sds((), jnp.int32), lens,
                    state).compile()
    else:
        compiled = jax.jit(
            lambda p, pr, n: T.prefill(p, pr, T.init_cache(cfg, 1, 512),
                                       cfg, true_len=n)).lower(
                params, ids, lens).compile()
        assert chip_smoke.kernel_calls(compiled.as_text(),
                                       "hvd_flash_fwd") == 1
    text = compiled.as_text()
    assert chip_smoke.kernel_calls(text, MOE.EXPERTS_NAME) >= 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.7e9, mem
    # a (1, V) row of logits, and the block: K, V of 2 layers, 8 states
    assert mem.output_size_in_bytes < 3e6, mem


@pytest.mark.parametrize("half", ["conv_mix", "attention_mix", "dense_feed",
                                  "expert_feed", "scores"])
def test_the_conv_cells_reference_fits_the_chip_in_one_width(one_chip, half):
    """The benchmark's own float32 reference of `lfm2-24b-a2b-serve`
    (``chipbench/reference_conv.py``), a sequence of any length laid in
    the engine's 8192 rows: each half of a layer (and the scores the
    expert bias is balanced on) compiles for the v5e with its
    temporaries well inside the chip (the engine is gone by then), its
    length a traced scalar — five executables whatever the seed
    draws."""
    from chipbench import reference_conv as R
    from chipbench import weights_conv as W

    dims, _ = _lfm2()
    key = R._layer_dims(dims)
    kind = "full_attention" if half == "attention_mix" else "conv"
    w = _on(one_chip, jax.eval_shape(lambda: {
        n: jnp.zeros(s, jnp.bfloat16) for n, (s, _) in W.layer_shapes(
            dims, kind, half == "dense_feed").items()}))
    x = _on(one_chip, jax.ShapeDtypeStruct(
        (dims["engine"]["max_len"], dims["hidden_size"]), jnp.float32))
    n = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        if half.endswith("_mix"):
            lowered = R._mix_fn(key, kind, "f32", 512, False).lower(
                x, {k: w[k] for k in R._MIX_LEAVES[kind]}, n)
        elif half == "scores":
            lowered = R._scores_fn(key, 512).lower(x, w["ln2"], w["router"],
                                                   n)
        else:
            lowered = R._feed_fn(key, "f32", 512).lower(
                x, {k: w[k] for k in R._FEED_LEAVES if k in w}, n)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3e9, mem
    assert "while" in compiled.as_text()    # the rows below n, no more


def _kernel_windows(text, name):
    """``[(grid, block of the first array operand)]`` of every Mosaic
    call named ``name`` in a compiled program's text: the serialised
    kernel's ``iteration_bounds`` and first ``window_bounds`` (the
    squeezed dimensions read 1)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def ints(attr, asm):
        found = re.search(attr + r" = array<i64: ([\d, ]+)>", asm)
        return tuple(int(d) for d in found.group(1).split(","))

    out = []
    for line in text.splitlines():
        if ('custom_call_target="tpu_custom_call"' not in line
                or f"/{name}/" not in line):
            continue
        body = base64.b64decode(re.search(r'"body":"([^"]*)"', line).group(1))
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False)
        out.append((ints("iteration_bounds", asm),
                    ints("window_bounds", asm)))
    return out


def _falcon_h1():
    import json

    from chipbench.drivers import serve_hybrid

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench",
                           "configs", "falcon-h1-34b-serve.json")) as f:
        dims = json.load(f)
    return dims, serve_hybrid.build_cfg(dims)


@pytest.mark.parametrize("what", ["tick", "chunk", "prompt"])
def test_the_served_hybrid_programs_compile_at_the_published_widths(
        one_chip, monkeypatch, what):
    """The benchmark's own configuration (`falcon-h1-34b-serve`: 64 slots
    of 4096, 8192 pages, a GQA group of FIVE, a matrix state of 2 MiB a
    slot and layer) as the engine's three programs, compiled for the
    v5e from shapes alone.  4 205 319 008 parameters = 8.41 GB in bf16
    are their argument (the issue's count, from an engine's own tree).
    The TICK holds the fused paged kernel once (five query rows padded
    to eight) and the state update's kernel once, takes 2.42 GB of
    pages, 1.21 GB of matrix states and 18 MB of taps and gives ALL of
    it back aliased, with no result the size of a layer of the states
    (134 MB: 64 x 32 x 128 x 256 values) or of ``k`` but the arrays
    passing through, and under 50 MB of temporaries.  A CHUNK of 512
    against 2048 landed tokens (both states as the chunk before left
    them) and a PROMPT of two rows of 512 — through the flash forward at
    20 heads, a Mosaic call and not the XLA form — compile inside 0.7
    GB of temporaries."""
    from horovod_tpu.ops import ssm as SSM

    for mod, name in ((PA, "use_interpret"), (SSM, "use_interpret"),
                      (ATT, "_use_interpret")):
        monkeypatch.setattr(mod, name, lambda: False)
    dims, cfg = _falcon_h1()
    eng = dims["engine"]
    assert (cfg.head_dim, cfg.n_heads // cfg.kv_heads, cfg.has_ssm) == (
        128, 5, True)
    params = _on(one_chip, jax.eval_shape(
        lambda: T.lay_out_projections(jax.tree_util.tree_map(
            lambda a: a.astype(cfg.dtype),
            T.init_params(jax.random.PRNGKey(0), cfg)))[0]))
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 4_205_319_008 and "head" in params
    weights = 2 * n_params

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L = cfg.n_layers
    if what == "tick":
        S_ = eng["n_slots"]
        pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
            cfg, S_, eng["n_pages"] + 1, eng["page_size"], None, L)))
        assert pool["k"].shape == (9, 8193, 4, 16, 128)
        assert pool["conv"].shape == (9, 64, 3, 5120)
        assert pool["ssm"].shape == (9, 64, 32, 128, 256)
        compiled = jax.jit(
            lambda p, tok, act, t, pl: T.decode_step_paged(
                p, tok, pl, t, cfg, act, kernel=True),
            donate_argnums=(4,)).lower(
                params, sds((S_,), jnp.int32), sds((S_,), jnp.bool_),
                sds((S_, eng["max_len"] // eng["page_size"]), jnp.int32),
                pool).compile()
        text = compiled.as_text()
        assert chip_smoke.kernel_calls(text, PA.KERNEL_NAME) == 1
        assert chip_smoke.kernel_calls(text, SSM.UPDATE_NAME) == 1
        # ONE group of sixteen bfloat16 heads a step: 1 MiB, the grid
        # and block the kernel had when a step took a group
        assert _kernel_windows(text, SSM.UPDATE_NAME) == [
            ((64, 2), (1, 1, 16, 128, 256))]
        for name in ("ssm", "k"):   # no copy the size of a layer of either
            layer = pool[name].size // pool[name].shape[0]
            offenders, largest = chip_smoke.pool_sized_results(text, layer)
            assert offenders == [], (name, offenders, largest)
        mem = compiled.memory_analysis()
        pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())
        assert abs(pool_bytes - 3.642e9) < 1e6
        assert mem.alias_size_in_bytes >= pool_bytes     # the states too
        assert mem.temp_size_in_bytes < 0.05e9, mem
        assert mem.argument_size_in_bytes < weights + pool_bytes + 1e6
        return
    if what == "landing":
        pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
            cfg, eng["n_slots"], eng["n_pages"] + 1, eng["page_size"], None,
            Lk, eng["max_len"] // eng["page_size"])))
        n_pg = C.landing_pages(512, eng["page_size"])
        kv = sds((Lk, 1, cfg.kv_heads, 512, cfg.head_dim), cfg.dtype)
        compiled = jax.jit(C.paged_insert, donate_argnums=(0,)).lower(
            pool, sds((1,), jnp.int32), sds((1,), jnp.int32),
            sds((1, n_pg), jnp.int32), sds((), jnp.int32),
            sds((1,), jnp.int32), {
                "k": kv, "v": kv,
                "ck": sds((Lk, 1, cfg.kv_heads, n_pg, cfg.head_dim),
                          cfg.dtype),
                "lin": sds((Ll, 1) + pool["lin"].shape[2:], jnp.float32),
            }).compile()
        mem = compiled.memory_analysis()
        pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())
        assert mem.alias_size_in_bytes >= pool_bytes > 5.06e9, mem
        assert mem.temp_size_in_bytes < 1e6, mem
        offenders, largest = chip_smoke.pool_sized_results(
            compiled.as_text(), pool["ck"].size // Lk)
        assert offenders == [], (offenders, largest)
        return
    if what == "chunk":
        ids, lens = sds((1, 512), jnp.int32), sds((1,), jnp.int32)
        pk = sds((L, cfg.kv_heads, 2048, cfg.head_dim), cfg.dtype)
        taps = sds((L, 1, cfg.conv_taps, cfg.conv_width), cfg.dtype)
        state = sds((L, 1, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    cfg.dtype)
        compiled = jax.jit(
            lambda p, suf, k, v, p0, n, a, b: T.prefill_with_prefix(
                p, suf, {"k": k, "v": v, "conv": a, "ssm": b}, p0, cfg,
                true_len=n)).lower(
                    params, ids, pk, pk, sds((), jnp.int32), lens, taps,
                    state).compile()
    else:
        ids, lens = sds((2, 512), jnp.int32), sds((2,), jnp.int32)
        compiled = jax.jit(
            lambda p, pr, n: T.prefill(p, pr, T.init_cache(cfg, 2, 512),
                                       cfg, true_len=n)).lower(
                params, ids, lens).compile()
        assert chip_smoke.kernel_calls(compiled.as_text(),
                                       "hvd_flash_fwd") == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.7e9, mem
    # a row of logits a request, and the block: K, V of 9 layers' 512
    # rows, 9 layers' taps and matrix states (2 MiB each) a request
    assert mem.output_size_in_bytes < 0.07e9, mem


@pytest.mark.parametrize("half", ["mix", "feed"])
def test_the_hybrid_cells_reference_fits_the_chip_in_one_width(one_chip,
                                                               half):
    """The benchmark's own float32 reference of `falcon-h1-34b-serve`
    (``chipbench/reference_hybrid.py``), a sequence of any length laid
    in the engine's 4096 rows: each half of a layer compiles for the
    v5e with its temporaries well inside the chip (the engine is gone by
    then), its length a traced scalar — two executables a mode whatever
    the seed draws; the recurrence is a loop over the tokens."""
    from chipbench import reference_hybrid as R
    from chipbench import weights_hybrid as W

    dims, _ = _falcon_h1()
    key = R._layer_dims(dims)
    w = _on(one_chip, jax.eval_shape(lambda: {
        n: jnp.zeros(s, jnp.bfloat16)
        for n, (s, _) in W.layer_shapes(dims).items()}))
    S_ = dims["engine"]["max_len"]
    x = _on(one_chip, jax.ShapeDtypeStruct((S_, dims["hidden_size"]),
                                           jnp.float32))
    n = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        if half == "mix":
            lowered = R._mix_fn(key, "f32", 512).lower(
                x, {k: w[k] for k in R._MIX_LEAVES}, n,
                _on(one_chip, jax.ShapeDtypeStruct((S_,), jnp.bool_)))
        else:
            lowered = R._feed_fn(key, "f32", 512).lower(
                x, {k: w[k] for k in R._FEED_LEAVES}, n)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3e9, mem
    assert "while" in compiled.as_text()    # the rows below n, no more


def _minicpm_sala():
    import json

    from chipbench.drivers import serve_linear_sparse

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench",
                           "configs", "minicpm-sala-serve.json")) as f:
        dims = json.load(f)
    return dims, serve_linear_sparse.build_cfg(dims)


def _select_attend_by_page(self, qh, k_t, v_t, kind):
    """``transformer._Tick.select_attend`` as it stood until PR 48: the
    compressed keys ``(L, P, Hkv * Dh)`` a row a PHYSICAL page, written
    at the page the token fills and read back through the page table —
    ``ck[layer, table]``, a row of 512 B a table entry."""
    cfg, pos, table, layer = self.cfg, self.pos, self.table, self.layer
    k_pool, v_pool = (self.pools[n] for n in kind.paged)
    (ck,) = (self.pools[n] for n in kind.page_rows)
    S, H, _, Dh = qh.shape
    Hkv, ps = k_pool.shape[2:4]
    blk, m = cfg.bsa_block, cfg.bsa_block // cfg.bsa_stride
    max_pages = table.shape[1]
    with jax.named_scope("kv_write"):
        phys, take = self._target(table, ps)
        k_pool = PA.write_pages(k_pool, layer, phys, k_t, take)
        v_pool = PA.write_pages(v_pool, layer, phys, v_t, take)
        at = jnp.clip(pos // ps, 0, max_pages - 1)
        before = table[jnp.arange(S), jnp.maximum(at - 1, 0)]
        row = T._bsa_window_mean(k_pool[layer, before], k_pool[layer, phys],
                                 cfg).reshape(S, Hkv * Dh)
        full = self.active & (pos % ps == ps - 1) & (at >= 1)
        ck = ck.at[layer, jnp.where(full, phys, 0)].set(row.astype(ck.dtype))
    qg = qh.reshape(S, Hkv, H // Hkv, 1, Dh)
    live = jnp.where(self.active, pos, -1)
    with jax.named_scope("hvd_bsa_score"):
        rows = ck[layer, table].reshape(S, max_pages, Hkv, Dh)
        score = T._bsa_block_scores(qg, jnp.moveaxis(rows, 1, 2),
                                    live[:, None], -(-max_pages // m), cfg)
    R = S * Hkv
    with jax.named_scope("hvd_bsa_select"):
        chosen, n_sel = T._bsa_chosen(
            score.reshape(R, -1), jnp.repeat(jnp.maximum(live, 0), Hkv), cfg)
    with jax.named_scope("hvd_bsa_attend"):
        page = (chosen[:, :, None] * m + jnp.arange(m, dtype=jnp.int32)
                ).reshape(R, -1)
        compact = PA.pages_of(jnp.repeat(table, Hkv, axis=0),
                              jnp.minimum(page, max_pages - 1) * ps, ps)
        limit = jnp.where(jnp.repeat(self.active, Hkv), (n_sel - 1) * blk
                          + jnp.repeat(pos, Hkv) % blk + 1, 0)
        o, _ = PA.paged_attend(qg[:, :, :, 0], k_pool, v_pool, None, None,
                               compact.reshape(S, Hkv, -1),
                               limit.reshape(S, Hkv), layer=layer)
    return o.reshape(S, H, 1, Dh).astype(cfg.dtype), k_pool, v_pool, ck


@pytest.mark.parametrize("what", ["tick", "tick-by_page", "landing", "chunk",
                                  "prompt"])
def test_the_served_linear_sparse_programs_compile_at_the_published_widths(
        one_chip, monkeypatch, what):
    """The benchmark's own configuration (`minicpm-sala-serve`: 48 slots
    of 28 672, 81 920 pages, published layers 16-27 — three block-sparse
    layers of 32 query / 2 KV heads, nine linear-attention layers of 32
    heads — a float32 matrix state of 2 MiB a slot and layer) as the
    engine's three programs, compiled for the v5e from shapes alone.
    3 929 973 152 parameters = 7.86 GB in bf16 are their argument: the
    issue's 3 929 866 240 of matrices, and 106 912 of norms and decays
    (two norms of 4096 a layer and the last, 3 x 128 + 32 a linear
    layer, 2 x 128 a sparse one).  The TICK holds the paged kernel over
    a table a slot and KV head three times (a layer unrolled each: the
    pattern is one period of twelve) and the state update's kernel nine
    times, takes 3.87 GB of pages, 132 MB of compressed keys and 0.91 GB
    of float32 states and gives ALL of it back aliased, with no result
    the size of a layer of the states (201 MB) or of ``k`` but the
    arrays passing through, under 0.5 GB of temporaries: 13.4 GB with
    the weights.  The compressed keys lie BY SLOT, ``(3, 48, 2, 1792,
    128)``, and the product that scores them reads a layer of them
    where it lies (an operand of its fusion): no instruction has a
    result of a slot table's worth of rows (44 MB).  ``tick-by_page`` is
    the read until PR 48 patched back in over an array a row a physical
    page: each sparse layer then GATHERS its 48 x 1792 = 86 016 rows of
    512 B through the page table under ``hvd_bsa_score``.  The LANDING
    of a chunk of 512 (33 pages' rows a layer and KV head, scattered to
    the slot's logical indices) gives the whole pool back aliased too,
    under a megabyte of temporaries.  A CHUNK of 512 against 32 768
    landed positions (the largest bucket: a query a row its own blocks,
    64 queries' scores in flight) and a PROMPT of two rows of 512
    compile inside 1.3 GB."""
    from horovod_tpu.ops import ssm as SSM

    for mod, name in ((PA, "use_interpret"), (SSM, "use_interpret"),
                      (ATT, "_use_interpret")):
        monkeypatch.setattr(mod, name, lambda: False)
    dims, cfg = _minicpm_sala()
    eng = dims["engine"]
    assert cfg.layer_kinds == ("block_sparse",) * 2 + ("linear",) * 4 + (
        "block_sparse",) + ("linear",) * 5
    assert (cfg.head_dim, cfg.n_heads // cfg.kv_heads,
            cfg.bsa_blocks_max) == (128, 16, 128)
    params = _on(one_chip, jax.eval_shape(
        lambda: T.lay_out_projections(jax.tree_util.tree_map(
            lambda a: a.astype(cfg.dtype),
            T.init_params(jax.random.PRNGKey(0), cfg)))[0]))
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 3_929_973_152 == 3_929_866_240 + 106_912
    weights = 2 * n_params

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    Lk, Ll = cfg.layers_with("k"), cfg.layers_with("lin")
    assert (Lk, Ll, cfg.layers_with("ck")) == (3, 9, 3)
    if what.startswith("tick"):
        S_, width = eng["n_slots"], eng["max_len"] // eng["page_size"]
        pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
            cfg, S_, eng["n_pages"] + 1, eng["page_size"], None, Lk, width)))
        assert pool["k"].shape == (3, 81921, 2, 16, 128)
        assert pool["ck"].shape == (3, 48, 2, 1792, 128)
        assert (pool["lin"].shape, pool["lin"].dtype) == (
            (9, 48, 32, 128, 128), jnp.float32)
        by_page = what == "tick-by_page"
        if by_page:
            pool["ck"] = sds((3, 81921, 256), cfg.dtype)
            monkeypatch.setattr(T._Tick, "select_attend",
                                _select_attend_by_page)
        compiled = jax.jit(
            lambda p, tok, act, t, pl: T.decode_step_paged(
                p, tok, pl, t, cfg, act, kernel=True),
            donate_argnums=(4,)).lower(
                params, sds((S_,), jnp.int32), sds((S_,), jnp.bool_),
                sds((S_, width), jnp.int32), pool).compile()
        text = compiled.as_text()
        assert chip_smoke.kernel_calls(text, PA.BSA_KERNEL_NAME) == 3
        assert chip_smoke.kernel_calls(text, SSM.UPDATE_NAME) == 9
        # SIXTEEN groups of one float32 head a step: 1 MiB, 96 steps a
        # layer (a group a step was 1536 of 64 KiB)
        assert _kernel_windows(text, SSM.UPDATE_NAME) == 9 * [
            ((48, 2), (1, 1, 16, 128, 128))]
        for name in ("lin", "k"):   # no copy the size of a layer of either
            layer = pool[name].size // pool[name].shape[0]
            offenders, largest = chip_smoke.pool_sized_results(text, layer)
            assert offenders == [], (name, offenders, largest)
        # a slot table's worth of compressed rows (86 016 of 512 B): a
        # result of that size under the scores' scope is the gather
        # through the page table
        gathers = [g for g in chip_smoke.pool_sized_results(
            text, S_ * width * cfg.kv_heads * cfg.head_dim)[0]
            if "hvd_bsa_score" in g[3]]
        rows = re.findall(r"= bf16\[86016,256\]\S* fusion\(", text)
        assert by_page or not gathers, gathers
        assert len(rows) == (3 if by_page else 0) == sum(
            g[1] == "fusion" and g[3].endswith("hvd_bsa_score/gather")
            for g in gathers), gathers
        mem = compiled.memory_analysis()
        pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())
        assert abs(pool_bytes - (5.058e9 if by_page else 5.064e9)) < 1e6
        assert mem.alias_size_in_bytes >= pool_bytes   # ck and lin too
        assert mem.temp_size_in_bytes < 0.5e9, mem
        assert mem.argument_size_in_bytes < weights + pool_bytes + 1e6
        return
    if what == "landing":
        pool = _on(one_chip, jax.eval_shape(lambda: C.init_page_pool(
            cfg, eng["n_slots"], eng["n_pages"] + 1, eng["page_size"], None,
            Lk, eng["max_len"] // eng["page_size"])))
        n_pg = C.landing_pages(512, eng["page_size"])
        kv = sds((Lk, 1, cfg.kv_heads, 512, cfg.head_dim), cfg.dtype)
        compiled = jax.jit(C.paged_insert, donate_argnums=(0,)).lower(
            pool, sds((1,), jnp.int32), sds((1,), jnp.int32),
            sds((1, n_pg), jnp.int32), sds((), jnp.int32),
            sds((1,), jnp.int32), {
                "k": kv, "v": kv,
                "ck": sds((Lk, 1, cfg.kv_heads, n_pg, cfg.head_dim),
                          cfg.dtype),
                "lin": sds((Ll, 1) + pool["lin"].shape[2:], jnp.float32),
            }).compile()
        mem = compiled.memory_analysis()
        pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())
        assert mem.alias_size_in_bytes >= pool_bytes > 5.06e9, mem
        assert mem.temp_size_in_bytes < 1e6, mem
        offenders, largest = chip_smoke.pool_sized_results(
            compiled.as_text(), pool["ck"].size // Lk)
        assert offenders == [], (offenders, largest)
        return
    if what == "chunk":
        ids, lens = sds((1, 512), jnp.int32), sds((1,), jnp.int32)
        pk = sds((Lk, cfg.kv_heads, 32768, cfg.head_dim), cfg.dtype)
        state = sds((Ll, 1, cfg.n_heads, cfg.head_dim, cfg.head_dim),
                    jnp.float32)
        compiled = jax.jit(
            lambda p, suf, k, v, p0, n, a: T.prefill_with_prefix(
                p, suf, {"k": k, "v": v, "lin": a}, p0, cfg,
                true_len=n)).lower(
                    params, ids, pk, pk, sds((), jnp.int32), lens,
                    state).compile()
    else:
        ids, lens = sds((2, 512), jnp.int32), sds((2,), jnp.int32)
        compiled = jax.jit(
            lambda p, pr, n: T.prefill(p, pr, T.init_cache(cfg, 2, 512),
                                       cfg, true_len=n)).lower(
                params, ids, lens).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.3e9, mem
    # a row of logits a request, and the block: K, V of 3 layers' 512
    # rows, their pages' compressed keys, 9 float32 states a request
    assert mem.output_size_in_bytes < 0.05e9, mem


@pytest.mark.parametrize("half", ["linear", "block_sparse", "feed"])
def test_the_linear_sparse_cells_reference_fits_the_chip_in_one_width(
        one_chip, half):
    """The benchmark's own float32 reference of `minicpm-sala-serve`
    (``chipbench/reference_linear_sparse.py``), a sequence of any length
    laid in the engine's 28 672 rows: each kind's first half of a layer
    and the MLP compile for the v5e with their temporaries inside the
    chip (the engine is gone by then), the length a traced scalar —
    three executables a mode whatever the seed draws; the recurrence and
    the rows' blocks are loops."""
    from chipbench import reference_linear_sparse as R
    from chipbench import weights_linear_sparse as W

    dims, _ = _minicpm_sala()
    key = R._layer_dims(dims)
    S_ = dims["engine"]["max_len"]
    x = _on(one_chip, jax.ShapeDtypeStruct((S_, dims["hidden_size"]),
                                           jnp.float32))
    n = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))

    def leaves(kind, names=None):
        return _on(one_chip, jax.eval_shape(lambda: {
            k: jnp.zeros(s, jnp.float32 if how == "decay" else jnp.bfloat16)
            for k, (s, how) in W.layer_shapes(dims, kind).items()
            if (k in R._FEED_LEAVES) == (names is R._FEED_LEAVES)}))

    with jax.default_matmul_precision("highest"):
        if half == "feed":
            lowered = R._feed_fn(key, "f32", 512).lower(
                x, leaves("linear", R._FEED_LEAVES), n)
        else:
            lowered = R._mix_fn(key, half, "f32", 512, True).lower(
                x, leaves(half), n,
                _on(one_chip, jax.ShapeDtypeStruct((S_,), jnp.bool_)))
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 6e9, mem
    assert "while" in compiled.as_text()    # the rows below n, no more
