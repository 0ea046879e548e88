"""Tensor-parallel serving replicas (ISSUE 15): the compiled engine
tick under GSPMD over a ``tp`` mesh.

The gold checks:

* a tp=2 engine (forced multi-device CPU — the
  ``tests/test_gspmd_multiprocess.py`` trick, armed process-wide by
  conftest's 8 virtual devices) serves greedy AND sampled output
  TOKEN-IDENTICAL to the tp=1 oracle, with ZERO decode recompiles
  across churn — sharding is an annotation on the same executables,
  so the live set, page tables, and sampling columns stay data;
* the compiled tick really is sharded: the lowered HLO carries the
  head-gather/psum collectives XLA inserted;
* sharding edge cases are TYPED config errors at engine construction
  (head count not divisible by tp, tp without paging, tp past the
  visible device count) — never an XLA shape crash;
* bf16/int8 page pools shard cleanly (int8 scales ride the same head
  split), COW prefix register/attach works under tp, and chunked
  prefill / speculative decoding / restart-resume each compose with
  the tp mesh token-identically;
* the ``/stats`` routing contract grows typed ``tp`` + ``mesh`` keys
  and the registry surfaces them;
* (chaos drill) SIGKILL a tp=2 replica mid-stream behind the router →
  journal-resumed on a SURVIVING tp replica, byte-identical tokens,
  gapless SSE indices.
"""

import dataclasses
import http.client
import json
import os
import signal
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T
from horovod_tpu.serving import sse
from horovod_tpu.serving.sharding import (
    ServingSharding,
    ShardingConfigError,
    make_tp_mesh,
)
from horovod_tpu.serving.router import (
    ReplicaRegistry,
    ReplicaSpec,
    ReplicaSupervisor,
    RouterServer,
)

pytestmark = pytest.mark.tp


def _cfg(**kw):
    base = T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=48, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)
    return dataclasses.replace(base, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def _engine(params, cfg, tp, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", cfg.max_seq)
    kw.setdefault("max_prefills_per_tick", 2)
    return serving.InferenceEngine(
        params, cfg, serving.EngineConfig(tp=tp, **kw))


def _drive(eng, reqs):
    """Submit ``(prompt, max_new, kwargs)`` triples, step to
    completion, return the per-request token lists."""
    futs = [eng.submit(p, max_new_tokens=n, **kw) for p, n, kw in reqs]
    while not all(f.done() for f in futs):
        eng.step()
    return [f.result() for f in futs]


# ---------------------------------------------------------------------------
# typed configuration errors (never an XLA shape crash)
# ---------------------------------------------------------------------------


class TestTpConfig:
    def test_heads_not_divisible_is_typed(self, model):
        params, cfg = model  # n_heads=4
        with pytest.raises(ShardingConfigError, match="n_heads"):
            _engine(params, cfg, tp=3)

    def test_kv_heads_not_divisible_is_typed(self):
        cfg = _cfg(n_heads=4, n_kv_heads=1)  # MQA: 1 kv head
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ShardingConfigError, match="kv_heads"):
            _engine(params, cfg, tp=2)

    def test_tp_past_device_count_is_typed(self):
        # Heads divide by 16, the 8 forced devices (conftest) do not.
        cfg = _cfg(n_heads=16, n_kv_heads=16, d_model=64)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ShardingConfigError, match="devices"):
            _engine(params, cfg, tp=16)

    def test_tp_zero_is_typed(self, model):
        params, cfg = model
        with pytest.raises(ShardingConfigError, match=">= 1"):
            _engine(params, cfg, tp=0)

    def test_mesh_helper_validates_device_list(self):
        with pytest.raises(ShardingConfigError, match="exactly"):
            make_tp_mesh(2, jax.devices()[:1])


# ---------------------------------------------------------------------------
# the sharded executable really is sharded
# ---------------------------------------------------------------------------


class TestTpCollectives:
    def test_sharded_decode_tick_emits_tp_collectives(self, model):
        """Lower the paged decode tick under the engine's exact in/out
        shardings and assert XLA inserted the tp collectives — the
        paper's negotiate/fuse/launch thread, compiled into the
        program."""
        params, cfg = model
        sh = ServingSharding(cfg, 2)
        params_tp = sh.shard_params(params)
        S, ps, n_pages, max_pages = 4, 8, 9, 6
        pool = serving.init_page_pool(cfg, S, n_pages, ps)
        pool = T.shard_kv_pool(pool, sh.mesh)
        table = jnp.zeros((S, max_pages), jnp.int32)
        active = jnp.zeros((S,), bool)
        tokens = jnp.zeros((S,), jnp.int32)
        R = sh.replicated
        poolsh = sh.pool_shardings(False)

        fn = jax.jit(
            lambda p, t, a, tb, pl: T.decode_step_paged(
                p, t, pl, tb, cfg, a),
            in_shardings=(sh.param_shardings(), R, R, R, poolsh),
            out_shardings=(R, poolsh))
        hlo = fn.lower(params_tp, tokens, active, table,
                       pool).compile().as_text()
        assert "all-reduce" in hlo or "all-gather" in hlo, (
            "tp decode tick must carry tp collectives")

    def test_the_picks_branches_hold_no_collective(self, model):
        """The tick with the engine's pick, under the engine's shardings:
        GSPMD leaves the logits sharded over the vocabulary, and a pick
        that took them so would sort with all-to-alls UNDER its
        conditionals — where two devices of one execution can wait for
        each other (XLA:CPU's rendezvous: seen as a 40 s stall and an
        abort under load).  Told the replicated sharding, every branch
        runs whole on each device; left untold, the collectives are
        there (the reader is not blind)."""
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import chip_smoke
        from horovod_tpu.serving.engine import InferenceEngine

        params, cfg = model
        sh = ServingSharding(cfg, 2)
        S, ps = 4, 8
        pool = T.shard_kv_pool(serving.init_page_pool(cfg, S, 9, ps),
                               sh.mesh)
        R = sh.replicated
        poolsh = sh.pool_shardings(False)

        def under_branches(replicated):
            def tick(p, t, a, tb, pl, s_t, s_k, s_p, s_key):
                logits, out = T.decode_step_paged(p, t, pl, tb, cfg, a)
                return InferenceEngine._pick(
                    logits, pl["pos"], a, s_t, s_k, s_p, s_key,
                    replicated=replicated), out

            hlo = jax.jit(
                tick,
                in_shardings=(sh.param_shardings(), R, R, R, poolsh,
                              R, R, R, R),
                out_shardings=((R, R), poolsh)).lower(
                    sh.shard_params(params), jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S,), bool), jnp.zeros((S, 6), jnp.int32),
                    pool, jnp.zeros((S,), jnp.float32),
                    jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.float32),
                    jnp.zeros((S, 2), jnp.uint32)).compile().as_text()
            conditionals, inside, _ = chip_smoke.sorts_by_conditional(
                hlo, chip_smoke._HLO_COLLECTIVES)
            assert conditionals >= 1
            return inside

        assert under_branches(R) == []
        assert under_branches(None) != []


# ---------------------------------------------------------------------------
# the tp=1 oracle A/Bs
# ---------------------------------------------------------------------------


MIXED_REQS = [
    ([1, 2, 3], 6, {}),
    ([5, 6], 7, {"temperature": 0.8, "top_k": 8, "seed": 1}),
    ([7, 8, 9, 10, 11], 8, {}),
    ([2], 6, {"temperature": 1.1, "top_p": 0.9, "seed": 2}),
    ([9, 9, 4], 5, {}),
    ([3, 1], 6, {"temperature": 0.9, "top_k": 4, "top_p": 0.8,
                 "seed": 3}),
]


class TestTpOracle:
    def test_mixed_churn_token_identical_zero_recompiles(self, model):
        """ACCEPTANCE: greedy AND sampled requests churning through a
        tp=2 engine produce token-identical output to the tp=1 oracle
        engine, and the decode tick never recompiles after warmup —
        sharding changed the placement, not the program."""
        params, cfg = model
        out, recompiles = {}, {}
        for tp in (1, 2):
            eng = _engine(params, cfg, tp)
            eng.warmup([4, 8])
            warm = eng.decode_compilations
            out[tp] = _drive(eng, MIXED_REQS)
            recompiles[tp] = eng.decode_compilations - warm
        assert out[2] == out[1]
        assert recompiles[2] == 0, (
            f"tp decode recompiled {recompiles[2]}x across churn")

    def test_stats_contract_grows_tp_and_mesh(self, model):
        """/stats carries typed tp (int) + mesh (str) keys — the
        routing-contract growth — and the serving_tp_degree gauge
        tracks the configured degree."""
        params, cfg = model
        eng = _engine(params, cfg, tp=2)
        snap = eng.stats()
        assert snap["tp"] == 2 and isinstance(snap["tp"], int)
        assert isinstance(snap["mesh"], str) and "tp=2" in snap["mesh"]
        assert eng.metrics.tp_degree.value == 2

        eng1 = _engine(params, cfg, tp=1)
        snap1 = eng1.stats()
        assert snap1["tp"] == 1 and snap1["mesh"] == ""
        assert eng1.metrics.tp_degree.value == 1

    def test_registry_surfaces_tp_and_mesh(self, model):
        """The registry's poll parses the new contract keys and the
        per-replica fleet view (status.as_dict, what the router's
        /stats replicas dict serves) carries them."""
        params, cfg = model
        eng = _engine(params, cfg, tp=2)
        srv = serving.ServingServer(eng, port=0).start()
        try:
            host, port = srv.address
            reg = ReplicaRegistry()
            from horovod_tpu.serving.router.registry import (
                ReplicaEndpoint,
            )
            reg.add(ReplicaEndpoint("r0g0", host, port))
            reg.poll_now()
            st = reg.statuses()[0]
            assert st.tp == 2
            assert "tp=2" in st.mesh
            d = st.as_dict()
            assert d["tp"] == 2 and "tp=2" in d["mesh"]
        finally:
            srv.stop(drain_timeout=5.0)


class TestTpKvDtypes:
    # bf16 is slow (PR 17 budget pass): int8 exercises the stricter
    # path (payload + per-vector scales both sharded) and stays
    # tier-1; each dtype's tp=1 behavior is covered in test_paged.
    @pytest.mark.parametrize(
        "kv_dtype",
        [pytest.param("bf16", marks=pytest.mark.slow), "int8"])
    def test_quantized_pools_shard_cleanly(self, model, kv_dtype):
        """bf16/int8 page pools under tp: the payload (and, for int8,
        the per-vector scales) ride the same head sharding, and output
        matches the tp=1 engine at the SAME kv_dtype (int8 is lossy vs
        f32, but deterministic — the oracle is the same-dtype tp=1
        engine)."""
        params, cfg = model
        out = {}
        for tp in (1, 2):
            eng = _engine(params, cfg, tp, kv_dtype=kv_dtype)
            eng.warmup([4])
            out[tp] = _drive(eng, MIXED_REQS[:4])
        assert out[2] == out[1]


class TestTpPrefix:
    @pytest.mark.slow
    def test_prefix_register_attach_cow_under_tp(self, model):
        # Slow (PR 17 budget pass): two engines + three sharer
        # admission shapes are ~13 s; the tp mixed-churn oracle stays
        # tier-1 and the COW ladder is covered at tp=1 in test_paged.
        """COW prefix sharing under tp: register a shared prefix (one
        prefill into head-sharded pinned pages), admit sharers that
        attach / suffix-prefill / COW-split its last page — output
        token-identical to the tp=1 engine doing the same."""
        params, cfg = model
        prefix = [7, 3, 5, 9, 2, 4, 6, 8, 1]  # 9 tokens: partial page
        reqs = [
            (prefix, 6, {}),                     # attach-only
            (prefix + [1, 2], 6, {}),            # suffix + COW split
            (prefix + [3], 5, {"temperature": 0.8, "seed": 3}),
            ([1, 2, 3], 6, {}),                  # no prefix
        ]
        out, shared = {}, {}
        for tp in (1, 2):
            eng = _engine(params, cfg, tp, page_size=4)
            eng.register_prefix(prefix)
            eng.warmup([4])
            # The registered prefix's pages really are pinned+shared.
            shared[tp] = eng.slots.pages_shared
            out[tp] = _drive(eng, reqs)
        assert out[2] == out[1]


class TestTpCompose:
    @pytest.mark.paged_kernel
    def test_fused_paged_kernel_under_tp(self, model):
        """The fused Pallas paged-attention kernel under a tp=2 mesh
        (int8 pool — the full spec set: head-sharded pages AND
        per-vector scales through ``paged_kernel_specs``): the kernel
        runs shard-locally per kv-head inside the tick's shard_map, and
        the tp=2 fused engine emits tokens identical to the tp=1
        UNFUSED int8 oracle, with zero decode recompiles across
        churn."""
        params, cfg = model
        reqs = [([3, 5, 7], 8, {}), ([11, 2], 6, {})]
        oracle = _engine(params, cfg, 1, kv_dtype="int8",
                         paged_kernel=False)
        oracle.warmup([4])
        want = _drive(oracle, reqs)

        eng = _engine(params, cfg, 2, kv_dtype="int8",
                      paged_kernel=True)
        eng.warmup([4])
        warm = eng.decode_compilations
        got = _drive(eng, reqs)
        assert got == want
        assert eng.decode_compilations - warm == 0
        assert eng.stats()["paged_kernel_engaged"] is True
        assert oracle.stats()["paged_kernel_engaged"] is False

    @pytest.mark.parametrize("kv,kernel", [(None, True), ("int8", False)])
    def test_sharded_pool_bytes_match_a_page_offset_writer(
            self, model, monkeypatch, kv, kernel):
        """The pool's write discipline on a head-sharded pool
        (``write_pages`` under GSPMD, the kernel's stacked operand under
        ``shard_map``): a landing with bucket padding, a suffix that
        starts mid-page, then ticks with an idle slot and a page
        crossed — every pool array, gathered from both shards, is byte
        for byte what a plain ``(page, offset)`` writer produces."""
        from conftest import KVSpy, PoolMirror

        params, cfg = model
        sh = ServingSharding(cfg, 2)
        params = {**params, "layers": {
            **params["layers"], "ln1": params["layers"]["ln1"]
            + 0.01 * jnp.arange(cfg.n_layers)[:, None]}}
        params_tp = sh.shard_params(params)
        S, ps = 3, 4
        rng = np.random.default_rng(5)
        spy = KVSpy(monkeypatch, ordered=False)   # two devices
        pc = serving.PagedSlotCache(cfg, S, max_len=24, page_size=ps,
                                    n_pages=14, kv_dtype=kv, mesh=sh.mesh)
        poolsh = sh.pool_shardings(pc.quantized)
        pc.cache = {n: a if n == "pos" else jax.device_put(
            jnp.asarray(rng.integers(-90, 90, a.shape), a.dtype), poolsh[n])
            for n, a in pc.cache.items()}
        mirror = PoolMirror(pc.cache, ps)

        def land(slots, lens, start, bucket):
            for s, n in zip(slots, lens):
                for idx in range(start // ps, -(-(start + n) // ps)):
                    if pc.table[s, idx] == serving.cache.NULL_PAGE:
                        pc.grant(s, idx)
            shape = (cfg.n_layers, len(slots), cfg.kv_heads, bucket,
                     cfg.head_dim)
            blk = {x: jax.device_put(
                jnp.asarray(rng.standard_normal(shape), jnp.float32),
                sh.prefill_cache_shardings()[x]) for x in "kv"}
            blk["pos"] = jnp.asarray([start + n for n in lens])
            pc.land(slots, blk, lens, start=start)
            mirror.land(("k", "v"), [pc.table[s].copy() for s in slots],
                        start, lens, blk["k"], blk["v"])

        a, b = pc.alloc(), pc.alloc()
        land([a, b], [6, 3], 0, 8)       # padded rows of one landing
        land([a], [5], 6, 8)             # a suffix from mid-page
        R = sh.replicated
        tick = jax.jit(
            lambda p, tok, pool, table, active: T.decode_step_paged(
                p, tok, pool, table, cfg, active, kernel=kernel,
                mesh=sh.mesh if kernel else None)[1],
            in_shardings=(sh.param_shardings(), R, poolsh, R, R),
            out_shardings=poolsh)
        for step in range(3):
            pos, active = pc.positions(), pc.active_mask()
            active[a] &= step != 1
            for s in np.nonzero(active)[0]:
                if pc.table[s, pos[s] // ps] == serving.cache.NULL_PAGE:
                    pc.grant(s, pos[s] // ps)
            table = pc.table.copy()
            pc.cache = tick(params_tp,
                            jnp.asarray(rng.integers(0, 64, S), jnp.int32),
                            pc.cache, jnp.asarray(table),
                            jnp.asarray(active))
            calls = spy.take()
            assert len(calls) == cfg.n_layers
            for l, (_, _, k, v) in enumerate(calls):
                mirror.write(("k", "v"), l, table, pos, active[:, None],
                             k, v)
        assert pc.positions().tolist() == [13, 6, 0]    # b crossed 4
        assert pc.cache["k"].sharding.spec == poolsh["k"].spec
        mirror.assert_holds(pc.cache)

    @pytest.mark.slow
    def test_chunked_prefill_under_tp(self, model):
        # Slow (PR 17 budget pass): oracle + tp engine pair is ~8 s;
        # the tp mixed-churn oracle and restart-resume-under-tp stay
        # tier-1, chunking itself is covered at tp=1 in test_sched.
        """Chunked ingestion through the sharded
        ``prefill_with_prefix`` executable: a tp=2 engine ingesting a
        long prompt chunk by chunk matches the tp=1 whole-prompt
        oracle, with zero decode recompiles."""
        params, cfg = model
        rng = np.random.default_rng(0)
        long_prompt = [int(x) for x in rng.integers(0, 64, 30)]
        oracle = _engine(params, cfg, 1)
        oracle.warmup([4])
        want = _drive(oracle, [(long_prompt, 8, {}), ([1, 2], 6, {})])

        eng = _engine(params, cfg, 2, prefill_chunk_tokens=8)
        eng.warmup([4])
        warm = eng.decode_compilations
        got = _drive(eng, [(long_prompt, 8, {}), ([1, 2], 6, {})])
        assert got == want
        assert eng.decode_compilations - warm == 0

    @pytest.mark.slow
    def test_speculative_under_tp(self, model):
        # Slow (PR 17 budget pass): spec tp engine + tp=1 oracle is
        # ~8 s; the tp mixed-churn oracle stays tier-1 and the verify
        # tick is covered at tp=1 in test_speculative.
        """The sharded ``decode_verify_paged`` tick: a speculative
        (n-gram draft) tp=2 engine emits byte-identical tokens to the
        plain tp=1 oracle — greedy, repetitive (high acceptance), and
        sampled (acceptance forced to 0 as data) rows alike."""
        params, cfg = model
        reqs = [([5, 6, 5, 6, 5], 8, {}), ([1, 2, 3], 6, {}),
                ([9, 9], 5, {"temperature": 1.0, "seed": 2})]
        oracle = _engine(params, cfg, 1)
        oracle.warmup([4])
        want = _drive(oracle, reqs)

        eng = _engine(params, cfg, 2, speculative=True, spec_k=3)
        eng.warmup([4])
        warm = eng.decode_compilations
        got = _drive(eng, reqs)
        assert got == want
        assert eng.decode_compilations - warm == 0

    def test_restart_resume_under_tp(self, model):
        """Durability composes: a deterministic mid-decode crash on a
        tp=2 engine restart-RESUMES its in-flight requests (fresh
        sharded pool, re-prefill of prompt+emitted through the sharded
        executables) byte-identical to the no-fault tp=1 oracle."""
        params, cfg = model
        reqs = [([3, 4, 5], 10, {}),
                ([8, 1], 8, {"temperature": 0.9, "seed": 11})]
        oracle = _engine(params, cfg, 1)
        oracle.warmup([4])
        want = _drive(oracle, reqs)

        inj = serving.FaultInjector()
        eng = _engine(params, cfg, 2, resume=True, restart_backoff=0.01,
                      faults=inj)
        eng.warmup([4])
        inj.add(serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=inj.visits("decode_tick") + 3))
        got = _drive(eng, reqs)
        assert got == want
        assert eng.metrics.resumed.value >= 1


# ---------------------------------------------------------------------------
# the front tier: N tp-K replicas behind the router (chaos drill)
# ---------------------------------------------------------------------------


def _oracle(params, cfg, prompt, steps, *, temperature=0.0, top_k=0,
            top_p=0.0, seed=0):
    return np.asarray(T.sample_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg,
        rng=jax.random.PRNGKey(seed), temperature=temperature,
        top_k=top_k, top_p=top_p))[0].tolist()


def _post(host, port, body, timeout=60, headers=None):
    c = http.client.HTTPConnection(host, port, timeout=timeout)
    c.request("POST", "/generate", body=json.dumps(body).encode(),
              headers=headers or {})
    return c, c.getresponse()


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.router
class TestTpFrontTierChaos:
    def test_sigkill_tp2_replica_mid_stream_resumes_on_tp_survivor(
            self, model):
        """ACCEPTANCE: SIGKILL a tp=2 replica while it streams a
        SAMPLED request.  The router reads the dead replica's journal
        post-mortem and continues on the SURVIVING tp=2 replica —
        gapless SSE indices, token sequence byte-identical to the
        per-request oracle, ``resumed: true`` on the done event.
        Mesh ownership is per-process (disjoint device sets from the
        supervisor), so failover/resume/streaming ride unchanged."""
        params, cfg = model
        spec = ReplicaSpec(seed=0, tp=2, slots=4, warm=(8,),
                           tick_timeout=30.0, drain_timeout=3.0,
                           request_timeout=90.0)
        reg = ReplicaRegistry(poll_interval=0.15, poll_timeout=1.0,
                              heartbeat_stale=5.0)
        journal_dir = tempfile.mkdtemp(prefix="tp_chaos_")
        sup = ReplicaSupervisor(spec, 2, registry=reg,
                                unhealthy_grace=1.5,
                                shutdown_grace=2.0,
                                backoff_initial=0.1,
                                journal_dir=journal_dir)
        rt = RouterServer(reg, port=0, max_attempts=4,
                          retry_backoff=0.05, proxy_timeout=120.0,
                          resume_lookup=sup.resume_lookup)
        sup.start()
        rt.start()
        try:
            assert sup.wait_ready(timeout=240), "tp replicas never ready"
            # Both replicas really are tp=2 meshes (contract keys
            # through a real subprocess poll).
            for st in reg.in_rotation():
                assert st.tp == 2 and "tp=2" in st.mesh
            host, port = rt.address
            steps = 40
            trace = "a" * 16
            kill_done = threading.Event()

            def kill_streaming_replica():
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    for h in sup.replicas():
                        try:
                            live = serving.RequestJournal.read_live(
                                sup._journal_paths[h.rid])
                        except Exception:
                            continue
                        d = live.get(trace)
                        if (d is not None and
                                5 <= len(d["emitted_tokens"])
                                <= steps - 15):
                            os.kill(h.pid, signal.SIGKILL)
                            kill_done.set()
                            return
                    time.sleep(0.01)

            killer = threading.Thread(target=kill_streaming_replica,
                                      daemon=True)
            c, r = _post(host, port,
                         {"tokens": [9, 11], "max_new_tokens": steps,
                          "temperature": 1.1, "seed": 5,
                          "timeout_ms": 90000, "stream": True},
                         timeout=120, headers={"X-Trace-Id": trace})
            assert r.status == 200
            killer.start()
            events = sse.read_stream(r)
            c.close()
            killer.join(5.0)
            assert kill_done.is_set(), \
                "the kill never landed mid-stream (request too fast?)"
            done = [p for k, p in events if k == "done"]
            assert len(done) == 1, f"expected one done event: {events}"
            done = done[0]
            want = _oracle(params, cfg, [9, 11], steps,
                           temperature=1.1, seed=5)
            idx = [p["i"] for k, p in events if k == "token"]
            toks = [p["token"] for k, p in events if k == "token"]
            assert idx == list(range(steps)), \
                "duplicated or dropped token events across the kill"
            assert toks == want
            assert done["tokens"] == want
            assert done.get("resumed") is True
            assert reg.metrics.resume_failovers.value >= 1
        finally:
            rt.stop()
            sup.stop(drain=False)
