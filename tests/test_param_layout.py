"""The engine's own parameter tree (ISSUE 37): an engine lays the
standard attention block's ``wq``/``wk``/``wv`` out once, at load, from a
checkpoint's ``(L, D, H, Dh)`` to ``(L, D, H * Dh)`` — what the product
over ``D`` reads — and serves from that tree, while the caller's tree
stays as it was.

Held here, on the CPU: whatever the configuration (uniform, a window
pattern with ``qk_norm``, ``tp=2``, a speculative draft model, latent
attention) the engine serves the tokens and the logits the model's own
whole-sequence programs give on the CALLER's tree; that tree is
bit-identical afterwards and still usable; ``/stats``
``params_relaid_bytes`` counts the leaves laid out (0 where there are
none); ``_qkv_proj`` tells the two forms apart by the leaf's rank alone;
and the tp specs follow the leaf's rank.  That the TPU compiler then
copies no leaf is ``tests/test_tpu_aot.py``'s to hold."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu import serving
from horovod_tpu.models import transformer as T

pytestmark = pytest.mark.serving

V = 64
BASE = T.TransformerConfig(
    vocab_size=V, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64, dtype=jnp.float32, attention_impl="reference")
DRAFT = dataclasses.replace(BASE, n_layers=1)
CONFIGS = {
    "uniform": BASE,
    "window_qk_norm": dataclasses.replace(
        BASE, n_layers=4, qk_norm=True, window=8,
        layer_pattern=("sliding", "full"), attention_impl="flash"),
    "tp2": BASE,
    "spec_draft": BASE,
    "latent": T.TransformerConfig(
        vocab_size=V, d_model=48, n_heads=4, n_layers=2, d_ff=96,
        max_seq=64, dtype=jnp.float32, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        attention_impl="flash"),
}
ENGINE = {
    "tp2": dict(tp=2),
    "spec_draft": dict(speculative=True, spec_k=3, spec_draft="model"),
}
PROJ = ("wq", "wk", "wv")


def _params(cfg, seed=0):
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    k = jax.random.PRNGKey(seed + 1)
    for i, name in enumerate(("ln1", "ln2", "q_norm", "k_norm")):
        if name in params["layers"]:    # not ones: a norm that scales
            a = params["layers"][name]
            params["layers"][name] = 1.0 + 0.1 * jax.random.normal(
                jax.random.fold_in(k, i), a.shape)
    return params


def _snapshot(tree):
    return jax.tree_util.tree_map(lambda a: np.array(a), tree)


def _same(a, b):
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)


def _proj_bytes(params):
    return sum(params["layers"][n].nbytes for n in PROJ
               if n in params["layers"])


def _reference_logits(params, cfg, seqs, lens):
    """The last-real-position logits of each row of ``seqs`` on the
    CALLER's tree: ``T.forward`` where the configuration is one it
    computes, else the whole-prompt ``T.prefill``."""
    if not cfg.has_window:
        full = np.asarray(T.forward(params, jnp.asarray(seqs), cfg))
        return full[np.arange(len(lens)), np.asarray(lens) - 1]
    cache = {"pos": jnp.zeros((), jnp.int32),
             "k": jnp.zeros((1, 1, 1, seqs.shape[1], 1))}
    return np.asarray(T.prefill(params, jnp.asarray(seqs), cache, cfg,
                                true_len=jnp.asarray(lens))[0])


def _first_logits(engine):
    """Each request's first-token logits as the engine computed them
    (the admission's or the last chunk's), by ``id(future)``."""
    rows, first = {}, engine._first_tokens

    def tap(reqs, logits):
        for r, row in zip(reqs, np.asarray(logits)):
            rows[id(r.future)] = row
        return first(reqs, logits)

    engine._first_tokens = tap
    return rows


@pytest.mark.parametrize("case", sorted(CONFIGS))
def test_an_engine_serves_a_checkpoints_tree_from_a_tree_of_its_own(case):
    cfg = CONFIGS[case]
    params = _params(cfg)
    before = _snapshot(params)
    leaves = {n: params["layers"].get(n) for n in PROJ}
    draft = {}
    if case == "spec_draft":
        draft = dict(draft_params=_params(DRAFT, 7), draft_cfg=DRAFT)
        draft_before = _snapshot(draft["draft_params"])
    engine = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**{**dict(
            n_slots=3, max_len=48, page_size=4, prefill_chunk_tokens=8,
            max_prefills_per_tick=2, min_prefill_bucket=4, overlap=False),
            **ENGINE.get(case, {})}), **draft)

    # the engine's tree: the three leaves re-laid, every other leaf the
    # caller's own array (placed on the mesh under tp)
    own = engine.params["layers"]
    want_bytes = _proj_bytes(params)
    if cfg.latent:
        assert want_bytes == 0 and not set(PROJ) & set(own)
    else:
        for n in PROJ:
            L, D, H, Dh = params["layers"][n].shape
            assert own[n].shape == (L, D, H * Dh)
            np.testing.assert_array_equal(
                np.asarray(own[n]),
                np.asarray(params["layers"][n]).reshape(L, D, H * Dh))
    if case == "tp2":   # whole, contiguous heads a device
        assert own["wq"].sharding.spec == P(None, None, "tp")
        assert own["wo"].sharding.spec == P(None, "tp", None, None)
    else:
        assert own["wo"] is params["layers"]["wo"]
        assert engine.params["embed"] is params["embed"]
    if draft:
        want_bytes += _proj_bytes(draft["draft_params"])
        assert engine.draft_params["layers"]["wq"].ndim == 3
    assert engine.stats()["params_relaid_bytes"] == want_bytes

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, V, n).tolist() for n in (5, 11, 19)]
    new = 6
    firsts = _first_logits(engine)
    futs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    for _ in range(400):
        if all(f.done() for f in futs):
            break
        engine.step()
    served = [f.result() for f in futs]
    assert all(len(t) == new for t in served)

    # the caller's tree: the same objects, the same bits, and usable
    for n in PROJ:
        assert params["layers"].get(n) is leaves[n]
    _same(params, before)
    if draft:
        _same(draft["draft_params"], draft_before)

    # every served token is the pick of the caller's tree, and the
    # first one's logits are that tree's: row (i, j) = prompt i + its
    # first j served tokens
    width = max(len(p) for p in prompts) + new
    seqs, lens, tokens, rows = [], [], [], []
    for i, (p, toks) in enumerate(zip(prompts, served)):
        for j, tok in enumerate(toks):
            s = p + toks[:j]
            seqs.append(s + [0] * (width - len(s)))
            lens.append(len(s))
            tokens.append(tok)
            rows.append(firsts[id(futs[i])] if j == 0 else None)
    ref = _reference_logits(params, cfg, np.asarray(seqs, np.int32), lens)
    assert np.argmax(ref, axis=-1).tolist() == tokens
    for want, got in zip(ref, rows):
        if got is not None:
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("qk_norm", [False, True])
@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_qkv_proj_reads_either_form_by_the_leafs_rank(kind, qk_norm):
    """One layer's projections from a 3-D leaf (training, ``forward``, a
    caller's own tree) and from the engine's 2-D one: the same
    contraction, the same heads."""
    cfg = dataclasses.replace(BASE, qk_norm=qk_norm, window=8,
                              layer_pattern=("sliding", "full"))
    params = _params(cfg)
    relaid, n = T.lay_out_projections(params)
    assert n == _proj_bytes(params)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, cfg.d_model))
    pos = jnp.arange(7)[None] + jnp.asarray([[3], [0]])
    outs = [T._qkv_proj(
        x, jax.tree_util.tree_map(lambda a: a[1], tree["layers"]), cfg,
        positions=pos, kind=kind) for tree in (params, relaid)]
    for a, b in zip(*outs):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_laying_out_shares_every_other_leaf_and_happens_once():
    params = _params(BASE)
    before = _snapshot(params)
    relaid, n = T.lay_out_projections(params)
    assert n == _proj_bytes(params) > 0
    assert relaid is not params and relaid["layers"] is not params["layers"]
    for name, leaf in params["layers"].items():
        if name in PROJ:
            assert relaid["layers"][name].shape == (
                *leaf.shape[:2], leaf.shape[2] * leaf.shape[3])
        else:
            assert relaid["layers"][name] is leaf
    assert relaid["head"] is params["head"]
    _same(params, before)
    # a tree already laid out is taken as it is: nothing to do, 0 bytes
    again, m = T.lay_out_projections(relaid)
    assert m == 0
    assert all(again["layers"][k] is relaid["layers"][k] for k in PROJ)


def test_serving_specs_follow_the_leafs_rank():
    """Under tp a re-laid leaf shards its joined ``H * Dh`` axis (whole,
    contiguous heads a shard); a checkpoint's tree keeps the 4-entry
    spec; no other leaf's spec changes."""
    params = _params(BASE)
    relaid, _ = T.lay_out_projections(params)
    plain = T.serving_param_specs(BASE)
    assert T.serving_param_specs(BASE, params=params) == plain
    fitted = T.serving_param_specs(BASE, params=relaid)
    for name, spec in plain["layers"].items():
        if name in PROJ:
            assert spec == P(None, None, "tp", None)
            assert fitted["layers"][name] == P(None, None, "tp")
        else:
            assert fitted["layers"][name] == spec
    from horovod_tpu.serving.sharding import ServingSharding
    sh = ServingSharding(BASE, 2)
    placed = sh.shard_params(relaid)
    wq = placed["layers"]["wq"]
    assert wq.sharding.spec == P(None, None, "tp")
    H, Dh = BASE.n_heads, BASE.head_dim
    for i, shard in enumerate(sorted(wq.addressable_shards,
                                     key=lambda s: s.index[2].start)):
        np.testing.assert_array_equal(
            np.asarray(shard.data),
            np.asarray(params["layers"]["wq"])[:, :, i * H // 2:
                                               (i + 1) * H // 2].reshape(
                BASE.n_layers, BASE.d_model, H // 2 * Dh))
