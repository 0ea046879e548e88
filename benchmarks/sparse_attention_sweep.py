"""Time the parts of learned sparse attention ALONE at the served
shapes, on the chip (``deepseek-v3.2-exp-serve``: 128 heads over rows of
640, 64 index heads of 128, ``index_topk`` 2048, pages of 16): the
numbers behind PERF.md's PR 32 tables.

A TICK's part, one layer, 24 slots at a ladder of contexts: the index
walk (``hvd_dsa_score``), the selection — the counting form
(``select_topk``) against ``lax.top_k`` — the token-granular gather of
the selected rows out of the pool, and their attend
(``hvd_dsa_attend``); beside them the dense walk of the same contexts
(``hvd_mla_decode``).  The selected parts must NOT grow with context.

A CHUNK's part, one layer, 512 queries against 8 k and 28 k landed
tokens: the selected form as shipped (scores, selection, gather,
attend: ``_dsa_chunk_attend``) against the dense expanded form
(``_mla_chunk_attend``, the flash kernel over every landed row — what a
MASKED form would cost at the least, its mask on top).

Wall time per call, the device drained before and after (a call is one
jitted function; 5 calls after 2 warm-ups).

    chiprun -- python benchmarks/sparse_attention_sweep.py

It needs a TPU and has no CPU mode.  Last stdout line: one JSON object.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, *args, n=5):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as T
    from horovod_tpu.ops import paged_attention as PA

    if jax.default_backend() != "tpu":
        print("sparse_attention_sweep: needs a TPU", file=sys.stderr)
        return 2
    hvd.place_compile_cache()
    cfg = T.TransformerConfig(
        vocab_size=1024, d_model=7168, n_heads=128, n_layers=1, d_ff=1024,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, index_n_heads=64,
        index_head_dim=128, index_topk=2048,
        rope_yarn=(40.0, 4096.0, 32.0, 1.0, 1.0, 1.0), max_seq=32768,
        dtype=jnp.bfloat16, attention_impl="flash")
    S, ps, max_len = 24, 16, 32768
    n_pages = S * max_len // ps
    key = jax.random.PRNGKey(0)
    bf = jnp.bfloat16
    pool = jax.random.normal(key, (1, n_pages + 1, 1, ps, 640), bf)
    ik_pool = jax.random.normal(key, (1, n_pages + 1, 1, ps, 128), bf)
    table = jnp.asarray(1 + np.random.default_rng(0).permutation(
        n_pages).reshape(S, max_len // ps), jnp.int32)
    q = jax.random.normal(key, (S, 128, 640), bf)
    qi = jax.random.normal(key, (S, 64, 128), bf)
    w = jax.random.normal(key, (S, 64), jnp.float32)
    layer = jnp.int32(0)
    out = {"tick": {}, "chunk": {}}

    walk = jax.jit(lambda lim: PA.index_scores(qi, w, ik_pool, table, lim,
                                               layer=layer))
    pick = jax.jit(lambda sc, lim: PA.select_topk(sc, lim, 2048))
    sort = jax.jit(lambda sc: jax.lax.top_k(sc, 2048)[1])
    lat = pool.reshape(-1, 640)

    def rows_of(idx):
        page = PA.pages_of(table, idx, ps)
        return lat[page * ps + idx % ps]

    gather = jax.jit(rows_of)
    attend = jax.jit(lambda rows, cnt: PA.selected_attend(
        q, rows, cnt, v_dim=512, sm_scale=cfg.mla_scale, kernel=True)[0])
    dense = jax.jit(lambda lim: PA.mla_decode(
        q, pool, table, lim, v_dim=512, sm_scale=cfg.mla_scale,
        layer=layer)[0])
    for ctx in (2048, 8192, 17408, 32768):
        lim = jnp.full((S,), ctx, jnp.int32)
        sc = walk(lim)
        idx, cnt = pick(sc, lim)
        want = np.sort(np.asarray(sort(sc[:, :ctx])), axis=1)
        assert (np.asarray(idx) == want).all(), "select_topk != lax.top_k"
        rows = gather(idx)
        out["tick"][ctx] = {
            "index_walk_ms": _time(walk, lim),
            "select_count_ms": _time(pick, sc, lim),
            "select_sort_ms": _time(sort, sc),
            "gather_ms": _time(gather, idx),
            "attend_ms": _time(attend, rows, cnt),
            "dense_walk_ms": _time(dense, lim)}
        print(f"[sweep] tick ctx {ctx}: " + json.dumps(out["tick"][ctx]),
              flush=True)

    p = jax.tree_util.tree_map(
        lambda a: a[0].astype(bf), T.init_params(key, cfg)["layers"])
    K, S0 = 1, 512
    q_nope = jax.random.normal(key, (K, S0, 128, 128), bf)
    q_rope = jax.random.normal(key, (K, S0, 128, 64), bf)
    cqi = jax.random.normal(key, (K, S0, 64, 128), bf)
    cw = jax.random.normal(key, (K, S0, 64), jnp.float32)
    own = jax.random.normal(key, (K, S0, 640), bf)
    own_ik = jax.random.normal(key, (K, S0, 128), bf)
    sel = jax.jit(lambda pl, pik, p0: T._dsa_chunk_attend(
        T._mla_absorb_q(q_nope, q_rope, p, cfg), cqi, cw, own, own_ik, pl,
        pik, p0, cfg))
    exp = jax.jit(lambda pl, p0: T._mla_chunk_attend(
        q_nope, q_rope, own, pl, p0, p, cfg))
    score = jax.jit(lambda keys: PA.index_scores_rows(cqi[0], cw[0], keys,
                                                      kernel=True))
    for landed, P0 in ((8192, 8192), (28672, 32768)):
        pl = jax.random.normal(key, (P0, 640), bf)
        pik = jax.random.normal(key, (P0, 128), bf)
        p0 = jnp.int32(landed)
        out["chunk"][landed] = {
            "selected_ms": _time(sel, pl, pik, p0, n=3),
            "scores_alone_ms": _time(score, jnp.pad(pik, ((0, S0), (0, 0))),
                                     n=3),
            "dense_expanded_ms": _time(exp, pl, p0, n=3)}
        print(f"[sweep] chunk landed {landed}: "
              + json.dumps(out["chunk"][landed]), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
