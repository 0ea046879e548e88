"""The jaxprs of the three served programs (the paged tick, a chunk
against its landed prefix, a whole-prompt prefill) of the architectures
the benchmark serves, at a small size, as digests: ``python
tests/served_program_digests.py`` prints them as JSON.
``tests/data/served_program_digests_pr38.json`` holds what the tree
BEFORE PR 40 printed for the FOUR served before conv layers came;
``tests/test_conv_layers.py`` holds this tree to it, so every field PR
40 added (``conv_kernel``, ``tie_embeddings``, ``kv_lane_dense``,
``norm_topk_eps``), left off, leaves those programs as they were,
equation for equation.  ``tests/data/served_program_digests_pr41.json``
holds what the tree before PR 42 (a layer of two mixers) printed for
the FIVE, the conv architecture among them; ``tests/test_hybrid_layers
.py`` holds this tree to that.  ONE entry of both files is PR 44's:
``sparse`` / ``tick``, whose selected attend reads each pick's page out
of a product (``ops.paged_attention.pages_of``) where it called
``jnp.take_along_axis``; its chunk and prompt, and every other
architecture's three programs, are the digests those trees printed.
FIVE entries of both files are PR 45's (one mixer a kind): ``latent`` /
``tick``, ``chunk`` and ``sparse`` / ``tick``, ``chunk``, ``prompt``
hold the SAME equations in another order — a tick absorbs its queries
after it has projected the row it writes, and every body lays a layer's
rows out as the block of one kv head (a reshape) where the attention
ends, not after the MLP or before the attention — and the TPU compiler
makes the same program of both (PERF.md section 6, PR 45: the optimised
HLO of all eighteen served programs at the published widths, equal but
for names); the other entries are what the parent printed.
``tests/data/served_program_digests_pr45.json`` holds what the tree
before PR 46 (linear-attention and block-sparse layers) printed for the
SIX, the hybrid architecture among them; ``tests/test_linear_sparse_
layers.py`` holds this tree to that.  ONE entry of that file is PR
47's: ``hybrid`` / ``tick``, whose state update (``ops/ssm.py``'s
kernel) takes as many groups of a slot a grid step as 1 MiB of stored
state holds — at this size both groups, grid ``(S, 1)`` for ``(S, 2)``,
a loop over the groups in the kernel's body — and is otherwise the
equations the parent traced; its chunk and prompt and the other five
architectures' programs are what the parent printed."""

import hashlib
import json
import re

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as T
from horovod_tpu.serving import cache as C

_LATENT = dict(
    vocab_size=96, d_model=48, n_heads=4, n_layers=3, d_ff=96, max_seq=128,
    dtype=jnp.float32, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    rope_yarn=(4.0, 16.0, 32.0, 1.0, 1.0, 1.0), n_dense_layers=1,
    n_experts=16, n_experts_per_tok=4, d_expert=32, n_shared_experts=1,
    moe_score="sigmoid", routed_scaling_factor=2.5, n_group=4, topk_group=2,
    norm_topk_prob=True, moe_impl="dropless", n_experts_held=4,
    expert_offset=4, attention_impl="flash")

CONFIGS = {
    # the standard GQA block (mistral-7b-v0.3-serve)
    "uniform": dict(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2,
                    n_layers=3, d_ff=128, max_seq=128, dtype=jnp.float32,
                    attention_impl="flash"),
    # window and full layers, top-k experts (mellum2-12b-a2.5b-serve)
    "patterned": dict(
        vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, n_layers=8,
        d_ff=32, max_seq=128, n_experts=8, n_experts_per_tok=2, d_expert=32,
        norm_topk_prob=True, d_head=16, qk_norm=True,
        layer_pattern=("sliding", "sliding", "sliding", "full"), window=8,
        rope_yarn=(4.0, 16, 32, 1, 1.1386), dtype=jnp.float32,
        attention_impl="flash", moe_impl="dropless"),
    # latent attention, a dense layer, a share of the experts (axk1-serve)
    "latent": _LATENT,
    # ... with an indexer and a biased router (deepseek-v3.2-exp-serve)
    "sparse": dict(_LATENT, index_n_heads=4, index_head_dim=16,
                   index_topk=12, moe_score_bias=True),
    # conv layers between attention layers whose narrow heads share a
    # stored row, a dense layer, a biased router, a tied head
    # (lfm2-24b-a2b-serve)
    "conv": dict(
        vocab_size=96, d_model=128, n_heads=4, n_kv_heads=2, d_head=64,
        n_layers=5, n_dense_layers=1, d_ff=96, d_expert=48, n_experts=8,
        n_experts_per_tok=2, norm_topk_prob=True, norm_topk_eps=1e-6,
        moe_impl="dropless", moe_score="sigmoid", moe_score_bias=True,
        qk_norm=True, norm_eps=1e-5,
        layer_pattern=("conv", "conv", "full", "conv"), conv_kernel=3,
        tie_embeddings=True, kv_lane_dense=True, max_seq=128,
        dtype=jnp.float32, attention_impl="flash"),
    # attention and a state-space mixer in every layer
    # (falcon-h1-34b-serve)
    "hybrid": dict(
        vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, n_layers=3,
        d_ff=128, max_seq=128, dtype=jnp.float32, attention_impl="flash",
        layer_pattern=("hybrid",), conv_kernel=4, ssm_heads=4,
        ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=4),
    # linear-attention layers between block-sparse attention layers
    # (minicpm-sala-serve)
    "linear_sparse": dict(
        vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=4, d_ff=128, max_seq=128, dtype=jnp.float32,
        attention_impl="flash", qk_norm=True,
        layer_pattern=("block_sparse", "linear", "linear", "block_sparse"),
        bsa_kernel=8, bsa_stride=4,
        bsa_block=8, bsa_topk=2, bsa_window=16, bsa_init_blocks=1,
        bsa_dense_len=24, ssm_chunk=4, embed_multiplier=3.0,
        head_multiplier=0.5, attn_out_multiplier=0.5,
        mlp_multipliers=(1.0, 0.5)),
}

SLOTS, PAGE, MAX_LEN, CHUNK = 3, 4, 64, 8


def _digest(fn, *args) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()


def programs(cfg):
    """``{name: digest}`` of the three programs for one configuration."""
    params = jax.eval_shape(lambda: T.lay_out_projections(
        T.init_params(jax.random.PRNGKey(0), cfg))[0])
    n_pg = SLOTS * MAX_LEN // PAGE + 1

    def pool(layers):
        return jax.eval_shape(lambda: C.init_page_pool(
            cfg, SLOTS, n_pg, PAGE, None, layers, MAX_LEN // PAGE))

    full = pool(cfg.layers_with("k"))
    table = jnp.zeros((SLOTS, MAX_LEN // PAGE), jnp.int32)
    tokens = jnp.zeros((SLOTS,), jnp.int32)
    active = jnp.ones((SLOTS,), bool)
    kw = {}
    if cfg.has_window:
        w = pool(cfg.kind_count("sliding"))
        full = {**full, "wk": w["k"], "wv": w["v"]}
        kw["wtable"] = table
    out = {"tick": _digest(
        lambda p, pl: T.decode_step_paged(
            p, tokens, pl, table, cfg, active, kernel=True,
            return_moe_load=cfg.n_experts > 1, **kw), params, full)}
    chunk = jnp.zeros((1, CHUNK), jnp.int32)
    lens = jnp.full((1,), CHUNK, jnp.int32)
    pages = jnp.zeros((2,), jnp.int32)

    def ingest(p, pl):
        prefix = C.gather_prefix_pages(
            {n: a for n, a in pl.items() if n not in T.WINDOW_ARRAYS}, pages)
        if cfg.has_window:
            landed = C.gather_prefix_pages(
                {n: pl[w] for w, n in T.WINDOW_ARRAYS.items()}, pages)
            prefix.update((w, landed[n]) for w, n in T.WINDOW_ARRAYS.items())
        for name in C._arrays(pl, "state"):   # the one row's, as a slot holds it
            prefix[name] = pl[name][:, :1]
        return T.prefill_with_prefix(p, chunk, prefix, jnp.int32(8), cfg,
                                     true_len=lens)

    out["chunk"] = _digest(ingest, params, full)
    out["prompt"] = _digest(
        lambda p: T.prefill(p, chunk, T.init_cache(cfg, 1, CHUNK), cfg,
                            true_len=lens), params)
    return out


def digests() -> dict:
    return {name: programs(T.TransformerConfig(**kw))
            for name, kw in CONFIGS.items()}


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1, sort_keys=True))
