"""ONE table of layer kinds (``models/transformer.py LAYER_KINDS``): for
each of the eight served architectures' toy configurations, the arrays
the table declares are the arrays the page pool allocates, a whole-prompt
prefill hands back, and a chunk's prefill accepts and hands back — under
the same names — and a prefix that lacks one of them, or holds another,
is refused, typed, while it is traced."""

import jax
import jax.numpy as jnp
import pytest

import served_program_digests as D
from horovod_tpu.models import transformer as T
from horovod_tpu.serving import cache as C

pytestmark = [pytest.mark.serving, pytest.mark.paged]

# the digests' toy builders (tests/served_program_digests.py)
CONFIGS = D.CONFIGS
PAGE, CHUNK = 4, 8


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_tables_arrays_are_the_pools_the_blocks_and_the_prefixs(name):
    cfg = T.TransformerConfig(**CONFIGS[name])
    declared = {n for k in cfg.kinds.values() for n in k.block}
    assert declared == {n for n in ("k", "v", "wk", "wv", "ik", "conv",
                                    "ssm", "ck", "lin")
                        if cfg.layers_with(n)}
    # (a chunk's prefix carries no array of a row a page: the rows are
    # read off the landed keys)
    landed = {n for k in cfg.kinds.values() for n in k.landed}
    assert landed == declared - {"ck"}
    # the pool(s): the main one, and a window layer's own under its names
    made, zeros = [], jnp.zeros
    with pytest.MonkeyPatch.context() as patch:   # each array made ONCE
        patch.setattr(jnp, "zeros", lambda *a, **kw: (
            made.append(a[0]), zeros(*a, **kw))[1])
        pool = C.init_page_pool(cfg, 2, 9, PAGE, None, cfg.layers_with("k"))
    assert len(made) == len(pool)
    if cfg.has_window:
        own = C.init_page_pool(cfg, 2, 9, PAGE, None,
                               cfg.kind_count("sliding"))
        pool.update((w, own[n]) for w, n in T.WINDOW_ARRAYS.items())
    assert set(pool) - {"pos"} == declared
    for kind in cfg.kinds.values():   # a row a page of a SLOT's table
        for n, row in kind.page_rows.items():
            assert pool[n].shape == (cfg.layers_with(n), 2, row(cfg)[0],
                                     cfg.max_seq // PAGE, row(cfg)[1])
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    ids, lens = jnp.zeros((1, CHUNK), jnp.int32), jnp.full((1,), CHUNK)
    # a whole prompt's block (a cache of one kind of pages takes it whole)
    _, block = jax.eval_shape(
        lambda p: T.prefill(p, ids, T.init_cache(cfg, 1, CHUNK), cfg,
                            true_len=lens), params)
    assert set(block) - {"pos"} == declared
    # a chunk's prefix, as the pool hands it over, and its block
    pages = jnp.zeros((2,), jnp.int32)
    prefix = C.gather_prefix_pages(
        {n: a for n, a in pool.items() if n not in T.WINDOW_ARRAYS}, pages)
    if cfg.has_window:
        own = C.gather_prefix_pages(
            {n: pool[w] for w, n in T.WINDOW_ARRAYS.items()}, pages)
        prefix.update((w, own[n]) for w, n in T.WINDOW_ARRAYS.items())
    prefix.update((n, pool[n][:, :1]) for n in C._arrays(pool, "state"))
    assert set(prefix) == landed

    def chunk(p, prefix):
        return T.prefill_with_prefix(p, ids, prefix, jnp.int32(PAGE), cfg,
                                     true_len=lens)[1]

    got = jax.eval_shape(chunk, params, prefix)
    assert set(got) - {"pos"} == declared
    assert {n: a.shape for n, a in got.items()} == {
        n: a.shape for n, a in block.items()}
    lacking = {n: a for n, a in prefix.items() if n != sorted(landed)[0]}
    foreign = {**prefix, "zz": prefix[sorted(landed)[0]]}
    for bad in (lacking, foreign):
        with pytest.raises(T.UnsupportedModelConfigError, match="prefix"):
            jax.eval_shape(chunk, params, bad)
