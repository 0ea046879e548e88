"""Seeded weights of a model whose every layer runs attention AND a
state-space mixer side by side (``kind: serve_hybrid``), made by the
benchmark and handed to the program: ``weights.py``'s keys —
``fold_in(fold_in(base(seed), layer), leaf index)``, any one layer again
from the same keys for the reference — with this model's leaves: the
attention's ``wq``/``wk``/``wv``/``wo`` beside the mixer's ``ssm_in (D,
2 d_ssm + 2 G N + heads)``, ``ssm_conv_k (C, K)``/``ssm_conv_b (C)``,
``ssm_dt_bias``/``ssm_A_log``/``ssm_D (heads)``, ``ssm_norm (d_ssm)``,
``ssm_out (d_ssm, D)``, the SwiGLU's three, two norms; every leaf stacked
over all the layers (the stack is uniform), an untied ``head``.

Scales (the configuration file's ``assumed.initialisation``): the model
is parametrised for width transfer, its streams MULTIPLIED by published
constants as small as 0.0078, so every matrix is drawn normal at std
``1 / (sqrt(fan_in) x the multipliers on its input and output)`` and each
multiplied stream has unit scale: at the plain ``1 / sqrt(fan_in)`` a key
times 0.011 makes every softmax uniform, and the comparison that decides
``correct`` would see neither the rope nor the order of the pages.
``ssm_in``'s columns take their part's multiplier (:func:`mup_vector`).
The recurrence's own leaves are Mamba-2's published initialisation: ``A``
uniform in 1-16, ``dt`` log-uniform in 1e-3..1e-1 (``dt_bias`` its
inverse softplus), ``D`` = 1.

Memory: a LEAF is drawn alone and PLACED into its stack, which is
donated through (``weights_conv``'s lesson: a layer drawn whole set the
run's peak)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.weights_sparse import _leaf   # a 3-D leaf drawn as a matrix

_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "ssm_in", "ssm_conv_k",
           "ssm_conv_b", "ssm_dt_bias", "ssm_A_log", "ssm_D", "ssm_norm",
           "ssm_out", "w_gate", "w_up", "w_down")
_TOP_LEAVES = ("embed", "ln_f", "head")


def conv_width(dims: dict) -> int:
    """What the mixer's convolution runs over: ``[x | B | C]``."""
    return dims["mamba_d_ssm"] + 2 * dims["mamba_n_groups"] \
        * dims["mamba_d_state"]


def in_width(dims: dict) -> int:
    """``[z | x | B | C | dt]``."""
    return dims["mamba_d_ssm"] + conv_width(dims) + dims["mamba_n_heads"]


def mup_vector(dims: dict) -> np.ndarray:
    """The five ``ssm_multipliers`` laid over ``ssm_in``'s columns."""
    i, gn = dims["mamba_d_ssm"], dims["mamba_n_groups"] * dims["mamba_d_state"]
    return np.repeat(np.asarray(dims["ssm_multipliers"], np.float32),
                     (i, i, gn, gn, dims["mamba_n_heads"]))


def layer_shapes(dims: dict) -> dict:
    """name -> (shape, how it is drawn): a float is a normal's std, None a
    norm's ones, a string one of the recurrence's own rules."""
    d, h, kv = dims["hidden_size"], dims["num_attention_heads"], \
        dims["num_key_value_heads"]
    dh, f, i = dims["head_dim"], dims["intermediate_size"], \
        dims["mamba_d_ssm"]
    hs, c, k = dims["mamba_n_heads"], conv_width(dims), dims["mamba_d_conv"]
    s_d = 1 / np.sqrt(d)
    m_ai, m_si = dims["attention_in_multiplier"], dims["ssm_in_multiplier"]
    m_g, m_d = dims["mlp_multipliers"]
    return {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "wq": ((d, h, dh), s_d / m_ai),
        "wk": ((d, kv, dh), s_d / (m_ai * dims["key_multiplier"])),
        "wv": ((d, kv, dh), s_d / m_ai),
        "wo": ((h, dh, d),
               1 / (np.sqrt(h * dh) * dims["attention_out_multiplier"])),
        "ssm_in": ((d, in_width(dims)), "mup"),
        "ssm_conv_k": ((c, k), 1 / np.sqrt(k)),
        "ssm_conv_b": ((c,), 0.1),
        "ssm_dt_bias": ((hs,), "dt"), "ssm_A_log": ((hs,), "A"),
        "ssm_D": ((hs,), None), "ssm_norm": ((i,), None),
        "ssm_out": ((i, d), 1 / (np.sqrt(i) * dims["ssm_out_multiplier"])),
        "w_gate": ((d, f), s_d / m_g), "w_up": ((d, f), s_d),
        "w_down": ((f, d), 1 / (np.sqrt(f) * m_d)),
    }


def top_shapes(dims: dict) -> dict:
    d, v = dims["hidden_size"], dims["vocab_size"]
    return {"embed": ((v, d), 1 / dims["embedding_multiplier"]),
            "ln_f": ((d,), None),
            "head": ((d, v), 1 / (np.sqrt(d) * dims["lm_head_multiplier"]))}


def _draw(key, shape, how, dims: dict, dtype):
    if how == "A":      # A uniform in 1-16; the leaf is its log
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    if how == "dt":     # dt log-uniform in 1e-3..1e-1, through softplus^-1
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if how == "mup":    # each part's columns over its own multiplier
        scale = 1 / (np.sqrt(shape[0]) * dims["ssm_in_multiplier"]
                     * mup_vector(dims))
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)
    return _leaf(key, shape, how, dtype)     # a normal's std; None: ones


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "intermediate_size", "vocab_size", "mamba_d_ssm",
         "mamba_n_heads", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
         "attention_in_multiplier", "attention_out_multiplier",
         "key_multiplier", "embedding_multiplier", "lm_head_multiplier",
         "ssm_in_multiplier", "ssm_out_multiplier")


def _dims_key(dims: dict) -> tuple:
    return tuple((k, dims[k]) for k in _KEYS) + (
        ("ssm_multipliers", tuple(dims["ssm_multipliers"])),
        ("mlp_multipliers", tuple(dims["mlp_multipliers"])))


@functools.lru_cache(maxsize=None)
def _leaf_fn(dims_key: tuple, dtype_name: str, name: str):
    """One leaf of one layer, an executable of its own."""
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    shape, how = layer_shapes(dims)[name]
    return jax.jit(lambda base, l: _draw(
        jax.random.fold_in(jax.random.fold_in(base, l),
                           _LEAVES.index(name)), shape, how, dims, dtype))


def _layer_leaves(seed: int, l: int, dims: dict, dtype):
    base = W.base_key(seed)
    for name in _LEAVES:
        yield name, _leaf_fn(_dims_key(dims), jnp.dtype(dtype).name, name)(
            base, jnp.int32(l))


def layer_params(seed: int, l: int, dims: dict, dtype):
    """Layer ``l`` alone (the reference walks the depth with these)."""
    return dict(_layer_leaves(seed, l, dims, dtype))


@functools.lru_cache(maxsize=None)
def _top_fn(dims_key: tuple, dtype_name: str, name: str):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    shape, how = top_shapes(dims)[name]
    return jax.jit(lambda base: _draw(
        jax.random.fold_in(jax.random.fold_in(base, W._TOP),
                           _TOP_LEAVES.index(name)), shape, how, dims, dtype))


def top_params(seed: int, dims: dict, dtype, names=_TOP_LEAVES):
    """The leaves outside the stack: ``embed``, ``ln_f``, ``head``."""
    return {n: _top_fn(_dims_key(dims), jnp.dtype(dtype).name, n)(
        W.base_key(seed)) for n in names}


_place = jax.jit(jax.lax.dynamic_update_index_in_dim, donate_argnums=(0,),
                 static_argnums=(3,))


def make_params(seed: int, dims: dict, dtype):
    """Every leaf, on the device; a leaf at a time into its stack (the
    stack donated through)."""
    L = dims["num_hidden_layers"]
    params = dict(top_params(seed, dims, dtype))
    shapes = {k: (L, *s) for k, (s, _) in layer_shapes(dims).items()}
    stack = jax.jit(lambda: {k: jnp.zeros(s, dtype)
                             for k, s in shapes.items()})()
    for l in range(L):
        for leaf, a in _layer_leaves(seed, l, dims, dtype):
            stack[leaf] = _place(stack[leaf], a, jnp.int32(l), 0)
    params["layers"] = stack
    return params


def param_count(dims: dict) -> int:
    """Parameters of the tree ``make_params`` makes."""
    return (sum(int(np.prod(s)) for s, _ in top_shapes(dims).values())
            + dims["num_hidden_layers"] * sum(
                int(np.prod(s)) for s, _ in layer_shapes(dims).values()))
