"""Seeded weights of a patterned expert model (``kind:
serve_patterned``), made by the benchmark and handed to the program:
``weights.py``'s scheme — one jitted call for the whole tree, any one
layer again from the same keys for the reference — with this model's
leaves: a norm on q and k, a router, and the experts stacked on an axis
of their own.  The leaves outside the stack are ``weights.py``'s.

Keys: ``fold_in(fold_in(base(seed), layer), leaf index)``, as there."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

_LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                 "router", "w_gate", "w_up", "w_down")


def layer_shapes(dims: dict) -> dict:
    """name -> (shape, init scale or None for a norm's ones)."""
    d, h, kv = dims["hidden_size"], dims["num_attention_heads"], \
        dims["num_key_value_heads"]
    dh, e, f = dims["head_dim"], dims["num_experts"], \
        dims["moe_intermediate_size"]
    s_d, s_o, s_f = 1 / np.sqrt(d), 1 / np.sqrt(h * dh), 1 / np.sqrt(f)
    return {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "wq": ((d, h, dh), s_d), "wk": ((d, kv, dh), s_d),
        "wv": ((d, kv, dh), s_d), "wo": ((h, dh, d), s_o),
        "q_norm": ((dh,), None), "k_norm": ((dh,), None),
        "router": ((d, e), s_d),
        "w_gate": ((e, d, f), s_d), "w_up": ((e, d, f), s_d),
        "w_down": ((e, f, d), s_f),
    }


def _layer(base, l, dims, dtype):
    k = jax.random.fold_in(base, l)
    return {n: W._leaf(jax.random.fold_in(k, i), *layer_shapes(dims)[n],
                       dtype) for i, n in enumerate(_LAYER_LEAVES)}


def _dims_key(dims: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_experts", "moe_intermediate_size",
            "vocab_size", "num_hidden_layers", "intermediate_size")
    return tuple((k, int(dims[k])) for k in keys)


@functools.lru_cache(maxsize=None)
def _make_fn(dims_key: tuple, dtype_name: str):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)

    def make(base):
        layers = jax.vmap(lambda l: _layer(base, l, dims, dtype))(
            jnp.arange(dims["num_hidden_layers"]))
        return {**W._top(base, dims, dtype), "layers": layers}

    return jax.jit(make)


def make_params(seed: int, dims: dict, dtype):
    """Every leaf, on the device, in ONE jitted call."""
    return _make_fn(_dims_key(dims), jnp.dtype(dtype).name)(
        W.base_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(dims_key: tuple, dtype_name: str):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    return jax.jit(lambda base, l: _layer(base, l, dims, dtype))


def layer_params(seed: int, l: int, dims: dict, dtype):
    """Layer ``l`` alone (the reference walks the depth with these)."""
    return _layer_fn(_dims_key(dims), jnp.dtype(dtype).name)(
        W.base_key(seed), jnp.int32(l))


def top_params(seed: int, dims: dict, dtype):
    return W.top_params(seed, dims, dtype)
