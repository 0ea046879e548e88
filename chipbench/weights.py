"""Seeded weights, made by the benchmark and handed to the program.

One jitted call makes every leaf on the device in the dtype it is used
in (``make_params``); the reference regenerates any one layer from the
same keys (``layer_params``, ``top_params``), so it never touches an
array the program holds.  The tree has the layout the program's entry
points take (a checkpoint's layout): ``embed (V, D)``, ``head (D, V)``,
``ln_f (D)`` and ``layers`` stacked on a leading axis.

Keys: ``fold_in(fold_in(base(seed), layer or TOP), leaf index)``, so a
leaf's values do not depend on the depth or on which other leaves are
made with it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo",
                 "w_gate", "w_up", "w_down")
_TOP_LEAVES = ("embed", "ln_f", "head")
_TOP = 1 << 20  # "layer index" of the leaves outside the stack


def base_key(seed: int):
    """A threefry key from any non-negative Python int (the driver's seeds
    pass 2**31): both 32-bit words are used."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def layer_shapes(dims: dict) -> dict:
    """name -> (shape, init scale or None for a norm's ones)."""
    d, h, kv = dims["hidden_size"], dims["num_attention_heads"], \
        dims["num_key_value_heads"]
    dh, f = dims["head_dim"], dims["intermediate_size"]
    s_d, s_f = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    return {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "wq": ((d, h, dh), s_d), "wk": ((d, kv, dh), s_d),
        "wv": ((d, kv, dh), s_d), "wo": ((h, dh, d), s_d),
        "w_gate": ((d, f), s_d), "w_up": ((d, f), s_d),
        "w_down": ((f, d), s_f),
    }


def top_shapes(dims: dict) -> dict:
    d, v = dims["hidden_size"], dims["vocab_size"]
    return {"embed": ((v, d), 1.0), "ln_f": ((d,), None),
            "head": ((d, v), 1.0 / np.sqrt(d))}


def _leaf(key, shape, scale, dtype):
    if scale is None:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _layer(base, l, dims, dtype):
    k = jax.random.fold_in(base, l)
    return {n: _leaf(jax.random.fold_in(k, i), *layer_shapes(dims)[n], dtype)
            for i, n in enumerate(_LAYER_LEAVES)}


def _top(base, dims, dtype, names=_TOP_LEAVES):
    k = jax.random.fold_in(base, _TOP)
    return {n: _leaf(jax.random.fold_in(k, _TOP_LEAVES.index(n)),
                     *top_shapes(dims)[n], dtype) for n in names}


def _dims_key(dims: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size",
            "num_hidden_layers")
    return tuple((k, int(dims[k])) for k in keys)


@functools.lru_cache(maxsize=None)
def _make_fn(dims_key: tuple, dtype_name: str, sharding):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)

    def make(base):
        layers = jax.vmap(lambda l: _layer(base, l, dims, dtype))(
            jnp.arange(dims["num_hidden_layers"]))
        return {**_top(base, dims, dtype), "layers": layers}

    return jax.jit(make, out_shardings=sharding)


def make_params(seed: int, dims: dict, dtype, sharding=None):
    """Every leaf, on the device, in ONE jitted call."""
    return _make_fn(_dims_key(dims), jnp.dtype(dtype).name, sharding)(
        base_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(dims_key: tuple, dtype_name: str):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    return jax.jit(lambda base, l: _layer(base, l, dims, dtype))


def layer_params(seed: int, l: int, dims: dict, dtype):
    """Layer ``l`` alone (the reference walks the depth with these)."""
    return _layer_fn(_dims_key(dims), jnp.dtype(dtype).name)(
        base_key(seed), jnp.int32(l))


@functools.lru_cache(maxsize=None)
def _top_fn(dims_key: tuple, dtype_name: str, names: tuple):
    return jax.jit(lambda b: _top(b, dict(dims_key), jnp.dtype(dtype_name),
                                  names))


def top_params(seed: int, dims: dict, dtype, names=_TOP_LEAVES):
    """The leaves outside the stack (all, or just ``names``)."""
    return _top_fn(_dims_key(dims), jnp.dtype(dtype).name, tuple(names))(
        base_key(seed))


def leaf_paths(dims: dict) -> list:
    """The tree's leaves as ``(path, is_stacked)``, in a fixed order."""
    return [((n,), False) for n in _TOP_LEAVES] \
        + [(("layers", n), True) for n in _LAYER_LEAVES]


def one_leaf(seed: int, path: tuple, dims: dict, dtype):
    """One leaf of ``make_params``'s tree (stacked, if a layer leaf):
    lets a caller compare against the seeded start leaf by leaf without
    holding a second copy of the model."""
    base = base_key(seed)
    if path[0] != "layers":
        return top_params(seed, dims, dtype, (path[0],))[path[0]]
    i = _LAYER_LEAVES.index(path[1])
    shape, scale = layer_shapes(dims)[path[1]]

    def stacked(base):
        return jax.vmap(lambda l: _leaf(
            jax.random.fold_in(jax.random.fold_in(base, l), i), shape, scale,
            jnp.dtype(dtype)))(jnp.arange(dims["num_hidden_layers"]))

    return jax.jit(stacked)(base)
