"""Seeded weights of a SPARSE latent-attention expert model (``kind:
serve_sparse``): ``weights_latent``'s tree and keys, and on every layer
the lightning indexer's leaves — ``wi_q (q_lora_rank, index_n_heads,
index_head_dim)``, ``wi_k (hidden, index_head_dim)`` with its
LayerNorm's ``i_k_norm`` (ones) and ``i_k_bias`` (zeros), ``wi_w
(hidden, index_n_heads)`` — and on every expert layer the router's
score-correction bias ``router_bias (router_outputs)``.

Keys: a new leaf's index follows ``weights_latent``'s last, so every
leaf the two kinds share draws the same numbers from the same seed.
Scales: normal, std ``1 / sqrt(fan_in)``; the bias normal with std
``assumed.router_bias_std`` (0.05 against sigmoid scores around 0.5: a
zero bias would choose as no bias does)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench import weights_latent as WL

_INDEX_LEAVES = ("wi_q", "wi_k", "i_k_norm", "i_k_bias", "wi_w")
_NEW_LEAVES = _INDEX_LEAVES + ("router_bias",)
_LEAF_INDEX = {n: i for i, n in enumerate(WL._LAYER_LEAVES + _NEW_LEAVES)}
ROUTER_BIAS_STD = 0.05


def layer_shapes(dims: dict, dense: bool) -> dict:
    """name -> (shape, init scale; None = ones, 0.0 = zeros)."""
    d, r = dims["hidden_size"], dims["q_lora_rank"]
    hi, di = dims["index_n_heads"], dims["index_head_dim"]
    out = dict(WL.layer_shapes(dims, dense))
    out.update({
        "wi_q": ((r, hi, di), 1 / np.sqrt(r)),
        "wi_k": ((d, di), 1 / np.sqrt(d)),
        "i_k_norm": ((di,), None), "i_k_bias": ((di,), 0.0),
        "wi_w": ((d, hi), 1 / np.sqrt(d)),
    })
    if not dense:
        out["router_bias"] = ((dims["router_outputs"],), ROUTER_BIAS_STD)
    return out


def _leaf(key, shape, scale, dtype):
    """``weights._leaf``'s numbers, drawn as a matrix and reshaped:
    XLA:TPU compiles the draw of ``wkv_b (512, 128, 256)`` in 1.2 s so
    and in 31.8 s as it stands, ``wo`` in 3.4 for 11.6, eight experts'
    matrices in 4.0 for 10.0 (compiled for the v5e with no chip, PR 32)."""
    if scale is None or len(shape) < 3:
        return W._leaf(key, shape, scale, dtype)
    draw = jax.random.normal(key, (int(np.prod(shape[:-1])), shape[-1]),
                             jnp.float32)
    return (draw.reshape(shape) * scale).astype(dtype)


def _layer(base, l, dims, dtype, dense: bool):
    k = jax.random.fold_in(base, l)
    return {n: _leaf(jax.random.fold_in(k, _LEAF_INDEX[n]), *sh, dtype)
            for n, sh in layer_shapes(dims, dense).items()}


_KEYS = WL._KEYS + ("index_n_heads", "index_head_dim")


def _dims_key(dims: dict) -> tuple:
    return tuple((k, int(dims[k])) for k in _KEYS)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims_key: tuple, dtype_name: str, dense: bool):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    return jax.jit(lambda base, l: _layer(base, l, dims, dtype, dense))


@functools.lru_cache(maxsize=None)
def _stack_fn():
    return jax.jit(lambda *layers: jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *layers))


def make_params(seed: int, dims: dict, dtype):
    """Every leaf, on the device: the layers one at a time through
    ``layer_params`` — the reference's own two executables, so a run
    compiles a layer kind's draw once — and stacked a kind."""
    kd, n = dims["first_k_dense_replace"], dims["num_hidden_layers"]
    return {
        **top_params(seed, dims, dtype),
        "dense_layers": _stack_fn()(*(layer_params(seed, l, dims, dtype)
                                      for l in range(kd))),
        "layers": _stack_fn()(*(layer_params(seed, l, dims, dtype)
                                for l in range(kd, n))),
    }


def layer_params(seed: int, l: int, dims: dict, dtype):
    """Layer ``l`` alone, dense or expert by its place."""
    return _layer_fn(_dims_key(dims), jnp.dtype(dtype).name,
                     l < dims["first_k_dense_replace"])(
        W.base_key(seed), jnp.int32(l))


def top_params(seed: int, dims: dict, dtype):
    return WL.top_params(seed, dims, dtype)
