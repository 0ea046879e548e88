"""Seeded weights of a model of LINEAR-ATTENTION layers between BLOCK-
SPARSE attention layers (``kind: serve_linear_sparse``), made by the
benchmark and handed to the program: ``weights.py``'s keys —
``fold_in(fold_in(base(seed), PUBLISHED layer index), leaf index)``, any
one layer again from the same keys for the reference — with this model's
leaves.  A layer's MIXER leaves are stacked over its KIND's layers (the
two kinds' projections differ in shape: 32 key heads against 2), every
other leaf over all the layers run:

* every layer: ``ln1``, ``ln2``, ``w_gate``/``w_up (D, F)``, ``w_down (F,
  D)``;
* a ``lightning-attn`` layer: ``lin_q``/``lin_k``/``lin_v``/``lin_g (D, H
  Dh)``, ``lin_o (H Dh, D)``, ``lin_norm``/``lin_q_norm``/``lin_k_norm
  (Dh)`` and ``lin_decay (H)`` = ``log lambda_h``, float32;
* a ``minicpm4`` layer: ``wq (D, H, Dh)``, ``wk``/``wv (D, H_kv, Dh)``,
  ``wo (H, Dh, D)``, ``q_norm``/``k_norm (Dh)``, ``wg (D, H Dh)``.

Scales (the configuration file's ``assumed.initialisation``): each
matrix normal at std ``1 / (sqrt(fan_in) x the multiplier on its
output's stream)``, so every SCALED stream has unit scale.  The decay is
no draw: the public lightning attention code's schedule at the published
layer index (``assumed.lightning_decay``).

Memory: a LEAF is drawn alone and PLACED into its stack, which is
donated through (``weights_conv``'s lesson)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.weights_sparse import _leaf   # a 3-D leaf drawn as a matrix

KINDS = {"lightning-attn": "linear", "minicpm4": "block_sparse"}
_COMMON = ("ln1", "ln2", "w_gate", "w_up", "w_down")
_MIXER = {
    "linear": ("lin_q", "lin_k", "lin_v", "lin_g", "lin_o", "lin_norm",
               "lin_q_norm", "lin_k_norm", "lin_decay"),
    "block_sparse": ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "wg")}
_LEAVES = _COMMON + _MIXER["linear"] + _MIXER["block_sparse"]
_TOP_LEAVES = ("embed", "ln_f", "head")


def layers_run(dims: dict) -> list:
    """``[(published index, kind)]`` of the layers this cut runs."""
    first = dims.get("first_layer", 0)
    return [(l, KINDS[dims["mixer_types"][l]])
            for l in range(first, first + dims["num_hidden_layers"])]


def residual_scale(dims: dict) -> float:
    """``scale_depth / sqrt(layers)`` at the PUBLISHED depth."""
    layers = dims.get("published", {}).get("num_hidden_layers",
                                           dims["num_hidden_layers"])
    return dims["scale_depth"] / math.sqrt(layers)


def logit_scale(dims: dict) -> float:
    return dims["dim_model_base"] / dims["hidden_size"]


def layer_shapes(dims: dict, kind: str) -> dict:
    """name -> (shape, how it is drawn): a float is a normal's std, None
    a norm's ones, ``"decay"`` the schedule."""
    d, f = dims["hidden_size"], dims["intermediate_size"]
    s_d, r = 1 / np.sqrt(d), residual_scale(dims)
    out = {"ln1": ((d,), None), "ln2": ((d,), None),
           "w_gate": ((d, f), s_d), "w_up": ((d, f), s_d),
           "w_down": ((f, d), 1 / (np.sqrt(f) * r))}
    if kind == "linear":
        h, dh = dims["lightning_nh"], dims["lightning_head_dim"]
        out.update({
            "lin_q": ((d, h * dh), s_d), "lin_k": ((d, h * dh), s_d),
            "lin_v": ((d, h * dh), s_d), "lin_g": ((d, h * dh), s_d),
            "lin_o": ((h * dh, d), 1 / (np.sqrt(h * dh) * r)),
            "lin_norm": ((dh,), None), "lin_q_norm": ((dh,), None),
            "lin_k_norm": ((dh,), None), "lin_decay": ((h,), "decay")})
    else:
        h, kv, dh = (dims["num_attention_heads"],
                     dims["num_key_value_heads"], dims["head_dim"])
        out.update({
            "wq": ((d, h, dh), s_d), "wk": ((d, kv, dh), s_d),
            "wv": ((d, kv, dh), s_d),
            "wo": ((h, dh, d), 1 / (np.sqrt(h * dh) * r)),
            "q_norm": ((dh,), None), "k_norm": ((dh,), None),
            "wg": ((d, h * dh), s_d)})
    return out


def top_shapes(dims: dict) -> dict:
    d, v = dims["hidden_size"], dims["vocab_size"]
    return {"embed": ((v, d), 1 / dims["scale_emb"]), "ln_f": ((d,), None),
            "head": ((d, v), 1 / (np.sqrt(d) * logit_scale(dims)))}


def decay(l, dims: dict):
    """``log lambda_h`` of published layer ``l`` (traced or not), float32
    ``(heads,)``: ``-s_h g_l``, ``s_h = 2^(-8 (h + 1) / H)``, ``g_l = 1 - l
    / (L - 1) + 1e-5`` over the PUBLISHED depth ``L``."""
    h = dims["lightning_nh"]
    depth = dims.get("published", {}).get("num_hidden_layers",
                                          dims["num_hidden_layers"])
    slope = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    g = 1.0 - jnp.asarray(l, jnp.float32) / max(depth - 1, 1) + 1e-5
    return -slope * g


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "intermediate_size", "vocab_size", "lightning_nh",
         "lightning_head_dim", "scale_emb", "scale_depth", "dim_model_base",
         "num_hidden_layers")


def _dims_key(dims: dict) -> tuple:
    return tuple((k, dims[k]) for k in _KEYS) + (
        ("published_layers", dims.get("published", {}).get(
            "num_hidden_layers", dims["num_hidden_layers"])),)


def _dims_of(key: tuple) -> dict:
    dims = dict(key)
    dims["published"] = {"num_hidden_layers": dims.pop("published_layers")}
    return dims


@functools.lru_cache(maxsize=None)
def _leaf_fn(dims_key: tuple, dtype_name: str, kind: str, name: str):
    """One leaf of one layer, an executable of its own."""
    dims, dtype = _dims_of(dims_key), jnp.dtype(dtype_name)
    shape, how = layer_shapes(dims, kind)[name]
    if how == "decay":
        return jax.jit(lambda base, l: decay(l, dims))
    return jax.jit(lambda base, l: _leaf(
        jax.random.fold_in(jax.random.fold_in(base, l),
                           _LEAVES.index(name)), shape, how, dtype))


def _layer_leaves(seed: int, l: int, kind: str, dims: dict, dtype):
    base = W.base_key(seed)
    for name in _COMMON + _MIXER[kind]:
        yield name, _leaf_fn(_dims_key(dims), jnp.dtype(dtype).name, kind,
                             name)(base, jnp.int32(l))


def layer_params(seed: int, l: int, dims: dict, dtype):
    """PUBLISHED layer ``l`` alone: ``(kind, its leaves)`` (the reference
    walks the depth with these)."""
    kind = KINDS[dims["mixer_types"][l]]
    return kind, dict(_layer_leaves(seed, l, kind, dims, dtype))


@functools.lru_cache(maxsize=None)
def _top_fn(dims_key: tuple, dtype_name: str, name: str):
    dims, dtype = _dims_of(dims_key), jnp.dtype(dtype_name)
    shape, how = top_shapes(dims)[name]
    return jax.jit(lambda base: _leaf(
        jax.random.fold_in(jax.random.fold_in(base, W._TOP),
                           _TOP_LEAVES.index(name)), shape, how, dtype))


def top_params(seed: int, dims: dict, dtype, names=_TOP_LEAVES):
    """The leaves outside the stack: ``embed``, ``ln_f``, ``head``."""
    return {n: _top_fn(_dims_key(dims), jnp.dtype(dtype).name, n)(
        W.base_key(seed)) for n in names}


_place = jax.jit(jax.lax.dynamic_update_index_in_dim, donate_argnums=(0,),
                 static_argnums=(3,))


def make_params(seed: int, dims: dict, dtype):
    """Every leaf, on the device; a leaf at a time into its stack (the
    stack donated through)."""
    run = layers_run(dims)
    count = {k: sum(kind == k for _, kind in run) for k in _MIXER}
    params = dict(top_params(seed, dims, dtype))
    shapes = {}
    for kind, n in count.items():
        for name, (s, how) in layer_shapes(dims, kind).items():
            depth = len(run) if name in _COMMON else n
            shapes[name] = ((depth, *s),
                            jnp.float32 if how == "decay" else dtype)
    stack = jax.jit(lambda: {k: jnp.zeros(s, d)
                             for k, (s, d) in shapes.items()})()
    seen = dict.fromkeys(count, 0)
    for i, (l, kind) in enumerate(run):
        for leaf, a in _layer_leaves(seed, l, kind, dims, dtype):
            at = i if leaf in _COMMON else seen[kind]
            stack[leaf] = _place(stack[leaf], a, jnp.int32(at), 0)
        seen[kind] += 1
    params["layers"] = stack
    return params


def param_count(dims: dict) -> int:
    """Parameters of the tree ``make_params`` makes."""
    return (sum(int(np.prod(s)) for s, _ in top_shapes(dims).values())
            + sum(int(np.prod(s)) for _, kind in layers_run(dims)
                  for s, _ in layer_shapes(dims, kind).values()))
