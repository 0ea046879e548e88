"""Seeded weights of a latent-attention expert model (``kind:
serve_latent``), made by the benchmark and handed to the program:
``weights.py``'s scheme — one jitted call for the whole tree, any one
layer again from the same keys for the reference — with this model's
leaves: the latent attention's two down-projections with their norms and
two up-projections, a leading DENSE stack beside the expert stack, a
router over ALL the published experts, the experts HELD here stacked on
an axis of their own, and the shared expert.  The leaves outside the
stacks are ``weights.py``'s.

Keys: ``fold_in(fold_in(base(seed), layer), leaf index)``, as there; the
layer index runs through both stacks (the dense layers first), and a
leaf's index is its place in ``_LAYER_LEAVES`` whichever stack it is in.
Scales: normal, std ``1 / sqrt(fan_in)`` (``wo``: heads x v_head_dim)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

_ATTN_LEAVES = ("ln1", "ln2", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                "kv_a_norm", "wkv_b", "wo")
_DENSE_LEAVES = _ATTN_LEAVES + ("w_gate", "w_up", "w_down")
_EXPERT_LEAVES = _DENSE_LEAVES + ("router", "ws_gate", "ws_up", "ws_down")
_LAYER_LEAVES = _EXPERT_LEAVES  # a leaf's key index, in either stack


def layer_shapes(dims: dict, dense: bool) -> dict:
    """name -> (shape, init scale or None for a norm's ones)."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    r, c = dims["q_lora_rank"], dims["kv_lora_rank"]
    n, p, v = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], \
        dims["v_head_dim"]
    s_d = 1 / np.sqrt(d)
    out = {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "wq_a": ((d, r), s_d), "q_a_norm": ((r,), None),
        "wq_b": ((r, h, n + p), 1 / np.sqrt(r)),
        "wkv_a": ((d, c + p), s_d), "kv_a_norm": ((c,), None),
        "wkv_b": ((c, h, n + v), 1 / np.sqrt(c)),
        "wo": ((h, v, d), 1 / np.sqrt(h * v)),
    }
    if dense:
        f = dims["intermediate_size"]
        out.update({"w_gate": ((d, f), s_d), "w_up": ((d, f), s_d),
                    "w_down": ((f, d), 1 / np.sqrt(f))})
        return out
    e, f = dims["n_routed_experts"], dims["moe_intermediate_size"]
    fs = dims["n_shared_experts"] * f
    out.update({
        "router": ((d, dims["router_outputs"]), s_d),
        "w_gate": ((e, d, f), s_d), "w_up": ((e, d, f), s_d),
        "w_down": ((e, f, d), 1 / np.sqrt(f)),
        "ws_gate": ((d, fs), s_d), "ws_up": ((d, fs), s_d),
        "ws_down": ((fs, d), 1 / np.sqrt(fs)),
    })
    return out


def _layer(base, l, dims, dtype, dense: bool):
    k = jax.random.fold_in(base, l)
    shapes = layer_shapes(dims, dense)
    return {n: W._leaf(jax.random.fold_in(k, _LAYER_LEAVES.index(n)),
                       *shapes[n], dtype)
            for n in (_DENSE_LEAVES if dense else _EXPERT_LEAVES)}


_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "intermediate_size", "moe_intermediate_size",
         "n_routed_experts", "router_outputs", "n_shared_experts",
         "vocab_size", "num_hidden_layers", "first_k_dense_replace")


def _dims_key(dims: dict) -> tuple:
    return tuple((k, int(dims[k])) for k in _KEYS)


@functools.lru_cache(maxsize=None)
def _make_fn(dims_key: tuple, dtype_name: str):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    kd, n = dims["first_k_dense_replace"], dims["num_hidden_layers"]

    def make(base):
        return {
            **W._top(base, dims, dtype),
            "dense_layers": jax.vmap(lambda l: _layer(
                base, l, dims, dtype, True))(jnp.arange(kd)),
            "layers": jax.vmap(lambda l: _layer(
                base, l, dims, dtype, False))(jnp.arange(kd, n)),
        }

    return jax.jit(make)


def make_params(seed: int, dims: dict, dtype):
    """Every leaf, on the device, in ONE jitted call."""
    return _make_fn(_dims_key(dims), jnp.dtype(dtype).name)(
        W.base_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(dims_key: tuple, dtype_name: str, dense: bool):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    return jax.jit(lambda base, l: _layer(base, l, dims, dtype, dense))


def layer_params(seed: int, l: int, dims: dict, dtype):
    """Layer ``l`` alone, dense or expert by its place (the reference
    walks the depth with these)."""
    return _layer_fn(_dims_key(dims), jnp.dtype(dtype).name,
                     l < dims["first_k_dense_replace"])(
        W.base_key(seed), jnp.int32(l))


def top_params(seed: int, dims: dict, dtype):
    """``embed``, ``ln_f``, ``head`` (``weights.py``'s leaves and keys;
    its own ``top_params`` asks for a dense model's dims)."""
    return W._top_fn(_dims_key(dims), jnp.dtype(dtype).name,
                     W._TOP_LEAVES)(W.base_key(seed))
