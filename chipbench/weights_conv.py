"""Seeded weights of a model of gated short convolutions between
attention layers (``kind: serve_conv``), made by the benchmark and
handed to the program: ``weights.py``'s keys — ``fold_in(fold_in(base(
seed), layer), leaf index)``, any one layer again from the same keys
for the reference — with this model's tree: NO ``head`` (it is tied to
``embed``), a leading ``dense_layers`` stack beside ``layers``, and in
each stack the MIXER's leaves stacked by kind: ``wq``/``wk``/``wv``/
``wo``/``q_norm``/``k_norm`` over the stack's attention layers,
``conv_in (D, 3D)``/``conv_k (D, K)``/``conv_out (D, D)`` over its conv
layers, every other leaf (``ln1``, ``ln2``, the FFN's, the router and
its bias) over all its layers.

Scales: normal, std ``1 / sqrt(fan_in)`` (``wo``: heads x head;
``conv_k``: its taps); the EMBEDDING ``1 / sqrt(hidden)`` — it is the
head too, and at std 1 every token's own logit would be ~hidden / rms
(the configuration file's ``assumed.initialisation``).

The EXPERT BIAS (``use_expert_bias``) is not drawn: it is what the
published balancing rule leaves behind, the bias under which every
expert of a layer is chosen alike (:func:`balanced_bias`, on the scores
``reference_conv.balanced_biases`` reads off one seeded sequence).  A
drawn bias over seeded weights routes SKEWED — an expert's scores share
what the tokens' hidden states share, so a few experts took 3.5 x the
mean load and 57.2-57.6 of 64 owned a row a tick, by the seed — where a
trained model's loads are level; and a tick reads the experts that own a
row, so its time moved with the seed (PERF.md, PR 40).

Memory: the tree is 10.5 GB at the published widths on a 16 GB chip, so
a LEAF is drawn alone (``_leaf_fn``; ``layer_params``, the reference's,
gathers a layer's) and PLACED into its stack, which is donated through
(``_place``): never two copies of a stack, one float32 draw at a
time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.weights_sparse import _leaf   # a 3-D leaf drawn as a matrix

_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
           "conv_in", "conv_k", "conv_out", "router", "router_bias",
           "w_gate", "w_up", "w_down")
_MIXER = {"full_attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
          "conv": ("conv_in", "conv_k", "conv_out")}


def head_dim(dims: dict) -> int:
    return dims["hidden_size"] // dims["num_attention_heads"]


def layer_shapes(dims: dict, kind: str, dense: bool) -> dict:
    """name -> (shape, init scale or None for a norm's ones)."""
    d, h, kv = dims["hidden_size"], dims["num_attention_heads"], \
        dims["num_key_value_heads"]
    dh, taps = head_dim(dims), dims["conv_L_cache"]
    s_d = 1 / np.sqrt(d)
    out = {"ln1": ((d,), None), "ln2": ((d,), None)}
    if kind == "conv":
        out.update(conv_in=((d, 3 * d), s_d),
                   conv_k=((d, taps), 1 / np.sqrt(taps)),
                   conv_out=((d, d), s_d))
    else:
        out.update(wq=((d, h, dh), s_d), wk=((d, kv, dh), s_d),
                   wv=((d, kv, dh), s_d),
                   wo=((h, dh, d), 1 / np.sqrt(h * dh)),
                   q_norm=((dh,), None), k_norm=((dh,), None))
    if dense:
        f = dims["intermediate_size"]
        out.update(w_gate=((d, f), s_d), w_up=((d, f), s_d),
                   w_down=((f, d), 1 / np.sqrt(f)))
    else:
        e, f = dims["num_experts"], dims["moe_intermediate_size"]
        out.update(router=((d, e), s_d),
                   router_bias=((e,), None),      # balanced, not drawn
                   w_gate=((e, d, f), s_d), w_up=((e, d, f), s_d),
                   w_down=((e, f, d), 1 / np.sqrt(f)))
    return out


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "conv_L_cache", "intermediate_size", "num_experts",
         "moe_intermediate_size", "vocab_size")


def _dims_key(dims: dict) -> tuple:
    return tuple((k, int(dims[k])) for k in _KEYS)


@functools.lru_cache(maxsize=None)
def _leaf_fn(dims_key: tuple, dtype_name: str, kind: str, dense: bool,
             name: str):
    """One leaf of one layer, an executable of its own: a layer drawn
    whole holds its three expert leaves' float32 draws at once (3.6 GB
    beside a 10.5 GB tree at the published widths: the run's peak was
    the weights' making, 16.34 GB, not the serving)."""
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    shape, scale = layer_shapes(dims, kind, dense)[name]
    return jax.jit(lambda base, l: _leaf(
        jax.random.fold_in(jax.random.fold_in(base, l),
                           _LEAVES.index(name)), shape, scale, dtype))


def balanced_bias(scores: np.ndarray, k: int, steps: int = 200):
    """The bias ``(E,)`` under which the top ``k`` of ``scores + bias``
    ``(N, E)`` fall on every expert alike: each expert's scores centred,
    then the published rule's move — an overloaded expert's bias down,
    an underloaded one's up, by its load's distance from the mean — at a
    step that shrinks.  Float32, mean zero."""
    scores = np.asarray(scores, np.float64)
    n, e = scores.shape
    bias = scores.mean() - scores.mean(axis=0)
    step = scores.std()
    for t in range(steps):
        top = np.argpartition(-(scores + bias), k - 1, axis=1)[:, :k]
        load = np.bincount(top.ravel(), minlength=e) * (e / (n * k))
        bias -= step / (2 + t / 4) * (load - 1.0)
    return (bias - bias.mean()).astype(np.float32)


def _layer_leaves(seed: int, l: int, dims: dict, dtype, bias="balanced"):
    """``(name, leaf)`` of layer ``l``, drawn one at a time: conv or
    attention, dense or expert, by its place.  ``bias=None`` leaves the
    expert bias out (the calibration's own walk puts its in)."""
    kind, dense = dims["layer_types"][l], l < dims["num_dense_layers"]
    base = W.base_key(seed)
    for name in layer_shapes(dims, kind, dense):
        if name == "router_bias":
            if bias is not None:
                from chipbench import reference_conv

                yield name, jnp.asarray(reference_conv.balanced_biases(
                    seed, dims, dtype)[l], dtype)
            continue
        yield name, _leaf_fn(_dims_key(dims), jnp.dtype(dtype).name, kind,
                             dense, name)(base, jnp.int32(l))


def layer_params(seed: int, l: int, dims: dict, dtype, bias="balanced"):
    """Layer ``l`` alone (the reference walks the depth with these)."""
    return dict(_layer_leaves(seed, l, dims, dtype, bias))


@functools.lru_cache(maxsize=None)
def _top_fn(dims_key: tuple, dtype_name: str):
    dims, dtype = dict(dims_key), jnp.dtype(dtype_name)
    d, v = dims["hidden_size"], dims["vocab_size"]

    def top(base):
        k = jax.random.fold_in(base, W._TOP)
        return {"embed": _leaf(jax.random.fold_in(k, 0), (v, d),
                               1 / np.sqrt(d), dtype),
                "ln_f": jnp.ones((d,), dtype)}

    return jax.jit(top)


def top_params(seed: int, dims: dict, dtype):
    """The leaves outside the stacks: ``embed`` (the head too), ``ln_f``."""
    return _top_fn(_dims_key(dims), jnp.dtype(dtype).name)(W.base_key(seed))


def stack_counts(dims: dict) -> dict:
    """``{stack: {"all": layers, kind: layers of that kind}}``."""
    nd = dims["num_dense_layers"]
    out = {}
    for name, kinds in (("dense_layers", dims["layer_types"][:nd]),
                        ("layers", dims["layer_types"][nd:])):
        out[name] = {"all": len(kinds),
                     **{k: kinds.count(k) for k in set(kinds)}}
    return out


_place = jax.jit(jax.lax.dynamic_update_index_in_dim, donate_argnums=(0,),
                 static_argnums=(3,))


def make_params(seed: int, dims: dict, dtype):
    """Every leaf, on the device; a leaf at a time into its stack (the
    stack donated through): a mixer leaf at the layer's place among its
    kind, every other at its place in the stack."""
    nd = dims["num_dense_layers"]
    counts = stack_counts(dims)
    if dims["use_expert_bias"]:
        # the calibration's float32 walk first, while the chip is empty
        from chipbench import reference_conv

        reference_conv.balanced_biases(seed, dims, dtype)
    params = dict(top_params(seed, dims, dtype))
    for name, first in (("dense_layers", 0), ("layers", nd)):
        n = counts[name]
        if not n["all"]:
            continue
        shapes = {}
        for kind in (k for k in n if k != "all"):
            for leaf, (shape, _) in layer_shapes(dims, kind,
                                                 name == "dense_layers").items():
                rows = n[kind] if leaf in _MIXER[kind] else n["all"]
                shapes[leaf] = (rows, *shape)
        stack = jax.jit(lambda: {k: jnp.zeros(s, dtype)
                                 for k, s in shapes.items()})()
        seen = {}
        for i in range(n["all"]):
            kind = dims["layer_types"][first + i]
            j = seen.get(kind, 0)
            seen[kind] = j + 1
            for leaf, a in _layer_leaves(seed, first + i, dims, dtype):
                stack[leaf] = _place(
                    stack[leaf], a,
                    jnp.int32(j if leaf in _MIXER[kind] else i), 0)
        params[name] = stack
    return params


def param_count(dims: dict) -> int:
    """Parameters of the tree ``make_params`` makes."""
    d, v, nd = dims["hidden_size"], dims["vocab_size"], \
        dims["num_dense_layers"]
    total = v * d + d
    for l, kind in enumerate(dims["layer_types"]):
        total += sum(int(np.prod(s)) for s, _ in
                     layer_shapes(dims, kind, l < nd).values())
    return total
