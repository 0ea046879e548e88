"""The plain reference of a model of gated short convolutions between
attention layers (``kind: serve_conv``): the benchmark's OWN copy of
the forward pass that ``horovod_tpu/models/plain_reference.py`` states
(``conv_*``) — straightforward ``jax.numpy``, float32 at
``default_matmul_precision("highest")``, no kernel, no cache, NO STATE
(the convolution reads the whole sequence), NOTHING imported from the
program — arranged so that 8192 tokens at the published widths fit on
one chip beside nothing else, and so that every sequence of a cell runs
through the SAME executables whatever its length (PR 32's lesson: a
sequence's own padded length compiles a layer anew for every length).

The layer, as ``LFM2-24B-A2B`` publishes it (``model_type: lfm2_moe``):
``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, no bias.
``Op`` of a ``conv`` layer: ``[B, C, X] = n W_in`` (three equal parts in
that order), ``u = B * X``, ``v[t] = sum_j k[:, j] u[t - (K - 1) + j]``
(depthwise, causal, zeros before the sequence), ``(C * v) W_out``.
``Op`` of a ``full_attention`` layer: heads of ``hidden / heads``, q and
k through an RMSNorm over the head with a learned scale, rotate-half
rope, causal softmax attention, ``W_o``.  ``FFN``: the first
``num_dense_layers`` a SwiGLU of ``intermediate_size``; the rest
``s = sigmoid(n W_r)`` in float32, the ``num_experts_per_tok`` largest
of ``s + b`` chosen, weighted by their raw ``s`` over ``sum + 1e-6``,
times ``routed_scaling_factor``, each expert a SwiGLU of
``moe_intermediate_size``.  A final RMSNorm; logits against the
EMBEDDING.  The configuration file's ``assumed`` lists what the config
has no key for.

Departures, in memory and time only (the forward is causal, so no row
depends on a later one): every sequence lies in an array of the SAME
width (the engine's ``max_len``) and rows go in blocks of ``q_block`` of
which only those below the sequence's own length ``n`` — a traced
scalar — are computed (``reference_sparse._rows``: the others stay
zero); a conv layer's ``u`` is made for every block first and the taps
are then shifted over the whole array; the experts one at a time, every
expert on every row of a block times the weight the router gave it; the
weights one layer at a time (an expert layer is 2.4 GB in float32).

``mode`` is ``reference.py``'s (``"f32"`` the reference, ``"fp8"`` /
``"bf16"`` the lower-precision controls: every matmul's operands
rounded, the router's too; fp8's scale is an operand's, so a block of
rows has its own).  ``zero_taps=True`` is the SECOND control, not the
model: the convolution keeps its current tap alone — what a program
serves that loses a request's state at every chunk and tick boundary.

``balanced_biases`` is where the model's expert bias comes from
(``use_expert_bias``): this forward over one seeded sequence, layer by
layer, each expert layer's bias set from the scores its own tokens get
so that every expert is chosen alike (``weights_conv.balanced_bias``) —
what the published training rule arrives at — before the layer's
experts are computed under it."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, _mm, rmsnorm
from chipbench.reference_latent import _swiglu
from chipbench.reference_patterned import (_freeze, _thaw, rope_tables,
                                           rotate)
from chipbench.reference_sparse import _rows

NORM_TOPK_EPS = 1e-6


def route(n, router, bias, dims: dict, mode: str):
    """``(S, E)`` combination weights: chosen on ``sigmoid + bias``,
    weighted by the raw sigmoid over ``sum + 1e-6``."""
    sc = jax.nn.sigmoid(_mm("sd,de->se", n, router, mode))
    _, top_e = jax.lax.top_k(sc + bias.astype(F32),
                             dims["num_experts_per_tok"])
    top_g = jnp.take_along_axis(sc, top_e, axis=-1)
    if dims["norm_topk_prob"]:
        top_g = top_g / (jnp.sum(top_g, axis=-1, keepdims=True)
                         + NORM_TOPK_EPS)
    top_g = top_g * dims["routed_scaling_factor"]
    return jnp.zeros_like(sc).at[
        jnp.arange(n.shape[0])[:, None], top_e].set(top_g)


def experts(n, w, dims: dict, mode: str):
    weight = route(n, w["router"], w["router_bias"], dims, mode)

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + _swiglu(n, wg, wu, wd, mode) * col[:, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return acc


def feed(h, w, n, dims: dict, mode: str, q_block: int):
    """``h + FFN(RMSNorm(h))`` over the rows below ``n``."""
    def ffn(start, hb):
        m = rmsnorm(hb, w["ln2"], dims["norm_eps"])
        if "router" in w:
            return hb + experts(m, w, dims, mode)
        return hb + _swiglu(m, w["w_gate"], w["w_up"], w["w_down"], mode)

    return _rows(ffn, n, q_block, 0, h.shape[0], h)


def conv_mix(x, w, n, dims: dict, mode: str, q_block: int,
             zero_taps: bool = False):
    """``x + Op(RMSNorm(x))`` of a conv layer over the rows below ``n``."""
    S = x.shape[0]

    def gates(start, xb):
        bcx = _mm("sd,dn->sn", rmsnorm(xb, w["ln1"], dims["norm_eps"]),
                  w["conv_in"], mode)
        b, c, xx = jnp.split(bcx, 3, axis=-1)
        return c, b * xx

    c, u = _rows(gates, n, q_block, 0, S, x)
    k = w["conv_k"].astype(F32)
    K = k.shape[1]
    past = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    taps = range(K - 1, K) if zero_taps else range(K)
    v = sum(past[j:j + S] * k[:, j] for j in taps)

    def out(start, xb, gb):
        return xb + _mm("sd,de->se", gb, w["conv_out"], mode)

    return _rows(out, n, q_block, 0, S, x, c * v)


def attn_mix(x, w, n, dims: dict, mode: str, q_block: int):
    """``x + Op(RMSNorm(x))`` of an attention layer over the rows below
    ``n``; a query block sees every key, masked causally."""
    S = x.shape[0]
    eps = dims["norm_eps"]
    dh = dims["hidden_size"] // dims["num_attention_heads"]

    def project(start, xb):
        h = rmsnorm(xb, w["ln1"], eps)
        pos = start + jnp.arange(xb.shape[0])
        cos, sin = rope_tables(pos, dh, dims["rope_parameters"])
        q = rmsnorm(_mm("sd,dhk->shk", h, w["wq"], mode), w["q_norm"], eps)
        k = rmsnorm(_mm("sd,dhk->shk", h, w["wk"], mode), w["k_norm"], eps)
        return (rotate(q, cos, sin), rotate(k, cos, sin),
                _mm("sd,dhk->shk", h, w["wv"], mode))

    q, k, v = _rows(project, n, q_block, 0, S, x)
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(S)

    def block(start, xb, qb):
        s = _mm("qhd,khd->hqk", qb, k, mode) / jnp.sqrt(F32(dh))
        qpos = (start + jnp.arange(qb.shape[0]))[None, :, None]
        p = jax.nn.softmax(jnp.where(kpos[None, None, :] <= qpos, s,
                                     -jnp.inf), axis=-1)
        return xb + _mm("shk,hkd->sd", _mm("hqk,khd->qhd", p, v, mode),
                        w["wo"], mode)

    return _rows(block, n, q_block, 0, S, x, q)


_MIX_LEAVES = {"conv": ("ln1", "conv_in", "conv_k", "conv_out"),
               "full_attention": ("ln1", "wq", "wk", "wv", "wo", "q_norm",
                                  "k_norm")}
_FEED_LEAVES = ("ln2", "router", "router_bias", "w_gate", "w_up", "w_down")


@functools.lru_cache(maxsize=None)
def _mix_fn(dims_frozen: tuple, kind: str, mode: str, q_block: int,
            zero_taps: bool):
    """A layer's first half, ``x + Op(RMSNorm(x))``, on one sequence
    laid in the cell's width: ONE executable a kind, whatever the
    length and whatever FFN follows."""
    dims = _thaw(dims_frozen)

    def f(x, w, n):
        if kind == "conv":
            return conv_mix(x, w, n, dims, mode, q_block, zero_taps)
        return attn_mix(x, w, n, dims, mode, q_block)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _feed_fn(dims_frozen: tuple, mode: str, q_block: int):
    """A layer's second half (an executable for the dense leaves, one
    for the experts': the FFN is told by the leaves)."""
    dims = _thaw(dims_frozen)
    return jax.jit(lambda h, w, n: feed(h, w, n, dims, mode, q_block),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _scores_fn(dims_frozen: tuple, q_block: int):
    """The router's sigmoid scores ``(S, E)`` of the rows of ``h`` below
    ``n``, float32: what :func:`balanced_biases` reads."""
    dims = _thaw(dims_frozen)

    def f(h, ln2, router, n):
        def one(start, hb):
            return jax.nn.sigmoid(_mm(
                "sd,de->se", rmsnorm(hb, ln2, dims["norm_eps"]), router,
                "f32"))

        return _rows(one, n, q_block, 0, h.shape[0], h)

    return jax.jit(f)


def _mixed(x, w, n, dims: dict, kind: str, mode: str, q_block: int,
           zero_taps: bool = False):
    return _mix_fn(_layer_dims(dims), kind, mode, q_block, zero_taps)(
        x, {k: w[k] for k in _MIX_LEAVES[kind]}, n)


def _fed(h, w, n, dims: dict, mode: str, q_block: int):
    return _feed_fn(_layer_dims(dims), mode, q_block)(
        h, {k: w[k] for k in _FEED_LEAVES if k in w}, n)


_BIASES: dict = {}
CALIBRATION_TOKENS = 4096


def balanced_biases(seed: int, dims: dict, weights_dtype) -> dict:
    """``{layer: router_bias (E,) float32}`` of every expert layer (see
    the module's docstring), once a (seed, configuration) and process.
    The sequence: ``min(4096, max_len)`` seeded token ids, laid in the
    cell's width so that the check's executables are these."""
    from chipbench import weights_conv as W

    key = (int(seed), jnp.dtype(weights_dtype).name, json.dumps(
        {k: v for k, v in dims.items() if k not in (
            "check", "assumed", "published", "deployment")},
        sort_keys=True))
    if key in _BIASES:
        return _BIASES[key]
    S = dims["engine"]["max_len"]
    n = min(CALIBRATION_TOKENS, S)
    q_block = min(512, S)
    ids = np.zeros((S,), np.int32)
    ids[:n] = np.random.default_rng([int(seed), 0xCA11B]).integers(
        0, dims["vocab_size"], n)
    top = W.top_params(seed, dims, weights_dtype)
    x = top["embed"].astype(F32)[jnp.asarray(ids)]
    nd, out = dims["num_dense_layers"], {}
    n_ = jnp.int32(n)
    with jax.default_matmul_precision("highest"):
        for l, kind in enumerate(dims["layer_types"]):
            w = W.layer_params(seed, l, dims, weights_dtype, bias=None)
            h = _mixed(x, w, n_, dims, kind, "f32", q_block)
            if l >= nd:
                s = _scores_fn(_layer_dims(dims), q_block)(
                    h, w["ln2"], w["router"], n_)
                out[l] = W.balanced_bias(np.asarray(s[:n]),
                                         dims["num_experts_per_tok"])
                w["router_bias"] = jnp.asarray(out[l], weights_dtype)
            x = _fed(h, w, n_, dims, "f32", q_block)
    _BIASES[key] = out
    return out


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, mode: str):
    return jax.jit(lambda x, i, ln_f, embed: _mm(
        "sd,vd->sv", rmsnorm(x[i], ln_f, eps), embed, mode))


def _layer_dims(dims: dict) -> tuple:
    keys = ("norm_eps", "hidden_size", "num_attention_heads",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "rope_parameters")
    return _freeze({k: dims[k] for k in keys})


def served_logits(seed: int, dims: dict, weights_dtype, tokens, prompt_lens,
                  n_served, *, mode: str = "f32", q_block: int = 512,
                  zero_taps: bool = False):
    """``reference.served_logits`` for this model: teacher-forced logits
    at the positions that produced served tokens; each sequence in the
    ONE width ``tokens`` has, its own length a traced scalar; one
    layer's weights at a time."""
    from chipbench import weights_conv as W

    tokens = np.asarray(tokens, np.int32)
    N, S = tokens.shape
    q_block = min(q_block, S)
    lens = np.asarray(prompt_lens) + np.asarray(n_served)
    top = W.top_params(seed, dims, weights_dtype)
    embed = top["embed"].astype(F32)
    xs = [embed[jnp.asarray(tokens[i])] for i in range(N)]
    nd = dims["num_dense_layers"]
    with jax.default_matmul_precision("highest"):
        for l, kind in enumerate(dims["layer_types"]):
            w = W.layer_params(seed, l, dims, weights_dtype)
            xs = [_fed(_mixed(x, w, jnp.int32(n), dims, kind, mode, q_block,
                              zero_taps), w, jnp.int32(n), dims, mode,
                       q_block) for x, n in zip(xs, lens)]
        m = int(max(n_served))
        idx = np.asarray(prompt_lens)[:, None] - 1 + np.arange(m)[None, :]
        valid = np.arange(m)[None, :] < np.asarray(n_served)[:, None]
        idx = np.where(valid, idx, 0)
        served = np.take_along_axis(tokens, idx + 1, axis=1)
        head = _head_fn(float(dims["norm_eps"]), mode)
        out = [np.asarray(head(xs[i], jnp.asarray(idx[i]), top["ln_f"],
                               top["embed"])) for i in range(N)]
    return np.stack(out), served, valid
