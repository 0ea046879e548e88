"""The plain reference of a model of LINEAR-ATTENTION layers between
BLOCK-SPARSE attention layers (``kind: serve_linear_sparse``): the
benchmark's OWN copy of the forward pass that ``horovod_tpu/models/
plain_reference.py`` states (``sala_*``) — straightforward ``jax.numpy``,
float32 at ``default_matmul_precision("highest")``, no kernel, no cache,
the recurrence a SEQUENTIAL loop over the tokens (never the chunked dual
form the program runs), the selection by BRUTE FORCE from its definition
(every window's mean, every block's score, ``lax.top_k``), NOTHING
imported from the program — arranged so that 28 672 tokens at the
published widths fit on one chip beside nothing else, and so that every
sequence of a cell runs through the SAME executables whatever its length.

The layers, as ``MiniCPM-SALA`` publishes them (``model_type:
minicpm_sala``), with ``r = scale_depth / sqrt(32)``: ``n = RMSNorm(x)``;
``x' = x + r Mix(n)``; ``y = x' + r SwiGLU(RMSNorm(x'))``; ``e = scale_emb
Embed[id]`` before the first layer and ``logits = Head(RMSNorm(y) /
(hidden_size / dim_model_base))`` after the last; no bias.
``lightning-attn``: ``q = RMSNorm(n W_q)``, ``k = RMSNorm(n W_k)`` a head
of 128, ``v = n W_v``; rotate-half rope on q, k; a head keeps ``S (128,
128)``: ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(128))
S_t``; ``(RMSNorm(o) * sigmoid(n W_g)) W_o``.
``minicpm4`` (InfLLM-V2; 32 query / 2 KV heads of 128, no rope): a
compressed key ``c_j = mean(k[16 j .. 16 j + 31])`` a KV head for every
window WHOLE in the query's context; a query whose context is longer than
``dense_len``: ``p_h = softmax_j(q_h . c_j / sqrt(128))``, summed over the
16 heads of the KV head; a block of 64 tokens scores the largest over the
windows that overlap it; the query attends the first ``init_blocks``
blocks, the ``window_size / block_size`` blocks up to its own and the
``topk`` best-scored of the rest (ties to the lower block), causally;
a shorter context attends everything; ``(o * sigmoid(n W_g)) W_o``.
The configuration file's ``assumed`` lists what the config has no key
for (the ``sparse_config``, the decay, the norms' and gates' places).

Departures, in memory and time only (the forward is causal, so no row
depends on a later one): every sequence lies in an array of the SAME
width (the engine's ``max_len``) and rows go in blocks of which only
those below the sequence's own length ``n`` — a traced scalar — are
computed (``reference_sparse._rows``); the recurrence's loop runs ``n``
steps; the weights one layer at a time; a block's score is read off the
windows ``m b - 1 .. m b + m - 1`` (``m`` strides a block), which are the
ones that overlap it, each checked against the overlap's definition.

``mode`` is ``reference.py``'s (``"f32"`` the reference, ``"fp8"`` /
``"bf16"`` the lower-precision controls: every matmul's operands
rounded).  ``reset`` and ``select`` are the SECOND and THIRD controls,
not the model: where ``reset`` is true the state ``S`` is ZERO before
that token (:func:`lost_state`: at every chunk boundary of the prompt and
at every tick); ``select=False`` attends everything everywhere."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, _mm, rmsnorm
from chipbench.reference_patterned import _freeze, _thaw, rope_tables, rotate
from chipbench.reference_sparse import _rows
from chipbench.weights_linear_sparse import (layers_run, logit_scale,
                                             residual_scale)


def sparse_config(dims: dict) -> dict:
    return dims.get("sparse_config") or dims["assumed"]["sparse_config"]


def linear(n, w, dims: dict, mode: str, q_block: int, length, reset):
    """The lightning mixer over the rows below ``length``: the
    projections in blocks of rows, the recurrence token by token."""
    S = n.shape[0]
    H, dh = dims["lightning_nh"], dims["lightning_head_dim"]
    eps = dims["rms_norm_eps"]

    def project(start, nb):
        def heads(leaf):
            return _mm("sd,dn->sn", nb, w[leaf], mode).reshape(-1, H, dh)

        q, k, v = heads("lin_q"), heads("lin_k"), heads("lin_v")
        if dims["qk_norm"]:
            q = rmsnorm(q, w["lin_q_norm"], eps)
            k = rmsnorm(k, w["lin_k_norm"], eps)
        if dims["lightning_use_rope"]:
            cos, sin = rope_tables(start + jnp.arange(nb.shape[0]), dh,
                                   {"rope_theta": dims["rope_theta"]})
            q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        return q / math.sqrt(dh), k, v, _mm("sd,dn->sn", nb, w["lin_g"], mode)

    q, k, v, g = _rows(project, length, q_block, 0, S, n)
    lam = jnp.exp(w["lin_decay"].astype(F32))[:, None, None]

    def token(t, carry):
        state, ys = carry                       # (H, dh, dh), (S, H, dh)
        state = jnp.where(reset[t], 0.0, state)
        state = lam * state + k[t][:, :, None] * v[t][:, None, :]
        y = jnp.einsum("hk,hkv->hv", q[t], state,
                       precision=jax.lax.Precision.HIGHEST)
        return state, ys.at[t].set(y)

    _, y = jax.lax.fori_loop(0, length, token, (
        jnp.zeros((H, dh, dh), F32), jnp.zeros((S, H, dh), F32)))

    def out(start, yb, gb):
        o = rmsnorm(yb, w["lin_norm"], eps).reshape(-1, H * dh)
        return _mm("sn,nd->sd", o * jax.nn.sigmoid(gb), w["lin_o"], mode)

    return _rows(out, length, q_block, 0, S, y, g)


def selected_blocks(qb, pos, c, n_b: int, sc: dict, mode: str):
    """``(Q, H_kv, n_blocks)`` bool: the blocks each query of ``qb``
    ``(Q, H, Dh)`` at positions ``pos`` ``(Q,)`` attends under the
    selection, from the compressed keys ``c`` ``(n_windows, H_kv, Dh)``
    — forced and picked, and every block where the context is no longer
    than ``dense_len``."""
    Q, H, dh = qb.shape
    n_w, hkv = c.shape[:2]
    ker, stride, blk = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    m = blk // stride
    j = jnp.arange(n_w)
    whole = stride * j[None, :] + ker <= pos[:, None] + 1    # (Q, nW)
    s = _mm("qhd,jhd->qhj", qb, jnp.repeat(c, H // hkv, axis=1), mode
            ) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(whole[:, None, :], s, -jnp.inf), axis=-1)
    p = jnp.where(whole[:, None, :], p, 0.0)
    p = jnp.where(whole[:, None, :],
                  p.reshape(Q, hkv, H // hkv, n_w).sum(axis=2), -jnp.inf)
    b = jnp.arange(n_b)
    score = jnp.full((Q, hkv, n_b), -jnp.inf, F32)
    for o in range(-((ker - 1) // stride), m):      # the windows m b + o
        jw = m * b + o
        overlap = ((jw >= 0) & (jw < n_w) & (stride * jw <= blk * b + blk - 1)
                   & (stride * jw + ker - 1 >= blk * b))
        got = jnp.take(p, jnp.clip(jw, 0, n_w - 1), axis=2)
        score = jnp.maximum(score, jnp.where(overlap, got, -jnp.inf))
    own = pos // blk
    w_blocks, init = sc["window_size"] // blk, sc["init_blocks"]
    forced = (((b[None, :] < init) | (b[None, :] > own[:, None] - w_blocks))
              & (b[None, :] <= own[:, None]))
    rest = (b[None, :] >= init) & (b[None, :] <= own[:, None] - w_blocks)
    topk = min(sc["topk"], n_b)
    _, best = jax.lax.top_k(jnp.where(rest[:, None, :], score, -jnp.inf), topk)
    ok = jnp.arange(topk)[None, :] < jnp.minimum(
        sc["topk"], rest.sum(axis=-1))[:, None]
    picked = jnp.any((best[..., None] == b) & ok[:, None, :, None], axis=2)
    dense = (pos + 1 <= sc["dense_len"])[:, None, None]
    return jnp.where(dense, (b[None, :] <= own[:, None])[:, None, :],
                     picked | forced[:, None, :])


def sparse_attention(n, w, dims: dict, mode: str, q_block: int, length,
                     select: bool):
    """The ``minicpm4`` mixer over the rows below ``length``; a query
    block sees every key, masked by its selection and causally."""
    S, dh, eps = n.shape[0], dims["head_dim"], dims["rms_norm_eps"]
    sc = sparse_config(dims)
    blk = sc["block_size"]

    def project(start, nb):
        q = _mm("sd,dhk->shk", nb, w["wq"], mode)
        k = _mm("sd,dhk->shk", nb, w["wk"], mode)
        if dims["qk_norm"]:
            q, k = rmsnorm(q, w["q_norm"], eps), rmsnorm(k, w["k_norm"], eps)
        if dims["attn_use_rope"]:
            cos, sin = rope_tables(start + jnp.arange(nb.shape[0]), dh,
                                   {"rope_theta": dims["rope_theta"]})
            q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        return (q, k, _mm("sd,dhk->shk", nb, w["wv"], mode),
                _mm("sd,dn->sn", nb, w["wg"], mode))

    q, k, v, g = _rows(project, length, q_block, 0, S, n)
    H, hkv = q.shape[1], k.shape[1]
    ker, stride = sc["kernel_size"], sc["kernel_stride"]
    n_w = (S - ker) // stride + 1
    # every window's mean: c_j = mean(k[stride j : stride j + ker])
    c = jnp.mean(jnp.stack([k[o:o + stride * (n_w - 1) + 1:stride]
                            for o in range(ker)]), axis=0)   # (nW, Hkv, Dh)
    kr, vr = jnp.repeat(k, H // hkv, axis=1), jnp.repeat(v, H // hkv, axis=1)
    kpos = jnp.arange(S)

    def block(start, qb, gb):
        pos = start + jnp.arange(qb.shape[0])
        vis = kpos[None, None, :] <= pos[:, None, None]      # (Q, 1, S)
        if select and S > sc["dense_len"]:
            blocks = selected_blocks(qb, pos, c, -(-S // blk), sc, mode)
            vis = vis & jnp.repeat(jnp.repeat(blocks, blk, axis=-1)[..., :S],
                                   H // hkv, axis=1)
        s = _mm("qhd,khd->qhk", qb, kr, mode) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1)
        o = _mm("qhk,khd->qhd", p, vr, mode)
        if dims["attn_use_output_gate"]:
            o = o * jax.nn.sigmoid(gb).reshape(o.shape)
        return _mm("shk,hkd->sd", o, w["wo"], mode)

    return _rows(block, length, q_block, 0, S, q, g)


def mix(x, w, length, reset, dims: dict, kind: str, mode: str, q_block: int,
        select: bool):
    """``x + r Mix(RMSNorm(x))`` of one layer of ``kind``."""
    n = _rows(lambda start, xb: rmsnorm(xb, w["ln1"], dims["rms_norm_eps"]),
              length, q_block, 0, x.shape[0], x)
    if kind == "linear":
        h = linear(n, w, dims, mode, q_block, length, reset)
    else:
        h = sparse_attention(n, w, dims, mode, min(q_block, 256), length,
                             select)
    return x + residual_scale(dims) * h


def feed(h, w, length, dims: dict, mode: str, q_block: int):
    """``h + r SwiGLU(RMSNorm(h))`` over the rows below ``length``."""
    r = residual_scale(dims)

    def mlp(start, hb):
        v = rmsnorm(hb, w["ln2"], dims["rms_norm_eps"])
        gate = jax.nn.silu(_mm("sd,df->sf", v, w["w_gate"], mode))
        return hb + r * _mm("sf,fd->sd", gate * _mm("sd,df->sf", v, w["w_up"],
                                                    mode), w["w_down"], mode)

    return _rows(mlp, length, q_block, 0, h.shape[0], h)


_FEED_LEAVES = ("ln2", "w_gate", "w_up", "w_down")
_DIMS = ("rms_norm_eps", "head_dim", "rope_theta", "lightning_nh",
         "lightning_head_dim", "lightning_use_rope", "attn_use_rope",
         "attn_use_output_gate", "qk_norm", "scale_depth",
         "num_hidden_layers")


def _layer_dims(dims: dict) -> tuple:
    return _freeze({**{k: dims[k] for k in _DIMS},
                    "sparse_config": sparse_config(dims),
                    "published": {"num_hidden_layers": dims.get(
                        "published", {}).get("num_hidden_layers",
                                             dims["num_hidden_layers"])}})


@functools.lru_cache(maxsize=None)
def _mix_fn(dims_frozen: tuple, kind: str, mode: str, q_block: int,
            select: bool):
    """A layer's first half on one sequence laid in the cell's width:
    ONE executable a kind, whatever the length."""
    dims = _thaw(dims_frozen)
    return jax.jit(lambda x, w, n, reset: mix(
        x, w, n, reset, dims, kind, mode, q_block, select),
        donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _feed_fn(dims_frozen: tuple, mode: str, q_block: int):
    dims = _thaw(dims_frozen)
    return jax.jit(lambda h, w, n: feed(h, w, n, dims, mode, q_block),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, scale: float, mode: str):
    return jax.jit(lambda x, i, ln_f, head: _mm(
        "sd,dv->sv", rmsnorm(x[i], ln_f, eps) * scale, head, mode))


def lost_state(width: int, prompt_len: int, chunk: int) -> np.ndarray:
    """The second control's ``reset`` of one sequence: True at every
    chunk boundary inside the prompt and at every position a tick
    serves (``prompt_len`` on)."""
    t = np.arange(width)
    return np.where(t < prompt_len, (t > 0) & (t % chunk == 0), True)


def served_logits(seed: int, dims: dict, weights_dtype, tokens, prompt_lens,
                  n_served, *, mode: str = "f32", q_block: int = 512,
                  lose_state: bool = False, select: bool = True):
    """``reference.served_logits`` for this model: teacher-forced logits
    at the positions that produced served tokens; each sequence in the
    ONE width ``tokens`` has, its own length a traced scalar; one
    layer's weights at a time."""
    from chipbench import weights_linear_sparse as W

    tokens = np.asarray(tokens, np.int32)
    N, S = tokens.shape
    q_block = min(q_block, S)
    lens = np.asarray(prompt_lens) + np.asarray(n_served)
    chunk = dims["engine"]["prefill_chunk_tokens"]
    resets = [jnp.asarray(lost_state(S, p, chunk) if lose_state
                          else np.zeros((S,), bool)) for p in prompt_lens]
    top = W.top_params(seed, dims, weights_dtype)
    embed = top["embed"].astype(F32) * dims["scale_emb"]
    xs = [embed[jnp.asarray(tokens[i])] for i in range(N)]
    key = _layer_dims(dims)
    with jax.default_matmul_precision("highest"):
        for l, kind in layers_run(dims):
            _, w = W.layer_params(seed, l, dims, weights_dtype)
            wf = {k: w.pop(k) for k in _FEED_LEAVES}
            xs = [_feed_fn(key, mode, q_block)(
                _mix_fn(key, kind, mode, q_block, select)(
                    x, w, jnp.int32(n), r), wf, jnp.int32(n))
                for x, n, r in zip(xs, lens, resets)]
        m = int(max(n_served))
        idx = np.asarray(prompt_lens)[:, None] - 1 + np.arange(m)[None, :]
        valid = np.arange(m)[None, :] < np.asarray(n_served)[:, None]
        idx = np.where(valid, idx, 0)
        served = np.take_along_axis(tokens, idx + 1, axis=1)
        head = _head_fn(float(dims["rms_norm_eps"]), logit_scale(dims), mode)
        out = [np.asarray(head(xs[i], jnp.asarray(idx[i]), top["ln_f"],
                               top["head"])) for i in range(N)]
    return np.stack(out), served, valid
