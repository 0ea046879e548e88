"""The plain reference of a model whose every layer runs attention AND a
state-space mixer side by side (``kind: serve_hybrid``): the benchmark's
OWN copy of the forward pass that ``horovod_tpu/models/plain_reference
.py`` states (``hybrid_*``) — straightforward ``jax.numpy``, float32 at
``default_matmul_precision("highest")``, no kernel, no cache, the
recurrence a SEQUENTIAL loop over the tokens (never the chunked dual
form the program runs), NOTHING imported from the program — arranged so
that 4096 tokens at the published widths fit on one chip beside nothing
else, and so that every sequence of a cell runs through the SAME
executables whatever its length (PR 32's lesson).

The layer, as ``Falcon-H1-34B-Instruct`` publishes it (``model_type:
falcon_h1``), with ``m_*`` the published multipliers: ``n = RMSNorm(x)``;
``x' = x + m_ao Attn(m_ai n) + m_so SSM(m_si n)``; ``y = x' +
MLP(RMSNorm(x'))``; ``e = m_emb Embed[id]`` before the first layer and
``logits = m_head RMSNorm(y) W_head`` after the last; no bias but the
convolution's.
``Attn``: 20 query / 4 KV heads of 128; ``k <- m_k k`` before the
rotate-half rope; causal ``softmax(q k^T / sqrt(128)) v``; ``W_o``.
``SSM`` (Mamba-2): ``[z | xBC | dt] = (u W_in) * mup`` (the five
``ssm_multipliers`` over the z, x, B, C, dt columns); ``xBC <-
silu(conv(xBC) + b)`` (depthwise, ``mamba_d_conv`` taps, causal, zeros
before the sequence); ``dt = softplus(dt + dt_bias)``; ``h_t = exp(-
exp(A_log) dt_t) h_{t-1} + dt_t x_t B_t^T`` a head (``h_{-1} = 0``; head
``h`` reads group ``h // (heads / groups)``); ``y_t = h_t C_t + D x_t``;
``g = y * silu(z)``, an RMSNorm over EACH GROUP's columns with a learned
scale; ``g W_out``.
``MLP``: ``down(silu(m_g gate(v)) * up(v)) * m_d``.
The configuration file's ``assumed`` lists what the config has no key
for.

Departures, in memory and time only (the forward is causal, so no row
depends on a later one): every sequence lies in an array of the SAME
width (the engine's ``max_len``) and rows go in blocks of ``q_block`` of
which only those below the sequence's own length ``n`` — a traced
scalar — are computed (``reference_sparse._rows``); the recurrence's
loop runs ``n`` steps; the weights one layer at a time.

``mode`` is ``reference.py``'s (``"f32"`` the reference, ``"fp8"`` /
``"bf16"`` the lower-precision controls: every matmul's operands
rounded).  ``reset`` is the SECOND control, not the model: a boolean a
position; where it is true the state ``h`` and the convolution's past
taps are ZERO before that token — what a program serves that loses a
request's state there (:func:`lost_state`: at every chunk boundary of
the prompt and at every tick)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, _mm, rmsnorm
from chipbench.reference_patterned import _freeze, _thaw, rope_tables, rotate
from chipbench.reference_sparse import _rows
from chipbench.weights_hybrid import mup_vector


def attn(n, w, dims: dict, mode: str, q_block: int, length):
    """``Attn(n)`` over the rows below ``length``; a query block sees
    every key, masked causally."""
    S, dh = n.shape[0], dims["head_dim"]

    def project(start, nb):
        pos = start + jnp.arange(nb.shape[0])
        cos, sin = rope_tables(pos, dh, {"rope_theta": dims["rope_theta"]})
        k = _mm("sd,dhk->shk", nb, w["wk"], mode) * dims["key_multiplier"]
        return (rotate(_mm("sd,dhk->shk", nb, w["wq"], mode), cos, sin),
                rotate(k, cos, sin), _mm("sd,dhk->shk", nb, w["wv"], mode))

    q, k, v = _rows(project, length, q_block, 0, S, n)
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(S)

    def block(start, qb):
        s = _mm("qhd,khd->hqk", qb, k, mode) / jnp.sqrt(F32(dh))
        qpos = (start + jnp.arange(qb.shape[0]))[None, :, None]
        p = jax.nn.softmax(jnp.where(kpos[None, None, :] <= qpos, s,
                                     -jnp.inf), axis=-1)
        return _mm("shk,hkd->sd", _mm("hqk,khd->qhd", p, v, mode),
                   w["wo"], mode)

    return _rows(block, length, q_block, 0, S, q)


def ssm(n, w, dims: dict, mode: str, q_block: int, length, reset):
    """``SSM(n)`` over the rows below ``length``: the projections in
    blocks of rows, the convolution and the recurrence token by token."""
    S = n.shape[0]
    I, H, P = dims["mamba_d_ssm"], dims["mamba_n_heads"], dims["mamba_d_head"]
    G, N, K = (dims["mamba_n_groups"], dims["mamba_d_state"],
               dims["mamba_d_conv"])
    gn, C = G * N, I + 2 * G * N
    mup = mup_vector(dims)      # the five multipliers over W_in's columns

    def project(start, nb):
        out = _mm("sd,dn->sn", nb, w["ssm_in"], mode) * mup
        dt = jax.nn.softplus(out[:, I + C:] + w["ssm_dt_bias"].astype(F32))
        return out[:, :I], out[:, I:I + C], dt

    z, xbc, dt = _rows(project, length, q_block, 0, S, n)
    a_neg = -jnp.exp(w["ssm_A_log"].astype(F32))
    kern, bias = w["ssm_conv_k"].astype(F32), w["ssm_conv_b"].astype(F32)
    skip = w["ssm_D"].astype(F32)

    def token(t, carry):
        h, taps, ys = carry             # (H, P, N), (K - 1, C), (S, I)
        lost = reset[t]
        h = jnp.where(lost, 0.0, h)
        taps = jnp.where(lost, 0.0, taps)
        window = jnp.concatenate([taps, xbc[t][None]])
        act = jax.nn.silu(jnp.sum(window * kern.T, axis=0) + bias)
        x = act[:I].reshape(H, P)
        b = jnp.repeat(act[I:I + gn].reshape(G, N), H // G, axis=0)
        c = jnp.repeat(act[I + gn:].reshape(G, N), H // G, axis=0)
        h = jnp.exp(a_neg * dt[t])[:, None, None] * h \
            + (dt[t][:, None] * x)[:, :, None] * b[:, None, :]
        y = jnp.einsum("hpn,hn->hp", h, c,
                       precision=jax.lax.Precision.HIGHEST) \
            + skip[:, None] * x
        return h, window[1:], ys.at[t].set(y.reshape(I))

    _, _, y = jax.lax.fori_loop(
        0, length, token, (jnp.zeros((H, P, N), F32),
                           jnp.zeros((K - 1, C), F32), jnp.zeros((S, I), F32)))

    def out(start, yb, zb):
        g = (yb * jax.nn.silu(zb)).reshape(-1, G, I // G)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                              + dims["rms_norm_eps"])
        return _mm("si,id->sd", g.reshape(-1, I) * w["ssm_norm"].astype(F32),
                   w["ssm_out"], mode)

    return _rows(out, length, q_block, 0, S, y, z)


def mix(x, w, length, reset, dims: dict, mode: str, q_block: int):
    """``x + m_ao Attn(m_ai n) + m_so SSM(m_si n)``."""
    n = _rows(lambda start, xb: rmsnorm(xb, w["ln1"], dims["rms_norm_eps"]),
              length, q_block, 0, x.shape[0], x)
    a = attn(n * dims["attention_in_multiplier"], w, dims, mode, q_block,
             length)
    s = ssm(n * dims["ssm_in_multiplier"], w, dims, mode, q_block, length,
            reset)
    return (x + dims["attention_out_multiplier"] * a
            + dims["ssm_out_multiplier"] * s)


def feed(h, w, length, dims: dict, mode: str, q_block: int):
    """``h + MLP(RMSNorm(h))`` over the rows below ``length``."""
    m_g, m_d = dims["mlp_multipliers"]

    def mlp(start, hb):
        v = rmsnorm(hb, w["ln2"], dims["rms_norm_eps"])
        gate = jax.nn.silu(_mm("sd,df->sf", v, w["w_gate"], mode) * m_g)
        return hb + _mm("sf,fd->sd", gate * _mm("sd,df->sf", v, w["w_up"],
                                                mode), w["w_down"], mode) * m_d

    return _rows(mlp, length, q_block, 0, h.shape[0], h)


_MIX_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ssm_in", "ssm_conv_k",
               "ssm_conv_b", "ssm_dt_bias", "ssm_A_log", "ssm_D", "ssm_norm",
               "ssm_out")
_FEED_LEAVES = ("ln2", "w_gate", "w_up", "w_down")
_DIMS = ("rms_norm_eps", "head_dim", "rope_theta", "key_multiplier",
         "attention_in_multiplier", "attention_out_multiplier",
         "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
         "mlp_multipliers", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
         "mamba_n_groups", "mamba_d_state", "mamba_d_conv")


def _layer_dims(dims: dict) -> tuple:
    return _freeze({k: dims[k] for k in _DIMS})


@functools.lru_cache(maxsize=None)
def _mix_fn(dims_frozen: tuple, mode: str, q_block: int):
    """A layer's first half on one sequence laid in the cell's width:
    ONE executable, whatever the length."""
    dims = _thaw(dims_frozen)
    return jax.jit(lambda x, w, n, reset: mix(x, w, n, reset, dims, mode,
                                              q_block), donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _feed_fn(dims_frozen: tuple, mode: str, q_block: int):
    dims = _thaw(dims_frozen)
    return jax.jit(lambda h, w, n: feed(h, w, n, dims, mode, q_block),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, scale: float, mode: str):
    return jax.jit(lambda x, i, ln_f, head: _mm(
        "sd,dv->sv", rmsnorm(x[i], ln_f, eps), head, mode) * scale)


def lost_state(width: int, prompt_len: int, chunk: int) -> np.ndarray:
    """The second control's ``reset`` of one sequence: True at every
    chunk boundary inside the prompt and at every position a tick
    serves (``prompt_len`` on)."""
    t = np.arange(width)
    return np.where(t < prompt_len, (t > 0) & (t % chunk == 0), True)


def served_logits(seed: int, dims: dict, weights_dtype, tokens, prompt_lens,
                  n_served, *, mode: str = "f32", q_block: int = 512,
                  lose_state: bool = False):
    """``reference.served_logits`` for this model: teacher-forced logits
    at the positions that produced served tokens; each sequence in the
    ONE width ``tokens`` has, its own length a traced scalar; one
    layer's weights at a time."""
    from chipbench import weights_hybrid as W

    tokens = np.asarray(tokens, np.int32)
    N, S = tokens.shape
    q_block = min(q_block, S)
    lens = np.asarray(prompt_lens) + np.asarray(n_served)
    chunk = dims["engine"]["prefill_chunk_tokens"]
    resets = [jnp.asarray(lost_state(S, p, chunk) if lose_state
                          else np.zeros((S,), bool)) for p in prompt_lens]
    top = W.top_params(seed, dims, weights_dtype)
    embed = top["embed"].astype(F32) * dims["embedding_multiplier"]
    xs = [embed[jnp.asarray(tokens[i])] for i in range(N)]
    key = _layer_dims(dims)
    with jax.default_matmul_precision("highest"):
        for l in range(dims["num_hidden_layers"]):
            w = W.layer_params(seed, l, dims, weights_dtype)
            wm = {k: w[k] for k in _MIX_LEAVES}
            wf = {k: w[k] for k in _FEED_LEAVES}
            xs = [_feed_fn(key, mode, q_block)(
                _mix_fn(key, mode, q_block)(x, wm, jnp.int32(n), r), wf,
                jnp.int32(n)) for x, n, r in zip(xs, lens, resets)]
        m = int(max(n_served))
        idx = np.asarray(prompt_lens)[:, None] - 1 + np.arange(m)[None, :]
        valid = np.arange(m)[None, :] < np.asarray(n_served)[:, None]
        idx = np.where(valid, idx, 0)
        served = np.take_along_axis(tokens, idx + 1, axis=1)
        head = _head_fn(float(dims["rms_norm_eps"]),
                        float(dims["lm_head_multiplier"]), mode)
        out = [np.asarray(head(xs[i], jnp.asarray(idx[i]), top["ln_f"],
                               top["head"])) for i in range(N)]
    return np.stack(out), served, valid
