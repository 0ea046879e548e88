"""The plain reference of a latent-attention expert model (``kind:
serve_latent``): the benchmark's OWN copy of the forward pass that
``horovod_tpu/models/plain_reference.py`` states (``latent_*``) —
straightforward ``jax.numpy``, float32 at
``default_matmul_precision("highest")``, NON-absorbed attention (every
head's K and V expanded from the latent, every position against every
earlier one), no kernel, no cache, NOTHING imported from the program —
arranged so that 18 k tokens at the published widths fit on one chip
beside nothing else.

The layer, as published (DeepSeek-V3's block, whose config keys A.X-K1's
are; no bias; ``n = RMSNorm(x)``; ``h = x + Attn(n1)``, ``y = h +
FFN(n2)``): ``cq = RMSNorm(n Wqa)``, ``q = cq Wqb`` -> heads x (nope +
rope); ``[ckv, kr] = n Wkva``, ``ckv = RMSNorm(ckv)``, ``k_rope =
RoPE(kr)`` ONE per token; ``[k_nope_i, v_i] = ckv Wkvb_i``; scores
``(q_nope_i . k_nope_i + q_rope_i . k_rope) * s``, causal, float32
softmax; ``o = concat_i(sum p v_i) Wo``.  YaRN over the rope dims, ``s =
(nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
Experts: ``sc = sigmoid(n Wr)`` over ALL the router's outputs, in
``n_group`` groups, a group's score the sum of its two largest, the
``topk_group`` best groups kept, among theirs the ``num_experts_per_tok``
largest, ``g = sc[sel] / sum(sc[sel]) * routed_scaling_factor``; ``FFN =
sum over the experts HELD of g_e E_e(n) + S(n)`` with one shared SwiGLU
``S``; the first ``first_k_dense_replace`` layers' FFN one dense SwiGLU.

The held range (``expert_offset`` .. + ``n_routed_experts``) is the
configuration's: what the absent experts would add is left out, here as
in the program, and the partial result goes on to the next layer.

Departures, each in memory only (same mathematics): queries go in
blocks, the held experts one at a time — EVERY held expert on every
position, times the weight the router gave it (0 where it was not
picked) — and the weights one layer at a time from
``weights_latent.layer_params``.  Assumptions (``topk_method: "none"``
read as no score-correction bias; -inf for the masked groups; rotate-half
pairing; ``seq_aux`` no part of the forward): the configuration file's
``assumed``, and ``plain_reference.py``'s list.

``mode`` is ``reference.py``'s: ``"f32"`` the reference, ``"bf16"`` and
``"fp8"`` the lower-precision CONTROLS (every matmul's operands rounded,
the router's too)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, _mm, logits_at, rmsnorm
from chipbench.reference_patterned import (_freeze, _thaw, rope_tables,
                                           rotate)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_entry(dims: dict) -> dict:
    """``rope_scaling`` as ``rope_tables`` takes a rope entry."""
    rs = dims["rope_scaling"]
    assert rs["type"] == "yarn", rs
    f = float(rs["factor"])
    return {"rope_type": "yarn", "rope_theta": dims["rope_theta"],
            "factor": f, "original_max_position_embeddings":
                rs["original_max_position_embeddings"],
            "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
            "attention_factor": _yarn_mscale(f, rs.get("mscale", 1))
            / _yarn_mscale(f, rs.get("mscale_all_dim", 0))}


def softmax_scale(dims: dict) -> float:
    s = (dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]) ** -0.5
    rs = dims["rope_scaling"]
    if rs.get("mscale_all_dim", 0):
        s *= _yarn_mscale(float(rs["factor"]), rs["mscale_all_dim"]) ** 2
    return s


def attention(q_nope, q_rope, k_nope, k_rope, v, scale: float, mode: str,
              q_block: int):
    """Causal softmax attention of one sequence, heads expanded.
    Queries go in blocks of ``q_block`` (memory only: each block sees
    every key, masked)."""
    S, H, _ = q_nope.shape
    q_block = min(q_block, S)
    assert S % q_block == 0, (S, q_block)
    kpos = jnp.arange(S)

    def block(args):
        qn, qr, start = args
        s = (_mm("qhd,khd->hqk", qn, k_nope, mode)
             + _mm("qhd,kd->hqk", qr, k_rope, mode)) * scale
        qpos = (start + jnp.arange(q_block))[None, :, None]
        p = jax.nn.softmax(
            jnp.where(kpos[None, None, :] <= qpos, s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", p, v, mode)

    nb = S // q_block
    out = jax.lax.map(block, (
        q_nope.reshape(nb, q_block, H, -1),
        q_rope.reshape(nb, q_block, H, -1), jnp.arange(nb) * q_block))
    return out.reshape(S, H, -1)


def route(n, router, dims: dict, mode: str):
    """``(S, E)`` combination weights over ALL the router's outputs."""
    logits = _mm("sd,de->se", n, router, mode)
    sc = (jax.nn.sigmoid(logits) if dims["scoring_func"] == "sigmoid"
          else jax.nn.softmax(logits, axis=-1))
    S, E = sc.shape
    choice, g = sc, dims["n_group"]
    if g > 1:
        grouped = sc.reshape(S, g, E // g)
        g_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(g_score, dims["topk_group"])
        kept = jnp.zeros((S, g), bool).at[
            jnp.arange(S)[:, None], keep].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf
                           ).reshape(S, E)
    _, top_e = jax.lax.top_k(choice, dims["num_experts_per_tok"])
    top_g = jnp.take_along_axis(sc, top_e, axis=-1)
    if dims["norm_topk_prob"]:
        top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
    top_g = top_g * dims["routed_scaling_factor"]
    return jnp.zeros_like(sc).at[jnp.arange(S)[:, None], top_e].set(top_g)


def _swiglu(n, gate, up, down, mode: str):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", n, gate, mode))
               * _mm("sd,df->sf", n, up, mode), down, mode)


def experts(n, w, dims: dict, mode: str):
    """The held experts one at a time on every position, weighted by
    the router's weight (0 if not picked), and the shared expert."""
    off = dims["expert_offset"]
    weight = route(n, w["router"], dims, mode)[
        :, off:off + w["w_gate"].shape[0]]

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + _swiglu(n, wg, wu, wd, mode) * col[:, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return acc + _swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"], mode)


def layer(x, w, dims: dict, mode: str, q_block: int):
    """One pre-norm block on one sequence ``x``: (S, D) float32; dense
    or expert by whether the layer has a router."""
    eps, nope, c = dims["rms_norm_eps"], dims["qk_nope_head_dim"], \
        dims["kv_lora_rank"]
    h = rmsnorm(x, w["ln1"], eps)
    cq = rmsnorm(_mm("sd,dr->sr", h, w["wq_a"], mode), w["q_a_norm"], eps)
    q = _mm("sr,rhk->shk", cq, w["wq_b"], mode)
    kv = _mm("sd,dc->sc", h, w["wkv_a"], mode)
    ckv = rmsnorm(kv[:, :c], w["kv_a_norm"], eps)
    cos, sin = rope_tables(jnp.arange(x.shape[0]),
                           dims["qk_rope_head_dim"], rope_entry(dims))
    q_rope = rotate(q[..., nope:], cos, sin)
    k_rope = rotate(kv[:, None, c:], cos, sin)[:, 0]
    kvb = _mm("sc,chk->shk", ckv, w["wkv_b"], mode)
    o = attention(q[..., :nope], q_rope, kvb[..., :nope], k_rope,
                  kvb[..., nope:], softmax_scale(dims), mode, q_block)
    x = x + _mm("shk,hkd->sd", o, w["wo"], mode)
    n = rmsnorm(x, w["ln2"], eps)
    if "router" in w:
        return x + experts(n, w, dims, mode)
    return x + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"], mode)


_LAYER_KEYS = ("rms_norm_eps", "qk_nope_head_dim", "qk_rope_head_dim",
               "kv_lora_rank", "rope_theta", "rope_scaling", "scoring_func",
               "n_group", "topk_group", "num_experts_per_tok",
               "norm_topk_prob", "routed_scaling_factor", "expert_offset")


@functools.lru_cache(maxsize=None)
def _rows_layer_fn(dims_frozen: tuple, mode: str, q_block: int):
    dims = _thaw(dims_frozen)

    def f(xs, w):
        return jax.lax.map(lambda x: layer(x, w, dims, mode, q_block), xs)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, mode: str):
    return jax.jit(lambda x, i, ln_f, head: logits_at(
        x[i], ln_f, head, {"rms_norm_eps": eps}, mode))


def served_logits(seed: int, dims: dict, weights_dtype, tokens, prompt_lens,
                  n_served, *, mode: str = "f32", q_block: int = 256,
                  pad_step: int = 2048):
    """``reference.served_logits`` for this model: teacher-forced logits
    at the positions that produced served tokens, layer by layer, one
    layer's weights at a time.  Each sequence runs at its OWN length
    rounded up to ``pad_step`` (sequences of one rounded length
    together): the forward is causal, so what lies behind a sequence's
    last served token cannot reach a position that is read, and the
    attention of every position against every earlier one is quadratic
    in what is kept."""
    from chipbench import weights_latent as W

    tokens = np.asarray(tokens, np.int32)
    plens, n_served = np.asarray(prompt_lens), np.asarray(n_served)
    top = W.top_params(seed, dims, weights_dtype)
    embed = top["embed"].astype(F32)
    need = np.minimum(-(-(plens + n_served) // pad_step) * pad_step,
                      tokens.shape[1])
    groups = {int(n): np.nonzero(need == n)[0] for n in sorted(set(need))}
    xs = {n: embed[jnp.asarray(tokens[rows, :n])]
          for n, rows in groups.items()}
    fn = _rows_layer_fn(_freeze({k: dims[k] for k in _LAYER_KEYS}), mode,
                        q_block)
    with jax.default_matmul_precision("highest"):
        for l in range(dims["num_hidden_layers"]):
            w = W.layer_params(seed, l, dims, weights_dtype)
            xs = {n: fn(x, w) for n, x in xs.items()}
        m = int(max(n_served))
        idx = plens[:, None] - 1 + np.arange(m)[None, :]
        valid = np.arange(m)[None, :] < n_served[:, None]
        idx = np.where(valid, idx, 0)
        served = np.take_along_axis(tokens, idx + 1, axis=1)
        head_fn = _head_fn(float(dims["rms_norm_eps"]), mode)
        out = [None] * tokens.shape[0]
        for n, rows in groups.items():
            for j, i in enumerate(rows):
                out[i] = np.asarray(head_fn(
                    xs[n][j], jnp.asarray(idx[i]), top["ln_f"], top["head"]))
    return np.stack(out), served, valid
