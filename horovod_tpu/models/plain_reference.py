"""The plain references of the served models that are more than the
uniform block: a patterned expert model (below), a LATENT-ATTENTION
expert model (``latent_*``, with its own description there) and that
model with a lightning indexer's SPARSE selection and a biased router
(``sparse_*``), a model of gated SHORT CONVOLUTIONS between attention
layers (``conv_*``), a model whose every layer runs attention AND a
STATE-SPACE mixer side by side (``hybrid_*``), and a model of LINEAR-
ATTENTION layers between BLOCK-SPARSE attention layers (``sala_*``, at
the end of the file).

The plain reference of a patterned expert model: its forward pass in
straightforward ``jax.numpy``, float32 at
``jax.default_matmul_precision("highest")``, with no kernel, no cache
and no batching — one sequence, every position against every earlier
one, every expert computed and masked.  It imports nothing of the
program: ``tests/test_window_layers.py`` holds the program's three serving
bodies (whole prefill, chunked prefill, paged decode through both
caches) to its LOGITS.

It follows the published description of the layer (Qwen3-MoE's config
class with ``layer_types`` and per-kind ``rope_parameters``, as
``Mellum2-12B-A2.5B-Instruct`` publishes it), ``n = RMSNorm(x)``,
``h = x + Attn(n1)``, ``y = h + MoE(n2)``, no bias anywhere:

* attention: ``q = n Wq`` (T, H, Dh), ``k``/``v`` (T, H_kv, Dh); q and k
  each pass an RMSNorm over the head with a learned scale; rotate-half
  rope over the whole head — plain on sliding layers, YaRN on full
  layers; scores ``q.k / sqrt(Dh)``; key ``j`` visible to query ``i``
  iff ``j <= i`` and, on a sliding layer, ``i - j < sliding_window``;
  each KV head serves ``H / H_kv`` query heads;
* experts: ``p = softmax(n Wr)`` in float32, the ``num_experts_per_tok``
  largest, renormalised when ``norm_topk_prob``; ``sum_e w_e Wdown_e
  (silu(Wgate_e n) * Wup_e n)``; nothing dropped, no shared expert;
* final RMSNorm and an untied head.

Departures from the published description: the q/k norm is the config
class's convention (the config has no key for it); the multi-token
prediction head the model card mentions has no key in ``config.json``
and is no part of the next-token forward, so it is left out.

``dims`` uses the published key names; ``params`` is a checkpoint's
tree: ``embed (V, D)``, ``head (D, V)``, ``ln_f (D)`` and ``layers``
stacked on a leading axis (``ln1``, ``ln2``, ``wq (D, H, Dh)``,
``wk``/``wv (D, H_kv, Dh)``, ``wo (H, Dh, D)``, ``q_norm``/``k_norm
(Dh)``, ``router (D, E)``, ``w_gate``/``w_up (E, D, F)``, ``w_down
(E, F, D)``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rmsnorm(x, w, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope_tables(positions, head_dim: int, rope: dict):
    """``(cos, sin)``, each ``(S, head_dim / 2)``, for one kind of
    layer's ``rope_parameters`` entry."""
    d, b = head_dim, float(rope["rope_theta"])
    i = jnp.arange(d // 2, dtype=F32)
    inv = b ** (-2.0 * i / d)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        s, L0 = float(rope["factor"]), float(rope[
            "original_max_position_embeddings"])

        def c(r):
            return d * math.log(L0 / (2 * math.pi * r)) / (2 * math.log(b))

        low = max(math.floor(c(rope.get("beta_fast", 32))), 0)
        high = min(math.ceil(c(rope.get("beta_slow", 1))), d - 1)
        if high == low:
            high += 0.001
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        inv = (ramp / s + (1.0 - ramp)) * inv
        scale = float(rope["attention_factor"])
    ang = positions.astype(F32)[:, None] * inv[None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate(x, cos, sin):
    """Rotate-half: ``x`` (S, H, Dh) with tables (S, Dh / 2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(n, w, dims: dict, kind: str):
    S = n.shape[0]
    eps, dh = dims["rms_norm_eps"], dims["head_dim"]
    q = jnp.einsum("sd,dhk->shk", n, w["wq"].astype(F32))
    k = jnp.einsum("sd,dhk->shk", n, w["wk"].astype(F32))
    v = jnp.einsum("sd,dhk->shk", n, w["wv"].astype(F32))
    q = rmsnorm(q, w["q_norm"], eps)
    k = rmsnorm(k, w["k_norm"], eps)
    cos, sin = rope_tables(jnp.arange(S), dh, dims["rope_parameters"][kind])
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    vis = j <= i
    if kind == "sliding_attention":
        vis &= i - j < dims["sliding_window"]
    p = jax.nn.softmax(jnp.where(vis[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v)
    return jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))


def experts(n, w, dims: dict):
    k = dims["num_experts_per_tok"]
    p = jax.nn.softmax(n @ w["router"].astype(F32), axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    if dims["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # every expert, masked by the weight it was given (0 if not picked)
    weight = jnp.zeros_like(p).at[
        jnp.arange(n.shape[0])[:, None], top_e].set(top_p)
    gate = jnp.einsum("sd,edf->esf", n, w["w_gate"].astype(F32))
    up = jnp.einsum("sd,edf->esf", n, w["w_up"].astype(F32))
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up,
                     w["w_down"].astype(F32))
    return jnp.einsum("esd,se->sd", out, weight)


def layer(x, w, dims: dict, kind: str):
    eps = dims["rms_norm_eps"]
    h = x + attention(rmsnorm(x, w["ln1"], eps), w, dims, kind)
    return h + experts(rmsnorm(h, w["ln2"], eps), w, dims)


def forward(params, tokens, dims: dict):
    """Logits ``(S, V)`` float32 of one sequence ``tokens`` ``(S,)``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        for l, kind in enumerate(dims["layer_types"]):
            w = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
            x = layer(x, w, dims, kind)
        x = rmsnorm(x, params["ln_f"], dims["rms_norm_eps"])
        return x @ params["head"].astype(F32)


# --- a latent-attention expert model (DeepSeek-V3's block) --------------------
#
# The forward pass of the block that ``A.X-K1`` publishes (its config
# keys are DeepSeek-V3's), in the same plain style: float32 at
# ``default_matmul_precision("highest")``, one sequence, NON-absorbed
# attention — every head's K and V expanded from the latent, every
# position against every earlier one — every held expert computed and
# masked, no cache, no kernel, nothing imported from the program.
# ``tests/test_latent_attention.py`` holds ``forward``, whole prefill +
# paged decode and chunked prefill + decode to its LOGITS.
#
# The layer (no bias; ``n = RMSNorm(x)``; ``h = x + Attn(n1)``, ``y = h +
# FFN(n2)``):
#
# * attention: ``cq = RMSNorm(n Wqa)``; ``q = cq Wqb`` -> H heads x (nope
#   + rope); ``[ckv, kr] = n Wkva``; ``ckv = RMSNorm(ckv)``; ``k_rope =
#   RoPE(kr)``, ONE per token, shared by all heads; ``q_rope =
#   RoPE(q_rope)``; ``[k_nope_i, v_i] = ckv Wkvb_i``; scores ``(q_nope_i .
#   k_nope_i + q_rope_i . k_rope) * s``, causal, softmax in float32; ``o =
#   concat_i(sum p v_i) Wo``.  RoPE is YaRN over the rope dims; ``s =
#   (nope + rope)^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor)
#   + 1``; cos/sin carry ``yarn_mscale(mscale) / yarn_mscale(mscale_all_dim)``.
# * experts: ``sc = sigmoid(n Wr)`` (or softmax, by ``scoring_func``);
#   the experts in ``n_group`` groups, a group's score the sum of its two
#   largest ``sc``, the ``topk_group`` best groups stay, among theirs the
#   ``num_experts_per_tok`` largest; weights ``g = sc[sel] / sum(sc[sel])
#   * routed_scaling_factor``; ``FFN(n) = sum_e g_e E_e(n) + S(n)``, every
#   ``E_e`` and the shared ``S`` a SwiGLU.  The first
#   ``first_k_dense_replace`` layers' FFN is one dense SwiGLU.
# * final RMSNorm and an untied head.
#
# A CHIP'S SHARE: ``params`` may hold only the experts ``expert_offset <=
# e < expert_offset + E_held`` (the stack's own size) of the router's
# outputs.  Routing and the weights' normalisation are over ALL the
# router's experts; what the absent experts would add is left out — the
# partial result is what goes on to the next layer, as in the program.
#
# Assumptions (the published config leaves these open), each the
# program's too: ``topk_method: "none"`` is a value DeepSeek's code does
# not define — read as NO score-correction bias (that is ``noaux_tc``'s),
# selection on the raw scores, group-limited as ``n_group`` /
# ``topk_group`` state, a group scored as DeepSeek-V3 scores one (sum of
# its top 2); experts outside the kept groups are masked with -inf where
# DeepSeek's code writes 0.0 (the same choice for positive scores);
# ``seq_aux`` is a training loss, no part of the forward; the rope pairs
# dims rotate-half (with seeded weights the published interleaved
# pairing is a permutation of ``Wqb`` / ``Wkva``'s columns).
#
# ``params``: ``embed``, ``head``, ``ln_f``, ``dense_layers`` (the leading
# dense stack) and ``layers`` (the expert stack), each stacked on a
# leading axis: ``ln1``, ``ln2``, ``wq_a (D, R)``, ``q_a_norm (R)``, ``wq_b
# (R, H, nope + rope)``, ``wkv_a (D, C + rope)``, ``kv_a_norm (C)``,
# ``wkv_b (C, H, nope + v)``, ``wo (H, v, D)``; a dense layer's ``w_gate``
# / ``w_up (D, F)``, ``w_down (F, D)``; an expert layer's ``router (D,
# E)``, ``w_gate`` / ``w_up (E_held, D, Fe)``, ``w_down (E_held, Fe, D)``
# and the shared ``ws_gate`` / ``ws_up (D, Fs)``, ``ws_down (Fs, D)``.


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def latent_rope(dims: dict) -> dict:
    """The ``rope_scaling`` group as :func:`rope_tables` takes it."""
    rs = dims["rope_scaling"]
    assert rs["type"] == "yarn", rs
    f = float(rs["factor"])
    return {"rope_type": "yarn", "rope_theta": dims["rope_theta"],
            "factor": f, "original_max_position_embeddings":
                rs["original_max_position_embeddings"],
            "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
            "attention_factor": _yarn_mscale(f, rs.get("mscale", 1))
            / _yarn_mscale(f, rs.get("mscale_all_dim", 0))}


def latent_softmax_scale(dims: dict) -> float:
    s = (dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]) ** -0.5
    rs = dims["rope_scaling"]
    if rs.get("mscale_all_dim", 0):
        s *= _yarn_mscale(float(rs["factor"]), rs["mscale_all_dim"]) ** 2
    return s


def latent_attention(n, w, dims: dict):
    S = n.shape[0]
    eps = dims["rms_norm_eps"]
    nope, c = dims["qk_nope_head_dim"], dims["kv_lora_rank"]
    cq = rmsnorm(n @ w["wq_a"].astype(F32), w["q_a_norm"], eps)
    q = jnp.einsum("sr,rhk->shk", cq, w["wq_b"].astype(F32))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kv = n @ w["wkv_a"].astype(F32)
    ckv = rmsnorm(kv[:, :c], w["kv_a_norm"], eps)
    cos, sin = rope_tables(jnp.arange(S), dims["qk_rope_head_dim"],
                           latent_rope(dims))
    q_rope = rotate(q_rope, cos, sin)
    k_rope = rotate(kv[:, None, c:], cos, sin)[:, 0]       # (S, rope)
    kvb = jnp.einsum("sc,chk->shk", ckv, w["wkv_b"].astype(F32))
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
         ) * latent_softmax_scale(dims)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v)
    return jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))


def latent_route(n, router, dims: dict):
    """``(S, E)`` combination weights over ALL the router's experts."""
    logits = n @ router.astype(F32)
    sc = (jax.nn.sigmoid(logits) if dims["scoring_func"] == "sigmoid"
          else jax.nn.softmax(logits, axis=-1))
    S, E = sc.shape
    choice = sc
    g = dims.get("n_group", 1)
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


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ gate.astype(F32)) * (n @ up.astype(F32))
            ) @ down.astype(F32)


def latent_experts(n, w, dims: dict):
    """The held experts' part of the routed sum, and the shared expert
    (every expert held computed on every position, masked by the weight
    the router gave it: 0 if not picked)."""
    weight = latent_route(n, w["router"], dims)
    off = dims.get("expert_offset", 0)
    weight = weight[:, off:off + w["w_gate"].shape[0]]
    gate = jnp.einsum("sd,edf->esf", n, w["w_gate"].astype(F32))
    up = jnp.einsum("sd,edf->esf", n, w["w_up"].astype(F32))
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up,
                     w["w_down"].astype(F32))
    y = jnp.einsum("esd,se->sd", out, weight)
    if dims.get("n_shared_experts", 0):
        y = y + _swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"])
    return y


def latent_layer(x, w, dims: dict):
    eps = dims["rms_norm_eps"]
    h = x + latent_attention(rmsnorm(x, w["ln1"], eps), w, dims)
    n = rmsnorm(h, w["ln2"], eps)
    if "router" in w:
        return h + latent_experts(n, w, dims)
    return h + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"])


def latent_forward(params, tokens, dims: dict):
    """Logits ``(S, V)`` float32 of one sequence ``tokens`` ``(S,)``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        kd = dims["first_k_dense_replace"]
        for l in range(dims["num_hidden_layers"]):
            stack, i = (("dense_layers", l) if l < kd
                        else ("layers", l - kd))
            w = jax.tree_util.tree_map(lambda a: a[i], params[stack])
            x = latent_layer(x, w, dims)
        x = rmsnorm(x, params["ln_f"], dims["rms_norm_eps"])
        return x @ params["head"].astype(F32)


# --- a sparse latent-attention expert model (DeepSeek-V3.2-Exp's block) -------
#
# DeepSeek-V3's block (above) with two additions, as the family's
# published inference code states them (``inference/model.py``: ``MLA``,
# ``Indexer``, ``Gate``), in the same plain style; ``h`` is the layer's
# input after ``attn_norm`` and ``cq`` the normed query latent that
# latent attention computes anyway:
#
# * a LIGHTNING INDEXER a layer: index queries ``q_I[t] = cq[t] W_Iq``
#   (``index_n_heads`` x ``index_head_dim``); ONE index key a token
#   ``k_I[s] = LayerNorm(h[s] W_Ik)`` (scale and bias, eps 1e-6); RoPE
#   (the layer's own YaRN tables) on the first ``qk_rope_head_dim`` dims
#   of both; head weights ``w[t] = (h[t] W_Iw) x index_n_heads^-0.5 x
#   index_head_dim^-0.5``; index score ``I[t, s] = sum_j w[t, j]
#   relu(q_I[t, j] . k_I[s])`` for ``s <= t``
#   (:func:`sparse_index_scores`).  The query attends the ``min(index_topk,
#   t + 1)`` positions of largest score, ties to the lower position
#   (``lax.top_k``'s rule: :func:`sparse_select`), and the softmax of
#   latent attention runs over that set alone (:func:`sparse_attention`).
# * the router CHOOSES on ``scores + e_score_correction_bias`` (``router_bias``
#   ``(E,)``: ``topk_method: "noaux_tc"``) — the groups' scores (sum of a
#   group's two largest) and the experts among the kept groups — and
#   WEIGHTS by the chosen experts' raw scores, renormalised, times
#   ``routed_scaling_factor`` (:func:`sparse_route`).
#
# Departures from the published code, each the program's too: (1) the
# multi-token-prediction module (``num_nextn_predict_layers``) is no part
# of the layers' forward pass (the family's own loader drops it) and is
# left out; (2) the published code rotates index queries and keys by a
# Hadamard matrix and quantises them to fp8 — the rotation is orthogonal
# and leaves ``q_I . k_I`` as it is, and here nothing is quantised; (3)
# the rope pairs dims rotate-half for the attention and the indexer alike
# (the published code pairs the two differently; with seeded weights
# either is a permutation of columns).
#
# ``params`` as for ``latent_*``, each layer with ``wi_q (R, Hi, Di)``,
# ``wi_k (D, Di)``, ``i_k_norm`` / ``i_k_bias (Di)``, ``wi_w (D, Hi)`` and
# an expert layer with ``router_bias (E)``; ``dims`` with
# ``index_n_heads``, ``index_head_dim``, ``index_topk``.


def _layernorm(x, w, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def sparse_index_scores(n, cq, w, dims: dict, rope: bool = True):
    """``I`` ``(S, S)``: the index score of every pair, unmasked.
    ``rope=False`` leaves the index vectors un-roped (a control the
    tests hold the tolerance against)."""
    S = n.shape[0]
    r = dims["qk_rope_head_dim"]
    hi, di = dims["index_n_heads"], dims["index_head_dim"]
    qi = jnp.einsum("sr,rhk->shk", cq, w["wi_q"].astype(F32))
    ki = _layernorm(n @ w["wi_k"].astype(F32), w["i_k_norm"],
                    w["i_k_bias"], 1e-6)
    if rope:
        cos, sin = rope_tables(jnp.arange(S), r, latent_rope(dims))
        qi = jnp.concatenate([rotate(qi[..., :r], cos, sin), qi[..., r:]],
                             -1)
        ki = jnp.concatenate(
            [rotate(ki[:, None, :r], cos, sin)[:, 0], ki[:, r:]], -1)
    wt = (n @ w["wi_w"].astype(F32)) * (hi ** -0.5 * di ** -0.5)
    return jnp.einsum("qh,qhk->qk", wt, jax.nn.relu(
        jnp.einsum("qhd,kd->qhk", qi, ki)))


def sparse_select(scores, topk: int):
    """``(S, S)`` bool: may query ``t`` attend position ``s``?  Its
    ``min(topk, t + 1)`` best-scored positions ``s <= t``, ties to the
    lower position (``lax.top_k``)."""
    S = scores.shape[0]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    _, top = jax.lax.top_k(jnp.where(j <= i, scores, -jnp.inf),
                           min(topk, S))
    picked = jnp.zeros((S, S), bool).at[i, top].set(True)
    return picked & (j <= i)


def sparse_attention(n, w, dims: dict, select: bool = True,
                     rope_index: bool = True):
    """Latent attention (``latent_attention``'s mathematics, non-
    absorbed) with the softmax over each query's selected set.
    ``select=False`` is dense attention (the control)."""
    S = n.shape[0]
    eps = dims["rms_norm_eps"]
    nope, c = dims["qk_nope_head_dim"], dims["kv_lora_rank"]
    cq = rmsnorm(n @ w["wq_a"].astype(F32), w["q_a_norm"], eps)
    q = jnp.einsum("sr,rhk->shk", cq, w["wq_b"].astype(F32))
    kv = n @ w["wkv_a"].astype(F32)
    ckv = rmsnorm(kv[:, :c], w["kv_a_norm"], eps)
    cos, sin = rope_tables(jnp.arange(S), dims["qk_rope_head_dim"],
                           latent_rope(dims))
    q_rope = rotate(q[..., nope:], cos, sin)
    k_rope = rotate(kv[:, None, c:], cos, sin)[:, 0]
    kvb = jnp.einsum("sc,chk->shk", ckv, w["wkv_b"].astype(F32))
    s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kvb[..., :nope])
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
         ) * latent_softmax_scale(dims)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    vis = j <= i
    if select:
        vis = sparse_select(sparse_index_scores(n, cq, w, dims, rope_index),
                            dims["index_topk"])
    p = jax.nn.softmax(jnp.where(vis[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, kvb[..., nope:])
    return jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))


def sparse_route(n, router, bias, dims: dict):
    """``(S, E)`` combination weights over ALL the router's experts:
    chosen on ``scores + bias``, weighted by the raw scores."""
    sc = jax.nn.sigmoid(n @ router.astype(F32))
    S, E = sc.shape
    choice = sc + bias.astype(F32)
    g = dims.get("n_group", 1)
    if g > 1:
        grouped = choice.reshape(S, g, E // g)
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


def sparse_experts(n, w, dims: dict):
    """The held experts' part of the routed sum under the biased
    choice, and the shared expert (``latent_experts`` with
    :func:`sparse_route`)."""
    weight = sparse_route(n, w["router"], w["router_bias"], dims)
    off = dims.get("expert_offset", 0)
    weight = weight[:, off:off + w["w_gate"].shape[0]]
    gate = jnp.einsum("sd,edf->esf", n, w["w_gate"].astype(F32))
    up = jnp.einsum("sd,edf->esf", n, w["w_up"].astype(F32))
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up,
                     w["w_down"].astype(F32))
    y = jnp.einsum("esd,se->sd", out, weight)
    if dims.get("n_shared_experts", 0):
        y = y + _swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"])
    return y


def sparse_layer(x, w, dims: dict, **controls):
    eps = dims["rms_norm_eps"]
    h = x + sparse_attention(rmsnorm(x, w["ln1"], eps), w, dims, **controls)
    n = rmsnorm(h, w["ln2"], eps)
    if "router" in w:
        return h + sparse_experts(n, w, dims)
    return h + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"])


def sparse_forward(params, tokens, dims: dict, **controls):
    """Logits ``(S, V)`` float32 of one sequence ``tokens`` ``(S,)``.
    ``controls`` (``select=False``, ``rope_index=False``) loosen the
    layer for the tests that hold the tolerance tight."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        kd = dims["first_k_dense_replace"]
        for l in range(dims["num_hidden_layers"]):
            stack, i = (("dense_layers", l) if l < kd
                        else ("layers", l - kd))
            w = jax.tree_util.tree_map(lambda a: a[i], params[stack])
            x = sparse_layer(x, w, dims, **controls)
        x = rmsnorm(x, params["ln_f"], dims["rms_norm_eps"])
        return x @ params["head"].astype(F32)


# --- gated short convolutions between attention layers (LFM2's block) ---------
#
# The forward pass of the block ``LFM2-24B-A2B`` publishes (``model_type:
# lfm2_moe``), in the same plain style: float32 at
# ``default_matmul_precision("highest")``, one sequence, every position
# against every earlier one, every expert computed and masked, no cache,
# no STATE — the convolution reads the whole sequence — no kernel,
# nothing imported from the program.  ``tests/test_conv_layers.py`` holds
# whole prefill, chunked prefill and paged decode to its LOGITS.
#
# A layer (no bias; ``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``),
# its ``Op`` by ``layer_types``:
#
# * ``conv`` (``conv_L_cache`` = K taps, ``conv_bias`` false): ``[B, C, X] =
#   n W_in`` (split in three in that order); ``u = B * X``; ``v[t] = sum_j
#   k[:, j] u[t - (K - 1) + j]`` with ``u[s] = 0`` for ``s < 0`` (depthwise
#   over the hidden size, causal); ``Op = (C * v) W_out``.  No activation.
# * ``full_attention``: q, k, v projections into heads of ``hidden_size /
#   num_attention_heads``; q and k each through an RMSNorm over the head
#   with a learned scale; rotate-half rope (``rope_parameters``); causal
#   ``softmax(q k^T / sqrt(Dh)) v``; ``W_o``.
# * FFN: the first ``num_dense_layers`` layers one SwiGLU of width
#   ``intermediate_size``; the rest ``s = sigmoid(n W_r)`` in float32, the
#   ``num_experts_per_tok`` experts the largest of ``s + b``
#   (``use_expert_bias``), their weights the raw ``s`` of the chosen,
#   divided by ``sum + 1e-6`` (``norm_topk_prob``), times
#   ``routed_scaling_factor``; each expert a SwiGLU of
#   ``moe_intermediate_size``; nothing dropped, no shared expert.
# * final RMSNorm; the logits against the EMBEDDING (tied).
#
# Assumptions (the published config has no key for them), the program's
# too: the tied head, the q/k norms, rotate-half pairing and the order
# ``B, C, X`` are the family's (LFM2's) convention; the 1e-6.
#
# ``zero_taps`` is a CONTROL, not the model: the convolution keeps its
# current tap alone (``v[t] = k[:, K-1] u[t]``) — what a program serves
# that loses a request's state at every chunk and tick boundary.
#
# ``params``: ``embed (V, D)``, ``ln_f``, ``dense_layers`` and ``layers``,
# each stacked on a leading axis with the mixer's leaves BY KIND: ``ln1``,
# ``ln2`` and the FFN's leaves over all the stack's layers; ``wq (D, H,
# Dh)``, ``wk``/``wv (D, H_kv, Dh)``, ``wo (H, Dh, D)``, ``q_norm``/
# ``k_norm (Dh)`` over its attention layers; ``conv_in (D, 3D)``,
# ``conv_k (D, K)``, ``conv_out (D, D)`` over its conv layers.

CONV_NORM_TOPK_EPS = 1e-6


def conv_mixer(n, w, zero_taps: bool = False):
    """The gated short convolution of one sequence ``n`` ``(S, D)``."""
    S = n.shape[0]
    b, c, x = jnp.split(n @ w["conv_in"].astype(F32), 3, axis=-1)
    u = b * x
    k = w["conv_k"].astype(F32)                       # (D, K)
    K = k.shape[1]
    past = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    taps = range(K - 1, K) if zero_taps else range(K)
    v = sum(past[j:j + S] * k[:, j] for j in taps)
    return (c * v) @ w["conv_out"].astype(F32)


def conv_route(n, router, bias, dims: dict):
    """``(S, E)`` combination weights: the experts chosen on ``sigmoid +
    bias``, weighted by the raw sigmoid over ``sum + 1e-6``."""
    sc = jax.nn.sigmoid(n @ router.astype(F32))
    _, top_e = jax.lax.top_k(sc + bias.astype(F32),
                             dims["num_experts_per_tok"])
    top_g = jnp.take_along_axis(sc, top_e, axis=-1)
    if dims["norm_topk_prob"]:
        top_g = top_g / (jnp.sum(top_g, axis=-1, keepdims=True)
                         + CONV_NORM_TOPK_EPS)
    top_g = top_g * dims["routed_scaling_factor"]
    return jnp.zeros_like(sc).at[
        jnp.arange(n.shape[0])[:, None], top_e].set(top_g)


def conv_experts(n, w, dims: dict):
    weight = conv_route(n, w["router"], w["router_bias"], dims)
    gate = jnp.einsum("sd,edf->esf", n, w["w_gate"].astype(F32))
    up = jnp.einsum("sd,edf->esf", n, w["w_up"].astype(F32))
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up,
                     w["w_down"].astype(F32))
    return jnp.einsum("esd,se->sd", out, weight)


def conv_layer(x, w, dims: dict, kind: str, zero_taps: bool = False):
    eps = dims["norm_eps"]
    n = rmsnorm(x, w["ln1"], eps)
    if kind == "conv":
        h = x + conv_mixer(n, w, zero_taps)
    else:
        h = x + attention(n, w, {
            "rms_norm_eps": eps,
            "head_dim": dims["hidden_size"] // dims["num_attention_heads"],
            "rope_parameters": {kind: dims["rope_parameters"]}}, kind)
    n = rmsnorm(h, w["ln2"], eps)
    if "router" in w:
        return h + conv_experts(n, w, dims)
    return h + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"])


def conv_forward(params, tokens, dims: dict, zero_taps: bool = False):
    """Logits ``(S, V)`` float32 of one sequence ``tokens`` ``(S,)``."""
    mixer = {"conv": ("conv_in", "conv_k", "conv_out")}
    attn = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        nd = dims["num_dense_layers"]
        seen = {}                       # (stack, kind) -> layers so far
        for l, kind in enumerate(dims["layer_types"]):
            stack, i = ("dense_layers", l) if l < nd else ("layers", l - nd)
            j = seen.get((stack, kind), 0)
            seen[stack, kind] = j + 1
            mine = mixer.get(kind, attn)
            w = {name: a[j if name in mine else i]
                 for name, a in params[stack].items()
                 if name in mine or name not in attn + mixer["conv"]}
            x = conv_layer(x, w, dims, kind, zero_taps)
        x = rmsnorm(x, params["ln_f"], dims["norm_eps"])
        return x @ params["embed"].astype(F32).T


# --- attention and a state-space mixer side by side (Falcon-H1's block) -------
#
# The forward pass of the block ``Falcon-H1-34B-Instruct`` publishes
# (``model_type: falcon_h1``), in the same plain style: float32 at
# ``default_matmul_precision("highest")``, one sequence, every position
# against every earlier one, no cache, no kernel, nothing imported from the
# program — and the recurrence as a SEQUENTIAL scan over the tokens, never
# the chunked dual form the program runs.  ``tests/test_hybrid_layers.py``
# holds whole prefill, chunked prefill and paged decode to its LOGITS.
#
# With ``m_*`` the published multipliers (their PLACES are the family's
# modeling code's; the config gives values): ``e = m_emb Embed[id]``; a
# layer ``n = RMSNorm(x)``, ``x' = x + m_ao Attn(m_ai n) + m_so SSM(m_si
# n)``, ``y = x' + MLP(RMSNorm(x'))``; logits ``m_head RMSNorm(y) W_head``
# (untied); no bias but the convolution's.
#
# * Attn: q, k, v into heads of ``head_dim``; ``k <- m_k k`` before the
#   rope; rotate-half rope (``rope_theta``, no scaling); causal ``softmax(q
#   k^T / sqrt(Dh)) v``; ``W_o``; no q/k norm.
# * SSM (Mamba-2): ``[z | xBC | dt] = (u W_in) * mup`` with ``mup`` the
#   vector that multiplies the ``z``, ``x``, ``B``, ``C``, ``dt`` columns by
#   ``ssm_multipliers[0..4]``; ``xBC <- silu(conv(xBC) + b)`` (depthwise over
#   the ``mamba_d_ssm + 2 groups d_state`` columns, ``mamba_d_conv`` taps,
#   causal, zeros before the sequence); ``x`` into ``mamba_n_heads`` heads of
#   ``mamba_d_head``, ``B``/``C`` into ``mamba_n_groups`` groups of
#   ``mamba_d_state`` (head ``h`` reads group ``h // (heads / groups)``);
#   ``dt = softplus(dt + dt_bias)``; ``a_t = exp(-exp(A_log) dt_t)``; ``h_t =
#   a_t h_{t-1} + dt_t x_t B_t^T`` (``h_{-1} = 0``); ``y_t = h_t C_t + D x_t``;
#   ``g = y * silu(z)`` (``mamba_norm_before_gate`` false), an RMSNorm over
#   EACH GROUP's ``mamba_d_ssm / groups`` columns with a learned scale;
#   ``g W_out``.
# * MLP: ``down(silu(m_g gate(v)) * up(v)) * m_d`` (``mlp_multipliers``).
#
# ``reset`` is a CONTROL, not the model: a boolean ``(S,)``; where it is
# true the state ``h`` and the convolution's past taps are ZERO before that
# token — what a program serves that loses a request's state there.
#
# ``params``: ``embed (V, D)``, ``head (D, V)``, ``ln_f`` and ``layers``
# stacked on a leading axis: ``ln1``, ``ln2``, ``wq (D, H, Dh)``, ``wk``/
# ``wv (D, H_kv, Dh)``, ``wo (H, Dh, D)``, ``ssm_in (D, 2 d_ssm + 2 G N +
# heads)``, ``ssm_conv_k (C, K)``, ``ssm_conv_b (C)``, ``ssm_dt_bias``/
# ``ssm_A_log``/``ssm_D (heads)``, ``ssm_norm (d_ssm)``, ``ssm_out (d_ssm,
# D)``, ``w_gate``/``w_up (D, F)``, ``w_down (F, D)``.


def hybrid_attention(n, w, dims: dict):
    S, dh = n.shape[0], dims["head_dim"]
    q = jnp.einsum("sd,dhk->shk", n, w["wq"].astype(F32))
    k = jnp.einsum("sd,dhk->shk", n, w["wk"].astype(F32))
    v = jnp.einsum("sd,dhk->shk", n, w["wv"].astype(F32))
    k = k * dims["key_multiplier"]
    cos, sin = rope_tables(jnp.arange(S), dh,
                           {"rope_theta": dims["rope_theta"]})
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    vis = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(vis[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v)
    return jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))


def hybrid_ssm(n, w, dims: dict, reset=None):
    """The state-space mixer of one sequence ``n`` ``(S, D)``, token by
    token."""
    S = n.shape[0]
    I, H, P = dims["mamba_d_ssm"], dims["mamba_n_heads"], dims["mamba_d_head"]
    G, N, K = (dims["mamba_n_groups"], dims["mamba_d_state"],
               dims["mamba_d_conv"])
    gn = G * N
    m = dims["ssm_multipliers"]
    mup = jnp.concatenate([jnp.full((I,), m[0], F32), jnp.full((I,), m[1]),
                           jnp.full((gn,), m[2]), jnp.full((gn,), m[3]),
                           jnp.full((H,), m[4])])
    zxd = (n @ w["ssm_in"].astype(F32)) * mup
    z, xbc, dt = zxd[:, :I], zxd[:, I:I + I + 2 * gn], zxd[:, 2 * I + 2 * gn:]
    dt = jax.nn.softplus(dt + w["ssm_dt_bias"].astype(F32))      # (S, H)
    a_neg = -jnp.exp(w["ssm_A_log"].astype(F32))
    kern, bias = w["ssm_conv_k"].astype(F32), w["ssm_conv_b"].astype(F32)
    if reset is None:
        reset = jnp.zeros((S,), bool)

    def token(carry, inp):
        h, taps = carry                 # (H, P, N), (K - 1, C)
        u, dt_t, lost = inp
        h = jnp.where(lost, 0.0, h)
        taps = jnp.where(lost, 0.0, taps)
        window = jnp.concatenate([taps, u[None]])               # (K, C)
        act = jax.nn.silu(jnp.sum(window * kern.T, axis=0) + bias)
        x = act[:I].reshape(H, P)
        b = jnp.repeat(act[I:I + gn].reshape(G, N), H // G, axis=0)
        c = jnp.repeat(act[I + gn:].reshape(G, N), H // G, axis=0)
        a = jnp.exp(a_neg * dt_t)
        h = a[:, None, None] * h + (dt_t[:, None] * x)[:, :, None] \
            * b[:, None, :]
        y = jnp.einsum("hpn,hn->hp", h, c) + w["ssm_D"].astype(F32)[
            :, None] * x
        return (h, window[1:]), y.reshape(I)

    init = (jnp.zeros((H, P, N), F32), jnp.zeros((K - 1, xbc.shape[1]), F32))
    _, y = jax.lax.scan(token, init, (xbc, dt, reset))
    g = (y * jax.nn.silu(z)).reshape(S, G, I // G)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + dims["rms_norm_eps"])
    return (g.reshape(S, I) * w["ssm_norm"].astype(F32)) @ w[
        "ssm_out"].astype(F32)


def hybrid_layer(x, w, dims: dict, reset=None):
    eps = dims["rms_norm_eps"]
    n = rmsnorm(x, w["ln1"], eps)
    h = (x + dims["attention_out_multiplier"] * hybrid_attention(
        n * dims["attention_in_multiplier"], w, dims)
        + dims["ssm_out_multiplier"] * hybrid_ssm(
            n * dims["ssm_in_multiplier"], w, dims, reset))
    v = rmsnorm(h, w["ln2"], eps)
    m_g, m_d = dims["mlp_multipliers"]
    gate = jax.nn.silu((v @ w["w_gate"].astype(F32)) * m_g)
    return h + ((gate * (v @ w["w_up"].astype(F32)))
                @ w["w_down"].astype(F32)) * m_d


def hybrid_forward(params, tokens, dims: dict, reset=None):
    """Logits ``(S, V)`` float32 of one sequence ``tokens`` ``(S,)``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens] * dims["embedding_multiplier"]
        for l in range(dims["num_hidden_layers"]):
            w = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
            x = hybrid_layer(x, w, dims, reset)
        x = rmsnorm(x, params["ln_f"], dims["rms_norm_eps"])
        return (x @ params["head"].astype(F32)) * dims["lm_head_multiplier"]


# --- linear attention between block-sparse attention (MiniCPM-SALA's blocks) ---
#
# The forward pass of the two layers ``MiniCPM-SALA`` publishes
# (``model_type: minicpm_sala``, ``mixer_types`` a layer), in the same plain
# style: float32 at ``default_matmul_precision("highest")``, one sequence,
# no cache, no kernel, nothing imported from the program — the recurrence a
# SEQUENTIAL scan over the tokens (never the chunked dual form), the
# selection by BRUTE FORCE from its definition (every window's mean, every
# block's score, ``lax.top_k``).  ``tests/test_linear_sparse_layers.py``
# holds whole prefill, chunked prefill and paged decode to its LOGITS.
#
# With ``r = scale_depth / sqrt(published layers)``: ``e = scale_emb
# Embed[id]``; a layer ``n = RMSNorm(x)``, ``x' = x + r Mix(n)``, ``y = x' +
# r SwiGLU(RMSNorm(x'))``; ``logits = Head(RMSNorm(y) / (hidden_size /
# dim_model_base))`` (untied); no bias.
#
# * ``lightning-attn``: ``q = RMSNorm(n W_q)``, ``k = RMSNorm(n W_k)`` a head
#   of ``lightning_head_dim`` with one learned scale (``qk_norm``), ``v = n
#   W_v``; rotate-half rope on q and k (``lightning_use_rope``); a head keeps
#   ``S (Dh, Dh)``: ``S_t = lambda_h S_{t-1} + k_t^T v_t`` (``S_{-1} = 0``),
#   ``o_t = (q_t / sqrt(Dh)) S_t``; ``(RMSNorm(o) * sigmoid(n W_g)) W_o``
#   (``use_output_norm`` a head with one learned scale, ``use_output_gate``).
#   ``lambda_h = exp(lin_decay[h])``: the leaf holds ``log lambda``.
# * ``minicpm4`` (InfLLM-V2): q, k normed as above, NO rope
#   (``attn_use_rope`` false); K/V of ``num_key_value_heads`` heads.  With
#   ``sparse_config`` ``(kernel_size, kernel_stride, block_size, topk,
#   window_size, init_blocks, dense_len)``: a compressed key ``c_j = mean(k[
#   stride j : stride j + kernel])`` a KV head for every window that lies
#   WHOLE in the query's context; a query at ``t`` with ``t + 1 >
#   dense_len``: ``p_h = softmax_j(q_h . c_j / sqrt(Dh))``, summed over the
#   query heads of the KV head; a block's score the largest over the windows
#   that overlap its ``block_size`` tokens; it attends the blocks ``<
#   init_blocks``, the ``window_size / block_size`` blocks up to its own, and
#   the ``topk`` best-scored of the rest (ties to the lower block), causally,
#   ``softmax(q k^T / sqrt(Dh)) v``; a shorter context attends everything.
#   ``(o * sigmoid(n W_g)) W_o`` (``attn_use_output_gate``).
#
# Departures from the published description, each an ``assumed`` of the
# configuration's file: the window as BLOCKS (the query's own and the 31
# before it: 1985-2048 tokens; InfLLM-V2's kernels count it so), the switch
# on the query's own context, the decay as a leaf.
#
# ``reset`` and ``select`` are CONTROLS, not the model: ``reset`` a boolean
# ``(S,)``, the state ``S`` ZERO before that token (a program that lost a
# request's state there); ``select=False`` attends everything everywhere.
#
# ``params``: ``embed (V, D)``, ``head (D, V)``, ``ln_f`` and ``layers``:
# ``ln1``, ``ln2``, ``w_gate``/``w_up (D, F)``, ``w_down (F, D)`` stacked over
# all the layers; ``lin_q``/``lin_k``/``lin_v``/``lin_g (D, H Dh)``, ``lin_o
# (H Dh, D)``, ``lin_norm``/``lin_q_norm``/``lin_k_norm (Dh)``, ``lin_decay
# (H)`` over the lightning layers; ``wq (D, H, Dh)``, ``wk``/``wv (D, H_kv,
# Dh)``, ``wo (H, Dh, D)``, ``q_norm``/``k_norm (Dh)``, ``wg (D, H Dh)`` over
# the ``minicpm4`` layers.

SALA_KINDS = {"lightning-attn": "linear", "minicpm4": "block_sparse"}
_SALA_LEAVES = {
    "linear": ("lin_q", "lin_k", "lin_v", "lin_g", "lin_o", "lin_norm",
               "lin_decay", "lin_q_norm", "lin_k_norm"),
    "block_sparse": ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "wg")}


def sala_residual_scale(dims: dict) -> float:
    """``scale_depth / sqrt(layers)`` at the PUBLISHED depth (a cut in
    depth keeps it)."""
    layers = dims.get("published", {}).get("num_hidden_layers",
                                           dims["num_hidden_layers"])
    return dims["scale_depth"] / math.sqrt(layers)


def sala_linear(n, w, dims: dict, reset=None):
    """The lightning layer's mixer of one sequence ``n`` ``(S, D)``,
    token by token."""
    S = n.shape[0]
    H, dh = dims["lightning_nh"], dims["lightning_head_dim"]
    eps = dims["rms_norm_eps"]
    q = (n @ w["lin_q"].astype(F32)).reshape(S, H, dh)
    k = (n @ w["lin_k"].astype(F32)).reshape(S, H, dh)
    v = (n @ w["lin_v"].astype(F32)).reshape(S, H, dh)
    if dims["qk_norm"]:
        q, k = rmsnorm(q, w["lin_q_norm"], eps), rmsnorm(k, w["lin_k_norm"],
                                                         eps)
    if dims["lightning_use_rope"]:
        cos, sin = rope_tables(jnp.arange(S), dh,
                               {"rope_theta": dims["rope_theta"]})
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    lam = jnp.exp(w["lin_decay"].astype(F32))
    if reset is None:
        reset = jnp.zeros((S,), bool)

    def token(state, inp):
        q_t, k_t, v_t, lost = inp
        state = jnp.where(lost, 0.0, state)
        state = lam[:, None, None] * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t / math.sqrt(dh), state)

    _, o = jax.lax.scan(token, jnp.zeros((H, dh, dh), F32), (q, k, v, reset))
    o = rmsnorm(o, w["lin_norm"], eps).reshape(S, H * dh)
    return (o * jax.nn.sigmoid(n @ w["lin_g"].astype(F32))) @ w[
        "lin_o"].astype(F32)


def sala_selected(q, k, sc: dict):
    """``(S, H_kv, S)`` bool: may the query at ``t`` (rows) attend key
    ``s`` under the selection — by brute force.  ``q`` ``(S, H, Dh)``,
    ``k`` ``(S, H_kv, Dh)`` as the scores take them."""
    S, H, dh = q.shape
    hkv = k.shape[1]
    ker, stride, blk = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    n_w = max((S - ker) // stride + 1, 0)
    n_b = -(-S // blk)
    t = jnp.arange(S)
    causal = t[None, :] <= t[:, None]
    everything = jnp.broadcast_to(causal[:, None, :], (S, hkv, S))
    if n_w == 0:
        return everything
    c = jnp.stack([jnp.mean(k[stride * j:stride * j + ker], axis=0)
                   for j in range(n_w)])                     # (nW, Hkv, Dh)
    j = jnp.arange(n_w)
    whole = stride * j[None, :] + ker <= t[:, None] + 1      # (S, nW)
    s = jnp.einsum("thd,jhd->thj", q, jnp.repeat(c, H // hkv, axis=1)
                   ) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(whole[:, None, :], s, -jnp.inf), axis=-1)
    p = jnp.where(whole[:, None, :], p, 0.0)
    p = p.reshape(S, hkv, H // hkv, n_w).sum(axis=2)         # (S, Hkv, nW)
    b = jnp.arange(n_b)
    overlap = ((stride * j[None, :] <= blk * b[:, None] + blk - 1)
               & (stride * j[None, :] + ker - 1 >= blk * b[:, None]))
    score = jnp.max(jnp.where(
        (overlap[None, :, :] & whole[:, None, :])[:, None], p[:, :, None, :],
        -jnp.inf), axis=-1)                                  # (S, Hkv, nB)
    own = t // blk
    w_blocks, init = sc["window_size"] // blk, sc["init_blocks"]
    forced = ((b[None, :] < init) | (b[None, :] > own[:, None] - w_blocks)
              ) & (b[None, :] <= own[:, None])               # (S, nB)
    rest = (b[None, :] >= init) & (b[None, :] <= own[:, None] - w_blocks)
    topk = min(sc["topk"], n_b)
    _, best = jax.lax.top_k(jnp.where(rest[:, None, :], score, -jnp.inf), topk)
    rank_ok = jnp.arange(topk)[None, :] < jnp.minimum(
        sc["topk"], rest.sum(axis=-1))[:, None]              # (S, topk)
    picked = jnp.any((best[..., None] == b) & rank_ok[:, None, :, None],
                     axis=2)                                 # (S, Hkv, nB)
    blocks = picked | forced[:, None, :]
    tokens = jnp.repeat(blocks, blk, axis=-1)[..., :S] & causal[:, None, :]
    sparse = (t + 1 > sc["dense_len"])[:, None, None]
    return jnp.where(sparse, tokens, everything)


def sala_sparse_attention(n, w, dims: dict, select: bool = True):
    """The ``minicpm4`` layer's mixer of one sequence ``n`` ``(S, D)``."""
    S, dh, eps = n.shape[0], dims["head_dim"], dims["rms_norm_eps"]
    q = jnp.einsum("sd,dhk->shk", n, w["wq"].astype(F32))
    k = jnp.einsum("sd,dhk->shk", n, w["wk"].astype(F32))
    v = jnp.einsum("sd,dhk->shk", n, w["wv"].astype(F32))
    if dims["qk_norm"]:
        q, k = rmsnorm(q, w["q_norm"], eps), rmsnorm(k, w["k_norm"], eps)
    if dims["attn_use_rope"]:
        cos, sin = rope_tables(jnp.arange(S), dh,
                               {"rope_theta": dims["rope_theta"]})
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    H, hkv = q.shape[1], k.shape[1]
    if select:
        vis = sala_selected(q, k, dims["sparse_config"])     # (S, Hkv, S)
    else:
        t = jnp.arange(S)
        vis = jnp.broadcast_to((t[None, :] <= t[:, None])[:, None, :],
                               (S, hkv, S))
    s = jnp.einsum("qhd,khd->qhk", q, jnp.repeat(k, H // hkv, axis=1)
                   ) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(jnp.repeat(vis, H // hkv, axis=1), s,
                                 -jnp.inf), axis=-1)
    o = jnp.einsum("qhk,khd->qhd", p, jnp.repeat(v, H // hkv, axis=1))
    if dims["attn_use_output_gate"]:
        o = o * jax.nn.sigmoid(n @ w["wg"].astype(F32)).reshape(o.shape)
    return jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))


def sala_layer(x, w, dims: dict, kind: str, reset=None, select: bool = True):
    eps, r = dims["rms_norm_eps"], sala_residual_scale(dims)
    n = rmsnorm(x, w["ln1"], eps)
    mix = (sala_linear(n, w, dims, reset) if kind == "linear"
           else sala_sparse_attention(n, w, dims, select))
    h = x + r * mix
    v = rmsnorm(h, w["ln2"], eps)
    gate = jax.nn.silu(v @ w["w_gate"].astype(F32))
    return h + r * ((gate * (v @ w["w_up"].astype(F32)))
                    @ w["w_down"].astype(F32))


def sala_forward(params, tokens, dims: dict, reset=None, select: bool = True):
    """Logits ``(S, V)`` float32 of one sequence ``tokens`` ``(S,)``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens] * dims["scale_emb"]
        seen = {kind: 0 for kind in _SALA_LEAVES}
        mixers = sum(_SALA_LEAVES.values(), ())
        for l, name in enumerate(dims["mixer_types"]):
            kind = SALA_KINDS[name]
            w = {leaf: a[seen[kind] if leaf in mixers else l]
                 for leaf, a in params["layers"].items()
                 if leaf in _SALA_LEAVES[kind] or leaf not in mixers}
            seen[kind] += 1
            x = sala_layer(x, w, dims, kind, reset, select)
        x = rmsnorm(x, params["ln_f"], dims["rms_norm_eps"])
        x = x / (dims["hidden_size"] / dims["dim_model_base"])
        return x @ params["head"].astype(F32)
