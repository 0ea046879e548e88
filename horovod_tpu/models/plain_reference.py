"""The plain reference of a patterned expert model: its forward pass in
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
