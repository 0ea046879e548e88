"""The plain reference of a patterned expert model (``kind:
serve_patterned``): the benchmark's OWN copy of the forward pass that
``horovod_tpu/models/plain_reference.py`` states — straightforward
``jax.numpy``, float32 at ``default_matmul_precision("highest")``, no
kernel, no cache, NOTHING imported from the program — arranged so that
the published widths fit beside nothing else on one chip.

The layer, as published (Qwen3-MoE's config class with ``layer_types``
and per-kind ``rope_parameters``): ``h = x + Attn(RMSNorm(x))``, ``y = h
+ MoE(RMSNorm(h))``, no bias; q and k pass an RMSNorm over the head with
a learned scale, then rotate-half rope — plain on sliding layers, YaRN
on full layers; key ``j`` visible to query ``i`` iff ``j <= i`` and, on
a sliding layer, ``i - j < sliding_window``; the experts' scores a
float32 softmax, the ``num_experts_per_tok`` largest renormalised, no
token dropped, no shared expert; final RMSNorm, untied head.

Departures, each in memory only (same mathematics): queries go in blocks
(``reference.py``'s), the experts one at a time — EVERY expert on every
position, times the weight the router gave it (0 where it was not
picked) — and the weights one layer at a time from
``weights_patterned.layer_params`` (one layer is 1.67 GB in float32).
Departures from the published description: the q/k norm is the config
class's convention (no key of the config states it); the model card's
multi-token-prediction head has no key and is left out.

``mode`` is ``reference.py``'s: ``"f32"`` the reference, ``"bf16"`` and
``"fp8"`` the lower-precision CONTROLS (every matmul's operands rounded,
the router's too)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, _dims_items, _mm, logits_at, rmsnorm


def rope_tables(positions, head_dim: int, rope: dict):
    """``(cos, sin)`` ``(S, head_dim / 2)`` of one layer kind's
    ``rope_parameters`` entry (YaRN as the published code computes it)."""
    d, b = head_dim, float(rope["rope_theta"])
    i = jnp.arange(d // 2, dtype=F32)
    inv = b ** (-2.0 * i / d)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        s = float(rope["factor"])
        L0 = float(rope["original_max_position_embeddings"])

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
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode: str, q_block: int, window: int):
    """Causal softmax attention of one sequence, ``window`` > 0 a
    sliding layer's.  Queries go in blocks of ``q_block`` (memory only:
    each block sees every key, masked)."""
    S, H, Dh = q.shape
    g = H // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    q_block = min(q_block, S)
    assert S % q_block == 0, (S, q_block)
    kpos = jnp.arange(S)

    def block(args):
        qb, start = args
        s = _mm("qhd,khd->hqk", qb, k, mode) / jnp.sqrt(F32(Dh))
        qpos = (start + jnp.arange(q_block))[None, :, None]
        vis = kpos[None, None, :] <= qpos
        if window:
            vis &= qpos - kpos[None, None, :] < window
        p = jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", p, v, mode)

    qb = q.reshape(S // q_block, q_block, H, Dh)
    starts = jnp.arange(S // q_block) * q_block
    return jax.lax.map(block, (qb, starts)).reshape(S, H, Dh)


def experts(n, w, dims: dict, mode: str):
    """Every expert on every position, one expert at a time, weighted
    by the router's renormalised top-k score (0 if not picked)."""
    k = dims["num_experts_per_tok"]
    p = jax.nn.softmax(_mm("sd,de->se", n, w["router"], mode), axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    if dims["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weight = jnp.zeros_like(p).at[
        jnp.arange(n.shape[0])[:, None], top_e].set(top_p)

    def one(acc, ew):
        wg, wu, wd, col = ew
        out = _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", n, wg, mode))
                  * _mm("sd,df->sf", n, wu, mode), wd, mode)
        return acc + out * col[:, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return acc


def layer(x, w, dims: dict, kind: str, mode: str, q_block: int):
    """One pre-norm block on one sequence ``x``: (S, D) float32."""
    eps, dh = dims["rms_norm_eps"], dims["head_dim"]
    h = rmsnorm(x, w["ln1"], eps)
    q = rmsnorm(_mm("sd,dhk->shk", h, w["wq"], mode), w["q_norm"], eps)
    k = rmsnorm(_mm("sd,dhk->shk", h, w["wk"], mode), w["k_norm"], eps)
    v = _mm("sd,dhk->shk", h, w["wv"], mode)
    cos, sin = rope_tables(jnp.arange(x.shape[0]), dh,
                           dims["rope_parameters"][kind])
    window = dims["sliding_window"] if kind == "sliding_attention" else 0
    o = attention(rotate(q, cos, sin), rotate(k, cos, sin), v, mode,
                  q_block, window)
    x = x + _mm("shk,hkd->sd", o, w["wo"], mode)
    return x + experts(rmsnorm(x, w["ln2"], eps), w, dims, mode)


def _freeze(tree):
    """A hashable form of a configuration's nested groups."""
    if isinstance(tree, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in tree.items()))
    if isinstance(tree, list):
        return tuple(_freeze(v) for v in tree)
    return tree


def _thaw(tree):
    if isinstance(tree, tuple) and tree and all(
            isinstance(v, tuple) and len(v) == 2 and isinstance(v[0], str)
            for v in tree):
        return {k: _thaw(v) for k, v in tree}
    return tree


@functools.lru_cache(maxsize=None)
def _rows_layer_fn(dims_frozen: tuple, kind: str, mode: str, q_block: int):
    dims = _thaw(dims_frozen)

    def f(xs, w):
        return jax.lax.map(
            lambda x: layer(x, w, dims, kind, mode, q_block), xs)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _head_fn(dims_items: tuple, mode: str):
    dims = dict(dims_items)
    return jax.jit(lambda x, i, ln_f, head: logits_at(
        x[i], ln_f, head, dims, mode))


def _layer_dims(dims: dict) -> tuple:
    keys = ("rms_norm_eps", "head_dim", "num_experts_per_tok",
            "norm_topk_prob", "sliding_window", "rope_parameters")
    return _freeze({k: dims[k] for k in keys})


def served_logits(seed: int, dims: dict, weights_dtype, tokens, prompt_lens,
                  n_served, *, mode: str = "f32", q_block: int = 512):
    """``reference.served_logits`` for this model: teacher-forced logits
    at the positions that produced served tokens, layer by layer over a
    padded batch of sequences, one layer's weights at a time."""
    from chipbench import weights_patterned as W

    tokens = jnp.asarray(tokens, jnp.int32)
    top = W.top_params(seed, dims, weights_dtype)
    xs = top["embed"].astype(F32)[tokens]
    with jax.default_matmul_precision("highest"):
        for l, kind in enumerate(dims["layer_types"]):
            w = W.layer_params(seed, l, dims, weights_dtype)
            xs = _rows_layer_fn(_layer_dims(dims), kind, mode, q_block)(
                xs, w)
        m = int(max(n_served))
        idx = np.asarray(prompt_lens)[:, None] - 1 + np.arange(m)[None, :]
        valid = np.arange(m)[None, :] < np.asarray(n_served)[:, None]
        idx = np.where(valid, idx, 0)
        served = np.take_along_axis(np.asarray(tokens), idx + 1, axis=1)
        head_fn = _head_fn(_dims_items(dims), mode)
        out = [np.asarray(head_fn(xs[i], jnp.asarray(idx[i]), top["ln_f"],
                                  top["head"]))
               for i in range(tokens.shape[0])]
    return np.stack(out), served, valid
