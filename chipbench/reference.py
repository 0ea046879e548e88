"""The plain reference: the configuration's forward pass, its loss and an
AdamW step in straightforward ``jax.numpy``, float32 at
``default_matmul_precision("highest")``, with no kernel, no cache, no
batching tricks and NOTHING imported from the program.  It follows the
published description of the model family (pre-norm RMSNorm, rotary
positions in the half-split layout, grouped-query causal attention,
SwiGLU, untied head).

Departures, each noted where it is made: queries are processed in blocks
and layers are checkpointed so that a 4096-token row fits beside the
optimizer state (same mathematics); weights arrive layer by layer from
``weights.py`` so that a 16-layer model never sits in float32 at once.

``mode`` selects the matmul arithmetic: ``"f32"`` is the reference;
``"bf16"`` and ``"fp8"`` are the lower-precision CONTROLS used only to show
that the comparison fails when it should (operands rounded to that type —
fp8 e4m3 with a per-tensor scale — and multiplied exactly)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_E4M3_MAX = 448.0


def _round_operand(a, mode: str):
    a = a.astype(F32)
    if mode == "f32":
        return a
    if mode == "bf16":
        low = a.astype(jnp.bfloat16).astype(F32)
    elif mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _E4M3_MAX
        low = (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    else:
        raise ValueError(f"unknown reference mode {mode!r}")
    # straight-through: the forward value is rounded, the gradient passes
    # (a cotangent cast to fp8 would underflow to zero, which no one ships)
    return a + jax.lax.stop_gradient(low - a)


def _mm(spec: str, a, b, mode: str):
    return jnp.einsum(spec, _round_operand(a, mode), _round_operand(b, mode),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def rmsnorm(x, w, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope(x, positions, theta: float):
    """``x``: (S, H, Dh); rotate_half convention of the published code."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode: str, q_block: int):
    """Causal softmax attention of one sequence.  q: (S, H, Dh), k/v:
    (S, Hkv, Dh).  Queries go in blocks of ``q_block`` (a departure in
    memory only: each block sees every key, masked causally)."""
    S, H, Dh = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    q_block = min(q_block, S)
    assert S % q_block == 0, (S, q_block)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = _mm("qhd,khd->hqk", qb, k, mode) / jnp.sqrt(F32(Dh))
        qpos = start + jnp.arange(q_block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, mode)

    qb = q.reshape(S // q_block, q_block, H, Dh)
    starts = jnp.arange(S // q_block) * q_block
    return jax.lax.map(block, (qb, starts)).reshape(S, H, Dh)


def layer(x, w, dims: dict, mode: str, q_block: int):
    """One pre-norm block on one sequence ``x``: (S, D) float32."""
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    pos = jnp.arange(x.shape[0])
    h = rmsnorm(x, w["ln1"], eps)
    q = rope(_mm("sd,dhk->shk", h, w["wq"], mode), pos, theta)
    k = rope(_mm("sd,dhk->shk", h, w["wk"], mode), pos, theta)
    v = _mm("sd,dhk->shk", h, w["wv"], mode)
    x = x + _mm("shk,hkd->sd", attention(q, k, v, mode, q_block),
                w["wo"], mode)
    h = rmsnorm(x, w["ln2"], eps)
    gate = _mm("sd,df->sf", h, w["w_gate"], mode)
    up = _mm("sd,df->sf", h, w["w_up"], mode)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, w["w_down"], mode)


def logits_at(x, ln_f, head, dims: dict, mode: str):
    return _mm("sd,dv->sv", rmsnorm(x, ln_f, dims["rms_norm_eps"]), head,
               mode)


# --- serving: layer-by-layer over a padded batch of sequences ---------------


@functools.lru_cache(maxsize=None)
def _rows_layer_fn(dims_items: tuple, mode: str, q_block: int):
    dims = dict(dims_items)

    def f(xs, w):
        return jax.lax.map(lambda x: layer(x, w, dims, mode, q_block), xs)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _head_fn(dims_items: tuple, mode: str):
    dims = dict(dims_items)
    return jax.jit(lambda x, i, ln_f, head: logits_at(
        x[i], ln_f, head, dims, mode))


def _dims_items(dims: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in dims.items()
                        if isinstance(v, (int, float)) and v is not None))


def served_logits(seed: int, dims: dict, weights_dtype, tokens, prompt_lens,
                  n_served, *, mode: str = "f32", q_block: int = 512):
    """Teacher-forced logits at the positions that produced served tokens.

    ``tokens`` (N, S): each row a prompt followed by the tokens that were
    served, right-padded (the forward is causal, so padding cannot reach a
    position that is read).  Returns ``(logits, served, valid)``:
    ``logits[i, j]`` (N, max_served, V) float32 are the model's logits, in
    precision ``mode``, at position ``prompt_lens[i] - 1 + j``;
    ``served[i, j]`` is the token the program emitted there and ``valid``
    marks the entries that exist.

    Weights come layer by layer from ``weights.layer_params`` in
    ``weights_dtype`` (the values as served) and are used in float32, so a
    16-layer model never sits in float32 at once."""
    from chipbench import weights as W

    tokens = jnp.asarray(tokens, jnp.int32)
    top = W.top_params(seed, dims, weights_dtype)
    xs = top["embed"].astype(F32)[tokens]
    fn = _rows_layer_fn(_dims_items(dims), mode, q_block)
    with jax.default_matmul_precision("highest"):
        for l in range(dims["num_hidden_layers"]):
            w = W.layer_params(seed, l, dims, weights_dtype)
            xs = fn(xs, w)
        m = int(max(n_served))
        # position that produced served token j of row i: prompt_len-1+j
        idx = np.asarray(prompt_lens)[:, None] - 1 + np.arange(m)[None, :]
        valid = np.arange(m)[None, :] < np.asarray(n_served)[:, None]
        idx = np.where(valid, idx, 0)
        served = np.take_along_axis(np.asarray(tokens), idx + 1, axis=1)
        out = []
        head_fn = _head_fn(_dims_items(dims), mode)
        for i in range(tokens.shape[0]):
            out.append(np.asarray(head_fn(
                xs[i], jnp.asarray(idx[i]), top["ln_f"], top["head"])))
    logits = np.stack(out)                      # (N, m, V) float32
    return logits, served, valid


def gaps_from_logits(ref_logits, picked, valid):
    """``gap``: reference's best minus the reference's logit of the token
    that was picked; ``margin``: reference's top-1 minus top-2."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, picked[..., None], -1)[..., 0]
    part = np.partition(ref_logits, -2, axis=-1)
    gap = np.where(valid, best - got, np.nan)
    margin = np.where(valid, part[..., -1] - part[..., -2], np.nan)
    return gap, margin


# --- training: loss, gradients and AdamW on rows, one row at a time ---------


def row_loss(params, tokens, targets, dims: dict, mode: str, q_block: int):
    """SUM of next-token cross-entropy over one row's positions."""
    x = params["embed"].astype(F32)[tokens]
    step = jax.checkpoint(
        lambda x, w: layer(x, w, dims, mode, q_block))
    for l in range(dims["num_hidden_layers"]):
        x = step(x, jax.tree_util.tree_map(lambda a: a[l], params["layers"]))
    lg = logits_at(x, params["ln_f"], params["head"], dims, mode)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))), tree)


def make_train_step(dims: dict, opt: dict, mesh, axis: str, *,
                    mode: str = "f32", q_block: int = 1024):
    """The reference's step over a global batch whose rows are spread over
    ``mesh`` (plain data parallelism so that four chips' rows take a
    quarter of the time; each device walks its own rows one at a time and
    the gradient sums are added).  Returns
    ``step(params, mu, nu, count, tokens, targets) -> (params, mu, nu,
    loss, grad_leaf_norms)`` with AdamW written out: no optimizer library.
    """
    from jax.sharding import PartitionSpec as P

    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, wd = opt["eps"], opt["weight_decay"]

    def local(params, tokens, targets):
        def one(carry, row):
            loss, g = jax.value_and_grad(row_loss)(
                params, row[0], row[1], dims, mode, q_block)
            return (carry[0] + loss,
                    jax.tree_util.tree_map(jnp.add, carry[1], g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, g), _ = jax.lax.scan(one, (F32(0.0), zero), (tokens, targets))
        return jax.lax.psum(loss, axis), jax.lax.psum(g, axis)

    sharded = jax.shard_map(local, mesh=mesh,
                            in_specs=(P(), P(axis), P(axis)),
                            out_specs=(P(), P()), check_vma=False)

    def step(params, mu, nu, count, tokens, targets):
        with jax.default_matmul_precision("highest"):
            loss, g = sharded(params, tokens, targets)
        n = tokens.size
        loss, g = loss / n, jax.tree_util.tree_map(lambda a: a / n, g)
        t = count + 1
        mu = jax.tree_util.tree_map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
        nu = jax.tree_util.tree_map(
            lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
        c1, c2 = 1 - b1 ** t.astype(F32), 1 - b2 ** t.astype(F32)
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), params, mu, nu)
        return params, mu, nu, loss, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))
