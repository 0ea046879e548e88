"""The plain reference of a SPARSE latent-attention expert model
(``kind: serve_sparse``): the benchmark's OWN copy of the forward pass
that ``horovod_tpu/models/plain_reference.py`` states (``sparse_*``) —
straightforward ``jax.numpy``, float32 at
``default_matmul_precision("highest")``, NON-absorbed attention, no
kernel, no cache, NOTHING imported from the program — arranged so that
32 k tokens at the published widths fit on one chip beside nothing else,
and so that every sequence of a cell runs through the SAME three
executables (a layer's attention half, the dense and the expert FFN
half; a sequence's own padded length would compile a layer anew for
every length: 12-21 s each at these widths, my chip run, PR 32).

The layer is ``reference_latent``'s (DeepSeek-V3's block) with two
additions, as the family's published inference code states them
(``inference/model.py``: ``MLA``, ``Indexer``, ``Gate``); ``h`` the
layer's input after its norm, ``cq`` the normed query latent:

* the LIGHTNING INDEXER: ``q_I[t] = cq[t] W_Iq`` (``index_n_heads`` x
  ``index_head_dim``), ONE key a token ``k_I[s] = LayerNorm(h[s] W_Ik)``
  (scale and bias, eps 1e-6), RoPE (the layer's YaRN tables) on the
  first ``qk_rope_head_dim`` dims of both, ``w[t] = (h[t] W_Iw) x
  index_n_heads^-0.5 x index_head_dim^-0.5``; ``I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s])``, ``s <= t``; the query attends the
  ``min(index_topk, t + 1)`` positions of largest ``I`` (``lax.top_k``:
  ties to the lower position), the softmax over that set alone;
* the router CHOOSES (groups by the sum of their two largest, then the
  experts) on ``scores + router_bias`` and WEIGHTS by the raw scores.

Departures from the published code, each the program's too and listed
in the configuration file's ``assumed``: the multi-token-prediction
module is left out; index queries and keys are not rotated by a
Hadamard matrix nor quantised to fp8 (the rotation leaves ``q_I . k_I``
as it is); rotate-half pairing for both ropes.  In memory and time only
(the forward is causal and every selection looks back only, so no row
depends on a later one): every sequence lies in an array of the SAME
width, and rows go in blocks of which only those below the sequence's
own length ``n`` are computed (``_rows``; the others stay zero, and
causality masks them); a layer first selects for every query — one mask a
layer — then attends the heads in groups under it; a query block sees
the keys up to the end of its BAND of ``band`` rows (what lies beyond is
masked by causality anyway); the held experts one at a time, the weights
one layer at a time.

``mode`` is ``reference.py``'s (``"f32"`` the reference, ``"fp8"`` /
``"bf16"`` the lower-precision controls: every matmul's operands
rounded, the indexer's and the router's too — fp8's scale is an
operand's, so a block of rows has its own); ``select=False`` is the
SECOND control: dense attention, the indexer ignored."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, _mm, logits_at, rmsnorm
from chipbench.reference_latent import (_swiglu, rope_entry,
                                        softmax_scale)
from chipbench.reference_patterned import (_freeze, _thaw, rope_tables,
                                           rotate)

BAND = 8192


def layernorm(x, w, b, eps: float = 1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def _rows(fn, n, block: int, first: int, last: int, *arrays, local=()):
    """``fn(start, *blocks)`` on the blocks of ``block`` rows of
    ``arrays[first:last]`` that begin below ``n`` (a traced length);
    its results, each ``(block, ...)``, land at their rows in zero arrays
    of ``last - first`` rows.  ``local`` arrays hold rows ``first`` to
    ``last`` alone and are cut with the others.  Time only: the rows at
    and beyond ``n`` are never computed."""
    assert (last - first) % block == 0, (first, last, block)

    def cut(i):
        return [jax.lax.dynamic_slice_in_dim(a, off + i * block, block)
                for off, group in ((first, arrays), (0, local))
                for a in group]

    shapes = jax.eval_shape(lambda: fn(jnp.int32(first), *cut(0)))
    live = jnp.clip(-(-(n - first) // block), 0, (last - first) // block)

    def body(i, outs):
        new = fn(first + i * block, *cut(i))
        return jax.tree_util.tree_map(
            lambda o, v: jax.lax.dynamic_update_slice_in_dim(
                o, v, i * block, 0), outs, new)

    zeros = jax.tree_util.tree_map(
        lambda sh: jnp.zeros((last - first, *sh.shape[1:]), sh.dtype),
        shapes)
    return jax.lax.fori_loop(0, live, body, zeros)


def _bands(S: int, band: int):
    band = min(band, S)
    assert S % band == 0, (S, band)
    return [(a, a + band) for a in range(0, S, band)]


def select(qi, wt, ki, topk: int, mode: str, q_block: int, n, band: int):
    """Every query's selection as a mask, one array a band of queries:
    ``(band, keys up to the band's end)`` bool, True at the ``min(topk,
    t + 1)`` positions ``s <= t`` of largest index score (``lax.top_k``:
    ties to the lower position; what it finds among the ``-inf`` of a
    query that sees fewer is masked again by causality)."""
    masks = []
    for a, b in _bands(qi.shape[0], band):
        kb, kpos = ki[:b], jnp.arange(b)

        def block(start, qib, wb, kb=kb, kpos=kpos, b=b):
            idx = jnp.einsum("qh,qhk->qk", wb, jax.nn.relu(
                _mm("qhd,kd->qhk", qib, kb, mode)))
            rows = jnp.arange(qib.shape[0])[:, None]
            seen = kpos[None, :] <= start + rows
            top = jax.lax.top_k(jnp.where(seen, idx, -jnp.inf),
                                min(topk, b))[1]
            return seen & jnp.zeros_like(seen).at[rows, top].set(True)

        masks.append(_rows(block, n, min(q_block, b - a), a, b, qi, wt))
    return masks


def attention(q_nope, q_rope, k_nope, k_rope, v, masks, scale: float,
              mode: str, q_block: int, n, band: int):
    """Softmax attention of one sequence, heads expanded, each query
    over the positions its row of ``masks`` marks (``select``'s; None:
    every earlier position, selection off)."""
    out = []
    for j, (a, b) in enumerate(_bands(q_nope.shape[0], band)):
        kn, kr, vb, kpos = k_nope[:b], k_rope[:b], v[:b], jnp.arange(b)

        def block(start, qn, qr, *vis, kn=kn, kr=kr, vb=vb, kpos=kpos):
            vis = vis[0] if vis else \
                kpos[None, :] <= start + jnp.arange(qn.shape[0])[:, None]
            s = (_mm("qhd,khd->hqk", qn, kn, mode)
                 + _mm("qhd,kd->hqk", qr, kr, mode)) * scale
            p = jax.nn.softmax(jnp.where(vis[None], s, -jnp.inf), axis=-1)
            return _mm("hqk,khd->qhd", p, vb, mode)

        out.append(_rows(block, n, min(q_block, b - a), a, b,
                         q_nope, q_rope,
                         local=() if masks is None else (masks[j],)))
    return jnp.concatenate(out)


def route(n, router, bias, dims: dict, mode: str):
    """``(S, E)`` combination weights over ALL the router's outputs:
    chosen on ``scores + bias``, weighted by the raw scores."""
    sc = jax.nn.sigmoid(_mm("sd,de->se", n, router, mode))
    S, E = sc.shape
    choice, g = sc + bias.astype(F32), dims["n_group"]
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


def experts(n, w, dims: dict, mode: str):
    """The held experts one at a time on every position, weighted by
    the router's weight (0 if not picked), and the shared expert."""
    off = dims["expert_offset"]
    weight = route(n, w["router"], w["router_bias"], dims, mode)[
        :, off:off + w["w_gate"].shape[0]]

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + _swiglu(n, wg, wu, wd, mode) * col[:, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return acc + _swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"], mode)


ATTENTION_LEAVES = ("ln1", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
                    "wkv_b", "wo", "wi_q", "wi_k", "i_k_norm", "i_k_bias",
                    "wi_w")


def _blocks(S: int, n, q_block: int, sel_block: int, band: int,
            row_block: int):
    """Every part computes the same rows: the sequence's, up to the end
    of the widest block (a query row whose selection was not computed
    would attend nothing, and its NaN would reach the next layer's V)."""
    step = min(max(q_block, sel_block, row_block), band, S)
    q_block, sel_block, row_block = (min(b, step) for b in
                                     (q_block, sel_block, row_block))
    assert not (step % q_block or step % sel_block or step % row_block)
    return -(-n // step) * step, q_block, sel_block, row_block


def attend(x, w, n, dims: dict, mode: str, select_on: bool = True,
           q_block: int = 512, sel_block: int = 128, head_group: int = 16,
           band: int = BAND, row_block: int = 512):
    """The attention half of one pre-norm block on one sequence: ``x``
    (S, D) float32 of which the first ``n`` rows are the sequence, ``w``
    the layer's ``ATTENTION_LEAVES``; ``x + Attn(norm(x))``.
    ``q_block`` (the attention's queries), ``sel_block`` (the
    selection's), ``head_group``, ``band`` and ``row_block`` (powers of
    two) divide the work in memory and time only."""
    eps, nope, c = dims["rms_norm_eps"], dims["qk_nope_head_dim"], \
        dims["kv_lora_rank"]
    r, S = dims["qk_rope_head_dim"], x.shape[0]
    n, q_block, sel_block, row_block = _blocks(S, n, q_block, sel_block,
                                               band, row_block)

    def project(start, xb):
        h = rmsnorm(xb, w["ln1"], eps)
        cq = rmsnorm(_mm("sd,dr->sr", h, w["wq_a"], mode), w["q_a_norm"],
                     eps)
        kv = _mm("sd,dc->sc", h, w["wkv_a"], mode)
        ckv = rmsnorm(kv[:, :c], w["kv_a_norm"], eps)
        cos, sin = rope_tables(start + jnp.arange(xb.shape[0]), r,
                               rope_entry(dims))
        out = {"cq": cq, "ckv": ckv, "cos": cos, "sin": sin,
               "k_rope": rotate(kv[:, None, c:], cos, sin)[:, 0]}
        if select_on:
            qi = _mm("sr,rhk->shk", cq, w["wi_q"], mode)
            ki = layernorm(_mm("sd,dk->sk", h, w["wi_k"], mode),
                           w["i_k_norm"], w["i_k_bias"])
            out["qi"] = jnp.concatenate(
                [rotate(qi[..., :r], cos, sin), qi[..., r:]], -1)
            out["ki"] = jnp.concatenate(
                [rotate(ki[:, None, :r], cos, sin)[:, 0], ki[:, r:]], -1)
            out["wt"] = _mm("sd,dh->sh", h, w["wi_w"], mode) * (
                dims["index_n_heads"] ** -0.5
                * dims["index_head_dim"] ** -0.5)
        return out

    pr = _rows(project, n, row_block, 0, S, x)
    cos, sin = pr["cos"], pr["sin"]
    # ONE selection a layer, every head under it
    masks = select(pr["qi"], pr["wt"], pr["ki"], dims["index_topk"], mode,
                   sel_block, n, band) if select_on else None
    # the heads in groups (memory only: at 128 heads and 32 k positions
    # every head's keys and values at once are 4.3 GB in float32)
    H = w["wq_b"].shape[1]
    hg = min(head_group, H)
    assert H % hg == 0, (H, hg)

    def group(attn, gw):
        wq_b, wkv_b, wo = gw
        q, kvb = _rows(
            lambda start, cq, ckv: (_mm("sr,rhk->shk", cq, wq_b, mode),
                                    _mm("sc,chk->shk", ckv, wkv_b, mode)),
            n, row_block, 0, S, pr["cq"], pr["ckv"])
        o = attention(q[..., :nope], rotate(q[..., nope:], cos, sin),
                      kvb[..., :nope], pr["k_rope"], kvb[..., nope:], masks,
                      softmax_scale(dims), mode, q_block, n, band)
        return attn + _rows(
            lambda start, ob: _mm("shk,hkd->sd", ob, wo, mode),
            n, row_block, 0, S, o), None

    def grouped(a, axis):  # (.., H, ..) -> (H / hg, .., hg, ..)
        a = a.reshape(*a.shape[:axis], H // hg, hg, *a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    attn, _ = jax.lax.scan(group, jnp.zeros_like(x), (
        grouped(w["wq_b"], 1), grouped(w["wkv_b"], 1), grouped(w["wo"], 0)))
    return x + attn


def feed(x, w, n, dims: dict, mode: str, q_block: int = 512,
         sel_block: int = 128, head_group: int = 16, band: int = BAND,
         row_block: int = 512):
    """The other half: ``x + FFN(norm(x))``, dense or expert by whether
    the layer has a router, on the rows ``attend`` computed (its
    blocks)."""
    n, _, _, row_block = _blocks(x.shape[0], n, q_block, sel_block, band,
                                 row_block)

    def ffn(start, xb):
        nb = rmsnorm(xb, w["ln2"], dims["rms_norm_eps"])
        if "router" in w:
            return xb + experts(nb, w, dims, mode)
        return xb + _swiglu(nb, w["w_gate"], w["w_up"], w["w_down"], mode)

    return _rows(ffn, n, row_block, 0, x.shape[0], x)


_LAYER_KEYS = ("rms_norm_eps", "qk_nope_head_dim", "qk_rope_head_dim",
               "kv_lora_rank", "rope_theta", "rope_scaling", "scoring_func",
               "n_group", "topk_group", "num_experts_per_tok",
               "norm_topk_prob", "routed_scaling_factor", "expert_offset",
               "index_n_heads", "index_head_dim", "index_topk")


@functools.lru_cache(maxsize=None)
def _layer_fns(dims_frozen: tuple, mode: str, select: bool, blocks: tuple):
    """The two halves, jitted apart: the attention's executable — most
    of a layer's compiling — serves the dense and the expert layers."""
    dims = _thaw(dims_frozen)
    return (jax.jit(lambda x, w, n: attend(x, w, n, dims, mode, select,
                                           *blocks), donate_argnums=(0,)),
            jax.jit(lambda x, w, n: feed(x, w, n, dims, mode, *blocks),
                    donate_argnums=(0,)))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, mode: str):
    return jax.jit(lambda x, i, ln_f, head: logits_at(
        x[i], ln_f, head, {"rms_norm_eps": eps}, mode))


def pairs(n: int, width: int, band: int = BAND, step: int = 512) -> int:
    """(query, key) pairs the reference computes a layer for a sequence
    of ``n`` tokens laid in ``width`` rows: what its time grows with."""
    n = -(-n // step) * step
    return sum(min(max(n - a, 0), b - a) * b for a, b in _bands(width, band))


def served_logits(seed: int, dims: dict, weights_dtype, tokens, prompt_lens,
                  n_served, *, mode: str = "f32", select: bool = True,
                  blocks: tuple = ()):
    """``reference_latent.served_logits`` for this model: teacher-forced
    logits at the positions that produced served tokens, layer by
    layer, one layer's weights at a time, every sequence in the width of
    ``tokens`` (a multiple of ``BAND`` or less than it) and computed up
    to its OWN length.  ``blocks``: ``attend``'s block sizes, in its
    order, where the defaults do not suit."""
    from chipbench import weights_sparse as W

    if dims["scoring_func"] != "sigmoid":
        raise ValueError("the biased choice is written for sigmoid scores")
    tokens = np.asarray(tokens, np.int32)
    plens, n_served = np.asarray(prompt_lens), np.asarray(n_served)
    top = W.top_params(seed, dims, weights_dtype)
    embed = top["embed"].astype(F32)
    xs = [embed[jnp.asarray(row)] for row in tokens]
    att, ffn = _layer_fns(_freeze({k: dims[k] for k in _LAYER_KEYS}), mode,
                          select, tuple(blocks))
    with jax.default_matmul_precision("highest"):
        for l in range(dims["num_hidden_layers"]):
            w = W.layer_params(seed, l, dims, weights_dtype)
            wa = {k: w.pop(k) for k in ATTENTION_LEAVES}
            xs = [ffn(att(x, wa, n), w, n)
                  for x, n in zip(xs, np.int32(plens + n_served))]
        m = int(max(n_served))
        idx = plens[:, None] - 1 + np.arange(m)[None, :]
        valid = np.arange(m)[None, :] < n_served[:, None]
        idx = np.where(valid, idx, 0)
        served = np.take_along_axis(tokens, idx + 1, axis=1)
        head_fn = _head_fn(float(dims["rms_norm_eps"]), mode)
        out = [np.asarray(head_fn(x, jnp.asarray(i), top["ln_f"],
                                  top["head"])) for x, i in zip(xs, idx)]
    return np.stack(out), served, valid
