"""What the algorithm NEEDS: operations and bytes computed from shapes.

These are the yardsticks of every utilisation and roofline share the
benchmark prints.  They count what the mathematics requires — not what a
kernel happens to fetch, and not what ``cost_analysis()`` says the
compiled program executes (that counts recomputation and misses scans).
Each function says which bound (FLOP/s or bytes/s) it is meant for, and
each has a hand-worked case in ``tests/test_costs.py``.

``dims`` is a configuration file's dict with the Hugging Face key names.
"""

from __future__ import annotations

from typing import Iterable


def matmul_params_per_layer(dims: dict) -> int:
    """Weights of one layer that take part in a matmul: q, k, v, o
    projections and the three SwiGLU matrices (norm scales do not)."""
    d, h, kv = dims["hidden_size"], dims["num_attention_heads"], \
        dims["num_key_value_heads"]
    dh, f = dims["head_dim"], dims["intermediate_size"]
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def train_flops_per_token(dims: dict, seq: int) -> float:
    """Required forward+backward FLOPs per trained token (bound: FLOP/s).

    6 per matmul weight (2 forward, 4 backward) for the layers and the
    untied vocabulary head (the embedding is a lookup); causal attention
    at half: the forward needs QK^T and PV over on average seq/2 keys,
    2 * 2 * (seq/2) * H * Dh per token per layer, and the backward twice
    that, so 6 * seq * H * Dh.  Recomputation (remat, the flash backward's
    second pass over the scores) is not required work and is not
    counted."""
    L = dims["num_hidden_layers"]
    weights = L * matmul_params_per_layer(dims) \
        + dims["hidden_size"] * dims["vocab_size"]
    attn = 6.0 * seq * dims["num_attention_heads"] * dims["head_dim"] * L
    return 6.0 * weights + attn


def head_share_of_train_flops(dims: dict, seq: int) -> float:
    """Share of :func:`train_flops_per_token` spent in the vocabulary
    head — large when the depth is cut (PERF.md says so)."""
    return 6.0 * dims["hidden_size"] * dims["vocab_size"] \
        / train_flops_per_token(dims, seq)


def flash_train_flops(dims: dict, seq: int, rows: int) -> float:
    """Required FLOPs of the attention kernels (forward + backward) for
    ``rows`` sequences of ``seq`` tokens through every layer, causal at
    half (bound: FLOP/s — at Dh 128 and seq 4096 the arithmetic intensity
    is far above the v5e's 240 FLOP/byte ridge)."""
    return 6.0 * seq * dims["num_attention_heads"] * dims["head_dim"] \
        * dims["num_hidden_layers"] * seq * rows


def paged_decode_bytes(dims: dict, context_lens: Iterable[int],
                       kv_bytes: int = 2, act_bytes: int = 2,
                       scale_bytes: int = 0) -> float:
    """Bytes the paged decode attention NEEDS for one token of each slot
    whose context (tokens already in the cache, the new one included) is
    listed, through every layer (bound: bytes/s — one query row per head
    against the whole context is ~1 FLOP per byte).

    Per slot and layer: the live tokens' K and V once, in the pool's
    storage dtype (``kv_bytes``; plus ``scale_bytes`` per token and KV head
    for a quantised pool), and the queries in and outputs out
    (``act_bytes``).  From the slots' positions — never from ``max_pages``:
    what the kernel fetches beyond the live tokens is its own waste."""
    L, kv = dims["num_hidden_layers"], dims["num_key_value_heads"]
    h, dh = dims["num_attention_heads"], dims["head_dim"]
    total = 0.0
    for n in context_lens:
        kv_tok = 2 * n * kv * (dh * kv_bytes + scale_bytes)
        qo = 2 * h * dh * act_bytes
        total += L * (kv_tok + qo)
    return total


def paged_decode_flops(dims: dict, context_lens: Iterable[int]) -> float:
    """FLOPs of the same calls (QK^T and PV: 4 * n * H * Dh per layer);
    kept to show the kernel is bytes-bound: flops/bytes ~ H/H_kv."""
    L = dims["num_hidden_layers"]
    h, dh = dims["num_attention_heads"], dims["head_dim"]
    return float(sum(4 * n * h * dh * L for n in context_lens))


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take and which bound sets it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
