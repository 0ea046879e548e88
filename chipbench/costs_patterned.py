"""What a patterned expert model's two mechanisms NEED, from shapes and
from the program's own counters (``costs.py``'s rule: the mathematics'
requirement, not what a kernel fetches).  Each function names its bound.

``dims`` is the configuration file's dict with the published key names.
"""

from __future__ import annotations

from typing import Iterable


def expert_weight_bytes(dims: dict, weight_bytes: int = 2) -> int:
    """One expert's three matrices (gate, up, down)."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"] \
        * weight_bytes


def moe_expert_bytes(dims: dict, experts_touched: float, rows: float,
                     weight_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the grouped expert products NEED (bound: bytes/s at a
    decode tick's few rows an expert — ~4 FLOPs a weight byte at 4 rows
    — and FLOP/s at a prefill chunk's).

    The weights of the experts that were handed at least one row, ONCE
    each — ``experts_touched`` is the program's count over layers and
    ticks (``moe_experts_touched_total``), never ``num_experts``: an
    expert no token picked need not be read — and each row's input and
    output (``hidden_size`` each; the ``moe_intermediate_size``-wide
    intermediates need not leave the chip's fast memory)."""
    return experts_touched * expert_weight_bytes(dims, weight_bytes) \
        + rows * 2 * dims["hidden_size"] * act_bytes


def moe_expert_flops(dims: dict, rows: float) -> float:
    """FLOPs of the same rows (bound: FLOP/s): three products of
    ``hidden_size x moe_intermediate_size`` a row, 2 a multiply-add."""
    return rows * 3 * 2.0 * dims["hidden_size"] \
        * dims["moe_intermediate_size"]


def windowed_decode_bytes(dims: dict, context_lens: Iterable[int],
                          kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the paged decode attention NEEDS for one token of each slot
    whose context (the new token included) is listed, through every
    layer of a stack with window layers (bound: bytes/s, as
    ``costs.paged_decode_bytes``).

    A full layer needs the ``n`` live tokens' K and V; a sliding layer
    the last ``min(n, sliding_window)`` only — what a walk fetches
    beyond that (block rounding) is its own waste.  Queries in and
    outputs out once a layer."""
    kv, h, dh = dims["num_key_value_heads"], dims["num_attention_heads"], \
        dims["head_dim"]
    w = dims["sliding_window"]
    kinds = dims["layer_types"]
    n_full = sum(k == "full_attention" for k in kinds)
    n_win = len(kinds) - n_full
    total = 0.0
    for n in context_lens:
        toks = n_full * n + n_win * min(n, w)
        total += 2 * toks * kv * dh * kv_bytes \
            + len(kinds) * 2 * h * dh * act_bytes
    return total
