"""What a latent-attention expert model's two mechanisms NEED, from
shapes and from the program's own counters (``costs.py``'s rule: the
mathematics' requirement, not what a kernel fetches).  Each function
names its bound.

``dims`` is the configuration file's dict with the published key names.
"""

from __future__ import annotations

from typing import Iterable


def latent_values(dims: dict) -> int:
    """What a token leaves in the cache, a layer: the latent and the
    one rope key (576; the pool may pad the row to whole lanes — its
    own waste, like a walk's rounding)."""
    return dims["kv_lora_rank"] + dims["qk_rope_head_dim"]


def mla_decode_bytes(dims: dict, context_lens: Iterable[int],
                     kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the absorbed decode attention NEEDS for one token of each
    slot whose context (the new token included) is listed, through
    every layer (bound: bytes/s, narrowly: 121 FLOPs a byte against a
    v5e's ridge of 240).

    Per slot and layer: the live tokens' cached rows ONCE — every head
    reads the same row — and the absorbed queries in (heads x 576) and
    the latent outputs out (heads x 512)."""
    L, h = dims["num_hidden_layers"], dims["num_attention_heads"]
    row, c = latent_values(dims), dims["kv_lora_rank"]
    return float(sum(L * (n * row * kv_bytes + h * (row + c) * act_bytes)
                     for n in context_lens))


def mla_decode_flops(dims: dict, context_lens: Iterable[int]) -> float:
    """FLOPs of the same calls (bound: FLOP/s): each head dots its
    absorbed query with every live row (576 wide) and sums the rows'
    latents (512 wide) — ``2 x heads x (576 + 512)`` a live token and
    layer."""
    L, h = dims["num_hidden_layers"], dims["num_attention_heads"]
    per = 2.0 * h * (latent_values(dims) + dims["kv_lora_rank"])
    return float(sum(L * n * per for n in context_lens))


def expert_weight_bytes(dims: dict, weight_bytes: int = 2) -> int:
    """One routed expert's three matrices (gate, up, down)."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"] \
        * weight_bytes


def held_expert_bytes(dims: dict, experts_touched: float, rows: float,
                      weight_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the grouped products over the experts HELD here need
    (bound: bytes/s at a decode tick's 1-2 rows an expert): the weights
    of the held experts that were handed at least one row, ONCE each
    (``moe_experts_touched_total``: of the 12, never of the router's
    192), and each row's input and output."""
    return experts_touched * expert_weight_bytes(dims, weight_bytes) \
        + rows * 2 * dims["hidden_size"] * act_bytes


def held_expert_flops(dims: dict, rows: float) -> float:
    """FLOPs of the rows routed HERE (bound: FLOP/s): three products of
    ``hidden_size x moe_intermediate_size`` a row."""
    return rows * 3 * 2.0 * dims["hidden_size"] \
        * dims["moe_intermediate_size"]


def rows_here_share(dims: dict) -> float:
    """The share of the routed picks that uniform routing would land
    on this chip: held experts over the router's outputs."""
    return dims["n_routed_experts"] / dims["router_outputs"]
