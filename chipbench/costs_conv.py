"""What the mechanisms of a model of gated short convolutions between
attention layers NEED, from shapes and from the program's own counters
(``costs.py``'s rule: the mathematics' requirement, not what a kernel
fetches).  Each function names its bound.  ``dims`` is the configuration
file's dict with the published key names (``kind: serve_conv``).

The expert product's needs are ``costs_patterned``'s (every expert is
held; rows and experts touched come from the program's counters)."""

from __future__ import annotations

from typing import Iterable

from chipbench.costs_patterned import (moe_expert_bytes,  # noqa: F401
                                       moe_expert_flops)


def attention_layers(dims: dict) -> int:
    return sum(k == "full_attention" for k in dims["layer_types"])


def kv_bytes_per_token(dims: dict, kv_bytes: int = 2) -> int:
    """K and V of every KV head of every ATTENTION layer — a conv layer
    leaves nothing that grows with the context (4 096 B at 2 attention
    layers x 8 heads x 64 in bf16)."""
    dh = dims["hidden_size"] // dims["num_attention_heads"]
    return attention_layers(dims) * 2 * dims["num_key_value_heads"] * dh \
        * kv_bytes


def paged_decode_bytes(dims: dict, context_lens: Iterable[int],
                       kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the paged decode attention NEEDS for one token of each slot
    whose context (the new token included) is listed, through the
    attention layers (bound: bytes/s, as ``costs.paged_decode_bytes``):
    the ``n`` live tokens' K and V, ``kv_bytes_per_token`` each, and the
    queries in and outputs out once an attention layer.  That two heads
    share a stored row changes nothing of the need."""
    h = dims["num_attention_heads"]
    dh = dims["hidden_size"] // h
    per_slot = attention_layers(dims) * 2 * h * dh * act_bytes
    per_tok = kv_bytes_per_token(dims, kv_bytes)
    return float(sum(n * per_tok + per_slot for n in context_lens))


def conv_state_bytes_per_slot(dims: dict, state_bytes: int = 2) -> int:
    """What a slot holds beside its pages: every conv layer's last
    ``conv_L_cache - 1`` gated inputs (65 536 B at 8 layers x 2 x 2048
    in bf16)."""
    n_conv = sum(k == "conv" for k in dims["layer_types"])
    return n_conv * (dims["conv_L_cache"] - 1) * dims["hidden_size"] \
        * state_bytes
