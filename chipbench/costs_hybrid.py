"""What the mechanisms of a model whose every layer runs attention AND a
state-space mixer side by side NEED, from shapes and from the program's
own counters (``costs.py``'s rule: the mathematics' requirement, not what
a kernel fetches).  Each function names its bound.  ``dims`` is the
configuration file's dict with the published key names (``kind:
serve_hybrid``)."""

from __future__ import annotations

from typing import Iterable


def kv_bytes_per_token(dims: dict, kv_bytes: int = 2) -> int:
    """K and V of every KV head of every layer — every layer attends
    (18 432 B at 9 layers x 4 heads x 128 in bf16)."""
    return dims["num_hidden_layers"] * 2 * dims["num_key_value_heads"] \
        * dims["head_dim"] * kv_bytes


def paged_decode_bytes(dims: dict, context_lens: Iterable[int],
                       kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the paged decode attention NEEDS for one token of each slot
    whose context (the new token included) is listed (bound: bytes/s, as
    ``costs.paged_decode_bytes``): the ``n`` live tokens' K and V,
    ``kv_bytes_per_token`` each, and the queries in and outputs out once
    a layer.  That five query heads share a KV head (padded to eight
    rows in the kernel) changes nothing of the need."""
    per_slot = dims["num_hidden_layers"] * 2 * dims["num_attention_heads"] \
        * dims["head_dim"] * act_bytes
    per_tok = kv_bytes_per_token(dims, kv_bytes)
    return float(sum(n * per_tok + per_slot for n in context_lens))


def ssm_state_bytes_per_layer(dims: dict, state_bytes: int = 2) -> int:
    """One slot's matrix state of one layer: heads x head x d_state
    (2 097 152 B at 32 x 128 x 256 in bf16)."""
    return dims["mamba_n_heads"] * dims["mamba_d_head"] \
        * dims["mamba_d_state"] * state_bytes


def ssm_state_bytes_per_slot(dims: dict, state_bytes: int = 2) -> int:
    """What a slot holds of it whatever its context (18 874 368 B at 9
    layers)."""
    return dims["num_hidden_layers"] * ssm_state_bytes_per_layer(
        dims, state_bytes)


def conv_state_bytes_per_slot(dims: dict, state_bytes: int = 2) -> int:
    """... and of the convolution's taps: every layer's last
    ``mamba_d_conv - 1`` pre-activation ``[x | B | C]`` (276 480 B at 9 x
    3 x 5120 in bf16)."""
    width = dims["mamba_d_ssm"] + 2 * dims["mamba_n_groups"] \
        * dims["mamba_d_state"]
    return dims["num_hidden_layers"] * (dims["mamba_d_conv"] - 1) * width \
        * state_bytes


def ssm_update_bytes(dims: dict, updated: float,
                     state_bytes: int = 2) -> float:
    """Bytes the tick's state update NEEDS for ``updated`` (slot, layer)
    pairs — the program's ``ssm_updated_slots_total``, active rows x
    layers (bound: bytes/s): each pair's matrix state read once and
    written once.  The token's ``x``, ``B``, ``C``, ``dt`` and the
    outputs are a thousandth of that and are not counted."""
    return float(updated) * 2 * ssm_state_bytes_per_layer(dims, state_bytes)


def ssm_scan_flops_per_token(dims: dict) -> int:
    """FLOPs a token and layer of the chunked dual form at the published
    ``mamba_chunk_size`` Q, multiply-adds counted twice, the four
    products and nothing else: the scores ``C B^T`` a GROUP (``2 Q N``
    each — B and C are a group's, so a head's scores are its group's),
    the masked ``(Q, Q)`` by ``(Q, P)`` a head (``2 Q P``), a chunk's
    contribution to the state a head (``2 P N``) and the carried state's
    to the outputs a head (``2 P N``): 5 373 952 at Q 128, 2 groups, 32
    heads of 128, state 256 (7 340 032 if the scores were counted a
    head).  The full ``(Q, Q)`` is counted, not its causal half: a
    blocked product computes it."""
    q, n, p = dims["mamba_chunk_size"], dims["mamba_d_state"], \
        dims["mamba_d_head"]
    return (dims["mamba_n_groups"] * 2 * q * n
            + dims["mamba_n_heads"] * (2 * q * p + 4 * p * n))


def ssm_scan_flops(dims: dict, scanned: float) -> float:
    """FLOPs the scan NEEDS for ``scanned`` (token, layer) pairs — the
    program's ``ssm_scanned_tokens_total``, true prompt tokens x layers
    (bound: FLOP/s)."""
    return float(scanned) * ssm_scan_flops_per_token(dims)
