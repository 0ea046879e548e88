"""What the mechanisms of a model of linear-attention layers between
block-sparse attention layers NEED, from shapes and from the program's own
counters (``costs.py``'s rule: the mathematics' requirement, not what a
kernel fetches).  Each function names its bound.  ``dims`` is the
configuration file's dict with the published key names (``kind:
serve_linear_sparse``)."""

from __future__ import annotations


def _count(dims: dict, mixer: str) -> int:
    first = dims.get("first_layer", 0)
    run = dims["mixer_types"][first:first + dims["num_hidden_layers"]]
    return sum(m == mixer for m in run)


def kv_bytes_per_token(dims: dict, kv_bytes: int = 2) -> int:
    """K and V of every KV head of every ``minicpm4`` layer — the
    lightning layers keep none (3072 B at 3 layers x 2 heads x 128 in
    bf16)."""
    return _count(dims, "minicpm4") * 2 * dims["num_key_value_heads"] \
        * dims["head_dim"] * kv_bytes


def compressed_bytes_per_page(dims: dict, kv_bytes: int = 2) -> int:
    """... and a compressed key a KV head and ``minicpm4`` layer a page
    (1536 B at 3 x 2 x 128 in bf16)."""
    return _count(dims, "minicpm4") * dims["num_key_value_heads"] \
        * dims["head_dim"] * kv_bytes


def lin_state_bytes_per_layer(dims: dict) -> int:
    """One slot's matrix state of one lightning layer, FLOAT32: heads x
    head x head (2 097 152 B at 32 x 128 x 128)."""
    return dims["lightning_nh"] * dims["lightning_head_dim"] ** 2 * 4


def lin_state_bytes_per_slot(dims: dict) -> int:
    """What a slot holds of it whatever its context (18 874 368 B at 9
    lightning layers)."""
    return _count(dims, "lightning-attn") * lin_state_bytes_per_layer(dims)


def lin_update_bytes(dims: dict, updated: float) -> float:
    """Bytes the tick's state update NEEDS for ``updated`` (slot, layer)
    pairs — the program's ``lin_updated_slots_total``, active rows x
    lightning layers (bound: bytes/s): each pair's float32 state read
    once and written once.  The token's q, k, v and the outputs are a
    thousandth of that and are not counted."""
    return float(updated) * 2 * lin_state_bytes_per_layer(dims)


def lin_scan_flops_per_token(dims: dict, chunk: int = 128) -> int:
    """FLOPs a token and lightning layer of the chunked dual form at
    block ``chunk`` Q, multiply-adds counted twice, the four products a
    HEAD and nothing else (a head's keys are its own): the scores ``q
    k^T`` (``2 Q Dh``), the masked ``(Q, Q)`` by ``(Q, Dh)`` (``2 Q
    Dh``), a chunk's contribution to the state (``2 Dh Dh``) and the
    carried state's to the outputs (``2 Dh Dh``): 4 194 304 at Q 128, 32
    heads of 128.  The full ``(Q, Q)`` is counted, not its causal half:
    a blocked product computes it."""
    dh = dims["lightning_head_dim"]
    return dims["lightning_nh"] * (4 * chunk * dh + 4 * dh * dh)


def lin_scan_flops(dims: dict, scanned: float) -> float:
    """FLOPs the scan NEEDS for ``scanned`` (token, layer) pairs — the
    program's ``lin_scanned_tokens_total``, true prompt tokens x
    lightning layers (bound: FLOP/s)."""
    return float(scanned) * lin_scan_flops_per_token(dims)


def bsa_score_bytes(dims: dict, rows: float, kv_bytes: int = 2) -> float:
    """Bytes the compressed keys' scoring NEEDS for ``rows`` (slot, KV
    head, layer, window) rows — the program's ``bsa_scored_rows_total``
    (bound: bytes/s): each row's ``head_dim`` values read once, 256 B in
    bf16.  Its 16 query heads' dots are 4096 FLOPs a row, 16 a byte: far
    under the ridge."""
    return float(rows) * dims["head_dim"] * kv_bytes


def bsa_attend_bytes(dims: dict, attended: float, kv_bytes: int = 2) -> float:
    """Bytes the attention over the CHOSEN blocks NEEDS for ``attended``
    (slot, layer, token) triples — the program's
    ``bsa_attended_tokens_total``, what a KV head attends and not what
    the slot holds (bound: bytes/s): K and V of both KV heads, each its
    own choice of as many tokens, 1024 B a token and layer in bf16 (16
    FLOPs a byte: bytes/s binds)."""
    return float(attended) * 2 * dims["num_key_value_heads"] \
        * dims["head_dim"] * kv_bytes
