"""What learned sparse attention's two tick mechanisms NEED, from shapes
and from the program's own counters (``costs.py``'s rule: the
mathematics' requirement, not what a kernel fetches or a gather
copies).  The held experts' needs are ``costs_latent``'s.

``dims`` is the configuration file's dict with the published key names.
"""

from __future__ import annotations

from chipbench.costs_latent import (held_expert_bytes,  # noqa: F401
                                    held_expert_flops, latent_values,
                                    rows_here_share)


def index_score_bytes(dims: dict, scored_tokens: float,
                      kv_bytes: int = 2) -> float:
    """Bytes the index walk NEEDS (bound: bytes/s — 64 FLOPs a byte
    against a v5e's ridge of 240): each scored token's ONE index key, a
    layer.  ``scored_tokens`` counts a layer once
    (``dsa_scored_tokens_total``)."""
    return float(scored_tokens) * dims["num_hidden_layers"] \
        * dims["index_head_dim"] * kv_bytes


def index_score_flops(dims: dict, scored_tokens: float) -> float:
    """FLOPs of the same walk: every index head dots its query with the
    token's key — ``index_n_heads x index_head_dim x 2`` a token and
    layer (16 384 at the published sizes)."""
    return float(scored_tokens) * dims["num_hidden_layers"] \
        * 2.0 * dims["index_n_heads"] * dims["index_head_dim"]


def selected_attend_bytes(dims: dict, selected_tokens: float,
                          kv_bytes: int = 2) -> float:
    """Bytes the selected attend NEEDS: each selected token's latent row
    (576 values) ONCE a layer — every head reads the same row.
    ``selected_tokens``: ``dsa_selected_tokens_total``, a layer counted
    once."""
    return float(selected_tokens) * dims["num_hidden_layers"] \
        * latent_values(dims) * kv_bytes


def selected_attend_flops(dims: dict, selected_tokens: float) -> float:
    """FLOPs of the same: each head dots its absorbed query with the
    row (576) and sums the row's latent (512) — ``heads x 2 x 1088`` a
    selected token and layer; 242 FLOPs a byte at 128 heads: at the
    v5e's ridge."""
    return float(selected_tokens) * dims["num_hidden_layers"] * 2.0 \
        * dims["num_attention_heads"] \
        * (latent_values(dims) + dims["kv_lora_rank"])
