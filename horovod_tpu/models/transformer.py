"""Flagship model: decoder-only Transformer LM, written TPU-first in pure
JAX with explicit GSPMD sharding rules for dp / fsdp / tp / sp / pp / ep.

The reference framework carries no models of its own (its benchmarks import
tf.keras/torchvision models); this module is the flagship for OUR benchmark
and multi-parallelism story: pick a mesh (:mod:`horovod_tpu.parallel.
meshes`), annotate parameters and activations with the specs from
:func:`param_specs` / :func:`batch_specs`, jit, and XLA inserts all
collectives (psum for dp/fsdp grads, all-gathers for tp, collective-permute
for pp-sharded layer scan) over ICI.

Design notes (TPU):
* bfloat16 activations/compute, float32 parameters and softmax/logsumexp.
* Layers are stacked on a leading axis and scanned with ``lax.scan`` —
  constant compile time in depth; the stacked axis shards over ``pp``.
* RMSNorm + SwiGLU + rotary positions; causal mask built from iota (no
  materialized (S,S) python loop, static shapes throughout).
* Optional mixture-of-experts MLP (``n_experts > 1``): experts stacked on
  an axis sharded over ``ep``; top-1 routing computed densely (exact, and
  compiles to einsums the MXU likes at benchmark scales).
* Serving only: a ``layer_pattern`` of full and sliding-window layers
  (rope by kind, two kinds of KV state) and top-k dropless experts, in
  ``prefill`` / ``prefill_with_prefix`` / ``decode_step_paged``
  (:func:`_scan_layer_kinds`); the other bodies refuse such a
  configuration (:class:`UnsupportedModelConfigError`).
* Serving only: latent attention (``kv_lora_rank`` and its four sizes):
  the cache holds one 576-wide latent a token and layer instead of every
  head's K and V; whole prompts and a chunk's own block EXPAND it and
  run the flash forward (``hvd_flash_fwd``), a chunk's landed prefix is
  expanded in blocks (``hvd_mla_expand``), and a decode tick attends it
  ABSORBED through the ``hvd_mla_decode`` kernel, its projections under
  ``hvd_mla_q`` / ``hvd_mla_kv`` / ``hvd_mla_out``.  Beside it: leading
  dense layers in a stack of their own (``n_dense_layers``), a shared
  expert (``hvd_moe_shared``), sigmoid group-limited routing, and ONE
  CHIP'S SHARE of the routed experts (``n_experts_held``).
* Serving only: learned SPARSE attention over that cache
  (``index_topk`` and its two sizes): an indexer's key cached beside the
  latent row, every query attending its ``index_topk`` best-scored
  positions — an index walk (``hvd_dsa_score``), an exact sort-free
  selection (``hvd_dsa_select``) and an attend over the selected rows
  (``hvd_dsa_attend``) in the tick, in a chunk and in a whole prompt —
  and a router that chooses under a score-correction bias
  (``moe_score_bias``).
* Serving only: layers of TWO mixers (``layer_pattern=("hybrid",)``):
  attention and a state-space mixer (Mamba-2: ``ssm_heads`` and its
  sizes) side by side on one normed input, summed before the residual,
  under a width-transfer parametrisation's published multipliers; every
  layer keeps pages AND a per-slot state (the mixer's matrix a head and
  its convolution's taps).  The mixer's two bodies are
  :mod:`horovod_tpu.ops.ssm` (a tick's update in place,
  ``hvd_ssm_update``; a prompt's chunked scan, ``hvd_ssm_scan``), its
  projections, convolution and gate under ``hvd_ssm_in`` /
  ``hvd_ssm_conv`` / ``hvd_ssm_out``.
* Serving only: ``"linear"`` and ``"block_sparse"`` layers in one
  pattern: LINEAR ATTENTION with a fixed decay a head (a float32 matrix
  state a head and slot, no pages; the recurrence is
  :mod:`horovod_tpu.ops.ssm`'s at a group a head) and softmax attention
  that SELECTS BLOCKS by scores over compressed keys (``bsa_*``: pages
  of K and V and a third pool array of one row a page; a tick attends
  the chosen blocks through the fused paged kernel over a table a slot
  and KV head), mixer leaves stacked by kind, under muP scales.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P


#: The kinds of layer a ``layer_pattern`` may name.  What each one keeps
#: for a request, by the page pool's names, and its mixer are declared
#: ONCE, in :data:`LAYER_KINDS` (below the mixers it names).
PATTERN_KINDS = ("full", "sliding", "conv", "hybrid", "linear",
                 "block_sparse")


@dataclasses.dataclass(frozen=True, eq=False)
class LayerKind:
    """One kind of layer, as :data:`LAYER_KINDS` declares it.

    ``mixer(x, p, cfg, kind, reach) -> (h, new)``: what the layer adds
    to its input ``x`` before the MLP and what it leaves in the cache,
    written once; ``reach`` is how the calling body reaches the cached
    state (:class:`_Prompt`, :class:`_Chunk`, :class:`_Tick`).
    ``paged``: the pool arrays a page table indexes, ``{name: cfg ->
    (heads, width)}`` of ``(L, P, heads, page, width)``; ``scales``: an
    int8 pool's per-vector scales beside them, in their order;
    ``page_rows``: pool arrays of one row a page of a SLOT's table,
    ``{name: cfg -> (heads, width)}`` of ``(L, S, heads, max_pages,
    width)`` — a summary of the page's tokens that a landing and the
    tick write with them at the page's LOGICAL index, so that the tick
    reads a slot's rows as they lie; a page of such a kind has one
    owner (no prefix is shared), and a slot's row 0 is never read and
    takes what the NULL page takes of the pages;
    ``state``: what a SLOT holds whatever its context, ``{name: cfg ->
    shape}`` of ``(L, S) + shape``, in the pool's dtype unless named in
    ``f32`` (a state that every token multiplies on: kept in float32);
    ``window``: the pages lie under a table of their own and are
    released behind the window."""
    mixer: Any
    paged: Any = dataclasses.field(default_factory=dict)
    scales: tuple = ()
    state: Any = dataclasses.field(default_factory=dict)
    window: bool = False
    page_rows: Any = dataclasses.field(default_factory=dict)
    f32: tuple = ()

    @property
    def landed(self) -> tuple:
        """The arrays a chunk's landed prefix carries for a layer."""
        return (*self.paged, *self.state)

    @property
    def block(self) -> tuple:
        """The arrays a prefill hands back for a layer."""
        return (*self.paged, *self.page_rows, *self.state)

    @property
    def arrays(self) -> tuple:
        """... and those of a (quantized) pool, in a tick's order."""
        return (*self.paged, *self.scales, *self.page_rows, *self.state)


class UnsupportedModelConfigError(ValueError):
    """An entry point was handed a configuration it does not compute
    (a layer pattern, a window or more than one expert a token where
    only the uniform top-1 model is written): refused, never run as
    another model."""


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 1024
    n_experts: int = 0  # 0/1 = dense MLP
    # MoE dispatch: "switch" = sparse capacity-factor token dispatch
    # (horovod_tpu.ops.moe — each token computes ONE expert; under
    # shard_map with moe_axis bound, one all_to_all each way and only
    # RESIDENT experts compute, so the ep axis shards compute).  "dense"
    # = evaluate every expert and combine with the routing one-hot (the
    # exact oracle; dropless, O(E) FLOPs — right for tiny E and for
    # decoding).
    moe_impl: str = "switch"
    # Per-expert capacity multiplier for switch dispatch: each expert
    # accepts ceil(cf * T / E) tokens per step; overflow tokens pass
    # through the residual only (standard Switch training behavior).
    capacity_factor: float = 2.0
    # Switch dispatch mechanism: "sort" (argsort + gathers — the TPU
    # fast path) or "cumsum" (one-hot running-position oracle).  Both
    # produce identical outputs, gradients, and drop patterns.
    moe_dispatch: str = "sort"
    # Mesh axis for expert parallelism when running under shard_map
    # (None = single-device sparse dispatch; the GSPMD/jit path shards
    # the expert axis via param_specs instead).
    moe_axis: Optional[str] = None
    # Switch load-balancing auxiliary loss coefficient (Switch paper's
    # alpha, typically 1e-2).  When > 0, loss_fn adds
    # ``coeff * sum_over_layers(E * sum_e frac_e * pbar_e)`` so the
    # router is pushed toward uniform expert load — without it a learned
    # router under tight capacity route-collapses (all tokens -> one
    # expert, capacity drops eat the batch).  0 disables (the oracle /
    # equivalence-test setting).
    moe_aux_coeff: float = 0.0
    # Grouped-query attention: K/V heads (0 = n_heads, i.e. MHA).  With
    # ring attention the rotating K/V shards shrink by n_heads/n_kv_heads
    # — the long-context ICI-bandwidth lever (beyond-reference extension).
    n_kv_heads: int = 0
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # "reference" = O(S^2) XLA softmax-attention; "flash" = the Pallas
    # fused kernel (horovod_tpu.ops.attention); "ring" = sequence-parallel
    # ring attention over the ``sp`` mesh axis (requires running under
    # shard_map with sp bound and sequence sharded over it; chunks run the
    # flash kernel).  "ring_zigzag" = ring with the zigzag chunk layout
    # (device i holds global chunks (i, 2P-1-i)): balances the causal
    # work so no device idles — feed batches permuted by
    # ops.attention.zigzag_perm.  "ring_reference" keeps the masked-XLA
    # chunk math — the second oracle and the benchmarking control.
    attention_impl: str = "reference"
    # Rematerialize each layer in the backward pass (jax.checkpoint):
    # activations are recomputed instead of stored, trading ~1/3 more
    # FLOPs for O(n_layers) less HBM — the standard long-context /
    # big-batch lever on TPU where HBM, not MXU, binds.
    remat: bool = False
    # Remat granularity: "full" saves nothing and recomputes everything
    # (max memory savings); "dots" keeps what is dear to recompute — the
    # matmul outputs (jax checkpoint_dots policy) and the flash forward
    # kernel's output and log-sum-exp — and redoes only the cheap
    # elementwise work.  The kernel's two arrays cost one activation
    # (B, S, n_heads, d_head) + an f32 row (B, n_heads, S) a layer (ring
    # attention: a pair per chunk of the ring), ~10 % over the dot
    # outputs; without them the backward pass runs the whole forward
    # kernel a second time.
    remat_policy: str = "full"
    # Head size when it is a key of its own (0 = d_model / n_heads): the
    # q projection is then d_model -> n_heads * d_head.
    d_head: int = 0
    # RMSNorm epsilon, every norm of the model.
    norm_eps: float = 1e-6
    # RMSNorm with a learned scale over each head's q and k, before RoPE.
    qk_norm: bool = False
    # One PERIOD of layer kinds, each "full" or "sliding", repeated
    # n_layers / len times (() = every layer full).  A sliding layer
    # attends the last ``window`` positions only and takes the plain
    # rope; a full layer takes ``rope_yarn`` when it is set.
    layer_pattern: tuple = ()
    window: int = 0
    # YaRN on the full layers: (factor, original_max_position,
    # beta_fast, beta_slow, attention_factor), () = plain rope.
    rope_yarn: tuple = ()
    rope_theta_sliding: float = 0.0  # 0 = rope_theta
    # Experts a token (top-k routing; serving dispatches only), the
    # experts' width (0 = d_ff) and whether the k weights are
    # renormalised to sum to one.
    n_experts_per_tok: int = 1
    d_expert: int = 0
    norm_topk_prob: bool = False
    # Latent attention (MLA), set = all five > 0 (the published keys'
    # meanings): q goes down to ``q_lora_rank``, is normed, and up to
    # n_heads x (qk_nope_head_dim + qk_rope_head_dim); K/V go down to
    # ONE ``kv_lora_rank`` latent (normed) plus ONE ``qk_rope_head_dim``
    # rope key a token, shared by every head, and each head's
    # ``qk_nope_head_dim`` key and ``v_head_dim`` value are read up
    # from the latent.  The cache holds the latent and the rope key.
    # With ``rope_yarn`` a sixth entry, YaRN's ``mscale_all_dim``,
    # scales the softmax (:attr:`mla_scale`).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The first ``n_dense_layers`` of the ``n_layers`` take a dense MLP
    # of width ``d_ff`` (a stack of their own, ``params["dense_layers"]``)
    # where the rest take experts of width ``d_expert``.
    n_dense_layers: int = 0
    # Experts every token passes through, beside the routed ones: one
    # dense SwiGLU of width ``n_shared_experts * d_expert``.
    n_shared_experts: int = 0
    # The router's score ("softmax" | "sigmoid"), the factor its
    # weights are multiplied by, and group-limited selection (the
    # experts in ``n_group`` runs, ``topk_group`` of them kept:
    # ops.moe.route_topk).
    moe_score: str = "softmax"
    routed_scaling_factor: float = 1.0
    n_group: int = 0
    topk_group: int = 0
    # One chip's share of an expert-parallel layer: the stack holds the
    # experts ``expert_offset <= e < expert_offset + n_experts_held`` of
    # the router's ``n_experts`` (0 = every expert is held).
    n_experts_held: int = 0
    expert_offset: int = 0
    # The router CHOOSES on ``scores + router_bias`` (a learned
    # score-correction bias, one value an expert and layer: the
    # published ``topk_method: "noaux_tc"``) and weights by the raw
    # scores.
    moe_score_bias: bool = False
    # Learned sparse attention over a latent cache (set = all three > 0;
    # the published keys' meanings): an INDEXER of ``index_n_heads``
    # heads of ``index_head_dim`` scores every earlier token for each
    # query — ``I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])``, the index
    # key ``k[s]`` ONE cached vector a token and layer — and the query
    # attends its ``index_topk`` best-scored positions only (all of
    # them while it has no more).
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # A "conv" layer of the pattern: a gated SHORT CONVOLUTION in the
    # attention's place — ``[B, C, X] = n W_in`` (``conv_in`` (D, 3D)),
    # ``u = B * X``, ``v[t] = sum_j k[:, j] u[t - (K - 1) + j]`` with a
    # depthwise kernel ``conv_k`` (D, K) of ``conv_kernel`` = K taps,
    # ``(C * v) W_out`` — no keys or values; decoding keeps the last
    # ``K - 1`` gated inputs ``u`` a layer and request (``conv_taps``).
    conv_kernel: int = 0
    # The logits are read against the embedding (no ``head`` leaf).
    tie_embeddings: bool = False
    # K/V heads narrower than the TPU's 128 lanes stored side by side:
    # ``128 / head_dim`` KV heads share one 128-lane row of a page
    # (:attr:`kv_pack`), so a page pool of 64-wide heads holds no
    # padding and the fused paged kernel reads whole lane groups.
    kv_lane_dense: bool = False
    # ``norm_topk_prob`` divides by ``sum + norm_topk_eps``.
    norm_topk_eps: float = 0.0
    # A "hybrid" layer of the pattern: attention AND a STATE-SPACE mixer
    # (Mamba-2) side by side on ONE normed input ``n``, summed before
    # the residual — ``x + m_ao Attn(m_ai n) + m_so SSM(m_si n)``.  The
    # mixer has ``ssm_heads`` heads of ``ssm_head_dim`` (``ssm_inner`` =
    # their product), a state of ``ssm_state`` columns a head row
    # (``d_state``), ``ssm_groups`` groups that share ``B``/``C``, a
    # depthwise causal convolution of ``conv_kernel`` taps (with a bias)
    # over ``[x | B | C]`` (:attr:`ssm_conv_width`), a gated RMSNorm
    # over each group and the dual form's block ``ssm_chunk`` for
    # prompts: :func:`_ssm_in` .. :func:`_ssm_out`.  Decoding keeps the
    # ``(ssm_head_dim, ssm_state)`` matrix a head and the last
    # ``conv_kernel - 1`` pre-activation ``[x | B | C]`` a layer and
    # request.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    # The published multipliers of a model parametrised for width
    # transfer (1 / () = none): on the embedding's rows, on the logits,
    # on the attention's input, output and keys (before the rope), on
    # the state-space branch's input and output, on the five parts ``(z,
    # x, B, C, dt)`` of its projection's output, and on the MLP's gate
    # (before its activation) and output.
    embed_multiplier: float = 1.0
    head_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = ()
    mlp_multipliers: tuple = ()
    # A "linear" layer of the pattern: LINEAR ATTENTION (lightning
    # attention) in the softmax's place — ``q``, ``k`` normed a head
    # (``qk_norm``) and roped, a head keeps ONE matrix
    # ``S`` ``(head_dim, head_dim)`` a request in FLOAT32, ``S_t =
    # lambda_h S_{t-1} + k_t^T v_t`` with a fixed decay a head and layer
    # (the leaf ``lin_decay`` holds ``log lambda``), ``o_t = (q_t /
    # sqrt(head_dim)) S_t``; an RMSNorm over each head of ``o`` and a
    # sigmoid gate of the layer's input before ``W_o``: :func:`_lin_in`
    # .. :func:`_lin_out`, the recurrence :mod:`horovod_tpu.ops.ssm`'s
    # (``ssm_chunk`` its dual form's block).  ``n_heads`` heads of
    # ``head_dim``, a key and value head each; no keys or values kept.
    # A "block_sparse" layer of the pattern: softmax attention that
    # SELECTS BLOCKS of keys by scores over COMPRESSED keys (InfLLM-V2).
    # A compressed key a KV head is the mean of ``bsa_kernel`` keys, one
    # every ``bsa_stride`` (= a page; a window spans two); a query whose
    # context is longer than ``bsa_dense_len`` scores them — softmax over
    # the whole windows it sees, summed over the query heads of its KV
    # head, a block of ``bsa_block`` tokens the largest over the windows
    # that overlap it — and attends its first ``bsa_init_blocks`` blocks,
    # the ``bsa_window / bsa_block`` up to its own and the ``bsa_topk``
    # best-scored of the rest; a shorter context attends everything.
    # Such a layer ropes neither q nor k, and its output is gated by
    # ``sigmoid(n W_g)`` before ``W_o``.  Set = the first five > 0.
    bsa_kernel: int = 0
    bsa_stride: int = 0
    bsa_block: int = 0
    bsa_topk: int = 0
    bsa_window: int = 0
    bsa_init_blocks: int = 1
    bsa_dense_len: int = 0

    def __post_init__(self):
        mla = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
               self.qk_rope_head_dim, self.v_head_dim)
        if any(mla) and not all(mla):
            raise ValueError(
                "latent attention is its five sizes together (q_lora_rank, "
                "kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, "
                f"v_head_dim); got {mla}")
        dsa = (self.index_n_heads, self.index_head_dim, self.index_topk)
        if any(dsa) and not (all(dsa) and all(mla)
                             and self.index_head_dim
                             >= self.qk_rope_head_dim):
            raise ValueError(
                "sparse attention is an indexer's three sizes together "
                "(index_n_heads, index_head_dim >= qk_rope_head_dim, "
                f"index_topk) over latent attention; got {dsa} with {mla}")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_score {self.moe_score!r}; "
                             "expected 'softmax' or 'sigmoid'")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of "
                f"n_layers={self.n_layers}")
        if self.n_experts_held and not (
                0 <= self.expert_offset
                and self.expert_offset + self.n_experts_held
                <= self.n_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.n_experts_held} "
                f"are not among the router's {self.n_experts}")
        if self.latent and self.has_window:
            raise UnsupportedModelConfigError(
                "latent attention together with window layers is not "
                "written (one latent pool, every layer full)")
        if self.n_dense_layers and self.has_window:
            raise UnsupportedModelConfigError(
                "leading dense layers together with window layers are "
                "not written")
        bad = [k for k in self.layer_pattern if k not in PATTERN_KINDS]
        if bad:
            raise ValueError(f"unknown layer kind(s) {bad}; expected "
                             f"{PATTERN_KINDS}")
        if self.layer_pattern and (
                self.n_layers - self.n_dense_layers) % len(self.layer_pattern):
            raise ValueError(
                f"n_layers={self.n_layers} after {self.n_dense_layers} "
                "leading dense layers is not a whole number of periods of "
                f"{self.layer_pattern}")
        if self.has_window and self.window < 1:
            raise ValueError("a 'sliding' layer needs window >= 1")
        if self.has_conv and self.conv_kernel < 2:
            raise ValueError("a 'conv' layer needs conv_kernel >= 2 taps")
        if self.has_conv and (self.has_window or self.latent):
            raise UnsupportedModelConfigError(
                "conv layers together with window layers or latent "
                "attention are not written")
        if self.has_ssm and (set(self.layer_pattern) != {"hybrid"}
                             or self.latent or self.kv_lane_dense):
            raise UnsupportedModelConfigError(
                "a two-mixer ('hybrid') layer together with window, "
                "latent or conv layers, or KV heads sharing a stored "
                "row, is not written")
        if self.has_ssm and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0
                and self.ssm_state > 0 and self.conv_kernel >= 2
                and self.ssm_groups > 0
                and self.ssm_heads % self.ssm_groups == 0
                and len(self.ssm_multipliers) in (0, 5)):
            raise ValueError(
                "a 'hybrid' layer needs ssm_heads (a multiple of "
                "ssm_groups), ssm_head_dim, ssm_state, conv_kernel >= 2 "
                "and none or five ssm_multipliers")
        if len(self.mlp_multipliers) not in (0, 2):
            raise ValueError("mlp_multipliers is (gate, down) or ()")
        if self.has_bsa and not (
                self.bsa_stride > 0 and self.bsa_kernel == 2 * self.bsa_stride
                and self.bsa_block > 0
                and self.bsa_block % self.bsa_stride == 0
                and self.bsa_topk > 0 and self.bsa_window > 0
                and self.bsa_window % self.bsa_block == 0
                and self.bsa_init_blocks >= 0 and self.bsa_dense_len >= 0):
            raise ValueError(
                "a 'block_sparse' layer needs bsa_stride, bsa_kernel = 2 "
                "bsa_stride (a window ends one page after it starts), "
                "bsa_block a multiple of bsa_stride, bsa_topk and "
                "bsa_window a multiple of bsa_block")
        if (self.has_linear or self.has_bsa) and (
                set(self.layer_pattern) - {"linear", "block_sparse"}
                or self.latent or self.kv_lane_dense or self.n_dense_layers
                or self.n_experts > 1 or self.tie_embeddings):
            raise UnsupportedModelConfigError(
                "'linear' and 'block_sparse' layers together with full, "
                "window, conv or hybrid layers, latent attention, KV "
                "heads sharing a stored row, leading dense layers, "
                "experts or a tied head are not written")
        if self.kv_lane_dense and (
                self.latent or self.head_dim >= 128 or 128 % self.head_dim
                or self.kv_heads % (128 // self.head_dim)):
            raise ValueError(
                "kv_lane_dense packs 128 / head_dim whole KV heads into a "
                f"128-lane row; head_dim={self.head_dim} with "
                f"{self.kv_heads} KV heads (or a latent cache) does not")

    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def latent(self) -> bool:
        """Latent attention (MLA)?"""
        return self.kv_lora_rank > 0

    @property
    def sparse(self) -> bool:
        """Learned sparse attention (an indexer beside the latent)?"""
        return self.index_topk > 0

    @property
    def latent_width(self) -> int:
        """What a token leaves in a latent cache, a layer: the latent
        and the one rope key (576 values at the published sizes)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The cached row's width in storage: :attr:`latent_width`
        rounded up to whole 128-lane groups, zeros behind the rope key
        (640 for 576).  The TPU lays a 576-wide bf16 row out in 640
        lanes whatever the program says, and a kernel can slice HBM in
        whole lane groups only: a pool declared 576 wide would be
        copied into the 640-lane layout for every kernel call."""
        return -(-self.latent_width // 128) * 128

    @property
    def mla_scale(self) -> float:
        """Latent attention's softmax scale: ``(nope + rope)**-0.5``,
        times ``m**2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``
        where YaRN states an ``mscale_all_dim`` (``rope_yarn[5]``)."""
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if len(self.rope_yarn) > 5 and self.rope_yarn[5] \
                and self.rope_yarn[0] > 1:
            m = 0.1 * self.rope_yarn[5] * math.log(self.rope_yarn[0]) + 1.0
            s *= m * m
        return s

    @property
    def experts_held(self) -> int:
        """The experts whose weights are HERE (all, or a chip's share)."""
        return self.n_experts_held or self.n_experts

    @property
    def held_offset(self):
        """First held expert, or None when every expert is held: the
        dispatch then is the one written before a share was."""
        if self.experts_held == self.n_experts:
            return None
        return self.expert_offset

    @property
    def moe_routing(self) -> dict:
        """:func:`~horovod_tpu.ops.moe.route_topk`'s keywords beyond the
        softmax top-k; empty for it."""
        out = {}
        if self.moe_score != "softmax":
            out["score"] = self.moe_score
        if self.n_group > 1:
            out.update(n_group=self.n_group, topk_group=self.topk_group)
        if self.routed_scaling_factor != 1.0:
            out["scale"] = self.routed_scaling_factor
        if self.norm_topk_eps:
            out["norm_eps"] = self.norm_topk_eps
        return out

    @property
    def has_window(self) -> bool:
        """Does any layer attend a window (two kinds of KV state)?"""
        return "sliding" in self.layer_pattern

    @property
    def has_conv(self) -> bool:
        """Is any layer a gated short convolution (a per-request state
        of ``conv_taps`` inputs beside the KV cache)?"""
        return "conv" in self.layer_pattern

    @property
    def conv_taps(self) -> int:
        """Past gated inputs a conv layer keeps a request: ``K - 1``."""
        return self.conv_kernel - 1

    @property
    def has_ssm(self) -> bool:
        """Is any layer a hybrid one (a state-space mixer beside its
        attention: a matrix state and a convolution's taps a slot)?"""
        return "hybrid" in self.layer_pattern

    @property
    def has_linear(self) -> bool:
        """Is any layer linear attention (a float32 matrix state a head
        and slot, ``pool["lin"]``, and no pages)?"""
        return "linear" in self.layer_pattern

    @property
    def has_bsa(self) -> bool:
        """Does any layer select blocks by compressed keys (a row a
        page of a slot's table, ``pool["ck"]``, beside its pages)?"""
        return "block_sparse" in self.layer_pattern

    @property
    def bsa_blocks_max(self) -> int:
        """The most blocks a query of a block-sparse layer attends: the
        forced and the picked, or every block of a context that is not
        longer than ``bsa_dense_len``."""
        return max(self.bsa_init_blocks + self.bsa_topk
                   + self.bsa_window // self.bsa_block,
                   -(-self.bsa_dense_len // self.bsa_block))

    @property
    def has_state(self) -> bool:
        """Does a request keep a state of fixed size beside its pages
        (``pool["conv"]``, ``pool["ssm"]``, ``pool["lin"]``)?"""
        return self.has_conv or self.has_ssm or self.has_linear

    @property
    def ssm_inner(self) -> int:
        """The state-space mixer's width (``d_ssm``)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """What its short convolution runs over: ``[x | B | C]``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def conv_width(self) -> int:
        """The width of the taps ``pool["conv"]`` keeps: a conv layer's
        gated inputs are ``d_model`` wide, a hybrid layer's ``[x | B |
        C]`` :attr:`ssm_conv_width`."""
        return self.ssm_conv_width if self.has_ssm else self.d_model

    def kind(self, name: str) -> LayerKind:
        """The table's entry for a layer the pattern calls ``name``: a
        full layer of a latent model is a ``latent`` one, with an
        indexer a ``sparse`` one."""
        if name == "full" and self.latent:
            name = "sparse" if self.sparse else "latent"
        return LAYER_KINDS[name]

    @property
    def kinds(self) -> Dict[str, LayerKind]:
        """The entries of the kinds this configuration HAS, by the
        pattern's names, in the table's order (never a set's: the
        order of a program's equations hangs on it)."""
        return {k: self.kind(k) for k in PATTERN_KINDS
                if k in self.layer_kinds}

    def layers_with(self, array: str) -> int:
        """How many layers keep the pool array ``array`` for a request
        (:data:`LAYER_KINDS`): by what a kind carries, not its name."""
        return sum(array in self.kind(k).arrays for k in self.layer_kinds)

    @property
    def layer_kinds(self) -> tuple:
        """Every layer's kind: the pattern repeated from layer 0 on,
        through the leading dense layers and the rest alike."""
        period = self.layer_pattern or ("full",)
        return tuple(period[l % len(period)] for l in range(self.n_layers))

    def kind_count(self, kind: str) -> int:
        """How many of the layers are of one kind."""
        return self.layer_kinds.count(kind)

    @property
    def kv_pack(self) -> int:
        """KV heads that share one stored row (1 = a row a head)."""
        return 128 // self.head_dim if self.kv_lane_dense else 1

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads or self.n_heads
        assert self.n_heads % kv == 0, (self.n_heads, kv)
        return kv


# --- parameters --------------------------------------------------------------


def init_params(rng, cfg: TransformerConfig) -> Dict:
    """Seeded parameters as a checkpoint lays them out: ``embed``,
    ``head`` (unless tied), ``ln_f`` and ``layers`` stacked on a
    leading axis.  With ``cfg.n_dense_layers`` the leading dense layers
    are a stack of their own, ``dense_layers``, and ``layers`` holds
    the rest.  In a stack with conv or linear layers the mixer's leaves
    are stacked BY KIND (:data:`_MIXER_LEAVES`): ``wq``/``wk``/``wv``/
    ``wo`` (the q/k norms, a block-sparse layer's gate ``wg (D, H
    Dh)``) over its attention layers, ``conv_in (D, 3D)``/``conv_k (D,
    K)``/``conv_out (D, D)`` over its conv layers, ``lin_q``/``lin_k``/
    ``lin_v``/``lin_g (D, H Dh)``, ``lin_o (H Dh, D)``, ``lin_norm``/
    ``lin_q_norm``/``lin_k_norm (Dh)`` and ``lin_decay (H)`` = ``log
    lambda`` over its linear layers; every other leaf over all of
    them."""
    keys = jax.random.split(rng, 10)
    D, V = cfg.d_model, cfg.vocab_size

    def norm_init(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(jnp.float32)

    def stack(keys, kinds, experts: bool):
        # ``kinds``: this stack's layers' kinds.  Where some are conv
        # layers the MIXER's leaves are stacked by kind — attention's
        # over the attention layers (``La`` of them), ``conv_*`` over
        # the conv layers — and every other leaf over all ``L``.
        L = len(kinds)
        Lc, Ll = kinds.count("conv"), kinds.count("linear")
        La = L - Lc - Ll
        H, Dh = cfg.n_heads, cfg.head_dim
        F = cfg.expert_width if experts else cfg.d_ff
        s_d, s_f = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)
        layers = {"ln1": jnp.ones((L, D), jnp.float32),
                  "ln2": jnp.ones((L, D), jnp.float32)}
        if cfg.has_ssm:     # beside the attention's leaves, every layer
            sk = jax.random.split(jax.random.fold_in(keys[0], 13), 5)
            Hs, I, C = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width
            # Mamba-2's published initialisation: A uniform in 1-16, dt
            # log-uniform in 1e-3..1e-1 through the inverse softplus
            dt0 = jnp.exp(jax.random.uniform(sk[3], (L, Hs)) * (
                math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
            layers.update(
                ssm_in=norm_init(sk[0], (L, D, I + C + Hs), s_d),
                ssm_conv_k=norm_init(sk[1], (L, C, cfg.conv_kernel),
                                     1.0 / np.sqrt(cfg.conv_kernel)),
                ssm_conv_b=jnp.zeros((L, C), jnp.float32),
                ssm_dt_bias=(dt0 + jnp.log(-jnp.expm1(-dt0))).astype(
                    jnp.float32),
                ssm_A_log=jnp.log(jax.random.uniform(
                    sk[4], (L, Hs), minval=1.0, maxval=16.0)).astype(
                        jnp.float32),
                ssm_D=jnp.ones((L, Hs), jnp.float32),
                ssm_norm=jnp.ones((L, I), jnp.float32),
                ssm_out=norm_init(sk[2], (L, I, D), 1.0 / np.sqrt(I)))
        if Lc:
            ck = jax.random.split(jax.random.fold_in(keys[0], 11), 3)
            layers.update(
                conv_in=norm_init(ck[0], (Lc, D, 3 * D), s_d),
                conv_k=norm_init(ck[1], (Lc, D, cfg.conv_kernel),
                                 1.0 / np.sqrt(cfg.conv_kernel)),
                conv_out=norm_init(ck[2], (Lc, D, D), s_d))
        if Ll:
            # a linear layer's leaves, as the products read them; log
            # lambda drawn a layer and head (a model's weights bring
            # their own: no schedule is the program's)
            lk = jax.random.split(jax.random.fold_in(keys[0], 17), 6)
            layers.update(
                lin_q=norm_init(lk[0], (Ll, D, H * Dh), s_d),
                lin_k=norm_init(lk[1], (Ll, D, H * Dh), s_d),
                lin_v=norm_init(lk[2], (Ll, D, H * Dh), s_d),
                lin_g=norm_init(lk[3], (Ll, D, H * Dh), s_d),
                lin_o=norm_init(lk[4], (Ll, H * Dh, D), 1.0 / np.sqrt(H * Dh)),
                lin_norm=jnp.ones((Ll, Dh), jnp.float32),
                lin_decay=-jax.random.uniform(lk[5], (Ll, H), jnp.float32,
                                              1e-3, 0.5))
            if cfg.qk_norm:
                layers.update(lin_q_norm=jnp.ones((Ll, Dh), jnp.float32),
                              lin_k_norm=jnp.ones((Ll, Dh), jnp.float32))
        if cfg.latent:      # (never beside conv layers: __post_init__)
            ak = jax.random.split(keys[0], 4)
            R, C = cfg.q_lora_rank, cfg.kv_lora_rank
            N, Rp, Vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
            layers.update(
                wq_a=norm_init(ak[0], (L, D, R), s_d),
                q_a_norm=jnp.ones((L, R), jnp.float32),
                wq_b=norm_init(ak[1], (L, R, H, N + Rp), 1.0 / np.sqrt(R)),
                wkv_a=norm_init(ak[2], (L, D, C + Rp), s_d),
                kv_a_norm=jnp.ones((L, C), jnp.float32),
                wkv_b=norm_init(ak[3], (L, C, H, N + Vd), 1.0 / np.sqrt(C)),
                wo=norm_init(keys[3], (L, H, Vd, D), 1.0 / np.sqrt(H * Vd)))
            if cfg.sparse:
                ik = jax.random.split(keys[1], 3)
                Hi, Di = cfg.index_n_heads, cfg.index_head_dim
                layers.update(
                    wi_q=norm_init(ik[0], (L, R, Hi, Di), 1.0 / np.sqrt(R)),
                    wi_k=norm_init(ik[1], (L, D, Di), s_d),
                    i_k_norm=jnp.ones((L, Di), jnp.float32),
                    i_k_bias=jnp.zeros((L, Di), jnp.float32),
                    wi_w=norm_init(ik[2], (L, D, Hi), s_d))
        elif La:
            layers.update(
                wq=norm_init(keys[0], (La, D, H, Dh), s_d),
                wk=norm_init(keys[1], (La, D, cfg.kv_heads, Dh), s_d),
                wv=norm_init(keys[2], (La, D, cfg.kv_heads, Dh), s_d),
                wo=norm_init(keys[3], (La, H, Dh, D), 1.0 / np.sqrt(H * Dh)))
        if cfg.qk_norm and La:
            layers.update(q_norm=jnp.ones((La, Dh), jnp.float32),
                          k_norm=jnp.ones((La, Dh), jnp.float32))
        if cfg.has_bsa:
            layers["wg"] = norm_init(jax.random.fold_in(keys[3], 19),
                                     (La, D, H * Dh), s_d)
        if experts:
            E = cfg.experts_held
            layers.update(
                router=norm_init(keys[4], (L, D, cfg.n_experts), s_d),
                w_gate=norm_init(keys[5], (L, E, D, F), s_d),
                w_up=norm_init(keys[6], (L, E, D, F), s_d),
                w_down=norm_init(keys[7], (L, E, F, D), s_f),
            )
            if cfg.moe_score_bias:
                # not zeros: a zero bias would choose as no bias does
                layers["router_bias"] = norm_init(
                    jax.random.fold_in(keys[4], 7), (L, cfg.n_experts), 0.05)
            if cfg.n_shared_experts:
                Fs = cfg.n_shared_experts * F
                sk = jax.random.split(keys[4], 4)[1:]
                layers.update(
                    ws_gate=norm_init(sk[0], (L, D, Fs), s_d),
                    ws_up=norm_init(sk[1], (L, D, Fs), s_d),
                    ws_down=norm_init(sk[2], (L, Fs, D), 1.0 / np.sqrt(Fs)))
        else:
            layers.update(
                w_gate=norm_init(keys[5], (L, D, F), s_d),
                w_up=norm_init(keys[6], (L, D, F), s_d),
                w_down=norm_init(keys[7], (L, F, D), s_f),
            )
        return layers

    nd = cfg.n_dense_layers
    params = {
        "embed": norm_init(keys[8], (V, D), 1.0),
        "layers": stack(keys, cfg.layer_kinds[nd:], cfg.n_experts > 1),
        "ln_f": jnp.ones((D,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = norm_init(keys[9], (D, V), 1.0 / np.sqrt(D))
    if nd:
        params["dense_layers"] = stack(
            jax.random.split(jax.random.fold_in(rng, 1), 10),
            cfg.layer_kinds[:nd], False)
    return params


def param_specs(cfg: TransformerConfig) -> Dict:
    """GSPMD sharding rules.  Axes: tp shards heads/ffn/vocab, fsdp shards
    the d_model dim of weights (ZeRO-3 style), pp shards the stacked layer
    axis, ep shards experts."""
    def stack(experts: bool):
        layers = {"ln1": P("pp", None), "ln2": P("pp", None)}
        if cfg.latent:
            # the down-projections and their norms are every head's:
            # replicated; the up-projections split by head
            layers.update(
                wq_a=P("pp", "fsdp", None), q_a_norm=P("pp", None),
                wq_b=P("pp", None, "tp", None),
                wkv_a=P("pp", "fsdp", None), kv_a_norm=P("pp", None),
                wkv_b=P("pp", None, "tp", None),
                wo=P("pp", "tp", None, "fsdp"))
            if cfg.sparse:     # the indexer is every head's: replicated
                layers.update(
                    wi_q=P("pp", None, None, None),
                    wi_k=P("pp", "fsdp", None), i_k_norm=P("pp", None),
                    i_k_bias=P("pp", None), wi_w=P("pp", "fsdp", None))
        else:
            layers.update(
                wq=P("pp", "fsdp", "tp", None),
                wk=P("pp", "fsdp", "tp", None),
                wv=P("pp", "fsdp", "tp", None),
                wo=P("pp", "tp", None, "fsdp"))
        if cfg.qk_norm:
            layers.update(q_norm=P("pp", None), k_norm=P("pp", None))
        if experts:
            layers.update(
                router=P("pp", None, None),
                w_gate=P("pp", "ep", "fsdp", "tp"),
                w_up=P("pp", "ep", "fsdp", "tp"),
                w_down=P("pp", "ep", "tp", "fsdp"),
            )
            if cfg.moe_score_bias:
                layers["router_bias"] = P("pp", None)
            if cfg.n_shared_experts:
                layers.update(ws_gate=P("pp", "fsdp", "tp"),
                              ws_up=P("pp", "fsdp", "tp"),
                              ws_down=P("pp", "tp", "fsdp"))
        else:
            layers.update(
                w_gate=P("pp", "fsdp", "tp"),
                w_up=P("pp", "fsdp", "tp"),
                w_down=P("pp", "tp", "fsdp"),
            )
        return layers

    specs = {
        "embed": P("tp", "fsdp"),
        "layers": stack(cfg.n_experts > 1),
        "ln_f": P(None),
        "head": P("fsdp", "tp"),
    }
    if cfg.n_dense_layers:
        specs["dense_layers"] = stack(False)
    return specs


_PROJ_LEAVES = ("wq", "wk", "wv")


def lay_out_projections(params: Dict):
    """``(tree, bytes)``: ``params`` with the standard attention block's
    ``wq``/``wk``/``wv`` laid out ``(L, D, H * Dh)`` as the product over
    ``D`` reads them, and the bytes of the leaves so laid out (0: a
    latent block has none).  A checkpoint stores ``(L, D, H, Dh)``,
    whose tiles on a TPU lie over ``(H, Dh)``: a program that scans such
    a stack copies every layer's three leaves into the other layout
    before it can multiply, each time it runs.  An engine does it ONCE,
    here, and :func:`_qkv_proj` multiplies by a leaf as it finds it.
    A reshape, exact; the caller's tree is not touched and every other
    leaf is shared with it."""
    out, laid = dict(params), 0
    for name in ("layers", "dense_layers"):
        stack = params.get(name)
        if stack is None:
            continue
        out[name] = stack = dict(stack)
        for leaf in _PROJ_LEAVES:
            w = stack.get(leaf)
            if w is not None and w.ndim == 4:
                stack[leaf] = w.reshape(*w.shape[:2], -1)
                laid += w.nbytes
    return out, laid


def batch_specs() -> Dict:
    """Activations: batch over dp(+fsdp), sequence over sp."""
    return {"tokens": P(("dp", "fsdp"), "sp"), "targets": P(("dp", "fsdp"), "sp")}


# --- forward -----------------------------------------------------------------


#: The flat vocabulary of device scopes (``jax.named_scope``) the compiled
#: bodies here, in ``serving/cache.py`` and in ``optim.py`` are cut into.
#: A scope is trace-time metadata: it becomes a component of every
#: operation's ``op_name`` (backward operations carry it inside
#: ``transpose(jvp(<scope>))``), which a profiler trace reports per
#: device operation — so device time is attributed to a phase of the
#: program by name.  The Pallas kernels carry names of their own
#: (``pl.pallas_call(name=)``: ``hvd_paged_attend``, ``hvd_mla_decode``,
#: ``hvd_flash_fwd``, ``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``,
#: ``hvd_moe_experts``), and so do the scopes that cut a mechanism out of
#: one of these (an operation reads as its INNERMOST name):
#: ``hvd_moe_route`` / ``hvd_moe_shared`` inside ``mlp``, and latent
#: attention's ``hvd_mla_q`` (q down, norm, up, rope, and a tick's
#: absorption of W_k), ``hvd_mla_kv`` (kv down, norm, rope),
#: ``hvd_mla_expand`` (latents up through W_kv into heads: a prompt's
#: own, and a chunk's landed prefix) and ``hvd_mla_out`` (a tick's W_v,
#: and W_o); and sparse attention's ``hvd_dsa_proj`` (the indexer's three
#: projections), ``hvd_dsa_score`` (the index walk — the kernel's name
#: too — and a chunk's scores), ``hvd_dsa_select`` and ``hvd_dsa_attend``
#: (the selected rows' gather and the kernel of that name over them);
#: linear attention's ``hvd_lin_in`` (projections, q/k norms, rope) and
#: ``hvd_lin_out`` (the output norm, the gate, W_o) around the
#: recurrence's ``hvd_ssm_scan`` / ``hvd_ssm_update``; and block-sparse
#: attention's ``hvd_bsa_score`` (the compressed rows' scores pooled into
#: blocks), ``hvd_bsa_select`` and ``hvd_bsa_attend`` (the chosen
#: blocks' pages compacted into a table a slot and KV head, and the
#: paged kernel over it).
DEVICE_SCOPES = (
    "embed",          # token-embedding lookup
    "layer_scan",     # the scan over layers' own slicing and stacking
    "attn_qkv",       # pre-attention norm, q/k/v projections, RoPE
    "kv_write",       # a decode tick's K/V into the cache or page pool
    "paged_attend",   # decode attention over pages (kernel or gather)
    "attn",           # whole-sequence attention (training, prefill)
    "landed_gather",  # a slot's landed pages read back as a prefix block
    "chunk_attn",     # a prompt chunk attending prefix + itself
    "kv_land",        # prefilled K/V landing in the cache or page pool
    "attn_out",       # attention output projection
    "mlp",            # pre-MLP norm, the MLP (or MoE), its residual
    "head",           # final norm + vocabulary projection
    "sample",         # next-token pick from the logits
    "loss",           # cross-entropy
    "grad_allreduce",  # optim.py: the gradient collectives
    "opt_update",     # optim.py: the inner optimizer update
)


def _rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale).astype(x.dtype)


def yarn_inv_freq(head_dim: int, theta: float, yarn: tuple):
    """YaRN's per-pair inverse frequencies ``(head_dim / 2,)`` float32
    for ``yarn = (factor, original_max_position, beta_fast, beta_slow,
    attention_factor)``: pair ``i`` keeps the plain ``theta**(-2i/d)``
    below the ramp (``i <= low``: wavelengths the original context
    turned ``beta_fast`` times or more), is divided by ``factor`` above
    it (``i >= high``), and is blended linearly between."""
    factor, orig, beta_fast, beta_slow = yarn[:4]
    d = head_dim

    def c(r):  # the pair whose wavelength turns r times in ``orig``
        return d * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), d - 1)
    if high == low:
        high += 0.001  # the published code's guard against 0 / 0
    i = jnp.arange(0, d // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (ramp / factor + (1.0 - ramp)) / (theta ** (i / (d // 2)))


def _rope(q, k, theta: float, pos_offset=0, positions=None, yarn=()):
    """Rotary position embedding over the head dim (applied to q and k).
    Shapes: (B, S, H, Dh).  ``pos_offset`` shifts positions when the
    sequence axis is sharded (ring attention: shard r starts at
    r*S_local); ``positions`` overrides with EXPLICIT global positions —
    ``(S,)`` per sequence row (zigzag layout: this shard's rows are
    non-contiguous) or ``(B, S)`` per BATCH row (continuous-batching
    decode: every cache slot sits at a different depth).  ``yarn``
    (:func:`yarn_inv_freq`) rescales the frequencies and multiplies cos
    and sin by its attention factor."""
    B, S, H, Dh = q.shape
    half = Dh // 2
    if yarn:
        freqs = yarn_inv_freq(Dh, theta, yarn)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    pos = (positions.astype(jnp.float32) if positions is not None
           else pos_offset + jnp.arange(S, dtype=jnp.float32))
    ang = pos[..., None] * freqs  # (S, half) or (B, S, half)
    if ang.ndim == 2:
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]  # (1 | B, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn:
        cos, sin = cos * yarn[4], sin * yarn[4]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _scan_layers(layer, init, xs):
    """``lax.scan`` over the stacked layers, under the ``layer_scan``
    scope: the scan's OWN operations — slicing each layer's parameters
    out of the stacked arrays, stacking its results — carry no other
    scope, so a profile tells them from the layer's.  A paged KV pool is
    never among ``xs`` or the results: it rides the carry and is written
    in place (:func:`decode_step_paged`)."""
    with jax.named_scope("layer_scan"):
        return lax.scan(layer, init, xs)


#: The bodies that compute EVERY kind of the table (:data:`LAYER_KINDS`);
#: every other body refuses what it does not, through the two below.
_KIND_BODIES = ("prefill", "prefill_with_prefix", "decode_step_paged")


def _require_uniform(cfg: TransformerConfig, what: str) -> None:
    """Refuse a configuration with more than one kind of layer where
    only the uniform block is written (training, the single-request
    and speculative decode bodies, the pipeline schedules)."""
    if cfg.has_window or cfg.has_state:
        raise UnsupportedModelConfigError(
            f"{what} computes one kind of layer; this configuration's "
            f"pattern {cfg.layer_pattern} (window {cfg.window}) is served "
            f"by {', '.join(_KIND_BODIES)} only")


def _require_no_latent(cfg: TransformerConfig, what: str) -> None:
    """Refuse latent attention, leading dense layers and a chip's share
    of the experts where they are not written (the speculative verify,
    the pipeline schedules, training's backward)."""
    for on, name in ((cfg.latent, "latent attention"),
                     (cfg.n_dense_layers, "leading dense layers"),
                     (cfg.tie_embeddings, "a head tied to the embedding"),
                     (cfg.kv_lane_dense, "KV heads sharing a stored row"),
                     (cfg.held_offset is not None,
                      "a share of the experts (n_experts_held)")):
        if on:
            raise UnsupportedModelConfigError(
                f"{what} is not written for {name}: "
                + ", ".join(("forward", "decode_step") + _KIND_BODIES)
                + " compute it")


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")

#: A layer's MIXER leaves by its kind.  In a stack that has conv or
#: linear layers (and there alone) these are stacked over THAT kind's
#: layers; every other leaf is stacked over all the layers.
_ATTN_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_MIXER_LEAVES = {"full": _ATTN_LEAVES, "sliding": _ATTN_LEAVES,
                 "conv": ("conv_in", "conv_k", "conv_out"),
                 "block_sparse": _ATTN_LEAVES + ("wg",),
                 "linear": ("lin_q", "lin_k", "lin_v", "lin_g", "lin_o",
                            "lin_norm", "lin_decay", "lin_q_norm",
                            "lin_k_norm")}
#: (A hybrid layer's two mixers' leaves — the attention's beside
#: ``ssm_in``, ``ssm_conv_k``/``ssm_conv_b``, ``ssm_dt_bias``,
#: ``ssm_A_log``, ``ssm_D``, ``ssm_norm``, ``ssm_out`` — are stacked
#: over all the layers: a stack of them is uniform.)


def _scan_layer_kinds(cfg: TransformerConfig, layer, init, layers, xs=None,
                      dense=None):
    """:func:`_scan_layers` for a stack with more than one kind of
    layer.  ``layer(carry, p, kind, xs_l) -> (carry, ys_l)`` is told its
    layer's kind as a Python string; ``xs`` maps a kind to a pytree
    stacked over THAT kind's layers (a landed prefix of its own length;
    for what rides the carry, such as a paged pool, the layer's index
    among its kind: ``jnp.arange``), and the result maps each kind to
    its layers' stacked ``ys``.

    A uniform stack is one plain scan over the layers.  A patterned one
    scans over PERIODS: the body applies the period's layers in order,
    each with its static kind, so the two kinds may differ in mask,
    rope and the shape of what they carry, and the program still holds
    one copy of each kind's layer whatever the depth.  A layer's
    parameters are cut out of the stack one layer at a time (as a plain
    scan cuts them), never a period at a time.

    An expert model's three expert matrices are NOT cut out at all:
    ``p["expert_stack"]`` hands the layer every layer's, stacked, with
    the layer's (traced) index — the grouped product reads its experts
    in place (:func:`~horovod_tpu.ops.moe.grouped_matmul`).

    ``dense``: the stack of the configuration's LEADING dense layers
    (``params["dense_layers"]``), run before ``layers``; ``xs`` is then
    stacked over both, in order.  The pattern runs from layer 0
    through both stacks (:attr:`TransformerConfig.layer_kinds`).

    In a stack with conv or linear layers a layer's MIXER leaves
    (:data:`_MIXER_LEAVES`) are stacked over its kind's layers alone,
    and cut out at the layer's index among its kind."""
    xs = xs or {}
    if dense is not None:
        # the leading dense stack, then the rest, ONE layer index
        # running through both: what is stacked over a kind's layers
        # (a landed prefix, the pool's layer indices) is cut where the
        # stacks meet, and the results are joined there
        nd = cfg.n_dense_layers
        kinds = cfg.layer_kinds
        # the pattern as each stack meets it: the dense layers' kinds
        # as one period, the rest's period rotated to start at ``nd``
        head, tail = (), ()
        if cfg.layer_pattern:
            head, tail = kinds[:nd], kinds[nd:nd + len(cfg.layer_pattern)]

        def cut(front: bool):
            # a kind's xs, stacked over its layers of BOTH stacks
            def part(k, x):
                led = kinds[:nd].count(k)
                return x[:led] if front else x[led:]

            return {k: jax.tree_util.tree_map(
                functools.partial(part, k), v) for k, v in xs.items()}

        carry, ys_d = _scan_layer_kinds(
            dataclasses.replace(cfg, n_layers=nd, n_dense_layers=0,
                                n_experts=0, n_experts_held=0,
                                layer_pattern=head),
            layer, init, dense, cut(True))
        carry, ys_e = _scan_layer_kinds(
            dataclasses.replace(cfg, n_layers=cfg.n_layers - nd,
                                n_dense_layers=0, layer_pattern=tail),
            layer, carry, layers, cut(False))
        return carry, {k: ys_e[k] if ys_d.get(k) is None else
                       ys_d[k] if ys_e.get(k) is None else
                       jax.tree_util.tree_map(
                           lambda a, b: jnp.concatenate([a, b]),
                           ys_d[k], ys_e[k]) for k in {**ys_d, **ys_e}}
    stack = None
    if cfg.n_experts > 1:
        stack = {k: layers[k] for k in _EXPERT_LEAVES}
        layers = {k: v for k, v in layers.items() if k not in stack}

    def with_stack(p, l):
        return p if stack is None else {**p, "expert_stack": (stack, l)}

    period = cfg.layer_pattern
    if len(set(period)) <= 1:
        kind = period[0] if period else "full"
        if stack is None:
            carry, ys = _scan_layers(
                lambda c, inp: layer(c, inp[0], kind, inp[1]), init,
                (layers, xs.get(kind)))
        else:
            carry, ys = _scan_layers(
                lambda c, inp: layer(c, with_stack(inp[0], inp[2]), kind,
                                     inp[1]), init,
                (layers, xs.get(kind),
                 jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        return carry, {kind: ys}
    n = cfg.n_layers // len(period)
    # (in the pattern's own order: a set's would move the equations, and
    # with them the compile cache's key, from one process to the next)
    count = {k: period.count(k) for k in dict.fromkeys(period)}
    # with conv layers, each kind's mixer leaves are a stack of its own
    own = {k: {} for k in count}
    if cfg.has_conv or cfg.has_linear:
        own = {k: {m: layers[m] for m in _MIXER_LEAVES[k] if m in layers}
               for k in count}
        layers = {m: v for m, v in layers.items()
                  if not any(m in o for o in own.values())}

    def at(tree, l):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            tree)

    def body(carry, q):                    # q: the period's index
        ys = {k: [] for k in count}
        for i, kind in enumerate(period):
            l = q * len(period) + i
            l_kind = q * count[kind] + len(ys[kind])
            xs_l = at(xs.get(kind), l_kind)
            p = at(layers, l)
            if own[kind]:
                p = {**p, **at(own[kind], l_kind)}
            carry, y = layer(carry, with_stack(p, l), kind, xs_l)
            ys[kind].append(y)
        return carry, {k: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *v) for k, v in ys.items()}

    carry, ys = _scan_layers(body, init, jnp.arange(n, dtype=jnp.int32))
    return carry, {k: jax.tree_util.tree_map(
        lambda a: a.reshape(n * count[k], *a.shape[2:]), v)
        for k, v in ys.items()}


def _embed(params, tokens, cfg: TransformerConfig):
    with jax.named_scope("embed"):
        e = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.embed_multiplier != 1.0:
            e = e * jnp.asarray(cfg.embed_multiplier, e.dtype)
        return e


def _attn_norm(x, p, cfg: TransformerConfig):
    """The pre-attention norm, under the scope of the projections it
    feeds."""
    with jax.named_scope("attn_qkv"):
        return _rmsnorm(x, p["ln1"], cfg.norm_eps)


def _qkv_proj(x, p, cfg: TransformerConfig, pos_offset=0, positions=None,
              kind: str = "full"):
    """Project to per-head Q/K/V with RoPE applied -> head-major
    ``(B, H, S, Dh)`` / ``(B, H_kv, S, Dh)`` (shared by the training
    attention, prefill, and decode paths so the math cannot drift).
    ``kind`` is the layer's: a full layer takes ``cfg.rope_yarn``, a
    sliding one the plain rope (at ``rope_theta_sliding`` if set), a
    block-sparse one none."""
    def heads(w):
        # a leaf as a checkpoint stores it, (D, H, Dh), or as an engine
        # holds it, (D, H * Dh) (lay_out_projections): the same
        # contraction over D.  There the small product is cut into
        # heads, not the weight — behind a barrier, or XLA:TPU moves
        # the reshape back onto the weight (a product by (H, Dh, D))
        # and copies the layer's leaf into that layout, every time
        w = w.astype(cfg.dtype)
        if w.ndim == 3:
            return jnp.einsum("bsd,dhk->bshk", x, w)
        y = lax.optimization_barrier(jnp.einsum("bsd,dn->bsn", x, w))
        return y.reshape(*y.shape[:2], -1, cfg.head_dim)

    with jax.named_scope("attn_qkv"):
        q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
        if cfg.key_multiplier != 1.0:
            k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
        if cfg.qk_norm:
            q = _rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = _rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if kind == "block_sparse":      # no rope
            pass
        elif kind == "sliding":
            q, k = _rope(q, k, cfg.rope_theta_sliding or cfg.rope_theta,
                         pos_offset, positions=positions)
        else:
            q, k = _rope(q, k, cfg.rope_theta, pos_offset,
                         positions=positions, yarn=cfg.rope_yarn)
        return (jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1))


def _out_proj(oh, p, cfg: TransformerConfig):
    with jax.named_scope("attn_out"):
        o = jnp.moveaxis(oh, 1, 2).astype(cfg.dtype)  # (B, S, H, Dh)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype))


def _pack_heads(x, n: int):
    """``(..., H_kv, T, Dh)`` as a pool with ``n`` KV heads a stored
    row holds it, ``(..., H_kv / n, T, n * Dh)``: heads ``n j .. n j +
    n - 1`` side by side in row ``j``'s lanes
    (:attr:`TransformerConfig.kv_pack`; ``n == 1``: as it is)."""
    if n == 1:
        return x
    *lead, hkv, t, dh = x.shape
    x = jnp.moveaxis(x.reshape(*lead, hkv // n, n, t, dh), -3, -2)
    return x.reshape(*lead, hkv // n, t, n * dh)


def _unpack_heads(x, n: int):
    """:func:`_pack_heads` undone."""
    if n == 1:
        return x
    *lead, rows, t, w = x.shape
    x = jnp.moveaxis(x.reshape(*lead, rows, t, n, w // n), -2, -3)
    return x.reshape(*lead, rows * n, t, w // n)


# --- the gated short convolution (a "conv" layer's mixer) --------------------
#
# ``[B, C, X] = n W_in``; ``u = B * X``; ``v[t] = sum_j k[:, j] u[t - (K -
# 1) + j]`` (depthwise, causal, ``u[s] = 0`` before the sequence); the
# mixer's output ``(C * v) W_out``.  No activation, no bias.  What a
# request carries from one position to the next is its last ``K - 1``
# gated inputs ``u`` — a STATE of fixed size a layer, where an attention
# layer's grows with the context.  Three scopes cut it out of a trace:
# ``hvd_conv_in`` (the norm and W_in), ``hvd_conv_scan`` (a prompt's or
# a chunk's convolution) / ``hvd_conv_update`` (a tick's one position),
# ``hvd_conv_out`` (the gate and W_out).


def _conv_in(x, p, cfg: TransformerConfig):
    """``(C, u)`` of the layer's input ``x`` ``(B, S, D)``, in FLOAT32
    from the projection's accumulator on: the mixer is a product of
    three of its outputs, and nothing averages a product's rounding as
    a softmax averages a score's.  Where a backend rounds ``B``, ``X``,
    ``u``, ``v`` and ``C * v`` each to bfloat16 (XLA:CPU does; XLA:TPU
    keeps a fused chain in float32 anyway, so the chip's reading did
    not move) a served token's logit lay several times as far from the
    float32 reference's (PERF.md, PR 40).  The state a request keeps is
    rounded once, where it is stored."""
    with jax.named_scope("hvd_conv_in"):
        n = _rmsnorm(x, p["ln1"], cfg.norm_eps)
        bcx = jnp.einsum("bsd,dn->bsn", n, p["conv_in"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        b, c, xx = jnp.split(bcx, 3, axis=-1)
        return c, b * xx


def _conv_taps(ext, p, n: int, leaf: str = "conv_k"):
    """``sum_j k[:, j] ext[:, j : j + n]`` in float32: the ``n``
    outputs whose ``K`` inputs ``ext`` ``(B, n + K - 1, D)`` holds
    (``leaf``: the depthwise kernel's name in ``p``)."""
    k = p[leaf].astype(jnp.float32)
    return sum(ext[:, j:j + n] * k[:, j] for j in range(k.shape[1]))


def _taps_over(state, u, p, true_len, leaf: str = "conv_k"):
    """A prompt's or a chunk's depthwise causal convolution of ``u``
    ``(B, S, C)`` from the taps before it: ``(outputs, new taps)``.
    ``state`` ``(B, K - 1, C)`` holds the ``K - 1`` inputs before ``u``
    (None: the zeros a sequence starts from); the new taps are the
    inputs at the ``K - 1`` positions before ``true_len`` ``(B,)`` —
    out of ``state`` where the row is shorter than that, never from
    the padding."""
    B, S, C = u.shape
    taps = p[leaf].shape[1] - 1
    if state is None:
        state = jnp.zeros((B, taps, C), u.dtype)
    ext = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    v = _conv_taps(ext, p, S, leaf)
    # ext[i] is position i - taps: the state ends at position len - 1
    idx = true_len[:, None] + jnp.arange(taps, dtype=jnp.int32)
    return v, jnp.take_along_axis(ext, idx[:, :, None], axis=1)


def _taps_step(states, layer, u, p, active, leaf: str = "conv_k"):
    """A tick's one position of that convolution, ``u`` ``(S, 1, C)``:
    ``(output, states)`` with ``states`` ``(L, S, K - 1, C)`` read and
    written IN PLACE at ``layer``; a row that is not ``active`` keeps
    its taps."""
    old = lax.dynamic_index_in_dim(states, layer, 0, keepdims=False)
    ext = jnp.concatenate([old.astype(u.dtype), u], axis=1)
    v = _conv_taps(ext, p, 1, leaf)
    new = jnp.where(active[:, None, None], ext[:, 1:].astype(old.dtype), old)
    return v, lax.dynamic_update_index_in_dim(states, new, layer, 0)


def _conv_out(c, v, p, cfg: TransformerConfig):
    with jax.named_scope("hvd_conv_out"):
        return jnp.einsum("bsd,de->bse", (c * v).astype(cfg.dtype),
                          p["conv_out"].astype(cfg.dtype))


def _conv_prefill(x, p, cfg: TransformerConfig, state, true_len):
    """A conv layer's mixer over a prompt or a chunk ``x`` ``(B, S,
    D)``: ``(output, new state)``.  ``state`` ``(B, K - 1, D)`` is the
    request's ``u`` at the ``K - 1`` positions before ``x`` (None: the
    zeros a sequence starts from); the new state its ``u`` at the
    ``K - 1`` positions before ``true_len`` ``(B,)`` — out of ``state``
    where the row is shorter than that, never from the padding."""
    c, u = _conv_in(x, p, cfg)
    with jax.named_scope("hvd_conv_scan"):
        v, new = _taps_over(state, u, p, true_len)
    return _conv_out(c, v, p, cfg), new.astype(cfg.dtype)


def _conv_decode(x, p, cfg: TransformerConfig, states, layer, active):
    """A conv layer's mixer for one token a slot, ``x`` ``(S, 1, D)``:
    ``(output, states)`` with ``states`` ``(L_conv, S, K - 1, D)``, every
    conv layer's and slot's, read and written IN PLACE at ``layer`` (a
    layer scan's loop state, like a page pool).  A row that is not
    ``active`` — idle, or a prompt between two of its chunks — keeps
    its state."""
    c, u = _conv_in(x, p, cfg)
    with jax.named_scope("hvd_conv_update"):
        v, states = _taps_step(states, layer, u, p, active)
    return _conv_out(c, v, p, cfg), states


# --- the state-space mixer (a "hybrid" layer's second mixer) -----------------
#
# Mamba-2 beside the attention, on the same normed input ``n``:
# ``[z | xBC | dt] = (m_si n) W_in * mup`` (``mup``: the five published
# multipliers laid over the parts' columns); ``xBC <- silu(conv(xBC) +
# b)`` (depthwise, causal, zeros before the sequence); ``x`` into heads,
# ``B``/``C`` into groups; ``dt = softplus(dt + dt_bias)`` a head; the
# recurrence ``S_t = exp(-exp(A_log) dt_t) S_{t-1} + (dt_t x_t) B_t^T``,
# ``y_t = S_t C_t + D x_t`` (:mod:`horovod_tpu.ops.ssm`); ``g = y *
# silu(z)``, an RMSNorm over EACH GROUP's columns with a learned scale;
# ``g W_out``.  In float32 from the projection's accumulator to the
# operand of ``W_out`` (as a conv layer's gates: :func:`_conv_in`).
# What a request carries: the matrix ``S`` a head and the last ``K - 1``
# PRE-activation ``xBC``.  Five scopes cut it out of a trace:
# ``hvd_ssm_in``, ``hvd_ssm_conv``, ``hvd_ssm_scan`` (a prompt, a chunk)
# / ``hvd_ssm_update`` (a tick; the kernel's name too), ``hvd_ssm_out``.


def _ssm_in(n, p, cfg: TransformerConfig):
    """``(z, xBC, dt)`` float32 of the layer's NORMED input ``n``."""
    with jax.named_scope("hvd_ssm_in"):
        if cfg.ssm_in_multiplier != 1.0:
            n = n * jnp.asarray(cfg.ssm_in_multiplier, n.dtype)
        out = jnp.einsum("bsd,dn->bsn", n, p["ssm_in"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        I, C = cfg.ssm_inner, cfg.ssm_conv_width
        if cfg.ssm_multipliers:
            gn = cfg.ssm_groups * cfg.ssm_state
            out = out * np.repeat(
                np.asarray(cfg.ssm_multipliers, np.float32),
                (I, I, gn, gn, cfg.ssm_heads))
        return out[..., :I], out[..., I:I + C], out[..., I + C:]


def _ssm_split(xbc, dt, p, cfg: TransformerConfig):
    """The activated ``xBC`` and the raw ``dt`` as the recurrence takes
    them: ``x (.., H, P)``, ``B``/``C`` ``(.., G, N)``, ``dt (.., H)``
    after its bias and softplus, ``A (H,)`` negative."""
    I, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :I].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim)
    b = xbc[..., I:I + gn].reshape(*lead, cfg.ssm_groups, cfg.ssm_state)
    c = xbc[..., I + gn:].reshape(*lead, cfg.ssm_groups, cfg.ssm_state)
    dt = jax.nn.softplus(dt + p["ssm_dt_bias"].astype(jnp.float32))
    return x, b, c, dt, -jnp.exp(p["ssm_A_log"].astype(jnp.float32))


def _ssm_out(y, x, z, p, cfg: TransformerConfig):
    """The skip ``D x``, the gate, the norm over each group, ``W_out``:
    ``y``/``x`` ``(.., H, P)``, ``z (.., I)`` float32."""
    with jax.named_scope("hvd_ssm_out"):
        y = y + p["ssm_D"].astype(jnp.float32)[:, None] * x
        g = y.reshape(z.shape) * jax.nn.silu(z)
        grp = g.reshape(*g.shape[:-1], cfg.ssm_groups, -1)
        grp = grp * lax.rsqrt(jnp.mean(jnp.square(grp), axis=-1,
                                       keepdims=True) + cfg.norm_eps)
        g = grp.reshape(g.shape) * p["ssm_norm"].astype(jnp.float32)
        return jnp.einsum("bsi,id->bsd", g.astype(cfg.dtype),
                          p["ssm_out"].astype(cfg.dtype))


def _ssm_prefill(n, p, cfg: TransformerConfig, conv_state, ssm_state,
                 true_len):
    """A hybrid layer's state-space mixer over a prompt or a chunk,
    ``n`` ``(B, S, D)`` normed: ``(output, new taps, new state)``.
    ``conv_state`` ``(B, K - 1, C)`` / ``ssm_state`` ``(B, H, P, N)``
    are the request's before ``n`` (None: the zeros a sequence starts
    from); the new ones stand at ``true_len`` ``(B,)`` — the padding
    behind it changes neither."""
    from horovod_tpu.ops import ssm

    z, xbc, dt = _ssm_in(n, p, cfg)
    B, S, _ = xbc.shape
    with jax.named_scope("hvd_ssm_conv"):
        v, new_conv = _taps_over(conv_state, xbc, p, true_len, "ssm_conv_k")
        act = jax.nn.silu(v + p["ssm_conv_b"].astype(jnp.float32))

    with jax.named_scope("hvd_ssm_scan"):
        x, b, c, dt, a_neg = _ssm_split(act, dt, p, cfg)
        real = jnp.arange(S, dtype=jnp.int32)[None, :] < true_len[:, None]
        if ssm_state is None:
            ssm_state = jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim,
                                   cfg.ssm_state), jnp.float32)
        y, new = ssm.ssm_scan(x, jnp.where(real[..., None], dt, 0.0), a_neg,
                              b, c, ssm_state, chunk=cfg.ssm_chunk,
                              dtype=cfg.dtype)
    return (_ssm_out(y, x, z, p, cfg), new_conv.astype(cfg.dtype),
            new.astype(cfg.dtype))


def _ssm_decode(n, p, cfg: TransformerConfig, taps, states, layer, active,
                kernel: bool):
    """A hybrid layer's state-space mixer for one token a slot, ``n``
    ``(S, 1, D)`` normed: ``(output, taps, states)`` with ``taps`` ``(L,
    S, K - 1, C)`` and ``states`` ``(L, S, H, P, N)`` every layer's and
    slot's, both read and written IN PLACE at ``layer`` (a layer scan's
    loop state, like a page pool).  A row that is not ``active`` keeps
    both."""
    from horovod_tpu.ops import ssm

    z, xbc, dt = _ssm_in(n, p, cfg)
    with jax.named_scope("hvd_ssm_conv"):
        v, taps = _taps_step(taps, layer, xbc, p, active, "ssm_conv_k")
        act = jax.nn.silu(v + p["ssm_conv_b"].astype(jnp.float32))
    with jax.named_scope("hvd_ssm_update"):
        x, b, c, dt, a_neg = _ssm_split(act[:, 0], dt[:, 0], p, cfg)
        y, states = ssm.ssm_update(states, layer, x, dt, a_neg, b, c,
                                   active, kernel=kernel)
    return _ssm_out(y[:, None], x[:, None], z, p, cfg), taps, states


def _mix(h_attn, h_ssm, cfg: TransformerConfig):
    """A hybrid layer's two branches summed, each under its output
    multiplier."""
    if cfg.attn_out_multiplier != 1.0:
        h_attn = h_attn * jnp.asarray(cfg.attn_out_multiplier, h_attn.dtype)
    if cfg.ssm_out_multiplier != 1.0:
        h_ssm = h_ssm * jnp.asarray(cfg.ssm_out_multiplier, h_ssm.dtype)
    return h_attn + h_ssm


def _attn_in(n, cfg: TransformerConfig):
    """The attention's input of a hybrid layer's normed ``n``."""
    if cfg.attn_in_multiplier == 1.0:
        return n
    return n * jnp.asarray(cfg.attn_in_multiplier, n.dtype)


# --- linear attention (a "linear" layer's mixer) ------------------------------
#
# Lightning attention: ``q = RMSNorm(n W_q)``, ``k = RMSNorm(n W_k)`` a
# head (``qk_norm``), ``v = n W_v``, rope on q and k; a
# head keeps ``S`` ``(Dh, Dh)`` a request, ``S_t = lambda_h S_{t-1} +
# k_t^T v_t``, ``o_t = (q_t / sqrt(Dh)) S_t``; ``(RMSNorm(o) *
# sigmoid(n W_g)) W_o``.  The recurrence IS :mod:`horovod_tpu.ops.ssm`'s
# with ``x = v``, ``B = k``, ``C = q / sqrt(Dh)``, ``dt = 1`` (0 on
# padding), ``A = log lambda`` (the leaf ``lin_decay``) and a group a
# head: the state ``(H, P = Dh of v, N = Dh of k)`` in FLOAT32 — with
# ``lambda`` up to 0.9995 a state rounded to bfloat16 at each of
# thousands of ticks drifts.  In float32 from the projections'
# accumulators to the operand of ``W_o``.  Scopes: ``hvd_lin_in``, the
# recurrence under its bodies' names (``hvd_ssm_scan``,
# ``hvd_ssm_update``), ``hvd_lin_out``.


def _lin_in(n, p, cfg: TransformerConfig, positions):
    """``(q / sqrt(Dh), k, v (B, S, H, Dh), g (B, S, H * Dh))`` float32
    of the layer's NORMED input ``n``."""
    with jax.named_scope("hvd_lin_in"):
        def proj(leaf):
            return jnp.einsum("bsd,dn->bsn", n, p[leaf].astype(cfg.dtype),
                              preferred_element_type=jnp.float32)

        heads = n.shape[:2] + (cfg.n_heads, cfg.head_dim)
        q, k, v = (proj(w).reshape(heads) for w in ("lin_q", "lin_k",
                                                    "lin_v"))
        if cfg.qk_norm:
            q = _rmsnorm(q, p["lin_q_norm"], cfg.norm_eps)
            k = _rmsnorm(k, p["lin_k_norm"], cfg.norm_eps)
        q, k = _rope(q, k, cfg.rope_theta, positions=positions)
        return q * cfg.head_dim ** -0.5, k, v, proj("lin_g")


def _lin_out(y, g, p, cfg: TransformerConfig):
    """The norm over each head of ``y`` ``(B, S, H, Dh)``, the gate and
    ``W_o``, under the layer's output multiplier."""
    with jax.named_scope("hvd_lin_out"):
        y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg.norm_eps) * p["lin_norm"].astype(jnp.float32)
        o = y.reshape(g.shape) * jax.nn.sigmoid(g)
        h = jnp.einsum("bsn,nd->bsd", o.astype(cfg.dtype),
                       p["lin_o"].astype(cfg.dtype))
        return _out_scaled(h, cfg)


def _out_scaled(h, cfg: TransformerConfig):
    if cfg.attn_out_multiplier == 1.0:
        return h
    return h * jnp.asarray(cfg.attn_out_multiplier, h.dtype)


def _lin_prefill(q, k, v, p, cfg: TransformerConfig, state, true_len):
    """A linear layer's recurrence over a prompt or a chunk from
    ``state`` ``(B, H, Dh, Dh)`` (None: zeros) to the state at
    ``true_len`` ``(B,)``: ``(y (B, S, H, Dh), new state)`` float32."""
    from horovod_tpu.ops import ssm

    B, S, H, Dh = q.shape
    with jax.named_scope("hvd_ssm_scan"):
        real = jnp.arange(S, dtype=jnp.int32)[None, :] < true_len[:, None]
        if state is None:
            state = jnp.zeros((B, H, Dh, Dh), jnp.float32)
        return ssm.ssm_scan(
            v, jnp.broadcast_to(real[..., None].astype(jnp.float32),
                                (B, S, H)),
            p["lin_decay"].astype(jnp.float32), k, q, state,
            chunk=cfg.ssm_chunk, dtype=cfg.dtype)


def _lin_decode(q, k, v, p, states, layer, active, kernel: bool):
    """... for one token a slot, ``q``/``k``/``v`` ``(S, H, Dh)``: ``(y
    (S, H, Dh), states)`` with ``states`` ``(L, S, H, Dh, Dh)`` float32
    read and written IN PLACE at ``layer``."""
    from horovod_tpu.ops import ssm

    with jax.named_scope("hvd_ssm_update"):
        return ssm.ssm_update(
            states, layer, v, jnp.ones(q.shape[:2], jnp.float32),
            p["lin_decay"].astype(jnp.float32), k, q, active, kernel=kernel)


# --- block-sparse attention (a "block_sparse" layer's mixer) ------------------
#
# InfLLM-V2's selection over a paged K/V cache.  Beside its keys a KV
# head keeps a COMPRESSED key for every whole window of ``bsa_kernel``
# tokens, one every ``bsa_stride`` — the mean of the window's keys; the
# page is the stride, so window ``j`` (tokens ``16 j .. 16 j + 31`` at
# the published sizes) ends with page ``j + 1`` and its row lies THERE,
# by the SLOT and the page's logical index (no page of this kind is
# shared, and the tick is the rows' one reader): ``ck[slot, head, r]``
# is the mean over pages ``r - 1`` and ``r``, written when page ``r``
# fills, and row 0 is never read.  A query at
# position ``t`` whose context ``t + 1`` is longer than ``bsa_dense_len``
# scores the rows it sees whole (``(r + 1) stride <= t + 1``): ``softmax_r
# (q_h . c_r / sqrt(Dh))`` a head, summed over the query heads of the KV
# head; a block of ``bsa_block`` tokens takes the largest over the rows
# ``m b .. m b + m`` (``m`` pages a block: the windows that overlap it);
# the query attends blocks ``< bsa_init_blocks``, the ``bsa_window /
# bsa_block`` blocks up to its own, and the ``bsa_topk`` best of the rest
# (:func:`~horovod_tpu.ops.paged_attention.select_topk`: ties to the
# lower block), causally, at ``1 / sqrt(Dh)``.  A shorter context attends
# everything.  Scopes: ``hvd_bsa_score`` (the compressed rows' scores and
# their pooling into blocks), ``hvd_bsa_select``, ``hvd_bsa_attend`` (a
# tick: the chosen blocks' pages compacted into a table a slot and KV
# head, and the fused paged kernel over it).

#: Queries whose ``(heads, queries, keys)`` scores are in flight together
#: in a chunk or a whole prompt: 32 x 64 x 33 280 float32 are 273 MB.
_BSA_QUERY_BLOCK = 64


def _bsa_window_mean(prev, this, cfg: TransformerConfig):
    """The compressed key of the window that ends with a page: the mean
    over the page before (``prev``) and the page (``this``), ``(...,
    page, Dh)`` each -> ``(..., Dh)`` float32."""
    total = (jnp.sum(prev.astype(jnp.float32), axis=-2)
             + jnp.sum(this.astype(jnp.float32), axis=-2))
    return total / cfg.bsa_kernel


def _bsa_compress(k_log, cfg: TransformerConfig):
    """Every page's row of keys that lie in logical order: ``k_log``
    ``(K, Hkv, T, Dh)`` -> ``(K, Hkv, ceil(T / stride), Dh)`` in the
    keys' dtype (what the pool stores); row 0 is page 0's alone and is
    never read."""
    K, Hkv, T, Dh = k_log.shape
    ps = cfg.bsa_stride
    pad = -T % ps
    pages = jnp.pad(k_log, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        K, Hkv, (T + pad) // ps, ps, Dh)
    prev = jnp.pad(pages, ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))[:, :, :-1]
    return _bsa_window_mean(prev, pages, cfg).astype(k_log.dtype)


def _bsa_block_scores(qg, rows, pos, n_blocks: int, cfg: TransformerConfig):
    """Each block's score for each query: ``qg`` ``(B, Hkv, G, Q, Dh)``
    queries at positions ``pos`` ``(B | 1, Q)``, ``rows`` ``(B, Hkv, nP,
    Dh)`` the compressed rows by page -> ``(B, Hkv, Q, n_blocks)``
    float32, -1 where a block overlaps no window the query sees whole."""
    nP, m = rows.shape[2], cfg.bsa_block // cfg.bsa_stride
    s = jnp.einsum("bkgqd,bkrd->bkgqr", qg.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * cfg.head_dim ** -0.5
    r = jnp.arange(nP, dtype=jnp.int32)
    seen = (r >= 1) & ((r + 1) * cfg.bsa_stride <= pos[..., None] + 1)
    seen = seen[:, None, None]                       # (B | 1, 1, 1, Q, nP)
    prob = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1),
                     0.0)
    score = jnp.where(seen[:, :, 0], jnp.sum(prob, axis=2), -1.0)
    # block b: the rows m b .. m b + m (the windows that overlap it)
    score = jnp.pad(score, ((0, 0),) * 3 + ((0, (n_blocks + 1) * m - nP),),
                    constant_values=-1.0)[..., :(n_blocks + 1) * m]
    score = score.reshape(score.shape[:-1] + (n_blocks + 1, m))
    return jnp.maximum(jnp.max(score[..., :-1, :], axis=-1),
                       score[..., 1:, 0])


def _bsa_chosen(score, pos, cfg: TransformerConfig):
    """The blocks each row attends, IN ORDER, as a tick's table takes
    them, from its block scores ``score`` ``(R, nB)`` and its position
    ``pos`` ``(R,)``: ``(chosen (R, bsa_blocks_max) int32 — the first
    ``n`` real, ascending, 0 behind —, n (R,))``: the first blocks, the
    picked, the window's — or, of a context no longer than
    ``bsa_dense_len``, every one up to the row's own (the LAST of the
    list either way).  The forced blocks are taken out before the
    selection: it runs over ``bsa_init_blocks <= b < own - W + 1``
    alone."""
    from horovod_tpu.ops import paged_attention as _pa

    n_max = cfg.bsa_blocks_max
    init, W = cfg.bsa_init_blocks, cfg.bsa_window // cfg.bsa_block
    own = pos // cfg.bsa_block
    dense = pos + 1 <= cfg.bsa_dense_len
    rest = score[:, init:]
    if rest.shape[1] == 0:
        rest = jnp.full((score.shape[0], 1), -1.0, score.dtype)
    picks, count = _pa.select_topk(
        rest, jnp.clip(own - W + 1 - init, 0, None),
        min(cfg.bsa_topk, rest.shape[1]))
    j = jnp.arange(n_max, dtype=jnp.int32)[None, :]
    n_init = jnp.minimum(init, own + 1)[:, None]
    lo = jnp.maximum(own - W + 1, init)[:, None]
    n_pick = n_init + count[:, None]
    # (no pick where a row's own block is among the first: count is 0)
    picks = jnp.pad(picks + init, ((0, 0), (init, max(
        n_max - init - picks.shape[1], 0))))[:, :n_max]
    chosen = jnp.where(j < n_init, j, jnp.where(
        j < n_pick, picks, lo + j - n_pick))
    n = n_pick[:, 0] + jnp.maximum(own + 1 - lo[:, 0], 0)
    chosen = jnp.where(dense[:, None], j, chosen)
    n = jnp.where(dense, own + 1, n)
    return jnp.where(j < n[:, None], chosen, 0), n


def _bsa_block_mask(score, pos, cfg: TransformerConfig):
    """``(R, nB)`` bool: :func:`_bsa_chosen`'s blocks as a mask, for the
    attention that is dense under it (a prompt's, a chunk's)."""
    chosen, n = _bsa_chosen(score, pos, cfg)
    real = jnp.arange(chosen.shape[1], dtype=jnp.int32)[None, :] < n[:, None]
    b = jnp.arange(score.shape[1], dtype=jnp.int32)
    return jnp.any((chosen[:, :, None] == b) & real[:, :, None], axis=1)


def _bsa_rows_attend(qh, k_log, v_log, pos, cfg: TransformerConfig):
    """Queries ``qh`` ``(K, H, S0, Dh)`` at logical positions ``pos``
    ``(S0,)`` against keys and values that lie in logical order,
    ``(K, Hkv, T, Dh)`` (a whole prompt's own; a chunk's landed prefix
    with the chunk behind it): each query its selected blocks, causally
    -> ``(oh (K, H, S0, Dh), the compressed rows (K, Hkv, nP, Dh))``.
    The selection becomes a MASK a block and the attention is dense
    under it, :data:`_BSA_QUERY_BLOCK` queries at a time."""
    K, H, S0, Dh = qh.shape
    Hkv, T = k_log.shape[1:3]
    G, blk = H // Hkv, cfg.bsa_block
    rows = _bsa_compress(k_log, cfg)
    nB = -(-T // blk)
    qb_n = min(_BSA_QUERY_BLOCK, S0)
    assert S0 % qb_n == 0, (S0, qb_n)
    qg = qh.reshape(K, Hkv, G, S0, Dh)
    sparse = T > cfg.bsa_dense_len      # else no query's context is longer

    def block(a):
        q_b, pos_b = a                           # (K, Hkv, G, qb_n, Dh)
        if sparse:
            with jax.named_scope("hvd_bsa_score"):
                score = _bsa_block_scores(q_b, rows, pos_b[None], nB, cfg)
            with jax.named_scope("hvd_bsa_select"):
                sel = _bsa_block_mask(
                    score.reshape(-1, nB), jnp.broadcast_to(
                        pos_b, (K, Hkv, qb_n)).reshape(-1), cfg
                ).reshape(K, Hkv, qb_n, nB)
        else:
            sel = jnp.broadcast_to(
                jnp.arange(nB, dtype=jnp.int32) <= pos_b[:, None] // blk,
                (K, Hkv, qb_n, nB))
        with jax.named_scope("chunk_attn"):
            s = jnp.einsum("bkgqd,bktd->bkgqt", q_b.astype(k_log.dtype),
                           k_log, preferred_element_type=jnp.float32
                           ) * Dh ** -0.5
            t = jnp.arange(T, dtype=jnp.int32)
            vis = (jnp.repeat(sel, blk, axis=-1)[..., :T]
                   & (t <= pos_b[:, None]))
            w = jax.nn.softmax(jnp.where(vis[:, :, None], s, -1e30), axis=-1)
            return jnp.einsum("bkgqt,bktd->bkgqd", w.astype(v_log.dtype),
                              v_log, preferred_element_type=jnp.float32)

    o = lax.map(block, (
        jnp.moveaxis(qg.reshape(K, Hkv, G, S0 // qb_n, qb_n, Dh), 3, 0),
        pos.reshape(S0 // qb_n, qb_n)))           # (nq, K, Hkv, G, qb_n, Dh)
    o = jnp.moveaxis(o, 0, 3).reshape(K, H, S0, Dh)
    return o.astype(cfg.dtype), rows


def _bsa_landing_rows(rows, p0, S0: int, cfg: TransformerConfig):
    """Of the compressed rows by page ``(K, Hkv, nP, Dh)``, those of the
    pages a block of ``S0`` tokens from position ``p0`` lands in, as the
    pool lays a slot's: ``(K, Hkv, landing pages, Dh)``."""
    n_pg = -(-(S0 + cfg.bsa_stride - 1) // cfg.bsa_stride)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, n_pg), (0, 0)))
    return lax.dynamic_slice_in_dim(rows, p0 // cfg.bsa_stride, n_pg, 2)


# --- latent attention (MLA) ---------------------------------------------------
#
# The cache of a latent layer is ONE key a token, shared by every head:
# ``[ckv | k_rope]`` (``kv_lora_rank + qk_rope_head_dim`` wide, 576 at
# the published sizes), the normed latent and the roped key.  Two ways
# to attend it, the same mathematics:
#
# * EXPANDED: each head's ``k_nope`` and ``v`` read up from the latent
#   through ``wkv_b`` (``_mla_expand``), then ordinary attention with
#   q/k ``nope + rope`` wide and v ``v_head_dim`` wide.  Whole prompts,
#   ``forward``, and a chunk (its own block, and its landed prefix in
#   blocks: ``_mla_chunk_attend``).
# * ABSORBED: ``q_lat_h = q_nope_h W_k,h^T`` so that the score is
#   ``[q_lat_h | q_rope_h] . [ckv | k_rope]`` and the output ``(sum p
#   ckv) W_v,h``: attention with ONE kv head whose key is the cached
#   row and whose value is that row's first ``kv_lora_rank`` lanes
#   (``_mla_absorb_q`` / ``_mla_absorbed_out``).  A decode tick: it
#   reads the cache as it lies, 1 152 bytes a token, where expanding
#   would cost 16.8 MFLOP a cached token and tick.


def _rope_one(x, cfg: TransformerConfig, pos_offset, positions):
    """:func:`_rope` (YaRN) on ONE array ``(B, S, heads, rope)``: latent
    attention ropes its queries' and its one key's rope parts apart."""
    return _rope(x, x, cfg.rope_theta, pos_offset, positions=positions,
                 yarn=cfg.rope_yarn)[0]


def _mla_q(x, p, cfg: TransformerConfig, pos_offset=0, positions=None,
           with_cq: bool = False):
    """Latent attention's queries: down, norm, up, rope on the rope
    part -> ``(q_nope (B, S, H, nope), q_rope (B, S, H, rope))``, and
    with ``with_cq`` the normed query latent ``cq (B, S, q_lora_rank)``
    too, which an indexer reads its own queries up from."""
    with jax.named_scope("hvd_mla_q"):
        cq = _rmsnorm(jnp.einsum("bsd,dr->bsr", x,
                                 p["wq_a"].astype(cfg.dtype)),
                      p["q_a_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(cfg.dtype))
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
        q_rope = _rope_one(q_rope, cfg, pos_offset, positions)
        return (q_nope, q_rope, cq) if with_cq else (q_nope, q_rope)


def _mla_kv(x, p, cfg: TransformerConfig, pos_offset=0, positions=None):
    """What a token leaves in the cache: ``(B, S, latent_row)`` — the
    latent after its norm, then the rope key after the rope, then
    zeros up to the row's stored width."""
    with jax.named_scope("hvd_mla_kv"):
        kv = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"].astype(cfg.dtype))
        ckv, kr = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
        ckv = _rmsnorm(ckv, p["kv_a_norm"], cfg.norm_eps)
        kr = _rope_one(kr[:, :, None], cfg, pos_offset, positions)[:, :, 0]
        pad = jnp.zeros(kr.shape[:2] + (cfg.latent_row - cfg.latent_width,),
                        kr.dtype)
        return jnp.concatenate([ckv, kr, pad], axis=-1)


def _mla_expand(lat, p, cfg: TransformerConfig):
    """Cached rows ``(..., T, latent_row)`` as every head's key and
    value, head-major: ``(k (..., H, T, nope + rope), v (..., H, T,
    v_head_dim))`` — ``k_nope`` and ``v`` up through ``wkv_b``, the one
    rope key repeated for every head."""
    with jax.named_scope("hvd_mla_expand"):
        ckv, kr, _ = jnp.split(lat.astype(cfg.dtype),
                               [cfg.kv_lora_rank, cfg.latent_width], axis=-1)
        kv = jnp.einsum("...tc,chk->...htk", ckv,
                        p["wkv_b"].astype(cfg.dtype))
        k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
        kr = jnp.broadcast_to(kr[..., None, :, :],
                              k_nope.shape[:-1] + kr.shape[-1:])
        return jnp.concatenate([k_nope, kr], axis=-1), v


def _mla_heads(q_nope, q_rope):
    """The expanded form's queries, head-major ``(B, H, S, nope +
    rope)``."""
    return jnp.moveaxis(jnp.concatenate([q_nope, q_rope], axis=-1), 2, 1)


def _mla_absorb_q(q_nope, q_rope, p, cfg: TransformerConfig):
    """The absorbed form's queries ``(B, S, H, latent_row)``:
    ``[q_nope W_k^T | q_rope | 0]``, to be dotted with cached rows."""
    with jax.named_scope("hvd_mla_q"):
        w_k = p["wkv_b"][..., :cfg.qk_nope_head_dim].astype(cfg.dtype)
        q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_k)
        pad = jnp.zeros(q_lat.shape[:-1]
                        + (cfg.latent_row - cfg.latent_width,), q_lat.dtype)
        return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def _mla_out(o, p, cfg: TransformerConfig, absorbed: bool = False):
    """Output projection from ``o`` ``(B, S, H, v_head_dim)`` — or,
    ``absorbed``, from ``o_lat`` ``(B, S, H, kv_lora_rank)``, each
    head's weighted sum of latents, read up through ``W_v`` first."""
    with jax.named_scope("hvd_mla_out"):
        o = o.astype(cfg.dtype)
        if absorbed:
            w_v = p["wkv_b"][..., cfg.qk_nope_head_dim:].astype(cfg.dtype)
            o = jnp.einsum("bshc,chv->bshv", o, w_v)
        return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(cfg.dtype))


# --- learned sparse attention (an indexer over the latent cache) --------------
#
# ``cfg.sparse``: beside the latent row a token leaves ONE index key
# ``k_I`` (``index_head_dim`` wide, LayerNorm'd, its first
# ``qk_rope_head_dim`` dims roped) a layer, and a query at ``t`` scores
# every position ``s <= t``: ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] .
# k_I[s])`` over ``index_n_heads`` index queries read up from the query
# latent ``cq``, ``w`` a projection of the layer's input times
# ``index_n_heads**-0.5 index_head_dim**-0.5``.  It then attends the
# ``min(index_topk, t + 1)`` best-scored positions only (ties to the
# lower position) — latent attention as it is, the softmax over that
# set.  Until a query sees more than ``index_topk`` positions the layer
# is the dense one.  Scopes: ``hvd_dsa_proj`` (the three projections),
# ``hvd_dsa_score``, ``hvd_dsa_select``, ``hvd_dsa_attend`` (the
# selected rows' gather and their absorbed attention).

_INDEX_NORM_EPS = 1e-6

#: Queries whose selection, gathered rows and attention are in flight
#: together in a chunk or a whole prompt: 128 x 2048 rows x 640 lanes
#: are 335 MB in bfloat16.
_DSA_QUERY_BLOCK = 128


def _layernorm(x, scale, bias, eps: float):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale
            + bias).astype(x.dtype)


def _dsa_proj(x, cq, p, cfg: TransformerConfig, pos_offset=0,
              positions=None):
    """The indexer's three projections of a layer's input ``x`` (after
    its norm) and query latent ``cq``: ``(q_I (B, S, Hi, Di), k_I (B,
    S, Di) — what the cache keeps —, w (B, S, Hi) float32)``."""
    r = cfg.qk_rope_head_dim

    def rope(a):           # the first r dims, the layer's own tables
        return jnp.concatenate(
            [_rope_one(a[..., :r], cfg, pos_offset, positions), a[..., r:]],
            axis=-1)

    with jax.named_scope("hvd_dsa_proj"):
        qi = rope(jnp.einsum("bsr,rhk->bshk", cq,
                             p["wi_q"].astype(cfg.dtype)))
        ki = _layernorm(jnp.einsum("bsd,dk->bsk", x,
                                   p["wi_k"].astype(cfg.dtype)),
                        p["i_k_norm"], p["i_k_bias"], _INDEX_NORM_EPS)
        ki = rope(ki[:, :, None])[:, :, 0]
        w = jnp.einsum("bsd,dh->bsh", x, p["wi_w"].astype(cfg.dtype))
        w = w.astype(jnp.float32) * (cfg.index_n_heads ** -0.5
                                     * cfg.index_head_dim ** -0.5)
        return qi, ki, w


def _dsa_select_attend(q, scores, n_valid, gather, cfg: TransformerConfig,
                       kernel: bool):
    """Queries ``q`` ``(R, H, latent_row)`` (absorbed) with their index
    ``scores`` ``(R, T)`` of the ``n_valid`` ``(R,)`` positions each
    sees: the selection (``hvd_dsa_select``), then ``gather(idx)`` ->
    the picked cache rows ``(R, K, latent_row)`` and their absorbed
    attention (``hvd_dsa_attend``) -> ``o_lat (R, H, kv_lora_rank)``
    float32."""
    from horovod_tpu.ops import paged_attention as _pa

    k = min(cfg.index_topk, scores.shape[1])
    with jax.named_scope("hvd_dsa_select"):
        idx, count = _pa.select_topk(scores, n_valid, k, -(-k // 16) * 16)
    with jax.named_scope("hvd_dsa_attend"):
        o, _ = _pa.selected_attend(
            q, gather(idx), count, v_dim=cfg.kv_lora_rank,
            sm_scale=cfg.mla_scale, kernel=kernel)
    return o


def _dsa_attend(q, qi, w, lat, ik, pos, cfg: TransformerConfig):
    """ONE sequence's queries against rows that lie in logical order:
    ``q`` ``(Q, H, latent_row)`` absorbed, ``qi`` ``(Q, Hi, Di)``, ``w``
    ``(Q, Hi)`` at logical positions ``pos`` ``(Q,)``; ``lat`` ``(T,
    latent_row)`` and ``ik`` ``(T, Di)`` the cache rows of positions
    ``0 .. T-1`` (what lies behind a query's position is never picked).
    Scores for all the queries at once, then selection, gather and
    attention :data:`_DSA_QUERY_BLOCK` queries at a time -> ``(Q, H,
    kv_lora_rank)`` float32."""
    from horovod_tpu.ops import paged_attention as _pa

    kernel = cfg.attention_impl == "flash"
    with jax.named_scope("hvd_dsa_score"):
        scores = _pa.index_scores_rows(qi, w, ik, kernel=kernel)
    Q = q.shape[0]
    qb = min(_DSA_QUERY_BLOCK, Q)
    assert Q % qb == 0, (Q, qb)

    def block(a):
        q_b, s_b, pos_b = a
        return _dsa_select_attend(q_b, s_b, pos_b + 1, lambda i: lat[i],
                                  cfg, kernel)

    o = lax.map(block, (q.reshape(Q // qb, qb, *q.shape[1:]),
                        scores.reshape(Q // qb, qb, -1),
                        pos.reshape(Q // qb, qb)))
    return o.reshape(Q, *o.shape[2:])


def _dsa_chunk_attend(q, qi, w, lat, ik, prefix_lat, prefix_ik, p0,
                      cfg: TransformerConfig):
    """A chunk's ``(K, S0)`` queries (``q`` absorbed) against the
    landed prefix (``prefix_lat`` ``(P0, latent_row)``, ``prefix_ik``
    ``(P0, Di)``: positions ``< p0`` of them) and their own rows
    ``lat`` / ``ik``, SELECTED: the chunk's rows are laid behind the
    landed ones at ``p0`` (:func:`_mla_chunk_attend`'s layout: row
    ``j`` of the whole is logical position ``j``), and the query at
    ``p0 + r`` picks among positions ``<= p0 + r`` (:func:`_dsa_attend`).
    -> ``(K, S0, H, kv_lora_rank)`` float32."""
    S0 = lat.shape[1]
    pos = jnp.asarray(p0, jnp.int32) + jnp.arange(S0, dtype=jnp.int32)

    def behind(prefix, own):
        with jax.named_scope("chunk_attn"):
            rows = jnp.pad(prefix.astype(own.dtype), ((0, S0), (0, 0)))
            return lax.dynamic_update_slice_in_dim(rows, own, p0, 0)

    return lax.map(lambda a: _dsa_attend(
        a[0], a[1], a[2], behind(prefix_lat, a[3]), behind(prefix_ik, a[4]),
        pos, cfg), (q, qi, w, lat, ik))


#: Rows a chunk expands and attends at a time: ``wkv_b``'s output for
#: 2048 rows is 64 heads x 2048 x 256 x 2 B = 67 MB (a 16 k prefix
#: expanded whole would be 671 MB a layer).
_MLA_PREFIX_BLOCK = 2048


def _mla_chunk_attend(q_nope, q_rope, lat, prefix_lat, p0, p,
                      cfg: TransformerConfig):
    """A chunk's ``(K, S0)`` queries against their own rows ``lat``
    ``(K, S0, latent_row)`` (causal) and the landed prefix
    ``prefix_lat`` ``(P0, latent_row)``, positions ``< p0`` of it —
    EXPANDED, through the flash forward (``hvd_flash_fwd``).

    The chunk's rows are laid behind the landed ones AT ``p0`` (over the
    gather's padding: ``P0`` is a power of two of pages), so that row
    ``j`` of the whole is logical position ``j`` and query ``r`` (at
    ``p0 + r``) sees ``j <= p0 + r``: one shifted-causal mask, ``col +
    (start - p0) <= row`` for the block that starts at ``start``.  The
    whole goes in blocks of :data:`_MLA_PREFIX_BLOCK` rows: each is
    read up through ``wkv_b`` (``hvd_mla_expand``), attended by the
    flash kernel with that shift, and folded in by its logsumexp — as
    many blocks as hold a visible position; what lies past ``p0 + S0``
    is neither expanded nor attended.

    Why expanded: against ``C`` landed tokens a chunk of 512 costs
    ``C x 16.8`` MFLOP to expand and ``C x 21.0`` to attend; absorbed,
    ``C x 71.3`` (every query-head pair dots 576 and sums 512 wide).
    Measured on the chip at ``C`` = 4 k and 16 k (PERF.md, PR 30).

    -> ``o`` ``(K, S0, H, v_head_dim)`` float32."""
    from horovod_tpu.ops import attention as attn

    K, S0 = lat.shape[:2]
    P0 = prefix_lat.shape[0]
    p0 = jnp.asarray(p0, jnp.int32)
    qh = _mla_heads(q_nope, q_rope)                       # (K, H, S0, 192)
    blk = min(_MLA_PREFIX_BLOCK, P0)
    n_rows = -(-(P0 + S0) // blk) * blk
    with jax.named_scope("chunk_attn"):
        rows = jnp.zeros((K, n_rows, lat.shape[-1]), lat.dtype)
        rows = rows.at[:, :P0].set(prefix_lat.astype(lat.dtype)[None])
        rows = lax.dynamic_update_slice_in_dim(rows, lat, p0, 1)

    def block(b, carry):
        o, lse = carry
        k_b, v_b = _mla_expand(
            lax.dynamic_slice_in_dim(rows, b * blk, blk, 1), p, cfg)
        with jax.named_scope("chunk_attn"):
            o_b, lse_b = attn.flash_attention_shifted(
                qh, k_b, v_b, b * blk - p0, cfg.mla_scale)
            new = jnp.logaddexp(lse, lse_b)
            o = (o * jnp.exp(lse - new)[..., None]
                 + o_b.astype(jnp.float32) * jnp.exp(lse_b - new)[..., None])
        return o, new

    H = qh.shape[1]
    o, _ = lax.fori_loop(
        0, (p0 + S0 + blk - 1) // blk, block,
        (jnp.zeros((K, H, S0, cfg.v_head_dim), jnp.float32),
         jnp.full((K, H, S0), -1e30, jnp.float32)))
    return jnp.moveaxis(o, 1, 2)


def _attention(x, p, cfg: TransformerConfig):
    if cfg.latent:
        return _latent_attention(x, p, cfg, cfg.kind("full"),
                                 _Prompt(cfg))[0]
    B, S, D = x.shape
    from horovod_tpu.ops import attention as attn

    pos_offset = 0
    positions = None
    if cfg.attention_impl in ("ring", "ring_reference", "ulysses"):
        # Sequence is sharded over sp: this shard's tokens start at
        # sp_index * S_local in the global sequence.
        pos_offset = lax.axis_index("sp") * S
    elif cfg.attention_impl == "ring_zigzag":
        # Zigzag layout: this shard holds global chunks (i, 2P-1-i) —
        # non-contiguous positions (feed data permuted by zigzag_perm).
        positions = attn.zigzag_positions(S, "sp")

    qh, kh, vh = _qkv_proj(x, p, cfg, pos_offset, positions=positions)
    with jax.named_scope("attn"):
        oh = _attention_core(qh, kh, vh, cfg, attn)
    return _out_proj(oh, p, cfg)


def _attention_core(qh, kh, vh, cfg: TransformerConfig, attn):
    """The whole-sequence attention ``cfg.attention_impl`` names, on
    projected heads."""
    if cfg.attention_impl == "ring":
        # GQA shards stay small through the ring; expansion is per-chunk.
        return attn.ring_attention(qh, kh, vh, axis_name="sp", causal=True)
    if cfg.attention_impl == "ring_zigzag":
        return attn.zigzag_ring_attention(qh, kh, vh, axis_name="sp")
    if cfg.attention_impl == "ring_reference":
        return attn.ring_attention(qh, kh, vh, axis_name="sp", causal=True,
                                   impl="reference")
    if cfg.attention_impl == "ulysses":
        return attn.ulysses_attention(qh, kh, vh, axis_name="sp",
                                      causal=True)
    if cfg.attention_impl == "flash":
        return attn.flash_attention(qh, attn.expand_kv(kh, cfg.n_heads),
                                    attn.expand_kv(vh, cfg.n_heads), True)
    if cfg.attention_impl == "reference":
        return attn.reference_attention(
            qh, attn.expand_kv(kh, cfg.n_heads),
            attn.expand_kv(vh, cfg.n_heads), causal=True)
    raise ValueError(
        f"unknown attention_impl {cfg.attention_impl!r}; expected "
        "'reference', 'flash', 'ring', 'ring_reference' or 'ulysses'")


def _dense_mlp(x, p, cfg: TransformerConfig):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cfg.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cfg.dtype))
    if cfg.mlp_multipliers:     # (gate, down)
        m_g, m_d = (jnp.asarray(m, g.dtype) for m in cfg.mlp_multipliers)
        return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g * m_g) * u,
                          p["w_down"].astype(cfg.dtype)) * m_d
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"].astype(cfg.dtype))


def _moe_mlp_dense(x, p, cfg: TransformerConfig, return_aux: bool = False):
    """Top-1 MoE, dense dispatch: compute routing probs, evaluate every
    expert, combine with the routing one-hot.  Exact and dropless — the
    oracle for the sparse path, and the right choice for decoding (a
    handful of tokens) and tiny E."""
    if (cfg.n_experts_per_tok > 1 or cfg.moe_routing or cfg.moe_score_bias
            or cfg.held_offset is not None):
        return _moe_mlp_dense_topk(x, p, cfg, return_aux)
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(cfg.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top = jnp.argmax(probs, axis=-1)  # (B, S)
    gate = jnp.max(probs, axis=-1)  # (B, S) top-1 prob
    onehot = jax.nn.one_hot(top, cfg.n_experts, dtype=cfg.dtype)
    g = jnp.einsum("bsd,edf->besf", x, p["w_gate"].astype(cfg.dtype))
    u = jnp.einsum("bsd,edf->besf", x, p["w_up"].astype(cfg.dtype))
    y = jnp.einsum("besf,efd->besd", jax.nn.silu(g) * u, p["w_down"].astype(cfg.dtype))
    y = jnp.einsum("besd,bse->bsd", y, onehot)
    y = y * gate[..., None].astype(cfg.dtype)
    if not return_aux:
        return y
    frac = onehot.astype(jnp.float32).reshape(-1, cfg.n_experts).mean(0)
    pbar = probs.reshape(-1, cfg.n_experts).mean(0)
    return y, cfg.n_experts * jnp.sum(frac * pbar)


def _routing(p, cfg: TransformerConfig) -> dict:
    """:func:`~horovod_tpu.ops.moe.route_topk`'s keywords for the layer
    ``p``: what the configuration states (``cfg.moe_routing``) and the
    layer's own score-correction bias."""
    if "router_bias" not in p:
        return cfg.moe_routing
    return {**cfg.moe_routing, "bias": p["router_bias"]}


def _moe_mlp_dense_topk(x, p, cfg: TransformerConfig, return_aux: bool):
    """Top-k MoE by every expert and a mask: the oracle of the dropless
    dispatch for more than one expert a token (router in float32, the
    k weights renormalised when ``cfg.norm_topk_prob``; any routing
    ``cfg.moe_routing`` states; of a chip's share, the held experts'
    columns of the combination alone)."""
    if return_aux:
        raise UnsupportedModelConfigError(
            "the balance loss is written for one softmax expert a token; "
            f"n_experts_per_tok={cfg.n_experts_per_tok}, routing "
            f"{cfg.moe_routing} and a share of the experts serve only")
    from horovod_tpu.ops import moe

    B, S, D = x.shape
    top, gate = moe.route_topk(x.reshape(-1, D), p["router"],
                               cfg.n_experts_per_tok, cfg.norm_topk_prob,
                               **_routing(p, cfg))
    comb = jnp.einsum("tke,tk->te",
                      jax.nn.one_hot(top, cfg.n_experts, dtype=jnp.float32),
                      gate).reshape(B, S, cfg.n_experts)
    if cfg.held_offset is not None:
        comb = comb[..., cfg.held_offset:cfg.held_offset + cfg.experts_held]
    g = jnp.einsum("bsd,edf->besf", x, p["w_gate"].astype(cfg.dtype))
    u = jnp.einsum("bsd,edf->besf", x, p["w_up"].astype(cfg.dtype))
    y = jnp.einsum("besf,efd->besd", jax.nn.silu(g) * u,
                   p["w_down"].astype(cfg.dtype))
    return jnp.einsum("besd,bse->bsd", y.astype(jnp.float32),
                      comb).astype(cfg.dtype)


def _moe_mlp(x, p, cfg: TransformerConfig, impl: Optional[str] = None,
             return_aux: bool = False, token_mask=None,
             return_counts: bool = False):
    """The expert layer's FFN: the routed experts (:func:`_moe_routed`)
    and, where the configuration has them, the shared experts beside —
    one dense SwiGLU every token passes through, under its own scope
    ``hvd_moe_shared``, added ONCE whatever share of the routed experts
    is held here."""
    out = _moe_routed(x, p, cfg, impl, return_aux, token_mask,
                      return_counts)
    if not cfg.n_shared_experts:
        return out
    with jax.named_scope("hvd_moe_shared"):
        shared = _dense_mlp(x, {k: p["ws_" + k[2:]] for k in _EXPERT_LEAVES},
                            cfg)
    if return_aux or return_counts:
        return out[0] + shared, out[1]
    return out + shared


def _moe_routed(x, p, cfg: TransformerConfig, impl: Optional[str] = None,
                return_aux: bool = False, token_mask=None,
                return_counts: bool = False):
    """Mixture-of-experts FFN; ``impl`` overrides ``cfg.moe_impl``:
    "switch" (capacity-factor sparse dispatch — training), "dense"
    (every-expert oracle — tiny E), "dropless" (grouped ragged matmuls,
    exact at k/E dense FLOPs — prefill chunks and paged decode ticks).
    With ``return_aux`` also returns the layer's Switch load-balancing
    loss (ops/moe.py switch_moe(return_aux=True); same formula for
    dense).  ``token_mask`` / ``return_counts`` are the dropless
    dispatch's (:func:`~horovod_tpu.ops.moe.dropless_moe`)."""
    impl = impl or cfg.moe_impl
    if impl != "dropless" and "expert_stack" in p:
        # only the grouped product reads a stack in place: cut this
        # layer's experts out for the other dispatches
        w, l = p["expert_stack"]
        p = {**p, **{k: lax.dynamic_index_in_dim(w[k], l, 0, keepdims=False)
                     for k in _EXPERT_LEAVES}}
    if impl == "dense":
        return _moe_mlp_dense(x, p, cfg, return_aux=return_aux)
    from horovod_tpu.ops import moe

    if impl == "dropless":
        if return_aux:
            raise ValueError(
                "moe_impl='dropless' is the serving dispatch — train with "
                "'switch' (+ moe_aux_coeff) for the balance loss")
        # every layer's experts and this layer's index (a serving
        # scan: _scan_layer_kinds), or this layer's own
        w, layer = p.get("expert_stack") or (p, None)
        return moe.dropless_moe(
            x, p["router"], *(w[k].astype(cfg.dtype)
                              for k in _EXPERT_LEAVES),
            k=cfg.n_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            token_mask=token_mask, return_counts=return_counts,
            layer=layer, routing=_routing(p, cfg) or None,
            held_offset=cfg.held_offset)
    if impl != "switch":
        raise ValueError(f"unknown moe_impl {impl!r}; "
                         "expected 'switch', 'dense', or 'dropless'")
    if (cfg.n_experts_per_tok > 1 or cfg.moe_routing or cfg.moe_score_bias
            or cfg.held_offset is not None):
        raise UnsupportedModelConfigError(
            "switch dispatch routes one softmax expert a token over "
            f"every expert; n_experts_per_tok={cfg.n_experts_per_tok}, "
            f"routing {cfg.moe_routing}, a score-correction bias or a "
            "share of the experts need "
            "moe_impl='dropless' (serving) or 'dense' (the oracle)")
    return moe.switch_moe(
        x, p["router"], p["w_gate"].astype(cfg.dtype),
        p["w_up"].astype(cfg.dtype), p["w_down"].astype(cfg.dtype),
        capacity_factor=cfg.capacity_factor, axis_name=cfg.moe_axis,
        return_aux=return_aux, dispatch=cfg.moe_dispatch)


def _mlp_block(x, p, cfg: TransformerConfig, moe_impl: Optional[str] = None,
               return_aux: bool = False, token_mask=None,
               return_counts: bool = False):
    """Residual MLP half of a layer, shared by forward, the pipeline, and
    the decode step.  Dense MLPs are bit-identical across all three; MoE
    decode/prefill take a dropless dispatch (the grouped products, or
    the every-expert oracle), so forward-vs-decode equivalence holds
    exactly when switch dispatch drops no tokens (capacity_factor
    >= n_experts guarantees that) and diverges by the dropped tokens'
    contributions otherwise — capacity drops are a training-time
    behavior, not part of the serving contract.  ``return_aux`` threads
    the MoE balance loss out (0 for dense MLPs so callers can accumulate
    unconditionally); ``return_counts`` the rows each expert was handed
    (dropless dispatch only; an empty vector for dense MLPs)."""
    with jax.named_scope("mlp"):
        m = _rmsnorm(x, p["ln2"], cfg.norm_eps)
        # a leading dense layer of an expert model has no router
        if cfg.n_experts > 1 and "router" in p:
            out = _moe_mlp(m, p, cfg, impl=moe_impl, return_aux=return_aux,
                           token_mask=token_mask,
                           return_counts=return_counts)
            if return_aux or return_counts:
                y, extra = out
                return x + y, extra
            return x + out
        if return_counts:
            return x + _dense_mlp(m, p, cfg), jnp.zeros((0,), jnp.int32)
        y = x + _dense_mlp(m, p, cfg)
        return (y, jnp.float32(0.0)) if return_aux else y


def _layer_body(x, p, cfg: TransformerConfig, return_aux: bool = False):
    x = x + _attention(_attn_norm(x, p, cfg), p, cfg)
    return _mlp_block(x, p, cfg, return_aux=return_aux)


def _remat(layer, cfg: TransformerConfig):
    if cfg.remat_policy == "full":
        return jax.checkpoint(layer)
    if cfg.remat_policy == "dots":
        from horovod_tpu.ops import attention as attn
        policies = jax.checkpoint_policies
        return jax.checkpoint(layer, policy=policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(attn.FLASH_OUT_NAME,
                                           attn.FLASH_LSE_NAME)))
    raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}; "
                     "expected 'full' or 'dots'")


def _head(params: Dict, cfg: TransformerConfig):
    """The vocabulary projection's weight as :func:`_lm_head` takes it:
    ``head`` ``(D, V)``, or the embedding ``(V, D)`` of a tied model."""
    return params["embed"] if cfg.tie_embeddings else params["head"]


def _lm_head(y, ln_f, head, cfg: TransformerConfig):
    """Final RMSNorm + vocabulary projection (f32 logits) — the ONE copy
    shared by forward, decode/prefill, and both pipeline schedules.
    ``head``: :func:`_head`'s."""
    with jax.named_scope("head"):
        h = _rmsnorm(y, ln_f, cfg.norm_eps)
        how = "bsd,vd->bsv" if cfg.tie_embeddings else "bsd,dv->bsv"
        logits = jnp.einsum(how, h, head.astype(cfg.dtype)).astype(
            jnp.float32)
        if cfg.head_multiplier != 1.0:
            logits = logits * cfg.head_multiplier
        return logits


def _xent_sum(logits, targets):
    """SUM of next-token cross-entropy over all positions (divide by the
    token count for a mean) — shared by loss_fn and the pipelines."""
    with jax.named_scope("loss"):
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1).squeeze(-1)
        return jnp.sum(logz - gold)


def forward(params: Dict, tokens, cfg: TransformerConfig,
            return_aux: bool = False):
    """Logits for next-token prediction.  ``tokens``: (B, S) int32.

    ``return_aux`` additionally returns the SUM over layers of the MoE
    load-balancing auxiliary loss (0.0 for dense models) — accumulated
    in the layer-scan carry."""
    _require_uniform(cfg, "forward")
    x = _embed(params, tokens, cfg)

    if return_aux:
        def layer(carry, p):
            x, aux = carry
            x, a = _layer_body(x, p, cfg, return_aux=True)
            return (x, aux + a), None
    else:
        def layer(x, p):
            return _layer_body(x, p, cfg), None

    if cfg.remat:
        layer = _remat(layer, cfg)
    carry = (x, jnp.float32(0.0)) if return_aux else x
    for stack in ("dense_layers", "layers"):   # the leading dense first
        if stack in params:
            carry, _ = _scan_layers(layer, carry, params[stack])
    if return_aux:
        return _lm_head(carry[0], params["ln_f"], _head(params, cfg),
                        cfg), carry[1]
    return _lm_head(carry, params["ln_f"], _head(params, cfg), cfg)


def loss_fn(params: Dict, batch: Dict, cfg: TransformerConfig):
    """Mean next-token cross-entropy.  ``batch = {tokens, targets}``.

    With ``cfg.moe_aux_coeff > 0`` on an MoE config, adds
    ``coeff * sum_over_layers(aux)`` — the Switch balance term that keeps
    the learned router from collapsing onto few experts."""
    _require_no_latent(cfg, "loss_fn (training: the backward)")
    if cfg.n_experts > 1 and cfg.moe_aux_coeff > 0.0:
        logits, aux = forward(params, batch["tokens"], cfg, return_aux=True)
        xent = _xent_sum(logits, batch["targets"]) / batch["targets"].size
        return xent + cfg.moe_aux_coeff * aux
    logits = forward(params, batch["tokens"], cfg)
    return _xent_sum(logits, batch["targets"]) / batch["targets"].size


def expert_load(params: Dict, tokens, cfg: TransformerConfig):
    """Routing observability: ``(n_layers, n_experts)`` fraction of tokens
    whose top-1 route lands on each expert, measured on the activations
    actually entering every MoE block.  Uniform rows (≈ 1/E) mean a
    balanced router; a collapsed router shows one column near 1.0 (and,
    under tight capacity, most tokens dropped).  Pair with
    ``cfg.moe_aux_coeff`` — the balance term that keeps this histogram
    flat during training."""
    if cfg.n_experts <= 1:
        raise ValueError("expert_load needs an MoE config (n_experts > 1)")
    _require_uniform(cfg, "expert_load")
    _require_no_latent(cfg, "expert_load")
    x = _embed(params, tokens, cfg)

    def layer(x, p):
        att = x + _attention(_attn_norm(x, p, cfg), p, cfg)
        m = _rmsnorm(att, p["ln2"], cfg.norm_eps)
        logits = (m.astype(jnp.float32).reshape(-1, cfg.d_model)
                  @ p["router"].astype(jnp.float32))
        frac = jax.nn.one_hot(
            jnp.argmax(logits, axis=-1), cfg.n_experts,
            dtype=jnp.float32).mean(0)
        return _mlp_block(att, p, cfg), frac

    _, fracs = _scan_layers(layer, x, params["layers"])
    return fracs


# --- autoregressive decoding (KV cache) ---------------------------------------


def serving_shardings(mesh, cfg: TransformerConfig, params=None):
    """``(param_shardings, cache_shardings)`` as ``NamedSharding`` trees
    for a tp serving mesh — the one-call recipe for
    :func:`sample_decode`'s ``cache_shardings`` plus the ``device_put``
    placement of restored params (see docs/inference.md).  ``params``:
    the tree to be placed, where it may be an engine's own
    (:func:`serving_param_specs`)."""
    from jax.sharding import NamedSharding

    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), serving_param_specs(
            cfg, params=params),
        is_leaf=lambda x: isinstance(x, P))
    cache_sh = {k: NamedSharding(mesh, s) for k, s in cache_specs().items()}
    return param_sh, cache_sh


def serving_param_specs(cfg: TransformerConfig, axes=("tp",),
                        params=None) -> Dict:
    """:func:`param_specs` restricted to the mesh axes available at
    SERVING time (default a tp-only mesh): any training-only axis (pp,
    fsdp, ep, ...) is replicated, so a model trained with tp>1 restores
    onto a tp serving mesh without resharding logic — heads/ffn/vocab
    stay sharded, everything else replicates.

    ``params``: the tree the specs are for.  A leaf whose heads and head
    size are ONE axis there (:func:`lay_out_projections`) takes its spec
    with the two joined — the head axis' entry, so each shard holds
    whole, contiguous heads as before."""
    def keep(spec):
        return P(*[a if a in axes else None for a in spec])

    def fit(spec, leaf):
        if len(spec) == leaf.ndim + 1 and spec[-1] is None:
            return P(*spec[:-1])
        return spec

    specs = jax.tree_util.tree_map(
        keep, param_specs(cfg), is_leaf=lambda x: isinstance(x, P))
    if params is None:
        return specs
    return jax.tree_util.tree_map(
        fit, specs, params, is_leaf=lambda x: isinstance(x, P))


def cache_specs() -> Dict:
    """KV-cache shardings for tp serving: the cache's kv-head dim shards
    over ``tp`` (cache layout ``(L, B, H_kv, T, Dh)``), matching the
    head-sharded K/V projections so no resharding happens on the decode
    hot path.  Requires ``cfg.kv_heads % tp == 0``.

    The same specs cover :func:`prefill` / :func:`prefill_with_prefix`
    OUTPUT blocks (``(L, K, H_kv, bucket, Dh)`` — axis 1 is the
    admission batch instead of the slot pool, but the sharded axis is
    the same H_kv dim), so a sharded prefill lands into a sharded page
    pool with a purely local scatter."""
    return {
        "k": P(None, None, "tp", None, None),
        "v": P(None, None, "tp", None, None),
        "pos": P(),
    }


def paged_pool_specs(quantized: bool = False) -> Dict:
    """Page-pool shardings for tp serving: the pool's kv-head dim
    shards over ``tp`` (pool layout ``(L, P, H_kv, page, Dh)``) —
    pages are sharded BY HEAD, never by page id, so the page table
    stays replicated host data and grants/COW/attach need no
    sharding awareness at all.  int8 pools' per-vector scales
    (``(L, P, H_kv, page)``) ride the identical head split.  Per-slot
    ``pos`` is replicated (tick data, like the table)."""
    specs = {
        "k": P(None, None, "tp", None, None),
        "v": P(None, None, "tp", None, None),
        "pos": P(),
    }
    if quantized:
        specs["k_scale"] = P(None, None, "tp", None)
        specs["v_scale"] = P(None, None, "tp", None)
    return specs


def paged_kernel_specs(quantized: bool = False):
    """Operand/result PartitionSpecs for the fused paged-attention
    kernel under a tp mesh — the ONE ordering contract
    :func:`_paged_kernel_attend`'s ``shard_map`` and
    :meth:`~horovod_tpu.serving.sharding.ServingSharding.
    paged_kernel_shardings` both read.  The kernel's grid is
    per-(slot, kv-head) with no cross-head communication, so grouped
    queries, the STACKED pool ``(L, P, H_kv, page, Dh)`` and int8 scales
    all split at the kv-head dim over ``tp`` while the page table, the
    per-slot limits and the layer's index stay replicated host data;
    outputs come back head-sharded, matching the out-projection that
    consumes them.  Returns ``(in_specs, out_specs)`` ordered as ``(q,
    k_pool, v_pool[, k_scale, v_scale], table, limit, layer)`` / ``(o,
    lse)``."""
    head = P(None, "tp", None, None)
    pool = P(None, None, "tp", None, None)
    scale = P(None, None, "tp", None)
    in_specs = (head, pool, pool)
    if quantized:
        in_specs = in_specs + (scale, scale)
    return in_specs + (P(), P(), P()), (head, P(None, "tp", None))


def prefix_kv_specs():
    """Sharding for a gathered shared-prefix block
    (:func:`~horovod_tpu.serving.cache.gather_prefix_pages` output,
    ``(L, H_kv, n * page, Dh)``): head dim over ``tp``, matching the
    pool it was gathered from and the suffix prefill that attends it."""
    return P(None, "tp", None, None)


def shard_params(params: Dict, mesh, cfg: TransformerConfig) -> Dict:
    """Place a parameter tree on a serving mesh per
    :func:`serving_param_specs` (heads/ffn/vocab over ``tp``,
    everything else replicated) — the one-call placement for an engine
    or a restored checkpoint.  The sharding tree itself comes from
    :func:`serving_shardings` (the ONE spec→NamedSharding mapping)."""
    param_sh, _ = serving_shardings(mesh, cfg, params)
    return jax.device_put(params, param_sh)


def shard_kv_pool(pool: Dict, mesh) -> Dict:
    """Place a paged KV pool (:func:`~horovod_tpu.serving.cache.
    init_page_pool`) on a serving mesh per :func:`paged_pool_specs` —
    head-dim sharded payload (and int8 scales), replicated ``pos``."""
    from jax.sharding import NamedSharding

    specs = paged_pool_specs(quantized="k_scale" in pool)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in pool.items()}


def init_cache(cfg: TransformerConfig, batch: int, max_len: int = 0) -> Dict:
    """Per-layer KV cache for autoregressive decoding.

    Shapes are STATIC — ``(L, B, H_kv, T, Dh)`` in ``cfg.dtype`` with a
    traced write position — so the decode step compiles once and every
    token reuses the executable (the XLA-friendly formulation; no
    growing arrays).  GQA (``n_kv_heads``) shrinks the cache by
    ``n_heads / kv_heads`` — the serving-memory lever."""
    T = max_len or cfg.max_seq
    # what a full layer pages (:data:`LAYER_KINDS`: K and V; a latent
    # model's rows alone, and its index keys), a row a head here
    flat = dataclasses.replace(cfg, kv_lane_dense=False)
    cache = {n: jnp.zeros((cfg.n_layers, batch, row(flat)[0], T,
                           row(flat)[1]), cfg.dtype)
             for n, row in cfg.kind("full").paged.items()}
    return {**cache, "pos": jnp.zeros((), jnp.int32)}


def _cache_attend(qh, k_cache, v_cache, mask, scale=None):
    """One query token per row against the full cache — the ONE copy of
    the decode attention math, shared by the scalar-position path
    (:func:`_attention_decode`) and the unfused paged path
    (:func:`_attention_decode_paged`, on each slot's gathered pages) so
    the bandwidth discipline cannot fork.  ``mask`` is broadcastable to
    ``(B, H_kv, G, T)``.  ``scale`` replaces ``1 / sqrt(Dh)`` (latent
    attention's absorbed form: one kv head, its value the key's first
    lanes, the scale :attr:`TransformerConfig.mla_scale`).

    Bandwidth discipline (decode is cache-bandwidth-bound): the cache is
    dotted IN ITS STORED DTYPE with f32 MXU accumulation
    (``preferred_element_type``) — an ``astype(f32)`` here materializes
    a 2× copy of the whole cache per token, and GQA expansion is done by
    GROUPING THE QUERIES (``(B, H_kv, G, ...)``) instead of broadcasting
    K/V to ``H`` — together these were a measured 3.6× decode
    throughput on chip.  For f32 caches the math is bit-identical to the
    upcast formulation; for bf16 caches the products round to bf16
    (standard TPU practice; accumulation stays f32)."""
    B, H, _, Dh = qh.shape
    Hkv = k_cache.shape[1]
    G = H // Hkv
    with jax.named_scope("attn"):
        qg = qh.reshape(B, Hkv, G, Dh)              # one token: drop q dim
        s = jnp.einsum("bkgd,bktd->bkgt", qg.astype(k_cache.dtype), k_cache,
                       preferred_element_type=jnp.float32)
        s = s / np.sqrt(Dh) if scale is None else s * scale
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgt,bktd->bkgd", w.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, H, 1, v_cache.shape[-1])


def _attention_decode(x, p, cfg: TransformerConfig, k_cache, v_cache, pos):
    """One-token attention against the cache: write this position's K/V
    at ``pos``, attend q over positions <= pos (static-shape mask; the
    attention math itself lives in :func:`_cache_attend`).  Latent
    attention has no ``v_cache``: the row is written to ``k_cache`` and
    attended absorbed; with an indexer ``v_cache`` is the index keys'
    cache ``(B, 1, T, index_head_dim)``."""
    if cfg.latent:
        q_nope, q_rope, cq = _mla_q(x, p, cfg, pos, with_cq=True)
        q = _mla_absorb_q(q_nope, q_rope, p, cfg)       # (B, 1, H, 640)
        with jax.named_scope("kv_write"):
            k_cache = lax.dynamic_update_slice_in_dim(
                k_cache, _mla_kv(x, p, cfg, pos)[:, None].astype(
                    k_cache.dtype), pos, axis=2)
        T = k_cache.shape[2]
        if cfg.sparse:
            from horovod_tpu.ops import paged_attention as _pa

            qi, ki, w = _dsa_proj(x, cq, p, cfg, pos)
            with jax.named_scope("kv_write"):
                v_cache = lax.dynamic_update_slice_in_dim(
                    v_cache, ki[:, None].astype(v_cache.dtype), pos, axis=2)
            with jax.named_scope("hvd_dsa_score"):
                scores = _pa.index_scores_dense(qi[:, 0], w[:, 0],
                                                v_cache[:, 0])
            o = _dsa_select_attend(
                q[:, 0], scores, jnp.full(scores.shape[:1], pos + 1),
                lambda i: jnp.take_along_axis(k_cache[:, 0], i[..., None],
                                              axis=1), cfg, kernel=False)
            return _mla_out(o[:, None], p, cfg,
                            absorbed=True), k_cache, v_cache
        mask = (lax.broadcasted_iota(jnp.int32, (T,), 0) <= pos)
        o = _cache_attend(jnp.moveaxis(q, 1, 2), k_cache,
                          k_cache[..., :cfg.kv_lora_rank],
                          mask[None, None, None, :], cfg.mla_scale)
        return _mla_out(jnp.moveaxis(o, 1, 2), p, cfg,
                        absorbed=True), k_cache, None
    qh, k_t, v_t = _qkv_proj(x, p, cfg, pos)        # qh: (B, H, 1, Dh)
    with jax.named_scope("kv_write"):
        k_cache = lax.dynamic_update_slice_in_dim(
            k_cache, k_t.astype(k_cache.dtype), pos, axis=2)
        v_cache = lax.dynamic_update_slice_in_dim(
            v_cache, v_t.astype(v_cache.dtype), pos, axis=2)
    T = k_cache.shape[2]
    mask = (lax.broadcasted_iota(jnp.int32, (T,), 0) <= pos)
    o = _cache_attend(qh, k_cache, v_cache, mask[None, None, None, :])
    return _out_proj(o.astype(cfg.dtype), p, cfg), k_cache, v_cache


def decode_step(params: Dict, tokens_t, cache: Dict, cfg: TransformerConfig):
    """One autoregressive step.

    ``tokens_t``: (B,) int32 — the token at position ``cache["pos"]``.
    Returns ``(logits (B, V) float32, updated cache)``; the logits match
    :func:`forward`'s at that position exactly (teacher-forcing
    equivalence, ``tests/test_models.py``).  The reference has no decode
    path (it is a training framework); this completes the serving story
    of docs/inference.md with a TPU-idiomatic static-shape cache.

    CONTRACT: at most ``max_len`` (the cache's static T) calls per
    cache — past capacity, ``dynamic_update_slice`` clamps the write to
    the last slot and output silently degrades.  Eager misuse raises;
    under jit the position is traced, so callers must size the cache
    (``init_cache(max_len=prompt + steps)``, as greedy_decode does)."""
    _require_uniform(cfg, "decode_step")
    pos = cache["pos"]
    T_cache = cache["k"].shape[3]
    if not isinstance(pos, jax.core.Tracer) and int(pos) >= T_cache:
        raise ValueError(
            f"decode_step past cache capacity (pos {int(pos)} >= "
            f"{T_cache}); init_cache with a larger max_len")
    x = _embed(params, tokens_t, cfg)[:, None]  # (B, 1, D)

    names = tuple(cfg.kind("full").paged)   # a latent cache has no V

    def layer(x, p, kind, kv):
        h, k_new, v_new = _attention_decode(
            _attn_norm(x, p, cfg), p, cfg, *(*kv, None)[:2], pos)
        return _mlp_block(x + h, p, cfg, moe_impl="dense"), (k_new, v_new)

    x, ys = _scan_layer_kinds(
        cfg, layer, x, params["layers"],
        {"full": tuple(cache[n] for n in names)},
        params.get("dense_layers"))
    logits = _lm_head(x, params["ln_f"], _head(params, cfg), cfg)
    return logits[:, 0], {**dict(zip(names, ys["full"])), "pos": pos + 1}


# --- paged KV cache (block tables resolved inside the tick) -------------------


_KV_QUANT_EPS = 1e-8


def kv_quantize(x):
    """Symmetric per-vector int8 quantization over the trailing head
    dim (the KIVI/KVQuant-style per-token granularity): each ``(..., Dh)``
    vector gets its own f32 scale, so a later write never has to
    re-quantize earlier positions — the scale is written once, by the
    same page write as the int8 payload, and write-before-attend carries
    over to quantized pages unchanged.  Returns ``(q int8, scale f32)``
    with ``scale`` lacking the trailing dim."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, _KV_QUANT_EPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale, dtype):
    """Inverse of :func:`kv_quantize`: ``q * scale`` cast to ``dtype``.

    PINNED compute dtype: the multiply happens in f32 — even when
    ``dtype`` is bf16 — and only the final cast narrows.  The fused
    paged-attention kernel replicates this exact f32-multiply-then-cast
    in its load (:data:`horovod_tpu.ops.paged_attention.DEQUANT_COMPUTE`
    is the single shared constant), so the unfused fallback and the
    fused path round int8 pages identically; change one and you must
    change both (``tests/test_paged.py`` pins the contract)."""
    return (q.astype(jnp.float32)
            * scale[..., None].astype(jnp.float32)).astype(dtype)


def _gather_pages(pool, layer, table):
    """Resolve one layer of a page pool through a page table: the
    stacked ``pool`` ``(L, P, H_kv, page, Dh)`` gathered at ``[layer,
    table]`` (``table`` ``(S, max_pages)``) -> the per-slot LOGICAL
    cache ``(S, H_kv, max_pages * page, Dh)``.  The layer is never cut
    out of the stack first: the gather's indices are the two leading
    dims.  The table is DATA (int32 indices), so the gather is one
    executable for every allocation pattern — pages can come, go, grow,
    and be shared without recompiling the tick."""
    S, max_pages = table.shape
    _, _, Hkv, ps, Dh = pool.shape
    g = pool[layer, table]                 # (S, max_pages, H_kv, ps, Dh)
    return jnp.moveaxis(g, 1, 2).reshape(S, Hkv, max_pages * ps, Dh)


def _gather_scales(scale, layer, table):
    """Scale companion of :func:`_gather_pages`: ``(L, P, H_kv, page)``
    -> ``(S, H_kv, max_pages * page)``."""
    S, max_pages = table.shape
    _, _, Hkv, ps = scale.shape
    g = scale[layer, table]                # (S, max_pages, H_kv, ps)
    return jnp.moveaxis(g, 1, 2).reshape(S, Hkv, max_pages * ps)


def _paged_kernel_attend(qg, k_pool, v_pool, k_scale, v_scale, layer,
                         table, limit, cfg: TransformerConfig, mesh=None,
                         lower=None):
    """Call the fused paged-attention kernel for layer ``layer`` of the
    STACKED pools, under ``shard_map`` when a tp mesh is given.

    The kernel takes the whole stack and the layer's index (a traced
    scalar): a custom call would otherwise make the layer scan cut its
    operand — a whole layer of the pool — out of the stack first.

    The kernel's grid is per-(slot, kv-head) with NO cross-head
    communication, so the tp=N head-sharded pool (``paged_pool_specs``)
    maps onto it shard-locally: each device runs the kernel over its
    own ``H_kv / tp`` heads against its own pool shard, with the table,
    the per-slot limits and the layer index replicated (host tick data).
    Outputs come back head-sharded, matching the projection that
    consumes them.  Without a mesh the kernel is called directly
    (single-device serving)."""
    from horovod_tpu.ops import paged_attention as _pa

    quantized = k_scale is not None
    if mesh is None:
        # rows that several heads share are scaled by the HEAD's width
        kw = ({"sm_scale": cfg.head_dim ** -0.5} if cfg.kv_pack > 1
              else {})
        return _pa.paged_attend(qg, k_pool, v_pool, k_scale, v_scale,
                                table, limit, compute_dtype=cfg.dtype,
                                lower=lower, layer=layer, **kw)
    if lower is not None:
        raise UnsupportedModelConfigError(
            "a window layer's paged kernel is not written for a tp mesh")

    from horovod_tpu import spmd

    in_specs, out_specs = paged_kernel_specs(quantized)
    scales = (k_scale, v_scale) if quantized else ()
    fn = spmd.shard(
        lambda q_, k_, v_, *rest: _pa.paged_attend(
            q_, k_, v_, *(rest[:-3] or (None, None)), *rest[-3:-1],
            compute_dtype=cfg.dtype, layer=rest[-1]),
        in_specs=in_specs, out_specs=out_specs, mesh=mesh)
    return fn(qg, k_pool, v_pool, *scales, table, limit, layer)


# --- how a body reaches a request's cached state ------------------------------
#
# A layer's mixer (:data:`LAYER_KINDS`, below) is written ONCE; the three
# served bodies differ only in how the cached state is reached.  A reach
# answers a mixer's four questions — ``attend`` (K/V pages), ``latent``
# (latent rows, with or without an indexer), ``conv`` and ``ssm`` (a
# per-slot state) — each with ``(output, *what the layer leaves)``.


class _Prompt:
    """The WHOLE prompt (:func:`prefill`): nothing is cached before it.
    Attention runs over the prompt itself — the flash forward, under
    ``shard_map`` on a tp ``mesh`` (GSPMD cannot partition a Mosaic
    kernel; attention is per head, and a contiguous tp split keeps every
    query head on the device that holds its KV head) — and a state
    starts from zeros and stands at each row's real length ``lens``."""

    positions = None    # 0 .. S0 - 1
    landed: Dict = {}   # the layer's arrays before these tokens, by name

    def __init__(self, cfg: TransformerConfig, lens=None, mesh=None):
        self.cfg, self.lens, self.mesh = cfg, lens, mesh

    def at(self, **layer):
        """This reach at one layer of the scan."""
        new = copy.copy(self)
        new.__dict__.update(layer)
        return new

    def conv(self, x, p, kind: LayerKind):
        return _conv_prefill(x, p, self.cfg,
                             *(self.landed.get(n) for n in kind.state),
                             self.lens)

    def ssm(self, n, p, kind: LayerKind):
        return _ssm_prefill(n, p, self.cfg,
                            *(self.landed.get(n) for n in kind.state),
                            self.lens)

    def lin(self, q, k, v, p, kind: LayerKind):
        return _lin_prefill(q, k, v, p, self.cfg,
                            *(self.landed.get(n) for n in kind.state),
                            self.lens)

    def select_attend(self, qh, kh, vh, kind: LayerKind):
        """Block-sparse attention over the prompt's own K/V, each query
        its selected blocks; what it leaves: K, V and the compressed
        rows of the pages it lands in."""
        S0 = qh.shape[2]
        oh, rows = _bsa_rows_attend(qh, kh, vh,
                                    jnp.arange(S0, dtype=jnp.int32), self.cfg)
        return oh, kh, vh, _bsa_landing_rows(rows, 0, S0, self.cfg)

    def attend(self, qh, kh, vh, kind: LayerKind):
        """Causal attention over the prompt's own (unexpanded, post-RoPE)
        K/V, which are also what it leaves: a window layer through the
        same kernel with the window's lower bound (blocks wholly behind
        it skipped).  The sequence-parallel impls need a bound mesh
        axis, so they prefill through the flash kernel (which takes the
        XLA form for untileable prompts)."""
        from horovod_tpu.ops import attention as attn

        reference = self.cfg.attention_impl == "reference"
        window = self.cfg.window if kind.window else 0

        def core(q, k, v):
            k, v = attn.expand_kv(k, q.shape[1]), attn.expand_kv(v, q.shape[1])
            if reference:
                return attn.reference_attention(q, k, v, causal=True,
                                                window=window)
            if window:
                return attn.flash_attention_windowed(q, k, v, window)
            return attn.flash_attention(q, k, v, True)

        if self.mesh is not None and not reference:
            from horovod_tpu import spmd

            head = P(None, "tp", None, None)
            core = spmd.shard(core, in_specs=(head, head, head),
                              out_specs=head, mesh=self.mesh)
        with jax.named_scope("attn"):
            return core(qh, kh, vh), kh, vh

    def latent(self, q_nope, q_rope, lat, index, p, kind: LayerKind):
        """Expanded, through the flash forward; with an indexer and a
        sequence longer than ``index_topk``, each query absorbed over
        its selected rows (:func:`_dsa_attend`)."""
        from horovod_tpu.ops import attention as attn

        cfg = self.cfg
        if self.mesh is not None:
            raise UnsupportedModelConfigError(
                "latent attention is not written for a tp mesh")
        if cfg.attention_impl not in ("reference", "flash"):
            raise UnsupportedModelConfigError(
                f"latent attention runs attention_impl 'flash' or "
                f"'reference', not {cfg.attention_impl!r}")
        S0 = lat.shape[1]
        if index is not None and S0 > cfg.index_topk:
            qi, ik, w = index     # else every query sees it all
            q = _mla_absorb_q(q_nope, q_rope, p, cfg)
            pos = jnp.arange(S0, dtype=jnp.int32)
            o = lax.map(lambda a: _dsa_attend(*a, pos, cfg), (
                q, qi, w, lat, ik))
            return (_mla_out(o, p, cfg, absorbed=True),
                    *_latent_rows(lat, index))
        k, v = _mla_expand(lat, p, cfg)
        qh = _mla_heads(q_nope, q_rope)
        with jax.named_scope("attn"):
            if cfg.attention_impl == "reference":
                oh = attn.reference_attention(qh, k, v, causal=True,
                                              sm_scale=cfg.mla_scale)
            else:
                oh = attn.flash_attention(qh, k, v, True, cfg.mla_scale)
        return (_mla_out(jnp.moveaxis(oh, 1, 2), p, cfg),
                *_latent_rows(lat, index))


class _Chunk(_Prompt):
    """A CHUNK of ``S0`` tokens behind ``prefix_len`` landed ones
    (:func:`prefill_with_prefix`): ``prefix`` holds what every layer
    kept for them, by the pool's names, ``landed`` one layer's share.
    Pages come as the pool stores them, gathered to ``P0 >=
    prefix_len`` positions (page-granular gathers round up: what lies
    at or past ``prefix_len`` is masked out, so page-tail junk is
    inert); a window layer's from logical position ``win_start`` on (a
    traced scalar: what lies behind the first query's window was never
    gathered), its query at ``i`` seeing key ``j`` iff ``j <= i`` and
    ``i - j < cfg.window``.  A state is each row's as the chunk before
    left it."""

    def __init__(self, cfg: TransformerConfig, lens, positions, p0,
                 prefix: Dict, win_start):
        super().__init__(cfg, lens)
        self.positions, self.p0, self.win_start = positions, p0, win_start
        kv = [k for k in cfg.kinds.values() if _kv_row in k.paged.values()]
        if cfg.kv_pack > 1:   # rows that several heads share, a row a head
            prefix = {n: _unpack_heads(a, cfg.kv_pack) if any(
                n in k.paged for k in kv) else a for n, a in prefix.items()}
        self.prefix, self._causal = prefix, None
        # (a block-sparse layer masks by its own selection)
        self.masks = {k: self._mask(k) for k in kv if not k.page_rows}

    def _mask(self, kind: LayerKind):
        """``(S0, P0 + S0)``: the real prefix visible (to a window layer,
        what of it lies within the row's window), the gather's padding
        (``>= p0``) never, and the chunk causal within itself."""
        p0, positions = self.p0, self.positions
        S0 = positions.shape[0]
        P0 = self.prefix[next(iter(kind.paged))].shape[2]
        if not kind.window:
            pre = lax.broadcasted_iota(jnp.int32, (P0,), 0)[None, :] < p0
            pre = jnp.broadcast_to(pre, (S0, P0))
        if self._causal is None:
            self._causal = (lax.broadcasted_iota(jnp.int32, (S0, S0), 1)
                            <= lax.broadcasted_iota(jnp.int32, (S0, S0), 0))
        own = self._causal
        if kind.window:
            # the block's column j is logical position win_start + j
            W = self.cfg.window
            kpos = (jnp.asarray(self.win_start, jnp.int32)
                    + lax.broadcasted_iota(jnp.int32, (P0,), 0))
            pre = ((kpos[None, :] < p0)
                   & (positions[:, None] - kpos[None, :] < W))
            rows = lax.broadcasted_iota(jnp.int32, (S0, S0), 0)
            cols = lax.broadcasted_iota(jnp.int32, (S0, S0), 1)
            own = own & (rows - cols < W)
        return jnp.concatenate([pre, own], axis=1)[None, None, None]

    def attend(self, qh, kh, vh, kind: LayerKind):
        """The chunk's queries against the landed K/V and their own,
        under the kind's mask — grouped-query attention with the same
        bandwidth discipline as :func:`_cache_attend`, S0 queries wide."""
        pk, pv = (self.landed[n] for n in kind.paged)
        (K, H, S0, Dh), (Hkv, P0) = qh.shape, pk.shape[:2]
        with jax.named_scope("chunk_attn"):
            k_full = jnp.concatenate(
                [jnp.broadcast_to(pk[None].astype(kh.dtype),
                                  (K, Hkv, P0, Dh)), kh], axis=2)
            v_full = jnp.concatenate(
                [jnp.broadcast_to(pv[None].astype(vh.dtype),
                                  (K, Hkv, P0, Dh)), vh], axis=2)
            qg = qh.reshape(K, Hkv, H // Hkv, S0, Dh)
            s = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(k_full.dtype),
                           k_full, preferred_element_type=jnp.float32
                           ) / np.sqrt(Dh)
            s = jnp.where(self.masks[kind], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bkgst,bktd->bkgsd", w.astype(v_full.dtype),
                           v_full, preferred_element_type=jnp.float32)
            oh = o.reshape(K, H, S0, Dh)
        return oh.astype(self.cfg.dtype), kh, vh

    def select_attend(self, qh, kh, vh, kind: LayerKind):
        """The chunk's rows laid behind the landed ones AT ``p0`` (row
        ``j`` of the whole is logical position ``j``), each query its
        selected blocks of prefix + chunk; the compressed rows are read
        off the keys so laid, the landed ones' with them."""
        K, S0 = qh.shape[0], qh.shape[2]

        def behind(prefix, own):
            with jax.named_scope("chunk_attn"):
                rows = jnp.broadcast_to(prefix[None].astype(own.dtype),
                                        (K,) + prefix.shape)
                rows = jnp.pad(rows, ((0, 0), (0, 0), (0, S0), (0, 0)))
                return lax.dynamic_update_slice_in_dim(rows, own, self.p0, 2)

        pk, pv = (self.landed[n] for n in kind.paged)
        oh, rows = _bsa_rows_attend(qh, behind(pk, kh), behind(pv, vh),
                                    self.positions, self.cfg)
        return oh, kh, vh, _bsa_landing_rows(rows, self.p0, S0, self.cfg)

    def latent(self, q_nope, q_rope, lat, index, p, kind: LayerKind):
        """The landed rows ``(1, P0, latent_row)`` attended EXPANDED, in
        blocks (:func:`_mla_chunk_attend`); with an indexer, once the
        chunk's last query sees more than ``index_topk`` positions, each
        query its selected rows, absorbed (:func:`_dsa_chunk_attend`)."""
        cfg = self.cfg
        rows = [self.landed[n][0] for n in kind.paged]
        if (index is not None and rows[0].shape[0] + lat.shape[1]
                > cfg.index_topk):
            qi, ik, w = index
            o = _dsa_chunk_attend(
                _mla_absorb_q(q_nope, q_rope, p, cfg), qi, w, lat, ik,
                *rows, self.p0, cfg)
            out = _mla_out(o, p, cfg, absorbed=True)
        else:
            out = _mla_out(_mla_chunk_attend(
                q_nope, q_rope, lat, rows[0], self.p0, p, cfg), p, cfg)
        return (out, *_latent_rows(lat, index))


class _Tick(_Prompt):
    """One token a slot (:func:`decode_step_paged`): ``pools`` are the
    STACKED arrays of the layer scan's carry and ``layer`` this layer's
    (traced) index among its kind's — every write lands at ``[layer,
    page]`` or ``[layer, slot]`` and every read takes ``[layer,
    table]`` (or, of what lies by slot, a layer as the operand of the
    product that reads it), so no operation cuts a layer out of a stack
    or has a result of its size.  Row ``s`` writes its K/V (or latent row) at
    logical position ``pos[s]`` — through the page table to ``(page
    table[s, pos // page], offset pos % page)`` — BEFORE it attends
    positions ``<= pos[s]``, so the attend sees exactly the pool's
    state, under the fused kernels (``kernel``; under ``shard_map`` on a
    tp ``mesh``) and the gathers alike.

    Inactive rows are routed to physical page 0, the reserved NULL/
    trash page no live slot's table ever maps below its own position:
    a stale write could land in a page that has since been re-granted
    or shared, so the inactive scribble is not merely harmless-by-
    overwrite — it must be (and is) aimed somewhere no one attends.
    Active rows never collide: the host allocator guarantees every
    active slot's write page is PRIVATE (refcount 1; copy-on-write
    splits a shared page before any write targets it).  A window
    layer's pages lie under ``wtable``, and its row attends positions
    ``pos[s] - window < t <= pos[s]`` only — the entries behind that
    may already be released."""

    def __init__(self, cfg: TransformerConfig, table, wtable, pos, active,
                 kernel, mesh):
        super().__init__(cfg, mesh=mesh)
        self.table, self.wtable, self.pos = table, wtable, pos
        self.active, self.kernel = active, kernel

    @property
    def positions(self):
        return self.pos[:, None]

    def conv(self, x, p, kind: LayerKind):
        return _conv_decode(x, p, self.cfg,
                            *(self.pools[n] for n in kind.state),
                            self.layer, self.active)

    def ssm(self, n, p, kind: LayerKind):
        return _ssm_decode(n, p, self.cfg,
                           *(self.pools[n] for n in kind.state),
                           self.layer, self.active, self.kernel)

    def lin(self, q, k, v, p, kind: LayerKind):
        y, states = _lin_decode(q[:, 0], k[:, 0], v[:, 0], p,
                                *(self.pools[n] for n in kind.state),
                                self.layer, self.active, self.kernel)
        return y[:, None], states

    def select_attend(self, qh, k_t, v_t, kind: LayerKind):
        """K and V written as :meth:`attend` writes them, and the row of
        the page that the write FILLS (from the pool's own two pages) at
        the page's logical index of the slot's rows; then the slot's
        compressed rows scored AS THEY LIE, the blocks selected, their
        pages compacted into a table a slot and KV head and attended by
        the fused kernel — the pool read by BLOCK."""
        from horovod_tpu.ops import paged_attention as _pa

        cfg, pos, table, layer = self.cfg, self.pos, self.table, self.layer
        k_pool, v_pool = (self.pools[n] for n in kind.paged)
        (ck,) = (self.pools[n] for n in kind.page_rows)
        S, H, _, Dh = qh.shape
        Hkv, ps = k_pool.shape[2:4]
        blk, m = cfg.bsa_block, cfg.bsa_block // cfg.bsa_stride
        max_pages = table.shape[1]
        if ps != cfg.bsa_stride:
            raise UnsupportedModelConfigError(
                f"a block-sparse layer's page is its compressed keys' "
                f"stride ({cfg.bsa_stride}), not {ps}")
        if ck.shape[1:4] != (S, Hkv, max_pages):
            raise ValueError(
                f"the compressed keys lie by slot, {ck.shape[1:4]}, under a "
                f"table of {(S, Hkv, max_pages)}: init_page_pool is told "
                f"the table's width")
        with jax.named_scope("kv_write"):
            phys, take = self._target(table, ps)
            k_pool = _pa.write_pages(k_pool, layer, phys, k_t, take)
            v_pool = _pa.write_pages(v_pool, layer, phys, v_t, take)
            # the window that ends with this page, once the page is
            # full; an idle row's, or one whose page is not, goes to the
            # slot's own row 0, which no query reads (the NULL page's part)
            at = jnp.clip(pos // ps, 0, max_pages - 1)
            before = table[jnp.arange(S), jnp.maximum(at - 1, 0)]
            row = _bsa_window_mean(k_pool[layer, before], k_pool[layer, phys],
                                   cfg)                       # (S, Hkv, Dh)
            full = self.active & (pos % ps == ps - 1)
            ck = ck.at[layer, jnp.arange(S)[:, None], jnp.arange(Hkv),
                       jnp.where(full, at, 0)[:, None]].set(
                row.astype(ck.dtype))
        qg = qh.reshape(S, Hkv, H // Hkv, 1, Dh)
        live = jnp.where(self.active, pos, -1)
        with jax.named_scope("hvd_bsa_score"):
            score = _bsa_block_scores(qg, ck[layer], live[:, None],
                                      -(-max_pages // m), cfg)
        R = S * Hkv
        with jax.named_scope("hvd_bsa_select"):
            chosen, n_sel = _bsa_chosen(
                score.reshape(R, -1), jnp.repeat(jnp.maximum(live, 0), Hkv),
                cfg)
        with jax.named_scope("hvd_bsa_attend"):
            page = (chosen[:, :, None] * m + jnp.arange(m, dtype=jnp.int32)
                    ).reshape(R, -1)
            compact = _pa.pages_of(jnp.repeat(table, Hkv, axis=0),
                                   jnp.minimum(page, max_pages - 1) * ps, ps)
            limit = jnp.where(jnp.repeat(self.active, Hkv),
                              (n_sel - 1) * blk + jnp.repeat(pos, Hkv) % blk
                              + 1, 0)
            attend = (_pa.paged_attend if self.kernel
                      else _pa.paged_attend_reference)
            o, _ = attend(qg[:, :, :, 0], k_pool, v_pool, None, None,
                          compact.reshape(S, Hkv, -1), limit.reshape(S, Hkv),
                          layer=layer)
        return (o.reshape(S, H, 1, Dh).astype(cfg.dtype), k_pool, v_pool, ck)

    def _target(self, table, ps: int):
        """Where each row's one position goes: ``(physical page, the
        page's offsets that take it)``."""
        S, max_pages = table.shape
        idx = jnp.clip(self.pos // ps, 0, max_pages - 1)
        phys = jnp.where(self.active, table[jnp.arange(S), idx], 0)
        take = jnp.arange(ps, dtype=jnp.int32) == (self.pos % ps)[:, None]
        return phys, take

    def attend(self, qh, k_t, v_t, kind: LayerKind):
        """An int8 pool's per-(head, position) f32 scales are written
        with the payload, which is dequantized AFTER the gather (or in
        the kernel's load): only the logical view, never the whole pool,
        exists at compute dtype."""
        from horovod_tpu.ops import paged_attention as _pa

        cfg, pos = self.cfg, self.pos
        stacks = [self.pools[n] for n in (*kind.paged, *kind.scales)
                  if n in self.pools]
        table = self.wtable if kind.window else self.table
        rows = (_pack_heads(k_t, cfg.kv_pack), _pack_heads(v_t, cfg.kv_pack))
        lower = (jnp.maximum(pos - cfg.window + 1, 0) if kind.window
                 else None)

        with jax.named_scope("kv_write"):
            phys, take = self._target(table, stacks[0].shape[3])
            if len(stacks) == 4:           # ... and the (S, H_kv, 1) scales
                (qk, sk), (qv, sv) = kv_quantize(k_t), kv_quantize(v_t)
                rows = (qk, qv, sk, sv)
            stacks = tuple(
                _pa.write_pages(stack, self.layer, phys, row, take)
                for stack, row in zip(stacks, rows))
        with jax.named_scope("paged_attend"):
            o = _paged_decode_attend(qh, *stacks, *(None,) * (4 - len(stacks)),
                                     self.layer, table, pos, self.active,
                                     cfg, self.kernel, self.mesh, lower)
        return (o.astype(cfg.dtype), *stacks)

    def latent(self, q_nope, q_rope, lat, index, p, kind: LayerKind):
        """ABSORBED: the row written is the row read, as it lies — by
        the latent walk; with an indexer, by the index walk over the
        slot's live tokens, the selection, then the attend over the
        selected rows alone: the pool read BY TOKEN, ``(table[s, t //
        page], t % page)``."""
        from horovod_tpu.ops import paged_attention as _pa

        cfg, table, layer = self.cfg, self.table, self.layer
        q = _mla_absorb_q(q_nope, q_rope, p, cfg)[:, 0]  # (S, H, 640)
        stacks = [self.pools[n] for n in kind.paged]
        rows = _latent_rows(lat, index)
        with jax.named_scope("kv_write"):
            phys, take = self._target(table, stacks[0].shape[3])
            stacks = tuple(_pa.write_pages(stack, layer, phys, row, take)
                           for stack, row in zip(stacks, rows))
        if index is None:
            with jax.named_scope("paged_attend"):
                limit = jnp.where(self.active, self.pos + 1, 0)
                attend = (_pa.mla_decode if self.kernel
                          else _pa.mla_decode_reference)
                o_lat, _ = attend(q, stacks[0], table, limit, layer=layer,
                                  v_dim=cfg.kv_lora_rank,
                                  sm_scale=cfg.mla_scale)
        else:
            qi, _, w = index
            with jax.named_scope("hvd_dsa_score"):
                limit = jnp.where(self.active, self.pos + 1, 0)
                walk = (_pa.index_scores if self.kernel
                        else _pa.index_scores_reference)
                scores = walk(qi[:, 0], w[:, 0], stacks[1], table, limit,
                              layer=layer)
            flat = stacks[0].reshape((-1,) + stacks[0].shape[-1:])
            n_pg, ps = stacks[0].shape[1], stacks[0].shape[3]

            def gather(idx):            # the rows, as they lie
                page = _pa.pages_of(table, idx, ps)
                return flat[(layer * n_pg + page) * ps + idx % ps]

            o_lat = _dsa_select_attend(q, scores, limit, gather, cfg,
                                       self.kernel)
        return (_mla_out(o_lat[:, None], p, cfg, absorbed=True), *stacks)


# --- the mixers, one a kind ----------------------------------------------------


def _kv_attention(n, p, cfg: TransformerConfig, kind: LayerKind, reach):
    """Attention over K/V pages, of a layer's NORMED input ``n``."""
    qh, kh, vh = _qkv_proj(n, p, cfg, positions=reach.positions,
                           kind="sliding" if kind.window else "full")
    oh, *new = reach.attend(qh, kh, vh, kind)
    return _out_proj(oh, p, cfg), tuple(new)


def _kv_mixer(x, p, cfg: TransformerConfig, kind: LayerKind, reach):
    return _kv_attention(_attn_norm(x, p, cfg), p, cfg, kind, reach)


def _hybrid_mixer(x, p, cfg: TransformerConfig, kind: LayerKind, reach):
    """Attention AND the state-space mixer side by side on ONE normed
    input, summed under their multipliers (:func:`_mix`)."""
    n = _attn_norm(x, p, cfg)
    h, rows = _kv_attention(_attn_in(n, cfg), p, cfg, kind, reach)
    hs, *state = reach.ssm(n, p, kind)
    return _mix(h, hs, cfg), rows + tuple(state)


def _conv_mixer(x, p, cfg: TransformerConfig, kind: LayerKind, reach):
    h, *state = reach.conv(x, p, kind)
    return h, tuple(state)


def _linear_mixer(x, p, cfg: TransformerConfig, kind: LayerKind, reach):
    n = _attn_norm(x, p, cfg)
    q, k, v, g = _lin_in(n, p, cfg, reach.positions)
    y, state = reach.lin(q, k, v, p, kind)
    return _lin_out(y, g, p, cfg), (state,)


def _bsa_mixer(x, p, cfg: TransformerConfig, kind: LayerKind, reach):
    """Block-sparse attention with its output gate: ``(o * sigmoid(n
    W_g)) W_o`` under the layer's output multiplier."""
    n = _attn_norm(x, p, cfg)
    qh, kh, vh = _qkv_proj(n, p, cfg, positions=reach.positions,
                           kind="block_sparse")
    oh, *new = reach.select_attend(qh, kh, vh, kind)
    with jax.named_scope("attn_out"):
        o = jnp.moveaxis(oh, 1, 2)                        # (B, S, H, Dh)
        g = jnp.einsum("bsd,dn->bsn", n, p["wg"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)
        o = o * jax.nn.sigmoid(g).reshape(o.shape)
        h = jnp.einsum("bshk,hkd->bsd", o.astype(cfg.dtype),
                       p["wo"].astype(cfg.dtype))
    return _out_scaled(h, cfg), tuple(new)


def _latent_rows(lat, index):
    """What a token leaves in a latent cache, shaped as the block of ONE
    kv head: its row, and with an indexer its index key."""
    return (lat[:, None],) + (() if index is None else (index[1][:, None],))


def _latent_attention(n, p, cfg: TransformerConfig, kind: LayerKind, reach):
    """Latent attention of a layer's NORMED input ``n``; the sparse kind
    projects the indexer's ``(q_I, k_I, w)`` beside."""
    q_nope, q_rope, cq = _mla_q(n, p, cfg, positions=reach.positions,
                                with_cq=True)
    lat = _mla_kv(n, p, cfg, positions=reach.positions)
    index = (_dsa_proj(n, cq, p, cfg, positions=reach.positions)
             if kind is LAYER_KINDS["sparse"] else None)
    h, *new = reach.latent(q_nope, q_rope, lat, index, p, kind)
    return h, tuple(new)


def _latent_mixer(x, p, cfg: TransformerConfig, kind: LayerKind, reach):
    return _latent_attention(_attn_norm(x, p, cfg), p, cfg, kind, reach)


def _kv_row(cfg: TransformerConfig):
    """A page's rows of K or V: one a KV head, or ``kv_pack`` narrow
    heads side by side in one."""
    return cfg.kv_heads // cfg.kv_pack, cfg.head_dim * cfg.kv_pack


def _taps(cfg: TransformerConfig):
    return cfg.conv_taps, cfg.conv_width


#: THE table of layer kinds: its mixer, and what a layer of each kind
#: keeps for a request by the page pool's names — pages of keys and
#: values (``k``/``v``, their scales when quantized; a window layer's a
#: pool of their own, ``wk``/``wv``), a latent layer's one row a token
#: (``k``) and an indexer's keys beside it (``ik``), a short
#: convolution's last inputs (``conv``, a slot), a state-space mixer's
#: matrix state (``ssm``, a slot).  The pattern's names first, in the
#: order programs take them; then what a ``full`` layer of a latent
#: model resolves to (:meth:`TransformerConfig.kind`).  A new kind is an
#: entry here and a reference in ``plain_reference.py``: the bodies and
#: the pool's allocation, landing and gather read this table.
LAYER_KINDS = {
    "full": LayerKind(_kv_mixer, {"k": _kv_row, "v": _kv_row},
                      ("k_scale", "v_scale")),
    "sliding": LayerKind(_kv_mixer, {"wk": _kv_row, "wv": _kv_row},
                         window=True),
    "conv": LayerKind(_conv_mixer, state={"conv": _taps}),
    "hybrid": LayerKind(
        _hybrid_mixer, {"k": _kv_row, "v": _kv_row},
        state={"conv": _taps, "ssm": lambda c: (
            c.ssm_heads, c.ssm_head_dim, c.ssm_state)}),
    "linear": LayerKind(
        _linear_mixer, f32=("lin",),
        state={"lin": lambda c: (c.n_heads, c.head_dim, c.head_dim)}),
    "block_sparse": LayerKind(
        _bsa_mixer, {"k": _kv_row, "v": _kv_row},
        page_rows={"ck": lambda c: (c.kv_heads, c.head_dim)}),
    "latent": LayerKind(_latent_mixer, {"k": lambda c: (1, c.latent_row)}),
    "sparse": LayerKind(_latent_mixer, {
        "k": lambda c: (1, c.latent_row),
        "ik": lambda c: (1, c.index_head_dim)}),
}

#: A window layer's arrays by the names they take in the pool of their
#: own (a full layer's: one allocator class serves both).
WINDOW_ARRAYS = dict(zip(LAYER_KINDS["sliding"].paged,
                         LAYER_KINDS["full"].paged))


def _paged_decode_attend(qh, k_pool, v_pool, k_scale, v_scale, layer, table,
                         pos, active, cfg: TransformerConfig, kernel, mesh,
                         lower=None):
    """The attend tail of :meth:`_Tick.attend` (after the write): the
    fused kernel, or gather -> dequant -> ``_cache_attend``; ``lower``
    is a window layer's first visible position."""
    max_pages = table.shape[1]
    ps = k_pool.shape[3]
    quantized = k_scale is not None
    B, H, _, Dh = qh.shape
    if kernel:
        # Fused path: attend positions <= pos ⇔ logical < pos + 1,
        # zeroed for inactive rows so their (NULL-page-routed) writes
        # are never attended.
        limit = jnp.where(active, pos + 1, 0)
        Hkv, n = k_pool.shape[2], cfg.kv_pack
        qg = qh.reshape(B, Hkv, H // Hkv, Dh)
        if n > 1:
            # a stored row is n heads' side by side: head a's queries
            # lie in ITS lanes, zeros in the others', so the row's
            # product is that head's alone — and of the output's lanes
            # each query reads its own head's
            G = H // (Hkv * n)
            lane = jnp.eye(n, dtype=qh.dtype)[:, None, :, None]
            qg = (qg.reshape(B, Hkv, n, G, 1, Dh) * lane).reshape(
                B, Hkv, n * G, n * Dh)
        o, _ = _paged_kernel_attend(qg, k_pool, v_pool, k_scale, v_scale,
                                    layer, table, limit, cfg, mesh, lower)
        if n > 1:
            o = o.reshape(B, Hkv, n, G, n, Dh)
            o = jnp.stack([o[:, :, a, :, a] for a in range(n)], axis=2)
        o = o.reshape(B, H, 1, Dh)
    else:
        kg, vg = _gather_kv(k_pool, v_pool, k_scale, v_scale, layer, table,
                            cfg)
        kg, vg = (_unpack_heads(a, cfg.kv_pack) for a in (kg, vg))
        T = max_pages * ps
        col = lax.broadcasted_iota(jnp.int32, (T,), 0)[None, :]
        mask = col <= pos[:, None]
        if lower is not None:
            mask &= col >= lower[:, None]
        o = _cache_attend(qh, kg, vg, mask[:, None, None, :])
    return o


def _gather_kv(k_pool, v_pool, k_scale, v_scale, layer, table,
               cfg: TransformerConfig):
    """The unfused attend's logical K/V of layer ``layer``, int8 pages
    dequantized after the gather."""
    kg = _gather_pages(k_pool, layer, table)
    vg = _gather_pages(v_pool, layer, table)
    if k_scale is not None:
        kg = kv_dequantize(kg, _gather_scales(k_scale, layer, table),
                           cfg.dtype)
        vg = kv_dequantize(vg, _gather_scales(v_scale, layer, table),
                           cfg.dtype)
    return kg, vg


def _pool_kinds(pool: Dict, cfg: TransformerConfig) -> Dict[str, LayerKind]:
    """``cfg.kinds``, once ``pool`` is seen to hold every array each
    declares, and scales only beside a kind that declares them (window
    layers keep their own unquantized pool)."""
    scaled = any(n in pool for k in LAYER_KINDS.values() for n in k.scales)
    for name, kind in cfg.kinds.items():
        lacks = [n for n in kind.block if n not in pool]
        if lacks or (scaled and kind.paged and not kind.scales):
            raise UnsupportedModelConfigError(
                f"a {name!r} layer keeps {kind.block} in the pool, "
                f"unquantized unless it declares scales; this pool lacks "
                f"{lacks}" + (" and is quantized" if scaled else ""))
    return cfg.kinds


def moe_load(counts):
    """``[rows, experts touched, largest expert's rows]`` summed over
    the layers, from the ``(L, E)`` rows each expert was handed."""
    return jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                      jnp.sum(jnp.max(counts, axis=-1))]).astype(jnp.int32)


def decode_step_paged(params: Dict, tokens_t, pool: Dict, table,
                      cfg: TransformerConfig, active, *, kernel=False,
                      mesh=None, wtable=None, return_moe_load=False):
    """One continuous-batching decode tick over a PAGED KV cache.

    ``pool``: the page pool (:func:`horovod_tpu.serving.cache.
    init_page_pool`) — ``k``/``v`` shaped ``(L, P, H_kv, page, Dh)``
    (plus ``k_scale``/``v_scale`` ``(L, P, H_kv, page)`` for int8
    storage) and per-slot ``pos`` ``(S,)``; ``table``: ``(S,
    max_pages)`` int32 page ids, logical position ``t`` of slot ``s``
    living at ``(table[s, t // page], t % page)``.  Shapes are static
    in S, P, and max_pages; the table and the live mask are DATA, so
    ONE compiled executable serves every allocation pattern — requests
    coming, going, growing pages, and sharing prefix pages never
    recompile the tick.  Inactive rows compute on zeros (the Join-style
    zero-substitution of ``horovod_tpu/join.py``) and their positions
    do not advance.  Row ``s`` of the logits equals
    :func:`decode_step`'s for the same request decoded alone at
    position ``pos[s]``, for any table that lays the slot's positions
    out in order (``tests/test_paged.py``).

    Returns ``(logits (S, V) float32, updated pool)`` — the table is
    host-owned and passed back unchanged.

    ``kernel=True`` routes every layer's attention through the fused
    Pallas kernels (:mod:`horovod_tpu.ops.paged_attention`); logits stay
    greedy-token-identical to the unfused path.  ``kernel``/``mesh`` are
    trace-time Python values: flipping them selects a DIFFERENT
    executable rather than recompiling an existing one.

    ``pool`` holds every array the configuration's kinds of layer
    declare (:data:`LAYER_KINDS`), each stacked over ITS kind's layers
    alone: pages under ``table`` — a window layer's under ``wtable``,
    whose pages behind ``pos - window`` may be released — and per-slot
    states ``(L_kind, S, ...)``, all read and written in place
    (:class:`_Tick`).  An expert model routes each
    active row to its ``n_experts_per_tok`` experts through the
    dropless grouped products — ``S * k`` expert rows a tick, idle
    slots none; ``return_moe_load`` adds :func:`moe_load` of the tick
    as a third result."""
    pos = pool["pos"]
    T_cap = table.shape[1] * pool["k"].shape[3]
    if not isinstance(pos, jax.core.Tracer) and not isinstance(
            active, jax.core.Tracer):
        over = np.asarray(active) & (np.asarray(pos) >= T_cap)
        if over.any():
            raise ValueError(
                f"decode_step_paged past table capacity (slots "
                f"{np.nonzero(over)[0].tolist()} at pos >= {T_cap}); "
                "init_page_pool with more pages per slot")
    x = _embed(params, tokens_t, cfg)[:, None]  # (S, 1, D)
    x = jnp.where(active[:, None, None], x, jnp.zeros_like(x))
    moe = cfg.n_experts > 1
    kinds = _pool_kinds(pool, cfg)
    reach = _Tick(cfg, table, wtable, pos, active, kernel, mesh)

    # The stacked pools are the scan's CARRY, beside x: loop state that
    # each layer writes in place at its own index.  As xs -> ys the scan
    # would cut every layer out of the stack and stack it back.
    def layer(carry, p, kind, i):
        x, pools = carry
        h, new = kinds[kind].mixer(x, p, cfg, kinds[kind],
                                   reach.at(pools=pools, layer=i))
        # (an unquantized pool has no scales: the zip stops short)
        pools = {**pools, **dict(zip(kinds[kind].arrays, new))}
        if not moe or "router" not in p:   # ... or a leading dense layer
            return (_mlp_block(x + h, p, cfg), pools), None
        # only the active rows' k picks are computed: S * k expert rows
        y, counts = _mlp_block(x + h, p, cfg, moe_impl="dropless",
                               token_mask=active, return_counts=True)
        return (y, pools), counts

    (x, pools), ys = _scan_layer_kinds(
        cfg, layer, (x, {n: pool[n] for k in kinds.values()
                         for n in k.arrays if n in pool}),
        params["layers"],
        {kind: jnp.arange(cfg.kind_count(kind), dtype=jnp.int32)
         for kind in kinds}, params.get("dense_layers"))
    logits = _lm_head(x, params["ln_f"], _head(params, cfg), cfg)
    out = {**pools, "pos": pos + active.astype(jnp.int32)}
    if not return_moe_load:
        return logits[:, 0], out
    load = (moe_load(jnp.concatenate([ys[k] for k in sorted(ys)])) if moe
            else jnp.zeros((3,), jnp.int32))
    return logits[:, 0], out, load


# --- speculative decoding (draft / verify multi-token ticks) ------------------
#
# Leviathan et al., "Fast Inference from Transformers via Speculative
# Decoding": draft K cheap tokens, verify them in ONE batched target
# forward, accept the agreeing prefix plus the target's correction token.
# Under GREEDY decoding the emitted tokens are ALWAYS the target's own
# argmax continuations — draft quality moves only the acceptance rate
# (tokens per tick), never the output — so byte-identity to the
# non-speculative path is a property of the verify kernel alone.


def draft_propose_paged(params: Dict, tokens_t, pool: Dict, table,
                        cfg: TransformerConfig, active, k: int, *,
                        kernel=False, mesh=None):
    """``k`` greedy draft tokens per slot from a (shallow) draft model:
    ``k + 1`` sequential :func:`decode_step_paged` steps in one trace —
    step ``i`` feeds the previous step's argmax, so the scan writes the
    draft's OWN K/V for every token it proposes (plus one extra step so
    the last draft's K/V lands too; its logits are discarded).  The
    draft pool's ``pos`` advances by ``k + 1`` — the caller rolls it
    back to the verified position, and write-before-attend makes the
    rejected tail's stale K/V inert (the next tick's draft overwrites
    position ``p`` before attending it, exactly the slot-reuse
    argument).  Returns ``(drafts (S, k) int32, updated draft pool)``."""

    def step(carry, _):
        tok, pl = carry
        logits, pl = decode_step_paged(params, tok, pl, table, cfg, active,
                                       kernel=kernel, mesh=mesh)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, pl), nxt

    (_, pool), ds = lax.scan(step, (tokens_t, pool), None, length=k + 1)
    return jnp.moveaxis(ds, 0, 1)[:, :k], pool


def ngram_propose(hist, pos, k: int):
    """Draft ``k`` tokens per slot by PROMPT LOOKUP (n-gram
    self-speculation — no second model): find the most recent earlier
    occurrence of the slot's final bigram in its committed token
    history and propose the ``k`` tokens that followed it.

    ``hist``: (S, T) committed tokens, position ``pos[s]`` holding slot
    ``s``'s last committed token; ``pos``: (S,) int32.  Slots with no
    earlier match (or fewer than two committed tokens) fall back to
    repeating the last token.  Entirely data-dependent gathers — one
    executable for every history.  Draft quality only moves the
    acceptance rate; the verify kernel owns correctness."""
    S, T = hist.shape
    rows = jnp.arange(S)
    last = hist[rows, jnp.clip(pos, 0, T - 1)]
    prev = hist[rows, jnp.clip(pos - 1, 0, T - 1)]
    iota = lax.broadcasted_iota(jnp.int32, (S, T), 1)
    nxt = jnp.concatenate([hist[:, 1:], jnp.zeros((S, 1), hist.dtype)],
                          axis=1)
    match = ((hist == prev[:, None]) & (nxt == last[:, None])
             & (iota + 1 < pos[:, None]))
    idx = jnp.max(jnp.where(match, iota, -1), axis=1)  # most recent
    found = idx >= 0
    gidx = (idx + 2)[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    drafts = jnp.take_along_axis(hist, jnp.clip(gidx, 0, T - 1), axis=1)
    # Gate the copy window to COMMITTED positions (<= pos): a match
    # near the end of history would otherwise draft uncommitted zeros
    # — on a pure repeat ("a a a a", where the most recent match ends
    # one short of the final bigram) that would cap acceptance at 1/k.
    # Past the committed region, fall back to repeating the last token
    # (exactly right for period-1 repeats, harmlessly wrong otherwise).
    ok = found[:, None] & (gidx <= pos[:, None])
    return jnp.where(ok, drafts, last[:, None])


def decode_verify_paged(params: Dict, window, pool: Dict, table,
                        cfg: TransformerConfig, active, spec_on=None,
                        sample=None, *, kernel=False, mesh=None,
                        replicated=None):
    """One batched W-position VERIFY forward over a paged cache — the
    speculative tick's target-model half.

    ``window``: (S, W) int32 — column 0 is each slot's last COMMITTED
    token, columns 1..W-1 its drafts.  The window runs as a
    prefill-style multi-position forward: query offset ``j`` (logical
    position ``pos[s] + j``) attends the slot's committed pages
    (positions ``< pos[s]``, gathered through the table exactly like
    :func:`decode_step_paged`) plus window offsets ``<= j``, with the
    window K/V attended AFTER a storage-dtype round trip (int8
    quantize-dequantize for quantized pools) so every position's logits
    are bit-identical to the sequential one-token path, which always
    reads its own K/V back from the pool.

    Acceptance is computed IN-KERNEL and is DATA: ``t = argmax`` per
    position is the target's greedy continuation, and ``acc[s]`` is the
    length of the agreeing draft prefix (``window[s, 1 + i] ==
    t[s, i]``), so a slot emits tokens ``t[s, 0..acc[s]]`` — the
    accepted drafts (identical to the target's own picks) plus the
    correction/bonus token.  Varying acceptance never recompiles.

    K/V is then scattered for ACCEPTED window offsets only (offset 0,
    the committed token, always writes): the rejected tail — and any
    position past the table's capacity — is routed to physical page 0,
    the reserved NULL/trash page, so a draft the target disagreed with
    can never contaminate a page another slot (or a COW prefix sharer)
    may come to own.  ``spec_on`` (optional (S,) bool) forces
    ``acc = 0`` for opted-out slots — they emit exactly the one greedy
    token per tick through the same executable.

    ``sample`` (optional ``(temperature, top_k, top_p, rng)`` per-slot
    columns — :func:`sample_token_rows`): rows with ``temperature > 0``
    replace the offset-0 token with a SAMPLED pick from the same
    logits (key index ``pos + 1``, the token's logical position — the
    identical schedule the plain tick and the oracle use) and have
    ``acc`` forced to 0: drafts are verified by argmax agreement, so a
    sampled stream never accepts them — it emits exactly one sampled
    token per tick through this executable, which is what lets mixed
    sampled/greedy-speculating batches share the program.
    ``replicated`` is :func:`sample_token_rows`'s: the sharding that
    holds the logits whole on every device of a tp mesh.

    Returns ``(target_tokens (S, W) int32, max_logits (S, W) f32,
    accepted (S,) int32, updated pool)`` with ``pos`` advanced by
    ``acc + 1`` per active slot.

    ``kernel=True`` splits each layer's attention into the fused Pallas
    kernel over the COMMITTED pages (positions ``< pos[s]``, streamed
    through VMEM with int8 dequant in the load) plus a dense causal
    pass over the W-wide window, merged by logsumexp — the standard
    flash-decoding cross-source combine.  The in-window K/V still takes
    its storage-dtype round trip first, so verify logits keep their
    bit-identity to the sequential one-token path."""
    _require_uniform(cfg, "decode_verify_paged")
    _require_no_latent(cfg, "decode_verify_paged (speculation)")
    pos = pool["pos"]
    S, W = window.shape
    max_pages = table.shape[1]
    ps = pool["k"].shape[3]
    T_cap = max_pages * ps
    quantized = "k_scale" in pool
    storage = pool["k"].dtype
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    G = H // Hkv

    x = _embed(params, window, cfg)  # (S, W, D)
    x = jnp.where(active[:, None, None], x, jnp.zeros_like(x))
    positions = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    # (S, 1, 1, W, T + W) mask: committed cache strictly below pos[s]
    # (page-tail junk and ungranted NULL-page garbage are >= pos, so
    # they are never attended), window causal within itself.
    cache_vis = (lax.broadcasted_iota(jnp.int32, (T_cap,), 0)[None, :]
                 < pos[:, None])
    cache_vis = jnp.broadcast_to(cache_vis[:, None, :], (S, W, T_cap))
    win_vis = (lax.broadcasted_iota(jnp.int32, (W, W), 1)
               <= lax.broadcasted_iota(jnp.int32, (W, W), 0))
    win_vis = jnp.broadcast_to(win_vis[None], (S, W, W))
    mask = jnp.concatenate([cache_vis, win_vis], axis=2)[:, None, None]
    # Committed-page limit for the fused kernel (strictly < pos, shared
    # by every window offset) — zeroed for inactive rows.
    climit = jnp.where(active, pos, 0)
    wmask = win_vis[:, None, None]              # (S, 1, 1, W, W)

    k_c, v_c = pool["k"], pool["v"]
    ks_c, vs_c = pool.get("k_scale"), pool.get("v_scale")

    # The scan only READS the pool (the window's K/V is written after
    # it, once acceptance is known): the stacks stay outside the scan's
    # xs and each layer attends ``[l, table]`` of them.
    def layer(x, inp):
        p, l = inp
        h = _attn_norm(x, p, cfg)
        qh, kh, vh = _qkv_proj(h, p, cfg, positions=positions)
        if quantized:
            qk, sk = kv_quantize(kh)
            qv, sv = kv_quantize(vh)
            kh_a = kv_dequantize(qk, sk, cfg.dtype)
            vh_a = kv_dequantize(qv, sv, cfg.dtype)
            ys = (qk, sk, qv, sv)
        else:
            kh_a = kh.astype(storage)
            vh_a = vh.astype(storage)
            ys = (kh_a, vh_a)
        qg = qh.reshape(S, Hkv, G, W, Dh)
        with jax.named_scope("paged_attend"):
            if kernel:
                # Fused kernel over the committed pages: W*G query rows per
                # (slot, kv-head) in one pass, pre-scatter pool (same state
                # the unfused gather reads), int8 dequant in the load.
                o_c, lse_c = _paged_kernel_attend(
                    qg.reshape(S, Hkv, G * W, Dh), k_c, v_c, ks_c, vs_c,
                    l, table, climit, cfg, mesh)
                o_c = o_c.reshape(S, Hkv, G, W, Dh)
                lse_c = lse_c.reshape(S, Hkv, G, W)
                # Dense causal attention within the window (post round-trip
                # K/V), kept unnormalized alongside its own logsumexp.
                sw = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(kh_a.dtype),
                                kh_a, preferred_element_type=jnp.float32
                                ) / np.sqrt(Dh)
                sw = jnp.where(wmask, sw, -1e30)
                mw = jnp.max(sw, axis=-1)           # (S, Hkv, G, W)
                pw = jnp.exp(sw - mw[..., None])
                lw = jnp.sum(pw, axis=-1)           # >= 1: diagonal visible
                o_w = jnp.einsum("bkgst,bktd->bkgsd", pw.astype(vh_a.dtype),
                                 vh_a, preferred_element_type=jnp.float32
                                 ) / lw[..., None]
                lse_w = mw + jnp.log(lw)
                # Cross-source LSE combine; a_c underflows to exactly 0 for
                # rows with no committed context (lse_c == NEG_INF).
                m = jnp.maximum(lse_c, lse_w)
                a_c = jnp.exp(lse_c - m)
                a_w = jnp.exp(lse_w - m)
                o = ((a_c[..., None] * o_c + a_w[..., None] * o_w)
                     / (a_c + a_w)[..., None])
            else:
                kg, vg = _gather_kv(k_c, v_c, ks_c, vs_c, l, table, cfg)
                k_full = jnp.concatenate([kg, kh_a], axis=2)  # (S,Hkv,T+W,Dh)
                v_full = jnp.concatenate([vg, vh_a], axis=2)
                # Grouped-query attention, W queries wide — _cache_attend's
                # bandwidth discipline (stored dtype, f32 MXU accumulation).
                sc = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(k_full.dtype),
                                k_full, preferred_element_type=jnp.float32
                                ) / np.sqrt(Dh)
                sc = jnp.where(mask, sc, -1e30)
                w = jax.nn.softmax(sc, axis=-1)
                o = jnp.einsum("bkgst,bktd->bkgsd", w.astype(v_full.dtype),
                               v_full, preferred_element_type=jnp.float32)
        out = _out_proj(o.reshape(S, H, W, Dh).astype(cfg.dtype), p, cfg)
        return _mlp_block(x + out, p, cfg, moe_impl="dense"), ys

    x, ys = _scan_layers(
        layer, x, (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    logits = _lm_head(x, params["ln_f"], params["head"], cfg)  # (S,W,V)
    t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    mx = jnp.max(logits, axis=-1)
    match = (window[:, 1:] == t[:, :-1]).astype(jnp.int32)
    acc = jnp.cumprod(match, axis=1).sum(axis=1)  # agreeing prefix len
    if spec_on is not None:
        acc = jnp.where(spec_on, acc, 0)
    if sample is not None:
        # Sampled rows: offset 0 becomes the sampled pick (same logits,
        # same key schedule as the plain tick), and acc is forced to 0
        # — argmax-verified drafts are never valid for a sampled
        # stream, whatever the host-side mask said.
        temp, s_tk, s_tp, s_rng = sample
        s0 = sample_token_rows(logits[:, 0, :], temp, s_tk, s_tp, s_rng,
                               pos + 1, jnp.zeros((S,), jnp.int32),
                               replicated=replicated)
        t = t.at[:, 0].set(jnp.where(temp > 0.0, s0, t[:, 0]))
        acc = jnp.where(temp > 0.0, 0, acc)
    acc = jnp.where(active, acc, 0)

    # Accepted-only write: window offset j lands at logical position
    # pos[s] + j through the table iff accepted (j <= acc) and within
    # capacity; everything else — rejected drafts, inactive rows,
    # out-of-capacity positions — is written nowhere.  The W positions
    # of a slot span at most ``C`` pages: they are merged into whole
    # pages first (a page is written once), and a page that takes none
    # of them is the NULL page (physical 0).
    from horovod_tpu.ops.paged_attention import write_pages

    with jax.named_scope("kv_write"):
        C = -(-(W - 1) // ps) + 1
        first = pos // ps                                   # (S,)
        tpos = ((first[:, None] + jnp.arange(C, dtype=jnp.int32)) * ps
                )[:, :, None] + jnp.arange(ps, dtype=jnp.int32)  # (S, C, ps)
        j = tpos - pos[:, None, None]
        take = ((j >= 0) & (j <= acc[:, None, None]) & (j < W)
                & active[:, None, None] & (tpos < T_cap))
        idxp = jnp.clip(first[:, None] + jnp.arange(C), 0, max_pages - 1)
        phys = jnp.where(jnp.any(take, axis=-1),
                         jnp.take_along_axis(table, idxp, axis=1), 0)
        jc = jnp.clip(j, 0, W - 1).reshape(S, C * ps)
        layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)[:, None, None]

        def write(stack, vals):
            # vals (L, S, Hkv, W[, Dh]) -> the pages' (L, S, C, Hkv, ps[, Dh])
            g = vals[:, jnp.arange(S)[:, None], :, jc]  # (S, C*ps, L, Hkv..)
            g = g.reshape((S, C, ps) + g.shape[2:])
            g = jnp.moveaxis(g, (3, 0, 1, 4, 2), (0, 1, 2, 3, 4))
            return write_pages(stack, layers, phys[None], g, take[None])

        names = ("k", "k_scale", "v", "v_scale") if quantized else ("k", "v")
        out = {n: write(pool[n], y) for n, y in zip(names, ys)}
    out["pos"] = pos + jnp.where(active, acc + 1, 0)
    return t, mx, acc, out


def _block(cfg: TransformerConfig, ys: Dict, pos) -> Dict:
    """A prefill's results, stacked by kind, as the block it returns:
    each array ``(L_kind, B, ...)`` under the pool's name, and ``pos``."""
    out = {"pos": pos}
    for name, y in ys.items():
        out.update(zip(cfg.kind(name).block, y))
    return out


def prefill_with_prefix(params: Dict, suffix, prefix: Dict, prefix_len,
                        cfg: TransformerConfig, *, true_len, win_start=0):
    """Prefill a (K, S0) SUFFIX whose first ``prefix_len`` logical
    positions already exist in the cache — a prompt's next CHUNK, or the
    prefix-sharing prefill: a registered system prompt is prefilled
    ONCE, and every request that starts with it runs only its suffix
    through the model, attending the shared prefix read back from its
    (refcounted) pages.

    ``prefix``: what the layers kept for those positions, ONE dict under
    the pool's names — exactly the arrays the configuration's kinds
    declare (:attr:`LayerKind.landed`; anything else is refused, typed):
    pages as :func:`~horovod_tpu.serving.cache.gather_prefix_pages`
    hands them over, ``(L_kind, heads, P0, width)``, shared by every
    row, and per-slot states ``(L_kind, K, ...)`` (:class:`_Chunk`).
    ``true_len``: ``(K,)`` per-row REAL suffix token counts (rows are
    right-padded to the bucket S0).  Suffix queries sit at global
    positions ``prefix_len + i`` (RoPE) and attend the full prefix plus
    their causal suffix span.  Returns ``(last-real-position logits (K,
    V), block)`` — the block for page landing under the same names,
    ``(L_kind, K, ...)``, with ``pos = prefix_len + true_len``: exactly
    :func:`prefill`'s contract shifted by the prefix.

    Position-wise the suffix's block (and logits) match a full-prompt
    :func:`prefill` bit-for-bit at f32: what a position leaves depends
    only on the tokens at and before it, and the layer is the same code
    (:data:`LAYER_KINDS`)."""
    K, S0 = suffix.shape
    kinds = cfg.kinds
    declared = {n for k in kinds.values() for n in k.landed}
    if set(prefix) != declared:
        raise UnsupportedModelConfigError(
            f"this configuration's layers keep {sorted(declared)} for a "
            f"request; the prefix holds {sorted(prefix)}")
    p0 = jnp.asarray(prefix_len, jnp.int32)
    true_len = jnp.asarray(true_len, jnp.int32)
    positions = p0 + jnp.arange(S0, dtype=jnp.int32)
    x = _embed(params, suffix, cfg)
    reach = _Chunk(cfg, true_len, positions, p0, prefix, win_start)

    def layer(x, p, kind, landed):
        h, new = kinds[kind].mixer(x, p, cfg, kinds[kind], reach.at(
            landed=dict(zip(kinds[kind].landed, landed))))
        return _mlp_block(x + h, p, cfg, moe_impl="dropless"), new

    x, ys = _scan_layer_kinds(
        cfg, layer, x, params["layers"],
        {name: tuple(reach.prefix[n] for n in k.landed)
         for name, k in kinds.items()}, params.get("dense_layers"))
    last = jnp.take_along_axis(x, (true_len - 1)[:, None, None], axis=1)
    logits = _lm_head(last, params["ln_f"], _head(params, cfg), cfg)
    return logits[:, 0], _block(cfg, ys, p0 + true_len)


def prefill(params: Dict, prompt, cache: Dict, cfg: TransformerConfig,
            *, moe_impl: str = "dropless", true_len=None, mesh=None):
    """Fill a FRESH cache with a (B, S0) prompt in ONE forward pass
    (the serving-shape prefill: batched MXU work instead of S0 serial
    decode steps) and return ``(last-position logits (B, V), cache)``
    with ``pos = S0``.  Continue with :func:`decode_step`.

    ``moe_impl`` selects the MoE dispatch for MoE configs: "dropless"
    (grouped ragged matmuls — exact like dense but k/E of its FFN FLOPs,
    the default: prefill ingests whole prompts) or "dense" (the
    every-expert oracle; benchmarking/fallback).

    ``true_len`` supports BUCKETED prefill (the serving engine's
    compile-stability lever): the prompt is RIGHT-padded to a bucket
    length S0 and ``true_len`` is its real token count — logits come
    from position ``true_len - 1`` and the returned ``pos`` is
    ``true_len``, so one compiled prefill per bucket serves every
    length in the bucket.  A SCALAR ``true_len`` (int or traced) keeps
    the scalar-``pos`` cache contract for :func:`decode_step`; a
    ``(B,)`` VECTOR gives every row its own length — the batch-K
    multi-request prefill the continuous-batching engine admits with —
    and the returned ``pos`` is the ``(B,)`` per-row count (consumed by
    ``serving.cache.paged_insert``, one slot per row).
    Causality makes the padding inert for the logits (position
    ``true_len - 1`` never attends past itself), and the junk K/V it
    leaves at positions ``>= true_len`` is never read: decode writes
    position ``p`` in the same step that first attends it.

    ``mesh``: the tp serving mesh when params are head-sharded under
    GSPMD (:class:`_Prompt`).

    Where the layers keep arrays ``cache`` has no place for (two kinds
    of pages, a per-slot state: :data:`LAYER_KINDS`) the results come
    back BY KIND for a paged engine's pools — each array ``(L_kind, B,
    ...)`` under its name, a state each row's at its ``true_len`` — and
    ``cache`` only gives ``pos``."""
    pos = cache["pos"]
    if not isinstance(pos, jax.core.Tracer) and int(pos) != 0:
        raise ValueError("prefill requires a fresh cache (pos == 0)")
    S0 = prompt.shape[1]
    T_cache = cache["k"].shape[3]
    if S0 > T_cache:  # shapes are static, so this raises under jit too
        raise ValueError(
            f"prompt ({S0} tokens) exceeds cache capacity ({T_cache}); "
            "init_cache with a larger max_len")
    x = _embed(params, prompt, cfg)
    kinds, lens = cfg.kinds, None
    if any(k.state for k in kinds.values()):   # each row's real length
        lens = jnp.broadcast_to(jnp.asarray(
            S0 if true_len is None else true_len, jnp.int32),
            prompt.shape[:1])
    reach = _Prompt(cfg, lens, mesh)

    def layer(x, p, kind, _):
        h, new = kinds[kind].mixer(x, p, cfg, kinds[kind], reach)
        return _mlp_block(x + h, p, cfg, moe_impl=moe_impl), new

    x, ys = _scan_layer_kinds(cfg, layer, x, params["layers"],
                              dense=params.get("dense_layers"))
    # Only one position's logits are needed: slice BEFORE the (B, S0, V)
    # head projection.
    if true_len is None:
        last = x[:, -1:]
        new_pos = pos + S0
    elif jnp.ndim(true_len) == 0:
        true_len = jnp.asarray(true_len, jnp.int32)
        last = lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        new_pos = pos + true_len
    else:
        # Per-row lengths (batch-K multi-request prefill): row b's
        # logits come from ITS position true_len[b] - 1, and pos
        # becomes the (B,) vector of per-row counts.
        true_len = jnp.asarray(true_len, jnp.int32)
        last = jnp.take_along_axis(x, (true_len - 1)[:, None, None],
                                   axis=1)
        new_pos = pos + true_len
    logits = _lm_head(last, params["ln_f"], _head(params, cfg), cfg)
    block = _block(cfg, ys, new_pos)
    if not set(block) <= set(cache):
        return logits[:, 0], block   # for the caller's pools, by kind
    with jax.named_scope("kv_land"):
        cache = {n: b if n == "pos" else lax.dynamic_update_slice_in_dim(
            cache[n], b.astype(cache[n].dtype), 0, axis=3)
            for n, b in block.items()}
    return logits[:, 0], cache


def sample_gates(temperature, top_k, top_p):
    """What a batch of sampling columns asks of :func:`sample_token_rows`,
    as three scalars: some row draws (``temperature > 0``), some row has
    a top-k, some row has a nucleus.  Array methods only, so the device
    (traced columns, inside the tick) and the host (``SlotSampling``'s
    numpy mirror, for the ``/stats`` counters) evaluate the SAME
    statement."""
    return ((temperature > 0.0).any(), (top_k > 0).any(),
            ((top_p > 0.0) & (top_p < 1.0)).any())


def sample_token_rows(logits, temperature, top_k, top_p, rng, positions,
                      rows, *, replicated=None):
    """Pick one token per row with EVERY sampling parameter as DATA —
    the serving engine's per-slot sampling kernel, and the math
    :func:`sample_decode` (the per-request oracle) is defined by.  One
    compiled executable serves any mix of greedy / temperature / top-k
    / top-p rows: the parameters are columns, not structure, so request
    churn never recompiles the decode tick.

    ``logits``: (R, V) float32.  ``temperature``: (R,) f32 — ``<= 0``
    is greedy argmax (raw logits), exactly the scalar ``temperature=0``
    case.  ``top_k``: (R,) int32 — ``> 0`` restricts sampling to the k
    most likely tokens (``0`` = off; the k-th value comes from a full
    descending sort so k is data, matching ``lax.top_k``'s k-th value
    bit-for-bit).  ``top_p``: (R,) f32 — nucleus sampling: keep the
    smallest probability-sorted set whose cumulative mass reaches
    ``top_p`` (ties at the threshold are kept; ``0`` or ``>= 1`` =
    off), applied AFTER top-k on the temperature-scaled distribution.

    What a batch pays is what its rows ask for, decided ON THE DEVICE
    each call from the three columns (:func:`sample_gates`; ``lax.cond``
    on a scalar, so still one executable): a batch of greedy rows runs
    the argmax and nothing else; a temperature-only batch adds the
    categorical draw; any top-k row adds one full-vocabulary sort; any
    nucleus row adds the softmax and one sort.  Each gate closes only a
    stage whose result the open path discards or leaves unchanged, so
    the tokens are those of the ungated body bit for bit.  Do not
    ``vmap`` over this function: a ``cond`` under ``vmap`` is a select.

    ``replicated``: under a tp serving mesh, the sharding that holds a
    whole array on every device.  The logits leave the head sharded
    over the vocabulary; they are pinned to ``replicated`` before the
    gates, so that each branch runs whole on every device.  Left
    sharded, GSPMD partitions the sorts and puts their all-to-alls
    UNDER the conditionals, where XLA:CPU's in-process rendezvous can
    cross-wait between the devices of one execution (seen under load
    in the tp tests: a 40 s stall, then an abort).

    PRNG schedule (the contract resume/failover identity hangs on):
    the token at logical sequence position ``p`` of batch row ``r``
    draws from ``fold_in(fold_in(rng[r], p), r)``.  Keys are a pure
    function of (seed, position, row) — NOT of how generation was
    sliced across prefills — so re-prefilling ``prompt + emitted`` and
    continuing lands on the identical key stream: restart-resume,
    router failover, and the engine/oracle A/B all compose by
    construction.  ``rng``: (R, 2) uint32 base keys; ``positions``:
    (R,) int32; ``rows``: (R,) int32 (the engine passes zeros — each
    slot is row 0 of its own per-request oracle call)."""
    with jax.named_scope("sample"):
        V = logits.shape[-1]
        if replicated is not None:
            logits = lax.with_sharding_constraint(logits, replicated)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        draws, any_k, any_p = sample_gates(temperature, top_k, top_p)

        def mask_top_k(scaled):
            srt = jnp.sort(scaled, axis=-1)[:, ::-1]        # descending
            kth = jnp.take_along_axis(
                srt, (jnp.clip(top_k, 1, V) - 1)[:, None], axis=1)
            return jnp.where((top_k > 0)[:, None] & (scaled < kth),
                             -jnp.inf, scaled)

        def mask_top_p(scaled):
            probs = jax.nn.softmax(scaled, axis=-1)
            ps = jnp.sort(probs, axis=-1)[:, ::-1]
            csum = jnp.cumsum(ps, axis=-1)
            # Sorted index i is in the nucleus iff the mass BEFORE it is
            # still under top_p (index 0 always is); the smallest kept
            # probability becomes the threshold, so threshold ties stay in.
            keep = (csum - ps) < top_p[:, None]
            thr = jnp.min(jnp.where(keep, ps, jnp.inf), axis=-1,
                          keepdims=True)
            p_on = (top_p > 0.0) & (top_p < 1.0)
            return jnp.where(p_on[:, None] & (probs < thr), -jnp.inf, scaled)

        def pick(key, pos, row, lrow):
            key = jax.random.fold_in(jax.random.fold_in(key, pos), row)
            return jax.random.categorical(key, lrow)

        def draw(logits):
            # Greedy rows divide by 1.0 (their sampled value is discarded
            # by the final where, but NaN/Inf from a 0-division must never
            # enter the softmax); sampled rows divide by their exact
            # temperature.
            scaled = logits / jnp.where(temperature > 0.0, temperature,
                                        1.0)[:, None]
            # A closed gate's mask is the identity already: no row has the
            # parameter, so its where() would keep every element.
            scaled = lax.cond(any_k, mask_top_k, lambda x: x, scaled)
            scaled = lax.cond(any_p, mask_top_p, lambda x: x, scaled)
            sampled = jax.vmap(pick)(rng, positions, rows, scaled)
            return jnp.where(temperature > 0.0, sampled.astype(jnp.int32),
                             greedy)

        return lax.cond(draws, draw, lambda _: greedy, logits)


def sample_decode(params: Dict, prompt, steps: int, cfg: TransformerConfig,
                  *, rng, temperature: float = 1.0, top_k: int = 0,
                  top_p: float = 0.0,
                  cache_shardings: Optional[Dict] = None):
    """Extend a (B, S0) prompt by ``steps`` SAMPLED tokens -> (B, steps).

    One batched :func:`prefill` forward fills the cache, then ``steps``
    compiled :func:`decode_step` calls generate.  ``temperature`` scales
    the logits; ``top_k > 0`` restricts sampling to the k most likely
    tokens (clamped to the vocabulary); ``top_p`` in (0, 1) keeps the
    nucleus — the smallest top-probability set whose mass reaches
    ``top_p`` — applied after top-k.  ``temperature=0`` is greedy
    (:func:`greedy_decode` is exactly that case).  The per-token pick
    is :func:`sample_token_rows` with every parameter broadcast to a
    column, which is what makes this THE per-request oracle for the
    serving engine's vectorized per-slot sampling.

    PRNG schedule: token ``i`` of row ``b`` (logical position
    ``S0 + i``) draws from ``fold_in(fold_in(rng, S0 + i), b)`` — keys
    depend on the token's absolute position, not the step count, so
    ``sample_decode(prompt + emitted, rng=same)`` continues the exact
    stream an interrupted call would have produced (the resume /
    failover identity the serving stack leans on).  Rows draw
    independent streams via the row fold.

    ``cache_shardings``: optional dict of ``NamedSharding`` matching
    :func:`cache_specs` — pins the KV cache's head dim over a ``tp``
    serving mesh so a model trained with tp>1 serves tp-sharded (the
    scan carry keeps the constraint for every decode step; GSPMD
    partitions the attention/FFN math and inserts the tp collectives)."""
    B, S0 = prompt.shape
    cache = init_cache(cfg, B, S0 + steps)
    if cache_shardings is not None:
        cache = {
            k: lax.with_sharding_constraint(v, cache_shardings[k])
            for k, v in cache.items()
        }
    logits, cache = prefill(params, prompt, cache, cfg)
    replicated = None
    if cache_shardings is not None:  # whole logits on every tp device
        from jax.sharding import NamedSharding

        replicated = NamedSharding(cache_shardings["k"].mesh, P())
    temp_col = jnp.full((B,), temperature, jnp.float32)
    tk_col = jnp.full((B,), top_k, jnp.int32)
    tp_col = jnp.full((B,), top_p, jnp.float32)
    keys = jnp.broadcast_to(jnp.asarray(rng, jnp.uint32), (B, 2))
    rows = jnp.arange(B, dtype=jnp.int32)

    def gen(carry, pos):
        cache, logits = carry
        tok = sample_token_rows(logits, temp_col, tk_col, tp_col, keys,
                                jnp.full((B,), pos, jnp.int32), rows,
                                replicated=replicated)
        logits, cache = decode_step(params, tok, cache, cfg)
        return (cache, logits), tok

    _, toks = lax.scan(gen, (cache, logits),
                       jnp.arange(S0, S0 + steps, dtype=jnp.int32))
    return jnp.moveaxis(toks, 0, 1)


def greedy_decode(params: Dict, prompt, steps: int, cfg: TransformerConfig,
                  *, cache_shardings: Optional[Dict] = None):
    """Extend a (B, S0) prompt by ``steps`` greedy tokens -> (B, steps)."""
    return sample_decode(params, prompt, steps, cfg,
                         rng=jax.random.PRNGKey(0), temperature=0.0,
                         cache_shardings=cache_shardings)


# --- true pipeline parallelism ------------------------------------------------


def pipelined_forward(params: Dict, tokens, cfg: TransformerConfig, *,
                      axis_name: str = "pp",
                      n_microbatches: Optional[int] = None,
                      return_aux: bool = False):
    """``forward`` with the layer stack executed as a GPipe pipeline over
    the ``axis_name`` mesh axis (one stage of ``n_layers/P`` blocks per
    device, microbatched activations flowing via ppermute —
    :mod:`horovod_tpu.parallel.pipeline`).

    Call INSIDE ``shard_map`` with every input replicated over the axis
    (``P()`` specs): each device slices its own stage out of the full
    layer stack locally, so no parameter resharding collectives are
    emitted.  Numerically identical to :func:`forward`.

    ``return_aux`` additionally returns this STAGE's MoE balance-loss sum
    (``psum`` over the axis == :func:`forward`'s aux; kept local so each
    stage owns its aux gradient).
    """
    from horovod_tpu.parallel import pipeline as _pl

    B = tokens.shape[0]
    M, my_layers, stage_fn = _pipeline_stage_setup(
        params, cfg, axis_name, B, n_microbatches, return_aux=return_aux)
    x = _embed(params, tokens, cfg)
    mb = x.reshape(M, B // M, *x.shape[1:])
    out = _pl.pipeline_apply(stage_fn, my_layers, mb, axis_name=axis_name,
                             stage_aux=return_aux)
    if return_aux:
        out, aux_local = out
    x = out.reshape(B, *x.shape[1:])
    logits = _lm_head(x, params["ln_f"], params["head"], cfg)
    return (logits, aux_local) if return_aux else logits


def _pipeline_stage_setup(params: Dict, cfg: TransformerConfig,
                          axis_name: str, batch: int,
                          n_microbatches: Optional[int],
                          return_aux: bool = False):
    """Shared pipeline plumbing (both schedules): divisibility checks,
    this stage's layer slice, and the scanned stage function (aux-carrying
    when ``return_aux`` — the per-stage MoE balance sum)."""
    _require_uniform(cfg, "the pipeline schedules")
    _require_no_latent(cfg, "the pipeline schedules")
    P_ = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    if cfg.n_layers % P_:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide over {P_} pipeline stages")
    per_stage = cfg.n_layers // P_
    M = n_microbatches or P_
    if batch % M:
        raise ValueError(f"batch {batch} must divide into {M} microbatches")
    my_layers = jax.tree_util.tree_map(
        lambda l: lax.dynamic_slice_in_dim(l, s * per_stage, per_stage, 0),
        params["layers"])

    if return_aux:
        def layer(carry, p):
            x, aux = carry
            x, a = _layer_body(x, p, cfg, return_aux=True)
            return (x, aux + a), None

        if cfg.remat:
            layer = _remat(layer, cfg)

        def stage_fn(lp_stack, xb):
            # Axis-varying zero init: the aux output is varying (computed
            # from the varying activations), so the scan carry init must
            # be too (shard_map VMA typing).
            aux0 = jnp.float32(0.0) + (s * 0).astype(jnp.float32)
            (out, aux), _ = _scan_layers(layer, (xb, aux0), lp_stack)
            return out, aux

        return M, my_layers, stage_fn

    def layer(x, p):
        return _layer_body(x, p, cfg), None

    if cfg.remat:
        layer = _remat(layer, cfg)

    def stage_fn(lp_stack, xb):
        out, _ = _scan_layers(layer, xb, lp_stack)
        return out

    return M, my_layers, stage_fn


def _varying_value_and_grad(local_loss_fn, params, s, axis_name):
    """value_and_grad of a replicated-parameter pipeline loss that is
    EXPLICITLY correct about gradient ownership under ANY shard_map VMA
    setting: ``local_loss_fn`` returns THIS DEVICE's gated loss
    contribution (NO psum inside — a psum's transpose is a psum, so a
    loss combined inside the differentiated function would multiply the
    seed cotangent by the axis size under ``check_vma=False``), params
    are made axis-VARYING before differentiation (no reliance on the
    implicit replicated-VJP psum that check_vma=False disables), and
    value + per-stage gradient partials combine with explicit psums
    OUTSIDE the grad.  Each parameter has exactly one owning stage in
    the gated construction (loss params on the last stage, embedding
    feed on stage 0, each layer via its dynamic_slice), so the psum
    adds one real contribution to zeros."""
    varying = jax.tree_util.tree_map(
        lambda a: a + (s * 0).astype(a.dtype), params)
    local, g_local = jax.value_and_grad(local_loss_fn)(varying)
    loss = lax.psum(local, axis_name)
    grads = jax.tree_util.tree_map(
        lambda x: lax.psum(x, axis_name), g_local)
    return loss, grads


def pipelined_value_and_grad(params: Dict, batch: Dict,
                             cfg: TransformerConfig, *,
                             axis_name: str = "pp",
                             n_microbatches: Optional[int] = None,
                             schedule: str = "gpipe",
                             n_virtual: int = 2):
    """Loss + EXACT full-parameter gradients of the pipelined model —
    call inside ``shard_map`` with params/batch replicated over the axis.

    ``schedule="gpipe"``: gradient accounting by construction rather than
    correction — the scalar loss is computed as ``psum(where(stage ==
    last, raw, 0))``, so the backward cotangent is nonzero only on the
    last stage for the head/ln_f path, only on stage 0 for the embedding
    path, and only on the owning stage for each layer (dynamic_slice
    VJP) — the psum that shard_map's transpose applies to each replicated
    parameter therefore sums one real contribution with zeros, giving
    gradients identical to ``jax.grad(loss_fn)`` with no replication
    factors to divide out.

    ``schedule="1f1b"``: the memory-bounded one-forward-one-backward
    schedule (:func:`horovod_tpu.parallel.pipeline_value_and_grad`) with
    the SAME full-parameter gradient contract: stage grads reassemble
    into the layer stack, the loss's head/ln_f grads come back via
    ``loss_params``, and the embedding grads via the returned input
    cotangents scattered through the token lookup.

    ``schedule="interleaved"``: virtual-stage (Megatron-interleaved)
    schedule — the layer stack splits into ``n_virtual * P`` chunks laid
    round-robin (:func:`horovod_tpu.parallel.interleaved_apply`), so the
    fill/drain bubble shrinks by ~``n_virtual`` at the cost of
    ``n_virtual×`` stage-boundary traffic; gradient construction is the
    gpipe one (loss gated to the last chunk's device, chunk slices taken
    inside the differentiated function so ``dynamic_slice``'s VJP
    scatters each chunk's gradient into the full stack).

    All three verified leaf-for-leaf against ``jax.grad(loss_fn)`` in
    ``tests/test_pipeline.py``.
    """
    P_ = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)

    aux_on = cfg.n_experts > 1 and cfg.moe_aux_coeff > 0.0

    if schedule == "gpipe":
        def _loss(p):
            if aux_on:
                logits, aux_local = pipelined_forward(
                    p, batch["tokens"], cfg, axis_name=axis_name,
                    n_microbatches=n_microbatches, return_aux=True)
            else:
                logits = pipelined_forward(p, batch["tokens"], cfg,
                                           axis_name=axis_name,
                                           n_microbatches=n_microbatches)
            raw = _xent_sum(logits, batch["targets"]) / batch["targets"].size
            local = jnp.where(s == P_ - 1, raw, 0.0)
            if aux_on:
                # Pipelined aux is computed PER MICROBATCH (the dispatch
                # group switch routing actually sees); the mean over
                # groups matches loss_fn's full-batch aux scale — and
                # equals it exactly at n_microbatches=1.  This stage's
                # LOCAL share; the psum happens outside the grad.
                M_ = n_microbatches or P_
                local = local + cfg.moe_aux_coeff * aux_local / M_
            return local

        return _varying_value_and_grad(_loss, params, s, axis_name)

    if schedule == "interleaved":
        from horovod_tpu.parallel import pipeline as _pl

        tokens, targets = batch["tokens"], batch["targets"]
        B, S = tokens.shape
        M, _, stage_fn = _pipeline_stage_setup(
            params, cfg, axis_name, B, n_microbatches, return_aux=aux_on)
        v = int(n_virtual)
        if cfg.n_layers % (v * P_):
            raise ValueError(
                f"n_layers={cfg.n_layers} must divide over "
                f"{v} virtual x {P_} stages")

        def _iloss(p):
            # Chunk slices taken INSIDE the differentiated function: the
            # dynamic_slice VJP scatters each chunk's gradient back into
            # the full (replicated) stack, same construction as gpipe.
            my_chunks = _pl.stack_to_chunks(p["layers"], P_, v, s)
            x = p["embed"].astype(cfg.dtype)[tokens]
            mbs = x.reshape(M, B // M, *x.shape[1:])
            if aux_on:
                outs, aux_local = _pl.interleaved_apply(
                    stage_fn, my_chunks, mbs, axis_name=axis_name,
                    n_virtual=v, stage_aux=True)
            else:
                outs = _pl.interleaved_apply(
                    stage_fn, my_chunks, mbs, axis_name=axis_name,
                    n_virtual=v)
            y = outs.reshape(B, *x.shape[1:])
            logits = _lm_head(y, p["ln_f"], p["head"], cfg)
            raw = _xent_sum(logits, targets) / targets.size
            local = jnp.where(s == P_ - 1, raw, 0.0)
            if aux_on:
                local = local + cfg.moe_aux_coeff * aux_local / M
            return local

        return _varying_value_and_grad(_iloss, params, s, axis_name)
    if schedule != "1f1b":
        raise ValueError(f"unknown pipeline schedule {schedule!r}")

    from horovod_tpu.parallel import pipeline as _pl

    tokens, targets = batch["tokens"], batch["targets"]
    B, S = tokens.shape
    M, my_layers, stage_fn = _pipeline_stage_setup(
        params, cfg, axis_name, B, n_microbatches, return_aux=aux_on)
    per_stage = cfg.n_layers // P_
    n_tok = B * S

    x = _embed(params, tokens, cfg)
    xs = x.reshape(M, B // M, S, cfg.d_model)
    ts = targets.reshape(M, B // M, S)

    def loss_fn(lp, y, tgt):
        logits = _lm_head(y, lp["ln_f"], lp["head"], cfg)
        return _xent_sum(logits, tgt) / n_tok  # microbatch losses sum to mean

    loss, stage_grads, extras = _pl.pipeline_value_and_grad(
        stage_fn, my_layers, xs, ts, loss_fn, axis_name=axis_name,
        schedule="1f1b",
        loss_params={"ln_f": params["ln_f"], "head": params["head"]},
        return_input_grads=True,
        # Per-microbatch aux averaged over the M dispatch groups (see the
        # gpipe branch) — the weight folds the 1/M in.
        aux_weight=cfg.moe_aux_coeff / M if aux_on else None)

    # Reassemble the full layer-stack gradient: each stage owns its slice
    # (zeros elsewhere), so writing it at the stage offset and psumming
    # concatenates.
    def expand(g):
        full = jnp.zeros((cfg.n_layers,) + g.shape[1:], g.dtype)
        full = lax.dynamic_update_slice_in_dim(full, g, s * per_stage, 0)
        return lax.psum(full, axis_name)

    layer_grads = jax.tree_util.tree_map(expand, stage_grads)
    # Loss-param grads live on the last stage (zero elsewhere): psum.
    lp_grads = jax.tree_util.tree_map(
        lambda g: lax.psum(g, axis_name), extras["loss_param_grads"])
    # Embedding grad: input cotangents live on stage 0 (zero elsewhere);
    # psum, then scatter-add through the token lookup's VJP.
    gx = lax.psum(extras["input_grads"], axis_name)  # (M, mb, S, D)
    embed_grad = (
        jnp.zeros(params["embed"].shape, cfg.dtype)
        .at[tokens.reshape(-1)]
        .add(gx.reshape(n_tok, cfg.d_model))
    ).astype(params["embed"].dtype)

    grads = {
        "embed": embed_grad,
        "layers": layer_grads,
        "ln_f": lp_grads["ln_f"],
        "head": lp_grads["head"],
    }
    return loss, grads


def synthetic_batch(rng, cfg: TransformerConfig, batch: int, seq: Optional[int] = None):
    seq = seq or cfg.max_seq
    k1, k2 = jax.random.split(jax.random.PRNGKey(rng) if isinstance(rng, int) else rng)
    tokens = jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    return {"tokens": tokens, "targets": targets}
