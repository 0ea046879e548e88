"""Expert-parallel mixture-of-experts dispatch (Switch-style top-1 with
capacity factor).

The reference framework has no MoE (this is a beyond-reference extension,
like ring attention); the design follows the Switch-Transformer /
Mesh-TensorFlow dispatch discipline re-thought for XLA static shapes:

* **Route**: top-1 expert per token from a softmax router (f32 for the
  argmax/gate numerics).
* **Capacity**: each expert accepts at most ``cap = ceil(capacity_factor
  * T / E)`` tokens; a token's slot is its running position within its
  expert (cumsum over the static token order), tokens past the capacity
  are DROPPED (their gate is zeroed, so only the residual passes — the
  standard Switch training behavior).  Static shapes throughout: the
  dispatch buffer is ``(E, cap, D)`` with one scratch slot that dropped
  tokens scatter into.
* **Exchange**: under ``shard_map`` with an ``ep`` axis bound, the
  dispatch buffer ``(E, cap, D) = (ep, E_local, cap, D)`` rides ONE
  ``lax.all_to_all`` so each device receives exactly the tokens routed
  to its RESIDENT experts (and only those); expert FFNs run as one
  batched einsum over the local expert axis (MXU-friendly); a reverse
  ``all_to_all`` returns expert outputs to the token owners.  Compute
  per device is ``T_local * FFN`` — flat in E — unlike dense dispatch's
  ``E * T * FFN``, and the ``ep`` axis now shards COMPUTE, not just
  storage.
* **Combine**: gather each token's slot from the returned buffer and
  scale by its gate probability.

Gradients flow through the scatter/gather and both all_to_alls (their
VJPs are the transpose gather/scatter and the reverse all_to_all), so
``jax.grad`` of a loss through :func:`switch_moe` is exact — verified
against the dense-dispatch oracle in ``tests/test_moe.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.ops._pallas_util import pl, pltpu, use_interpret


def capacity(T: int, n_experts: int, capacity_factor: float) -> int:
    """Per-expert token capacity: ``ceil(cf * T / E)`` clamped to [1, T]."""
    cap = int(np.ceil(capacity_factor * T / n_experts))
    return max(1, min(cap, T))


def _cumsum_dispatch(xt, e_star, E: int, cap: int):
    """Original dispatch: f32 one-hot running-position cumsum + row
    scatter into the (E, cap+1, D) buffer.  Kept as the oracle and the
    fallback; the sort dispatch below is the fast path on TPU."""
    T, D = xt.shape
    onehot = jax.nn.one_hot(e_star, E, dtype=jnp.float32)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1).astype(jnp.int32) - 1
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)  # dropped tokens -> scratch slot
    buf = jnp.zeros((E, cap + 1, D), xt.dtype).at[e_star, slot].set(xt)
    frac = onehot.mean(axis=0)
    return buf[:, :cap], jnp.where(keep, pos, cap), keep, frac


def _sort_dispatch(xt, e_star, E: int, cap: int):
    """Sort-based dispatch: argsort tokens by expert (stable — original
    arrival order within an expert is preserved, so drop semantics match
    the cumsum oracle exactly), then build the (E, cap, D) buffer with
    ONE row gather (row (e, c) = sorted token ``starts[e] + c``).  No
    row scatter and no (T, E) f32 cumsum — the two ops that made the
    cumsum dispatch eat the MFU on chip (only 1-D int sorts/scatters
    remain, plus the unavoidable row gathers whose VJPs are the
    scatter-adds autodiff inserts in the backward)."""
    T, D = xt.shape
    e32 = e_star.astype(jnp.int32)
    order = jnp.argsort(e32, stable=True)
    es = e32[order]
    eye = jnp.arange(E, dtype=e32.dtype)
    starts = jnp.searchsorted(es, eye).astype(jnp.int32)
    counts = (jnp.searchsorted(es, eye, side="right").astype(jnp.int32)
              - starts)
    pos_sorted = jnp.arange(T, dtype=jnp.int32) - starts[es]
    xs = xt[order]
    rowidx = starts[:, None] + jnp.arange(cap, dtype=jnp.int32)[None]
    rowvalid = jnp.arange(cap, dtype=jnp.int32)[None] < counts[:, None]
    buf = jnp.where(rowvalid[..., None],
                    xs[jnp.clip(rowidx, 0, T - 1)],
                    jnp.zeros((), xt.dtype))
    # Per-original-token slot: unsort the within-expert positions (1-D
    # int32 scatter — cheap, unlike a (T, D) row scatter).
    slot = jnp.zeros((T,), jnp.int32).at[order].set(pos_sorted)
    keep = slot < cap
    frac = counts.astype(jnp.float32) / T
    return buf, jnp.where(keep, slot, cap), keep, frac


def switch_moe(
    x,
    router,
    w_gate,
    w_up,
    w_down,
    *,
    capacity_factor: float = 2.0,
    axis_name: Optional[str] = None,
    return_aux: bool = False,
    dispatch: str = "sort",
):
    """Top-1 expert-parallel MoE FFN.

    Args:
      x: ``(..., D)`` tokens (leading dims flattened internally).
      router: ``(D, E)`` router weights, REPLICATED (E = global experts).
      w_gate, w_up: ``(E_local, D, F)`` — this device's resident experts
        (the global stack sharded over ``axis_name``; pass the full
        ``(E, D, F)`` stack when ``axis_name`` is None).
      w_down: ``(E_local, F, D)``.
      capacity_factor: per-expert capacity multiplier (see module doc).
      axis_name: the ``ep`` mesh axis bound by ``shard_map``, or None for
        single-device dispatch (still sparse: each token computes ONE
        expert's FFN).
      return_aux: also return the Switch load-balancing auxiliary loss
        ``E * sum_e fraction_e * mean_prob_e`` (1.0 at perfect balance).
      dispatch: ``"sort"`` (argsort + gathers — the fast path on TPU,
        where row scatters and the (T, E) f32 running-position cumsum
        dominate the dispatch cost) or ``"cumsum"`` (the original
        formulation, kept as the oracle).  Identical results including
        drop patterns: the stable sort preserves each expert's original
        arrival order.

    Returns:
      ``y`` shaped like ``x`` (add it to the residual stream), or
      ``(y, aux_loss)`` with ``return_aux``.
    """
    lead, D = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    ep = lax.axis_size(axis_name) if axis_name is not None else 1
    E_loc = w_gate.shape[0]
    E = E_loc * ep
    if router.shape[1] != E:
        raise ValueError(
            f"router routes over {router.shape[1]} experts but the expert "
            f"stack provides {E_loc} local x {ep} devices = {E} "
            "(sharded weights outside shard_map, or axis_name missing?)")
    if dispatch not in ("sort", "cumsum"):
        raise ValueError(f"unknown dispatch {dispatch!r}; "
                         "expected 'sort' or 'cumsum'")
    dt = x.dtype

    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    e_star = jnp.argmax(probs, axis=-1)  # (T,)
    gate = jnp.max(probs, axis=-1)  # (T,)

    cap = capacity(T, E, capacity_factor)
    dispatch_fn = _sort_dispatch if dispatch == "sort" else _cumsum_dispatch
    buf, slot, keep, frac = dispatch_fn(xt, e_star, E, cap)
    gate = jnp.where(keep, gate, 0.0)

    if ep > 1:
        # (ep * E_loc, cap, D): chunk e goes to device e // E_loc.  After
        # the exchange, block i holds source i's tokens for MY experts.
        recv = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
        toks = (recv.reshape(ep, E_loc, cap, D)
                .transpose(1, 0, 2, 3)
                .reshape(E_loc, ep * cap, D))
    else:
        toks = buf  # (E, cap, D)

    # Resident experts only: one batched einsum over the local expert
    # axis — (E_loc, tokens, D) x (E_loc, D, F) on the MXU.
    g = jnp.einsum("ecd,edf->ecf", toks, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", toks, w_up.astype(dt))
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down.astype(dt))

    if ep > 1:
        # Reverse exchange: piece j = outputs for source j's tokens;
        # the concat arrives back in GLOBAL expert-major order.
        out = (out.reshape(E_loc, ep, cap, D)
               .transpose(1, 0, 2, 3)
               .reshape(E, cap, D))
        out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                             tiled=True)

    y = out[e_star, jnp.minimum(slot, cap - 1)]  # (T, D); dropped gate=0
    y = (y * gate[:, None].astype(dt)).reshape(*lead, D)
    if not return_aux:
        return y
    pbar = probs.mean(axis=0)
    aux = E * jnp.sum(frac * pbar)  # frac = routed fraction (pre-drop)
    return y, aux


#: The grouped expert product's name on a device trace
#: (``pl.pallas_call(name=)``), and the scope its ``lax.ragged_dot``
#: form is put under: readers of a profile find either by it.
EXPERTS_NAME = "hvd_moe_experts"


def _work_items(counts, m_tiles: int, tm: int):
    """The grouped product's walk over ``(expert, row tile)`` pairs, from
    the rows each expert was handed (rows sorted by expert): ``offsets``
    ``(E + 1,)`` row bounds, and for each of the ``m_tiles + E - 1`` work
    items its expert and its row tile, plus how many are real.  An
    expert with no row gets no item; a row tile shared by several
    experts is visited once for each.  Items past the real ones repeat
    the last real one and are skipped; with :func:`_run` in the index
    maps they name the block already resident, whatever ``k_tiles``: no
    new transfer.  No real item at all: item 0 names the last expert's
    matrix, fetched once and not used."""
    E = counts.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    item_end = jnp.cumsum(tiles)
    num = item_end[-1]
    t = jnp.arange(m_tiles + E - 1, dtype=jnp.int32)
    t = jnp.minimum(t, jnp.maximum(num - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(item_end, t, side="right"),
                      E - 1).astype(jnp.int32)
    mid = (first[gid] + t - (item_end - tiles)[gid]).astype(jnp.int32)
    offsets = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    return offsets, gid, mid, num.astype(jnp.int32).reshape(1)


def _grouped_kernel(layer_ref, offs_ref, gid_ref, mid_ref, num_ref,
                    x_ref, w_ref, o_ref, *acc, tm: int, k_tiles: int):
    """One work item: the rows of tile ``mid[t]`` that belong to expert
    ``gid[t]``, times that expert's matrix (the block the index map
    fetched from ``w[layer, gid[t]]``).  The output tile stays resident
    while consecutive items share it; its first visit clears the rows
    no expert owns.  A matrix too large for one transfer comes in
    ``k_tiles`` runs of whole rows (the grid's inner axis), summed in
    the float32 scratch ``acc`` and written with the last.  An item
    past the real ones runs no product here and, by :func:`_run`, had
    nothing fetched for it either."""
    del layer_ref  # read by the weights' index map
    t = pl.program_id(0)
    k = pl.program_id(1) if k_tiles > 1 else 0

    def write(acc):
        e, m = gid_ref[t], mid_ref[t]
        rows = m * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (rows >= offs_ref[e]) & (rows < offs_ref[e + 1])
        fresh = (t == 0) | (mid_ref[jnp.maximum(t - 1, 0)] != m)
        old = jnp.where(fresh, 0.0, o_ref[...].astype(jnp.float32))
        o_ref[...] = jnp.where(mine, acc, old).astype(o_ref.dtype)

    @pl.when(t < num_ref[0])
    def _item():
        part = lax.dot_general(x_ref[...], w_ref[...],
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        if k_tiles == 1:
            write(part)
            return
        acc_ref, = acc

        @pl.when(k == 0)
        def _first():
            acc_ref[...] = part

        @pl.when(k > 0)
        def _more():
            acc_ref[...] += part

        @pl.when(k == k_tiles - 1)
        def _last():
            write(acc_ref[...])


#: The most of one expert's matrix a step of the grouped product
#: fetches (of two such buffers): a whole ``2304 x 896`` bf16 matrix
#: (4.1 MB) is one step; ``7168 x 2048`` (29 MB, more than the kernel's
#: VMEM holds twice) goes in 7 runs of 1024 whole rows.  Only an item
#: that owns a row walks its runs (:func:`_run`): a call moves the
#: matrices of the experts touched, once a row tile each, and no other.
_EXPERT_BLOCK_BYTES = 9 * 512 * 1024


def _k_tile(K: int, N: int, itemsize: int) -> int:
    """Rows of a ``(K, N)`` expert matrix one step fetches: all, if they
    fit :data:`_EXPERT_BLOCK_BYTES`; else the largest divisor of ``K``
    in whole 128s that does (whole rows: one contiguous transfer)."""
    if K * N * itemsize <= _EXPERT_BLOCK_BYTES or K % 128:
        return K
    fits = [d * 128 for d in range(1, K // 128 + 1)
            if (K // 128) % d == 0
            and d * 128 * N * itemsize <= _EXPERT_BLOCK_BYTES]
    return max(fits) if fits else 128


def _run(t, k, num, k_tiles: int):
    """The run of the matrix (and of the rows' columns) that grid step
    ``(t, k)`` holds: ``k``, while item ``t`` is real; the LAST run for
    an item past the real ones, so that every skipped step — and the
    step from the last real one into the first skipped — names the block
    the step before it left resident, and the pipeline fetches nothing.
    (With ``k`` left to walk, each skipped item fetched the last
    expert's whole matrix again.)  One run a matrix: ``k`` as it is."""
    if k_tiles == 1:
        return k
    return jnp.where(t < num[0], k, k_tiles - 1)


def _rows_block(t, k, layer, offsets, gid, mid, num, *, k_tiles: int):
    """Index map of the rows ``xs``, in blocks of ``(tm, tk)``."""
    del layer, offsets, gid
    return mid[t], _run(t, k, num, k_tiles)


def _matrix_block(t, k, layer, offsets, gid, mid, num, *, k_tiles: int):
    """Index map of the weights ``w``, in blocks of ``(1, 1, tk, N)``."""
    del offsets, mid
    return layer[0], gid[t], _run(t, k, num, k_tiles), 0


def grouped_matmul(xs, w, layer, counts):
    """``xs[rows of expert e] @ w[layer, e]`` for every expert, as one
    Pallas kernel (:data:`EXPERTS_NAME`): ``xs`` ``(M, K)`` rows sorted
    by expert, ``counts`` ``(E,)`` the rows of each, ``w`` ``(L, E, K,
    N)`` EVERY layer's experts as the checkpoint stacks them, ``layer``
    a (traced) index into it.  The weights stay where they are: each
    expert that owns a row has its ``(K, N)`` matrix fetched straight
    from ``w[layer, e]`` — one contiguous transfer (or :func:`_k_tile`
    rows of it at a time), overlapped with the previous product; the
    grid's other steps ask for no transfer (:func:`_run`) — so a layer
    scan hands the kernel the whole stack and no slice of it is ever
    copied (``lax.ragged_dot`` is a custom call whose operand a scan
    must first cut out: a copy of every expert, every tick).  Rows past ``sum(counts)`` come back
    zero where their tile was visited and undefined where not."""
    M, K = xs.shape
    L, E, _, N = w.shape
    tm = 128 if M >= 1024 else 32 if M >= 128 else 16
    m_tiles = -(-M // tm)
    if m_tiles * tm != M:
        xs = jnp.pad(xs, ((0, m_tiles * tm - M), (0, 0)))
    tk = _k_tile(K, N, jnp.dtype(w.dtype).itemsize)
    k_tiles = K // tk
    offsets, gid, mid, num = _work_items(counts, m_tiles, tm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(m_tiles + E - 1, k_tiles),
        in_specs=[
            pl.BlockSpec((tm, tk),
                         functools.partial(_rows_block, k_tiles=k_tiles)),
            pl.BlockSpec((None, None, tk, N),
                         functools.partial(_matrix_block, k_tiles=k_tiles)),
        ],
        out_specs=pl.BlockSpec((tm, N),
                               lambda t, k, l, o, g, m, n: (m[t], 0)),
        scratch_shapes=([pltpu.VMEM((tm, N), jnp.float32)]
                        if k_tiles > 1 else []),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, tm=tm, k_tiles=k_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_tiles * tm, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two buffers of an expert's block, 4-4.5 MB each
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=use_interpret(),
        name=EXPERTS_NAME,
    )(layer, offsets, gid, mid, num, xs, w)
    return out[:M]


def route_topk(xt, router, k: int = 1, norm_topk: bool = False, *,
               score: str = "softmax", n_group: int = 0, topk_group: int = 0,
               scale: float = 1.0, bias=None, norm_eps: float = 0.0):
    """Routing in float32: ``(experts (T, k) int32, weights (T, k)
    float32)`` — the ``k`` largest scores of ``x @ router``,
    renormalised to sum to one when ``norm_topk`` (divided by ``sum +
    norm_eps`` where a model publishes one), times ``scale`` (a
    published ``routed_scaling_factor``).

    ``score``: ``"softmax"`` over the experts, or ``"sigmoid"`` of each
    expert's logit alone.  ``n_group`` > 1 limits the choice by GROUPS
    (the experts in ``n_group`` equal runs of the router's outputs): a
    group's score is the sum of its two largest experts' scores, the
    ``topk_group`` best groups stay, and the ``k`` experts are the
    largest among theirs.  ``bias`` ``(E,)``: a learned
    score-correction bias (the published ``noaux_tc``) — groups and
    experts are CHOSEN on ``scores + bias``.  The weights are always the
    chosen experts' OWN scores, the bias no part of them.  Ties go to
    the lower index, at every step (``lax.top_k``'s rule)."""
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router score {score!r}; expected "
                         "'softmax' or 'sigmoid'")
    choice = probs if bias is None else probs + bias.astype(jnp.float32)
    if n_group > 1:
        T, E = probs.shape
        if E % n_group or not 0 < topk_group <= n_group:
            raise ValueError(
                f"{E} experts do not split into {n_group} groups of "
                f"which {topk_group} stay")
        grouped = choice.reshape(T, n_group, E // n_group)
        g_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, g_keep = lax.top_k(g_score, topk_group)
        keep = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], g_keep].set(True)
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf
                           ).reshape(T, E)
    if k == 1 and choice is probs:
        e = jnp.argmax(probs, axis=-1)[:, None]
        gate = jnp.max(probs, axis=-1)[:, None]
    else:
        gate, e = lax.top_k(choice, k)
        if choice is not probs:
            gate = jnp.take_along_axis(probs, e, axis=-1)
        if norm_topk:
            total = jnp.sum(gate, axis=-1, keepdims=True)
            gate = gate / (total + norm_eps if norm_eps else total)
    if scale != 1.0:
        gate = gate * scale
    return e.astype(jnp.int32), gate


def dropless_moe(x, router, w_gate, w_up, w_down, *, k: int = 1,
                 norm_topk: bool = False, token_mask=None,
                 return_counts: bool = False, layer=None,
                 routing: Optional[dict] = None, held_offset=None):
    """Top-k MoE FFN, DROPLESS, via grouped (ragged) matmuls: each
    token's ``k`` rows sorted by expert, the three FFN matmuls as
    ``lax.ragged_dot`` with the per-expert group sizes, unsorted, and
    the weighted sum back.

    Exact (== the dense dispatch oracle — no capacity, nothing dropped)
    at k/E of dense FLOPs: each row touches only its own expert's
    weights, and the grouped matmuls stay MXU-shaped.  This is the
    SERVING dispatch, for prefill chunks and decode ticks alike: a tick
    of S slots computes ``S * k`` expert rows, not ``S * E`` (training
    keeps capacity-factor :func:`switch_moe` — fixed shapes and the one
    all_to_all each way under ``ep``).  Single-device or tp-sharded; no
    ep axis (ragged group sizes are data-dependent, which an all_to_all
    cannot carry statically).

    ``token_mask`` ``(T,)`` bool leaves tokens out of every group (a
    decode tick's idle slots): they cost no expert row and come back as
    zeros.  ``return_counts`` adds the ``(E,)`` int32 rows each expert
    was handed.  ``layer``: the three weights are EVERY layer's, stacked
    ``(L, E, ...)`` as a checkpoint holds them, and this is the (traced)
    index of the layer at hand — the products then run as
    :func:`grouped_matmul`, which reads ``w[layer, e]`` in place (what a
    layer scan needs: see there); without it they are one layer's
    ``(E, ...)`` and run as ``lax.ragged_dot``.  On a device trace the
    routing (scores, top-k, sort, weighted sum) reads ``hvd_moe_route``
    and the grouped products ``hvd_moe_experts``, in either form.

    ``routing``: :func:`route_topk`'s further keywords (``score``,
    ``n_group``, ``topk_group``, ``scale``).  ``held_offset``: this is
    ONE CHIP'S SHARE of an expert-parallel layer — the weights hold
    only the experts ``held_offset <= e < held_offset + E_held`` of the
    router's ``E`` (``E_held`` is their leading size).  Every token is
    still routed over all ``E`` and its weights normalised over all
    ``k`` picks; the picks whose expert lies elsewhere are left out of
    every group (a token keeps 0 to ``k`` of its rows), so the result
    is ``sum over the held picks of g_e E_e(x)`` — the part of the
    layer's output this chip gives.  Nothing stands in for the other
    chips' parts.  ``counts`` are then the HELD experts' ``(E_held,)``.
    ``None`` (every expert held) is the one dispatch written before a
    share was."""
    lead, D = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    E = w_gate.shape[-3]                      # experts held here
    dt = x.dtype
    if held_offset is None and E != router.shape[1]:
        raise ValueError(
            f"the router scores {router.shape[1]} experts and the stack "
            f"holds {E}: a share of the experts needs held_offset")

    with jax.named_scope("hvd_moe_route"):
        e_top, gate = route_topk(xt, router, k, norm_topk,
                                 **(routing or {}))
        e_rows = e_top.reshape(-1)            # row r belongs to token r // k
        here = None
        if held_offset is not None:
            e_rows = e_rows - held_offset     # the held experts' own index
            here = (e_rows >= 0) & (e_rows < E)
        if token_mask is not None:
            live = jnp.repeat(token_mask.reshape(-1), k)
            here = live if here is None else here & live
        if here is not None:
            # expert E is no expert: its rows sort past every group
            e_rows = jnp.where(here, e_rows, E)
        order = jnp.argsort(e_rows, stable=True)
        xs = xt[order if k == 1 else order // k]
        es = e_rows[order]
        eye = jnp.arange(E, dtype=jnp.int32)
        counts = (jnp.searchsorted(es, eye, side="right")
                  - jnp.searchsorted(es, eye)).astype(jnp.int32)

    with jax.named_scope(EXPERTS_NAME):
        if layer is None:
            def mm(rows, w):
                return lax.ragged_dot(rows, w.astype(dt), counts)
        else:
            def mm(rows, w):
                return grouped_matmul(rows, w.astype(dt), layer, counts)
        y_s = mm(jax.nn.silu(mm(xs, w_gate)) * mm(xs, w_up), w_down)

    with jax.named_scope("hvd_moe_route"):
        inv = jnp.argsort(order)  # unsort permutation
        y_r = y_s[inv]
        if held_offset is not None:
            # rows past the groups are whatever the grouped product
            # left: a pick held elsewhere adds nothing HERE
            y_r = jnp.where(here[:, None], y_r, jnp.zeros_like(y_r))
        if k == 1:
            y = y_r * gate.astype(dt)
        else:
            y = jnp.sum((y_r.astype(jnp.float32)
                         * gate.reshape(-1, 1)).reshape(T, k, D),
                        axis=1).astype(dt)
        if token_mask is not None and held_offset is None:
            # rows past the groups are whatever the grouped product left
            y = jnp.where(token_mask.reshape(-1, 1), y, jnp.zeros_like(y))
        y = y.reshape(*lead, D)
    return (y, counts) if return_counts else y
