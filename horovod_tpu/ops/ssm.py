"""The two bodies of a state-space mixer (Mamba-2's selective recurrence).

A head ``h`` of width ``P`` keeps a MATRIX state ``S_h`` ``(P, N)`` a
request (``N`` = ``d_state``): with ``a_t = exp(A dt_t)`` (``A < 0`` a
head, ``dt_t > 0`` a head and token), ``B_t``, ``C_t`` ``(N,)`` shared
by the heads of one GROUP (head ``h`` reads group ``h // (H / G)``)::

    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T        y_t = S_t C_t

(the ``D x_t`` skip, the gate and the norm are the model's:
``models/transformer.py``).  Two ways to run it:

* :func:`ssm_update` — a decode TICK, one token a slot.  The states of
  every layer and slot are ONE array ``(L, S, H, P, N)`` that a layer
  scan carries; the update reads and writes layer ``layer`` of it IN
  PLACE and nothing has a result the size of a layer's states.  Its
  cost is the state's bytes, read once and written once.  ``kernel=
  True`` is the Pallas kernel ``hvd_ssm_update`` (a grid step takes
  as many whole groups of one slot as ``_BLOCK_BYTES`` of stored state
  hold — the shape alone decides — aliased onto its operand; the
  read-out ``S C`` on the MXU, each group against its own ``C``),
  ``False`` the same arithmetic as XLA operations.
* :func:`ssm_scan` — a prompt or a chunk of one, FROM a given state TO
  the state after its last token, in the chunked dual form: inside a
  chunk of ``Q`` tokens the outputs are a masked ``(Q, Q)`` product,
  ``Y = (L o C B^T) (dt X)`` with ``L[i, j] = a_{j+1} .. a_i``; between
  chunks the state is carried by a short sequential scan.  A token with
  ``dt = 0`` leaves the state as it is (``a = 1``, nothing added): that
  is how a caller makes padding inert.

Arithmetic is float32 throughout (the products take operands of
``dtype`` and accumulate in float32); the stored state has the array's
dtype and is rounded once, where it is written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops._pallas_util import pl, pltpu, use_interpret

__all__ = ["UPDATE_NAME", "ssm_update", "ssm_scan"]

#: The tick kernel's name on a device trace (``pl.pallas_call(name=)``).
UPDATE_NAME = "hvd_ssm_update"

# What one grid step of the tick kernel takes of a slot's STORED states:
# as many whole groups as fit.  A step costs ~0.3 us of its own beside
# its bytes, so on a v5e the kernel alone moves, of MiniCPM-SALA's
# float32 heads of 128 x 128 (64 KiB a group), 283 GB/s at a group a
# step, 488 at 256 KiB, 580 at 512, 639 at 1 MiB, 642 at 2 MiB (a whole
# slot), and of Falcon-H1's groups of 16 bfloat16 heads of 128 x 256
# (1 MiB) 648 at one a step, 650 at both (PERF.md, PR 47): past 1 MiB
# nothing is gained, and twice the VMEM is held.
_BLOCK_BYTES = 1024 * 1024


def _groups_a_step(G: int, group_bytes: int) -> int:
    """The largest divisor of ``G`` whose groups' stored states stay at
    or under ``_BLOCK_BYTES`` (one group where one alone is over)."""
    fit = max(1, _BLOCK_BYTES // group_bytes)
    return max(d for d in range(1, G + 1) if G % d == 0 and d <= fit)


def _update_kernel(layer_ref, h_ref, da_ref, dtx_ref, b_ref, c_ref,
                   h_out, y_out):
    del layer_ref                      # (the index maps read it)
    gb, hg, p = da_ref.shape
    n = h_ref.shape[-1]
    for g in range(gb):                # each group its OWN b and c
        heads = pl.ds(g * hg, hg)
        new = (da_ref[g][:, :, None] * h_ref[heads].astype(jnp.float32)
               + dtx_ref[g][:, :, None] * b_ref[g][None])
        h_out[heads] = new.astype(h_out.dtype)
        # y[h, p] = sum_n new[h, p, n] c[n]: the reduction over the
        # lanes is the MXU's, (1, N) x (Hg P, N)^T, and comes back
        # lane-dense
        y_out[g] = lax.dot_general(
            c_ref[g], new.reshape(hg * p, n), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _update_pallas(states, layer, da, dtx, b, c):
    L, S, H, P, N = states.shape
    G = b.shape[1]
    hg = H // G
    gb = _groups_a_step(G, hg * P * N * states.dtype.itemsize)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    held = pl.BlockSpec((None, None, gb * hg, P, N),
                        lambda s, j, l: (l[0], s, j, 0, 0))
    row = pl.BlockSpec((None, gb, hg, P), lambda s, j, l: (s, j, 0, 0))
    vec = pl.BlockSpec((None, gb, 1, N), lambda s, j, l: (s, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, G // gb),
        in_specs=[held, row, row, vec, vec],
        out_specs=[
            held,
            pl.BlockSpec((None, gb, 1, hg * P),
                         lambda s, j, l: (s, j, 0, 0))],
    )
    bcast = jnp.broadcast_to(da[..., None], dtx.shape)
    new, y = pl.pallas_call(
        _update_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct((S, G, 1, hg * P), jnp.float32)],
        # the states are written where they are read (operand 1: the
        # layer's index is operand 0)
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # a step's states in and out, two buffers each, and the
            # float32 forms of ONE group (a bfloat16 group's are twice
            # its stored bytes): 4.5 MB at 1 MiB a step of 64 KiB
            # groups, 5.5 MB at one bfloat16 group of 1 MiB, by the
            # compiler's count; the rest is room for a group that is
            # over _BLOCK_BYTES alone
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=use_interpret(),
        name=UPDATE_NAME,
    )(layer, states, bcast.reshape(S, G, hg, P), dtx.reshape(S, G, hg, P),
      b[:, :, None], c[:, :, None])
    return y.reshape(S, H, P), new


def ssm_update(states, layer, x, dt, a_neg, b, c, active, *,
               kernel: bool = False):
    """One token a slot: ``(y (S, H, P) float32, states)``.

    ``states`` ``(L, S, H, P, N)``: every layer's and slot's, read and
    written at ``layer`` (a traced scalar) alone.  ``x`` ``(S, H, P)``,
    ``dt`` ``(S, H)`` (after its softplus), ``a_neg`` ``(H,)`` (``A``,
    negative), ``b``/``c`` ``(S, G, N)``, all float32.  A row that is
    not ``active`` keeps its state (its ``a`` is 1 and nothing is
    added); its ``y`` is unspecified."""
    da = jnp.where(active[:, None], jnp.exp(dt * a_neg), 1.0)
    dtx = jnp.where(active[:, None, None], dt[..., None] * x, 0.0)
    if kernel:
        return _update_pallas(states, layer, da, dtx, b, c)
    S, H, P = x.shape
    G = b.shape[1]
    old = lax.dynamic_index_in_dim(states, layer, 0, keepdims=False)
    old = old.reshape(S, G, H // G, P, -1).astype(jnp.float32)
    new = (da.reshape(S, G, -1)[..., None, None] * old
           + dtx.reshape(S, G, H // G, P)[..., None]
           * b[:, :, None, None, :])
    y = jnp.einsum("sgjpn,sgn->sgjp", new, c,
                   precision=lax.Precision.HIGHEST)
    new = new.reshape(S, H, P, -1).astype(states.dtype)
    return y.reshape(S, H, P), lax.dynamic_update_index_in_dim(
        states, new, layer, 0)


def ssm_scan(x, dt, a_neg, b, c, h0, *, chunk: int, dtype=jnp.float32):
    """A sequence from a state to a state: ``(y (B, S, H, P) float32,
    h (B, H, P, N) float32)``.

    ``x`` ``(B, S, H, P)``, ``dt`` ``(B, S, H)`` (after its softplus; 0
    where a position is padding), ``a_neg`` ``(H,)``, ``b``/``c`` ``(B,
    S, G, N)``, ``h0`` ``(B, H, P, N)`` the state before position 0.
    ``chunk``: the dual form's block ``Q``; the four products — ``C
    B^T`` a group, the masked ``(Q, Q)`` by ``(Q, P)`` a head, a chunk's
    contribution to the state and the carried state's to the outputs —
    take operands of ``dtype`` and accumulate in float32."""
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:     # whole blocks: dt = 0 leaves state and outputs alone
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                               (v.ndim - 2)) for v in (x, dt, b, c))
    nc, J = (S + pad) // Q, H // G
    f32 = jnp.float32
    dt = dt.astype(f32).reshape(B, nc, Q, G, J)
    dtx = dt[..., None] * x.astype(f32).reshape(B, nc, Q, G, J, P)
    b = b.reshape(B, nc, Q, G, N).astype(dtype)
    c = c.reshape(B, nc, Q, G, N).astype(dtype)
    # cum[i] = log(a_0 .. a_i) inside a chunk (<= 0, decreasing)
    cum = jnp.cumsum(dt * a_neg.astype(f32).reshape(G, J), axis=2)
    cum = jnp.moveaxis(cum, 2, -1)                      # (B, nc, G, J, Q)

    def dot(spec, u, v):
        return jnp.einsum(spec, u.astype(dtype), v.astype(dtype),
                          preferred_element_type=f32)

    # inside a chunk: L[i, j] = a_{j+1} .. a_i for j <= i
    cb = dot("bcqgn,bckgn->bcgqk", c, b)
    seg = cum[..., :, None] - cum[..., None, :]         # (.., Q(i), Q(j))
    low = (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    w = jnp.where(low, jnp.exp(seg), 0.0)
    y = dot("bcgjqk,bckgjp->bcqgjp", w * cb[:, :, :, None], dtx)
    # a chunk's own contribution to the state at its end ...
    to_end = jnp.exp(cum[..., -1:] - cum)               # (B, nc, G, J, Q)
    own = dot("bcqgjp,bcqgn->cbgjpn",
              dtx * jnp.moveaxis(to_end, -1, 2)[..., None], b)
    # ... carried from chunk to chunk: h_in[c] is the state entering c
    whole = jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0)   # (nc, B, G, J)

    def step(h, inp):
        s_c, d = inp
        return d[..., None, None] * h + s_c, h

    h, h_in = lax.scan(step, h0.astype(f32).reshape(B, G, J, P, N),
                       (own, whole))
    y = y + dot("bcqgn,cbgjpn->bcqgjp", c, h_in) * jnp.moveaxis(
        jnp.exp(cum), -1, 2)[..., None]
    return (y.reshape(B, nc * Q, H, P)[:, :S], h.reshape(B, H, P, N))
