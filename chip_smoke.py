"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one model, both hot paths, through the entry points a user
calls, at the full width of a model the repo documents (the
``d2048 L8 seq2048 b4, Pallas flash + remat(dots)`` row of
``docs/benchmarks.md``, with 4 KV heads so head_dim is 128 and both kernel
families sit on their supported tiling):

1. **kernels** — flash forward and gradients against
   ``attention.reference_attention`` (plain and under ``remat``);
   ``paged_attend`` against ``paged_attend_reference`` on the same pool,
   table and limits (bf16 pages of 16, int8 pages of 32), without and with
   a window layer's lower bound; the top-k grouped expert products (the
   Pallas kernel and ``lax.ragged_dot``) against every expert under a
   mask; one fused decode tick against the unfused one at LOGIT level on
   the full-width model.
2. **trainer** — ``hvd.init()`` → ``hvd.DistributedOptimizer(optax.adamw)``
   → ``jax.jit(spmd.shard(step), donate_argnums=...)`` exactly as
   ``benchmarks/transformer.py`` builds it, a few steps on a fixed batch:
   loss finite and lower at the end; the compiled step holds ONE
   ``hvd_flash_fwd`` call (remat ``dots`` saves what the backward reads).
3. **server** — ``serving.InferenceEngine`` with its defaults left alone
   (paged, overlapped, ``paged_kernel=None``, pages of 16, bf16 KV) →
   ``warmup`` → ``serving.ServingServer(port=0)`` → concurrent
   ``POST /generate`` of mixed prompt lengths → ``GET /stats``.  The
   compiled decode tick and the compiled landing are then read: the KV
   pool is written in place, so no instruction of either may have a
   result the size of one layer of it (:func:`pool_sized_results`).
4. on more than one chip — the data-parallel step spread over all of
   them and agreeing with one device, the eager allreduce, and ``tp=n``
   serving answering like ``tp=1``.

Every phase raises on failure; nothing is caught and logged.  Tokens are
judged at logit level: an engine token must equal the oracle's argmax only
where the oracle's top-1/top-2 margin exceeds the stated tolerance (a
random-init bf16 model has near-uniform logits, and an argmax may flip on
any reordering of a bf16 sum).

It needs a TPU: there is no flag or variable that lets it pass without
one.  Off-chip it prints what JAX found and exits non-zero with no result
line.  The last line of a passing run's standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.

The phases are plain functions of a :class:`SmokeConfig`, so
``tests/test_chip_smoke.py`` runs them at toy size on CPU (where the
library interprets the same kernel bodies); this script has no such mode.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.metadata
import json
import os
import re
import shutil
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """One model and how hard to drive it.  The defaults ARE the smoke;
    the tier-1 test shrinks them."""

    vocab_size: int = 32000
    d_model: int = 2048
    n_heads: int = 16
    n_kv_heads: int = 4
    n_layers: int = 8
    d_ff: int = 4096
    seq: int = 2048
    dtype: str = "bfloat16"
    # trainer
    batch_per_chip: int = 4
    train_steps: int = 4
    learning_rate: float = 3e-4
    # server: prompt lengths span several prefill buckets and leave
    # partial last pages (page_size stays the engine default, 16)
    n_slots: int = 4
    serve_max_len: int = 256
    # The KV pool is sized as a deployment's (16 384 pages: 134 M
    # elements a layer, 4.3 GB of bf16 K and V over 8 layers), not as
    # these six requests need: the compiled tick and landing are read
    # for results the size of one layer of it, and at that size nothing
    # else in either program is as large (the embedding is 65.5 M).
    serve_n_pages: int = 16384
    prompt_lens: Tuple[int, ...] = (5, 23, 40, 100, 9, 61)
    max_new_tokens: int = 8
    # Logit-level tolerance, in units of the spread of the logits it is
    # applied to (sigma = max(1, std over the vocabulary)): rms
    # |dlogits| <= logit_tol * sigma, and no single logit off by more
    # than 5x that.  docs/serving.md gives |dlogits| ~ 3e-3 for
    # fused-vs-unfused in bf16 on a toy model; eight full-width bf16
    # layers on the v5e measured rms 1.2e-2 sigma and a max of 5e-2 to
    # 6e-2 sigma over the 96k logits of one tick (PR 21).  Tokens are
    # compared only where the oracle's top-1/top-2 margin is clear of
    # twice the per-logit bound.
    logit_tol: float = 2e-2
    seed: int = 0
    # True on the chip: every kernel must have been COMPILED (Mosaic
    # custom call in the executable, interpret off, engine kernel
    # engaged by its own default).  The CPU test passes False and asks
    # for the (interpreted) paged kernel explicitly.
    expect_compiled: bool = True

    def model_cfg(self):
        from horovod_tpu.models import transformer as T

        return T.TransformerConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            n_layers=self.n_layers, d_ff=self.d_ff, max_seq=self.seq,
            dtype=jnp.dtype(self.dtype), attention_impl="flash",
            remat=True, remat_policy="dots")


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _require_compiled(smoke: SmokeConfig, text: str, at_least: int,
                      what: str) -> None:
    """The executable must contain the Mosaic custom call(s) — i.e. the
    Pallas kernel went through the TPU compiler, not the interpreter and
    not an XLA substitute."""
    if not smoke.expect_compiled:
        return
    from horovod_tpu.ops import _pallas_util

    _require(not _pallas_util.use_interpret(),
             f"{what}: Pallas kernels would be interpreted")
    n = text.count("tpu_custom_call")
    _require(n >= at_least,
             f"{what}: {n} Mosaic custom call(s) in the compiled "
             f"executable, expected at least {at_least}")


def kernel_calls(text: str, name: str) -> int:
    """How many Mosaic custom calls of a compiled program are the Pallas
    kernel ``name`` (its ``pl.pallas_call(name=)``, which the call's
    ``op_name`` carries).  A scanned layer counts once: the loop's body
    holds the call, whatever the depth."""
    return sum(1 for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and f"/{name}/" in line)


# What may have a result the size of the KV pool in a compiled program
# that writes it in place: the pool's own pass-through.
_POOL_PASS_THROUGH = ("parameter", "while", "tuple", "get-tuple-element",
                      "bitcast")
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_HLO_ARRAY = re.compile(r"\b[a-z]+\d+\[([\d,]*)\]")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")


def _elements(dims: str) -> int:
    """``"6,4096,128"`` (an HLO array type's dimensions) -> 3145728."""
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def pool_sized_results(text: str, floor: int):
    """Read a compiled executable's HLO text: ``(offenders, largest)``.

    ``largest`` are the five instructions with the largest results
    (elements of the largest array in the result's type) outside the
    pass-through opcodes; ``offenders`` those of at least ``floor``
    elements that are neither pass-through nor an IN-PLACE write — an
    instruction whose result the compiler aliased to an operand
    (``aliasing_operands`` of a TPU scatter fusion, a custom call's
    ``output_to_operand_aliasing``): the donated pool updated where it
    lies.  Instructions inside a fused computation have no buffer of
    their own and are skipped.  Each entry is ``(elements, opcode,
    name, op_name)``."""
    lines = text.splitlines()
    fused = set()
    for line in lines:
        if " fusion(" in line:
            fused.update(re.findall(r"calls=%?([\w.\-]+)", line))
    found, inside = [], None
    for line in lines:
        comp = _HLO_COMPUTATION.match(line)
        if comp:
            inside = comp.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m or inside in fused:
            continue
        name, result, opcode = m.groups()
        if (opcode in _POOL_PASS_THROUGH
                or 'custom_call_target="ConcatBitcast"' in line):
            continue   # (asynchronous slices' results seen as one array)
        if opcode.endswith("-start") and result.startswith("(("):
            # an asynchronous slice's type restates its OPERANDS first,
            # ``((operands), result, context)``: those are no result
            depth = np.cumsum([(c == "(") - (c == ")") for c in result])
            result = result[1 + int(np.argmax(depth[1:] == 1)):]
        size = max(map(_elements, _HLO_ARRAY.findall(result)), default=0)
        in_place = ('"aliasing_operands":{"lists":[{' in line
                    or "output_to_operand_aliasing={" in line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        found.append((size, opcode, name, op_name.group(1) if op_name else "",
                      in_place))
    found.sort(reverse=True)
    return ([f[:4] for f in found if f[0] >= floor and not f[4]],
            [f[:4] for f in found[:5]])


_HLO_TYPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
_HLO_DTYPES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def product_fusions(text: str) -> Dict[str, str]:
    """Read a compiled executable's HLO text: ``{name: result type}`` of
    every fusion whose fused computation holds a ``convolution`` — a
    matmul, as XLA:TPU writes it."""
    holds, inside = set(), None
    for line in text.splitlines():
        comp = _HLO_COMPUTATION.match(line)
        if comp:
            inside = comp.group(1)
        elif " convolution(" in line:
            holds.add(inside)
    found = {}
    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m and m.group(3) == "fusion" and holds.intersection(
                re.findall(r"calls=%?([\w.\-]+)", line)):
            found[m.group(1)] = m.group(2)
    return found


def products_carrying_an_update(text: str, params) -> Dict[str, str]:
    """The product fusions of a compiled train step that write a
    parameter's worth of a parameter's dtype MORE THAN ONCE: a weight
    gradient's matmul with the optimizer's outputs (AdamW: parameter,
    ``mu``, ``nu``) in its epilogue, which XLA:TPU builds when nothing
    stands between a gradient and its update and which ran at 41-52 % of
    the MXU where the product alone reached 83-87 (PERF.md section 6, PR
    41).  ``optim.distributed_gradients`` hands the optimizer VALUES, so
    there are none."""
    leaves = {(_HLO_DTYPES.get(str(x.dtype)), int(np.prod(x.shape)))
              for x in jax.tree_util.tree_leaves(params)}
    found = {}
    for name, result in product_fusions(text).items():
        written = [(dtype, _elements(dims))
                   for dtype, dims in _HLO_TYPE.findall(result)]
        if any(written.count(w) > 1 for w in leaves.intersection(written)):
            found[name] = result
    return found


def entry_instructions(text: str):
    """Read a compiled executable's HLO text: the entry computation's
    instructions in the order the compiler scheduled them, each ``(name,
    result type, opcode, line)``."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY "))
    for line in lines[start + 1:]:
        if line.startswith("}"):
            return
        m = _HLO_INSTRUCTION.match(line)
        if m:
            yield (*m.groups(), line)


def leaves_relaid_for_a_bucket(text: str, params, floor: int):
    """What a compiled train step pays for reducing a parameter leaf of
    ``floor`` elements or more as a FLAT array: ``(relayouts, passes)``
    out of the entry computation, ``{name: (opcode, result type)}`` and
    ``{name: result type}``.

    ``relayouts`` are the ``copy`` and ``reshape`` instructions whose
    result is such a leaf whole, in its own dimensions, the compiler's or
    flat: on a TPU the leaf lies in tiles of ``(8, 128)`` and the flat
    array in tiles of 1024, so each is a pass over the whole leaf.
    ``passes`` are the fusions under ``opt_update`` with two or more FLAT
    outputs of a leaf's size: AdamW's moments computed on the flat
    buffer, a pass of their own in front of the one that writes
    parameter, ``mu`` and ``nu`` in the leaf's shape.  Five copies, ten
    reshapes and five such passes for the five leaves of 58.7 M and 134 M
    elements of ``m7b-train-dp4`` while a leaf alone in its bucket was
    raveled too (PERF.md section 6, PR 43); ``ops/fusion.py`` reduces it
    in its own shape, so there are none."""
    big = {(_HLO_DTYPES.get(str(x.dtype)), int(np.prod(x.shape)))
           for x in jax.tree_util.tree_leaves(params)
           if int(np.prod(x.shape)) >= floor}

    relayouts, passes = {}, {}
    for name, result, opcode, line in entry_instructions(text):
        # (is it flat, is it a big leaf's dtype and size) of each array
        found = [("," not in dims, (dtype, _elements(dims)) in big)
                 for dtype, dims in _HLO_TYPE.findall(result)]
        if opcode in ("copy", "reshape"):
            if any(leaf for _, leaf in found):
                relayouts[name] = (opcode, result)
        elif opcode == "fusion" and "/opt_update/" in line:
            if sum(flat and leaf for flat, leaf in found) > 1:
                passes[name] = result
    return relayouts, passes


_HLO_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_HLO_CALLED_LIST = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")


_HLO_COLLECTIVES = tuple(
    op + tail for op in ("all-reduce", "all-gather", "all-to-all",
                         "collective-permute", "reduce-scatter")
    for tail in ("", "-start"))


def sorts_by_conditional(text: str, opcodes=("sort",)):
    """Read a compiled executable's HLO text: ``(conditionals, inside,
    outside)`` — how many ``conditional`` instructions survived the
    compiler, and the names of the ``sort`` instructions (or those of
    ``opcodes``) that lie in a computation some conditional's branch
    reaches (run only when that branch is taken) and of those that lie
    elsewhere (run every time).  A conditional the compiler flattened
    into "run both, select" shows as no conditional and every sort
    outside."""
    calls: Dict[str, set] = {}
    sorts: Dict[str, List[str]] = {}
    branches, conditionals, inside = set(), 0, None
    for line in text.splitlines():
        comp = _HLO_COMPUTATION.match(line)
        if comp:
            inside = comp.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        called = set(_HLO_CALLED.findall(line))
        for group in _HLO_CALLED_LIST.findall(line):
            called.update(n.strip().lstrip("%") for n in group.split(","))
        calls.setdefault(inside, set()).update(called)
        if m.group(3) == "conditional":
            conditionals += 1
            branches.update(called)
        elif m.group(3) in opcodes:
            sorts.setdefault(inside, []).append(m.group(1))
    reached, todo = set(), list(branches)
    while todo:
        c = todo.pop()
        if c not in reached:
            reached.add(c)
            todo.extend(calls.get(c, ()))
    return (conditionals,
            sorted(n for c, ns in sorts.items() if c in reached for n in ns),
            sorted(n for c, ns in sorts.items() if c not in reached
                   for n in ns))


def _require_pool_in_place(smoke: SmokeConfig, text: str, floor: int,
                           what: str) -> None:
    """No instruction of ``text`` may produce ``floor`` elements — one
    layer of a KV pool — other than the pool's pass-through and its
    in-place writes.  The largest results are printed either way; the
    requirement is the TPU compiler's to meet (layout assignment is
    its), so off the chip they are only printed."""
    offenders, largest = pool_sized_results(text, floor)
    _say(f"{what}: largest results (a layer of the pool is {floor} "
         f"elements): " + "; ".join(
             f"{n} {op} {name} [{scope}]" for n, op, name, scope in largest))
    if smoke.expect_compiled:
        _require(not offenders,
                 f"{what}: {len(offenders)} instruction(s) with a result "
                 f"the size of a layer of the KV pool: {offenders[:5]}")


def _require_sorts_gated(smoke: SmokeConfig, text: str, what: str) -> None:
    """The next-token pick's sorts (top-k, nucleus) run only in a tick
    whose batch asks for them: in the compiled program the conditionals
    of ``sample_token_rows`` survive, with every sort under a branch.
    Whether a conditional stays one is the TPU compiler's to decide, so
    off the chip the reading is only printed.  (The smoke's model is
    dense, so the pick's are the tick's only sorts; an expert layer's
    routing sorts too, every tick.)  And on every backend: no branch
    holds a collective — under tp the pick takes the logits whole, so
    the devices of one execution never wait for each other inside a
    branch (XLA:CPU can cross-wait there: ``sample_token_rows``)."""
    conditionals, inside, outside = sorts_by_conditional(text)
    collectives = sorts_by_conditional(text, _HLO_COLLECTIVES)[1]
    _say(f"{what}: {conditionals} conditional(s); sorts under a branch "
         f"{inside}, outside {outside}; collectives under a branch "
         f"{collectives}")
    _require(not collectives,
             f"{what}: collectives under a conditional: {collectives}")
    if smoke.expect_compiled:
        _require(conditionals >= 1 and inside and not outside,
                 f"{what}: the pick's sorts are not gated: {conditionals} "
                 f"conditional(s), sorts under a branch {inside}, "
                 f"outside {outside}")


# --- phase 0: what machine is this -------------------------------------------


def report_environment() -> Dict:
    """Print versions and devices first; return the device record."""
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    _say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
         f"libtpu {libtpu} python {sys.version.split()[0]}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _say(f"devices: platform={device['platform']} "
         f"device_kind={device['kind']} count={device['count']}")
    return device


def phase_native() -> None:
    """The C++ control plane must have been built HERE from the tracked
    sources (the .so is git-ignored; ``horovod_tpu.native`` runs ``make``
    on first use)."""
    from horovod_tpu import native

    prebuilt = os.path.exists(native._LIB_PATH)
    cxx = shutil.which(os.environ.get("CXX", "g++"))
    if not native.native_built():
        raise SmokeFailure(
            "native control plane unavailable: "
            + ("this machine has no C++ compiler (g++), so "
               "libhvd_native.so cannot be built from horovod_tpu/native/src"
               if cxx is None else f"build failed: {native.build_error()}"))
    _say("native control plane: "
         + ("loaded an existing libhvd_native.so (sources not newer)"
            if prebuilt else f"built from tracked sources with {cxx}"))


# --- phase 1: kernels against their references -------------------------------


def phase_kernels(smoke: SmokeConfig) -> Dict:
    from horovod_tpu.ops import attention as attn
    from horovod_tpu.ops import paged_attention as pa

    dt = jnp.dtype(smoke.dtype)
    low = dt == jnp.bfloat16
    out_tol = 2e-2 if low else 2e-4
    Dh = smoke.d_model // smoke.n_heads
    S = smoke.seq
    key = jax.random.PRNGKey(smoke.seed)
    kq, kk, kv, kd = jax.random.split(key, 4)
    shape = (1, smoke.n_kv_heads, S, Dh)
    q = jax.random.normal(kq, shape, jnp.float32).astype(dt)
    k = jax.random.normal(kk, shape, jnp.float32).astype(dt)
    v = jax.random.normal(kv, shape, jnp.float32).astype(dt)
    do = jax.random.normal(kd, shape, jnp.float32).astype(dt)
    report = {}

    def flash(q, k, v):
        return attn.flash_attention(q, k, v, True)

    def ref(q, k, v):
        return attn.reference_attention(q, k, v, causal=True)

    fwd = jax.jit(flash).lower(q, k, v).compile()
    _require_compiled(smoke, fwd.as_text(), 1, "flash forward")
    o = fwd(q, k, v)
    o_ref = jax.jit(ref)(q, k, v)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - o_ref.astype(jnp.float32))))
    _require(bool(jnp.isfinite(o.astype(jnp.float32)).all())
             and err <= out_tol,
             f"flash forward vs reference: max|d|={err} > {out_tol}")
    report["flash_fwd_max_abs_err"] = err

    def vjp_of(f):
        def g(q, k, v):
            return jax.vjp(f, q, k, v)[1](do)
        return g

    ref_grads = jax.jit(vjp_of(ref))(q, k, v)
    for name, f in (("plain", flash), ("remat", jax.checkpoint(flash))):
        bwd = jax.jit(vjp_of(f)).lower(q, k, v).compile()
        # forward + dk/dv + dq (remat re-runs the forward: still >= 3)
        _require_compiled(smoke, bwd.as_text(), 3,
                          f"flash backward ({name})")
        worst = 0.0
        for g, g_ref in zip(bwd(q, k, v), ref_grads):
            g32, r32 = g.astype(jnp.float32), g_ref.astype(jnp.float32)
            _require(bool(jnp.isfinite(g32).all()),
                     f"flash grads ({name}) not finite")
            rel = float(jnp.linalg.norm(g32 - r32)
                        / jnp.maximum(jnp.linalg.norm(r32), 1e-6))
            worst = max(worst, rel)
        _require(worst <= out_tol,
                 f"flash grads ({name}) vs reference: rel err {worst} "
                 f"> {out_tol}")
        report[f"flash_bwd_{name}_rel_err"] = worst

    # paged decode attention: same pool, table and limits on both sides
    from horovod_tpu.models import transformer as T

    n_slots, max_pages, n_pages = 5, 24, 64
    G = smoke.n_heads // smoke.n_kv_heads
    rng = np.random.RandomState(smoke.seed)
    for store, ps in (("bf16", 16), ("int8", 32)):
        table = jnp.asarray(rng.randint(1, n_pages, (n_slots, max_pages)),
                            jnp.int32)
        qg = jnp.asarray(rng.randn(n_slots, smoke.n_kv_heads, G, Dh), dt)
        kf = jnp.asarray(rng.randn(n_pages, smoke.n_kv_heads, ps, Dh),
                         jnp.float32)
        vf = jnp.asarray(rng.randn(n_pages, smoke.n_kv_heads, ps, Dh),
                         jnp.float32)
        if store == "int8":
            (kp, ks), (vp, vs) = T.kv_quantize(kf), T.kv_quantize(vf)
            stored = jnp.int8
        else:
            stored = jnp.bfloat16 if low else dt
            kp, vp, ks, vs = kf.astype(stored), vf.astype(stored), None, None
        # the kernel walks the table in blocks of pages (two blocks and
        # three at the smoke's widths): a slot at capacity, one whose
        # limit ends inside the second block, partial last page,
        # inactive slot, one-past-a-page
        block = ps * pa.block_pages(ps, smoke.n_kv_heads, Dh, stored,
                                    max_pages)
        limit = jnp.asarray(
            [ps * max_pages, min(block + ps + 5, ps * max_pages - 3),
             ps * 2 + 5, 0, ps + 1], jnp.int32)
        if smoke.expect_compiled:
            _require(pa.kernel_supported(stored, ps, Dh),
                     f"kernel_supported rejects the smoke's own "
                     f"{store}/page {ps}/Dh {Dh} layout")

        def fused(qg, kp, vp, ks, vs, table, limit):
            return pa.paged_attend(qg, kp, vp, ks, vs, table, limit,
                                   compute_dtype=dt)

        def unfused(qg, kp, vp, ks, vs, table, limit):
            return pa.paged_attend_reference(qg, kp, vp, ks, vs, table,
                                             limit, compute_dtype=dt)

        args = (qg, kp, vp, ks, vs, table, limit)
        run = jax.jit(fused).lower(*args).compile()
        _require_compiled(smoke, run.as_text(), 1,
                          f"paged_attend ({store})")
        o_k, lse_k = run(*args)
        o_r, lse_r = jax.jit(unfused)(*args)
        live = np.asarray(limit) > 0
        e_o = float(jnp.max(jnp.abs(o_k - o_r)))
        e_l = float(np.max(np.abs(np.asarray(lse_k)[live]
                                  - np.asarray(lse_r)[live])))
        _require(e_o <= out_tol and e_l <= out_tol,
                 f"paged_attend ({store}, page {ps}) vs reference: "
                 f"max|do|={e_o} max|dlse|={e_l} > {out_tol}")
        _require(not np.asarray(o_k)[~live].any(),
                 f"paged_attend ({store}): masked slot produced output")
        report[f"paged_{store}_max_abs_err"] = max(e_o, e_l)

        # the same pool through a WINDOW layer's call: a lower bound
        # that starts mid-block, one inside the first block, a window
        # longer than the context, an idle slot, a window of one page
        lower = jnp.maximum(limit - jnp.asarray(
            [block + 3, ps + 5, 10 * ps * max_pages, 0, ps], jnp.int32), 0)
        wargs = args + (lower,)
        run_w = jax.jit(lambda *a: pa.paged_attend(
            *a[:7], compute_dtype=dt, lower=a[7])).lower(*wargs).compile()
        _require_compiled(smoke, run_w.as_text(), 1,
                          f"paged_attend with a lower bound ({store})")
        o_k, lse_k = run_w(*wargs)
        o_r, lse_r = jax.jit(lambda *a: pa.paged_attend_reference(
            *a[:7], compute_dtype=dt, lower=a[7]))(*wargs)
        e_w = max(float(jnp.max(jnp.abs(o_k - o_r))),
                  float(np.max(np.abs(np.asarray(lse_k)[live]
                                      - np.asarray(lse_r)[live]))))
        _require(e_w <= out_tol,
                 f"paged_attend with a lower bound ({store}, page {ps}) "
                 f"vs reference: max err {e_w} > {out_tol}")
        report[f"paged_{store}_window_max_abs_err"] = e_w

    # top-k dropless experts: the grouped products of sorted rows (the
    # Pallas kernel over the stacked layers' experts, and lax.ragged_dot
    # over one layer's) against every expert under a mask
    from horovod_tpu.ops import moe

    L, E, F, topk, rows = 2, 8, 4 * Dh, 2, 4 * smoke.n_heads
    ke = jax.random.split(kd, 5)
    x = jax.random.normal(ke[0], (rows, smoke.d_model), jnp.float32)
    router = jax.random.normal(ke[1], (smoke.d_model, E), jnp.float32)
    s_d, s_f = smoke.d_model ** -0.5, F ** -0.5
    wg = jax.random.normal(ke[2], (L, E, smoke.d_model, F)) * s_d
    wu = jax.random.normal(ke[3], (L, E, smoke.d_model, F)) * s_d
    wd = jax.random.normal(ke[4], (L, E, F, smoke.d_model)) * s_f
    x, wg, wu, wd = (a.astype(dt) for a in (x, wg, wu, wd))
    mask = jnp.arange(rows) % 5 != 0
    layer = jnp.int32(L - 1)

    def stacked(x, wg, wu, wd, layer):
        return moe.dropless_moe(x, router, wg, wu, wd, k=topk,
                                norm_topk=True, token_mask=mask,
                                layer=layer)

    def ragged(x, wg, wu, wd, layer):
        return moe.dropless_moe(x, router, wg[L - 1], wu[L - 1], wd[L - 1],
                                k=topk, norm_topk=True, token_mask=mask)

    def oracle(x, wg, wu, wd, layer):
        top, gate = moe.route_topk(x, router, topk, True)
        w = jnp.zeros((rows, E)).at[jnp.arange(rows)[:, None], top].set(gate)
        xf, g, u, d = (a.astype(jnp.float32)
                       for a in (x, wg[L - 1], wu[L - 1], wd[L - 1]))
        y = jnp.einsum("esf,efd->esd",
                       jax.nn.silu(jnp.einsum("sd,edf->esf", xf, g))
                       * jnp.einsum("sd,edf->esf", xf, u), d)
        return jnp.where(mask[:, None], jnp.einsum("esd,se->sd", y, w), 0.0)

    eargs = (x, wg, wu, wd, layer)
    run_e = jax.jit(stacked).lower(*eargs).compile()
    _require_compiled(smoke, run_e.as_text(), 3, "grouped expert products")
    want = jax.jit(oracle)(*eargs)
    scale = float(jnp.max(jnp.abs(want)))
    for name, got in (("kernel", run_e(*eargs)),
                      ("ragged_dot", jax.jit(ragged)(*eargs))):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / scale
        _require(err <= out_tol, f"top-{topk} dropless experts ({name}) vs "
                 f"every expert under a mask: rel err {err} > {out_tol}")
        report[f"moe_top{topk}_{name}_rel_err"] = err
    _say("kernels: " + json.dumps(report))
    return report


def phase_decode_logits(smoke: SmokeConfig, params) -> Dict:
    """One decode tick at LOGIT level on the full-width model: the fused
    kernel tick against the unfused XLA tick on the same pool."""
    from horovod_tpu import serving
    from horovod_tpu.models import transformer as T

    cfg = smoke.model_cfg()
    n_slots, ps, max_pages = smoke.n_slots, 16, 4
    n_pages = n_slots * max_pages + 1
    rng = np.random.RandomState(smoke.seed + 1)
    pool = serving.init_page_pool(cfg, n_slots, n_pages, ps)
    # random committed context so attention has something to read
    for name in ("k", "v"):
        pool[name] = jnp.asarray(
            rng.randn(*pool[name].shape), pool[name].dtype)
    pos = np.asarray([ps * 2 + 3, 1, ps - 1, ps * 3][:n_slots]
                     + [2] * max(n_slots - 4, 0), np.int32)
    pool["pos"] = jnp.asarray(pos)
    table = jnp.asarray(
        1 + np.arange(n_slots * max_pages).reshape(n_slots, max_pages),
        jnp.int32)
    active = jnp.asarray([True] * n_slots).at[1].set(False)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (n_slots,)),
                         jnp.int32)

    def tick(kernel):
        return jax.jit(lambda p, t, pl_, tb, a: T.decode_step_paged(
            p, t, pl_, tb, cfg, a, kernel=kernel)[0])

    fused = tick(True).lower(params, tokens, pool, table, active).compile()
    _require_compiled(smoke, fused.as_text(), 1, "fused decode tick")
    lk = fused(params, tokens, pool, table, active)
    lu = tick(False)(params, tokens, pool, table, active)
    a = np.asarray(active)
    lk, lu = np.asarray(lk)[a], np.asarray(lu)[a]
    sigma = max(1.0, float(np.std(lu)))
    rms = float(np.sqrt(np.mean(np.square(lk - lu)))) / sigma
    worst = float(np.max(np.abs(lk - lu))) / sigma
    _require(np.isfinite(lk).all() and rms <= smoke.logit_tol
             and worst <= 5 * smoke.logit_tol,
             f"fused vs unfused decode tick: rms|dlogits|={rms:.2e} "
             f"max={worst:.2e} (in logit std {sigma:.2f}) vs tolerance "
             f"{smoke.logit_tol} rms / {5 * smoke.logit_tol} max")
    _say(f"decode tick logits: fused vs unfused rms={rms:.2e} "
         f"max={worst:.2e} of logit std {sigma:.2f} (tolerance "
         f"{smoke.logit_tol} rms, {5 * smoke.logit_tol} max)")
    return {"rms": rms, "max": worst, "logit_std": sigma}


# --- phase 2: the trainer -----------------------------------------------------


def phase_train(smoke: SmokeConfig):
    """A few data-parallel steps built exactly as
    ``benchmarks/transformer.py`` builds them.  Returns
    ``(host params after training, report)``."""
    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.models import transformer as T
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    n = hvd.size()
    _require(n == len(jax.devices()),
             f"hvd.size()={n} but JAX sees {len(jax.devices())} devices")
    cfg = smoke.model_cfg()
    mesh = hvd.mesh()
    params = spmd.init_replicated(
        T.init_params(jax.random.PRNGKey(smoke.seed), cfg))
    opt = hvd.DistributedOptimizer(optax.adamw(smoke.learning_rate))
    opt_state = opt.init(params)

    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, hvd.AXIS))

    step = jax.jit(spmd.shard(
        _step, in_specs=(P(), P(), P(hvd.AXIS)),
        out_specs=(P(), P(), P()), mesh=mesh), donate_argnums=(0, 1))

    rows = smoke.batch_per_chip * n
    tok_host = np.random.RandomState(smoke.seed).randint(
        0, smoke.vocab_size, (rows, smoke.seq))
    host_batch = {"tokens": tok_host.astype(np.int32),
                  "targets": np.roll(tok_host, -1, axis=1).astype(np.int32)}
    batch = jax.device_put(host_batch, NamedSharding(mesh, P(hvd.AXIS)))

    report = {"chips": n, "global_batch_rows": rows}
    _require(len(batch["tokens"].sharding.device_set) == n,
             "batch is not spread over every chip")
    leaf = jax.tree_util.tree_leaves(params)[0]
    _require(len(leaf.sharding.device_set) == n
             and leaf.sharding.is_fully_replicated,
             "parameters are not replicated on every chip")

    if n > 1:
        # The same rows on ONE device, chunk by chunk, before training
        # touches the parameters: the data-parallel loss must agree.
        report["one_device_loss"] = _one_device_loss(
            smoke, cfg, params, host_batch, n)

    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch).compile()
    report["compile_s"] = round(time.perf_counter() - t0, 1)
    # forward + dk/dv + dq kernels in the scanned layer, and NO second
    # forward in the backward loop: remat "dots" saved its two results
    text = compiled.as_text()
    _require_compiled(smoke, text, 3, "train step")
    if smoke.expect_compiled:
        n_fwd = kernel_calls(text, "hvd_flash_fwd")
        report["flash_fwd_calls"] = n_fwd
        _require(n_fwd == 1,
                 f"train step: {n_fwd} hvd_flash_fwd calls in the compiled "
                 "step, expected 1 (the forward loop's; the backward pass "
                 "reads the saved output and log-sum-exp)")

        carrying = products_carrying_an_update(text, params)
        report["products_carrying_an_update"] = sorted(carrying)
        _require(not carrying,
                 "train step: weight-gradient products with the optimizer's "
                 f"outputs in their epilogue: {carrying}")

        # a matrix of 50 M elements fills a 64 MiB bucket alone and is
        # reduced as it lies (this model's embedding, head and MLP
        # leaves: 65.5 M and 67.1 M)
        relaid, passes = leaves_relaid_for_a_bucket(text, params, 50_000_000)
        report["leaves_relaid_for_a_bucket"] = sorted(relaid) + sorted(passes)
        _require(not relaid and not passes,
                 "train step: leaves reduced alone are relaid to a flat "
                 f"buffer and back: {relaid}, with a pass of the optimizer "
                 f"over the flat form: {passes}")

    losses: List[float] = []
    for _ in range(smoke.train_steps):
        params, opt_state, loss = compiled(params, opt_state, batch)
        losses.append(float(np.asarray(jax.device_get(loss))))
    report["losses"] = [round(x, 4) for x in losses]
    _require(all(np.isfinite(losses)), f"loss not finite: {losses}")
    _require(losses[-1] < losses[0],
             f"loss did not fall over {smoke.train_steps} steps: {losses}")
    if n > 1:
        ref = report["one_device_loss"]
        tol = 1e-2 if jnp.dtype(smoke.dtype) == jnp.bfloat16 else 1e-4
        _require(abs(losses[0] - ref) <= tol * max(abs(ref), 1.0),
                 f"data-parallel loss {losses[0]} vs one-device loss "
                 f"{ref} on the same {rows} rows (tol {tol})")
        out = hvd.allreduce(np.ones(4, np.float32), hvd.Sum)
        _require(np.allclose(np.asarray(out), float(n)),
                 f"eager allreduce(ones, Sum) = {np.asarray(out)} != {n}")
        report["eager_allreduce_sum"] = float(np.asarray(out)[0])
    leaf = jax.tree_util.tree_leaves(params)[0]
    _require(len(leaf.sharding.device_set) == n,
             "updated parameters left some chip")
    host_params = jax.device_get(params)
    _say("trainer: " + json.dumps(report))
    return host_params, report


def _one_device_loss(smoke, cfg, params, host_batch, n) -> float:
    from horovod_tpu.models import transformer as T

    dev0 = jax.devices()[0]
    p0 = jax.device_put(jax.device_get(params), dev0)
    loss_of = jax.jit(lambda p, b: T.loss_fn(p, b, cfg))
    b = smoke.batch_per_chip
    parts = []
    for i in range(n):  # equal chunks: the mean of means is the mean
        chunk = {k: jax.device_put(v[i * b:(i + 1) * b], dev0)
                 for k, v in host_batch.items()}
        parts.append(float(np.asarray(jax.device_get(loss_of(p0, chunk)))))
    return float(np.mean(parts))


# --- phase 3: the server --------------------------------------------------------


def _prompts(smoke: SmokeConfig) -> List[List[int]]:
    rng = np.random.RandomState(smoke.seed + 2)
    return [[int(t) for t in rng.randint(0, smoke.vocab_size, n)]
            for n in smoke.prompt_lens]


def _recording(seen: Dict, name: str, fn):
    """``fn``, noting under ``seen[name]`` the shapes (and shardings) of
    its first call's arguments, so that the SAME executable can be
    lowered and read afterwards."""
    def call(*args):
        seen.setdefault(name, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), a.dtype,
                sharding=getattr(a, "sharding", None)), args))
        return fn(*args)
    return call


def phase_serve(smoke: SmokeConfig, host_params, *, tp: int = 1) -> Dict:
    """The path of ``examples/serve.py``: engine -> warmup -> HTTP server
    -> concurrent /generate -> /stats.  Returns the report, including
    every request's tokens."""
    from horovod_tpu import serving

    cfg = smoke.model_cfg()
    if tp == 1:
        params = jax.device_put(host_params, jax.devices()[0])
    else:
        params = host_params  # the engine shards them over its tp mesh
    engine = serving.InferenceEngine(
        params, cfg,
        serving.EngineConfig(
            n_slots=smoke.n_slots, max_len=smoke.serve_max_len, tp=tp,
            n_pages=smoke.serve_n_pages,
            # the chip takes the engine's own default (auto); the CPU
            # test asks for the interpreted kernel explicitly
            paged_kernel=None if smoke.expect_compiled else True))
    # Record the shapes the engine calls its decode tick and its landing
    # with, so the SAME executables can be inspected afterwards.
    seen = {}
    tick, land = engine._tick_fn, engine.slots._insert
    engine._tick_fn = _recording(seen, "tick", tick)
    engine.slots._insert = _recording(seen, "land", land)
    t0 = time.perf_counter()
    engine.warmup(sorted(set(smoke.prompt_lens)))
    warm_s = round(time.perf_counter() - t0, 1)
    warm = engine.stats()
    prompts = _prompts(smoke)
    out: Dict[int, Dict] = {}
    errors: List[str] = []
    with serving.ServingServer(engine, port=0) as srv:
        host, port = srv.address
        base = f"http://{host}:{port}"

        def client(i: int, **sampling) -> None:
            try:
                req = urllib.request.Request(
                    base + "/generate",
                    data=json.dumps({
                        "tokens": prompts[i % len(prompts)],
                        "max_new_tokens": smoke.max_new_tokens,
                        **sampling}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=600) as r:
                    out[i] = json.loads(r.read())
            except Exception as e:  # re-raised below, on the main thread
                errors.append(f"request {i}: {e!r}")

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        with urllib.request.urlopen(base + "/stats", timeout=60) as r:
            greedy = json.loads(r.read())
        # ... then ONE sampled request (temperature, top-k, nucleus): the
        # same executable takes the pick's other branches, as data.
        client(len(prompts), temperature=0.8, top_k=40, top_p=0.9, seed=7)
        with urllib.request.urlopen(base + "/stats", timeout=60) as r:
            stats = json.loads(r.read())
    _require(not errors and len(out) == len(prompts) + 1,
             f"/generate failed: {errors or 'missing replies'}")
    sampled = out.pop(len(prompts))["tokens"]
    _require(len(sampled) == smoke.max_new_tokens
             and all(0 <= t < smoke.vocab_size for t in sampled),
             f"sampled request: bad tokens {sampled}")
    # The host's count of the pick's gates: every greedy tick ran no
    # sort, every tick of the sampled request (alone by then) did.
    ticks, free = "decode_ticks", "sample_ticks_sortfree_total"
    _require(greedy[free] == greedy[ticks] > 0
             and stats[free] == greedy[free]
             and stats[ticks] > greedy[ticks],
             f"the pick's gates: {greedy[free]} of {greedy[ticks]} greedy "
             f"ticks sort-free, then {stats[free] - greedy[free]} of "
             f"{stats[ticks] - greedy[ticks]} with a top-p request")

    _require(stats["engine_restarts"] == 0 and stats["requests_resumed"] == 0,
             f"the supervised tick loop swallowed a failure: "
             f"engine_restarts={stats['engine_restarts']} "
             f"requests_resumed={stats['requests_resumed']}")
    _require(stats["decode_compilations"] == warm["decode_compilations"],
             f"decode recompiled after warmup: "
             f"{warm['decode_compilations']} -> "
             f"{stats['decode_compilations']}")
    _require(stats["paged"] and stats["overlap"]
             and stats["page_size"] == 16,
             "engine defaults changed under the smoke")
    _require(stats["paged_kernel_engaged"] is True,
             "/stats says the fused paged kernel is NOT in the tick")
    _require(stats["requests_completed"] >= len(prompts),
             f"{stats['requests_completed']} requests completed")
    # the engine serves from its own tree: the three projection leaves
    # laid out at load as the product reads them, the caller's as it was
    relaid = sum(host_params["layers"][n].nbytes for n in ("wq", "wk", "wv"))
    _require(stats["params_relaid_bytes"] == relaid
             and np.ndim(params["layers"]["wq"]) == 4,
             f"/stats params_relaid_bytes {stats['params_relaid_bytes']}, "
             f"the three leaves hold {relaid}")
    # The decode tick's own executable, at the shapes it was served at.
    text = tick.lower(*seen["tick"]).compile().as_text()
    _require_compiled(smoke, text, 1, f"decode tick (tp={tp})")
    # ... and the pool it serves from is written where it lies: in the
    # tick, and in the landing of a prefill.
    pool = engine.slots.cache["k"]
    layer = int(np.prod(pool.shape[1:])) // tp
    _require_pool_in_place(smoke, text, layer, f"decode tick (tp={tp})")
    _require_sorts_gated(smoke, text, f"decode tick (tp={tp})")
    _require_pool_in_place(
        smoke, land.lower(*seen["land"]).compile().as_text(), layer,
        f"landing (tp={tp})")

    tokens = {i: out[i]["tokens"] for i in sorted(out)}
    for i, toks in tokens.items():
        _require(len(toks) == smoke.max_new_tokens
                 and all(0 <= t < smoke.vocab_size for t in toks),
                 f"request {i}: bad tokens {toks}")
    checked = _check_against_oracle(smoke, host_params, prompts, tokens)
    report = {
        "tp": tp, "mesh": stats["mesh"], "warmup_s": warm_s,
        "requests": len(prompts), "prompt_lens": list(smoke.prompt_lens),
        "prefill_buckets": stats["prefill_buckets"],
        "decode_compilations": stats["decode_compilations"],
        "paged_kernel_engaged": stats["paged_kernel_engaged"],
        "kv_dtype": stats["kv_dtype"],
        "oracle_positions_checked": checked,
        "oracle_positions_total": len(prompts) * smoke.max_new_tokens,
        "tokens": tokens,
    }
    _say("server: " + json.dumps({k: v for k, v in report.items()
                                  if k != "tokens"}))
    del engine, params
    return report


def _check_against_oracle(smoke, host_params, prompts, tokens, cfg=None,
                          oracle=None):
    """Teacher-forced logit-level check: run the plain XLA forward
    (reference attention, no kernel of ours) over prompt + the engine's
    own tokens; wherever the oracle's top-1/top-2 margin exceeds the
    tolerance the engine's token must BE the oracle's argmax.  ``cfg``:
    another model than the smoke's own.  ``oracle(params, tokens (n,
    width)) -> logits``: another forward than ``T.forward`` (which
    computes one kind of layer)."""
    from horovod_tpu.models import transformer as T

    cfg = dataclasses.replace(cfg or smoke.model_cfg(),
                              attention_impl="reference", remat=False)
    params = jax.device_put(host_params, jax.devices()[0])
    # One padded batch, one compile: the forward is causal, so right
    # padding cannot reach the positions that are read.
    width = max(map(len, prompts)) + smoke.max_new_tokens
    batch = np.zeros((len(prompts), width), np.int32)
    for i, prompt in enumerate(prompts):
        batch[i, :len(prompt) + smoke.max_new_tokens] = prompt + tokens[i]
    forward = oracle or (lambda p, t: T.forward(p, t, cfg))
    logits = np.asarray(jax.jit(forward)(
        params, jnp.asarray(batch)))                   # (n, width, V) f32
    checked = 0
    for i, prompt in enumerate(prompts):
        for j, tok in enumerate(tokens[i]):
            row = logits[i, len(prompt) - 1 + j]
            top2 = np.partition(row, -2)[-2:]
            # per-logit bound (5x the rms tolerance), on either side
            tol = 5 * smoke.logit_tol * max(1.0, float(np.std(row)))
            if top2[1] - top2[0] <= 2 * tol:
                continue  # too close to call at this precision
            checked += 1
            _require(int(np.argmax(row)) == tok,
                     f"request {i} token {j}: engine said {tok}, oracle "
                     f"argmax {int(np.argmax(row))} with margin "
                     f"{top2[1] - top2[0]:.3f} > {2 * tol:.3f}")
    _require(checked > 0, "no position had a margin wide enough to check")
    return checked


def latent_cfg(smoke: SmokeConfig):
    """The smoke's small LATENT-attention model: rows of 128 + 64 in 256
    lanes (what the compiled ``hvd_mla_decode`` can tile), a leading
    dense layer, one shared expert beside 4 held of 8 sigmoid-routed."""
    from horovod_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=smoke.vocab_size, d_model=256, n_heads=2, n_layers=3,
        d_ff=512, max_seq=smoke.serve_max_len, dtype=jnp.dtype(smoke.dtype),
        attention_impl="flash", q_lora_rank=128, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_yarn=(4.0, 64.0, 32.0, 1.0, 1.0, 1.0), n_dense_layers=1,
        n_experts=8, n_experts_held=4, expert_offset=4, n_experts_per_tok=2,
        d_expert=256, n_shared_experts=1, moe_score="sigmoid", n_group=2,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5,
        moe_impl="dropless")


def _serve_small(smoke: SmokeConfig, cfg, seed: int):
    """A small model of another architecture through the engine: seeded
    weights, every prompt of the smoke served to its end, the tick's and
    the landing's first calls recorded for a later lowering ->
    ``(engine, params, prompts, futures, seen, tick, land)``."""
    from horovod_tpu import serving
    from horovod_tpu.models import transformer as T

    params = jax.tree_util.tree_map(
        lambda a: a.astype(cfg.dtype),
        T.init_params(jax.random.PRNGKey(seed), cfg))
    engine = serving.InferenceEngine(params, cfg, serving.EngineConfig(
        n_slots=smoke.n_slots, max_len=smoke.serve_max_len,
        n_pages=smoke.serve_n_pages, prefill_chunk_tokens=32,
        paged_kernel=None if smoke.expect_compiled else True))
    seen = {}
    tick, land = engine._tick_fn, engine.slots._insert
    engine._tick_fn = _recording(seen, "tick", tick)
    engine.slots._insert = _recording(seen, "land", land)
    prompts = _prompts(smoke)
    futs = [engine.submit(p, max_new_tokens=smoke.max_new_tokens)
            for p in prompts]
    while not all(f.done() for f in futs):
        engine.step()
    return engine, params, prompts, futs, seen, tick, land


def phase_serve_latent(smoke: SmokeConfig) -> Dict:
    """The small latent configuration through the engine on one chip:
    whole and chunked prompts, the absorbed decode kernel engaged, the
    tokens ``forward``'s where its margin is clear, and the compiled
    tick and landing writing the ONE latent pool array in place."""
    cfg = latent_cfg(smoke)
    engine, params, prompts, futs, seen, tick, land = _serve_small(
        smoke, cfg, smoke.seed + 3)
    stats = engine.stats()
    _require(stats["paged_kernel_engaged"] is True
             and stats["decode_compilations"] == 1,
             f"latent engine: kernel engaged "
             f"{stats['paged_kernel_engaged']}, decode compilations "
             f"{stats['decode_compilations']}")
    _require(stats["kv_latent_bytes_per_token"] == cfg.n_layers
             * cfg.latent_row * jnp.dtype(cfg.dtype).itemsize
             and set(engine.slots.cache) == {"k", "pos"},
             f"the latent pool's layout: {stats['kv_latent_bytes_per_token']}"
             f" B a token, arrays {sorted(engine.slots.cache)}")
    _require(stats["moe_rows_routed_away_total"] > 0,
             "a share of the experts routed nothing away")
    text = tick.lower(*seen["tick"]).compile().as_text()
    _require_compiled(smoke, text, 1, "latent decode tick")
    layer = int(np.prod(engine.slots.cache["k"].shape[1:]))
    _require_pool_in_place(smoke, text, layer, "latent decode tick")
    _require_pool_in_place(
        smoke, land.lower(*seen["land"]).compile().as_text(), layer,
        "latent landing")
    # the tokens are forward's own, where its margin is clear
    tokens = {i: f.result() for i, f in enumerate(futs)}
    checked = _check_against_oracle(smoke, params, prompts, tokens, cfg)
    report = {"requests": len(prompts), "oracle_positions_checked": checked,
              "kv_latent_bytes_per_token":
                  stats["kv_latent_bytes_per_token"],
              "moe_rows_here": stats["moe_rows_total"],
              "moe_rows_routed_away": stats["moe_rows_routed_away_total"]}
    _say("latent server: " + json.dumps(report))
    del engine, params
    return report


def sparse_cfg(smoke: SmokeConfig):
    """:func:`latent_cfg` with a lightning indexer (4 index heads of
    128, ``index_topk`` half the longest prompt: 50 of 100, so the long
    contexts select and the short ones keep everything) and the router's
    score-correction bias."""
    return dataclasses.replace(
        latent_cfg(smoke), index_n_heads=4, index_head_dim=128,
        index_topk=max(max(smoke.prompt_lens) // 2, 1), moe_score_bias=True)


def phase_serve_sparse(smoke: SmokeConfig) -> Dict:
    """The small SPARSE configuration through the engine on one chip:
    contexts past ``index_topk``, the index walk and the selected attend
    compiled (``hvd_dsa_score``, ``hvd_dsa_attend``), both pool arrays
    written in place by the compiled tick and landing, the counters as
    the layout says, the tokens ``forward``'s where its margin is
    clear."""
    from horovod_tpu.ops import paged_attention as PA

    cfg = sparse_cfg(smoke)
    engine, params, prompts, futs, seen, tick, land = _serve_small(
        smoke, cfg, smoke.seed + 4)
    stats = engine.stats()
    item = jnp.dtype(cfg.dtype).itemsize
    _require(stats["paged_kernel_engaged"] is True
             and stats["decode_compilations"] == 1,
             f"sparse engine: kernel engaged "
             f"{stats['paged_kernel_engaged']}, decode compilations "
             f"{stats['decode_compilations']}")
    _require(stats["kv_index_bytes_per_token"] == cfg.n_layers
             * cfg.index_head_dim * item
             and stats["kv_latent_bytes_per_token"] == cfg.n_layers
             * cfg.latent_row * item
             and set(engine.slots.cache) == {"k", "ik", "pos"},
             f"the two-array pool's layout: latent "
             f"{stats['kv_latent_bytes_per_token']} + index "
             f"{stats['kv_index_bytes_per_token']} B a token, arrays "
             f"{sorted(engine.slots.cache)}")
    # every decoded token of a request alone scores its whole context
    # and selects min(index_topk, context) of it
    # (the overlapped loop may have dispatched one tick more a request)
    ctx = [len(p) + j + 1 for p in prompts
           for j in range(smoke.max_new_tokens - 1)]
    last = [len(p) + smoke.max_new_tokens for p in prompts]
    scored, picked = (stats["dsa_scored_tokens_total"],
                      stats["dsa_selected_tokens_total"])
    k = cfg.index_topk
    _require(sum(ctx) <= scored <= sum(ctx) + sum(last)
             and sum(min(k, c) for c in ctx) <= picked
             <= sum(min(k, c) for c in ctx + last)
             and picked < scored and max(ctx) > k,
             f"sparse counters: scored {stats['dsa_scored_tokens_total']} "
             f"(contexts sum {sum(ctx)}), selected "
             f"{stats['dsa_selected_tokens_total']}")
    text = tick.lower(*seen["tick"]).compile().as_text()
    _require_compiled(smoke, text, 2, "sparse decode tick")
    _require(not smoke.expect_compiled or (
        PA.INDEX_KERNEL_NAME in text and PA.SELECT_ATTEND_NAME in text),
        "sparse decode tick: hvd_dsa_score / hvd_dsa_attend not compiled")
    layer = min(int(np.prod(engine.slots.cache[n].shape[1:]))
                for n in ("k", "ik"))
    _require_pool_in_place(smoke, text, layer, "sparse decode tick")
    _require_pool_in_place(
        smoke, land.lower(*seen["land"]).compile().as_text(), layer,
        "sparse landing")
    tokens = {i: f.result() for i, f in enumerate(futs)}
    checked = _check_against_oracle(smoke, params, prompts, tokens, cfg)
    report = {"requests": len(prompts), "oracle_positions_checked": checked,
              "kv_index_bytes_per_token": stats["kv_index_bytes_per_token"],
              "dsa_scored_tokens": stats["dsa_scored_tokens_total"],
              "dsa_selected_tokens": stats["dsa_selected_tokens_total"],
              "dsa_full_rows": stats["dsa_full_rows_total"]}
    _say("sparse server: " + json.dumps(report))
    del engine, params
    return report


def conv_cfg(smoke: SmokeConfig):
    """The smoke's small model of gated SHORT CONVOLUTIONS between
    attention layers: conv, conv (dense) then full, conv, conv, conv
    (2 of 8 sigmoid-routed experts under a bias); 4 query / 2 KV heads
    of 64 stored two to a 128-lane row; a head tied to the embedding."""
    from horovod_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=smoke.vocab_size, d_model=256, n_heads=4, n_kv_heads=2,
        d_head=64, n_layers=6, n_dense_layers=2, d_ff=512,
        max_seq=smoke.serve_max_len, dtype=jnp.dtype(smoke.dtype),
        attention_impl="flash", layer_pattern=("conv", "conv", "full",
                                               "conv"),
        conv_kernel=3, tie_embeddings=True, kv_lane_dense=True,
        qk_norm=True, n_experts=8, n_experts_per_tok=2, d_expert=256,
        moe_score="sigmoid", moe_score_bias=True, norm_topk_prob=True,
        norm_topk_eps=1e-6, moe_impl="dropless")


def phase_serve_conv(smoke: SmokeConfig) -> Dict:
    """The small CONV configuration through the engine on one chip:
    whole and chunked prompts (a state handed from chunk to chunk), the
    fused paged kernel engaged over rows that two heads of 64 share, the
    pages AND the per-slot conv state written in place by the compiled
    tick and landing, the tokens the plain reference's where its margin
    is clear; and the flash forward at heads of 64 against the XLA
    form."""
    from horovod_tpu.models import plain_reference as R
    from horovod_tpu.ops import attention as attn
    from horovod_tpu.ops import paged_attention as PA

    cfg = conv_cfg(smoke)
    engine, params, prompts, futs, seen, tick, land = _serve_small(
        smoke, cfg, smoke.seed + 5)
    stats = engine.stats()
    item = jnp.dtype(cfg.dtype).itemsize
    _require(stats["paged_kernel_engaged"] is True
             and stats["decode_compilations"] == 1,
             f"conv engine: kernel engaged {stats['paged_kernel_engaged']}"
             f", decode compilations {stats['decode_compilations']}")
    pool = engine.slots.cache
    _require(set(pool) == {"k", "v", "conv", "pos"}
             and pool["k"].shape[2:] == (1, engine.slots.page_size, 128)
             and pool["conv"].shape == (5, smoke.n_slots, 2, 256)
             and stats["kv_bytes_per_token"] == 2 * 2 * 64 * item
             and stats["conv_state_bytes_per_slot"] == 5 * 2 * 256 * item,
             f"the conv pool's layout: arrays {sorted(pool)}, k "
             f"{pool['k'].shape}, {stats['kv_bytes_per_token']} B a token, "
             f"{stats['conv_state_bytes_per_slot']} B of state a slot")
    text = tick.lower(*seen["tick"]).compile().as_text()
    _require_compiled(smoke, text, 2, "conv decode tick")
    _require(not smoke.expect_compiled or PA.KERNEL_NAME in text,
             "conv decode tick: hvd_paged_attend not compiled")
    layer = int(np.prod(pool["k"].shape[1:]))
    _require_pool_in_place(smoke, text, layer, "conv decode tick")
    _require_pool_in_place(
        smoke, land.lower(*seen["land"]).compile().as_text(), layer,
        "conv landing")
    # the flash forward at heads of 64 (a whole prompt's attention)
    rng = np.random.RandomState(smoke.seed)
    q, k, v = (jnp.asarray(rng.randn(1, 4, 512, 64), cfg.dtype)
               for _ in range(3))
    fwd = jax.jit(lambda q, k, v: attn.flash_attention(
        q, k, v, True)).lower(q, k, v).compile()
    _require_compiled(smoke, fwd.as_text(), 1, "flash forward, heads of 64")
    err = float(jnp.max(jnp.abs(
        fwd(q, k, v).astype(jnp.float32) - attn.reference_attention(
            q, k, v, causal=True).astype(jnp.float32))))
    tol = 2e-2 if cfg.dtype == jnp.bfloat16 else 2e-4
    _require(err <= tol, f"flash forward at heads of 64: max|d|={err}")
    # the tokens are the plain reference's, where its margin is clear
    dims = dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        norm_eps=cfg.norm_eps, num_dense_layers=cfg.n_dense_layers,
        num_experts_per_tok=cfg.n_experts_per_tok, norm_topk_prob=True,
        routed_scaling_factor=1.0,
        rope_parameters={"rope_theta": cfg.rope_theta,
                         "rope_type": "default"},
        layer_types=["conv" if kind == "conv" else "full_attention"
                     for kind in cfg.layer_kinds])
    tokens = {i: f.result() for i, f in enumerate(futs)}
    checked = _check_against_oracle(
        smoke, params, prompts, tokens, cfg,
        oracle=lambda p, t: jax.vmap(
            lambda row: R.conv_forward(p, row, dims))(t))
    report = {"requests": len(prompts), "oracle_positions_checked": checked,
              "flash_fwd_d64_max_abs_err": err,
              "kv_bytes_per_token": stats["kv_bytes_per_token"],
              "conv_state_bytes_per_slot":
                  stats["conv_state_bytes_per_slot"]}
    _say("conv server: " + json.dumps(report))
    del engine, params
    return report


def hybrid_cfg(smoke: SmokeConfig):
    """The smoke's small model of TWO-MIXER layers: 10 query / 2 KV
    heads of 128 (a GQA group of five) beside a state-space mixer of 4
    heads of 128 with a state of 128 columns in 2 groups and 4 taps,
    every published multiplier set."""
    from horovod_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=smoke.vocab_size, d_model=256, n_heads=10, n_kv_heads=2,
        d_head=128, n_layers=3, d_ff=512, max_seq=smoke.serve_max_len,
        dtype=jnp.dtype(smoke.dtype), attention_impl="flash",
        layer_pattern=("hybrid",), conv_kernel=4, ssm_heads=4,
        ssm_head_dim=128, ssm_state=128, ssm_groups=2, ssm_chunk=32,
        embed_multiplier=2.0, head_multiplier=0.5, attn_out_multiplier=0.5,
        key_multiplier=0.5, ssm_in_multiplier=0.5, ssm_out_multiplier=0.7,
        ssm_multipliers=(0.7, 0.5, 0.7, 1.0, 0.7),
        mlp_multipliers=(0.5, 0.5))


def phase_serve_hybrid(smoke: SmokeConfig) -> Dict:
    """The small HYBRID configuration through the engine on one chip:
    whole and chunked prompts (both states handed from chunk to chunk),
    the fused paged kernel at a group of five AND the state update's
    kernel compiled into the tick, the pages and both per-slot states
    written in place by the compiled tick and landing, a slot's states
    zero at its next grant, the tokens the plain reference's where its
    margin is clear."""
    from horovod_tpu.models import plain_reference as R
    from horovod_tpu.ops import paged_attention as PA
    from horovod_tpu.ops import ssm as SSM

    cfg = hybrid_cfg(smoke)
    engine, params, prompts, futs, seen, tick, land = _serve_small(
        smoke, cfg, smoke.seed + 7)
    stats = engine.stats()
    item = jnp.dtype(cfg.dtype).itemsize
    _require(stats["paged_kernel_engaged"] is True
             and stats["decode_compilations"] == 1,
             f"hybrid engine: kernel engaged {stats['paged_kernel_engaged']}"
             f", decode compilations {stats['decode_compilations']}")
    pool = engine.slots.cache
    _require(set(pool) == {"k", "v", "conv", "ssm", "pos"}
             and pool["k"].shape[0] == 3
             and pool["conv"].shape == (3, smoke.n_slots, 3, 1024)
             and pool["ssm"].shape == (3, smoke.n_slots, 4, 128, 128)
             and stats["kv_bytes_per_token"] == 3 * 2 * 2 * 128 * item
             and stats["ssm_state_bytes_per_slot"] == 3 * 4 * 128 * 128 * item
             and stats["conv_state_bytes_per_slot"] == 3 * 3 * 1024 * item,
             f"the hybrid pool's layout: arrays {sorted(pool)}, "
             f"{stats['kv_bytes_per_token']} B a token, "
             f"{stats['ssm_state_bytes_per_slot']} + "
             f"{stats['conv_state_bytes_per_slot']} B of state a slot")
    n_prompt = sum(map(len, prompts))
    # (an overlapped engine dispatches up to one tick past a request's
    # last token: the counter counts the rows DISPATCHED)
    rows = stats["ssm_updated_slots_total"] / (3 * len(prompts))
    _require(stats["ssm_scanned_tokens_total"] == 3 * n_prompt
             and smoke.max_new_tokens - 1 <= rows <= smoke.max_new_tokens,
             f"state-space counters: scanned "
             f"{stats['ssm_scanned_tokens_total']} (prompts {n_prompt} x 3 "
             f"layers), updated {stats['ssm_updated_slots_total']}")
    text = tick.lower(*seen["tick"]).compile().as_text()
    _require_compiled(smoke, text, 2, "hybrid decode tick")
    _require(not smoke.expect_compiled or (
        PA.KERNEL_NAME in text and SSM.UPDATE_NAME in text),
        "hybrid decode tick: hvd_paged_attend / hvd_ssm_update not compiled")
    # (a layer of the pages; at four slots a layer of the states is
    # smaller than the head's weights, so THAT array's turn is
    # tests/test_tpu_aot.py's, at 64 slots of 2 MiB)
    layer = int(np.prod(pool["k"].shape[1:]))
    _require_pool_in_place(smoke, text, layer, "hybrid decode tick")
    _require_pool_in_place(
        smoke, land.lower(*seen["land"]).compile().as_text(), layer,
        "hybrid landing")
    # a released slot keeps what its tenant left until its NEXT grant
    left = float(jnp.abs(pool["ssm"][:, 0]).max())
    slot = engine.slots.alloc()
    fresh = max(float(jnp.abs(engine.slots.cache[n][:, slot]).max())
                for n in ("conv", "ssm"))
    engine.slots.free(slot)
    _require(slot == 0 and left > 0 and fresh == 0.0,
             f"slot {slot}'s states at its next grant: {fresh} (its last "
             f"tenant left {left})")
    dims = dict(
        hidden_size=cfg.d_model, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        num_hidden_layers=cfg.n_layers, mamba_d_ssm=cfg.ssm_inner,
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_d_state=cfg.ssm_state, mamba_n_groups=cfg.ssm_groups,
        mamba_d_conv=cfg.conv_kernel,
        embedding_multiplier=cfg.embed_multiplier,
        lm_head_multiplier=cfg.head_multiplier,
        attention_in_multiplier=cfg.attn_in_multiplier,
        attention_out_multiplier=cfg.attn_out_multiplier,
        key_multiplier=cfg.key_multiplier,
        ssm_in_multiplier=cfg.ssm_in_multiplier,
        ssm_out_multiplier=cfg.ssm_out_multiplier,
        ssm_multipliers=cfg.ssm_multipliers,
        mlp_multipliers=cfg.mlp_multipliers)
    tokens = {i: f.result() for i, f in enumerate(futs)}
    checked = _check_against_oracle(
        smoke, params, prompts, tokens, cfg,
        oracle=lambda p, t: jax.vmap(
            lambda row: R.hybrid_forward(p, row, dims))(t))
    report = {"requests": len(prompts), "oracle_positions_checked": checked,
              "kv_bytes_per_token": stats["kv_bytes_per_token"],
              "ssm_state_bytes_per_slot": stats["ssm_state_bytes_per_slot"],
              "ssm_updated_slots": stats["ssm_updated_slots_total"],
              "ssm_scanned_tokens": stats["ssm_scanned_tokens_total"]}
    _say("hybrid server: " + json.dumps(report))
    del engine, params
    return report


def linear_sparse_cfg(smoke: SmokeConfig):
    """The smoke's small model of LINEAR-ATTENTION layers between
    BLOCK-SPARSE attention layers: 4 query / 2 KV heads of 128, sparse,
    linear, linear, sparse; windows of 32 keys every
    16 (the page), blocks of 32, the first block, a window of one and
    the best of the rest, the switch at 32 tokens — the longest prompts
    cross it in chunks and tick behind it; the muP scales set."""
    from horovod_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=smoke.vocab_size, d_model=256, n_heads=4, n_kv_heads=2,
        d_head=128, n_layers=4, d_ff=512, max_seq=smoke.serve_max_len,
        dtype=jnp.dtype(smoke.dtype), attention_impl="flash", qk_norm=True,
        layer_pattern=("block_sparse", "linear", "linear", "block_sparse"),
        bsa_kernel=32, bsa_stride=16,
        bsa_block=32, bsa_topk=1, bsa_window=32, bsa_init_blocks=1,
        bsa_dense_len=32, ssm_chunk=32, embed_multiplier=2.0,
        head_multiplier=0.5, attn_out_multiplier=0.5,
        mlp_multipliers=(1.0, 0.5))


def phase_serve_linear_sparse(smoke: SmokeConfig) -> Dict:
    """The small LINEAR + BLOCK-SPARSE configuration through the engine
    on one chip: whole and chunked prompts (the float32 state handed
    from chunk to chunk), the paged kernel over a table a slot and KV
    head AND the state update's kernel at a group a head compiled into
    the tick, the pages, the compressed keys and the state written in
    place by the compiled tick and landing, the tokens the plain
    reference's where its margin is clear."""
    from horovod_tpu.models import plain_reference as R
    from horovod_tpu.ops import paged_attention as PA
    from horovod_tpu.ops import ssm as SSM

    cfg = linear_sparse_cfg(smoke)
    engine, params, prompts, futs, seen, tick, land = _serve_small(
        smoke, cfg, smoke.seed + 9)
    stats = engine.stats()
    item = jnp.dtype(cfg.dtype).itemsize
    _require(stats["paged_kernel_engaged"] is True
             and stats["decode_compilations"] == 1,
             f"linear-sparse engine: kernel engaged "
             f"{stats['paged_kernel_engaged']}, decode compilations "
             f"{stats['decode_compilations']}")
    pool = engine.slots.cache
    _require(set(pool) == {"k", "v", "ck", "lin", "pos"}
             and pool["k"].shape[0] == 2
             and pool["ck"].shape == (2, smoke.n_slots, 2,
                                      engine.slots.max_pages, 128)
             and pool["lin"].shape == (2, smoke.n_slots, 4, 128, 128)
             and pool["lin"].dtype == jnp.float32
             and stats["kv_bytes_per_token"] == 2 * 2 * 2 * 128 * item
             and stats["kv_compressed_bytes_per_page"] == 2 * 2 * 128 * item
             and stats["lin_state_bytes_per_slot"] == 2 * 4 * 128 * 128 * 4,
             f"the linear-sparse pool's layout: arrays {sorted(pool)}, "
             f"{stats['kv_bytes_per_token']} B a token, "
             f"{stats['kv_compressed_bytes_per_page']} B a page, "
             f"{stats['lin_state_bytes_per_slot']} B of state a slot")
    n_prompt = sum(map(len, prompts))
    rows = stats["lin_updated_slots_total"] / (2 * len(prompts))
    _require(stats["lin_scanned_tokens_total"] == 2 * n_prompt
             and smoke.max_new_tokens - 1 <= rows <= smoke.max_new_tokens
             # (less than it holds once a context passes three blocks)
             and 0 < stats["bsa_attended_tokens_total"] < (
                 stats["bsa_live_tokens_total"]
                 + (max(map(len, prompts)) <= 3 * cfg.bsa_block)),
             f"linear-sparse counters: scanned "
             f"{stats['lin_scanned_tokens_total']} (prompts {n_prompt} x 2 "
             f"layers), updated {stats['lin_updated_slots_total']}, attended "
             f"{stats['bsa_attended_tokens_total']} of "
             f"{stats['bsa_live_tokens_total']}")
    text = tick.lower(*seen["tick"]).compile().as_text()
    _require_compiled(smoke, text, 4, "linear-sparse decode tick")
    _require(not smoke.expect_compiled or (
        kernel_calls(text, PA.BSA_KERNEL_NAME) == 2
        and kernel_calls(text, SSM.UPDATE_NAME) == 2),
        "linear-sparse decode tick: hvd_bsa_attend / hvd_ssm_update not "
        "compiled twice each")
    layer = int(np.prod(pool["k"].shape[1:]))
    _require_pool_in_place(smoke, text, layer, "linear-sparse decode tick")
    _require_pool_in_place(
        smoke, land.lower(*seen["land"]).compile().as_text(), layer,
        "linear-sparse landing")
    dims = dict(
        hidden_size=cfg.d_model, head_dim=cfg.head_dim,
        lightning_nh=cfg.n_heads, lightning_head_dim=cfg.head_dim,
        qk_norm=True, lightning_use_rope=True, attn_use_rope=False,
        attn_use_output_gate=True, rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, num_hidden_layers=cfg.n_layers,
        scale_emb=cfg.embed_multiplier, dim_model_base=128,
        scale_depth=cfg.attn_out_multiplier * 2.0,   # r = 0.5 at 4 layers
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"],
        sparse_config=dict(kernel_size=32, kernel_stride=16, block_size=32,
                           topk=1, window_size=32, init_blocks=1,
                           dense_len=32))
    tokens = {i: f.result() for i, f in enumerate(futs)}
    checked = _check_against_oracle(
        smoke, params, prompts, tokens, cfg,
        oracle=lambda p, t: jax.vmap(
            lambda row: R.sala_forward(p, row, dims))(t))
    report = {"requests": len(prompts), "oracle_positions_checked": checked,
              "kv_bytes_per_token": stats["kv_bytes_per_token"],
              "lin_state_bytes_per_slot": stats["lin_state_bytes_per_slot"],
              "lin_updated_slots": stats["lin_updated_slots_total"],
              "bsa_attended_tokens": stats["bsa_attended_tokens_total"],
              "bsa_live_tokens": stats["bsa_live_tokens_total"]}
    _say("linear-sparse server: " + json.dumps(report))
    del engine, params
    return report


def phase_tp(smoke: SmokeConfig, host_params, tp: int, single: Dict) -> Dict:
    """``EngineConfig(tp=n)`` answers the same requests as ``tp=1``."""
    report = phase_serve(smoke, host_params, tp=tp)
    _require(f"tp={tp}" in report["mesh"],
             f"/stats mesh {report['mesh']!r} does not name tp={tp}")
    # Both were held to the oracle where its margin is wide; what is
    # left is how often they agree with EACH OTHER overall.
    same = sum(a == b for i in single["tokens"]
               for a, b in zip(single["tokens"][i], report["tokens"][i]))
    report["tokens_equal_to_tp1"] = same
    _say(f"tp={tp}: {same}/{report['oracle_positions_total']} tokens equal "
         f"to tp=1 (both oracle-checked where the margin allows)")
    return report


# --- the script ----------------------------------------------------------------


def run(smoke: SmokeConfig, *, tp: Optional[int] = None) -> Dict:
    """Every phase, in order; raises on the first failure.  ``tp``: the
    tensor-parallel degree of the extra serving pass (None = skip)."""
    phase_native()
    report = {"kernels": phase_kernels(smoke)}
    host_params, report["train"] = phase_train(smoke)
    params0 = jax.device_put(host_params, jax.devices()[0])
    report["decode_logits"] = phase_decode_logits(smoke, params0)
    del params0
    gc.collect()
    report["serve"] = phase_serve(smoke, host_params)
    gc.collect()  # the engine is a reference cycle holding device buffers
    report["serve_latent"] = phase_serve_latent(smoke)
    gc.collect()
    report["serve_sparse"] = phase_serve_sparse(smoke)
    gc.collect()
    report["serve_conv"] = phase_serve_conv(smoke)
    gc.collect()
    report["serve_hybrid"] = phase_serve_hybrid(smoke)
    gc.collect()
    report["serve_linear_sparse"] = phase_serve_linear_sparse(smoke)
    gc.collect()
    if tp:
        report["serve_tp"] = phase_tp(smoke, host_params, tp,
                                      report["serve"])
    return report


def main() -> int:
    device = report_environment()
    if device["platform"] != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found platform "
              f"{device['platform']!r}.  Nothing was run.", file=sys.stderr)
        return 2
    import horovod_tpu as hvd

    _say(f"compile cache: {hvd.place_compile_cache()}"
         + (" (from JAX_COMPILATION_CACHE_DIR)"
            if os.environ.get("JAX_COMPILATION_CACHE_DIR") else ""))
    t0 = time.perf_counter()
    n = device["count"]
    report = run(SmokeConfig(), tp=n if n > 1 else None)
    hvd.shutdown()
    _say(f"all phases passed in {time.perf_counter() - t0:.0f}s "
         f"(train compile {report['train']['compile_s']}s, "
         f"server warmup {report['serve']['warmup_s']}s)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
