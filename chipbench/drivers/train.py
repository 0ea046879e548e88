"""A trained model: ``hvd.init()``, ``hvd.DistributedOptimizer(optax.adamw)``
and ``jax.jit(spmd.shard(step), donate...)`` as ``chip_smoke.py`` builds
them, fed by a host iterator that runs during the window.

Set-up builds ONE object — the compiled step with its state — drives it
from the seed through its first steps with the window's own call and feed,
and hands that same object to the window.  After the window the state is
freed and the reference follows those first steps: each step's loss, the
first gradient's norm leaf by leaf (read back from AdamW's first moment
after one step) and the norm of the parameters' change."""

from __future__ import annotations

import gc
import importlib
import json
import os
import queue
import shutil
import threading
import time

import numpy as np

from chipbench import costs, harness, peaks, reference, weights, xplane
from chipbench.harness import say


class Feed:
    """The window's feed: a host thread makes the next batches while the
    device works; ``next()`` places one on the chips."""

    def __init__(self, batches, sharding, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._sharding = sharding

        def work():
            for b in batches:
                while not self._stop.is_set():
                    try:
                        self._q.put(b, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return

        self._t = threading.Thread(target=work, name="chipbench-feed",
                                   daemon=True)
        self._t.start()

    def next(self):
        import jax

        return jax.device_put(self._q.get(), self._sharding)

    def close(self) -> None:
        self._stop.set()
        self._t.join(10)


def _find_mu(opt_state):
    """AdamW's first moment, wherever the optimizer chain keeps it."""
    import jax

    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise RuntimeError("no first-moment (mu) state in the optimizer state")


def _named(tree) -> dict:
    """{"layers/wq": float} for a tree of scalars."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in flat}


def _norms(tree) -> dict:
    """{path: float norm} of every leaf."""
    import jax

    return _named(jax.jit(reference.leaf_norms)(tree))


def _delta_norms(params, seed: int, dims: dict, dtype) -> dict:
    """Norm of (params now - params at the seed), leaf by leaf, the seeded
    leaf made again so that no second copy of the model is held."""
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out = {}
    for path, _ in weights.leaf_paths(dims):
        leaf = params
        for k in path:
            leaf = leaf[k]
        start = weights.one_leaf(seed, path, dims, dtype)
        out["/".join(path)] = float(diff(leaf, jax.device_put(
            start, leaf.sharding)))
    return out


def worst_leaf_gap(prog: dict, ref: dict) -> tuple:
    """The widest gap between the program's norm and the reference's over
    the leaves, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for k, r in ref.items():
        g = abs(prog[k] - r) / max(r, med, 1e-30)
        if g >= worst:
            worst, where = g, k
    return worst, where


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        control: bool, t0: float, device: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.models import transformer as T

    marks = {"import": time.monotonic() - t0}
    dims, traffic = cell["dims"], cell["traffic_params"]
    tr, opt_cfg, chk = dims["train"], dims["optimizer"], dims["check"]
    say(f"compile cache: {harness.place_caches()}")
    hvd.init()
    n = hvd.size()
    if n != len(jax.devices()):
        raise RuntimeError(f"hvd.size()={n}, JAX sees {len(jax.devices())}")
    mesh = hvd.mesh()
    seq, rows = int(traffic["seq"]), int(traffic["rows_per_chip"]) * n
    cfg = T.TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["hidden_size"],
        n_heads=dims["num_attention_heads"],
        n_kv_heads=dims["num_key_value_heads"],
        n_layers=dims["num_hidden_layers"], d_ff=dims["intermediate_size"],
        max_seq=seq, rope_theta=dims["rope_theta"],
        dtype=jnp.dtype(tr["compute_dtype"]),
        attention_impl=dims["attention_impl"], remat=tr["remat"],
        remat_policy=tr["remat_policy"])
    repl = NamedSharding(mesh, P())
    pdtype = jnp.dtype(tr["param_dtype"])
    params = weights.make_params(seed, dims, pdtype, repl)
    opt = hvd.DistributedOptimizer(optax.adamw(
        opt_cfg["learning_rate"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"]))
    opt_state = jax.jit(opt.init, out_shardings=repl)(params)
    jax.block_until_ready((params, opt_state))
    marks["weights"] = time.monotonic() - t0

    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, hvd.AXIS))

    step = jax.jit(spmd.shard(
        _step, in_specs=(P(), P(), P(hvd.AXIS)),
        out_specs=(P(), P(), P()), mesh=mesh), donate_argnums=(0, 1))
    gen = importlib.import_module(
        f"chipbench.generators.{traffic['generator']}")
    feed = Feed(gen.batches(traffic, seed, rows, dims["vocab_size"]),
                NamedSharding(mesh, P(hvd.AXIS)))
    n_chk = int(chk["steps"])
    prog = {"loss": []}
    try:
        for i in range(n_chk):
            params, opt_state, loss = step(params, opt_state, feed.next())
            prog["loss"].append(float(loss))
            if i == 0:
                marks["first_step"] = time.monotonic() - t0
                prog["grad"] = {k: v / (1.0 - opt_cfg["b1"]) for k, v in
                                _norms(_find_mu(opt_state)).items()}
        prog["delta"] = _delta_norms(params, seed, dims, pdtype)
        marks["check_steps"] = time.monotonic() - t0
        # the window: the same step, state and feed
        watch = harness.WindowWatch().install()
        watch.arm(True)
        t_open = time.monotonic()
        setup_s = t_open - t0
        t_close = t_open + seconds
        step_s, losses, tr_obs = [], [], {}
        trace_at = int(traffic.get("trace_after_steps", 4)) if trace else -1
        trace_n = int(traffic.get("trace_steps", 6))
        while time.monotonic() < t_close:
            k = len(step_s)
            if k == trace_at:
                out_dir = _trace_dir(cell)
                jax.profiler.start_trace(out_dir)
            a = time.monotonic()
            with jax.profiler.TraceAnnotation("chipbench:next_batch"):
                batch = feed.next()
            with jax.profiler.TraceAnnotation("chipbench:step_dispatch"):
                params, opt_state, loss = step(params, opt_state, batch)
            with jax.profiler.TraceAnnotation("chipbench:loss_fetch"):
                losses.append(float(loss))
            step_s.append(time.monotonic() - a)
            if trace and k == trace_at + trace_n - 1:
                jax.profiler.stop_trace()
                tr_obs = {"trace": xplane.summarise(xplane.load(
                    xplane.find_xplane(out_dir))), "trace_steps": trace_n}
        window_s = time.monotonic() - t_open
        watch.arm(False)
        if trace and not tr_obs and len(step_s) > trace_at >= 0:
            jax.profiler.stop_trace()
    finally:
        feed.close()
    peak = harness.memory_peak_bytes()
    marks["window_open"] = setup_s
    say("set-up breakdown (s since process start): " + json.dumps(
        {k: round(v, 2) for k, v in marks.items()}))
    say(f"slowest step {max(step_s) * 1e3:.1f} ms; " + watch.line())
    if watch.compiles and not trace:
        raise RuntimeError(f"{len(watch.compiles)} compilation(s) inside the "
                           "measured window: the run measures nothing")
    tokens = len(step_s) * rows * seq
    half = len(step_s) // 2
    say(f"samples: steps {len(step_s)} of {rows} rows x {seq} tokens on "
        f"{n} chip(s) in {window_s:.3f} s; step p50 first half "
        f"{np.median(step_s[:half]) * 1e3:.2f} ms second half "
        f"{np.median(step_s[half:]) * 1e3:.2f} ms; head share of required "
        f"FLOPs {costs.head_share_of_train_flops(dims, seq):.3f}; "
        f"peak HBM {peak} bytes")
    obs = {"window_s": window_s, "train_tokens": tokens, "seq": seq,
           "chips": n, "dims": dims, "step_ms": [s * 1e3 for s in step_s],
           "peaks": peaks.peaks_for(device["kind"])
           if device["platform"] == "tpu" else None, **tr_obs}
    if tr_obs:
        obs["flash_need_flops"] = costs.flash_train_flops(
            dims, seq, rows // n) * tr_obs["trace_steps"]
    bad_steps = sum(1 for x in losses if not np.isfinite(x))
    del params, opt_state, step, loss, batch
    gc.collect()
    correct = _check(cell, seed, rows, prog, control, n_chk)
    hvd.shutdown()
    return {"obs": obs, "setup_s": setup_s, "correct": correct
            and bad_steps == 0, "attempted": len(step_s),
            "failed": bad_steps, "memory_peak_bytes": peak}


def _trace_dir(cell: dict) -> str:
    out_dir = os.path.join(harness.ROOT, ".chipbench_work", "trace",
                           cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    return out_dir


def reference_steps(cell: dict, seed: int, rows: int, n_steps: int,
                    mode: str = "f32") -> dict:
    """The reference's own first steps on the same rows: losses, the first
    gradient's leaf norms, the leaf norms of the parameters' change."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    dims, traffic = cell["dims"], cell["traffic_params"]
    mesh = Mesh(np.array(jax.devices()), ("ref",))
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("ref"))
    gen = importlib.import_module(
        f"chipbench.generators.{traffic['generator']}")
    batches = gen.batches(traffic, seed, rows, dims["vocab_size"])
    pdtype = jnp.dtype(dims["train"]["param_dtype"])
    params = weights.make_params(seed, dims, pdtype, repl)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
                    out_shardings=repl)
    mu, nu = zeros(params), zeros(params)
    step = reference.make_train_step(
        dims, dims["optimizer"], mesh, "ref", mode=mode,
        q_block=int(dims["check"].get("q_block", 1024)))
    out = {"loss": []}
    for i in range(n_steps):
        b = jax.device_put(next(batches), split)
        params, mu, nu, loss, gn = step(params, mu, nu, jnp.int32(i),
                                        b["tokens"], b["targets"])
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = _named(gn)
    out["delta"] = _delta_norms(params, seed, dims, pdtype)
    return out


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """Each number beside its limit; True if all hold."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    g_gap, g_leaf = worst_leaf_gap(prog["grad"], ref["grad"])
    d_gap, d_leaf = worst_leaf_gap(prog["delta"], ref["delta"])
    rows = [("loss gap (widest of the steps)", loss_gap,
             limits["loss_gap_limit"], ""),
            ("first-gradient norm gap (worst leaf)", g_gap,
             limits["grad_norm_gap_limit"], g_leaf),
            ("parameter-change norm gap (worst leaf)", d_gap,
             limits["delta_norm_gap_limit"], d_leaf)]
    return rows, all(np.isfinite(v) and v <= lim for _, v, lim, _ in rows)


def _check(cell, seed, rows, prog, control, n_steps) -> bool:
    t = time.monotonic()
    ref = reference_steps(cell, seed, rows, n_steps)
    limits = cell["dims"]["check"]
    table, ok = compare(prog, ref, limits)
    say(f"losses: program {prog['loss']} reference {ref['loss']}")
    for what, v, lim, leaf in table:
        say(f"correct: {what} {v:.6g} (limit {lim}) {leaf}")
    say(f"reference took {time.monotonic() - t:.1f} s")
    if control:
        low = reference_steps(cell, seed, rows, n_steps,
                              mode=limits["control_mode"])
        for what, v, lim, leaf in compare(low, ref, limits)[0]:
            say(f"CONTROL {limits['control_mode']}: {what} {v:.6g} "
                f"(limit {lim}) {leaf}")
    return ok
