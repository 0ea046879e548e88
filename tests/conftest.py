"""Test fixture: 8 virtual CPU devices standing in for an 8-chip TPU slice.

The reference runs every test body under a 2-process mpirun/horovodrun
launcher (SURVEY.md §4).  Here the same multi-worker coverage comes from 8
virtual CPU devices — single process, real XLA collectives through the same
shard_map code paths that run on ICI.  Multi-process behavior is covered
separately by the launcher tests, which spawn real processes.

The platform and device count are pinned in code (jax.config.update, before
any backend is touched) so the suite runs on CPU whatever the environment
says.
"""

import os

# Child processes the tests spawn inherit the flag (their 8 virtual CPU
# devices); this process takes the config option below.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# One persistent compile cache for the session, placed by the program's own
# rule.  Most serving tests build a fresh engine over the same toy model, so
# the same executables are compiled again and again; with every compile
# cached (threshold 0) a rebuilt engine loads them instead.  Compile-COUNT
# guards are unaffected: they count traces, not XLA compilations.
from horovod_tpu.compile_cache import place_compile_cache  # noqa: E402

place_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd


@pytest.fixture()
def hvd(_hvd):
    return _hvd


def http_post_json(url, payload, timeout=60.0):
    """POST JSON to the serving server; returns (status, parsed body),
    unwrapping HTTPError so typed rejections (429/413/503/504) read
    like normal responses.  Shared by the serving and chaos suites so
    the response-protocol handling cannot silently diverge."""
    import json
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def assert_compile_set(engine, *, decode=None, prefill=None, sample=None):
    """The compile-count guard: assert an engine has built EXACTLY the
    expected executables — no more, no fewer.  Shared by the paged /
    sched / tp suites so every zero-recompile assertion reads the same
    counters the /stats endpoint exposes (``decode_compilations`` etc.),
    and so the fused paged-kernel path proves it adds NEW executables
    (prefill + decode [+ verify]) rather than per-tick retraces: run
    traffic, snapshot, run more traffic, call again with the same
    expectations.  ``None`` skips a counter."""
    stats = engine.stats()
    got = {
        "decode": stats["decode_compilations"],
        "prefill": stats["prefill_compilations"],
        "sample": stats["sample_compilations"],
    }
    want = {"decode": decode, "prefill": prefill, "sample": sample}
    bad = {k: (got[k], want[k]) for k in got
           if want[k] is not None and got[k] != want[k]}
    assert not bad, (
        "compile-set mismatch (counter: got != expected): "
        + ", ".join(f"{k}: {g} != {w}" for k, (g, w) in bad.items()))
    return got


def parse_prometheus_text(text):
    """STRICT parser/validator for Prometheus text exposition (0.0.4);
    the golden check behind the /metrics tests (shared by test_obs.py
    and test_chaos.py so the format contract cannot silently diverge).

    Asserts the structural rules a real scraper relies on — every
    sample line parses, a sample's family has a preceding # TYPE,
    sample names match their family (histograms: _bucket/_sum/_count),
    histogram bucket counts are cumulative and the +Inf bucket equals
    _count — and returns {family: {"type": ..., "help": ...,
    "samples": [(name, labels_dict, value)]}}.
    """
    import re

    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # metric name
        r"(?:\{([^}]*)\})?"                      # optional labels
        r" (-?(?:[0-9.]+(?:[eE][+-]?[0-9]+)?|Inf)|NaN|\+Inf)$")
    label_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    families = {}
    current = None
    for line in text.splitlines():
        assert line.strip() == line and line, f"bad line framing: {line!r}"
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            fam = rest.split(" ", 1)[0]
            assert name_re.match(fam), fam
            families.setdefault(fam, {"type": None, "help": None,
                                      "samples": []})
            families[fam]["help"] = rest.partition(" ")[2]
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, f"bad TYPE line: {line!r}"
            fam, kind = parts[2], parts[3]
            assert name_re.match(fam), fam
            assert kind in ("counter", "gauge", "histogram"), kind
            families.setdefault(fam, {"type": None, "help": None,
                                      "samples": []})
            families[fam]["type"] = kind
            current = fam
            continue
        m = sample_re.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, raw_labels, raw_value = m.groups()
        labels = dict(label_re.findall(raw_labels)) if raw_labels else {}
        value = float(raw_value.replace("+Inf", "inf"))
        # the sample must belong to the most recent TYPE'd family
        assert current is not None, f"sample before any TYPE: {line!r}"
        kind = families[current]["type"]
        if kind == "histogram":
            assert name in (current + "_bucket", current + "_sum",
                            current + "_count"), (name, current)
            if name.endswith("_bucket"):
                assert "le" in labels, line
        else:
            assert name == current, (name, current)
        families[current]["samples"].append((name, labels, value))
    # histogram invariants: buckets cumulative, +Inf == _count
    for fam, f in families.items():
        if f["type"] != "histogram":
            continue
        series = {}
        count = {}
        for name, labels, value in f["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            if name.endswith("_bucket"):
                series.setdefault(key, []).append(
                    (float(labels["le"].replace("+Inf", "inf")), value))
            elif name.endswith("_count"):
                count[key] = value
        for key, buckets in series.items():
            buckets.sort()
            values = [v for _, v in buckets]
            assert values == sorted(values), (fam, key, "not cumulative")
            assert buckets[-1][0] == float("inf"), (fam, key)
            assert buckets[-1][1] == count.get(key), (fam, key)
    return families


# --- the paged KV pool's write discipline (serving/cache.py write_pages) ------
#
# Shared by tests/test_paged.py, test_window_layers.py, test_speculative.py
# and test_tp_serving.py: what a jaxpr must not do to a pool (structure), and
# a plain numpy writer of (page, offset) to hold a pool's bytes to (contents).


def _sub_jaxprs(eqn):
    """The jaxprs an equation carries (scan / while / cond / pjit /
    closed_call / shard_map bodies), a Pallas kernel's own body apart:
    it addresses the pool by reference, and is tested where it is."""
    if eqn.primitive.name == "pallas_call":
        return
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def pool_structure_faults(jaxpr, pool_shapes):
    """What a traced program does to a paged pool that the write
    discipline forbids, as a list of sentences (empty: sound).
    ``pool_shapes`` are the stacked pool arrays' shapes, ``(L, P, H_kv,
    page[, Dh])``.

    * no ``scan`` takes an array of a pool's shape among its ``xs`` or
      yields one among its ``ys`` (the pool is the loop's carry);
    * no ``dynamic_slice`` / ``gather`` / ``squeeze`` yields a whole
      layer of a pool (``(P, H_kv, page[, Dh])``, unit dims aside);
    * every ``scatter`` INTO an array of a pool's shape indexes leading
      dims only: the scattered dims are ``0..k-1``, the window every
      dim behind them."""
    pools = {tuple(s) for s in pool_shapes}
    layers = {tuple(d for d in s[1:] if d != 1) for s in pools}
    faults = []

    def walk(j):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name == "scan":
                nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
                for kind, vs in (("xs", eqn.invars[nc + nk:]),
                                 ("ys", eqn.outvars[nk:])):
                    faults.extend(
                        f"a scan has a pool {v.aval.shape} among its {kind}"
                        for v in vs if tuple(v.aval.shape) in pools)
            elif name in ("dynamic_slice", "gather", "squeeze"):
                shape = tuple(d for d in eqn.outvars[0].aval.shape if d != 1)
                if shape in layers:
                    faults.append(f"{name} yields a whole layer of a pool "
                                  f"{eqn.outvars[0].aval.shape}")
            elif name.startswith("scatter") and tuple(
                    eqn.invars[0].aval.shape) in pools:
                dn = eqn.params["dimension_numbers"]
                lead = tuple(range(len(dn.scatter_dims_to_operand_dims)))
                if (tuple(dn.scatter_dims_to_operand_dims) != lead
                        or tuple(dn.inserted_window_dims) != lead):
                    faults.append(
                        f"a scatter into a pool {eqn.invars[0].aval.shape} "
                        f"indexes dims {dn.scatter_dims_to_operand_dims} "
                        f"(inserted {dn.inserted_window_dims})")
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return faults


class KVSpy:
    """Records the K/V a decode or verify body computes, layer by layer,
    without touching what it does with them: ``transformer._qkv_proj``
    is wrapped (``monkeypatch``) to ship its results to the host
    (``jax.debug.callback``) before returning them.  ``take()`` hands
    back and forgets the calls so far, in layer order, each ``(kind,
    positions, k, v)`` with ``k``/``v`` ``(S, H_kv, W, Dh)`` float
    arrays — the values the program then stores, bit for bit.

    The calls arrive in program order; a program over several devices
    cannot promise that (``ordered=False``), so there the layers are
    told apart by their first ``ln1`` weight, which the test makes rise
    with the layer."""

    def __init__(self, monkeypatch, ordered=True):
        from horovod_tpu.models import transformer as T

        self.calls = []
        self.ordered = ordered
        real = T._qkv_proj

        def spied(x, p, cfg, pos_offset=0, positions=None, kind="full"):
            q, k, v = real(x, p, cfg, pos_offset=pos_offset,
                           positions=positions, kind=kind)
            jax.debug.callback(
                lambda tag, pos, k_, v_: self.calls.append(
                    (float(tag), kind, np.asarray(pos), np.asarray(k_),
                     np.asarray(v_))),
                p["ln1"][0], positions, k, v, ordered=ordered)
            return q, k, v

        monkeypatch.setattr(T, "_qkv_proj", spied)

    def take(self):
        jax.effects_barrier()
        calls, self.calls = self.calls, []
        if not self.ordered:
            calls.sort(key=lambda c: c[0])
        return [c[1:] for c in calls]


class PoolMirror:
    """A plain numpy writer of ``(page, offset)``: the bytes a paged
    pool must hold after a sequence of landings and ticks, computed
    position by position from the host's page tables.  ``names`` maps a
    layer kind to the ``(k, v)`` array names of its pool (``("k",
    "v")``, a window kind's ``("wk", "wv")``); int8 pools' scales ride
    beside (``k_scale``, ``v_scale``), from
    ``transformer.kv_quantize``."""

    def __init__(self, pool, page_size):
        self.a = {n: np.array(a) for n, a in pool.items() if n != "pos"}
        self.ps = page_size

    def _stored(self, name, x):
        """``x`` ``(..., Dh)`` float as the pool stores it: ``[(array
        name, values)]`` — the payload cast, or int8 with its scales
        (quantized under ``jit``, as the program's are)."""
        from horovod_tpu.models import transformer as T

        scale = f"{name[-1]}_scale"
        if scale in self.a:
            q, s = jax.jit(T.kv_quantize)(jnp.asarray(x))
            return [(name, np.asarray(q)), (scale, np.asarray(s))]
        return [(name, np.asarray(jnp.asarray(x).astype(
            self.a[name].dtype)))]

    def land(self, names, rows, start, true_lens, k, v):
        """A landed block ``(L, K, H_kv, Tb, Dh)``: column ``t`` of row
        ``i`` is logical position ``start + t`` of table row
        ``rows[i]``, written iff ``t < true_lens[i]``."""
        for name, x in zip(names, (k, v)):
            for arr, val in self._stored(name, x):
                for i, row in enumerate(rows):
                    for t in range(int(true_lens[i])):
                        page, off = divmod(start + t, self.ps)
                        # a page released behind a window: nowhere
                        if page < len(row) and row[page] != 0:
                            self.a[arr][:, row[page], :, off] = val[:, i, :, t]

    def write(self, names, layer, table, pos, ok, k, v):
        """One layer's K/V ``(S, H_kv, W, Dh)`` of a tick (``W = 1``)
        or a verify: offset ``j`` of slot ``s`` is logical position
        ``pos[s] + j``, written iff ``ok[s, j]``."""
        for name, x in zip(names, (k, v)):
            for arr, val in self._stored(name, x):
                for s, j in zip(*np.nonzero(ok)):
                    page, off = divmod(int(pos[s]) + j, self.ps)
                    if page < table.shape[1]:
                        self.a[arr][layer, table[s, page], :, off] = (
                            val[s, :, j])

    def assert_holds(self, pool):
        """Every array byte for byte, the NULL page (page 0) excepted."""
        for n, want in self.a.items():
            got = np.asarray(pool[n])
            bad = np.argwhere(got[:, 1:] != want[:, 1:])
            assert bad.size == 0, (
                f"pool[{n!r}] differs from the (page, offset) writer at "
                f"{len(bad)} places, first (layer, page-1, ...) = "
                f"{bad[0].tolist()}")


# --- tensor fusion before PR 43 (ops/fusion.py) ------------------------------
#
# Shared by tests/test_optim.py (the numbers and the traced program) and
# tests/test_tpu_aot.py (what the TPU compiler makes of it).


def every_bucket_packed(monkeypatch):
    """``ops/fusion.py`` as it was before PR 43: a leaf ALONE in its
    bucket is raveled and reshaped back like a packed one's leaves."""
    from horovod_tpu.ops import fusion

    def flatten(leaves):
        flats = [jnp.ravel(jnp.asarray(l)) for l in leaves]
        return jnp.concatenate(flats) if len(flats) > 1 else flats[0]

    def split(buf, leaves):
        out, off = [], 0
        for l in leaves:
            a = jnp.asarray(l)
            n = int(np.prod(a.shape)) if a.ndim else 1
            out.append(jnp.reshape(buf[off:off + n], a.shape))
            off += n
        return out

    monkeypatch.setattr(fusion, "_flatten_bucket", flatten)
    monkeypatch.setattr(fusion, "_split_bucket", split)
