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
