"""The readers of the program's own names on a trace: device time by
scope (``trace_scope_per``) and idle time by covering span
(``trace_gap_by_span``), on hand-made intervals and on two small recorded
v5e traces; and every per-layer metric file of ``BENCHMARK.json`` read
against a run that observed nothing."""

import importlib
import json
import os

import pytest

from conftest import ROOT

from chipbench import harness, xplane
from chipbench.readers import trace_gap_by_span as gaps
from chipbench.readers import trace_scope_per as scopes

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
SCOPED = os.path.join(HERE, "data", "scoped_v5e.xplane.pb")


def _rows(*ops):
    return [{"name": n, "tf_op": t, "source": "", "category": "",
             "parts": scopes.components(t), "seconds": s}
            for n, t, s in ops]


def test_components_of_an_op_name():
    assert scopes.components(
        "jit(_tick)/jit(main)/while/body/transpose(jvp(mlp))/dot_general:"
    ) == ["jit", "_tick", "jit", "main", "while", "body", "transpose",
          "jvp", "mlp", "dot_general"]
    assert scopes.components("") == [] and scopes.components(None) == []


def test_seconds_under_a_scope_by_hand():
    rows = _rows(
        ("fusion.1", "jit(_tick)/while/body/attn_qkv/dot_general", 1.0),
        ("fusion.2", "jit(_tick)/while/body/mlp/dot_general", 2.0),
        ("fusion.3", "jit(_prefill)/while/body/mlp/dot_general", 4.0),
        ("custom-call.1",
         "jit(_tick)/while/body/paged_attend/hvd_paged_attend/pallas_call",
         8.0),
        ("copy.5", "", 16.0))
    v = frozenset({"attn_qkv", "mlp", "paged_attend", "attn"})
    assert scopes.seconds_under(rows, "mlp", v) == 6.0
    assert scopes.seconds_under(rows, "mlp", v, within="_tick") == 2.0
    assert scopes.seconds_under(rows, ["attn_qkv", "mlp"], v, "_tick") == 3.0
    assert scopes.seconds_under(rows, "hvd_paged_attend", v) == 8.0
    assert scopes.seconds_under(rows, "attn", v) == 0.0  # whole components
    # one name each, the innermost: the scopes partition the time; the
    # kernel inside paged_attend is the kernel's, not the scope's
    assert [scopes.named(r, v) for r in rows] == [
        "attn_qkv", "mlp", "mlp", "hvd_paged_attend", None]
    assert scopes.seconds_under(rows, "paged_attend", v) == 0.0
    # a kernel's name counts without being in the vocabulary
    assert scopes.named(rows[3], frozenset()) == "hvd_paged_attend"


def test_read_by_hand(monkeypatch):
    tr = {"self": _rows(("a", "jit(_tick)/mlp/dot", 0.3),
                        ("b", "jit(_tick)/kv_write/scatter", 0.1),
                        ("c", "", 0.1)),
          "ops": {0: [(1, 0.0, 0.5)]}, "spans": []}
    monkeypatch.setattr(scopes, "parsed", lambda obs: tr)
    obs = {"trace": {}, "trace_ticks": 2}
    per_tick = {"per": "trace_ticks", "scale": 1000.0}
    assert scopes.read(obs, {"scope": "mlp", **per_tick}) \
        == pytest.approx(150.0)
    # the trace carries the program's names, so a scope with no time in
    # the window reads 0 and stays in the line
    assert scopes.read(obs, {"scope": "absent", **per_tick}) == 0.0
    assert scopes.read(obs, {"scope": "absent", "fallback": "kv_write",
                             **per_tick}) == pytest.approx(50.0)
    assert scopes.read({"trace": {}}, {"scope": "mlp", **per_tick}) is None
    monkeypatch.setattr(scopes, "known_scopes",
                        lambda: frozenset({"mlp", "kv_write"}))
    assert scopes.read(obs, {"scope": None}) == pytest.approx(20.0)
    # a trace in which nothing carries a name of the vocabulary (the
    # parent's) has nothing to read
    monkeypatch.setattr(scopes, "known_scopes", lambda: frozenset({"x"}))
    assert scopes.read(obs, {"scope": None}) is None
    assert scopes.read(obs, {"scope": "x", **per_tick}) is None


def test_the_vocabulary_is_the_benchmarks_and_matches_the_program():
    """What counts as unscoped is the benchmark's to say (a data file);
    this test is where a scope the program adds or drops shows."""
    from horovod_tpu.models import transformer as T

    known = scopes.known_scopes()
    assert known == frozenset(T.DEVICE_SCOPES)
    used = set()
    for path in sorted(os.listdir(os.path.join(harness.HERE,
                                               "layer_metrics"))):
        spec = harness.load_json("layer_metrics", path)
        if spec["reader"] != "trace_scope_per":
            continue
        for key in ("scope", "fallback"):
            v = spec["args"].get(key) or []
            used |= {v} if isinstance(v, str) else set(v)
    assert {u for u in used if not u.startswith(scopes.KERNEL_PREFIX)} \
        <= known


def test_stats_diff_less_by_hand():
    from chipbench.readers import stats_diff_less

    args = harness.load_json(
        "layer_metrics", "prefill_padding_pct.chat.json")["args"]
    s0 = {"prefill_tokens_total": 100, "prefill_padded_tokens_total": 128}
    s1 = {"prefill_tokens_total": 400, "prefill_padded_tokens_total": 528}
    # 400 padded tokens ran for 300 real ones
    assert stats_diff_less.read({"stats0": s0, "stats1": s1}, args) \
        == pytest.approx(25.0)
    assert stats_diff_less.read({"stats0": s1, "stats1": s1}, args) is None


def test_idle_by_span_by_hand():
    # chip 0 idles 1.0-2.0 and 3.0-3.5; chip 1 idles 1.5-2.0
    ops = {0: [(1, 0.0, 1.0), (2, 2.0, 3.0), (3, 3.5, 4.0)],
           1: [(1, 0.0, 1.5), (2, 2.0, 4.0)]}
    spans = [("hvd:tick_host", 0.9, 1.6), ("chipbench:loss_fetch", 0.0, 2.5),
             ("hvd:idle", 1.6, 2.1), ("hvd:far", 10.0, 11.0)]
    out = gaps.idle_by_span(ops, spans)
    # 1.0-2.0: tick_host covers 0.6, idle 0.4 -> tick_host (the program's
    # spans cover it, so they win over the benchmark's, which also does);
    # 1.5-2.0 on chip 1: idle covers 0.4, tick_host 0.1; 3.0-3.5: nothing
    assert out == {"hvd:tick_host": pytest.approx(0.5),
                   "hvd:idle": pytest.approx(0.25),
                   gaps.NO_SPAN: pytest.approx(0.25)}
    assert gaps.label(3.0, 3.5, spans) == gaps.NO_SPAN
    assert gaps.label(2.2, 2.4, spans) == "chipbench:loss_fetch"
    # a program span that covers under half of a gap does not label it
    assert gaps.label(2.0, 2.5, spans) == "chipbench:loss_fetch"


def test_gap_reader_by_hand(monkeypatch):
    tr = {"ops": {0: [(1, 0.0, 1.0), (2, 2.0, 3.0), (3, 3.5, 4.0)]},
          "spans": [("hvd:tick_host", 0.9, 2.0)], "self": []}
    monkeypatch.setattr(scopes, "parsed", lambda obs: tr)
    obs = {"trace": {}, "trace_ticks": 4}
    assert gaps.read(obs, {"span": None}) == pytest.approx(100 / 3)
    assert gaps.read(obs, {"span": "hvd:tick_host", "per": "trace_ticks",
                           "scale": 1000.0}) == pytest.approx(250.0)
    assert gaps.read(obs, {"span": "hvd:admit", "per": "trace_ticks"}) \
        is None
    tr2 = {"ops": tr["ops"], "spans": [], "self": []}
    monkeypatch.setattr(scopes, "parsed", lambda obs: tr2)
    assert gaps.read(obs, {"span": None}) is None   # no span written at all


def test_tiny_trace_metadata_and_times_agree_with_profile_data():
    tr = scopes.decode(TINY)
    assert tr["bytes"] == os.path.getsize(TINY) and list(tr["ops"]) == [0]
    by_name = {m["name"]: m for m in tr["meta"][0].values()}
    assert by_name["fusion.4"]["tf_op"] == "jit(call_wrapped)/dot_general:"
    assert by_name["fusion.4"]["source"].endswith("record_tiny_trace.py:20")
    assert by_name["fusion.4"]["hlo_category"] == "convolution fusion"
    # the same events, to the nanosecond, as JAX's own reader gives
    ref = xplane.device_ops(xplane.load(TINY))[0]
    mine = tr["ops"][0]
    assert len(mine) == len(ref) == 9
    for (_, s, e), (_, rs, re_) in zip(mine, ref):
        assert s == pytest.approx(rs, abs=2e-9)
        assert e == pytest.approx(re_, abs=2e-9)
    assert [n for n, _, _ in tr["spans"]] == ["chipbench:tiny"]
    rows = scopes.self_by_operation(tr)
    assert rows[0]["name"] == "fusion.4"
    assert sum(r["seconds"] for r in rows) == pytest.approx(
        xplane.summarise(xplane.load(TINY))["busy_s"], rel=2e-3)  # ns vs ps
    idle = gaps.idle_by_span(tr["ops"], tr["spans"])
    assert max(idle, key=idle.get) == "chipbench:tiny"


def test_scoped_trace_one_scope_one_kernel_one_span():
    if not os.path.exists(SCOPED):
        pytest.skip("no scoped trace in this checkout")
    tr = scopes.decode(SCOPED)
    rows = scopes.self_by_operation(tr)
    kernel = [r for r in rows if "hvd_paged_attend" in r["parts"]]
    assert kernel and all("paged_attend" in r["parts"] for r in kernel)
    assert all(r["source"].endswith("paged_attention.py:%s"
                                    % r["source"].rsplit(":", 1)[1])
               for r in kernel)
    v = frozenset({"mlp", "paged_attend"})
    mlp = scopes.seconds_under(rows, "mlp", v)
    attend = scopes.seconds_under(rows, "hvd_paged_attend", v)
    assert mlp > 0 and attend > 0
    total = sum(r["seconds"] for r in rows)
    assert (mlp + attend + scopes.seconds_under(rows, "paged_attend", v)) \
        == pytest.approx(total, rel=0.05)
    names = {n for n, _, _ in tr["spans"]}
    assert names == {"hvd:tick_dispatch", "hvd:tick_device_wait",
                     "chipbench:tiny"}
    idle = gaps.idle_by_span(tr["ops"], tr["spans"])
    # the host sleeps between repetitions under the benchmark's span only
    assert max(idle, key=idle.get) == "chipbench:tiny"
    assert gaps.NO_SPAN not in idle or idle[gaps.NO_SPAN] < 0.05 * sum(
        idle.values())


def test_find_trace_takes_only_a_file_this_process_wrote(tmp_path):
    import shutil

    d = tmp_path / "cell" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    f = d / "host.xplane.pb"
    shutil.copy(TINY, f)
    assert scopes.find_trace(str(tmp_path)) == str(f)
    os.utime(f, (1.0, 1.0))          # older than this process
    assert scopes.find_trace(str(tmp_path)) is None
    assert scopes.parsed({}) is None           # the run took no trace


def _metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m["name"] for m in bench["per_layer"]]


@pytest.mark.parametrize("name", _metrics()[1])
def test_every_per_layer_metric_has_its_file_and_reads_nothing_as_none(name):
    bench, _ = _metrics()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = harness.load_json("layer_metrics", name + ".json")
    assert spec["layer"] == entry["layer"]
    assert spec["source"] == entry["source"]
    if "moves" in spec:
        assert spec["moves"] == entry["moves"]
    reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    # a run that observed nothing leaves the metric out
    assert reader.read({}, spec.get("args", {})) is None
    # ... and so does, for a metric only the expert, latent or sparse
    # cells read (one the toy tree's list, which is the real entries of
    # its four Mistral cells, does not have), a program without the newer
    # counters: a /stats with the old keys alone
    old = {"decode_ticks": 1, "tick_host_seconds": {"sum": 0.5}}
    value = reader.read({"stats0": old, "stats1": {
        "decode_ticks": 9, "tick_host_seconds": {"sum": 0.9}},
        "trace": None}, spec.get("args", {}))
    with open(os.path.join(HERE, "toy", "BENCHMARK.json")) as f:
        accepted = {m["name"] for m in json.load(f)["per_layer"]}
    assert value is None or name in accepted
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert entry["moves"] in e2e and entry["moves"] != "setup_s"
    assert entry["workloads"] and len(set(entry["workloads"])) == len(
        entry["workloads"])
    for cell in entry["workloads"]:
        assert cell in cells
        assert cell in e2e[entry["moves"]].get("workloads", cells)


def test_toy_cell_runs_traced_with_the_real_per_layer_list(tmp_path):
    """The toy tree with the REAL ``per_layer`` list (the toy's own file
    is the benchmark's and lists what it listed): a traced run of the
    serving cell passes with every new entry present, engine phases
    written under a live profiler session."""
    import shutil
    import time

    from conftest import TOY

    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    with open(root / "BENCHMARK.json") as f:
        toy = json.load(f)
    toy["per_layer"] = _metrics()[0]["per_layer"]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(toy, f)
    lines = []
    rc = harness.run_cell("m7b-serve-longdoc", 7, 2.0, True,
                          t0=time.monotonic(), root=str(root),
                          need_chip=False, out=lines.append)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {}      # a CPU time is never a device metric
    # the trace this process just wrote holds the engine's own spans
    tr = scopes.decode(scopes.find_trace())
    assert tr["ops"] == {}            # no TPU plane on a CPU
    # (every step passes through these three, a busy one or an idle one)
    assert {"hvd:reclaim", "hvd:admit", "hvd:bookkeeping"} <= {
        n for n, _, _ in tr["spans"]}
