"""The trace reduction on hand-made intervals and on a recorded trace."""

import os

import pytest

from chipbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_subtract():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xplane.total(xplane.union([(0, 1), (0.5, 2)])) == 2
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6)]) \
        == [(0, 1), (2, 4), (6, 10)]
    assert xplane.subtract([(0, 2), (5, 6)], [(1, 5.5)]) \
        == [(0, 1), (5.5, 6)]


def _planes():
    ops0 = [("fusion.1", 0.0, 1.0), ("all-reduce.3", 1.0, 1.5),
            ("custom-call.7", 2.0, 3.0)]
    # chip 1: the collective is half hidden behind a fusion
    ops1 = [("fusion.1", 0.0, 1.25), ("all-reduce.3", 1.0, 1.5),
            ("custom-call.7", 2.0, 3.0)]
    host = [("chipbench:loss_fetch", 1.4, 2.1), ("other", 0.0, 3.0)]
    return {"/device:TPU:0": {"XLA Ops": ops0, "Steps": [("s", 0, 3)]},
            "/device:TPU:1": {"XLA Ops": ops1},
            "/host:CPU": {"main": host}}


def test_summarise_by_hand():
    s = xplane.summarise(_planes())
    assert s["chips"] == 2 and s["window_s"] == 3.0
    assert s["busy_s"] == pytest.approx((2.5 + 2.5) / 2)
    assert s["exposed_collective_s"] == pytest.approx((0.5 + 0.25) / 2)
    assert xplane.op_seconds(s, r"custom-call") == pytest.approx(1.0)
    assert s["device_ops"][0][0] in ("fusion.1", "custom-call.7")
    # the one idle gap (1.5 -> 2.0) falls under the benchmark's own span
    assert s["idle_gaps"] == [["loss_fetch", pytest.approx(0.5)]]
    idle_pct = 100 * (1 - s["busy_s"] / s["window_s"])
    assert idle_pct == pytest.approx(100 / 6)


def test_a_gap_goes_under_the_engines_phase_before_the_benchmarks_span():
    """Both prefixes the readers take; where both kinds cover a gap, the
    program's phase names it; a sliver of a phase does not."""
    from chipbench.readers import trace_scope_per

    assert xplane.SPAN_PREFIXES == trace_scope_per.SPAN_PREFIXES
    ops = [("fusion.1", 0.0, 1.0), ("fusion.2", 2.0, 3.0),
           ("fusion.3", 4.0, 5.0), ("fusion.4", 6.0, 7.0)]
    host = [("chipbench:window", 0.0, 7.0),
            ("hvd:ingest_chunk", 0.9, 1.8), ("hvd:tick_dispatch", 1.8, 2.1),
            ("hvd:gc", 1.0, 1.1),                   # nested in the phase
            ("hvd:bookkeeping", 3.0, 3.1),          # a tenth of gap two
            ("other", 0.0, 7.0)]
    planes = {"/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": {"main": host}}
    assert {n for n, _, _ in xplane.host_spans(planes)} == {
        "chipbench:window", "hvd:ingest_chunk", "hvd:tick_dispatch",
        "hvd:gc", "hvd:bookkeeping"}
    gaps = dict(xplane.summarise(planes)["idle_gaps"])
    # 1 -> 2: phases cover it whole, ingest_chunk most of it; 3 -> 4: the
    # phases cover a tenth, the benchmark's span all of it; 5 -> 6 likewise
    assert gaps == {"ingest_chunk": pytest.approx(1.0),
                    "window": pytest.approx(2.0)}
    # no benchmark span at all: the phase that covers most, else nothing
    planes["/host:CPU"]["main"] = host[1:]
    gaps = dict(xplane.summarise(planes)["idle_gaps"])
    assert gaps == {"ingest_chunk": pytest.approx(1.0),
                    "bookkeeping": pytest.approx(1.0),
                    "no benchmark span": pytest.approx(1.0)}


def test_nested_operations_count_once():
    evs = [("while.1", 0.0, 4.0), ("fusion.2", 0.5, 1.5),
           ("all-reduce.9", 1.5, 2.5), ("fusion.2", 3.0, 4.0)]
    own = dict()
    for n, sec in xplane.self_seconds(evs):
        own[n] = own.get(n, 0.0) + sec
    assert own == {"while.1": 1.0, "fusion.2": 2.0, "all-reduce.9": 1.0}
    s = xplane.summarise({"/device:TPU:0": {"XLA Ops": evs}})
    assert s["busy_s"] == 4.0
    # the while does not hide the collective inside it
    assert s["exposed_collective_s"] == pytest.approx(1.0)


def test_no_device_operation_gives_nothing():
    assert xplane.summarise({"/host:CPU": {"main": [("x", 0, 1)]}}) is None


def test_recorded_trace():
    path = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    s = xplane.summarise(xplane.load(path))
    assert s is not None and s["chips"] >= 1
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["device_ops"] and len(s["device_ops"]) <= 10
