"""The per-layer table of the real ``BENCHMARK.json``: one entry for one
measurement.  Two entries are the same measurement when their files name
the same reader with the same arguments and they move the same end-to-end
metric; such a pair is ONE entry whose ``workloads`` lists both cells.  A
new cell joins the lists of what it shares with an older cell and brings
entries only for what is new in it.  (That each entry is whole — its file,
its reader, its cells, the end-to-end metric it moves — is
``test_scope_readers.py``'s, an entry a case.)"""

import json
import os

import pytest

from chipbench import harness

from conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
LIMIT = 128       # the contract's: 1 to 128 per-layer metrics


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _key(args):
    return json.dumps(args, sort_keys=True)


def _measurement(entry):
    spec = harness.load_json("layer_metrics", entry["name"] + ".json")
    return spec["reader"], _key(spec.get("args", {})), entry["moves"]


def _parent():
    with open(os.path.join(HERE, "data", "pr37_resolved_pairs.json")) as f:
        return json.load(f)


def test_the_table_is_within_the_limit():
    assert 1 <= len(_bench()["per_layer"]) <= LIMIT


def test_no_two_entries_are_one_measurement():
    seen = {}
    for entry in _bench()["per_layer"]:
        twin = seen.setdefault(_measurement(entry), entry["name"])
        assert twin == entry["name"], (
            f"{entry['name']} and {twin} read the same thing for the same "
            f"end-to-end metric: make them one entry over both lists")


def test_every_metric_file_has_its_entry():
    """No file of a replaced entry is left behind."""
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(harness.HERE, "layer_metrics")) if f.endswith(".json")}
    assert files == {m["name"] for m in _bench()["per_layer"]}


@pytest.mark.parametrize("cell", sorted(_parent()["cells"]))
def test_a_cell_still_resolves_what_it_resolved_at_the_parent(cell):
    """Every (reader, args) pair the harness resolved for the cell before
    the twins were merged it resolves now, under whatever name — but for
    what the record lists as amended, where the pair's new arguments must
    be resolved instead."""
    record = _parent()
    amended = {_key(a["from"]): a["to"] for a in record["amended"]}
    now = {(s["reader"], _key(s.get("args", {})))
           for s in harness.metric_specs(
               harness.load_cell(cell), "per_layer").values()}
    for name, reader, args in record["cells"][cell]:
        args = amended.get(_key(args), args)
        assert (reader, _key(args)) in now, f"{cell} lost {name}"


def test_the_sparse_cell_reads_what_the_full_table_denied_it():
    have = set(harness.metric_specs(
        harness.load_cell("dsv32-serve-deepctx"), "per_layer"))
    assert {"ttft_p90_ms.tput", "tick_span_coverage_pct.tput",
            "sample_sortfree_ticks_pct.tput", "mla_expand_ms_per_tick",
            "moe_rows_here_pct", "kv_latent_bytes_per_token"} <= have
