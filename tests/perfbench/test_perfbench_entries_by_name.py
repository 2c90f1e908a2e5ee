"""What ``test_perfbench_contract`` checks of a configuration's entry, for
the configurations that list their cuts under ``reduced`` (that test also
asserts ``reduced == []``, so ``tests/conftest.py`` marks their cases), with
each configuration's cell, metrics and limits: every entry looked up by its
name, none by its place in a list and no list by its length, so the next PR
that appends to ``BENCHMARK.json`` leaves these as they are."""

import json
import os
import re

import pytest

from perfbench import harness

SPARSE_LIMITS = {
    "window_compiles", "nonfinite_losses", "loss_gap_1_3", "support_recall_1",
    "support_recall_2", "value_gap_1", "value_gap_2", "dparam_gap_3",
    "loss_ratio"}
# configuration -> its cuts, its cell, the cell's traffic and the per-layer
# metrics that came with it as name -> (unit, better).
DECODERS = {
    "keye_vl2_30b_a3b_ep16": dict(
        reduced=["num_hidden_layers", "experts_held", "vocab_rows"],
        cell="keye_vl2_ep16.gtopk", traffic="gtopk_r001_s16384_b1",
        metrics={"dsa_index_ms": ("ms", "lower"),
                 "dsa_select_ms": ("ms", "lower"),
                 "dsa_attn_ms": ("ms", "lower"),
                 "dsa_index_roofline": ("%", "higher"),
                 "dsa_attn_roofline": ("%", "higher"),
                 "dsa_kept_ratio": ("ratio", "lower")}),
    "trinity_mini_26b_a3b_ep16": dict(
        reduced=["num_hidden_layers", "num_dense_layers", "experts_held",
                 "vocab_rows"],
        cell="trinity_mini_ep16.gtopk", traffic="gtopk_r001_s16384_b1_afmoe",
        metrics={"swa_attn_ms": ("ms", "lower"),
                 "full_attn_ms": ("ms", "lower"),
                 "swa_attn_roofline": ("%", "higher"),
                 "full_attn_roofline": ("%", "higher"),
                 "dense_mlp_ms": ("ms", "lower"),
                 "moe_route_imbalance": ("ratio", "lower")}),
}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTH = re.compile(r"(_dim|_rank|_size|intermediate|head_dim|per_tok)")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("config", DECODERS)
def test_entries_keep_the_contracts_letter(bench, config):
    want = DECODERS[config]
    (entry,) = [c for c in bench["configs"] if c["name"] == config]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert all(NAME.match(k) for k in entry["reduced"])
    assert entry["reduced"] == want["reduced"] and len(entry["reduced"]) <= 16
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    for key in ("why", "source"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
            and "\t" not in entry[key]
    assert entry["file"] == f"perfbench/configs/{config}.json"
    with open(os.path.join(harness.ROOT, entry["file"])) as fh:
        held = json.load(fh)
    assert held["name"] == entry["name"] and held["source"] == entry["source"]
    assert held["reduced"] == entry["reduced"]

    # The configuration's one cell, and that no other runs it.
    (cell,) = [w for w in bench["workloads"] if w["config"] == config]
    assert cell["name"] == want["cell"] and cell["traffic"] == want["traffic"]
    assert cell["chips"] == 1 and 1 <= len(cell["why"]) <= 200

    # Its metrics, in the order they were appended, listed for its cell
    # alone; and the cell in no other metric's list.
    mine = [m for m in bench["per_layer"] if m["name"] in want["metrics"]]
    assert {m["name"]: (m["unit"], m["better"]) for m in mine} \
        == want["metrics"]
    assert [m["name"] for m in mine] == list(want["metrics"])
    assert all(m["workloads"] == [want["cell"]] and m["moves"] == "throughput"
               and m["layer"] == "decoder layer kinds" for m in mine)
    assert not any(want["cell"] in m.get("workloads", [])
                   for m in bench["per_layer"]
                   if m["name"] not in want["metrics"])

    limits = harness.load_cell(want["cell"]).traffic["limits"]
    assert set(limits) == SPARSE_LIMITS
    assert all("why" in v and "PLACEHOLDER" not in v["why"]
               for v in limits.values())
