"""The readers of PR 37: a decoder's attention time by part and by pass
(``part_ms.py``), the share of it under any part (``attn_parts_share.py``),
the three stages round the flat gradient (``flat_ms``, through the
``stage_ms.py`` that was there) and the device time no scope can reach
(``untagged_ms.py``): on recorded paths, on a made-up trace whose parts and
passes have known milliseconds, on a program without the scopes, and the
rule that they came as new files and appended entries alone."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from perfbench import harness  # noqa: E402
from perfbench.metrics import (attn_parts_share, layer_ms, part_ms,  # noqa: E402
                               scoped, untagged_ms)

MS = 1_000_000
PARENT = "066ca7294de6c8a48ad524642bc6242d3f7d8015"
DECODERS = ["qwen3_next_ep64.gtopk", "keye_vl2_ep16.gtopk",
            "trinity_mini_ep16.gtopk"]
# name -> (unit, better, layer, the cells it lists or None for every cell)
NEW = {
    "attn_kernel_ms": ("ms", "lower", "decoder layer kinds", DECODERS),
    "attn_proj_ms": ("ms", "lower", "decoder layer kinds", DECODERS),
    "attn_layout_ms": ("ms", "lower", "decoder layer kinds", DECODERS),
    "attn_pointwise_ms": ("ms", "lower", "decoder layer kinds", DECODERS),
    "attn_parts_share": ("%", "higher", "decoder layer kinds", DECODERS),
    "replay_ms": ("ms", "lower", "decoder layer kinds", DECODERS),
    "flat_ms": ("ms", "lower", "accumulate + select + mask", None),
    "untagged_ms": ("ms", "lower", "device", None),
}
KINDS = ["attn", "attn_window", "attn_full"]
STEP = "jit(gtopk_train_step)/gtopk/fwd_bwd/while/body/closed_call/"

# Recorded forms of a tf_op path (the AFMoE and the sparse-attention
# decoders' published steps compiled for a v5e, PR 37), by pass, with and
# without a part.
PATHS = {
    # forward
    "flash_attention_forward.14":
        STEP + "jvp(TrinityMini)/layer_4/layer/attn_full/mixer/part/kernel/"
        "flash_attention_forward/pallas_call",
    "fusion.11":
        STEP + "jvp(TrinityMini)/layer_1/layer/attn_window/mixer/part/proj/"
        "dot_general",
    "fusion.12":
        STEP + "jvp(TrinityMini)/layer_1/layer/attn_window/part/pointwise/"
        "mul",
    "copy.13":
        STEP + "jvp(TrinityMini)/layer_1/layer/attn_window/mixer/part/"
        "layout/transpose",
    # The indexer's loops stand outside every part: a body's operation has
    # the indexer's kind, and what the compiler leaves without an op_name in
    # there is filed under the loop's own path (fusion.15, backward).
    "fusion.14":
        STEP + "jvp(KeyeVL2)/layer_0/layer/attn/mixer/jit(kernel_attention)/"
        "closed_call/while/body/closed_call/layer/dsa_index/"
        "bqjd,bkd->bjqk/dot_general",
    "fusion.15":
        STEP + "transpose(jvp(KeyeVL2))/jvp(KeyeVL2)/checkpoint/layer_0/"
        "layer/attn/mixer/jit(kernel_attention)/while",
    # replay: the layer's second forward, inside the backward pass
    "fusion.21":
        STEP + "transpose(jvp(TrinityMini))/jvp(TrinityMini)/checkpoint/"
        "rematted_computation/layer_1/layer/attn_window/mixer/part/proj/"
        "dot_general",
    "fusion.22":
        STEP + "transpose(jvp(TrinityMini))/jvp(TrinityMini)/checkpoint/"
        "rematted_computation/layer_1/layer/moe_router/moe/layer/"
        "moe_router/top_k",
    # backward
    "flash_attention_backward_kv.10":
        STEP + "transpose(jvp(TrinityMini))/jvp(TrinityMini)/checkpoint/"
        "layer_4/layer/attn_full/mixer/part/kernel/"
        "flash_attention_backward_kv/pallas_call",
    "fusion.31":
        STEP + "transpose(jvp(TrinityMini))/jvp(TrinityMini)/checkpoint/"
        "layer_1/layer/attn_window/mixer/part/layout/reduce_sum",
    "fusion.32":
        STEP + "transpose(jvp(TrinityMini))/jvp(TrinityMini)/checkpoint/"
        "layer_1/layer/attn_window/mixer/mul",
    "fusion.33":
        STEP + "transpose(jvp(TrinityMini))/layer/head/dot_general",
    # outside the model
    "fusion.41": "jit(gtopk_train_step)/gtopk/flatten/concatenate",
    "fusion.42": "jit(gtopk_train_step)/gtopk/clip/mul",
    "fusion.43": "jit(gtopk_train_step)/gtopk/unflatten/split",
    "fusion.44": "jit(gtopk_train_step)/gtopk/apply/add",
    "ragged-dot-none.5": "ragged-dot-none",
}
READ = {
    "flash_attention_forward.14": ("attn_full", "kernel", "forward"),
    "fusion.11": ("attn_window", "proj", "forward"),
    "fusion.12": ("attn_window", "pointwise", "forward"),
    "copy.13": ("attn_window", "layout", "forward"),
    "fusion.14": ("dsa_index", "", "forward"),
    "fusion.15": ("attn", "", "backward"),
    "fusion.21": ("attn_window", "proj", "replay"),
    "fusion.22": ("moe_router", "", "replay"),
    "flash_attention_backward_kv.10": ("attn_full", "kernel", "backward"),
    "fusion.31": ("attn_window", "layout", "backward"),
    "fusion.32": ("attn_window", "", "backward"),
    "fusion.33": ("head", "", "backward"),
    "fusion.41": ("", "", "forward"), "fusion.42": ("", "", "forward"),
    "fusion.43": ("", "", "forward"), "fusion.44": ("", "", "forward"),
    "ragged-dot-none.5": ("", "", "forward"),
}
STAGES = {
    "fusion.41": "gtopk/flatten", "fusion.42": "gtopk/clip",
    "fusion.43": "gtopk/unflatten", "fusion.44": "gtopk/apply",
    "ragged-dot-none.5": ""}


def read(name, ctx):
    return harness.read_metric({"name": name}, ctx)


def test_paths_are_read_by_kind_part_and_pass():
    assert {op: (layer_ms.kind_of(p), part_ms.part_of(p), part_ms.pass_of(p))
            for op, p in PATHS.items()} == READ
    # The older readers read a path that holds a part as they did without.
    for op, path in PATHS.items():
        bare = path.replace("part/kernel/", "").replace(
            "part/proj/", "").replace("part/pointwise/", "").replace(
                "part/layout/", "")
        assert "part/" not in bare
        assert layer_ms.kind_of(path) == layer_ms.kind_of(bare), op
        assert scoped.scope_of(path) == scoped.scope_of(bare) \
            == STAGES.get(op, "gtopk/fwd_bwd"), op
    assert part_ms.part_of("jit(f)/apart/kernel/dot") == ""
    assert part_ms.part_of("part/proj/x/part/layout/transpose") == "layout"


# Milliseconds a step of each operation of the made-up step.
SPANS = {
    "flash_attention_forward.14": 3.0, "fusion.11": 2.0, "fusion.12": 1.5,
    "copy.13": 1.0, "fusion.14": 0.5, "fusion.21": 2.0, "fusion.22": 0.75,
    "flash_attention_backward_kv.10": 4.0, "fusion.31": 0.5,
    "fusion.32": 0.25, "fusion.33": 1.0, "fusion.41": 0.5, "fusion.42": 0.25,
    "fusion.43": 0.125, "fusion.44": 0.5, "ragged-dot-none.5": 0.375,
    # No tf_op at all: the compiler's own.
    "copy-done.7": 0.5, "dynamic-update-slice.9": 0.75,
}


def made_up(parts=True):
    """One chip, two steps, the operations of ``SPANS`` back to back; the
    last three of the model's run inside a ``while`` that has no ``tf_op``
    and spans them with 0.125 ms of its own before and after."""
    devices, modules = [], []
    for k in range(2):
        t = start = k * 40 * MS
        for op, ms in SPANS.items():
            if op == "fusion.31":
                loop = t
                t += MS // 8
            devices.append([op, t, int(ms * MS)])
            t += int(ms * MS)
            if op == "fusion.33":
                t += MS // 8
                devices.append(["while.3", loop, t - loop])
        modules.append(["jit_gtopk_train_step(9)", start, t - start])
    events = {"devices": {0: sorted(devices, key=lambda e: e[1])},
              "modules": {0: modules}, "async": {}, "spans": []}
    kept = READ if parts else {op: (kind, "", pass_)
                               for op, (kind, _, pass_) in READ.items()}
    stages = {op: scoped.scope_of(path) for op, path in PATHS.items()}
    return {"events": events, "steps": 2, "chips": 1, "peaks": None,
            "parts": dict(kept),
            "layer_kinds": {op: kind for op, (kind, _, _) in READ.items()},
            "scoped": {"scopes": stages, "start_ns": 0, "stop_ns": 80 * MS,
                       "spans": []}}


def test_every_new_metric_reads_its_milliseconds_from_the_made_up_trace():
    ctx = made_up()
    assert read("attn_kernel_ms", ctx) == pytest.approx(3.0 + 4.0)
    assert read("attn_proj_ms", ctx) == pytest.approx(2.0 + 2.0)
    assert read("attn_layout_ms", ctx) == pytest.approx(1.0 + 0.5)
    assert read("attn_pointwise_ms", ctx) == pytest.approx(1.5)
    # fusion.14 is the indexer's, in a loop outside every part; fusion.32
    # has the kind and no part.
    kinds = layer_ms.read(ctx, KINDS)
    assert kinds == pytest.approx(7.0 + 4.0 + 1.5 + 1.5 + 0.25)
    share = read("attn_parts_share", ctx)
    assert share == pytest.approx(100 * 14.0 / 14.25)
    # The contract of the four: they add up to the share of the kinds' time.
    assert sum(read(f"attn_{part}_ms", ctx) for part in (
        "kernel", "proj", "layout", "pointwise")) == pytest.approx(
            share / 100 * kinds)
    assert read("replay_ms", ctx) == pytest.approx(2.0 + 0.75)
    assert read("flat_ms", ctx) == pytest.approx(0.5 + 0.25 + 0.125)
    # The two untagged operations and the loop's own quarter millisecond,
    # not the loop's whole span.
    assert read("untagged_ms", ctx) == pytest.approx(0.5 + 0.75 + 0.25)
    # With what carries a tf_op outside every stage it is what scoped_share
    # leaves of the step.
    busy = sum(SPANS.values()) + 0.25
    assert read("scoped_share", ctx) == pytest.approx(
        100 * (1 - (1.5 + 0.375) / busy))


def test_reader_filters_by_kind_part_and_pass():
    ctx = made_up()
    assert part_ms.read(ctx, ["attn_full"], ["kernel"], ["forward"]) \
        == pytest.approx(3.0)
    assert part_ms.read(ctx, ["attn_window"], None, ["backward"]) \
        == pytest.approx(0.5 + 0.25)
    assert part_ms.read(ctx, KINDS, passes=["replay"]) == pytest.approx(2.0)
    assert part_ms.read(ctx, None, ["layout"]) == pytest.approx(1.0 + 0.5)
    assert part_ms.read(ctx, ["dsa_index"], [""]) == pytest.approx(0.5)
    assert part_ms.read(ctx, ["head"]) == pytest.approx(1.0)
    assert part_ms.read(ctx, KINDS, ["kernel"], ["replay"]) == 0.0
    by_pass = [part_ms.read(ctx, passes=[p]) for p in part_ms.PASSES]
    assert sum(by_pass) == pytest.approx(sum(
        ms for op, ms in SPANS.items() if op in READ))


def test_a_program_without_the_scopes_leaves_the_readers_nothing():
    """The parent of PR 37 has kinds and stages and no part: the readers
    that ask for parts return None, the others read it like any other."""
    ctx = made_up(parts=False)
    for name in ("attn_kernel_ms", "attn_proj_ms", "attn_layout_ms",
                 "attn_pointwise_ms", "attn_parts_share"):
        assert read(name, ctx) is None, name
    assert read("replay_ms", ctx) == pytest.approx(2.75)
    assert read("untagged_ms", ctx) == pytest.approx(1.5)
    # Stages it has, the three new ones not: 0.0, as for any absent stage.
    ctx["scoped"]["scopes"] = {
        op: "" if scope in ("gtopk/flatten", "gtopk/clip", "gtopk/unflatten")
        else scope for op, scope in ctx["scoped"]["scopes"].items()}
    assert read("flat_ms", ctx) == 0.0
    # A program with no kinds (a convnet), and one with no scope at all.
    bare = dict(ctx, parts={op: ("", "", pass_) for op, (_, _, pass_)
                            in READ.items()})
    assert read("replay_ms", bare) is None
    assert attn_parts_share.read(bare, KINDS) is None
    nothing = dict(ctx, parts={}, scoped={
        "scopes": {}, "start_ns": 0, "stop_ns": 80 * MS, "spans": []})
    assert read("replay_ms", nothing) is None
    assert read("untagged_ms", nothing) is None
    assert read("flat_ms", nothing) is None
    assert untagged_ms.read(dict(ctx, scoped=None)) is None


def test_no_operation_of_the_kinds_is_no_share():
    ctx = made_up()
    assert attn_parts_share.read(ctx, ["gdn_scan"]) is None
    assert part_ms.read(ctx, ["gdn_scan"], ["kernel"]) == 0.0


# ------------------------------------------------------ the extension rule
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", NEW)
def test_entry_and_file_of_a_new_metric(name):
    unit, better, layer, cells = NEW[name]
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    want = {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": "throughput"}
    if cells:
        want["workloads"] = cells
    assert entry == want
    spec = harness._read(os.path.join(REPO, "perfbench", "metrics",
                                      name + ".json"))
    assert set(spec) == {"name", "layer", "unit", "moves", "cells", "source",
                         "reader", "what"}
    for cell in DECODERS:
        listed = name in {m["name"] for m in harness.load_cell(cell).per_layer}
        assert listed
    for cell in ("resnet50.gtopk", "lstm_ptb.gtopk", "resnet50.dense",
                 "resnet50.gtopk_dp4"):
        listed = name in {m["name"] for m in harness.load_cell(cell).per_layer}
        assert listed == (cells is None), (name, cell)


def test_the_eight_metrics_were_added_by_new_files_and_entries_alone():
    """Against the parent commit: no file under perfbench/ or
    tests/perfbench/ that was there differs, and BENCHMARK.json gained the
    eight per_layer entries at the end and nothing else."""
    def git(*args):
        return subprocess.run(["git", *args], cwd=REPO, capture_output=True,
                              text=True, timeout=60)

    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("the parent commit is not in this checkout's history")
    changed = git("diff", "--name-status", PARENT, "--", "perfbench",
                  "tests/perfbench").stdout.split("\n")
    assert [line for line in changed if line and not line.startswith("A")] == []
    before = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    after = bench()
    n = len(before["per_layer"])
    assert [m["name"] for m in after["per_layer"][n:n + 8]] == list(NEW)
    # What the parent had is still there, in place (later PRs append too).
    for key, value in before.items():
        if isinstance(value, list) and key not in ("command", "paths"):
            assert after[key][:len(value)] == value, key
        else:
            assert after[key] == value, key


# The whole of ``test_perfbench_entries_by_name.py``'s letter test for the two
# decoders that list their cuts, assertion for assertion, but for its last
# clause ("the cell in no other metric's list"), which held until a metric
# was shared between cells: the six of this PR list the three decoder cells,
# so here that clause names them (``tests/conftest.py`` marks that test's two
# cases for that one assertion; a ``benchmark`` PR gives it this form).
@pytest.mark.parametrize("config", ["keye_vl2_30b_a3b_ep16",
                                    "trinity_mini_26b_a3b_ep16"])
def test_entries_keep_the_contracts_letter_beside_the_shared_metrics(config):
    from test_perfbench_entries_by_name import (DECODERS as theirs, NAME,
                                                SPARSE_LIMITS, WIDTH)

    want, b = theirs[config], bench()
    (entry,) = [c for c in b["configs"] if c["name"] == config]
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
    (cell,) = [w for w in b["workloads"] if w["config"] == config]
    assert cell["name"] == want["cell"] and cell["traffic"] == want["traffic"]
    assert cell["chips"] == 1 and 1 <= len(cell["why"]) <= 200

    # Its metrics, in the order they were appended, listed for its cell
    # alone; and the cell in no other metric's list but the shared six's.
    mine = [m for m in b["per_layer"] if m["name"] in want["metrics"]]
    assert {m["name"]: (m["unit"], m["better"]) for m in mine} \
        == want["metrics"]
    assert [m["name"] for m in mine] == list(want["metrics"])
    assert all(m["workloads"] == [want["cell"]] and m["moves"] == "throughput"
               and m["layer"] == "decoder layer kinds" for m in mine)
    shared = [m["name"] for m in b["per_layer"]
              if want["cell"] in m.get("workloads", [])
              and m["name"] not in want["metrics"]]
    assert shared == [name for name, spec in NEW.items() if spec[3]]

    limits = harness.load_cell(want["cell"]).traffic["limits"]
    assert set(limits) == SPARSE_LIMITS
    assert all("why" in v and "PLACEHOLDER" not in v["why"]
               for v in limits.values())
