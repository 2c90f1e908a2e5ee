"""qwen3_next_ep64.gtopk on the CPU at the model's ``tiny`` preset: a whole
run, a traced run, the control, a run whose timed path is broken
underneath, the reader of the layer kinds, and the configuration's files
against the program's published preset and the contract's letter."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from perfbench import compare, harness, reference, traffic  # noqa: E402
from perfbench.metrics import layer_ms  # noqa: E402

CELL = "qwen3_next_ep64.gtopk"
CONFIG = "qwen3_next_80b_a3b_ep64"
NEW = ["gdn_proj_ms", "gdn_scan_ms", "attn_ms", "moe_route_ms", "moe_expert_ms",
       "head_ms", "gdn_scan_roofline", "moe_load_imbalance"]
MS = 1_000_000


def tiny_cell():
    """``perfbench_tiny.tiny_cell`` shrinks the traffic; the model's sizes
    are shrunk here, to the program's ``tiny`` preset, on both sides. The
    two sides compute the delta rule, attention and the experts in
    different orders, so in bfloat16 their first steps differ by rounding
    (``value_gap_1`` 0.005 where the LSTM's reads 0): its limit here lies
    between that and the control's 0.015."""
    from gtopkssgd_tpu.models.qwen3_next import PRESETS

    cell = tiny.tiny_cell(CELL)
    cell.config["sizes"] = dict(PRESETS["tiny"])
    cell.config["input"].update(vocab_size=PRESETS["tiny"]["vocab_rows"],
                                bptt=PRESETS["tiny"]["seq_len"])
    cell.config["program"]["model_preset"] = "tiny"
    cell.traffic["density"] = 0.01
    cell.traffic["limits"].update(value_gap_1={"max": 0.009})
    return cell


def test_whole_run_is_correct_and_its_line_has_the_schema():
    cell = tiny_cell()
    result, lines = tiny.run(cell, traced=False)
    tiny.check_schema(cell, result, traced=False)
    assert result["correct"] is True, lines
    assert any(line.startswith("reference steps=") for line in lines)
    for name in cell.traffic["limits"]:
        assert any(line.startswith(f"compare {name} = ") and "limit [" in line
                   for line in lines)


def test_traced_run_reports_what_a_cpu_trace_can_give():
    cell = tiny_cell()
    result, lines = tiny.run(cell, traced=True)
    tiny.check_schema(cell, result, traced=True)
    assert {"io_ms", "dispatch_ms", "obs_read_ms", "device_idle",
            "device_step_ms", "moe_load_imbalance"} <= set(result["metrics"])
    assert 1.0 <= result["metrics"]["moe_load_imbalance"]["value"] < 4.0
    # The CPU's trace carries no tf_op and the CPU has no peak: the layer
    # kinds and the roofline share find nothing to read and are left out.
    assert not set(result["metrics"]) & set(NEW[:7])
    assert "mfu" not in result["metrics"]


def test_lower_precision_control_is_not_correct():
    """The reference with bfloat16 master weights, in the program's place."""
    cell = tiny_cell()
    tr = cell.traffic
    pool = traffic.make_pool(cell.config, tr, 3)
    ref = reference.train(cell.config, tr, 3, pool, tr["probe_steps"])
    low = reference.train(cell.config, tr, 3, pool, tr["probe_steps"],
                          master_bits=16)
    lines = []
    values = compare.numbers(low, ref, cell.config, tr)
    limits = {k: v for k, v in tr["limits"].items() if k in values}
    assert not compare.decide(values, limits, lines.append)
    assert any("value_gap_1" in line and "FAILED" in line for line in lines)
    assert compare.decide(compare.numbers(ref, ref, cell.config, tr), limits,
                          lines.append)


def test_step_that_leaves_the_state_alone_is_not_correct(monkeypatch):
    import jax

    build = harness.build_trainer

    def broken(cell, seed, pool):
        trainer = build(cell, seed, pool)
        step = trainer._train_step.__wrapped__
        trainer._train_step = jax.jit(
            lambda s, c, b: (s, c) + tuple(step(s, c, b)[2:]))
        return trainer

    monkeypatch.setattr(harness, "build_trainer", broken)
    result, lines = tiny.run(tiny_cell(), traced=False)
    assert result["correct"] is False
    assert any("dparam_gap_3" in line and "FAILED" in line for line in lines)


# ------------------------------------------------------- the layer kinds
PATHS = {
    # Recorded form of a tf_op path: scopes nest, the innermost counts.
    "fusion.11": "jit(gtopk_train_step)/gtopk/fwd_bwd/while/body/closed_call/"
                 "checkpoint/Qwen3Next/layer_0/mixer/layer/gdn_proj/dot_general",
    "triangular-solve.3": "jit(gtopk_train_step)/gtopk/fwd_bwd/while/body/"
                          "checkpoint/layer_1/mixer/while/body/checkpoint/"
                          "layer/gdn_scan/triangular_solve",
    "custom-call.7": "jit(gtopk_train_step)/gtopk/fwd_bwd/transpose(jvp(layer_3))/"
                     "moe/layer/moe_router/custom_vjp_call/while/body/"
                     "layer/moe_experts/ragged_dot",
    "scatter.2": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer_3/moe/"
                 "layer/moe_router/custom_vjp_call/while/body/"
                 "layer/moe_router/scatter-add",
    "fusion.40": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer_2/moe/"
                 "layer/shared_expert/dot_general",
    "fusion.41": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer_3/mixer/layer/attn/exp",
    "fusion.50": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer/head/reduce_max",
    "multiply_add_fusion.6": "jit(gtopk_train_step)/gtopk/apply/add",
    "approx_top_k.0": "jit(gtopk_train_step)/gtopk/select/approx_top_k",
}


def test_kind_of_takes_the_innermost_layer_scope():
    kinds = {op: layer_ms.kind_of(path) for op, path in PATHS.items()}
    assert kinds == {
        "fusion.11": "gdn_proj", "triangular-solve.3": "gdn_scan",
        "custom-call.7": "moe_experts", "scatter.2": "moe_router",
        "fusion.40": "shared_expert", "fusion.41": "attn", "fusion.50": "head",
        "multiply_add_fusion.6": "", "approx_top_k.0": ""}
    assert layer_ms.kind_of("jit(f)/my_layer/attn/dot") == ""


def made_up():
    """One chip, two steps of 10 ms: each operation of PATHS once a step, 1
    ms each but the scan's 3, back to back; a small program between the
    steps reuses a name."""
    devices, modules = [], []
    for k in range(2):
        t = k * 10 * MS
        for i, op in enumerate(PATHS):
            dur = 3 * MS if op == "triangular-solve.3" else MS // 2
            devices.append([op, t + i * MS // 2 + (3 * MS if i > 1 else 0), dur])
        devices.append(["fusion.11", t + 9 * MS, 1000])
        modules += [["jit_gtopk_train_step(5)", t, 8 * MS],
                    ["jit_convert(9)", t + 9 * MS, 2000]]
    events = {"devices": {0: sorted(devices, key=lambda e: e[1])},
              "modules": {0: modules}, "async": {}, "spans": []}
    return {"events": events, "steps": 2, "chips": 1, "peaks": None,
            "layer_kinds": {op: layer_ms.kind_of(p) for op, p in PATHS.items()}}


def test_layer_ms_sums_each_kind_inside_the_step_programs():
    ctx = made_up()
    read = lambda kinds: layer_ms.read(ctx, kinds)
    assert read(["gdn_scan"]) == pytest.approx(3.0)
    assert read(["gdn_proj"]) == pytest.approx(0.5)      # not the 9 ms one
    assert read(["moe_experts", "shared_expert"]) == pytest.approx(1.0)
    assert read(["moe_router"]) == read(["attn"]) == read(["head"]) \
        == pytest.approx(0.5)
    # A program without the scopes: nothing to read, not zero.
    assert layer_ms.read(dict(ctx, layer_kinds={op: "" for op in PATHS}),
                         ["attn"]) is None
    assert layer_ms.read(dict(ctx, layer_kinds=None), ["attn"]) is None


def test_new_readers_return_nothing_without_the_programs_counters(monkeypatch):
    """What the parent commit gives these readers: no counters, no scopes."""
    from gtopkssgd_tpu.obs import counters
    from perfbench.metrics import gdn_scan_roofline, moe_load_imbalance

    monkeypatch.setattr(counters, "_last_model", {})
    assert moe_load_imbalance.read({}) is None
    monkeypatch.delattr(counters, "last_model_scalars")
    assert moe_load_imbalance.read({}) is None
    cell = harness.load_cell(CELL)
    ctx = dict(made_up(), config=cell.config)
    assert gdn_scan_roofline.read(ctx) is None            # no peak on a CPU
    peaks = harness.peaks_for("TPU v5 lite")
    share = gdn_scan_roofline.read(dict(ctx, peaks=peaks))
    # 8.9 ms at the HBM's bandwidth over the made-up 3 ms: the reader
    # divides; a real run cannot pass 100.
    assert share == pytest.approx(100 * 8.8956 / 3.0, rel=1e-3)
    assert gdn_scan_roofline.read(
        dict(ctx, peaks=peaks, layer_kinds={op: "" for op in PATHS})) is None


# --------------------------------------------------- the files themselves
def test_sizes_agree_with_the_programs_preset_and_the_catalog():
    from gtopkssgd_tpu.models.qwen3_next import PRESETS

    cell = harness.load_cell(CELL)
    cfg, preset = cell.config, PRESETS["80b_a3b_ep64"]
    assert cfg["program"]["model_preset"] == "80b_a3b_ep64"
    assert {k: cfg["sizes"][k] for k in preset} == preset
    # Every key of the published config.json is in the file, unchanged but
    # for the depth; what else is cut has a key of its own beside the
    # published count.
    published = {k: v for k, v in cfg["sizes"].items()
                 if k not in ("experts_held", "expert_offset",
                              "expert_parallel", "vocab_rows", "seq_len")}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 4 and cfg["num_experts"] == 512
    assert cfg["vocab_size"] == 151936 and cfg["hidden_size"] == 2048
    assert cfg["reduced"] == ["num_hidden_layers", "experts_held", "vocab_rows"]
    assert cfg["experts_held"] * cfg["sizes"]["expert_parallel"] \
        == cfg["num_experts"]
    assert cfg["vocab_rows"] * 8 == cfg["vocab_size"]
    assert "64 chips" in cfg["deployment"]
    assert cfg["input"]["vocab_size"] == cfg["vocab_rows"]
    assert cfg["input"]["bptt"] == cfg["sizes"]["seq_len"]
    assert cfg["parameters"] == 323_677_248     # counted in test_qwen3_next.py


def test_entries_keep_the_contracts_letter():
    """What ``test_perfbench_contract`` checks of an entry, for this
    configuration's entry too: that test also asserts ``reduced == []``,
    true of the two configurations it was written for and not of a
    chip's share (conftest.py marks that one case)."""
    import re

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    assert name.match(entry["name"]) and all(name.match(k) for k in entry["reduced"])
    assert len(entry["reduced"]) <= 16
    for key in ("why", "source"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
            and "\t" not in entry[key]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    with open(os.path.join(harness.ROOT, entry["file"])) as fh:
        config = json.load(fh)
    assert config["name"] == entry["name"] and config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    widths = re.compile(r"(_dim|_rank|_size|intermediate|head_dim|per_tok)")
    assert not any(widths.search(k) for k in entry["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    new = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == NEW
    assert all(m["workloads"] == [CELL] and m["moves"] == "throughput"
               for m in new)
    assert {m["layer"] for m in new} == {"decoder layer kinds"}
    limits = cell and harness.load_cell(CELL).traffic["limits"]
    assert all("why" in v and "PLACEHOLDER" not in v["why"]
               for v in limits.values())
