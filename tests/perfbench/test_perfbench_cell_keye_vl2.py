"""keye_vl2_ep16.gtopk on the CPU at the model's ``tiny`` preset: a whole
run, a traced run, the control, a run whose timed path is broken
underneath, the new readers on a program without their scopes or counters,
and the configuration's files against the program's published preset, the
catalog's keys and the contract's letter."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from perfbench import compare, harness, reference, traffic  # noqa: E402
from perfbench.metrics import layer_ms  # noqa: E402

CELL = "keye_vl2_ep16.gtopk"
CONFIG = "keye_vl2_30b_a3b_ep16"
NEW = ["dsa_index_ms", "dsa_select_ms", "dsa_attn_ms", "dsa_index_roofline",
       "dsa_attn_roofline", "dsa_kept_ratio"]
CUT = ("experts_held", "expert_offset", "expert_parallel", "vocab_rows",
       "seq_len")
MS = 1_000_000


def tiny_cell():
    """``perfbench_tiny.tiny_cell`` shrinks the traffic; the model's sizes
    are shrunk here, to the program's ``tiny`` preset, on both sides. In
    bfloat16 the two sides round the softmax's weights at different points
    (the reference the probabilities, the program exp(logit - bound) before
    it divides by their sum), so at 8 keys a query and 64 hidden units their
    first steps differ by rounding noise: ``value_gap_1`` 0.022 to 0.027 and
    ``value_gap_2`` 0.038 to 0.068 over seeds 3 and 7, where the control
    reads 0.048 to 0.066 and 0.079 to 0.082. The first limit lies between;
    the second step's is left wide, the control fails the first."""
    from gtopkssgd_tpu.models.keye_vl2 import PRESETS

    cell = tiny.tiny_cell(CELL)
    cell.config["sizes"] = dict(PRESETS["tiny"])
    cell.config["input"].update(vocab_size=PRESETS["tiny"]["vocab_rows"],
                                bptt=PRESETS["tiny"]["seq_len"])
    cell.config["program"]["model_preset"] = "tiny"
    cell.traffic["density"] = 0.01
    cell.traffic["limits"].update(value_gap_1={"max": 0.036},
                                  value_gap_2={"max": 0.15})
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
            "device_step_ms", "dsa_kept_ratio"} <= set(result["metrics"])
    # Never under 1: a query keeps its topk keys and whatever ties with the
    # last of them (at 4 indexer heads a score in sixteen is exactly 0).
    assert 1.0 <= result["metrics"]["dsa_kept_ratio"]["value"] < 1.1
    # The CPU's trace carries no tf_op and the CPU has no peak: the layer
    # kinds and the roofline shares find nothing to read and are left out.
    assert not set(result["metrics"]) & set(NEW[:5])
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
    "fusion.3": "jit(gtopk_train_step)/gtopk/fwd_bwd/checkpoint/KeyeVL2/layer_0/"
                "layer/attn/mixer/layer/dsa_index/dot_general",
    "while.9": "jit(gtopk_train_step)/gtopk/fwd_bwd/checkpoint/layer_1/layer/attn/"
               "mixer/while/body/layer/dsa_select/while/body/reduce_sum",
    "fusion.12": "jit(gtopk_train_step)/gtopk/fwd_bwd/transpose(jvp(layer_2))/"
                 "layer/attn/mixer/while/body/checkpoint/layer/attn/while/body/exp",
    "fusion.20": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer_3/layer/moe_router/moe/"
                 "layer/moe_router/top_k",
    "fusion.50": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer/head/reduce_max",
    "multiply_add_fusion.6": "jit(gtopk_train_step)/gtopk/apply/add",
}


def test_kind_of_reads_the_new_scopes_innermost():
    assert {op: layer_ms.kind_of(path) for op, path in PATHS.items()} == {
        "fusion.3": "dsa_index", "while.9": "dsa_select", "fusion.12": "attn",
        "fusion.20": "moe_router", "fusion.50": "head",
        "multiply_add_fusion.6": ""}


def made_up():
    """One chip, two steps of 10 ms: the index operation 2 ms a step, the
    selection 1 ms, the attention 4 ms, back to back."""
    spans = {"fusion.3": 2 * MS, "while.9": MS, "fusion.12": 4 * MS,
             "fusion.20": MS // 2, "fusion.50": MS // 2,
             "multiply_add_fusion.6": MS // 2}
    devices, modules = [], []
    for k in range(2):
        t = k * 10 * MS
        modules.append(["jit_gtopk_train_step(5)", t, 9 * MS])
        for op, dur in spans.items():
            devices.append([op, t, dur])
            t += dur
    events = {"devices": {0: devices}, "modules": {0: modules}, "async": {},
              "spans": []}
    return {"events": events, "steps": 2, "chips": 1, "peaks": None,
            "layer_kinds": {op: layer_ms.kind_of(p) for op, p in PATHS.items()}}


def test_new_readers_return_nothing_on_a_program_without_scopes_or_counters(
        monkeypatch):
    """What the parent commit, or any program that never ran this model,
    gives the new readers: no counters, no scopes; and what they divide
    where there is something to read."""
    from gtopkssgd_tpu.obs import counters
    from perfbench.metrics import dsa_kept_ratio, work_roofline

    monkeypatch.setattr(counters, "_last_model", {"moe_load_mean": 3.0})
    assert dsa_kept_ratio.read({}) is None
    monkeypatch.setattr(counters, "_last_model",
                        {"dsa_keys_kept": 714.0, "dsa_keys_due": 712.0})
    assert dsa_kept_ratio.read({}) == pytest.approx(714 / 712)
    monkeypatch.delattr(counters, "last_model_scalars")
    assert dsa_kept_ratio.read({}) is None

    cell = harness.load_cell(CELL)
    ctx = dict(made_up(), config=cell.config)
    index = dict(work="dsa_index_work", kinds=["dsa_index", "dsa_select"])
    attn = dict(work="dsa_attn_work", kinds=["attn"])
    assert work_roofline.read(ctx, **index) is None          # no peak on a CPU
    peaks = harness.peaks_for("TPU v5 lite")
    ctx["peaks"] = peaks
    # 3 x 2 x 4 layers x (16,384 x 2,260,992 + 134,225,920 x 1,024) MACs at
    # the bf16 peak over the made-up 3 ms; 4 x (16,384 x 18,874,368 +
    # 31,458,304 x 8,192) over 4 ms: the reader divides; a run cannot pass 100.
    assert work_roofline.read(ctx, **index) == pytest.approx(
        100 * 24 * (16384 * 2260992 + 134225920 * 1024) / 197e12 * 1e3 / 3.0)
    assert work_roofline.read(ctx, **attn) == pytest.approx(
        100 * 24 * (16384 * 18874368 + 31458304 * 8192) / 197e12 * 1e3 / 4.0)
    assert work_roofline.read(ctx, work="no_such_work", kinds=["attn"]) is None
    bare = dict(ctx, layer_kinds={op: "" for op in PATHS})
    assert work_roofline.read(bare, **index) is None
    assert layer_ms.read(bare, ["dsa_select"]) is None
    # The other decoder's configuration counts no such work.
    other = dict(ctx, config=harness.load_cell("qwen3_next_ep64.gtopk").config)
    assert work_roofline.read(other, **attn) is None


def test_work_counts_the_models_key_sets_not_the_masked_form():
    import importlib

    cfg = harness.load_cell(CELL).config
    ref = importlib.import_module(f"perfbench.refmodels.{cfg['reference_model']}")
    sizes = cfg["sizes"]
    assert ref.index_pairs(sizes) == 16384 * 16385 // 2
    assert ref.keys_due(sizes) == 2048 * 2049 // 2 + (16384 - 2048) * 2048
    ops, moved = ref.dsa_attn_work(sizes, 1)
    assert ops == 24 * (16384 * 18874368 + 31458304 * 8192)
    # Operations bound both: at the chip's peaks the least bytes take less.
    for work in (ref.dsa_index_work, ref.dsa_attn_work):
        ops, moved = work(sizes, 1)
        assert ops / 197e12 > moved / 819e9 > 0
    per_token = ref.forward_macs(sizes) / sizes["seq_len"]
    assert 230.3e6 < per_token < 230.5e6


# --------------------------------------------------- the files themselves
def test_sizes_agree_with_the_programs_preset_and_the_catalog():
    from gtopkssgd_tpu.models.keye_vl2 import PRESETS

    cell = harness.load_cell(CELL)
    cfg, preset = cell.config, PRESETS["30b_a3b_ep16"]
    assert cfg["program"]["model_preset"] == "30b_a3b_ep16"
    assert cfg["program"]["dnn"] == cfg["reference_model"] == "keye_vl2"
    assert {k: cfg["sizes"][k] for k in preset} == preset
    # Every key of the published config.json is in the file at the top
    # level, unchanged but for the depth (nested groups whole); what else is
    # cut has a key of its own beside the published count; the preset's flat
    # indexer keys are ``sa_config``'s.
    flat = cfg["sa_config"]
    published = {k: v for k, v in cfg["sizes"].items()
                 if k not in CUT and k not in flat}
    assert {k: cfg[k] for k in published} == published
    assert {k: cfg["sizes"][k] for k in flat} == flat == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert cfg["num_hidden_layers"] == 4 and cfg["num_experts"] == 128
    assert cfg["num_experts_per_tok"] == 8 and cfg["moe_intermediate_size"] == 768
    assert cfg["vocab_size"] == 151936 and cfg["hidden_size"] == 2048
    assert cfg["model_type"] == "KeyeVL2" and cfg["tie_word_embeddings"] is False
    assert cfg["reduced"] == ["num_hidden_layers", "experts_held", "vocab_rows"]
    assert cfg["experts_held"] * cfg["sizes"]["expert_parallel"] \
        == cfg["num_experts"]
    assert cfg["vocab_rows"] * 8 == cfg["vocab_size"]
    assert "16 chips" in cfg["deployment"]
    assert any("vision tower is not built" in a for a in cfg["assumed"])
    assert cfg["input"]["vocab_size"] == cfg["vocab_rows"]
    assert cfg["input"]["bptt"] == cfg["sizes"]["seq_len"] == 16384
    assert cfg["parameters"] == 314_396_160        # counted in test_keye_vl2.py
    assert cell.traffic["batch_size"] == 1 and cell.traffic["density"] == 0.001


def test_entries_keep_the_contracts_letter():
    """What ``test_perfbench_contract`` checks of an entry, for this
    configuration's entry too: that test also asserts ``reduced == []``,
    true of the two configurations it was written for and not of a chip's
    share (``tests/conftest.py`` marks that one case)."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert bench["configs"][-1] is entry
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
    cell = bench["workloads"][-1]
    assert cell["name"] == CELL and cell["traffic"] == "gtopk_r001_s16384_b1"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    new = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in new] == NEW
    assert all(m["workloads"] == [CELL] and m["moves"] == "throughput"
               for m in new)
    assert {m["layer"] for m in new} == {"decoder layer kinds"}
    assert [m["unit"] for m in new] == ["ms", "ms", "ms", "%", "%", "ratio"]
    # No accepted metric's list was touched: the cell is in none of them.
    assert not any(CELL in m.get("workloads", []) for m in
                   bench["per_layer"][:-len(NEW)])
    limits = harness.load_cell(CELL).traffic["limits"]
    assert all("why" in v and "PLACEHOLDER" not in v["why"]
               for v in limits.values())
