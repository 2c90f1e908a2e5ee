"""sdar_ep8.gtopk on the CPU at the model's ``tiny`` preset: a traced whole
run (``correct``, the schema, ``bd_masked_share`` and
``bd_moe_load_imbalance`` from the program's own counters), the control,
the fourteen readers on recorded paths and on a
program without their scopes, the work functions, and the configuration's
files against the program's published preset, the catalog's keys and the
contract's letter (every entry looked up by its name, none by its place in
a list)."""

import importlib
import json
import math
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from perfbench import compare, harness, reference, traffic  # noqa: E402
from perfbench.metrics import layer_ms, part_ms  # noqa: E402
from test_perfbench_cell_kanana2 import (  # noqa: E402
    MS, NAME, SPARSE_LIMITS, WIDTH, reader_args)

CELL = "sdar_ep8.gtopk"
CONFIG = "sdar_30b_a3b_ep8"
TRAFFIC = "gtopk_r001_s8192_b1_bd4"
NEW = {"bd_attn_ms": ("ms", "lower"), "bd_attn_kernel_ms": ("ms", "lower"),
       "bd_attn_proj_ms": ("ms", "lower"),
       "bd_attn_pointwise_ms": ("ms", "lower"),
       "bd_attn_layout_ms": ("ms", "lower"), "bd_moe_route_ms": ("ms", "lower"),
       "bd_moe_expert_ms": ("ms", "lower"), "bd_head_ms": ("ms", "lower"),
       "bd_noise_ms": ("ms", "lower"), "bd_replay_ms": ("ms", "lower"),
       "bd_attn_roofline": ("%", "higher"),
       "bd_attn_kernel_roofline": ("%", "higher"),
       "bd_masked_share": ("ratio", "higher"),
       "bd_moe_load_imbalance": ("ratio", "lower")}
COUNTER_READ = {"bd_masked_share": "loss_ratio_32",
                "bd_moe_load_imbalance": "throughput"}
TRACE_READ = [name for name in NEW if name not in COUNTER_READ]
REDUCED = ["num_hidden_layers", "experts_held", "vocab_rows"]
# The catalog's ``config`` of SDAR-30B-A3B-Chat, every key; the file holds
# each at its top level, the depth cut and listed.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def tiny_cell():
    """``perfbench_tiny.tiny_cell`` shrinks the traffic; the model's sizes
    are shrunk here, to the program's ``tiny`` preset, on both sides, the
    data's ids below the mask id as in the published file. In bfloat16 at
    64 hidden units the two sides' first steps differ by rounding noise;
    the limit lies between that and the control's."""
    from gtopkssgd_tpu.models.sdar import PRESETS

    cell = tiny.tiny_cell(CELL)
    cell.config["sizes"] = dict(PRESETS["tiny"])
    cell.config["input"].update(vocab_size=PRESETS["tiny"]["mask_token_id"],
                                bptt=PRESETS["tiny"]["seq_len"])
    cell.config["program"]["model_preset"] = "tiny"
    cell.traffic["density"] = 0.01
    cell.traffic["limits"].update(value_gap_1={"max": 0.01})
    return cell


def test_traced_run_is_correct_and_reads_the_masked_share():
    """The cell through ``harness.run_cell`` with the profiler on: both
    sides draw the same noise from the key they share (the first losses
    agree), every limit printed and kept, the line's schema, what a CPU
    trace can give, and ``bd_masked_share`` from the last step's counter."""
    from gtopkssgd_tpu.obs import counters

    cell = tiny_cell()
    result, lines = tiny.run(cell, traced=True)
    tiny.check_schema(cell, result, traced=True)
    assert result["correct"] is True, lines
    assert any(line.startswith("reference steps=") for line in lines)
    for name in cell.traffic["limits"]:
        assert any(line.startswith(f"compare {name} = ") and "limit [" in line
                   for line in lines)
    gap = next(float(line.split()[3]) for line in lines
               if line.startswith("compare loss_gap_1_3 = "))
    assert gap < 5e-3              # another mask would move the loss by 10%
    assert {"io_ms", "dispatch_ms", "obs_read_ms", "device_idle",
            "device_step_ms"} <= set(result["metrics"])
    # The CPU's trace carries no tf_op and the CPU has no peak: the kinds,
    # the parts and the roofline shares find nothing to read.
    assert not set(result["metrics"]) & set(TRACE_READ)
    assert "mfu" not in result["metrics"]
    share = result["metrics"]["bd_masked_share"]
    assert share["unit"] == "ratio" and 0.1 < share["value"] < 0.9
    last = counters.last_model_scalars()
    assert share["value"] == last["bd_masked_share"]
    # The held experts' fullest over their mean: what tells a heavy seed.
    uneven = result["metrics"]["bd_moe_load_imbalance"]
    assert uneven["unit"] == "ratio" and uneven["value"] >= 1.0
    assert uneven["value"] == last["moe_load_max"] / last["moe_load_mean"]


def test_masked_share_reader_on_a_program_without_the_counter(monkeypatch):
    from gtopkssgd_tpu.obs import counters
    from perfbench.metrics import bd_masked_share

    monkeypatch.setattr(counters, "_last_model", {"moe_load_max": 3.0})
    assert bd_masked_share.read({}) is None
    monkeypatch.setattr(counters, "_last_model", {"bd_masked_share": 0.0})
    assert bd_masked_share.read({}) == 0.0          # nothing masked: a reading
    monkeypatch.delattr(counters, "last_model_scalars")
    assert bd_masked_share.read({}) is None


def test_lower_precision_control_is_not_correct():
    """The reference with bfloat16 master weights, in the program's place."""
    cell = tiny_cell()
    tr = cell.traffic
    pool = traffic.make_pool(cell.config, tr, 3)
    assert max(b["tokens"].max() for b in pool) < 127       # no mask id
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


# ------------------------------------------------ the kinds and the parts
FORWARD = "jit(gtopk_train_step)/gtopk/fwd_bwd/jvp(SDAR)/"
BACKWARD = "jit(gtopk_train_step)/gtopk/fwd_bwd/transpose(jvp(SDAR))/"
REPLAY = BACKWARD + "layer_1/layer_1/checkpoint/rematted_computation/Layer/"
PATHS = {
    # As the published step compiled for a described v5e names them:
    # scopes nest, the innermost counts.
    "fusion.1": FORWARD + "layer_1/Layer/layer/attn/mixer/part/kernel/"
                "flash_attention_forward/pallas_call",
    "fusion.2": BACKWARD + "layer_1/layer_1/checkpoint/Layer/layer/attn/"
                "mixer/part/kernel/flash_attention_backward_kv/pallas_call",
    "fusion.3": FORWARD + "layer_1/Layer/layer/attn/mixer/part/proj/"
                "dot_general",
    "fusion.4": REPLAY + "layer/attn/part/pointwise/rsqrt",
    "fusion.5": FORWARD + "layer_1/Layer/layer/attn/mixer/part/layout/"
                "convert_element_type",
    "fusion.9": REPLAY + "layer/moe_router/moe/layer/moe_router/top_k",
    "fusion.10": FORWARD + "layer_0/Layer/layer/moe_router/moe/while/body/"
                 "layer/moe_experts/ragged_dot",
    "fusion.20": FORWARD + "layer/head/dot_general",
    "fusion.21": FORWARD + "layer/noise/threefry2x32",
    "multiply_add_fusion.6": "jit(gtopk_train_step)/gtopk/apply/add",
}
SPANS = {"fusion.1": 3 * MS, "fusion.2": 5 * MS, "fusion.3": 2 * MS,
         "fusion.4": MS, "fusion.5": MS, "fusion.9": 2 * MS,
         "fusion.10": 4 * MS, "fusion.20": 5 * MS, "fusion.21": MS // 2,
         "multiply_add_fusion.6": MS // 2}


def made_up():
    """One chip, two steps: the mixer 12 ms a step (kernels 8, projections
    2, a replayed norm 1, a cast 1), the router 2 (replayed), the experts
    4, the head 5, the noise 0.5, back to back with the rest."""
    devices, modules = [], []
    for k in range(2):
        t = k * 30 * MS
        modules.append(["jit_gtopk_train_step(5)", t, 29 * MS])
        for op, dur in SPANS.items():
            devices.append([op, t, dur])
            t += dur
    events = {"devices": {0: devices}, "modules": {0: modules}, "async": {},
              "spans": []}
    return {"events": events, "steps": 2, "chips": 1, "peaks": None,
            "layer_kinds": {op: layer_ms.kind_of(p)
                            for op, p in PATHS.items()},
            "parts": {op: (layer_ms.kind_of(p), part_ms.part_of(p),
                           part_ms.pass_of(p)) for op, p in PATHS.items()}}


def test_the_readers_on_recorded_paths_and_on_a_program_without_them():
    cell = harness.load_cell(CELL)
    ctx = dict(made_up(), config=cell.config)
    assert layer_ms.kind_of(PATHS["fusion.21"]) == "noise"
    assert layer_ms.kind_of(PATHS["fusion.10"]) == "moe_experts"
    assert part_ms.pass_of(PATHS["fusion.9"]) == "replay"
    want = {"bd_attn_ms": 12.0, "bd_attn_kernel_ms": 8.0,
            "bd_attn_proj_ms": 2.0, "bd_attn_pointwise_ms": 1.0,
            "bd_attn_layout_ms": 1.0, "bd_moe_route_ms": 2.0,
            "bd_moe_expert_ms": 4.0, "bd_head_ms": 5.0, "bd_noise_ms": 0.5,
            "bd_replay_ms": 3.0}
    for name, ms in want.items():
        read, args = reader_args(name)
        assert read(ctx, **args) == pytest.approx(ms), name
    # The four parts are the whole of the kind.
    assert sum(want[f"bd_attn_{p}_ms"] for p in (
        "kernel", "proj", "pointwise", "layout")) == want["bd_attn_ms"]
    rooflines = {"bd_attn_roofline": 12.0, "bd_attn_kernel_roofline": 8.0}
    for name in rooflines:
        read, args = reader_args(name)
        assert read(ctx, **args) is None            # no peak on a CPU
    ctx["peaks"] = harness.peaks_for("TPU v5 lite")
    # 3 passes x 2 operations x the MACs the loss depends on at the bf16
    # peak over the made-up milliseconds: the readers divide.
    pairs = 4 * 67_141_632 - 33_570_816
    macs = {"bd_attn_kernel_roofline": pairs * 8192,
            "bd_attn_roofline": pairs * 8192 + 8192 * (
                8 * 18_874_368 - 16_777_216)}
    for name, ms in rooflines.items():
        read, args = reader_args(name)
        assert read(ctx, **args) == pytest.approx(
            100 * 6 * macs[name] / 197e12 * 1e3 / ms), name
    # A program without the scopes (the parent, or one that never ran this
    # model), and another decoder's configuration: nothing to read.
    bare = dict(ctx, layer_kinds={op: "" for op in PATHS},
                parts={op: ("", "", "forward") for op in PATHS})
    for name in TRACE_READ:
        read, args = reader_args(name)
        assert read(bare, **args) is None, name
    other = dict(ctx, config=harness.load_cell("kanana2_ep16.gtopk").config)
    for name in rooflines:
        read, args = reader_args(name)
        assert read(other, **args) is None


def test_work_counts_what_the_loss_depends_on():
    cfg = harness.load_cell(CELL).config
    ref = importlib.import_module(
        f"perfbench.refmodels.{cfg['reference_model']}")
    sizes = cfg["sizes"]
    assert ref.live_pairs(sizes) == (33_570_816, 33_538_048, 32_768)
    assert sum(ref.live_pairs(sizes)) == 67_141_632
    assert ref._pair_macs(sizes) == 32 * 256 == 8192
    assert ref._row_macs(sizes) == (16_777_216, 2_097_152,
                                    262_144 + 4_718_592)
    row = 16_777_216 + 2_097_152 + 262_144 + 4_718_592
    assert row == 23_855_104                          # ISSUE 45's 23.86M
    layer = 16384 * row + 67_141_632 * 8192
    head = 8192 * 2048 * 18992
    everything = 4 * layer + head
    assert ref.forward_macs(sizes, everything=True) == everything
    assert everything == pytest.approx(4.08e12, rel=1e-3)
    unused = 8192 * (16_777_216 + 262_144 + 4_718_592) + 33_570_816 * 8192
    macs = everything - unused
    assert ref.forward_macs(sizes) == macs == 3_628_844_711_936
    assert cfg["flops_per_sample"]["forward_macs"] == macs
    assert cfg["flops_per_sample"]["train"] == 6 * macs \
        == 21_773_068_271_616                         # 21.8 TFLOP a sample
    # The live pairs 54%, the projections 30%, router and experts 8%, the
    # head 8% of what a program that computes every row multiplies.
    shares = [4 * 67_141_632 * 8192, 4 * 16384 * 18_874_368,
              4 * 16384 * 4_980_736, head]
    assert sum(shares) == everything
    assert [s / everything for s in shares] == pytest.approx(
        [0.539, 0.303, 0.080, 0.078], abs=2e-3)
    pairs_ops, pairs_bytes = ref.bd_pairs_work(sizes, 1)
    assert pairs_ops == 6 * (4 * 67_141_632 - 33_570_816) * 8192
    attn_ops, attn_bytes = ref.bd_attn_work(sizes, 1)
    assert attn_ops == pairs_ops + 6 * 8192 * (8 * 18_874_368 - 16_777_216)
    assert ref.bd_attn_work(sizes, 2)[0] == 2 * attn_ops
    # Operations bound both: at the chip's peaks the least bytes take less.
    for ops, moved in ((pairs_ops, pairs_bytes), (attn_ops, attn_bytes)):
        assert ops / 197e12 > moved / 819e9 > 0


# --------------------------------------------------- the files themselves
def test_sizes_agree_with_the_programs_preset_and_the_catalog():
    from gtopkssgd_tpu.models.sdar import PRESETS

    cell = harness.load_cell(CELL)
    cfg, preset = cell.config, PRESETS["30b_a3b_ep8"]
    assert cfg["program"]["model_preset"] == "30b_a3b_ep8"
    assert cfg["program"]["dnn"] == cfg["reference_model"] == "sdar"
    assert cfg["sizes"] == preset                     # key for key
    # Every key of the published config.json is in the file at the top
    # level, unchanged but for the depth.
    assert {k: cfg[k] for k in PUBLISHED} == dict(PUBLISHED,
                                                  num_hidden_layers=4)
    assert len(PUBLISHED) == 24
    shared = [k for k in PUBLISHED if k in preset]
    assert {k: preset[k] for k in shared} == {k: cfg[k] for k in shared}
    assert (cfg["experts_held"], cfg["vocab_rows"]) == (16, 18992) \
        == (preset["experts_held"], preset["vocab_rows"])
    assert preset["expert_parallel"] * preset["experts_held"] \
        == cfg["num_experts"] == 128
    assert cfg["num_experts_per_tok"] == 8 and cfg["norm_topk_prob"] is True
    assert cfg["input"]["vocab_size"] == preset["mask_token_id"] \
        == preset["vocab_rows"] - 1 == 18991
    assert cfg["input"]["bptt"] == preset["seq_len"] == 8192
    assert cfg["input"]["follow"] == 0.5 and cfg["input"]["kind"] == "tokens"
    assert (preset["block_length"], preset["noise_eps"]) == (4, 1e-3)
    assert cfg["reduced"] == REDUCED
    assert not any(WIDTH.search(k) for k in cfg["reduced"])
    assert "one of 8 chips" in cfg["deployment"] \
        and "other 44 layers" in cfg["deployment"]
    assert "456,346,624" in cfg["cut"]["parameters"]
    assert "1,024" in cfg["cut"]["tokens_per_expert"] \
        and "8,192" in cfg["cut"]["tokens_per_expert"]
    assert "54%" in cfg["cut"]["share_of_work"] \
        and "84%" in cfg["cut"]["share_of_work"]
    for said in ("block length 4", "one t ~ U(0, 1) a block", "eps = 1e-3",
                 "1 / p", "batch x L", "no shift", "18,991", "zero-centred",
                 "N(0, 0.02)", "adapted autoregressive", "program.seed",
                 "not AdamW", "position 0", "one leaf", "not_given"):
        assert any(said in a for a in cfg["assumed"]), said
    assert "highest" in cfg["precisions"]
    assert cfg["parameters"] == 456_346_624           # counted in test_sdar.py
    tr = cell.traffic
    assert tr["name"] == TRAFFIC
    assert (tr["batch_size"], tr["density"], tr["compression"]) \
        == (1, 0.001, "gtopk")
    assert math.ceil(tr["density"] * cfg["parameters"]) == 456_347
    assert (tr["pool_batches"], tr["probe_steps"], tr["ratio_steps"],
            tr["chunk_steps"], tr["trace_steps"]) == (32, 32, [25, 32], 16, 4)


def test_entries_keep_the_contracts_letter():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        text = fh.read()
    assert len(text.encode()) <= 64 * 1024
    bench = json.loads(text)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert all(NAME.match(k) for k in entry["reduced"])
    assert entry["reduced"] == REDUCED
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    for key in ("why", "source"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
            and "\t" not in entry[key]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    with open(os.path.join(harness.ROOT, entry["file"])) as fh:
        held = json.load(fh)
    assert held["name"] == entry["name"] and held["source"] == entry["source"]
    assert held["reduced"] == entry["reduced"]

    # The configuration's one cell, on one chip.
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert cell["name"] == CELL and cell["traffic"] == TRAFFIC
    assert cell["chips"] == 1 and 1 <= len(cell["why"]) <= 200
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}

    # Its fourteen metrics, in the order they were appended, listed for its
    # cell alone, each with a file of its own; the cell in no other
    # metric's list.
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert {m["name"]: (m["unit"], m["better"]) for m in mine} == NEW
    assert [m["name"] for m in mine] == list(NEW) and len(mine) == 14
    assert all(m["workloads"] == [CELL]
               and m["moves"] == COUNTER_READ.get(m["name"], "throughput")
               and m["layer"] == "decoder layer kinds" for m in mine)
    assert {m["name"] for m in mine if m["source"] != "device_trace"} \
        == set(COUNTER_READ)
    for m in mine:
        with open(os.path.join(harness.ROOT, "perfbench", "metrics",
                               m["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert (spec["name"], spec["unit"], spec["source"], spec["cells"],
                spec["moves"]) \
            == (m["name"], m["unit"], m["source"], CELL, m["moves"])
        assert "TBD" not in spec["what"]
    assert not any(CELL in m.get("workloads", [])
                   for m in bench["per_layer"] if m["name"] not in NEW)
    assert len(bench["workloads"]) >= 10 and len(bench["configs"]) >= 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1

    limits = harness.load_cell(CELL).traffic["limits"]
    assert set(limits) == SPARSE_LIMITS
    assert all("why" in v and "TBD" not in v["why"] for v in limits.values())
    # Every limit the control is held to says both readings.
    for name in ("value_gap_1", "support_recall_1", "support_recall_2",
                 "value_gap_2", "dparam_gap_3", "loss_gap_1_3", "loss_ratio"):
        assert "sound" in limits[name]["why"] \
            and "control" in limits[name]["why"], name


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.ROOT, "perfbench", "refmodels", "sdar.py")
    with open(path) as fh:
        source = fh.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert imports and not any(m.startswith(("gtopkssgd_tpu", "perfbench"))
                               for m in imports)
