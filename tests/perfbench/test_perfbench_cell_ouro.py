"""ouro_l5.gtopk on the CPU at the model's ``tiny`` preset: a traced whole
run (``correct``, the schema, ``loop_exit_entropy`` from the program's own
counter), the control, the thirteen readers on recorded paths and on a
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

CELL = "ouro_l5.gtopk"
CONFIG = "ouro_2p6b_l5"
TRAFFIC = "gtopk_r001_s4096_b1_loop4"
NEW = {"loop_attn_ms": ("ms", "lower"), "loop_attn_kernel_ms": ("ms", "lower"),
       "loop_attn_proj_ms": ("ms", "lower"),
       "loop_attn_pointwise_ms": ("ms", "lower"),
       "loop_attn_layout_ms": ("ms", "lower"), "loop_mlp_ms": ("ms", "lower"),
       "loop_head_ms": ("ms", "lower"), "loop_exit_ms": ("ms", "lower"),
       "loop_replay_ms": ("ms", "lower"),
       "loop_attn_roofline": ("%", "higher"),
       "loop_mlp_roofline": ("%", "higher"),
       "loop_head_roofline": ("%", "higher"),
       "loop_exit_entropy": ("ratio", "higher")}
TRACE_READ = [name for name in NEW if name != "loop_exit_entropy"]
REDUCED = ["num_hidden_layers"]
# What the file's ``sizes`` holds beside the published keys: the whole
# vocabulary's rows, the sequence and the one assumed coefficient.
BESIDE = ("vocab_rows", "seq_len", "exit_entropy_coeff")


def tiny_cell():
    """``perfbench_tiny.tiny_cell`` shrinks the traffic; the model's sizes
    are shrunk here, to the program's ``tiny`` preset, on both sides. In
    bfloat16 at 64 hidden units the two sides' first steps differ by
    rounding noise (their products round alike, their sums are taken in
    another order); the limit lies between that and the control's."""
    from gtopkssgd_tpu.models.ouro import PRESETS

    cell = tiny.tiny_cell(CELL)
    cell.config["sizes"] = dict(PRESETS["tiny"])
    cell.config["input"].update(vocab_size=PRESETS["tiny"]["vocab_rows"],
                                bptt=PRESETS["tiny"]["seq_len"])
    cell.config["program"]["model_preset"] = "tiny"
    cell.traffic["density"] = 0.01
    cell.traffic["limits"].update(value_gap_1={"max": 0.01})
    return cell


def test_traced_run_is_correct_and_reads_the_exit_entropy():
    """The cell through ``harness.run_cell`` with the profiler on: every
    limit printed and kept, the line's schema, what a CPU trace can give,
    and ``loop_exit_entropy`` from the last step's counter (three passes
    from a zero gate: near H(1/2, 1/4, 1/4) / ln 3 = 0.946)."""
    from gtopkssgd_tpu.obs import counters

    cell = tiny_cell()
    result, lines = tiny.run(cell, traced=True)
    tiny.check_schema(cell, result, traced=True)
    assert result["correct"] is True, lines
    assert any(line.startswith("reference steps=") for line in lines)
    for name in cell.traffic["limits"]:
        assert any(line.startswith(f"compare {name} = ") and "limit [" in line
                   for line in lines)
    assert {"io_ms", "dispatch_ms", "obs_read_ms", "device_idle",
            "device_step_ms"} <= set(result["metrics"])
    # The CPU's trace carries no tf_op and the CPU has no peak: the kinds,
    # the parts and the roofline shares find nothing to read and are left
    # out, as on a program without the scopes.
    assert not set(result["metrics"]) & set(TRACE_READ)
    assert "mfu" not in result["metrics"]
    entropy = result["metrics"]["loop_exit_entropy"]
    assert entropy["unit"] == "ratio" and 0.9 < entropy["value"] <= 1.0
    assert entropy["value"] == counters.last_model_scalars()[
        "loop_exit_entropy"]


def test_exit_entropy_reader_on_a_program_without_the_counter(monkeypatch):
    from gtopkssgd_tpu.obs import counters
    from perfbench.metrics import loop_exit_entropy

    monkeypatch.setattr(counters, "_last_model", {"moe_count_max": 3.0})
    assert loop_exit_entropy.read({}) is None
    monkeypatch.setattr(counters, "_last_model", {"loop_exit_entropy": 0.0})
    assert loop_exit_entropy.read({}) == 0.0        # collapsed is a reading
    monkeypatch.delattr(counters, "last_model_scalars")
    assert loop_exit_entropy.read({}) is None


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


# ------------------------------------------------ the kinds and the parts
ROOT = "jit(gtopk_train_step)/gtopk/fwd_bwd/while/body/closed_call/"
FORWARD = ROOT + "jvp(Ouro)/while/body/closed_call/"
BACKWARD = ROOT + "transpose(jvp(Ouro))/while/body/closed_call/"
PATHS = {
    # Recorded from the published step compiled for a described v5e: the
    # passes' loop is the inner ``while``; scopes nest, the innermost counts.
    "fusion.1": FORWARD + "layer_1/Layer/layer/attn/mixer/part/kernel/"
                "flash_attention_forward/pallas_call",
    "fusion.2": BACKWARD + "layer_1/layer_1/checkpoint/Layer/layer/attn/"
                "mixer/part/kernel/flash_attention_backward_kv/pallas_call",
    "fusion.3": FORWARD + "layer_1/Layer/layer/attn/mixer/part/proj/"
                "dot_general",
    "fusion.4": BACKWARD + "layer_1/layer_1/checkpoint/rematted_computation/"
                "Layer/layer/attn/part/pointwise/rsqrt",
    "fusion.5": FORWARD + "layer_1/Layer/layer/attn/mixer/part/layout/"
                "convert_element_type",
    "fusion.9": BACKWARD + "layer_0/layer_0/checkpoint/rematted_computation/"
                "Layer/layer/dense_mlp/mlp/dot_general",
    "fusion.10": FORWARD + "layer_0/Layer/layer/dense_mlp/mlp/dot_general",
    "fusion.20": FORWARD + "layer/head/dot_general",
    "fusion.21": ROOT + "transpose(jvp(Ouro))/layer/exit_gate/exp",
    "multiply_add_fusion.6": "jit(gtopk_train_step)/gtopk/apply/add",
}
SPANS = {"fusion.1": 3 * MS, "fusion.2": 5 * MS, "fusion.3": 2 * MS,
         "fusion.4": MS, "fusion.5": MS, "fusion.9": 2 * MS,
         "fusion.10": 4 * MS, "fusion.20": 5 * MS, "fusion.21": MS // 2,
         "multiply_add_fusion.6": MS // 2}


def made_up():
    """One chip, two steps: the mixer 12 ms a step (kernels 8, projections
    2, a replayed norm 1, a transpose 1), the feed-forward 6 (2 replayed),
    the heads 5, the exit objective 0.5, back to back with the rest."""
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
    assert layer_ms.kind_of(PATHS["fusion.21"]) == "exit_gate"
    assert part_ms.pass_of(PATHS["fusion.9"]) == "replay"
    want = {"loop_attn_ms": 12.0, "loop_attn_kernel_ms": 8.0,
            "loop_attn_proj_ms": 2.0, "loop_attn_pointwise_ms": 1.0,
            "loop_attn_layout_ms": 1.0, "loop_mlp_ms": 6.0,
            "loop_head_ms": 5.0, "loop_exit_ms": 0.5, "loop_replay_ms": 3.0}
    for name, ms in want.items():
        read, args = reader_args(name)
        assert read(ctx, **args) == pytest.approx(ms), name
    # The four parts are the whole of the kind.
    assert sum(want[f"loop_attn_{p}_ms"] for p in (
        "kernel", "proj", "pointwise", "layout")) == want["loop_attn_ms"]
    rooflines = {"loop_attn_roofline": 12.0, "loop_mlp_roofline": 6.0,
                 "loop_head_roofline": 5.0}
    for name in rooflines:
        read, args = reader_args(name)
        assert read(ctx, **args) is None            # no peak on a CPU
    ctx["peaks"] = harness.peaks_for("TPU v5 lite")
    # 3 passes x 2 operations x MACs of all 20 layer-passes (4 heads) at
    # the bf16 peak over the made-up milliseconds: the readers divide.
    macs = {"loop_attn_roofline": 20 * (4096 * 16_777_216
                                        + 8_390_656 * 4096),
            "loop_mlp_roofline": 20 * 4096 * 34_603_008,
            "loop_head_roofline": 4 * 4096 * 2048 * 49152}
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
    # The other decoders' attention kinds are not this one.
    assert layer_ms.read(ctx, ["attn_full", "attn_window", "attn_latent"]) \
        == 0.0


def test_work_counts_every_pass():
    cfg = harness.load_cell(CELL).config
    ref = importlib.import_module(
        f"perfbench.refmodels.{cfg['reference_model']}")
    sizes = cfg["sizes"]
    assert ref.causal_pairs(sizes) == 4096 * 4097 // 2 == 8_390_656
    assert ref.layer_passes(sizes) == 20
    assert ref._projection_macs(sizes) == 16_777_216
    assert ref._pair_macs(sizes) == 16 * 256 == 4096
    per_token = 20 * (16_777_216 + 34_603_008) + 4 * 49152 * 2048
    assert per_token == 1_430_257_664                 # ISSUE 41's count
    macs = 4096 * per_token + 20 * 8_390_656 * 4096
    assert ref.forward_macs(sizes) == macs == 6_545_697_931_264
    assert cfg["flops_per_sample"]["forward_macs"] == macs
    assert cfg["flops_per_sample"]["train"] == 6 * macs
    works = [getattr(ref, name)(sizes, 1) for name in (
        "loop_attn_work", "loop_mlp_work", "loop_head_work")]
    assert sum(ops for ops, _ in works) == 6 * macs
    assert ref.loop_attn_work(sizes, 2)[0] == 2 * works[0][0]
    # Operations bound all three: at the chip's peaks the least bytes take
    # less.
    for ops, moved in works:
        assert ops / 197e12 > moved / 819e9 > 0
    # SwiGLU 43%, the four heads 25%, the mixers 31.5% (projections 21%,
    # pairs 10.5%) of the step's mathematics.
    shares = [ops / (6 * macs) for ops, _ in works]
    assert shares == pytest.approx([0.315, 0.433, 0.252], abs=2e-3)


# --------------------------------------------------- the files themselves
def test_sizes_agree_with_the_programs_preset_and_the_catalog():
    from gtopkssgd_tpu.models.ouro import PRESETS

    cell = harness.load_cell(CELL)
    cfg, preset = cell.config, PRESETS["2p6b_l5"]
    assert cfg["program"]["model_preset"] == "2p6b_l5"
    assert cfg["program"]["dnn"] == cfg["reference_model"] == "ouro"
    assert {k: cfg["sizes"][k] for k in preset} == preset
    # Every key of the published config.json is in the file at the top
    # level, unchanged but for the depth (and ``layer_types`` as the five
    # kept).
    published = {k: v for k, v in cfg["sizes"].items() if k not in BESIDE}
    assert {k: cfg[k] for k in published} == published
    assert len(published) == 20
    assert cfg["num_hidden_layers"] == 5 and cfg["total_ut_steps"] == 4
    assert cfg["layer_types"] == ["full_attention"] * 5
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"]) \
        == (2048, 5632, 128, 16, 16)
    assert cfg["rope_theta"] == 1000000 and cfg["rope_scaling"] is None
    assert cfg["rms_norm_eps"] == 1e-6 and cfg["hidden_act"] == "silu"
    assert cfg["vocab_size"] == cfg["sizes"]["vocab_rows"] == 49152
    assert cfg["model_type"] == "ouro" and cfg["early_exit_threshold"] == 1
    assert cfg["tie_word_embeddings"] is False
    assert cfg["use_sliding_window"] is False and cfg["sliding_window"] is None
    assert cfg["max_position_embeddings"] == 65536
    assert cfg["max_window_layers"] == 48
    assert cfg["sizes"]["exit_entropy_coeff"] == 0.1
    assert cfg["reduced"] == REDUCED
    assert "first five layers" in cfg["deployment"] \
        and "other 43" in cfg["deployment"]
    assert "25%" in cfg["cut"]["share_of_work"] \
        and "4%" in cfg["cut"]["share_of_work"]
    assert "458,272,769" in cfg["cut"]["parameters"]
    for said in ("post-attention", "between the passes", "bias",
                 "exit distribution", "beta = 0.1", "4,096", "zero-centred",
                 "N(0, 0.02)", "not AdamW", "position 0"):
        assert any(said in a for a in cfg["assumed"]), said
    assert "highest" in cfg["precisions"]
    assert cfg["input"]["vocab_size"] == cfg["vocab_size"]
    assert cfg["input"]["bptt"] == cfg["sizes"]["seq_len"] == 4096
    assert cfg["input"]["follow"] == 0.5 and cfg["input"]["kind"] == "tokens"
    assert cfg["parameters"] == 458_272_769       # counted in test_ouro.py
    tr = cell.traffic
    assert tr["name"] == TRAFFIC
    assert (tr["batch_size"], tr["density"], tr["compression"]) \
        == (1, 0.001, "gtopk")
    assert math.ceil(tr["density"] * cfg["parameters"]) == 458_273
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

    # Its thirteen metrics, in the order they were appended, listed for its
    # cell alone, each with a file of its own; the cell in no other
    # metric's list.
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert {m["name"]: (m["unit"], m["better"]) for m in mine} == NEW
    assert [m["name"] for m in mine] == list(NEW)
    assert all(m["workloads"] == [CELL] and m["moves"] == "throughput"
               and m["layer"] == "decoder layer kinds" for m in mine)
    assert {m["name"] for m in mine if m["source"] != "device_trace"} \
        == {"loop_exit_entropy"}
    for m in mine:
        with open(os.path.join(harness.ROOT, "perfbench", "metrics",
                               m["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert (spec["name"], spec["unit"], spec["source"], spec["cells"]) \
            == (m["name"], m["unit"], m["source"], CELL)
    assert not any(CELL in m.get("workloads", [])
                   for m in bench["per_layer"] if m["name"] not in NEW)
    assert len(bench["workloads"]) >= 9 and len(bench["configs"]) >= 7
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
    path = os.path.join(harness.ROOT, "perfbench", "refmodels", "ouro.py")
    with open(path) as fh:
        source = fh.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert imports and not any(m.startswith(("gtopkssgd_tpu", "perfbench"))
                               for m in imports)
