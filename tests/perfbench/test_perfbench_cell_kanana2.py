"""kanana2_ep16.gtopk on the CPU at the model's ``tiny`` preset: a whole run,
a traced run, the control, the new readers on a program without their
scopes, the work functions, and the configuration's files against the
program's published preset, the catalog's keys and the contract's letter
(every entry looked up by its name, none by its place in a list)."""

import importlib
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from perfbench import compare, harness, reference, traffic  # noqa: E402
from perfbench.metrics import layer_ms, part_ms  # noqa: E402

CELL = "kanana2_ep16.gtopk"
CONFIG = "kanana2_30b_a3b_ep16"
TRAFFIC = "gtopk_r001_s8192_b2_mla"
NEW = {"mla_attn_ms": ("ms", "lower"), "mla_kernel_ms": ("ms", "lower"),
       "mla_proj_ms": ("ms", "lower"), "mla_pointwise_ms": ("ms", "lower"),
       "mla_layout_ms": ("ms", "lower"), "mla_attn_roofline": ("%", "higher"),
       "mla_kernel_roofline": ("%", "higher")}
REDUCED = ["num_hidden_layers", "experts_held", "vocab_rows"]
# What the file's ``sizes`` holds beside the published keys: the cuts, the
# deployment's numbers and the one assumed rate.
CUT = ("experts_held", "expert_offset", "expert_parallel", "vocab_rows",
       "seq_len", "load_balance_coeff")
SPARSE_LIMITS = {
    "window_compiles", "nonfinite_losses", "loss_gap_1_3", "support_recall_1",
    "support_recall_2", "value_gap_1", "value_gap_2", "dparam_gap_3",
    "loss_ratio"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTH = re.compile(r"(_dim|_rank|_size|intermediate|head_dim|per_tok)")
MS = 1_000_000


def tiny_cell():
    """``perfbench_tiny.tiny_cell`` shrinks the traffic; the model's sizes
    are shrunk here, to the program's ``tiny`` preset, on both sides. In
    bfloat16 at 64 hidden units the two sides' first steps differ by
    rounding noise (their products round alike, their sums are taken in
    another order): ``value_gap_1`` 0.0021 to 0.0031 at seeds 3, 7 and 11
    where the control reads 0.026 to 0.028; the limit lies between."""
    from gtopkssgd_tpu.models.kanana2 import PRESETS

    cell = tiny.tiny_cell(CELL)
    cell.config["sizes"] = dict(PRESETS["tiny"])
    cell.config["input"].update(vocab_size=PRESETS["tiny"]["vocab_rows"],
                                bptt=PRESETS["tiny"]["seq_len"])
    cell.config["program"]["model_preset"] = "tiny"
    cell.traffic["density"] = 0.01
    cell.traffic["limits"].update(value_gap_1={"max": 0.01})
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
            "device_step_ms"} <= set(result["metrics"])
    # The CPU's trace carries no tf_op and the CPU has no peak: the kind,
    # its parts and the roofline shares find nothing to read and are left
    # out, as on a program without the scopes.
    assert not set(result["metrics"]) & set(NEW)
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


def test_the_harness_carries_the_bias_in_batch_stats():
    import jax
    import numpy as np

    cell = tiny_cell()
    pool = traffic.make_pool(cell.config, cell.traffic, 5)
    trainer = harness.build_trainer(cell, 5, pool)
    try:
        before = jax.tree.leaves(trainer.state.batch_stats)
        assert len(before) == 2 and not any(np.asarray(b).any() for b in before)
        harness.probe(trainer, 2)
        after = jax.tree.leaves(trainer.state.batch_stats)
        assert all(np.asarray(b).any() for b in after)
    finally:
        trainer.close()


# ------------------------------------------------ the kind and its parts
ROOT = "jit(gtopk_train_step)/gtopk/fwd_bwd/"
PATHS = {
    # Recorded form of a tf_op path: scopes nest, the innermost counts.
    "fusion.1": ROOT + "jvp(Kanana2)/layer_1/layer/attn_latent/mixer/"
                "part/kernel/flash_attention_forward/pallas_call",
    "fusion.2": ROOT + "transpose(jvp(Kanana2))/layer_1/layer/attn_latent/"
                "mixer/part/kernel/flash_attention_backward_kv/pallas_call",
    "fusion.3": ROOT + "jvp(Kanana2)/layer_1/layer/attn_latent/mixer/"
                "part/proj/dot_general",
    "fusion.4": ROOT + "transpose(jvp(Kanana2))/rematted_computation/layer_1/"
                "layer/attn_latent/part/pointwise/rsqrt",
    "fusion.5": ROOT + "jvp(Kanana2)/layer_1/layer/attn_latent/mixer/"
                "part/layout/concatenate",
    "fusion.9": ROOT + "checkpoint/layer_0/layer/dense_mlp/mlp/dot_general",
    "fusion.20": ROOT + "layer_3/layer/moe_router/moe/layer/moe_router/top_k",
    "multiply_add_fusion.6": "jit(gtopk_train_step)/gtopk/apply/add",
}
SPANS = {"fusion.1": 3 * MS, "fusion.2": 5 * MS, "fusion.3": 2 * MS,
         "fusion.4": MS, "fusion.5": MS, "fusion.9": MS, "fusion.20": MS,
         "multiply_add_fusion.6": MS // 2}


def made_up():
    """One chip, two steps: the mixer 12 ms a step (kernels 8, projections
    2, a replayed norm 1, a join 1), back to back with the rest."""
    devices, modules = [], []
    for k in range(2):
        t = k * 20 * MS
        modules.append(["jit_gtopk_train_step(5)", t, 19 * MS])
        for op, dur in SPANS.items():
            devices.append([op, t, dur])
            t += dur
    events = {"devices": {0: devices}, "modules": {0: modules}, "async": {},
              "spans": []}
    return {"events": events, "steps": 2, "chips": 1, "peaks": None,
            "layer_kinds": {op: layer_ms.kind_of(p) for op, p in PATHS.items()},
            "parts": {op: (layer_ms.kind_of(p), part_ms.part_of(p),
                           part_ms.pass_of(p)) for op, p in PATHS.items()}}


def reader_args(name):
    with open(os.path.join(harness.ROOT, "perfbench", "metrics",
                           name + ".json")) as fh:
        reader = json.load(fh)["reader"]
    return importlib.import_module(
        f"perfbench.metrics.{reader['module']}").read, reader["args"]


def test_the_seven_readers_on_recorded_paths_and_on_a_program_without_them():
    cell = harness.load_cell(CELL)
    ctx = dict(made_up(), config=cell.config)
    assert layer_ms.kind_of(PATHS["fusion.2"]) == "attn_latent"
    assert part_ms.part_of(PATHS["fusion.4"]) == "pointwise"
    assert part_ms.pass_of(PATHS["fusion.4"]) == "replay"
    want = {"mla_attn_ms": 12.0, "mla_kernel_ms": 8.0, "mla_proj_ms": 2.0,
            "mla_pointwise_ms": 1.0, "mla_layout_ms": 1.0}
    for name, ms in want.items():
        read, args = reader_args(name)
        assert read(ctx, **args) == pytest.approx(ms), name
    for name in ("mla_attn_roofline", "mla_kernel_roofline"):
        read, args = reader_args(name)
        assert read(ctx, **args) is None            # no peak on a CPU
    ctx["peaks"] = harness.peaks_for("TPU v5 lite")
    # 3 passes x 2 operations x 5 layers x 2 sequences x MACs at the bf16
    # peak over the made-up 12 and 8 ms: the readers divide; a run cannot
    # pass 100.
    pairs = 33_558_528 * 10_240
    read, args = reader_args("mla_attn_roofline")
    assert read(ctx, **args) == pytest.approx(
        100 * 60 * (8192 * 26_345_472 + pairs) / 197e12 * 1e3 / 12.0)
    read, args = reader_args("mla_kernel_roofline")
    assert args["parts"] == ["kernel"] and args["kinds"] == ["attn_latent"]
    assert read(ctx, **args) == pytest.approx(
        100 * 60 * pairs / 197e12 * 1e3 / 8.0)
    # A program without the scopes (the parent, or one that never ran this
    # model), and another decoder's configuration: nothing to read.
    bare = dict(ctx, layer_kinds={op: "" for op in PATHS},
                parts={op: ("", "", "forward") for op in PATHS})
    for name in NEW:
        read, args = reader_args(name)
        assert read(bare, **args) is None, name
    other = dict(ctx, config=harness.load_cell(
        "trinity_mini_ep16.gtopk").config)
    for name in ("mla_attn_roofline", "mla_kernel_roofline"):
        read, args = reader_args(name)
        assert read(other, **args) is None
    # The other decoders' kinds are not this one.
    assert layer_ms.read(ctx, ["attn_full", "attn_window", "attn"]) == 0.0


def test_work_counts_the_models_mathematics():
    cfg = harness.load_cell(CELL).config
    ref = importlib.import_module(f"perfbench.refmodels.{cfg['reference_model']}")
    sizes = cfg["sizes"]
    assert ref.causal_pairs(sizes) == 8192 * 8193 // 2 == 33_558_528
    assert ref.layer_counts(sizes) == (1, 4)
    assert ref._projection_macs(sizes) == 26_345_472
    assert ref._pair_macs(sizes) == 32 * (192 + 128) == 10_240
    per_token = 5 * 26_345_472 + 37_748_736 + 4 * 9_437_184 \
        + 4 * 6 * 8 * 4_718_592 // 128 + 4 * 262_144 + 16_032 * 2_048
    assert per_token == 248_184_832                   # ISSUE 39's count
    macs = 8192 * per_token + 5 * 33_558_528 * 10_240
    assert ref.forward_macs(sizes) == macs == 3_751_326_777_344
    assert cfg["flops_per_sample"]["train"] == 6 * macs
    ops, moved = ref.mla_attn_work(sizes, 2)
    assert ops == 60 * (8192 * 26_345_472 + 33_558_528 * 10_240)
    assert ref.mla_attn_work(sizes, 1)[0] * 2 == ops
    kernel_ops, kernel_moved = ref.mla_kernel_work(sizes, 2)
    assert kernel_ops == 60 * 33_558_528 * 10_240
    # Operations bound both: at the chip's peaks the least bytes take less.
    for ops_, moved_ in ((ops, moved), (kernel_ops, kernel_moved)):
        assert ops_ / 197e12 > moved_ / 819e9 > 0
    # The mixer is three quarters of the step's mathematics.
    assert 0.74 < ops / 2 / (6 * macs) < 0.76


# --------------------------------------------------- the files themselves
def test_sizes_agree_with_the_programs_preset_and_the_catalog():
    from gtopkssgd_tpu.models.kanana2 import PRESETS

    cell = harness.load_cell(CELL)
    cfg, preset = cell.config, PRESETS["30b_a3b_ep16"]
    assert cfg["program"]["model_preset"] == "30b_a3b_ep16"
    assert cfg["program"]["dnn"] == cfg["reference_model"] == "kanana2"
    assert {k: cfg["sizes"][k] for k in preset} == preset
    # Every key of the published config.json is in the file at the top
    # level, unchanged but for the depth; what else is cut has a key of its
    # own beside the published count.
    published = {k: v for k, v in cfg["sizes"].items() if k not in CUT}
    assert {k: cfg[k] for k in published} == published
    assert len(published) == 34
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["qk_head_dim"], cfg["v_head_dim"],
            cfg["head_dim"]) == (512, 128, 64, 192, 128, 64)
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    assert cfg["rope_interleave"] is True and cfg["rope_theta"] == 1000000
    assert cfg["num_attention_heads"] == cfg["num_key_value_heads"] == 32
    assert cfg["hidden_size"] == 2048 and cfg["intermediate_size"] == 6144
    assert cfg["n_routed_experts"] == 128 and cfg["num_experts_per_tok"] == 6
    assert cfg["moe_intermediate_size"] == 768 and cfg["n_shared_experts"] == 2
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"] is True
    assert cfg["topk_method"] == "noaux_tc"
    assert cfg["routed_scaling_factor"] == 2.448
    assert cfg["n_group"] == cfg["topk_group"] == cfg["moe_layer_freq"] == 1
    assert cfg["vocab_size"] == 128256 and cfg["model_type"] == "deepseek_v3"
    assert cfg["tie_word_embeddings"] is False and cfg["rms_norm_eps"] == 1e-6
    assert cfg["attention_bias"] is False
    assert cfg["max_position_embeddings"] == 32768
    assert cfg["reduced"] == REDUCED
    assert cfg["experts_held"] * cfg["sizes"]["expert_parallel"] \
        == cfg["n_routed_experts"]
    assert cfg["vocab_rows"] * 8 == cfg["vocab_size"]
    assert "16 chips" in cfg["deployment"] and "43 layers" in cfg["deployment"]
    assert any("batch_stats" in a for a in cfg["assumed"])
    assert any("arXiv:2412.19437" in a and "0.001" in a for a in cfg["assumed"])
    assert any("not AdamW" in a for a in cfg["assumed"])
    assert "768 tokens" in cfg["cut"]["tokens_per_expert"]
    assert cfg["input"]["vocab_size"] == cfg["vocab_rows"]
    assert cfg["input"]["bptt"] == cfg["sizes"]["seq_len"] == 8192
    assert cfg["input"]["follow"] == 0.5 and cfg["input"]["kind"] == "tokens"
    assert cfg["parameters"] == 424_960_512     # counted in test_kanana2.py
    tr = cell.traffic
    assert tr["name"] == TRAFFIC
    assert (tr["batch_size"], tr["density"], tr["compression"]) \
        == (2, 0.001, "gtopk")
    assert (tr["pool_batches"], tr["probe_steps"], tr["ratio_steps"],
            tr["chunk_steps"], tr["trace_steps"]) == (32, 32, [25, 32], 16, 4)


def test_entries_keep_the_contracts_letter():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
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

    # Its seven metrics, in the order they were appended, listed for its
    # cell alone; and the cell in no other metric's list.
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert {m["name"]: (m["unit"], m["better"]) for m in mine} == NEW
    assert [m["name"] for m in mine] == list(NEW)
    assert all(m["workloads"] == [CELL] and m["moves"] == "throughput"
               and m["layer"] == "decoder layer kinds"
               and m["source"] == "device_trace" for m in mine)
    assert not any(CELL in m.get("workloads", [])
                   for m in bench["per_layer"] if m["name"] not in NEW)
    # The benchmark had seven cells and five configurations before it.
    assert len(bench["workloads"]) >= 8 and len(bench["configs"]) >= 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1

    limits = harness.load_cell(CELL).traffic["limits"]
    assert set(limits) == SPARSE_LIMITS
    assert all("why" in v and "PLACEHOLDER" not in v["why"]
               for v in limits.values())
    # Every limit the control is held to says both readings.
    for name in ("value_gap_1", "support_recall_1", "support_recall_2",
                 "value_gap_2", "dparam_gap_3", "loss_gap_1_3", "loss_ratio"):
        assert "sound" in limits[name]["why"] \
            and "control" in limits[name]["why"], name


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.ROOT, "perfbench", "refmodels", "kanana2.py")
    with open(path) as fh:
        source = fh.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert imports and not any(m.startswith(("gtopkssgd_tpu", "perfbench"))
                               for m in imports)
