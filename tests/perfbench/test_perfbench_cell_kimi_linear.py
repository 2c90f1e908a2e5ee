"""kimi_linear_ep32.gtopk on the CPU at the model's ``tiny`` preset: a traced
whole run (``correct``, the schema, ``kda_log_decay_min`` and
``kda_moe_load_imbalance`` from the program's own counters), the control, the
eleven readers on recorded paths and on a program without their scopes or
counter, the work functions, and the configuration's files against the
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
from test_perfbench_cell_kanana2 import (  # noqa: E402
    MS, NAME, SPARSE_LIMITS, WIDTH, reader_args)

CELL = "kimi_linear_ep32.gtopk"
CONFIG = "kimi_linear_48b_a3b_ep32"
TRAFFIC = "gtopk_r001_s8192_b1_kda"
NEW = {"kda_proj_ms": ("ms", "lower"), "kda_scan_ms": ("ms", "lower"),
       "kda_scan_roofline": ("%", "higher"), "kda_mla_ms": ("ms", "lower"),
       "kda_mla_roofline": ("%", "higher"),
       "kda_moe_route_ms": ("ms", "lower"),
       "kda_moe_expert_ms": ("ms", "lower"), "kda_head_ms": ("ms", "lower"),
       "kda_replay_ms": ("ms", "lower"),
       "kda_moe_load_imbalance": ("ratio", "lower"),
       "kda_log_decay_min": ("nats", "higher")}
COUNTER_READ = ("kda_moe_load_imbalance", "kda_log_decay_min")
TRACE_READ = [name for name in NEW if name not in COUNTER_READ]
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "experts_held",
           "vocab_rows"]
# What the file's ``sizes`` holds beside the published keys: the cuts, the
# deployment's numbers, the published group's sizes under flat names, the
# held layers' kinds and the two assumed sizes.
CUT = ("experts_held", "expert_offset", "expert_parallel", "vocab_rows",
       "seq_len", "load_balance_coeff", "layer_kinds", "kda_num_heads",
       "kda_head_dim", "kda_conv_kernel_size", "kda_gate_rank")


def tiny_cell():
    """``perfbench_tiny.tiny_cell`` shrinks the traffic; the model's sizes
    are shrunk here, to the program's ``tiny`` preset, on both sides. In
    bfloat16 at 64 hidden units the two sides' first steps differ by
    rounding noise; the limit lies between that and the control's."""
    from gtopkssgd_tpu.models.kimi_linear import PRESETS

    cell = tiny.tiny_cell(CELL)
    cell.config["sizes"] = dict(PRESETS["tiny"])
    cell.config["input"].update(vocab_size=PRESETS["tiny"]["vocab_rows"],
                                bptt=PRESETS["tiny"]["seq_len"])
    cell.config["program"]["model_preset"] = "tiny"
    cell.traffic["density"] = 0.01
    cell.traffic["limits"].update(value_gap_1={"max": 0.01})
    return cell


def test_traced_run_is_correct_and_reads_the_counters():
    """The cell through ``harness.run_cell`` with the profiler on: every
    limit printed and kept, the line's schema, what a CPU trace can give,
    and the two counter-read metrics from the last step's counters."""
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
    # The CPU's trace carries no tf_op and the CPU has no peak: the kinds
    # and the roofline shares find nothing to read.
    assert not set(result["metrics"]) & set(TRACE_READ)
    assert "mfu" not in result["metrics"]
    last = counters.last_model_scalars()
    fallen = result["metrics"]["kda_log_decay_min"]
    assert fallen["unit"] == "nats" and fallen["value"] < 0
    assert fallen["value"] == last["kda_log_decay_min"]
    uneven = result["metrics"]["kda_moe_load_imbalance"]
    assert uneven["unit"] == "ratio" and uneven["value"] >= 1.0
    assert uneven["value"] == last["moe_load_max"] / last["moe_load_mean"]
    # The harness carried the biases in ``batch_stats`` (four expert layers).
    assert last["moe_bias_absmax"] > 0


def test_log_decay_reader_on_a_program_without_the_counter(monkeypatch):
    from gtopkssgd_tpu.obs import counters
    from perfbench.metrics import kda_log_decay_min

    monkeypatch.setattr(counters, "_last_model", {"moe_load_max": 3.0})
    assert kda_log_decay_min.read({}) is None
    monkeypatch.setattr(counters, "_last_model", {"kda_log_decay_min": -91.5})
    assert kda_log_decay_min.read({}) == -91.5
    monkeypatch.delattr(counters, "last_model_scalars")
    assert kda_log_decay_min.read({}) is None


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


# ------------------------------------------------------------- the kinds
FORWARD = "jit(gtopk_train_step)/gtopk/fwd_bwd/jvp(KimiLinear)/"
BACKWARD = "jit(gtopk_train_step)/gtopk/fwd_bwd/transpose(jvp(KimiLinear))/"
REPLAY = BACKWARD + "layer_1/layer_1/checkpoint/rematted_computation/Layer/"
PATHS = {
    # As the published step compiled for a described v5e names them: scopes
    # nest, the innermost counts.
    "fusion.1": FORWARD + "layer_0/Layer/layer/kda_proj/mixer/layer/kda_proj/"
                "dot_general",
    "fusion.2": FORWARD + "layer_0/Layer/layer/kda_proj/mixer/layer/kda_scan/"
                "while/body/checkpoint/triangular_solve",
    "fusion.3": BACKWARD + "layer_1/layer_1/checkpoint/Layer/layer/kda_proj/"
                "mixer/layer/kda_scan/while/body/dot_general",
    "fusion.4": REPLAY + "layer/kda_proj/mixer/layer/kda_proj/jit(forward)/"
                "gdn_conv_forward/pallas_call",
    "fusion.5": FORWARD + "layer_3/Layer/layer/attn_latent/mixer/part/kernel/"
                "flash_attention_forward/pallas_call",
    "fusion.6": BACKWARD + "layer_3/layer_3/checkpoint/Layer/layer/"
                "attn_latent/mixer/part/proj/dot_general",
    "fusion.9": REPLAY + "layer/moe_router/moe/layer/moe_router/top_k",
    "fusion.10": FORWARD + "layer_0/Layer/layer/moe_router/moe/while/body/"
                 "layer/moe_experts/ragged_dot",
    "fusion.20": FORWARD + "layer/head/dot_general",
    "multiply_add_fusion.6": "jit(gtopk_train_step)/gtopk/apply/add",
}
SPANS = {"fusion.1": 3 * MS, "fusion.2": 6 * MS, "fusion.3": 10 * MS,
         "fusion.4": MS, "fusion.5": 2 * MS, "fusion.6": 2 * MS,
         "fusion.9": 2 * MS, "fusion.10": 4 * MS, "fusion.20": 5 * MS,
         "multiply_add_fusion.6": MS // 2}


def made_up():
    """One chip, two steps: the KDA projections 4 ms a step (1 replayed),
    its rule 16, the latent mixer 4, the router 2 (replayed), the experts
    4, the head 5, back to back with the rest."""
    devices, modules = [], []
    for k in range(2):
        t = k * 40 * MS
        modules.append(["jit_gtopk_train_step(5)", t, 39 * MS])
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
    assert layer_ms.kind_of(PATHS["fusion.2"]) == "kda_scan"
    assert layer_ms.kind_of(PATHS["fusion.4"]) == "kda_proj"
    assert part_ms.pass_of(PATHS["fusion.4"]) == "replay"
    want = {"kda_proj_ms": 4.0, "kda_scan_ms": 16.0, "kda_mla_ms": 4.0,
            "kda_moe_route_ms": 2.0, "kda_moe_expert_ms": 4.0,
            "kda_head_ms": 5.0, "kda_replay_ms": 3.0}
    for name, ms in want.items():
        read, args = reader_args(name)
        assert read(ctx, **args) == pytest.approx(ms), name
    for name in ("kda_scan_roofline", "kda_mla_roofline"):
        read, args = reader_args(name)
        assert read(ctx, **args) is None            # no peak on a CPU
    ctx["peaks"] = harness.peaks_for("TPU v5 lite")
    # The rule's least bytes bound it: 3 passes x 4 B x 3 layers x 8,192
    # tokens x 32 heads x (q, k, g, v, o of 128 and beta) over the made-up
    # 16 ms; the latent mixer's operations at the bf16 peak over 4 ms.
    read, args = reader_args("kda_scan_roofline")
    assert args == {"work": "kda_scan_work", "kinds": ["kda_scan"]}
    moved = 12 * 3 * 8192 * 32 * (5 * 128 + 1)
    assert read(ctx, **args) == pytest.approx(
        100 * moved / 819e9 * 1e3 / 16.0)
    read, args = reader_args("kda_mla_roofline")
    assert args == {"work": "mla_attn_work", "kinds": ["attn_latent"]}
    assert read(ctx, **args) == pytest.approx(
        100 * 6 * (8192 * 29_114_368 + 33_558_528 * 10_240) / 197e12 * 1e3
        / 4.0)
    # A program without the scopes (the parent, or one that never ran this
    # model), and another decoder's configuration: nothing to read.
    bare = dict(ctx, layer_kinds={op: "" for op in PATHS},
                parts={op: ("", "", "forward") for op in PATHS})
    for name in TRACE_READ:
        read, args = reader_args(name)
        assert read(bare, **args) is None, name
    other = dict(ctx, config=harness.load_cell("kanana2_ep16.gtopk").config)
    read, args = reader_args("kda_scan_roofline")
    assert read(other, **args) is None
    # The other decoders' kinds are not this one's.
    assert layer_ms.read(ctx, ["gdn_scan", "gdn_proj", "attn"]) == 0.0


def test_work_counts_the_models_mathematics():
    cfg = harness.load_cell(CELL).config
    ref = importlib.import_module(f"perfbench.refmodels.{cfg['reference_model']}")
    sizes = cfg["sizes"]
    assert ref.causal_pairs(sizes) == 8192 * 8193 // 2 == 33_558_528
    assert ref.layer_counts(sizes) == (3, 1)
    assert ref._kda_projection_macs(sizes) == 39_460_864
    assert ref._mla_projection_macs(sizes) == 29_114_368
    assert ref._pair_macs(sizes) == 32 * (192 + 128) == 10_240
    rule = 3 * 32 * 128 * 128
    moe = 2304 * 256 + 3 * 2304 * 1024 + 8 * 8 * 3 * 2304 * 1024 // 256
    assert moe == 9_437_184
    per_token = 3 * (39_460_864 + rule) + 29_114_368 + 4 * moe + 2304 * 20480
    macs = 8192 * per_token + 33_558_528 * 10_240
    assert ref.forward_macs(sizes) == macs == 2_286_373_830_656
    assert cfg["flops_per_sample"]["forward_macs"] == macs
    assert cfg["flops_per_sample"]["train"] == 6 * macs      # 13.7 TFLOP
    ops, moved = ref.kda_scan_work(sizes, 1)
    per_chunk = 2 * 64 * 64 * 128 + 64 * 64 * 256 + 3 * 64 * 128 * 128 \
        + 64 * 64 * 128
    assert ops == 6 * 3 * 32 * 128 * per_chunk
    assert ref.kda_scan_work(sizes, 2) == (2 * ops, 2 * moved)
    # The least bytes bound the rule at the chip's peaks; operations the
    # latent mixer.
    assert moved / 819e9 > ops / 197e12 > 0
    ops, moved = ref.mla_attn_work(sizes, 1)
    assert ops == 6 * (8192 * 29_114_368 + 33_558_528 * 10_240)
    assert ops / 197e12 > moved / 819e9 > 0


# --------------------------------------------------- the files themselves
def test_sizes_agree_with_the_programs_preset_and_the_catalog():
    from gtopkssgd_tpu.models.kimi_linear import PRESETS

    cell = harness.load_cell(CELL)
    cfg, preset = cell.config, PRESETS["48b_a3b_ep32"]
    assert cfg["program"]["model_preset"] == "48b_a3b_ep32"
    assert cfg["program"]["dnn"] == cfg["reference_model"] == "kimi_linear"
    assert {k: cfg["sizes"][k] for k in preset} == preset
    # Every key of the published config.json is in the file at the top
    # level, unchanged but for the two cuts of depth; what else is cut has a
    # key of its own beside the published count.
    published = {k: v for k, v in cfg["sizes"].items() if k not in CUT}
    assert {k: cfg[k] for k in published} == published
    assert len(published) == 34
    assert cfg["num_hidden_layers"] == 4 and cfg["first_k_dense_replace"] == 0
    group = cfg["linear_attn_config"]
    assert group["kda_layers"][3:6] == [5, 6, 7]
    assert group["full_attn_layers"][1] == 8 and len(group["kda_layers"]) == 20
    assert (group["num_heads"], group["head_dim"],
            group["short_conv_kernel_size"]) == (
        cfg["sizes"]["kda_num_heads"], cfg["sizes"]["kda_head_dim"],
        cfg["sizes"]["kda_conv_kernel_size"]) == (32, 128, 4)
    assert cfg["sizes"]["kda_gate_rank"] == group["head_dim"]
    assert cfg["sizes"]["layer_kinds"] == "kda,kda,kda,mla"
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["head_dim"]) \
        == (512, 128, 64, 128, 72)
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    assert cfg["mla_use_nope"] is True and cfg["rope_theta"] == 10000
    assert cfg["num_attention_heads"] == cfg["num_key_value_heads"] == 32
    assert cfg["hidden_size"] == 2304 and cfg["intermediate_size"] == 9216
    assert cfg["num_experts"] == 256 and cfg["num_experts_per_token"] == 8
    assert cfg["moe_intermediate_size"] == 1024
    assert cfg["num_shared_experts"] == 1
    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert cfg["moe_renormalize"] is True and cfg["use_grouped_topk"] is True
    assert cfg["routed_scaling_factor"] == 2.446
    assert cfg["num_expert_group"] == cfg["topk_group"] == 1
    assert cfg["moe_layer_freq"] == 1 and cfg["num_nextn_predict_layers"] == 0
    assert cfg["vocab_size"] == 163840 and cfg["model_type"] == "kimi_linear"
    assert cfg["tie_word_embeddings"] is False and cfg["rms_norm_eps"] == 1e-5
    assert cfg["model_max_length"] == 1048576 and cfg["hidden_act"] == "silu"
    assert cfg["reduced"] == REDUCED
    assert cfg["experts_held"] * cfg["sizes"]["expert_parallel"] \
        == cfg["num_experts"]
    assert cfg["vocab_rows"] * 8 == cfg["vocab_size"]
    assert "32 chips" in cfg["deployment"] and "5-8" in cfg["deployment"]
    assert "602.4M" in cfg["cut"]["first_k_dense_replace"]
    assert any("batch_stats" in a for a in cfg["assumed"])
    assert any("arXiv:2412.19437" in a and "0.001" in a for a in cfg["assumed"])
    assert any("not the AdamW" in a for a in cfg["assumed"])
    assert any("in_proj_fzb" in a and "one leaf" in a for a in cfg["assumed"])
    assert any("kda_gate_rank" in a for a in cfg["assumed"])
    assert "256 tokens" in cfg["cut"]["tokens_per_expert"]
    assert "delta rule's state, decay and chunk algebra" in cfg["precisions"]
    assert cfg["input"]["vocab_size"] == cfg["vocab_rows"]
    assert cfg["input"]["bptt"] == cfg["sizes"]["seq_len"] == 8192
    assert cfg["input"]["follow"] == 0.5 and cfg["input"]["kind"] == "tokens"
    assert cfg["parameters"] == 499_213_536  # counted in test_kimi_linear.py
    tr = cell.traffic
    assert tr["name"] == TRAFFIC
    assert (tr["batch_size"], tr["density"], tr["compression"]) \
        == (1, 0.001, "gtopk")
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
    assert "256 tokens" in cell["why"] and "PLACEHOLDER" not in cell["why"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}

    # Its eleven metrics, in the order they were appended, listed for its
    # cell alone; and the cell in no other metric's list.
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert {m["name"]: (m["unit"], m["better"]) for m in mine} == NEW
    assert [m["name"] for m in mine] == list(NEW)
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    assert all(m["workloads"] == [CELL] and m["moves"] == "throughput"
               and m["layer"] == "decoder layer kinds" for m in mine)
    assert {m["name"] for m in mine if m["source"] == "program_counter"} \
        == set(COUNTER_READ)
    assert all(m["source"] == "device_trace" for m in mine
               if m["name"] in TRACE_READ)
    assert not any(CELL in m.get("workloads", [])
                   for m in bench["per_layer"] if m["name"] not in NEW)
    for name in NEW:
        with open(os.path.join(harness.ROOT, "perfbench", "metrics",
                               name + ".json")) as fh:
            spec = json.load(fh)
        assert spec["name"] == name and spec["cells"] == CELL
    # Eleven cells, nine configurations, one cell on four chips.
    assert len(bench["workloads"]) >= 11 and len(bench["configs"]) >= 9
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
    path = os.path.join(harness.ROOT, "perfbench", "refmodels",
                        "kimi_linear.py")
    with open(path) as fh:
        source = fh.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert imports and not any(m.startswith(("gtopkssgd_tpu", "perfbench"))
                               for m in imports)
    # The recurrence is token by token, in sums and products.
    rule = source[source.index("def delta_rule"):source.index(
        "class KimiDeltaAttention")]
    assert "lax.scan(token" in rule and "einsum" not in rule \
        and "jnp.dot" not in rule and "matmul" not in rule
