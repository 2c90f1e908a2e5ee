"""trinity_mini_ep16.gtopk on the CPU at the model's ``tiny`` preset: a
whole run, a traced run, the control, a run whose timed path is broken
underneath, the new readers on a program without their scopes or counters,
the work functions, ``perfbench/control.py`` rehearsed, and the
configuration's files against the program's published preset and the
catalog's keys (the contract's letter: ``test_perfbench_entries_by_name.py``)."""

import importlib
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from perfbench import compare, control, harness, reference, traffic  # noqa: E402
from perfbench.metrics import layer_ms  # noqa: E402

CELL = "trinity_mini_ep16.gtopk"
CONFIG = "trinity_mini_26b_a3b_ep16"
NEW = ["swa_attn_ms", "full_attn_ms", "swa_attn_roofline",
       "full_attn_roofline", "dense_mlp_ms", "moe_route_imbalance"]
REDUCED = ["num_hidden_layers", "num_dense_layers", "experts_held",
           "vocab_rows"]
CUT = ("layer_kinds", "experts_held", "expert_offset", "expert_parallel",
       "vocab_rows", "seq_len")
MS = 1_000_000


def tiny_cell():
    """``perfbench_tiny.tiny_cell`` shrinks the traffic; the model's sizes
    are shrunk here, to the program's ``tiny`` preset, on both sides. In
    bfloat16 at 64 hidden units the two sides' first steps differ by
    rounding noise (their products round alike, their sums are taken in
    another order): ``value_gap_1`` 0.0148 and 0.0166 at seeds 3 and 7
    where the control reads 0.0464 and 0.0406; the limit lies between, the
    second step's is left wide (0.051 and 0.042 against 0.082 and 0.072)."""
    from gtopkssgd_tpu.models.trinity_mini import PRESETS

    cell = tiny.tiny_cell(CELL)
    cell.config["sizes"] = dict(PRESETS["tiny"])
    cell.config["input"].update(vocab_size=PRESETS["tiny"]["vocab_rows"],
                                bptt=PRESETS["tiny"]["seq_len"])
    cell.config["program"]["model_preset"] = "tiny"
    cell.traffic["density"] = 0.01
    cell.traffic["limits"].update(value_gap_1={"max": 0.03},
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
            "device_step_ms", "moe_route_imbalance"} <= set(result["metrics"])
    # The fullest of the 16 experts over the mean: at least 1, and at this
    # size far from all the tokens on one expert (4 = 16 / top 4).
    assert 1.0 <= result["metrics"]["moe_route_imbalance"]["value"] < 4.0
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
    # ``perfbench/control.py`` reads the same numbers where two whole
    # trainings and ``compare.numbers`` do not fit the host (N = 504M).
    light = control.numbers(low, ref, cell.config, tr)
    assert set(light) == set(values)
    for name, value in values.items():
        assert light[name] == pytest.approx(value, rel=1e-5, abs=1e-9), name


def test_control_script_rehearsed_on_the_cpu(monkeypatch, capsys):
    """The chip command of ``perfbench/control.py`` end to end at the tiny
    sizes: the program's sent density, one sound reading that keeps the
    limits, the control that does not."""
    cell = tiny_cell()
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    assert control.main(["--workload", CELL, "--seeds", "3",
                         "--program", "1"]) == 0
    lines = capsys.readouterr().out.splitlines()
    rows = [json.loads(line) for line in lines if line.startswith("{")]
    sent = next(r for r in rows if "achieved_density" in r)["achieved_density"]
    assert list(sent) == ["1", "2", "3"] and all(
        0.01 <= v < 0.05 for v in sent.values())
    sound = next(r for r in rows if "sound" in r)["sound"]
    assert compare.decide(sound, {k: v for k, v in cell.traffic[
        "limits"].items() if k in sound}, lambda line: None)
    assert rows[-1] == {"control_not_correct_on_every_seed": True}
    assert any(line.startswith("compare value_gap_1") and "FAILED" in line
               for line in lines)


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


def test_the_harness_carries_the_bias_in_batch_stats():
    """``adopt_weights`` hands the trainer the reference's model state under
    the one name both sides carry, and a probe's steps move it."""
    import jax
    import numpy as np

    cell = tiny_cell()
    pool = traffic.make_pool(cell.config, cell.traffic, 5)
    trainer = harness.build_trainer(cell, 5, pool)
    try:
        before = jax.tree.leaves(trainer.state.batch_stats)
        assert len(before) == 4 and not any(np.asarray(b).any() for b in before)
        harness.probe(trainer, 2)
        after = jax.tree.leaves(trainer.state.batch_stats)
        assert all(np.asarray(b).any() for b in after)
    finally:
        trainer.close()


# ------------------------------------------------------- the layer kinds
PATHS = {
    # Recorded form of a tf_op path: scopes nest, the innermost counts.
    "fusion.3": "jit(gtopk_train_step)/gtopk/fwd_bwd/checkpoint/TrinityMini/"
                "layer_1/layer/attn_window/mixer/while/body/checkpoint/"
                "dot_general",
    "fusion.7": "jit(gtopk_train_step)/gtopk/fwd_bwd/transpose(jvp(layer_4))/"
                "layer/attn_full/mixer/checkpoint/exp",
    "fusion.9": "jit(gtopk_train_step)/gtopk/fwd_bwd/checkpoint/layer_0/"
                "layer/dense_mlp/mlp/dot_general",
    "fusion.20": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer_3/layer/moe_router/"
                 "moe/layer/moe_router/top_k",
    "fusion.21": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer_3/layer/moe_router/"
                 "moe/layer/shared_expert/dot_general",
    "fusion.50": "jit(gtopk_train_step)/gtopk/fwd_bwd/layer/head/reduce_max",
    "multiply_add_fusion.6": "jit(gtopk_train_step)/gtopk/apply/add",
}


def test_kind_of_reads_the_new_scopes_innermost():
    assert {op: layer_ms.kind_of(path) for op, path in PATHS.items()} == {
        "fusion.3": "attn_window", "fusion.7": "attn_full",
        "fusion.9": "dense_mlp", "fusion.20": "moe_router",
        "fusion.21": "shared_expert", "fusion.50": "head",
        "multiply_add_fusion.6": ""}


def made_up():
    """One chip, two steps of 10 ms: the sliding layers 4 ms a step, the
    full layer 2 ms, the dense feed-forward 1 ms, back to back."""
    spans = {"fusion.3": 4 * MS, "fusion.7": 2 * MS, "fusion.9": MS,
             "fusion.20": MS // 2, "fusion.21": MS // 2, "fusion.50": MS // 2,
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
    from perfbench.metrics import moe_route_imbalance, work_roofline

    monkeypatch.setattr(counters, "_last_model", {"moe_load_mean": 3.0})
    assert moe_route_imbalance.read({}) is None
    monkeypatch.setattr(counters, "_last_model",
                        {"moe_count_max": 1500.0, "moe_count_mean": 1024.0})
    assert moe_route_imbalance.read({}) == pytest.approx(1500 / 1024)
    monkeypatch.delattr(counters, "last_model_scalars")
    assert moe_route_imbalance.read({}) is None

    cell = harness.load_cell(CELL)
    ctx = dict(made_up(), config=cell.config)
    assert layer_ms.read(ctx, ["attn_window"]) == pytest.approx(4.0)
    assert layer_ms.read(ctx, ["attn_full"]) == pytest.approx(2.0)
    assert layer_ms.read(ctx, ["dense_mlp"]) == pytest.approx(1.0)
    swa = dict(work="swa_attn_work", kinds=["attn_window"])
    full = dict(work="full_attn_work", kinds=["attn_full"])
    assert work_roofline.read(ctx, **swa) is None            # no peak on a CPU
    ctx["peaks"] = harness.peaks_for("TPU v5 lite")
    # 3 passes x 2 operations x layers x (16,384 x 27,262,976 + pairs x
    # 8,192) MACs at the bf16 peak over the made-up 4 and 2 ms: the reader
    # divides; a run cannot pass 100.
    assert work_roofline.read(ctx, **swa) == pytest.approx(
        100 * 6 * 4 * (16384 * 27262976 + 31458304 * 8192) / 197e12 * 1e3 / 4.0)
    assert work_roofline.read(ctx, **full) == pytest.approx(
        100 * 6 * 1 * (16384 * 27262976 + 134225920 * 8192) / 197e12 * 1e3 / 2.0)
    bare = dict(ctx, layer_kinds={op: "" for op in PATHS})
    assert work_roofline.read(bare, **swa) is None
    assert layer_ms.read(bare, ["attn_full"]) is None
    # Another decoder's configuration counts no such work, and its own
    # scopes are not these.
    other = dict(ctx, config=harness.load_cell("keye_vl2_ep16.gtopk").config)
    assert work_roofline.read(other, **swa) is None
    assert layer_ms.read(ctx, ["attn"]) == 0.0


def test_work_counts_the_due_pairs_not_the_blocks_extents():
    cfg = harness.load_cell(CELL).config
    ref = importlib.import_module(f"perfbench.refmodels.{cfg['reference_model']}")
    sizes = cfg["sizes"]
    assert ref.window_pairs(sizes) == 2048 * 2049 // 2 + (16384 - 2048) * 2048 \
        == 31_458_304
    assert ref.causal_pairs(sizes) == 16384 * 16385 // 2 == 134_225_920
    # A sequence inside one window is plain causal attention.
    short = dict(sizes, seq_len=1024)
    assert ref.window_pairs(short) == ref.causal_pairs(short)
    assert ref.layer_kinds(sizes) == (4, 1, 1, 4)
    ops, _ = ref.swa_attn_work(sizes, 1)
    assert ops == 24 * (16384 * 27262976 + 31458304 * 8192)
    ops, _ = ref.full_attn_work(sizes, 1)
    assert ops == 6 * (16384 * 27262976 + 134225920 * 8192)
    assert ref.swa_attn_work(sizes, 2)[0] == 2 * ref.swa_attn_work(sizes, 1)[0]
    # Operations bound both: at the chip's peaks the least bytes take less.
    for work in (ref.swa_attn_work, ref.full_attn_work):
        ops, moved = work(sizes, 1)
        assert ops / 197e12 > moved / 819e9 > 0
    per_token = ref.forward_macs(sizes) / sizes["seq_len"]
    assert 394.0e6 < per_token < 394.3e6
    assert 38.6e12 < 6 * ref.forward_macs(sizes) < 38.9e12


# --------------------------------------------------- the files themselves
def test_sizes_agree_with_the_programs_preset_and_the_catalog():
    from gtopkssgd_tpu.models.trinity_mini import PRESETS

    cell = harness.load_cell(CELL)
    cfg, preset = cell.config, PRESETS["26b_a3b_ep16"]
    assert cfg["program"]["model_preset"] == "26b_a3b_ep16"
    assert cfg["program"]["dnn"] == cfg["reference_model"] == "trinity_mini"
    assert {k: cfg["sizes"][k] for k in preset} == preset
    # Every key of the published config.json is in the file at the top
    # level, unchanged but for the two depths (nested groups whole); what
    # else is cut has a key of its own beside the published count.
    published = {k: v for k, v in cfg["sizes"].items() if k not in CUT}
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 32 and cfg["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == cfg["layer_types"][:4] * 8
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert cfg["global_attn_every_n_layers"] == 4
    assert cfg["sliding_window"] == 2048 and cfg["rope_theta"] == 10000
    assert cfg["num_experts"] == 128 and cfg["num_experts_per_tok"] == 8
    assert cfg["moe_intermediate_size"] == 1024
    assert cfg["intermediate_size"] == 6144 and cfg["hidden_size"] == 2048
    assert cfg["score_func"] == "sigmoid" and cfg["route_scale"] == 2.826
    assert cfg["route_norm"] is True and cfg["mup_enabled"] is True
    assert cfg["load_balance_coeff"] == 0.001 and cfg["num_shared_experts"] == 1
    assert cfg["n_group"] == cfg["topk_group"] == cfg["num_limited_groups"] == 1
    assert cfg["vocab_size"] == 200192 and cfg["model_type"] == "afmoe"
    assert cfg["tie_word_embeddings"] is False and cfg["rope_scaling"] is None
    assert cfg["reduced"] == REDUCED
    assert cfg["experts_held"] * cfg["sizes"]["expert_parallel"] \
        == cfg["num_experts"]
    assert cfg["vocab_rows"] * 8 == cfg["vocab_size"]
    assert "16 chips" in cfg["deployment"]
    assert any("batch_stats" in a for a in cfg["assumed"])
    assert any("arXiv:2408.15664" in a for a in cfg["assumed"])
    assert cfg["input"]["vocab_size"] == cfg["vocab_rows"]
    assert cfg["input"]["bptt"] == cfg["sizes"]["seq_len"] == 16384
    assert cfg["parameters"] == 504_147_200    # counted in test_trinity_mini.py
    assert cell.traffic["batch_size"] == 1 and cell.traffic["density"] == 0.001
    assert cell.traffic["chunk_steps"] == 16 and cell.traffic["trace_steps"] == 4


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.ROOT, "perfbench", "refmodels",
                        "trinity_mini.py")
    with open(path) as fh:
        source = fh.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert imports and not any(m.startswith(("gtopkssgd_tpu", "perfbench"))
                               for m in imports)
