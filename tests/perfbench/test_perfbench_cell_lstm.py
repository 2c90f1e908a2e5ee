"""lstm_ptb.gtopk on the CPU at a tiny size: a whole run, the control, and a
run whose timed path is broken underneath."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from perfbench import compare, harness, reference, traffic  # noqa: E402


def test_whole_run_is_correct_and_its_line_has_the_schema():
    cell = tiny.tiny_cell("lstm_ptb.gtopk")
    result, lines = tiny.run(cell, traced=False)
    tiny.check_schema(cell, result, traced=False)
    assert result["correct"] is True, lines
    assert any(line.startswith("setup import=") for line in lines)
    assert any(line.startswith("reference steps=") for line in lines)
    # Each number compared is printed beside its limit.
    for name in cell.traffic["limits"]:
        assert any(line.startswith(f"compare {name} = ") and "limit [" in line
                   for line in lines)


def test_traced_run_reports_per_layer_metrics_and_a_breakdown():
    cell = tiny.tiny_cell("lstm_ptb.gtopk")
    result, lines = tiny.run(cell, traced=True)
    tiny.check_schema(cell, result, traced=True)
    assert {"io_ms", "dispatch_ms", "obs_read_ms", "device_idle",
            "device_step_ms"} <= set(result["metrics"])
    # No peak for a CPU: the reader finds nothing and the metric is left out.
    assert "mfu" not in result["metrics"]
    assert "comm_ms" not in result["metrics"]


def test_lower_precision_control_is_not_correct():
    """The reference with bfloat16 master weights, in the program's place."""
    cell = tiny.tiny_cell("lstm_ptb.gtopk")
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
    cell = tiny.tiny_cell("lstm_ptb.gtopk")
    result, lines = tiny.run(cell, traced=False)
    assert result["correct"] is False
    assert any("dparam_gap_3" in line and "FAILED" in line for line in lines)
