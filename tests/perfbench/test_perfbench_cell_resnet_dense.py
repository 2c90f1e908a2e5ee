"""resnet50.dense on the CPU at a tiny size: one whole run through the harness
(set-up, probe, window, reference, result line)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402


def test_whole_run_is_correct_and_its_line_has_the_schema():
    cell = tiny.tiny_cell("resnet50.dense")
    result, lines = tiny.run(cell, traced=True)
    tiny.check_schema(cell, result, traced=True)
    assert result["correct"] is True, lines


def test_lower_precision_control_is_not_correct_on_the_dense_cell():
    from perfbench import compare, reference, traffic

    cell = tiny.tiny_cell("resnet50.dense")
    tr = cell.traffic
    pool = traffic.make_pool(cell.config, tr, 5)
    ref = reference.train(cell.config, tr, 5, pool, tr["probe_steps"])
    low = reference.train(cell.config, tr, 5, pool, tr["probe_steps"],
                          master_bits=16)
    values = compare.numbers(low, ref, cell.config, tr)
    limits = {k: v for k, v in tr["limits"].items() if k in values}
    lines = []
    assert not compare.decide(values, limits, lines.append)
    assert any("value_gap_1" in line and "FAILED" in line for line in lines)
