"""resnet50.gtopk on the CPU at a tiny size: one whole run through the harness
(set-up, probe, window, reference, result line)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402


def test_whole_run_is_correct_and_its_line_has_the_schema():
    cell = tiny.tiny_cell("resnet50.gtopk")
    result, lines = tiny.run(cell, traced=False)
    tiny.check_schema(cell, result, traced=False)
    assert result["correct"] is True, lines
