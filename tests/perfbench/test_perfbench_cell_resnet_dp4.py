"""resnet50.gtopk_dp4 on the CPU at a tiny size: one whole run through the harness
(set-up, probe, window, reference, result line)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402


def test_whole_run_is_correct_and_its_line_has_the_schema():
    cell = tiny.tiny_cell("resnet50.gtopk_dp4")
    result, lines = tiny.run(cell, traced=False)
    tiny.check_schema(cell, result, traced=False)
    assert result["correct"] is True, lines
    assert any(line.startswith("compare replica_leaves_differing = 0 ")
               for line in lines)


def test_the_four_chip_cell_is_the_only_one_and_reports_the_collectives():
    cell = tiny.tiny_cell("resnet50.gtopk_dp4")
    assert cell.chips == 4 and cell.traffic["chips"] == 4
    assert {"comm_ms", "comm_exposed_ms"} <= {m["name"] for m in cell.per_layer}
    assert "comm_ms" not in {m["name"] for m in
                             tiny.tiny_cell("resnet50.gtopk").per_layer}
