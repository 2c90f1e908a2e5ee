"""The reduction from a trace to numbers: interval arithmetic on made-up
events, and the whole reduction on a trace recorded on the TPU."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import trace  # noqa: E402

MS = 1_000_000


def made_up():
    """Two chips, two steps of 10 ms: 6 ms of compute, then an asynchronous
    collective of 2 ms (its start and done are short operations, its flight
    is on the asynchronous line) of which the second half runs beside a
    fusion, then 2 ms idle while the host sits in ``obs_read``."""
    devices, modules, flights = {}, {}, {}
    for chip in (0, 1):
        ops = []
        for step in (0, 1):
            t = step * 10 * MS
            ops += [["fusion.1", t, 6 * MS],
                    ["collective-permute-start.1", t + 6 * MS, MS // 10],
                    ["fusion.2", t + 7 * MS, 1 * MS],
                    ["collective-permute-done.1", t + 8 * MS - 1000, 1000]]
        devices[chip] = ops
        flights[chip] = [["collective-permute-start.1", s * 10 * MS + 6 * MS, 2 * MS]
                         for s in (0, 1)] + [["copy-start.3", 0, 5 * MS]]
        modules[chip] = [["jit_step(1)", 0, 8 * MS], ["jit_tiny(2)", 9 * MS, 10],
                         ["jit_step(1)", 10 * MS, 8 * MS]]
    spans = [["io", 0, MS // 2], ["dispatch", MS // 2, MS],
             ["obs_read", 2 * MS, 8 * MS - 1000],
             ["io", 10 * MS, MS // 2], ["dispatch", 10 * MS + MS // 2, MS],
             ["obs_read", 12 * MS, 6 * MS]]
    return {"devices": devices, "modules": modules, "async": flights,
            "spans": spans}


def test_union_and_subtract():
    assert trace.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert trace.subtract([[0, 10]], [[2, 3], [5, 11]]) == [[0, 2], [3, 5]]
    assert trace.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert trace.total([[0, 3], [7, 9]]) == 5


def test_busy_idle_and_collectives_on_made_up_events():
    ev = made_up()
    t0, t1 = trace.window(ev)
    assert (t0, t1) == (0, 18 * MS)
    # 6 + 0.1 + 1 + 0.001 ms of operations a step, minus the 1 us in which
    # the done overlaps the fusion's end.
    assert trace.busy_seconds(ev) == pytest.approx(2 * 7.1e-3, rel=1e-3)
    comm, exposed = trace.collective_seconds(ev)
    assert comm == pytest.approx(0.004) and exposed == pytest.approx(0.002)
    assert trace.span_seconds(ev, "io") == [0.0005, 0.0005]
    top = trace.top_operations(ev)
    assert top[0] == ["fusion.1", pytest.approx(0.012)]


def test_idle_gap_is_named_by_the_span_that_covers_it():
    gaps = trace.idle_gaps(made_up())
    assert gaps[0] == ["obs_read", pytest.approx(0.002)]
    assert [g[0] for g in gaps[:3]] == ["obs_read"] * 3
    ev = made_up()
    ev["spans"] = [s for s in ev["spans"] if s[0] != "obs_read"]
    assert trace.idle_gaps(ev)[0][0] == "host"


def _placed(ev, host, shift):
    want = sorted(([n, s * 1e9 - 5e9 + shift, d * 1e9] for n, s, d in host),
                  key=lambda e: e[1])
    ev["spans"] = []
    placed = trace.place_spans(ev, host)["spans"]
    assert len(placed) == len(want)
    for got, (name, start, dur) in zip(placed, want):
        assert got[0] == name and got[2] == pytest.approx(dur)
        assert got[1] == pytest.approx(start, abs=1000)


def test_spans_from_the_hosts_clock_are_placed_by_the_idle_dispatch():
    """No blocking read among the spans. The host's clock runs 5 s ahead;
    the first dispatch finds the chip idle (its program starts 0.2 ms after
    it), the second went out 3 ms early and finds it busy."""
    ev = made_up()
    ev["modules"] = {0: [["jit_step(1)", 0.7 * MS, 8 * MS], ["jit_tiny(2)", 9 * MS, 10],
                         ["jit_step(1)", 10.7 * MS, 8 * MS]]}
    host = [[n, 5.0 + s / 1e9, d / 1e9] for n, s, d in ev["spans"]
            if n != "obs_read"]
    host[3][1] -= 0.003
    # Short of the launch latency, 0.2 ms, every span is back in its place.
    _placed(ev, host, 0.2 * MS)
    with pytest.raises(ValueError):
        trace.place_spans(ev, host[:2])


def test_spans_are_placed_by_the_blocking_read_where_there_is_one():
    """Each program starts 3 ms after its dispatch, once its input has
    reached the chip, and each ``obs_read`` returns half a millisecond
    after its program's end: the reads place the spans, short of that half
    millisecond, and the dispatches would have placed them 3 ms late."""
    ev = made_up()
    ev["modules"] = {0: [["jit_step(1)", 3.5 * MS, 6 * MS], ["jit_tiny(2)", 9.6 * MS, 10],
                         ["jit_step(1)", 13.5 * MS, 4 * MS]]}
    host = [[n, 5.0 + s / 1e9, d / 1e9] for n, s, d in ev["spans"]]
    _placed(ev, host, -0.499 * MS)
    # A read that returns before the program it is matched with has ended
    # was no blocking read: the dispatches' bound holds.
    ev["modules"][0][2] = ["jit_step(1)", 10.7 * MS, 30 * MS]
    _placed(ev, host, 0.2 * MS)


def test_save_load_and_clip_round_trip(tmp_path):
    ev = made_up()
    path = str(tmp_path / "t.events.json.gz")
    trace.save(ev, path)
    assert trace.load(path) == ev
    first = trace.clip(ev, 0, 10 * MS)
    assert len(first["devices"][1]) == 4 and len(first["spans"]) == 3
    assert len(first["async"][1]) == 2 and len(first["modules"][1]) == 2


def test_metric_readers_on_made_up_events():
    from perfbench import harness

    ctx = {"events": made_up(), "steps": 2, "throughput": 1000.0, "chips": 2,
           "config": {"flops_per_sample": {"train": 1.97e9}},
           "peaks": {"bf16_flops": 197e12}}
    read = lambda name: harness.read_metric({"name": name}, ctx)
    assert read("device_idle") == pytest.approx(100 * (1 - 14.2 / 18), rel=1e-3)
    assert read("device_step_ms") == pytest.approx(7.1, rel=1e-3)
    assert read("comm_ms") == pytest.approx(2.0)
    assert read("comm_exposed_ms") == pytest.approx(1.0)
    assert read("io_ms") == pytest.approx(0.5)
    assert read("obs_read_ms") == pytest.approx((8 - 0.001 + 6) / 2)
    assert read("mfu") == pytest.approx(1.0)
    assert harness.read_metric({"name": "comm_ms"}, dict(ctx, chips=1)) is None
    assert harness.read_metric({"name": "mfu"}, dict(ctx, peaks=None)) is None


FIXTURES = os.path.join(REPO, "perfbench", "fixtures")


def test_reduction_of_a_trace_recorded_on_the_tpu():
    """Two ResNet-50 b512 gtopk steps on one TPU v5e (PR 23's chip run,
    host tracer off, the program's spans placed by ``place_spans``)."""
    from perfbench import harness

    ev = trace.load(os.path.join(
        FIXTURES, "tpu_v5e_resnet50_gtopk_2steps.events.json.gz"))
    assert set(ev["devices"]) == {0} and len(ev["devices"][0]) == 5886
    assert [m[0].split("(")[0] for m in ev["modules"][0]] == ["jit_shardwise"] * 2
    t0, t1 = trace.window(ev)
    # The chip works 212 ms of each 280 ms step ...
    assert trace.busy_seconds(ev) == pytest.approx(0.424678, abs=1e-5)
    assert (t1 - t0) / 1e9 == pytest.approx(0.492951, abs=1e-5)
    # ... and the one long gap between the two steps comes while the host
    # sits in the next step's obs_read: that step's program starts 57 ms
    # after its dispatch, once its batch has reached the chip.
    gaps = trace.idle_gaps(ev)
    assert gaps[0] == ["obs_read", pytest.approx(0.068241, abs=1e-5)]
    assert all(seconds < 1e-4 for _, seconds in gaps[1:])
    launch = [s for s in ev["spans"] if s[0] == "dispatch"][1][1]
    assert (ev["modules"][0][1][1] - launch) / 1e6 == pytest.approx(57.2, abs=0.1)
    assert trace.top_operations(ev)[0] == [
        "convert_reduce_fusion", pytest.approx(0.012613, abs=1e-5)]
    # One chip, gtopk at P=1: no collective in the step.
    assert trace.collective_seconds(ev) == (0.0, 0.0)
    ctx = {"events": ev, "steps": 2, "chips": 1}
    assert harness.read_metric({"name": "device_step_ms"}, ctx) \
        == pytest.approx(212.339, abs=1e-2)
    assert harness.read_metric({"name": "device_idle"}, ctx) \
        == pytest.approx(13.85, abs=0.01)
    assert harness.read_metric({"name": "obs_read_ms"}, ctx) \
        == pytest.approx(265.435, abs=1e-2)
    assert harness.read_metric({"name": "io_ms"}, ctx) == pytest.approx(5.398, abs=1e-2)


def test_collectives_in_a_four_chip_trace_recorded_on_the_tpu():
    """One ResNet-50 b512 gtopk step on four TPU v5e chips (PR 23's chip
    run): two tree rounds of collective-permutes and one all-reduce a chip;
    their flights are on chip 0's asynchronous line only."""
    ev = trace.load(os.path.join(
        FIXTURES, "tpu_v5e_resnet50_gtopk_dp4_1step.events.json.gz"))
    assert sorted(ev["devices"]) == [0, 1, 2, 3]
    assert [len(ev["async"][c]) > 0 for c in range(4)] == [True, False, False, False]
    for chip in range(4):
        names = [n for n, _, _ in ev["devices"][chip]]
        assert sum(n.startswith("collective-permute-start") for n in names) == 4
        assert sum(n.startswith("all-reduce") for n in names) == 1
    comm, exposed = trace.collective_seconds(ev)
    assert comm == pytest.approx(1.1798e-4, rel=1e-3)
    assert exposed == pytest.approx(4.7229e-5, rel=1e-3)
    assert 0 < exposed < comm < 1e-3 * trace.busy_seconds(ev)
    assert trace.busy_seconds(ev) == pytest.approx(0.217699, abs=1e-5)
    # Before the step the chips wait half a second for the batch: the gap
    # ahead of the first operation is outside the window. The one inside it
    # comes while the host is already in its blocking read (spans placed by
    # that read's end, not by the dispatch: ``place_spans``).
    assert trace.idle_gaps(ev)[0] == ["obs_read", pytest.approx(0.004711, abs=1e-5)]
    assert [s[0] for s in ev["spans"]] == ["io", "dispatch", "obs_read"]
    assert ev["spans"][0][2] / 1e9 == pytest.approx(0.384, abs=1e-3)
