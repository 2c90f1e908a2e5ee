"""The stage metrics and the host-side span metrics (PR 24): on made-up
events, on two scoped ResNet-50 steps recorded on the TPU, on a trace file
written for the purpose, and the rule that they were added by new files."""

import json
import os
import struct
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import harness, trace  # noqa: E402
from perfbench.metrics import scoped  # noqa: E402

MS = 1_000_000
PARENT = "5dc4e05c402d79d0cb79fc957d74ecb7decc5359"
NEW = ["fwd_bwd_ms", "compress_ms", "apply_ms", "telemetry_ms", "merge_ms",
       "scoped_share", "input_wait_ms", "assemble_ms", "step_start_lag_ms"]


def read(name, ctx):
    return harness.read_metric({"name": name}, ctx)


def made_up():
    """Two chips, three steps of 10 ms. A step: 5 ms forward/backward, an
    unscoped concatenate (0.5 ms), top-k (1 ms), one merge round (a
    collective's start and done round 0.4 ms of merge compute), a mask
    fusion that the counters were fused into (0.5 ms), an operation the map
    does not know (a compiler's copy, 0.2 ms), the apply (0.8 ms). A small
    program between the steps runs an operation called fusion.1 too. The
    host dispatched the first step before the trace began."""
    step = [("fusion.1", 0, 5000), ("concatenate.1", 5000, 500),
            ("approx_top_k.0", 5500, 1000),
            ("collective-permute-start.1", 6500, 100),
            ("fusion.7", 6600, 400),
            ("collective-permute-done.1", 7000, 100),
            ("select_reduce_fusion", 7100, 500), ("copy-done.3", 7600, 200),
            ("multiply_add_fusion.6", 7800, 800)]
    devices, modules = {}, {}
    for chip in (0, 1):
        ops, programs = [], []
        for k in range(3):
            t = k * 10 * MS
            ops += [[n, t + s * 1000, d * 1000] for n, s, d in step]
            ops.append(["fusion.1", t + 9 * MS, 1000])
            programs += [["jit_gtopk_train_step(77)", t, 8600 * 1000],
                         ["jit_convert(5)", t + 9 * MS, 2000]]
        devices[chip], modules[chip] = ops, programs
    scopes = {"fusion.1": "gtopk/fwd_bwd", "concatenate.1": "",
              "approx_top_k.0": "gtopk/select",
              "collective-permute-start.1": "gtopk/allreduce/round0",
              "fusion.7": "gtopk/allreduce/round0",
              "collective-permute-done.1": "gtopk/allreduce/round0",
              "select_reduce_fusion": "gtopk/mask",
              "multiply_add_fusion.6": "gtopk/apply"}
    spans = []
    for k in (1, 2):                 # step 0 was dispatched before the trace
        t = k * 10 * MS
        spans += [["io", t - 3 * MS, MS, k, "MainThread"],
                  ["io/wait", t - 3 * MS, MS // 4, k, "MainThread"],
                  ["io/put", t - 2.75 * MS, MS // 2, k, "MainThread"],
                  ["dispatch", t - 2 * MS, MS // 2, k, "MainThread"],
                  ["obs_read", t - 1.5 * MS, 9 * MS, k, "MainThread"],
                  ["prefetch/assemble", t - 4 * MS, (2 + k) * MS, k + 2,
                   "prefetch"]]
    events = {"devices": devices, "modules": modules,
              "async": {0: [], 1: []}, "spans": []}
    return {"events": events, "steps": 3, "chips": 2,
            "scoped": {"scopes": scopes, "start_ns": 0, "stop_ns": 30 * MS,
                       "spans": sorted(spans, key=lambda s: s[1])}}


def test_stage_metrics_on_made_up_events():
    ctx = made_up()
    assert read("fwd_bwd_ms", ctx) == pytest.approx(5.0)
    # select + mask; the step has no accumulate or repair operation.
    assert read("compress_ms", ctx) == pytest.approx(1.5)
    assert read("apply_ms", ctx) == pytest.approx(0.8)
    # The counters were fused into the mask's fusion: its root's scope
    # counts, so the stage reads zero (a reading, not a missing metric).
    assert read("telemetry_ms", ctx) == 0.0
    # The merge compute between the collectives, not the collectives.
    assert read("merge_ms", ctx) == pytest.approx(0.4)
    # 8.6 ms busy a step, of which the concatenate (foreign scope) and the
    # copy (unknown to the map) are under no stage; the small program's
    # fusion.1 is outside the step and counts nowhere.
    assert read("scoped_share", ctx) == pytest.approx(100 * 7.9 / 8.6)
    assert scoped.scoped_seconds(
        ctx["events"], ctx["scoped"]["scopes"], lambda *_: True) \
        == pytest.approx(3 * 8.6e-3)


def test_a_round_belongs_to_its_stage_and_the_outermost_scope_counts():
    assert scoped.scope_of(
        "jit(gtopk_train_step)/gtopk/allreduce/round1/gtopk/select/"
        "approx_top_k:") == "gtopk/allreduce/round1"
    assert scoped.scope_of("gtopk/telemetry/gtopk/telemetry/reduce_sum:") \
        == "gtopk/telemetry"
    assert scoped.scope_of("jit(gtopk_train_step)/concatenate:") == ""
    assert scoped.scope_of("jit(f)/not_gtopk/mask/x") == ""


def test_span_metrics_on_made_up_events():
    ctx = made_up()
    # Two io/wait spans of 0.25 ms over three traced steps.
    assert read("input_wait_ms", ctx) == pytest.approx(0.5 / 3)
    # Per batch, whatever the steps: assemblies of 3 and 4 ms.
    assert read("assemble_ms", ctx) == pytest.approx(3.5)
    # Steps 1 and 2 start 1.5 ms after their dispatch spans end; the
    # first program has no dispatch in the trace and is left out, so the
    # second dispatch is not paired with the third program.
    assert read("step_start_lag_ms", ctx) == pytest.approx(1.5)
    late = made_up()
    late["scoped"]["spans"] = [
        s if s[0] != "dispatch" or s[3] != 2 else [s[0], s[1] - MS] + s[2:]
        for s in late["scoped"]["spans"]]
    assert read("step_start_lag_ms", late) == pytest.approx((1.5 + 2.5) / 2)
    # The clocks agree to a millisecond or so: a program that seems to
    # start 0.6 ms before its dispatch opened is still that dispatch's.
    early = made_up()
    early["scoped"]["spans"] = [
        s if s[0] != "dispatch" else [s[0], s[1] + 2.6 * MS] + s[2:]
        for s in early["scoped"]["spans"]]
    assert read("step_start_lag_ms", early) == pytest.approx(1.5 - 2.6)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_to_read_and_says_so(name):
    """A program without scopes, buffer or step name: None, no exception."""
    ctx = made_up()
    empty = dict(ctx, scoped=None)
    assert read(name, empty) is None
    bare = dict(ctx, scoped=dict(ctx["scoped"], scopes={}, spans=None))
    assert read(name, bare) is None
    renamed = made_up()
    for programs in renamed["events"]["modules"].values():
        for program in programs:
            program[0] = program[0].replace("gtopk_train_step", "shardwise")
    renamed["scoped"]["spans"] = [s for s in renamed["scoped"]["spans"]
                                  if "/" not in s[0]]
    assert read(name, renamed) is None


FIXTURE = os.path.join(REPO, "perfbench", "fixtures",
                       "tpu_v5e_resnet50_gtopk_scoped_2steps")


def recorded():
    with open(FIXTURE + ".scoped.json") as fh:
        info = json.load(fh)
    return {"events": trace.load(FIXTURE + ".events.json.gz"), "steps": 2,
            "chips": 1, "scoped": info}


def test_readers_on_two_scoped_steps_recorded_on_the_tpu():
    """Two ResNet-50 b512 gtopk steps on one TPU v5e with the scopes in
    (PR 24's first chip run): the events as the harness reduces them, the
    operation -> scope map from the events' metadata, the trace's start on
    the epoch clock and the span buffer of the traced steps."""
    ctx = recorded()
    ev, info = ctx["events"], ctx["scoped"]
    assert [m[0].split("(")[0] for m in ev["modules"][0]] \
        == ["jit_gtopk_train_step"] * 2
    assert len(ev["devices"][0]) == 5886 == len(scoped.step_operations(ev, 0))
    assert set(info["scopes"].values()) == {
        "", "gtopk/fwd_bwd", "gtopk/select", "gtopk/mask", "gtopk/apply",
        "gtopk/telemetry"}
    whole = read("device_step_ms", ctx)
    assert whole == pytest.approx(212.346, abs=1e-2)
    stages = {name: read(name, ctx) for name in
              ("fwd_bwd_ms", "compress_ms", "apply_ms", "telemetry_ms")}
    assert stages["fwd_bwd_ms"] == pytest.approx(205.610, abs=1e-2)
    assert stages["compress_ms"] == pytest.approx(0.839, abs=1e-2)
    assert stages["apply_ms"] == pytest.approx(0.560, abs=1e-2)
    assert stages["telemetry_ms"] == pytest.approx(0.433, abs=1e-2)
    # The stages do not overlap on one chip, and what is under none of
    # them (the flat gradient's concatenate, the compiler's own copies)
    # is 2.3% of the step.
    assert sum(stages.values()) == pytest.approx(
        whole * read("scoped_share", ctx) / 100, rel=1e-6)
    assert read("scoped_share", ctx) == pytest.approx(97.69, abs=0.01)
    assert read("merge_ms", ctx) == 0.0           # one chip: no allreduce
    assert read("input_wait_ms", ctx) == pytest.approx(0.0488, abs=1e-3)
    assert read("assemble_ms", ctx) == pytest.approx(174.14, abs=0.01)
    assert read("step_start_lag_ms", ctx) == pytest.approx(54.73, abs=0.01)
    # Every span of the buffer has its step and its thread; the worker's
    # span is not among the ones the sink gave the harness.
    paths = {s[0] for s in info["spans"]}
    assert paths == {"io", "io/wait", "io/put", "dispatch", "obs_read",
                     "final_sync", "prefetch/assemble"}
    assert {s[4] for s in info["spans"] if s[0] == "prefetch/assemble"} \
        == {"prefetch"}
    assert all(s[3] is not None for s in info["spans"] if s[0] != "final_sync")
    assert "prefetch/assemble" not in {s[0] for s in ev["spans"]}
    # The anchor against place_spans' inference: the blocking read had put
    # the spans 6.5 ms early (a read returns that long after its program).
    placed = next(s for s in ev["spans"] if s[0] == "dispatch")
    anchored = next(s for s in info["spans"] if s[0] == "dispatch")
    assert (anchored[1] - placed[1]) / 1e6 == pytest.approx(6.46, abs=0.01)
    assert trace.idle_gaps(ev)[0][0] == "obs_read"


# ------------------------------------------------- a trace file on disk
def _varint(value):
    out = bytearray()
    while True:
        byte, value = value & 0x7F, value >> 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, event_metadata=(), stats=()):
    ids = {n: i + 1 for i, n in enumerate(stat_names)}

    def stat(key, value):
        kind = 5 if isinstance(value, str) else 2 if isinstance(value, float) else 3
        return _field(1, ids[key]) + _field(kind, value)

    body = _field(2, name)
    for key, i in ids.items():
        body += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, key)))
    for i, (text, its) in enumerate(event_metadata):
        meta = _field(1, i + 1) + _field(2, text) + b"".join(
            _field(5, stat(k, v)) for k, v in its.items())
        body += _field(4, _field(1, i + 1) + _field(2, meta))
    body += b"".join(_field(6, stat(k, v)) for k, v in stats)
    return _field(1, body)


def test_scope_map_and_clock_are_read_from_a_trace_files_metadata(tmp_path):
    """The fields scoped.read_xplane decodes, written by hand as the TPU
    runtime lays them out: an operation's scope path is the tf_op stat of
    its event metadata, beside the id of its program."""
    names = ["tf_op", "program_id", "flops", "hlo_category"]
    ops = [("%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
            {"hlo_category": "fusion", "program_id": 77, "flops": 2.5,
             "tf_op": "jit(gtopk_train_step)/gtopk/mask/jit(_where)/select_n:"}),
           ("%concatenate.1 = f32[16]{0} concatenate(f32[8]{0} %a)",
            {"program_id": 77, "tf_op": "jit(gtopk_train_step)/concatenate:"}),
           ("%copy-done.3 = f32[8]{0} copy-done(%copy-start.3)",
            {"program_id": 77}),
           ("%fusion.12 = f32[] fusion(f32[] %q)",
            {"program_id": 5, "tf_op": "jit(convert)/convert_element_type:"})]
    blob = (_plane("/device:TPU:0", names, ops)
            + _plane("/host:CPU", [])
            + _plane("Task Environment",
                     ["profile_start_time", "profile_stop_time"],
                     stats=[("profile_start_time", 1790558169501846399),
                            ("profile_stop_time", 1790558170354426811)]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(blob)
    found = scoped.read_xplane(str(path))
    assert found["start_ns"] == 1790558169501846399
    assert found["stop_ns"] - found["start_ns"] == 852580412
    assert found["scopes"] == {
        "77": {"fusion.12": "gtopk/mask", "concatenate.1": ""},
        "5": {"fusion.12": ""}}
    path.write_bytes(_plane("/host:CPU", []))
    assert scoped.read_xplane(str(path)) == {
        "start_ns": None, "stop_ns": None, "scopes": {}}


def test_run_info_finds_this_runs_trace_and_the_programs_spans(
        tmp_path, monkeypatch):
    """A traced window on the CPU: the helper finds the trace the harness
    reduced (not another cell's beside it), reads the clock from it and
    takes the window's spans from the program's buffer, the worker
    thread's among them. The CPU's events carry no scope path."""
    import jax
    import jax.numpy as jnp

    from gtopkssgd_tpu.obs import tracing
    from gtopkssgd_tpu.utils import Prefetcher

    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    work = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    work(x).block_until_ready()
    tracer = tracing.Tracer()
    with tracer.span("io", step=0):
        pass                                   # before the trace: left out
    events = {}
    for cell in ("a", "b"):
        trace_dir = str(tmp_path / "chiprun_out" / "perfbench" / f"{cell}.trace")
        feed = Prefetcher(lambda: 1, depth=1, tracer=tracer)
        with tracing.profile(trace_dir):
            for step in (1, 2):
                with tracer.span("io", step=step):
                    with tracer.span("wait"):
                        next(feed)
                with tracer.span("dispatch", step=step):
                    work(x).block_until_ready()
        feed.close()
        files = list((tmp_path / "chiprun_out").rglob("*.xplane.pb"))
        events[cell] = trace.extract(str(max(files, key=os.path.getmtime)))
        with open(os.path.join(trace_dir, "spans.json")) as fh:
            written = json.load(fh)
        assert {s["path"] for s in written["spans"]} >= {
            "io", "io/wait", "dispatch"}
        assert all(abs(s["epoch_ns"] - (s["t0"] * 1e9 + s["anchor_ns"])) < 2
                   for s in written["spans"])
    assert events["a"]["devices"][0] and events["a"]["devices"] != events["b"]["devices"]
    info = scoped.run_info({"events": events["a"], "steps": 2})
    assert info["scopes"] == {}
    assert 0 < info["stop_ns"] - info["start_ns"] < 60e9
    mine = [s for s in info["spans"] if s[0] in ("io", "io/wait", "dispatch")]
    assert [(s[0], s[3]) for s in mine] == [
        ("io", 1), ("io/wait", 1), ("dispatch", 1),
        ("io", 2), ("io/wait", 2), ("dispatch", 2)]
    assert all(0 <= s[1] <= info["stop_ns"] - info["start_ns"] for s in mine)
    assert {s[4] for s in info["spans"] if s[0] == "prefetch/assemble"} \
        == {"prefetch"}
    ctx = {"events": events["a"], "steps": 2, "chips": 1}
    assert read("input_wait_ms", ctx) > 0
    assert read("fwd_bwd_ms", ctx) is None and read("scoped_share", ctx) is None
    assert read("step_start_lag_ms", ctx) is None
    # Events no trace under the root matches: nothing to read.
    other = dict(events["b"], devices={0: events["b"]["devices"][0][:-1]})
    assert scoped.run_info({"events": other, "steps": 2}) is None


# ------------------------------------------------------ the extension rule
def test_the_nine_metrics_were_added_by_new_files_and_entries_alone():
    """Against the parent commit: no file under perfbench/ or
    tests/perfbench/ that was there differs, and BENCHMARK.json gained the
    nine per_layer entries at the end and nothing else."""
    def git(*args):
        return subprocess.run(["git", *args], cwd=REPO, capture_output=True,
                              text=True, timeout=60)

    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("the parent commit is not in this checkout's history")
    changed = git("diff", "--name-status", PARENT, "--", "perfbench",
                  "tests/perfbench").stdout.split("\n")
    assert [line for line in changed if line and not line.startswith("A")] == []
    before = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        after = json.load(fh)
    n = len(before["per_layer"])
    assert [m["name"] for m in after["per_layer"][n:n + 9]] == NEW
    # What the parent had is still there, in place (later PRs append too).
    for key, value in before.items():
        if isinstance(value, list) and key not in ("command", "paths"):
            assert after[key][:len(value)] == value, key
        else:
            assert after[key] == value, key
