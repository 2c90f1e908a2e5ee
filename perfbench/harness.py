"""One run of one cell: set-up, probe, window, reference, result line.

``run_cell`` is what ``run.py`` calls after it has found the chips; the tests
call it, and the steps below it, directly on the CPU with tiny files. Nothing
here knows a cell, a configuration, a traffic mix or a metric by name: they
are files found through ``BENCHMARK.json``.
"""

import dataclasses
import gc
import glob
import importlib
import json
import math
import os
import shutil
import time

from perfbench import compare, reference, trace, traffic as traffic_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list
    root: str


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def load_cell(workload, root=ROOT):
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"perfbench: no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    reports = lambda m: "workloads" not in m or workload in m["workloads"]
    traffic = _read(os.path.join(root, "perfbench", "traffic",
                                 entry["traffic"] + ".json"))
    if traffic["chips"] != entry["chips"]:
        raise SystemExit(f"perfbench: {workload}: chips {entry['chips']} in "
                         f"BENCHMARK.json, {traffic['chips']} in its traffic file")
    return Cell(name=workload, chips=entry["chips"],
                config=_read(os.path.join(root, config["file"])),
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if reports(m)],
                per_layer=[m for m in bench["per_layer"] if reports(m)],
                root=root)


def peaks_for(device_kind, root=ROOT):
    table = _read(os.path.join(root, "perfbench", "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"perfbench: no peaks for device kind {device_kind!r} "
                         "in perfbench/peaks.json; add it with its source")
    return table[device_kind]


class LoweringCounter:
    """Counts the programs jax lowers while ``active``: each is a compile
    (or a load from the persistent cache) that the window must not hold."""

    def __init__(self):
        import jax.monitoring

        self.count, self.active = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self.active and event == LOWERING_EVENT:
            self.count += 1


# ------------------------------------------------------------------ set-up
def build_trainer(cell, seed, pool):
    """The program's ``Trainer`` for this cell, by its normal constructor,
    on the benchmark's pool of batches, then given the benchmark's weights."""
    import jax

    from gtopkssgd_tpu import trainer as program
    from gtopkssgd_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    cfg, tr = cell.config, cell.traffic
    opt = cfg["optimizer"]

    def dataset(name, *, split, rank=0, **_):
        return traffic_mod.PoolShard(pool, rank if split == "train" else 0,
                                     tr["steps_per_epoch"])

    flags = dict(cfg["program"], batch_size=tr["batch_size"],
                 nworkers=tr["chips"], compression=tr["compression"],
                 density=tr.get("density", 1.0), lr=opt["lr"],
                 momentum=opt["momentum"], weight_decay=opt["weight_decay"],
                 clip_grad_norm=opt.get("clip_grad_norm"),
                 **tr.get("program_flags", {}))
    original, program.get_dataset = program.get_dataset, dataset
    try:
        trainer = program.Trainer(program.TrainConfig(**flags))
    finally:
        program.get_dataset = original
    adopt_weights(trainer, cfg, seed)
    return trainer


def adopt_weights(trainer, config, seed):
    """Both sides start from the benchmark's weights: the reference's init,
    one jitted call from the seed, laid out as the program laid out its own."""
    import jax

    params, model_state = reference.init_variables(config, seed)
    state = trainer.state

    def adopt(mine, theirs):
        paths = lambda t: [(jax.tree_util.keystr(k), v.shape, v.dtype) for k, v
                           in jax.tree_util.tree_flatten_with_path(t)[0]]
        if paths(mine) != paths(theirs):
            raise SystemExit("perfbench: the program's parameters and the "
                             "reference model's differ in names or shapes")
        return jax.tree.map(lambda m, t: jax.device_put(m, t.sharding),
                            mine, theirs)

    trainer.state = state._replace(
        params=adopt(params, state.params),
        batch_stats=adopt(model_state, state.batch_stats))


def flat_params(trainer):
    import numpy as np
    import jax

    return np.concatenate([np.asarray(leaf).ravel()
                           for leaf in jax.tree.leaves(trainer.state.params)])


def probe(trainer, steps, keep=3):
    """The first ``steps`` optimizer steps from the seed's state through the
    window's own call, one at a time so that each loss is read; the first
    is the compile (or the load from the cache). Returns the losses, the
    flat parameters at steps 0..keep, and the first step's seconds."""
    flats = [flat_params(trainer)]
    losses, first = [], None
    for t in range(steps):
        t0 = time.perf_counter()
        losses.append(trainer.train(1)["loss"])
        if first is None:
            first = time.perf_counter() - t0
        if t < keep:
            flats.append(flat_params(trainer))
    return {"losses": losses, "params": flats}, first


def measure(trainer, seconds, chunk, counter, trace_dir=None, trace_steps=0):
    """``Trainer.train`` in whole chunks until ``seconds`` have passed; the
    chunk in flight is finished and counted, and each chunk's last loss is
    kept. With ``trace_dir`` the second
    chunk is ``trace_steps`` long and runs under the profiler, the program's
    spans going to ``host_spans`` through its tracer's sink."""
    import jax

    jax.block_until_ready(trainer.state)
    steps, chunks, host_spans, losses = 0, 0, [], []
    counter.count, counter.active = 0, True
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds or (trace_dir and chunks < 2):
        if trace_dir and chunks == 1:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            # See perfbench/trace.py: the host tracer stays off on the TPU.
            # The CPU's operations run on host threads, so a rehearsal
            # there needs it.
            options.host_tracer_level = (
                0 if jax.default_backend() == "tpu" else 2)
            trainer.tracer.sink = lambda name, t0, dur: host_spans.append(
                [name, t0, dur])
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                losses.append(trainer.train(trace_steps)["loss"])
            finally:
                jax.profiler.stop_trace()
                trainer.tracer.sink = None
            steps += trace_steps
        else:
            losses.append(trainer.train(chunk)["loss"])
            steps += chunk
        chunks += 1
    jax.block_until_ready(trainer.state)
    elapsed = time.perf_counter() - t0
    counter.active = False
    return {"steps": steps, "seconds": elapsed, "compiles": counter.count,
            "host_spans": host_spans, "losses": losses}


def replicas_differ(trainer):
    """Leaves of the parameters whose copies on the chips are not bit-identical."""
    import jax
    import numpy as np

    bad = 0
    for leaf in jax.tree.leaves(trainer.state.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        bad += not all(np.array_equal(copies[0], c) for c in copies[1:])
    return bad


def peak_bytes(chips):
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()[:chips]]
    peaks = [s["peak_bytes_in_use"] for s in stats
             if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


# ------------------------------------------------------------ per layer
def read_metric(entry, ctx, root=ROOT):
    """One per-layer metric through its own file; None when its reader
    finds nothing to read."""
    spec = _read(os.path.join(root, "perfbench", "metrics",
                              entry["name"] + ".json"))
    reader = spec["reader"]
    if "span" in reader:
        spans = trace.span_seconds(ctx["events"], reader["span"])
        return 1e3 * sum(spans) / ctx["steps"] if spans else None
    module = importlib.import_module(f"perfbench.metrics.{reader['module']}")
    return module.read(ctx, **reader.get("args", {}))


def reduce_trace(cell, trace_dir, ctx, emit):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise SystemExit("perfbench: the profiler wrote no xplane file")
    events = trace.extract(files[0])
    if not any(events["devices"].values()):
        raise SystemExit("perfbench: no operation ran on a device in the trace")
    if not events["spans"]:
        trace.place_spans(events, ctx["host_spans"])
    t0, t1 = trace.window(events)
    ctx = dict(ctx, events=events)
    metrics = {}
    for entry in cell.per_layer:
        value = read_metric(entry, ctx, cell.root)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    out_dir = os.path.join(cell.root, "chiprun_out", "perfbench")
    os.makedirs(out_dir, exist_ok=True)
    trace.save(events, os.path.join(out_dir, cell.name + ".events.json.gz"))
    emit(f"trace: {sum(map(len, events['devices'].values()))} device events "
         f"on {len(events['devices'])} chip(s), {len(events['spans'])} spans")
    return metrics, {
        "busy_s": trace.busy_seconds(events), "window_s": (t1 - t0) / 1e9,
    }, {"device_ops": trace.top_operations(events),
        "idle_gaps": trace.idle_gaps(events)}


# ---------------------------------------------------------------- the run
def run_cell(cell, seed, seconds, traced, *, started, emit=print):
    """Everything after the chips were found. Returns the result object."""
    import jax

    tr = cell.traffic
    marks, last = {}, started

    def mark(name):
        nonlocal last
        now = time.perf_counter()
        marks[name], last = now - last, now

    mark("import")
    pool = traffic_mod.make_pool(cell.config, tr, seed)
    mark("pool")
    counter = LoweringCounter()
    trainer = build_trainer(cell, seed, pool)
    mark("build")
    program, first = probe(trainer, tr["probe_steps"])
    mark("probe")
    marks.update(compile_or_load=first, probe=marks["probe"] - first)
    setup_s = time.perf_counter() - started
    emit("setup " + " ".join(f"{k}={v:.2f}s" for k, v in marks.items())
         + f" total={setup_s:.2f}s")

    trace_dir = None
    if traced:
        trace_dir = os.path.join(cell.root, "chiprun_out", "perfbench",
                                 cell.name + ".trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = measure(trainer, seconds, tr["chunk_steps"], counter,
                     trace_dir, tr["trace_steps"])
    samples = window["steps"] * tr["batch_size"] * cell.chips
    throughput = samples / window["seconds"] / cell.chips
    emit(f"window steps={window['steps']} seconds={window['seconds']:.3f} "
         f"samples/s/chip={throughput:.2f}")
    peak = peak_bytes(cell.chips)
    differ = replicas_differ(trainer) if cell.chips > 1 else 0
    device = jax.devices()[0]
    trainer.close()
    del trainer
    gc.collect()

    ref = reference.train(cell.config, tr, seed, pool, tr["probe_steps"])
    emit(f"reference steps={tr['probe_steps']} seconds={ref['seconds']:.2f}")
    values = compare.numbers(program, ref, cell.config, tr)
    values["window_compiles"] = window["compiles"]
    # Every probe step's loss, and the window's at the end of each chunk.
    values["nonfinite_losses"] = sum(
        not math.isfinite(x) for x in program["losses"] + window["losses"])
    if cell.chips > 1:
        values["replica_leaves_differing"] = differ
    correct = compare.decide(values, tr["limits"], emit)

    result = {
        "correct": bool(correct),
        "attempted": tr["probe_steps"] + window["steps"],
        "failed": values["nonfinite_losses"],
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": cell.chips, "memory_peak_bytes": peak},
    }
    measured = {"throughput": throughput, "loss_ratio_32": values["loss_ratio"],
                "setup_s": setup_s}
    if not traced:
        result["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        return result
    ctx = {"steps": tr["trace_steps"], "throughput": throughput,
           "host_spans": window["host_spans"],
           "chips": cell.chips, "config": cell.config,
           "peaks": (peaks_for(device.device_kind, cell.root)
                     if device.platform == "tpu" else None)}
    result["metrics"], extra, result["breakdown"] = reduce_trace(
        cell, trace_dir, ctx, emit)
    result["device"].update(extra)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return result
