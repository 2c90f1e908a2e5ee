"""Compile- and memory-plane observability — the space-plane sibling of
trace_attr.py.

The obs stack measures the TIME plane (trace_attr's T_compute/T_select/
T_comm), the WIRE plane (ledger + calib), and the QUALITY plane (recall
audits); this module lights up the remaining dark plane: what the
compiled program costs in HBM, whether the hot step keeps its one
executable, and whether device memory is drifting. ROADMAP items 4
(elastic dp-mesh resize) and 5 (dp×tp Transformer) are memory-bound
decisions — resizing P or adding a tp axis changes per-device footprint
— and memory-bounded collective scheduling (arXiv:2112.01075) needs the
measurement before any planning against it.

Three layers, all host-side and sync-free (every read piggybacks on a
sync the train loop already pays):

  * Extraction helpers — ``cost_summary`` / ``memory_summary`` normalize
    ``compiled.cost_analysis()`` (dict OR list-of-dict across jax
    versions) and ``compiled.memory_analysis()`` (CompiledMemoryStats)
    into flat numeric dicts. ``compiled_flops`` is the ONE code path for
    XLA flop counts. The peak-HBM estimate is the standard decomposition
    arguments + outputs + temps + generated code − aliased bytes.
  * ``CompileWatch`` — tracks a jitted callable's executable-cache size
    (``_cache_size()``; a ``jax.monitoring`` event listener counts
    backend compile events as a corroborating fast path where
    available). The first poll adopts the current size as baseline (the
    initial trace is a compile, not a REcompile); later growth is a
    recompile.
  * ``MemWatch`` — the trainer-facing facade: per-dispatch-shape compile
    accounting (one fsync'd "compile" record each, AOT lower/compile
    keyed by ``batch_shape_key``), recompile records + the
    ``recompile_storm`` rule via ``AnomalyMonitor.observe_compile``, and
    sampled live memory ("mem" records: ``jax.live_arrays()`` count and
    bytes by dtype + per-device ``memory_stats()`` where the backend
    exposes them — CPU returns none and the watch degrades to
    live_arrays-only) feeding the ``device_mem_leak`` / ``hbm_headroom``
    rules via ``observe_memory``.

Record-before-rule ordering (same contract as calib.py's refit): every
record is durably written BEFORE the monitor sees the sample, so a halt
can never lose the evidence that triggered it.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, Optional, Tuple

# ------------------------------------------------------------ extraction

# cost_analysis keys -> record field names. XLA spells "bytes accessed"
# with a space; records use identifier-safe names (exporter families,
# report columns).
_COST_KEYS = (("flops", "flops"), ("bytes accessed", "bytes_accessed"))

# CompiledMemoryStats attributes -> record field names (device-side
# sizes only; the host_* mirror fields are zero off-TPU and noise on).
_MEM_ATTRS = (
    ("temp_size_in_bytes", "temp_bytes"),
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def cost_summary(compiled) -> Dict[str, float]:
    """Normalized ``cost_analysis()``: ``{"flops", "bytes_accessed"}``
    with only finite positive values; {} when the backend exposes
    nothing. Accepts both the dict and list-of-dict return shapes."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    out: Dict[str, float] = {}
    for key, name in _COST_KEYS:
        try:
            val = float(cost.get(key, -1.0))
        except (TypeError, ValueError):
            continue
        if val > 0 and math.isfinite(val):
            out[name] = val
    return out


def compiled_flops(compiled) -> Optional[float]:
    """Per-step FLOPs as XLA counts them (cost_analysis), None if
    absent. The single flop-count code path: the "compile" records
    read this."""
    return cost_summary(compiled).get("flops")


def memory_summary(compiled) -> Dict[str, int]:
    """Normalized ``memory_analysis()``: the device-side byte sizes plus
    the derived ``peak_hbm_bytes`` estimate (arguments + outputs + temps
    + generated code − aliased bytes); {} when the backend exposes no
    memory analysis."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    out: Dict[str, int] = {}
    for attr, name in _MEM_ATTRS:
        val = getattr(mem, attr, None)
        if isinstance(val, (int, float)) and math.isfinite(val) and val >= 0:
            out[name] = int(val)
    if out:
        peak = (out.get("argument_bytes", 0) + out.get("output_bytes", 0)
                + out.get("temp_bytes", 0)
                + out.get("generated_code_bytes", 0)
                - out.get("alias_bytes", 0))
        out["peak_hbm_bytes"] = max(int(peak), 0)
    return out


def batch_shape_key(tree) -> str:
    """Stable text key of a pytree's leaf shapes/dtypes — the identity
    of a dispatch shape. Two batches with the same key hit the same
    executable; a new key is a retrace. Long keys (a whole train-state
    pytree lists hundreds of leaves) collapse to a digest so a "compile"
    record stays a line, not a page."""
    import hashlib

    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        dtype = getattr(leaf, "dtype", None)
        parts.append("x".join(str(int(s)) for s in shape)
                      + ":" + str(dtype))
    key = ";".join(parts)
    if len(key) > 160:
        digest = hashlib.sha1(key.encode("utf-8")).hexdigest()[:16]
        key = f"sha1:{digest}:{len(parts)}leaves"
    return key


def compile_record(compiled=None, *, shape_key: str = "",
                   lower_s: Optional[float] = None,
                   compile_s: Optional[float] = None) -> Dict[str, Any]:
    """One "compile" record body: the normalized cost/memory summaries
    plus lowering/compile wall times and the dispatch-shape key."""
    rec: Dict[str, Any] = {"shape_key": str(shape_key)}
    if lower_s is not None:
        rec["lower_s"] = round(float(lower_s), 6)
    if compile_s is not None:
        rec["compile_s"] = round(float(compile_s), 6)
    if compiled is not None:
        rec.update(cost_summary(compiled))
        rec.update(memory_summary(compiled))
    return rec


# --------------------------------------------------------- recompile watch
class CompileWatch:
    """Executable-cache growth detector for one jitted callable.

    ``_cache_size()`` is the source of truth (it counts the compiled
    entries the dispatch path actually consults); a ``jax.monitoring``
    event listener corroborates with a backend-compile event count where
    the API exists. Both degrade to None/0 silently — a watch must never
    take down training."""

    def __init__(self, fn, use_monitoring: bool = True):
        self.fn = fn
        self.last: Optional[int] = None
        self.compile_events = 0
        self._listener = None
        if use_monitoring:
            self._install_listener()

    def _install_listener(self) -> None:
        def _on_event(event, **kw):
            if "compile" in str(event):
                self.compile_events += 1

        try:
            import jax.monitoring

            jax.monitoring.register_event_listener(_on_event)
            self._listener = _on_event
        except Exception:
            self._listener = None

    def cache_size(self) -> Optional[int]:
        try:
            return int(self.fn._cache_size())
        except Exception:
            return None

    def poll(self) -> Optional[Tuple[int, int]]:
        """(entries grown, current size) when the cache grew since the
        last poll, else None. The first successful poll adopts the
        current size as the baseline."""
        size = self.cache_size()
        if size is None:
            return None
        if self.last is None:
            self.last = size
            return None
        if size > self.last:
            grown = size - self.last
            self.last = size
            return (grown, size)
        self.last = size
        return None

    def close(self) -> None:
        if self._listener is not None:
            try:
                from jax._src import monitoring as _monitoring

                _monitoring._unregister_event_listener_by_callback(
                    self._listener)
            except Exception:
                pass
            self._listener = None


# ------------------------------------------------------- live-memory reads
def live_array_summary() -> Dict[str, Any]:
    """Host view of every live device buffer this process holds:
    ``live_count`` / ``live_bytes`` totals plus a ``live_bytes_<dtype>``
    breakdown. {} when the runtime refuses the enumeration."""
    import jax

    try:
        arrays = jax.live_arrays()
    except Exception:
        return {}
    total = 0
    by_dtype: Dict[str, int] = {}
    for arr in arrays:
        try:
            nbytes = int(arr.nbytes)
            dtype = str(arr.dtype)
        except Exception:
            continue
        total += nbytes
        by_dtype[dtype] = by_dtype.get(dtype, 0) + nbytes
    out: Dict[str, Any] = {"live_count": len(arrays),
                           "live_bytes": int(total)}
    for dtype in sorted(by_dtype):
        out[f"live_bytes_{dtype}"] = int(by_dtype[dtype])
    return out


def device_memory_summary() -> Dict[str, int]:
    """Allocator stats summed over addressable devices (bytes_in_use /
    peak_bytes_in_use / bytes_limit where the backend reports them,
    plus how many devices did). {} on backends without memory_stats
    (CPU) — the live-memory watch then runs on live_arrays alone."""
    import jax

    try:
        devices = jax.local_devices()
    except Exception:
        return {}
    totals: Dict[str, int] = {}
    reporting = 0
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        reporting += 1
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            val = stats.get(key)
            if isinstance(val, (int, float)) and math.isfinite(val):
                totals[key] = totals.get(key, 0) + int(val)
    if reporting:
        totals["devices_reporting"] = reporting
    return totals


# ----------------------------------------------------------------- facade
class MemWatch:
    """Trainer-facing compile/memory watch (``--obs-mem``).

    ``account(fn, *args)`` AOT-lowers and compiles ``fn`` at the args'
    shapes, logs one fsync'd "compile" record, and memoizes by shape key
    — one record per distinct dispatch shape for the life of the run.
    ``attach(fn)`` arms the CompileWatch on the jitted step;
    ``poll(step, fn=..., args=...)`` is the sync-point hook: accounts a
    never-seen dispatch shape, logs a "compile" recompile record per
    cache growth, samples live memory every ``mem_interval`` steps, and
    feeds the monitor (observe_compile / observe_memory) AFTER each
    record is durably written — so an AnomalyHalt raised here never
    loses its evidence. Everything degrades to a logger warning; the
    watch must never take down training."""

    def __init__(self, metrics=None, monitor=None, mem_interval: int = 50,
                 logger=None):
        self.metrics = metrics
        self.monitor = monitor
        self.mem_interval = max(1, int(mem_interval))
        self.logger = logger
        self.watch: Optional[CompileWatch] = None
        self.recompile_count = 0
        # shape_key -> its "compile" record (memo: one AOT compile and
        # one record per distinct dispatch shape).
        self.shapes: Dict[str, Dict[str, Any]] = {}
        self._last_mem_step: Optional[int] = None

    # ------------------------------------------------- compile accounting
    def account(self, fn, *args, shape_key: Optional[str] = None,
                step: int = 0, log: bool = True) -> Optional[Dict[str, Any]]:
        """AOT lower+compile ``fn`` at ``args``' shapes (ShapeDtypeStructs
        welcome — nothing executes) and build one "compile" record;
        memoized per shape key. Returns the record (also when memoized),
        or None when the backend refuses."""
        key = batch_shape_key(args) if shape_key is None else str(shape_key)
        if key in self.shapes:
            return self.shapes[key]
        try:
            t0 = time.perf_counter()
            lowered = fn.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception as e:
            if self.logger is not None:
                self.logger.warning("compile accounting failed: %s", e)
            return None
        rec = compile_record(compiled, shape_key=key,
                             lower_s=t1 - t0, compile_s=t2 - t1)
        rec["step"] = int(step)
        rec["shape_index"] = len(self.shapes)
        self.shapes[key] = rec
        if log:
            self.log_compile(rec)
        return rec

    def log_compile(self, rec: Dict[str, Any]) -> None:
        """Durably write one "compile" record (fsync'd — compile
        evidence must survive the halt it may be about to trigger)."""
        if self.metrics is not None:
            self.metrics.log("compile", flush=True, **rec)

    @property
    def peak_hbm_bytes(self) -> Optional[int]:
        """The largest per-shape peak-HBM estimate seen so far (what the
        manifest stamps)."""
        peaks = [rec.get("peak_hbm_bytes") for rec in self.shapes.values()]
        peaks = [p for p in peaks if isinstance(p, (int, float))]
        return int(max(peaks)) if peaks else None

    # --------------------------------------------------------- sync hook
    def attach(self, fn) -> None:
        """Arm the recompile watch on the jitted step callable."""
        self.watch = CompileWatch(fn)

    def poll(self, step: int, fn=None, args=None) -> None:
        """Sync-point hook (the step is already synced; no device reads
        beyond live_arrays/memory_stats). May raise AnomalyHalt via the
        monitor — after every record is durably written."""
        if fn is not None and args is not None:
            key = batch_shape_key(args)
            if key not in self.shapes:
                self.account(fn, *args, shape_key=key, step=step)
        self._poll_recompile(step)
        if (self._last_mem_step is None
                or step - self._last_mem_step >= self.mem_interval):
            self._last_mem_step = int(step)
            self.sample(step)

    def _poll_recompile(self, step: int) -> None:
        if self.watch is None:
            return
        growth = self.watch.poll()
        if growth is not None:
            grown, size = growth
            self.recompile_count += grown
            rec = {
                "event": "recompile", "step": int(step),
                "cache_size": int(size),
                "recompile_count": int(self.recompile_count),
                "compile_events": int(self.watch.compile_events),
            }
            if self.metrics is not None:
                self.metrics.log("compile", flush=True, **rec)
        if self.monitor is not None and self.watch.last is not None:
            self.monitor.observe_compile(
                step, cache_size=self.watch.last,
                grew=growth is not None)

    # ------------------------------------------------------- mem sampling
    def sample(self, step: int) -> Dict[str, Any]:
        """One live-memory window: "mem" record (sampled — not fsync'd)
        then the leak/headroom rules."""
        rec: Dict[str, Any] = {"step": int(step)}
        rec.update(live_array_summary())
        rec.update(device_memory_summary())
        in_use, limit = rec.get("bytes_in_use"), rec.get("bytes_limit")
        if in_use and limit:
            rec["headroom_frac"] = round(float(in_use) / float(limit), 6)
        rec["recompile_count"] = int(self.recompile_count)
        if self.metrics is not None:
            self.metrics.log("mem", **rec)
        if self.monitor is not None:
            self.monitor.observe_memory(
                step, live_bytes=rec.get("live_bytes"),
                bytes_in_use=in_use, bytes_limit=limit)
        return rec

    def close(self) -> None:
        if self.watch is not None:
            self.watch.close()
            self.watch = None
