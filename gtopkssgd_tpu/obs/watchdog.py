"""Dispatch stall watchdog — fail fast with a diagnostic, never hang blind.

The failure mode this exists for: a step is dispatched, the device stops
answering, and the next host-side read of a device value blocks FOREVER
inside the PJRT client — the run hangs with zero diagnostics. A blocked
C-extension call cannot be interrupted from Python, so the only honest
remedy is a monitor THREAD that notices the main thread has been waiting
too long, emits a
structured diagnostic record (last completed step, phase means, backend
info), and fails the process fast so whatever supervises the run can
restart it.

Protocol (trainer.train wires this up):

    wd.arm("train_step", step=s)      # entering a region that must make
                                      # progress within deadline_s
    wd.heartbeat(step=s)              # progress proof — resets the clock
                                      # (call AFTER a blocking device read,
                                      # not after an async dispatch: an
                                      # enqueue succeeding proves nothing)
    wd.disarm()                       # leaving the region

Device/backend info is captured EAGERLY at construction: querying a wedged
backend from the monitor thread could itself hang.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Optional

# Exit code for a detected stall — distinct from generic failure so the
# driver/retry loop can classify hung runs without parsing logs.
# Single source: gtopkssgd_tpu/exit_codes.py (re-exported here under the
# historical name every consumer already imports).
from gtopkssgd_tpu.exit_codes import EXIT_STALL as STALL_EXIT_CODE


def _device_info() -> Dict[str, object]:
    try:
        import jax

        dev = jax.devices()[0]
        return {
            "backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", "?"),
            "device_count": jax.device_count(),
            "process_index": jax.process_index(),
        }
    except Exception as e:  # backend not initialized / already dead
        return {"error": repr(e)}


def _default_on_stall(record: Dict[str, object]) -> None:
    """Last-resort action: dump the diagnostic to stderr and hard-exit.
    os._exit, not sys.exit — the main thread is blocked in a C call and
    will never run an exception handler or atexit hook."""
    print("STALL WATCHDOG: " + json.dumps(record), file=sys.stderr,
          flush=True)
    os._exit(STALL_EXIT_CODE)


class StallWatchdog:
    """Monitor thread that fires when an armed region exceeds its deadline.

    ``on_stall(record)`` is called ONCE (from the monitor thread) with the
    structured diagnostic; the default dumps it to stderr and hard-exits
    with STALL_EXIT_CODE. ``diagnostics`` is an optional zero-arg callable
    whose dict is merged into the record at fire time (the trainer passes
    its span phase-means through here) — it must only touch host-side
    state, never the device."""

    def __init__(
        self,
        deadline_s: float,
        *,
        on_stall: Optional[Callable[[Dict[str, object]], None]] = None,
        diagnostics: Optional[Callable[[], Dict[str, object]]] = None,
        poll_s: Optional[float] = None,
    ):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self.poll_s = poll_s if poll_s is not None else min(
            1.0, self.deadline_s / 4)
        self._on_stall = on_stall or _default_on_stall
        self._diagnostics = diagnostics
        self.device_info = _device_info()
        self._lock = threading.Lock()
        self._armed_at: Optional[float] = None
        self._label: Optional[str] = None
        self._armed_step: Optional[int] = None
        self._last_step: Optional[int] = None
        self._fired = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="obs-stall-watchdog")
        self._thread.start()

    # ------------------------------------------------------------- control
    def arm(self, label: str, step: Optional[int] = None) -> None:
        with self._lock:
            self._armed_at = time.monotonic()
            self._label = label
            self._armed_step = step

    def heartbeat(self, step: Optional[int] = None) -> None:
        """Progress proof: resets the deadline clock; records the last
        step known complete. No-op when disarmed."""
        with self._lock:
            if step is not None:
                self._last_step = step
            if self._armed_at is not None:
                self._armed_at = time.monotonic()

    def disarm(self) -> None:
        with self._lock:
            self._armed_at = None
            self._label = None

    @contextmanager
    def watch(self, label: str, step: Optional[int] = None):
        self.arm(label, step)
        try:
            yield self
        finally:
            self.disarm()

    @property
    def fired(self) -> bool:
        return self._fired.is_set()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2 * self.poll_s + 1.0)

    # ------------------------------------------------------------- monitor
    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            with self._lock:
                armed_at = self._armed_at
                label = self._label
                armed_step = self._armed_step
                last_step = self._last_step
            if armed_at is None or self._fired.is_set():
                continue
            waited = time.monotonic() - armed_at
            if waited < self.deadline_s:
                continue
            record: Dict[str, object] = {
                "kind": "stall",
                "time": time.time(),
                "label": label,
                "waited_s": round(waited, 3),
                "deadline_s": self.deadline_s,
                "armed_step": armed_step,
                "last_completed_step": last_step,
                "device": self.device_info,
            }
            if self._diagnostics is not None:
                try:
                    record.update(self._diagnostics() or {})
                except Exception as e:
                    record["diagnostics_error"] = repr(e)
            self._fired.set()
            self._on_stall(record)
