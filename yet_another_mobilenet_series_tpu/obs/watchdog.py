"""Stall watchdog: a heartbeat thread that turns a silent hang into a
post-mortem file.

The motivating failure is a hang nobody sees: a collective that never
completes because one replica died, an input pipeline stalled on a dead
mount, a device that stops answering. The train loop blocks forever inside
a dispatch (or ``next(train_iter)``), nothing is logged, and the job dies
only when the scheduler reaps it. The watchdog is
armed by the train loop at every completed step (and at eval/checkpoint/
rematerialize progress events, whose host time legitimately dwarfs a step);
when no heartbeat lands within the configured deadline it writes
``hang_report.json`` to the log dir — open spans from the tracer, the last
completed step and phase, a full registry snapshot, and every thread's stack
— then keeps the process untouched (the job still dies; now it dies loud).

The report is written at most once per process: a hang is a terminal state,
and re-dumping every poll interval would only shred the first, most accurate
stack capture.
"""

# yamt-lint: disable-file=YAMT019 — lock-free by design: arm() publishes the
# heartbeat fields (_beat_ns/_step/_phase) as single GIL-atomic stores from
# one writer (the train loop), and the poll thread tolerates a torn trio or a
# stale read for exactly one poll interval; _fired/_info follow the same
# single-writer publish discipline (docs/LINT.md "Concurrency rules").

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback

from .registry import MetricsRegistry
from .trace import SpanTracer

REPORT_NAME = "hang_report.json"


class StallWatchdog:
    def __init__(
        self,
        log_dir: str,
        deadline_s: float,
        *,
        tracer: SpanTracer | None = None,
        registry: MetricsRegistry | None = None,
        poll_s: float = 0.0,
        logger=None,
        info_providers: dict | None = None,
    ):
        if deadline_s <= 0:
            raise ValueError(f"watchdog deadline must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self.poll_s = float(poll_s) if poll_s > 0 else max(min(deadline_s / 4.0, 1.0), 0.05)
        self.report_path = os.path.join(log_dir, REPORT_NAME)
        self._tracer = tracer
        self._registry = registry
        self._logger = logger
        self._beat_ns: int | None = None
        self._step: int | None = None
        self._phase = "startup"
        self._fired = False
        # name -> zero-arg callable whose return value lands in the report's
        # "info" section (e.g. serving: batcher threads, window occupancy,
        # breaker state); a provider that raises contributes its error string
        # instead of taking the report down
        self._info: dict = dict(info_providers or {})
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="yamt-obs-watchdog", daemon=True)

    # -- train-loop surface --------------------------------------------------

    def start(self) -> None:
        # arm immediately: a backend or collective that hangs before step 1
        # completes is exactly the hang this exists for (deadline must therefore exceed
        # the first step's compile time — docs/OBSERVABILITY.md tuning)
        self.arm(step=None, phase="startup")
        self._thread.start()

    def register_info(self, name: str, fn) -> None:
        """Attach a named state provider to future hang reports (the serving
        stack registers breaker/queue/window state here — docs/SERVING.md)."""
        self._info[name] = fn

    def arm(self, step: int | None = None, phase: str = "step") -> None:
        """Heartbeat: "the loop made progress". Called per completed train
        step and at eval/checkpoint/rematerialize boundaries."""
        if step is not None:
            self._step = step
        self._phase = phase
        self._beat_ns = time.monotonic_ns()

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=max(self.poll_s * 4, 1.0))

    @property
    def fired(self) -> bool:
        return self._fired

    # -- watchdog thread -----------------------------------------------------

    def _run(self) -> None:
        # top-level guard (yamt-lint YAMT011): a crashed watchdog thread is a
        # silently-disarmed alarm — at least say so on the way down
        try:
            self._run_inner()
        except Exception:  # noqa: BLE001 — terminal for the thread; be loud
            sys.stderr.write("WATCHDOG: thread crashed:\n" + traceback.format_exc())

    def _run_inner(self) -> None:
        while not self._stop.wait(self.poll_s):
            beat = self._beat_ns
            if beat is None or self._fired:
                continue
            elapsed = (time.monotonic_ns() - beat) / 1e9
            if elapsed <= self.deadline_s:
                continue
            self._fired = True
            try:
                self._dump(elapsed)
                msg = (
                    f"WATCHDOG: no progress for {elapsed:.1f}s "
                    f"(deadline {self.deadline_s:.1f}s, last phase "
                    f"'{self._phase}', last step {self._step}); wrote {self.report_path}"
                )
                if self._logger is not None:
                    self._logger.error(msg)
                else:
                    sys.stderr.write(msg + "\n")
            except Exception:
                sys.stderr.write("WATCHDOG: failed to write hang report:\n" + traceback.format_exc())

    def _dump(self, elapsed_s: float) -> None:
        names = {t.ident: t.name for t in threading.enumerate()}
        threads = {
            f"{names.get(tid, 'thread')}-{tid}": traceback.format_stack(frame)
            for tid, frame in sys._current_frames().items()
        }
        info = {}
        for name, fn in self._info.items():
            try:
                info[name] = fn()
            except Exception as e:  # noqa: BLE001 — a dying provider must not kill the report
                info[name] = f"provider failed: {type(e).__name__}: {e}"
        # the device-side compile/cost table (obs/device.py): a hang during
        # or right after a compile names WHICH executable was last built and
        # what the compiler said it costs — memory gauges ride in the
        # registry snapshot below
        from .device import compile_report

        report = {
            "seconds_since_last_beat": elapsed_s,
            "deadline_s": self.deadline_s,
            "last_step": self._step,
            "last_phase": self._phase,
            "open_spans": self._tracer.open_spans() if self._tracer is not None else [],
            "registry": self._registry.snapshot() if self._registry is not None else {},
            "executables": compile_report(),
            "threads": threads,
            "info": info,
        }
        tmp = f"{self.report_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=1)
        os.replace(tmp, self.report_path)
