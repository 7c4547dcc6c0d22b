"""Step health guard: survive non-finite steps instead of dying on them.

A single NaN loss — one rotten batch, one overflow in a bf16 reduction, one
cosmic-ray bit — used to kill a multi-day run at the next log boundary
(cli/train.py raised FloatingPointError). The guard turns that into a
bounded skip: the step's update is REJECTED and the pre-step TrainState
restored, on device, inside the compiled program (:func:`wrap_step_fn` —
a per-leaf ``where`` select on the step's own finiteness verdict, fused by
XLA; no extra host syncs and no second program). The step counter still
advances, so the LR schedule, data-order resume arithmetic, and the host
step counter stay aligned — the bad batch is consumed and skipped, exactly
like a corrupt record in the data pipeline.

The host half (:class:`StepGuard`) reads the per-step verdicts once per
``train.log_every`` boundary — the metrics are already synced there, so the
guard adds zero forced syncs — counts them (``train.skipped_steps`` /
``train.nonfinite_events``), and aborts with :class:`TrainHealthError`
after ``train.guard.max_skipped_steps`` total skips, dumping a
``train_health.json`` post-mortem (the watchdog hang_report.json's sibling:
bounded recovery, then a loud, attributable death instead of either a
silent crash or an unbounded NaN treadmill). ``info()`` plugs into the
stall watchdog's info providers so a hang report also shows the guard
state.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from ..obs.registry import get_registry
from ..obs.scopes import scope

HEALTH_REPORT_NAME = "train_health.json"


class TrainHealthError(RuntimeError):
    """More non-finite steps than train.guard.max_skipped_steps tolerates —
    the run is systematically unhealthy (LR blowup, poisoned data, broken
    kernel), not transiently unlucky. train_health.json has the post-mortem."""


def wrap_step_fn(step_fn):
    """Wraps an UN-JITTED (ts, batch, rng) -> (ts, metrics) step with the
    device-side skip: when the step's loss or grad norm is non-finite, every
    TrainState field except ``step`` is rolled back to its pre-step value.
    Must wrap INSIDE the jit boundary (parallel/dp.py does) — outside it the
    donated pre-step buffers would already be gone.

    Adds a ``skipped`` metric (1.0 = this step was rejected). The verdict is
    computed from the pmean'd metrics, so every replica selects the same
    branch and replicated state stays replicated.
    """

    def guarded(ts, batch, rng):
        new_ts, metrics = step_fn(ts, batch, rng)
        with scope("guard"):
            ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(metrics["grad_norm"])
            rolled = jax.tree.map(lambda new, old: jnp.where(ok, new, old), new_ts, ts)
        # the step counter always advances: LR schedule, RNG folding, and the
        # resume data-order arithmetic count CONSUMED batches, not applied
        # updates
        rolled = rolled.replace(step=new_ts.step)
        metrics = dict(metrics, skipped=1.0 - ok.astype(jnp.float32))
        return rolled, metrics

    return guarded


class StepGuard:
    """Host-side accounting for the guarded step. ``observe`` stashes the
    lazy per-step ``skipped`` verdicts (device arrays — nothing syncs);
    ``check`` reads them at the log cadence, right after the metric snapshot
    already forced the same arrays, and enforces the skip bound."""

    def __init__(self, gc, log_dir: str | None, logger=None):
        self.max_skipped = int(gc.max_skipped_steps)
        self._log_dir = log_dir  # None on non-coordinator hosts: no dump
        self._logger = logger
        self._pending: list[tuple[int, object]] = []
        self.skipped_total = 0
        self.skipped_steps: list[int] = []  # recent skip step indices (bounded)

    def observe(self, step_i: int, metrics: dict) -> None:
        self._pending.append((step_i, metrics.get("skipped")))

    def check(self, step_i: int) -> None:
        """Called at the log boundary (and once at loop exit). Raises
        TrainHealthError — after dumping train_health.json — when the total
        skip count exceeds the bound."""
        pending, self._pending = self._pending, []
        bad = [s for s, v in pending if v is not None and float(v) > 0.0]
        if bad:
            reg = get_registry()
            reg.counter("train.skipped_steps").inc(len(bad))
            reg.counter("train.nonfinite_events").inc()
            self.skipped_total += len(bad)
            self.skipped_steps = (self.skipped_steps + bad)[-64:]
            if self._logger is not None:
                self._logger.log(
                    f"step guard: {len(bad)} non-finite step(s) skipped and rolled "
                    f"back at {bad} ({self.skipped_total}/{self.max_skipped} budget used)"
                )
        if self.skipped_total > self.max_skipped:
            path = self._dump(step_i)
            raise TrainHealthError(
                f"{self.skipped_total} non-finite steps exceed "
                f"train.guard.max_skipped_steps={self.max_skipped}"
                + (f"; post-mortem in {path}" if path else "")
            )

    def info(self) -> dict:
        """Watchdog info provider: guard state for hang_report.json."""
        return {
            "skipped_total": self.skipped_total,
            "max_skipped_steps": self.max_skipped,
            "recent_skipped_steps": list(self.skipped_steps),
        }

    def _dump(self, step_i: int) -> str | None:
        if not self._log_dir:
            return None
        report = {
            "reason": "non-finite step budget exceeded",
            "last_step": step_i,
            "skipped_total": self.skipped_total,
            "max_skipped_steps": self.max_skipped,
            "recent_skipped_steps": list(self.skipped_steps),
            "registry": get_registry().snapshot(),
        }
        path = os.path.join(self._log_dir, HEALTH_REPORT_NAME)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(report, f, indent=1)
            os.replace(tmp, path)
        except OSError as e:
            if self._logger is not None:
                self._logger.error(f"could not write {HEALTH_REPORT_NAME}: {e}")
            return None
        return path
