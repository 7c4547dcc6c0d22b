"""The one general load generator: closed loop and open loop, from a traffic
file's parameters and the run's seed. One generator thread submits; the
system's own completion thread stamps each answer through the future's
done-callback, so a latency ends when the request RESOLVED, not when some
collector got round to it.

- closed loop (`loop: closed`, `clients`): each client has one request out and
  sends its next when the answer is back. Callers that wait for their reply.
- open loop (`loop: open`, `rate_per_s`, `arrivals: poisson|uniform`, optional
  `burst_on_s`/`burst_off_s`): requests are due on a schedule whatever the
  system does. A latency runs from the DUE time, so a stall is charged to
  every request it delays, and how late the generator itself ran is reported
  (`late_ms`): a starved generator must not read as a fast server.

Every seed gets the same work in another order: the open loop's gaps are one
fixed set (drawn from `schedule_seed`) permuted by the seed, and the images
cycle through seeded permutations of the pool.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np


def arrival_times(rate_per_s: float, horizon_s: float, seed: int, *, arrivals: str = "poisson",
                  schedule_seed: int = 0, burst_on_s: float = 0.0, burst_off_s: float = 0.0) -> np.ndarray:
    """Due times in [0, horizon_s): round(rate x horizon) of them for every
    seed. With bursts the same mean rate arrives in on-stretches only."""
    n = int(round(rate_per_s * horizon_s))
    if n <= 0:
        return np.zeros(0)
    if arrivals == "uniform":
        gaps = np.full(n, 1.0)
    elif arrivals == "poisson":
        gaps = np.random.default_rng(schedule_seed).exponential(1.0, n)
    else:
        raise ValueError(f"arrivals must be poisson or uniform, got {arrivals!r}")
    gaps = np.random.default_rng(seed).permutation(gaps)
    times = np.cumsum(gaps) - gaps[0]
    bursty = burst_on_s > 0 and burst_off_s > 0
    on_total = horizon_s * burst_on_s / (burst_on_s + burst_off_s) if bursty else horizon_s
    times *= on_total / (times[-1] + gaps[0])  # the set of gaps fills the on-time exactly
    if bursty:  # on-time -> wall time: an off-stretch after every full on-stretch
        times += np.floor(times / burst_on_s) * burst_off_s
    return times


def image_order(pool: int, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.concatenate([rng.permutation(pool) for _ in range(n // pool + 1)])[:n]


@dataclass
class Records:
    """One row per request, times in seconds on the generator's clock."""

    loop: str
    due: list = field(default_factory=list)    # open loop: when it should go; closed: when it went
    sent: list = field(default_factory=list)
    done: list = field(default_factory=list)   # None until resolved
    ok: list = field(default_factory=list)

    def window(self, t0: float, t1: float) -> dict:
        """Everything answered (or failed, or still unanswered though due) in
        [t0, t1): the contract's attempted/failed and the latencies."""
        lat, attempted, failed = [], 0, 0
        late = []
        for due, sent, done, ok in zip(self.due, self.sent, self.done, self.ok):
            t = done if done is not None else due  # never answered: a miss where it was due
            if not (t0 <= t < t1):
                continue
            attempted += 1
            if done is None or not ok:
                failed += 1
                continue
            lat.append(done - due)
            late.append(sent - due)
        lat_ms = np.asarray(lat) * 1e3
        pct = (lambda q: float(np.percentile(lat_ms, q))) if len(lat_ms) else (lambda q: None)
        return {
            "attempted": attempted, "failed": failed, "completed": len(lat),
            "per_s": len(lat) / (t1 - t0),
            "p50_ms": pct(50), "p95_ms": pct(95), "p99_ms": pct(99),
            "late_p95_ms": float(np.percentile(np.asarray(late) * 1e3, 95)) if late else None,
        }


class LoadGen:
    """submit(image) -> concurrent.futures.Future; raises when it refuses."""

    def __init__(self, submit, images: list, params: dict, seed: int, duration_s: float, spans=None):
        self._submit = submit
        self._images = images
        self._p = params
        self._seed = seed
        self._duration = duration_s
        self._spans = spans
        self.records = Records(loop=params["loop"])
        self._thread = threading.Thread(target=self._run, name="bench-loadgen", daemon=True)
        self._t0 = 0.0
        self._error: Exception | None = None
        self._outstanding = 0
        self._lock = threading.Lock()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> float:
        """Returns the generator's time zero (time.perf_counter)."""
        self._t0 = time.perf_counter()
        self._thread.start()
        return self._t0

    def join(self, drain_s: float = 10.0) -> Records:
        self._thread.join()
        if self._error is not None:
            raise self._error
        deadline = time.perf_counter() + drain_s
        while self._outstanding and time.perf_counter() < deadline:
            time.sleep(0.005)
        return self.records

    def now(self) -> float:
        return time.perf_counter() - self._t0

    # -- the generator thread ----------------------------------------------

    def _run(self) -> None:
        try:
            (self._closed if self._p["loop"] == "closed" else self._open)()
        except Exception as e:  # noqa: BLE001 - the thread's boundary: handed to join(), which raises it
            self._error = e

    def _send(self, i: int, due: float, image, on_done=None) -> None:
        r = self.records
        r.due.append(due)
        r.done.append(None)
        r.ok.append(False)
        r.sent.append(self.now())

        def resolved(fut):
            r.ok[i] = fut.exception() is None
            r.done[i] = self.now()
            with self._lock:
                self._outstanding -= 1
            if on_done is not None:
                on_done()

        with self._lock:
            self._outstanding += 1
        try:
            with self._spans.span("submit") if self._spans is not None else contextlib.nullcontext():
                fut = self._submit(image)
        except Exception:  # noqa: BLE001 - a refusal is a failed request, not a crash
            r.done[i] = self.now()
            with self._lock:
                self._outstanding -= 1
            if on_done is not None:
                time.sleep(0.001)  # a refused closed-loop client backs off, as a real one would
                on_done()
            return
        fut.add_done_callback(resolved)

    def _closed(self) -> None:
        clients = int(self._p["clients"])
        free: queue.SimpleQueue = queue.SimpleQueue()
        for _ in range(clients):
            free.put(None)
        order = image_order(len(self._images), 1 << 16, self._seed)
        i = 0
        while True:
            left = self._duration - self.now()
            if left <= 0:
                return
            try:
                free.get(timeout=min(left, 0.05))
            except queue.Empty:
                continue
            now = self.now()
            self._send(i, now, self._images[order[i % len(order)]], on_done=lambda: free.put(None))
            i += 1

    def _open(self) -> None:
        p = self._p
        due = arrival_times(p["rate_per_s"], self._duration, self._seed,
                            arrivals=p.get("arrivals", "poisson"), schedule_seed=p.get("schedule_seed", 0),
                            burst_on_s=p.get("burst_on_s", 0.0), burst_off_s=p.get("burst_off_s", 0.0))
        order = image_order(len(self._images), len(due), self._seed)
        for i, t in enumerate(due):
            while True:
                ahead = t - self.now()
                if ahead <= 0:
                    break
                # sleep(0) yields the GIL to the system's threads while spinning
                time.sleep(ahead - 0.0005 if ahead > 0.001 else 0)
            self._send(i, float(t), self._images[order[i]])
