"""Failure-recovery tests (SURVEY.md §5 failure detection / §4.3).

Two halves:

- **training** (slow): SIGKILL a training process mid-run, then verify a
  relaunch resumes cleanly from the latest checkpoint and finishes — the
  preemption-recovery story of the framework (gang-scheduled SPMD: a dead
  process means relaunch + resume).
- **serving** (fast, tier-1): seeded chaos via serve/faults.py against the
  admission/retry/breaker/drain stack (serve/admission.py,
  serve/batcher.py) — engine failures hit only their own clients, retries
  absorb transients, the breaker opens on a failure streak and recovers
  through its half-open probe, an injected hang trips the drain timeout
  instead of hanging shutdown, and under mixed chaos NO client call ever
  hangs: every future resolves to a result or a typed error. The fault
  schedule is deterministic (seeded), so these are regression tests, not
  flaky chaos monkeys.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from yet_another_mobilenet_series_tpu.obs.registry import get_registry
from yet_another_mobilenet_series_tpu.serve.admission import (
    AdmissionController,
    BreakerOpen,
    BREAKER_CLOSED,
    BREAKER_OPEN,
)
from yet_another_mobilenet_series_tpu.serve.batcher import DeadlineExceeded, DrainTimeout, QueueFull
from yet_another_mobilenet_series_tpu.serve.faults import FaultyEngine, InjectedFault
from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRIVER = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["TF_CPP_MIN_LOG_LEVEL"] = "2"
from yet_another_mobilenet_series_tpu.cli.train import main
main(sys.argv[1:])
"""


def _args(log_dir, epochs):
    return [
        "data.dataset=fake", "data.image_size=24", "data.fake_train_size=320", "data.fake_eval_size=32",
        "model.arch=mobilenet_v2", "model.num_classes=4", "model.dropout=0.0",
        "model.block_specs=[{t: 2, c: 8, n: 1, s: 2}]",
        "train.batch_size=32", "train.eval_batch_size=32", "train.log_every=5",
        "train.compute_dtype=float32", f"train.log_dir={log_dir}",
        "train.eval_every_epochs=100",  # keep the victim run simple
        "schedule.base_lr=0.02", "schedule.warmup_epochs=0", "schedule.scale_by_batch=false",
        "dist.num_devices=8", f"train.epochs={epochs}",
    ]


# ---------------------------------------------------------------------------
# serve-side chaos (fast, tier-1): serve/faults.py against the resilience edge
# ---------------------------------------------------------------------------


def _row_id_predict(images):
    return images[:, 0, 0, :1]


class _EchoEngine:
    """Pure-host engine protocol double: logits echo each image's id plane,
    so row routing survives any amount of chaos re-batching."""

    def predict_async(self, images):
        class _Handle:
            def result(_self):
                return _row_id_predict(images)

        return _Handle()

    def predict(self, images):
        return self.predict_async(images).result()


def _img(val=0.0):
    return np.full((4, 4, 3), float(val), np.float32)


def _batcher(engine, **kw):
    kw.setdefault("max_batch", 1)  # one request per dispatch: fault schedule == request order
    kw.setdefault("max_wait_ms", 0.0)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("drain_timeout_s", 2.0)
    return PipelinedBatcher(engine, **kw).start()


def test_faulty_engine_schedule_is_deterministic():
    """Same seed -> bitwise-identical fault schedule; different seed differs
    (the chaos suite is a regression suite, not a dice roll)."""
    def schedule(seed):
        eng = FaultyEngine(_EchoEngine(), seed=seed, failure_rate=0.3, latency_s=0.001, latency_rate=0.2)
        out = []
        for _ in range(64):
            try:
                eng.predict(_img()[None])  # direct engine call: batched input
                out.append("ok")
            except InjectedFault:
                out.append("fail")
        return out

    assert schedule(7) == schedule(7)
    assert schedule(7) != schedule(11)


def test_latency_after_n_gates_the_degrade_onset():
    """latency_after_n: the first N dispatches run CLEAN, then the latency
    injection begins — the mid-run gray-failure knob (a replica that was
    healthy when the router learned its baseline, then degraded)."""
    reg = get_registry()
    eng = FaultyEngine(_EchoEngine(), seed=0, latency_s=0.05, latency_rate=1.0,
                       latency_after_n=3)
    d0 = reg.snapshot().get("serve.faults.delays", 0)
    clean_t0 = time.perf_counter()
    for _ in range(3):
        eng.predict(_img()[None])
    clean_s = time.perf_counter() - clean_t0
    assert reg.snapshot().get("serve.faults.delays", 0) == d0  # onset not reached
    t0 = time.perf_counter()
    eng.predict(_img()[None])  # dispatch #3: the onset
    assert time.perf_counter() - t0 >= 0.05
    assert reg.snapshot().get("serve.faults.delays", 0) == d0 + 1
    assert clean_s < 0.05  # the pre-onset dispatches really were undelayed


@pytest.mark.parametrize("fail_at", ["dispatch", "result"])
def test_fail_n_batches_only_those_clients_error(fail_at):
    """The first N dispatches fail (at either failure edge): exactly those
    clients see the error, everyone after gets correct rows — the engine
    failure stays contained to its own batch."""
    eng = FaultyEngine(_EchoEngine(), fail_first_n=2, fail_at=fail_at)
    b = _batcher(eng)
    try:
        outcomes = []
        for i in range(6):
            fut = b.submit(_img(i))
            try:
                outcomes.append(float(fut.result(timeout=10)[0]))
            except InjectedFault:
                outcomes.append("fault")
        assert outcomes == ["fault", "fault", 2.0, 3.0, 4.0, 5.0]
    finally:
        b.stop()


def test_retry_absorbs_transient_failures():
    """A transient failure costs a bounded retry, not a client error:
    fail-1-then-recover resolves correctly with serve.retries counted."""
    eng = FaultyEngine(_EchoEngine(), fail_first_n=1)
    b = _batcher(eng)
    ac = AdmissionController(b, max_retries=2, retry_backoff_ms=1.0, breaker_threshold=10)
    base = get_registry().snapshot()
    try:
        assert float(ac.submit(_img(3)).result(timeout=10)[0]) == 3.0
    finally:
        b.stop()
    snap = get_registry().snapshot()
    assert snap["serve.retries"] - base.get("serve.retries", 0) == 1
    assert snap["serve.retries.interactive"] - base.get("serve.retries.interactive", 0) == 1
    assert snap["serve.completed.interactive"] - base.get("serve.completed.interactive", 0) == 1


def test_retries_are_bounded():
    """A hard-down engine exhausts max_retries and surfaces the error —
    never an unbounded retry loop."""
    eng = FaultyEngine(_EchoEngine(), failure_rate=1.0)
    b = _batcher(eng)
    ac = AdmissionController(b, max_retries=2, retry_backoff_ms=1.0, breaker_threshold=100)
    base = get_registry().snapshot()
    try:
        with pytest.raises(InjectedFault):
            ac.submit(_img()).result(timeout=10)
    finally:
        b.stop()
    snap = get_registry().snapshot()
    assert snap["serve.retries"] - base.get("serve.retries", 0) == 2  # bounded: 1 try + 2 retries
    assert snap["serve.engine_failures"] - base.get("serve.engine_failures", 0) == 3


def test_breaker_opens_on_streak_and_recovers_via_probe():
    """The full breaker lifecycle: a failure streak opens it (fast-fail, no
    engine traffic), the cooldown admits ONE half-open probe, probe success
    closes it and traffic resumes."""
    eng = FaultyEngine(_EchoEngine(), fail_first_n=3)
    b = _batcher(eng)
    ac = AdmissionController(b, max_retries=0, breaker_threshold=3, breaker_cooldown_s=0.15)
    reg = get_registry()
    base = reg.snapshot()
    try:
        for _ in range(3):
            with pytest.raises(InjectedFault):
                ac.submit(_img()).result(timeout=10)
        assert ac.breaker.state == BREAKER_OPEN
        assert reg.snapshot()["serve.breaker_state"] == BREAKER_OPEN
        dispatched_when_open = eng._idx
        with pytest.raises(BreakerOpen):
            ac.submit(_img())
        assert eng._idx == dispatched_when_open  # fast fail: the engine saw nothing
        time.sleep(0.2)  # cooldown elapses -> next arrival is the probe
        assert float(ac.submit(_img(9)).result(timeout=10)[0]) == 9.0
        assert ac.breaker.state == BREAKER_CLOSED
        assert reg.snapshot()["serve.breaker_state"] == BREAKER_CLOSED
        assert float(ac.submit(_img(4)).result(timeout=10)[0]) == 4.0  # traffic resumed
    finally:
        b.stop()
    snap = reg.snapshot()
    assert snap["serve.breaker_opens"] - base.get("serve.breaker_opens", 0) == 1
    assert snap["serve.rejected_breaker"] - base.get("serve.rejected_breaker", 0) == 1


def test_failed_probe_reopens_breaker():
    """A half-open probe that fails re-opens the breaker for another full
    cooldown instead of closing it."""
    eng = FaultyEngine(_EchoEngine(), fail_first_n=4)  # streak of 3 + the probe
    b = _batcher(eng)
    ac = AdmissionController(b, max_retries=0, breaker_threshold=3, breaker_cooldown_s=0.15)
    try:
        for _ in range(3):
            with pytest.raises(InjectedFault):
                ac.submit(_img()).result(timeout=10)
        time.sleep(0.2)
        with pytest.raises(InjectedFault):  # the probe itself fails
            ac.submit(_img()).result(timeout=10)
        assert ac.breaker.state == BREAKER_OPEN
        with pytest.raises(BreakerOpen):  # re-opened: fast fail again
            ac.submit(_img())
        time.sleep(0.2)  # second cooldown; engine recovered by now
        assert float(ac.submit(_img(5)).result(timeout=10)[0]) == 5.0
        assert ac.breaker.state == BREAKER_CLOSED
    finally:
        b.stop()


def test_injected_hang_trips_drain_timeout():
    """A wedged engine cannot hang shutdown: stop(drain=True) fails the
    still-unresolved requests with DrainTimeout within drain_timeout_s and
    abandons the wedged (daemon) worker."""
    eng = FaultyEngine(_EchoEngine(), hang_at=0)
    b = _batcher(eng, drain_timeout_s=0.5)
    reg = get_registry()
    base = reg.snapshot()
    futs = [b.submit(_img(i)) for i in range(3)]
    time.sleep(0.1)  # first batch dispatched and wedged
    t0 = time.perf_counter()
    b.stop()
    stop_s = time.perf_counter() - t0
    assert stop_s < 3.0, f"stop took {stop_s:.1f}s — the drain bound did not hold"
    for fut in futs:
        with pytest.raises((DrainTimeout, RuntimeError)):
            fut.result(timeout=1)
    snap = reg.snapshot()
    assert snap["serve.drain_timeouts"] - base.get("serve.drain_timeouts", 0) == 1
    assert snap["serve.faults.hangs"] - base.get("serve.faults.hangs", 0) == 1


def test_hang_release_recovers():
    """hang-until-event is a hang, not a kill: releasing the event serves
    the wedged batch for real."""
    eng = FaultyEngine(_EchoEngine(), hang_at=0)
    b = _batcher(eng, drain_timeout_s=5.0)
    try:
        fut = b.submit(_img(8))
        time.sleep(0.05)
        assert not fut.done()
        eng.hang_release.set()
        assert float(fut.result(timeout=10)[0]) == 8.0
    finally:
        b.stop()


def test_mixed_chaos_no_client_ever_hangs():
    """The acceptance criterion: under seeded failures + latency spikes,
    with retries, deadlines, and concurrent clients, EVERY call resolves —
    a result or a typed error, never a hang — and the books balance."""
    eng = FaultyEngine(_EchoEngine(), seed=3, failure_rate=0.25, latency_s=0.01, latency_rate=0.3)
    b = _batcher(eng, max_batch=4, max_wait_ms=1.0, drain_timeout_s=5.0)
    ac = AdmissionController(
        b, max_retries=2, retry_backoff_ms=1.0, breaker_threshold=50, breaker_cooldown_s=0.1
    )
    classes = ("interactive", "batch", "best_effort")
    outcomes = {"ok": 0, "typed_error": 0, "rejected": 0}
    lock = threading.Lock()

    def client(i):
        try:
            fut = ac.submit(_img(i), priority=classes[i % 3], deadline_ms=5000.0)
        except (QueueFull, BreakerOpen) as e:  # typed arrival rejection
            with lock:
                outcomes["rejected"] += 1
            return
        try:
            val = fut.result(timeout=30)  # a hang fails the test right here
            assert float(val[0]) == float(i)
            with lock:
                outcomes["ok"] += 1
        except (InjectedFault, DeadlineExceeded, DrainTimeout):
            with lock:
                outcomes["typed_error"] += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(40)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "a client hung"
    b.stop()
    assert sum(outcomes.values()) == 40  # every call resolved, one way or another
    assert outcomes["ok"] > 0  # chaos did not take the service down


@pytest.mark.slow
def test_sigkill_midrun_then_resume(tmp_path):
    log_dir = str(tmp_path / "run")
    env = dict(os.environ, PYTHONPATH=REPO)

    # victim: many epochs, checkpointing every epoch
    victim = subprocess.Popen(
        [sys.executable, "-c", _DRIVER] + _args(log_dir, epochs=50),
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    # wait until at least one checkpoint is fully written, then SIGKILL
    deadline = time.time() + 300
    ckpt_dir = os.path.join(log_dir, "ckpt")
    seen = False
    while time.time() < deadline:
        if victim.poll() is not None:
            out = victim.stdout.read()
            pytest.fail(f"victim exited early:\n{out[-2000:]}")
        steps = [d for d in (os.listdir(ckpt_dir) if os.path.isdir(ckpt_dir) else []) if d.isdigit()]
        # orbax renames the tmp dir into place when complete
        if steps and all("tmp" not in d for d in steps):
            seen = True
            time.sleep(1.0)  # let another save start mid-flight for extra chaos
            break
        time.sleep(0.5)
    assert seen, "no checkpoint appeared within the deadline"
    victim.send_signal(signal.SIGKILL)
    victim.wait()
    victim.stdout.read()

    # relaunch with a small total epoch budget: must resume and complete
    out = subprocess.run(
        [sys.executable, "-c", _DRIVER] + _args(log_dir, epochs=6),
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "resumed at step" in out.stdout
    assert "done:" in out.stdout
