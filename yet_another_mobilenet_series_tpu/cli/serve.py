"""Serving entry point — ``python -m yet_another_mobilenet_series_tpu.cli.serve
app:<yaml> [key=value ...]`` (sibling of cli.train / cli.profile).

Three phases, all optional, driven by the ``serve:`` config block:

1. **export** (``serve.export_from`` set): checkpoint -> InferenceBundle at
   ``serve.bundle`` — prune masks hard-applied, EMA weights selected, BN
   folded into conv weights (serve/export.py). With
   ``serve.quant.weights=int8`` the export additionally runs the gated
   post-training quantization pass (seeded synthetic calibration batch
   normalized with ``data.mean/std``; refused below the top-1 gate).
2. **synthetic load** (``serve.requests`` > 0): load the bundle, AOT-warm
   the engine's (bucket, image_size) ladder, and drive a synthetic
   closed-loop load of ``serve.requests`` single-image requests from
   ``serve.clients`` client threads through the batcher — the pipelined
   continuous-batching one by default (``serve.pipelined``,
   serve/pipeline.py), or the legacy sync micro-batcher. Prints p50/p99
   end-to-end latency and QPS; with a log_dir, metrics + obs_registry.json
   land where scripts/obs_report.py reads them. The process exits non-zero
   (:class:`LoadFailed`) when any request failed for a reason other than the
   shedding the config asks for (a deadline, a full queue): an engine that
   raises on the device must not look like a served load.
3. **listen** (``serve.listen.enable`` or the ``--listen`` shorthand): the
   fault-tolerant front door — a loopback HTTP server (serve/frontend.py)
   in front of priority/QoS admission control, bounded retry, and a
   circuit breaker (serve/admission.py). ``POST /predict`` takes
   ``X-Priority`` / ``X-Deadline-Ms`` headers; ``GET /healthz`` reports
   breaker + queue state. SIGTERM/SIGINT stops accepting and drains
   in-flight work bounded by ``serve.drain_timeout_s``; the bound address
   lands in ``<log_dir>/listen_addr.json`` so callers never race the bind.
   ``serve.faults.enable`` wraps the engine in the seeded chaos injector
   (serve/faults.py) for recovery drills. With
   ``obs.watchdog_deadline_s`` > 0 a stall watchdog guards the serving
   loop, its hang report carrying batcher threads + window + breaker state.

``serve.requests=0`` with a bundle still warms up every bucket — a
deploy-time smoke that the artifact compiles and serves shape-correctly.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

import numpy as np

from ..config import Config, parse_cli
from ..obs import device as obs_device
from ..obs import registry as obs_registry
from ..obs import trace as obs_trace
from ..obs.watchdog import StallWatchdog
from ..parallel import mesh as mesh_lib
from ..serve.admission import AdmissionController
from ..serve.batcher import DeadlineExceeded, MicroBatcher, QueueFull
from ..serve.brownout import BrownoutController
from ..serve.engine import InferenceEngine
from ..serve.signals import SignalReader
from ..serve.faults import FaultyEngine
from ..serve.frontend import Frontend, write_listen_addr
from ..serve.pipeline import PipelinedBatcher
from ..serve import quant
from ..serve.export import export_checkpoint, load_bundle
from ..utils import compile_cache
from ..utils.logging import Logger


class LoadFailed(RuntimeError):
    """The synthetic load finished with requests that failed for a reason
    the config did not ask for (engine error, timeout, crashed client).
    ``summary`` is the full count dict the run would have returned."""

    def __init__(self, summary: dict):
        self.summary = summary
        super().__init__(
            f"synthetic load: {summary['failed']} of {summary['requests']} requests failed, "
            f"{summary['client_crashes']} client thread(s) crashed; "
            f"first failure: {summary['first_failure'] or 'n/a'}"
        )


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(round(q * (len(sorted_vals) - 1))), len(sorted_vals) - 1)
    return sorted_vals[idx]


def _synthetic_image(rng, image_size: int, wire: str) -> np.ndarray:
    """One synthetic client image in the configured wire's input space:
    normalized f32 pixels on the float32 wire (pipeline semantics), raw u8
    pixels on the uint8 wire (the engine denormalizes on device)."""
    if wire == "uint8":
        return rng.randint(0, 256, (image_size, image_size, 3)).astype(np.uint8)
    return rng.normal(0, 1, (image_size, image_size, 3)).astype(np.float32)


def _drive_load(cfg: Config, batcher: MicroBatcher, image_size: int, log: Logger) -> dict:
    """Closed-loop synthetic clients: each thread submits one request, waits
    for its logits, repeats. Returns the latency/QPS summary."""
    n_total = cfg.serve.requests
    n_clients = max(1, cfg.serve.clients)
    rng = np.random.RandomState(0)
    image = _synthetic_image(rng, image_size, cfg.serve.quant.wire)
    latencies: list[float] = []
    errors = {"shed": 0, "rejected": 0, "failed": 0, "crashed": 0, "first_failure": ""}
    lock = threading.Lock()
    counter = {"left": n_total}

    def client_inner():
        while True:
            with lock:
                if counter["left"] <= 0:
                    return
                counter["left"] -= 1
            t0 = time.perf_counter()
            try:
                fut = batcher.submit(image, deadline_ms=cfg.serve.deadline_ms or None)
                fut.result(timeout=60)
            except QueueFull:
                with lock:
                    errors["rejected"] += 1
                time.sleep(0.001)  # back off, as a real client would
                continue
            except DeadlineExceeded:  # the shedding serve.deadline_ms asks for
                with lock:
                    errors["shed"] += 1
                continue
            except Exception as e:  # noqa: BLE001 — engine failure/timeout: count, keep driving
                with lock:
                    errors["failed"] += 1
                    errors["first_failure"] = errors["first_failure"] or f"{type(e).__name__}: {e}"
                continue
            with lock:
                latencies.append(time.perf_counter() - t0)

    def client():
        # YAMT011: a silently-dead client thread would skew the measured load
        try:
            client_inner()
        except Exception:  # noqa: BLE001 — count the loss, keep the run honest
            with lock:
                errors["crashed"] += 1

    threads = [threading.Thread(target=client, daemon=True) for _ in range(n_clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    latencies.sort()
    summary = {
        "requests": n_total,
        "completed": len(latencies),
        "shed": errors["shed"],
        "rejected_full": errors["rejected"],
        "failed": errors["failed"],
        "first_failure": errors["first_failure"],
        "client_crashes": errors["crashed"],
        "wall_s": wall,
        "qps": len(latencies) / wall if wall > 0 else 0.0,
        "p50_ms": _percentile(latencies, 0.50) * 1e3,
        "p99_ms": _percentile(latencies, 0.99) * 1e3,
    }
    log.log(
        f"load: {summary['completed']}/{n_total} ok ({summary['shed']} shed, "
        f"{summary['rejected_full']} rejected, {summary['failed']} failed), "
        f"{summary['qps']:.1f} qps, "
        f"p50 {summary['p50_ms']:.2f} ms, p99 {summary['p99_ms']:.2f} ms"
    )
    return summary


def _make_batcher(cfg: Config, engine) -> MicroBatcher:
    common = dict(
        max_batch=cfg.serve.max_batch,
        max_wait_ms=cfg.serve.max_wait_ms,
        queue_depth=cfg.serve.queue_depth,
        default_deadline_ms=cfg.serve.deadline_ms,
        drain_timeout_s=cfg.serve.drain_timeout_s,
        # submit-side coercion follows the engine's wire (serve.quant.wire);
        # FaultyEngine proxies the attribute, bare doubles default to f32
        wire_dtype=getattr(engine, "wire_np_dtype", np.float32),
    )
    if cfg.serve.pipelined:
        return PipelinedBatcher(
            engine,
            max_inflight=cfg.serve.max_inflight,
            # back-to-back dispatch rides the overlap block: a saturated
            # bucket dispatches runs with one completion wake-up per run
            run_max=cfg.serve.overlap.run_max if cfg.serve.overlap.enable else 1,
            # ring feed/drain engages iff the ENGINE has ring_slots > 0
            # (serve.ring.enable wired into eng_kw); min_fill only sets the
            # engagement threshold here
            ring_min_fill=cfg.serve.ring.min_fill,
            **common,
        )
    return MicroBatcher(engine.predict, **common)


def _serving_info(batcher, admission) -> dict:
    """The watchdog hang-report 'serving' section: worker thread liveness,
    in-flight window occupancy, breaker + per-class queue state, and the
    OLDEST in-flight request's id/class/age/phase — a wedged window names
    whose request is stuck and which hop it is stuck at."""
    info: dict = {"admission": admission.state(),
                  "oldest_request": admission.oldest_inflight()}
    if hasattr(batcher, "worker_threads"):
        info["batcher_threads"] = batcher.worker_threads()
        info["inflight"] = batcher.inflight()
    else:
        t = batcher._thread
        info["batcher_threads"] = [] if t is None else [{"name": t.name, "alive": t.is_alive()}]
    return info


def _listen(cfg: Config, engine, log: Logger, reg, tracer, zoo=None) -> dict:
    """The front-door serving loop: HTTP frontend + admission + batcher,
    running until SIGTERM/SIGINT."""
    stop_event = threading.Event()

    def _on_signal(signum, frame):
        log.log(f"signal {signum}: stopping accept loop, draining in-flight work")
        stop_event.set()

    # only the main thread may install handlers; an embedded (test) run
    # drives shutdown through the returned stop_event instead
    try:
        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
    except ValueError:
        pass

    # fleet-spawned replicas (cli/fleet.py sets YAMT_FLEET_PARENT) self-
    # drain when their supervisor PROCESS disappears — a supervisor killed
    # -9 cannot run its drain paths, and an orphaned replica would hold its
    # port and device lease forever. getppid() changing away from the
    # recorded pid (reparenting to init/subreaper) is the death signal.
    supervisor_pid = os.environ.get("YAMT_FLEET_PARENT")

    def _orphan_watch():
        try:  # YAMT011: a dead watcher silently disables orphan protection
            parent = int(supervisor_pid)
            while not stop_event.wait(0.5):
                if os.getppid() != parent:
                    log.log(f"supervisor {parent} gone (now child of {os.getppid()}): "
                            "orphaned — draining")
                    reg.counter("serve.orphan_exits").inc()
                    stop_event.set()
                    return
        except Exception as e:  # noqa: BLE001 — contain, count, report
            reg.counter("serve.thread_crashes").inc()
            log.log(f"[serve] orphan watcher crashed: {type(e).__name__}: {e}")

    if supervisor_pid:
        threading.Thread(target=_orphan_watch, name="serve-orphan-watch", daemon=True).start()

    batcher = _make_batcher(cfg, engine).start()
    watchdog = None
    if cfg.obs.watchdog_deadline_s > 0 and cfg.train.log_dir:
        watchdog = StallWatchdog(
            cfg.train.log_dir,
            cfg.obs.watchdog_deadline_s,
            tracer=tracer,
            registry=reg,
            poll_s=cfg.obs.watchdog_poll_s,
            logger=log,
        )
    admission = AdmissionController.from_config(
        batcher,
        cfg.serve.admission,
        heartbeat=(lambda: watchdog.arm(phase="serve")) if watchdog is not None else None,
        # zoo'd replicas validate X-Model at the door and meter per-model
        # quotas (serve/zoo.py admission_kwargs); a bundle replica keeps the
        # pre-zoo behavior (no model vocabulary, nothing to reject)
        **(zoo.admission_kwargs() if zoo is not None else {}),
    )
    if watchdog is not None:
        watchdog.register_info("serving", lambda: _serving_info(batcher, admission))
        watchdog.start()
    # brownout ladder at the REPLICA tier: the controller reads this
    # process's own admission-side signals (windowed per-class p99 +
    # admitted backlog + breaker) and actuates the batcher (fill-or-flush)
    # and the admission controller (class shed / margin / retries)
    brownout = None
    if cfg.serve.brownout.enable:
        brownout = BrownoutController.from_config(
            cfg.serve.brownout,
            SignalReader(
                latency_family="serve.latency_seconds",
                signal_class=cfg.serve.brownout.signal_class,
                queue_depth_fn=admission.queued_total,
            ),
            targets=(batcher, admission),
        ).start()
        log.log(f"brownout ladder armed (L0..L{cfg.serve.brownout.max_level}, "
                f"up p99 > {cfg.serve.brownout.up_p99_ms:.0f}ms or "
                f"queue > {cfg.serve.brownout.up_queue_depth:.0f})")
    # HTTP-triggered jax.profiler capture (obs/device.py): xplane dumps land
    # in <log_dir>/trace (or serve.listen.profile_dir) for trace_ops.py; the
    # drain path below guarantees a still-open window closes at shutdown
    profile_dir = cfg.serve.listen.profile_dir or (
        os.path.join(cfg.train.log_dir, "trace") if cfg.train.log_dir else ""
    )
    profiler = obs_device.ProfilerCapture(profile_dir) if profile_dir else None
    frontend = Frontend(
        admission,
        host=cfg.serve.listen.host,
        port=cfg.serve.listen.port,
        request_timeout_s=cfg.serve.listen.request_timeout_s,
        retry_after_s=cfg.serve.admission.breaker_cooldown_s,
        profiler=profiler,
        replica_id=cfg.serve.listen.replica_id,
    ).start()
    # ephemeral ports (listen.port=0) make N replicas on one host trivial;
    # the bound port is published ATOMICALLY (temp + rename) so a polling
    # supervisor (cli/fleet.py) never reads a partial JSON
    addr = {"host": cfg.serve.listen.host, "port": frontend.port, "pid": os.getpid(),
            "replica_id": frontend.replica_id}
    if cfg.train.log_dir:
        write_listen_addr(cfg.train.log_dir, addr)
    log.log(f"listening on {frontend.url} (POST /predict, GET /healthz|/metrics|/varz)")
    # TTL-lease self-registration (serve.listen.register_to): the replica
    # heartbeats its OWN address into a fleet router that never spawned it
    # — the multi-host membership path. The lease outliving the heartbeat
    # is the router's signal this process (or the route to it) vanished.
    reg_client = None
    if cfg.serve.listen.register_to:
        from ..serve.client import ClientHTTPError, ReplicaClient
        r_host, r_port = cfg.serve.listen.register_to.rsplit(":", 1)
        ttl_s = cfg.serve.listen.register_ttl_s
        reg_client = ReplicaClient(r_host, int(r_port), timeout_s=5.0,
                                   connect_timeout_s=2.0)
        # the lease's served-model advertisement ({name: digest}): the
        # router routes a model only to replicas advertising it, and refuses
        # a digest that conflicts with another live replica's for the name
        lease_models = zoo.lease_models() if zoo is not None else None

        def _heartbeat():
            try:  # YAMT011: a dead heartbeat thread = silent lease expiry
                period = max(ttl_s / 3.0, 0.1)
                while not stop_event.is_set():
                    try:
                        reg_client.register(addr["host"], addr["port"], ttl_s=ttl_s,
                                            replica_id=frontend.replica_id,
                                            models=lease_models)
                        reg.counter("serve.register_heartbeats").inc()
                    except ClientHTTPError as e:
                        if e.tag == "digest_conflict":
                            # the fleet serves a DIFFERENT artifact under one
                            # of our model names: renewing can never succeed,
                            # so stop beating loudly instead of spinning
                            reg.counter("serve.register_conflicts").inc()
                            log.log(f"[serve] register REFUSED (digest conflict): {e}")
                            return
                        reg.counter("serve.register_failures").inc()
                    except Exception:  # noqa: BLE001 — the router may be down;
                        # keep beating: the next renewal re-admits us
                        reg.counter("serve.register_failures").inc()
                    stop_event.wait(period)
            except Exception as e:  # noqa: BLE001 — contain, count, report
                reg.counter("serve.thread_crashes").inc()
                log.log(f"[serve] register heartbeat crashed: {type(e).__name__}: {e}")

        threading.Thread(target=_heartbeat, name="serve-register", daemon=True).start()
        log.log(f"registering with {cfg.serve.listen.register_to} "
                f"(ttl={ttl_s:.1f}s, heartbeat every {max(ttl_s / 3.0, 0.1):.1f}s)")
    try:
        stop_event.wait()
    finally:
        t0 = time.perf_counter()
        # this drain's timeouts, not the process's: the counter is
        # process-wide, and a process that served before (one test after
        # another, chip_smoke.py's phases) may come here with it already moved
        timeouts0 = reg.counter("serve.drain_timeouts").value
        if reg_client is not None:
            try:
                # clean drain: leave the fleet NOW instead of via TTL lapse
                reg_client.deregister(addr["host"], addr["port"])
            except Exception:  # noqa: BLE001 — the router may already be gone;
                # the lease lapses on its own, so count it and move on
                reg.counter("serve.deregister_failures").inc()
            reg_client.close()
        frontend.stop()
        if brownout is not None:
            brownout.stop()
        if profiler is not None:
            # a capture the operator never stopped must not outlive the
            # server (the drain-path half of the YAMT013 discipline)
            profiler.stop_if_active()
        batcher.stop(drain=True)  # bounded by serve.drain_timeout_s
        if watchdog is not None:
            watchdog.stop()
        drain_s = time.perf_counter() - t0
        timeouts = int(reg.counter("serve.drain_timeouts").value - timeouts0)
        log.log(f"drained in {drain_s:.2f}s ({'clean' if not timeouts else 'DRAIN TIMEOUT'})")
    return {"listened": True, **addr, "drain_s": drain_s, "drain_timeouts": timeouts}


def run(cfg: Config) -> dict:
    compile_cache.configure()  # before the first compile; replicas resolve the same dir
    is_coord = mesh_lib.is_coordinator()
    log = Logger(cfg.train.log_dir, enabled=is_coord, tensorboard=False)
    reg = obs_registry.get_registry()
    if cfg.obs.histogram_buckets:
        # before any serving histogram exists: the ladder applies at creation
        reg.set_default_buckets(cfg.obs.histogram_buckets)
    # version attribution (/metrics build_info family) + device memory gauges
    reg.set_build_info(obs_device.build_info())
    obs_device.install_memory_gauges(reg)
    log.set_registry(reg)
    tracer = obs_trace.configure(
        enabled=bool(cfg.obs.trace) and is_coord, ring_size=cfg.obs.trace_ring_size,
        # the merged fleet trace's process-lane label (trace_merge.py):
        # replicas identify by their supervisor-assigned replica_id
        process_name=cfg.serve.listen.replica_id or f"replica pid-{os.getpid()}",
    )
    result: dict = {}
    try:
        bundle_dir = cfg.serve.bundle
        if cfg.serve.export_from:
            if not bundle_dir:
                bundle_dir = os.path.join(cfg.train.log_dir, "bundle")
            calib = None
            if cfg.serve.quant.weights == "int8":
                # held-out calibration batch for the int8 gate: seeded
                # synthetic u8 pixels normalized with the pipeline's
                # mean/std (no dataset is wired into the serve CLI; the
                # bundle's provenance records the synthetic source)
                q = cfg.serve.quant
                crng = np.random.RandomState(q.calib_seed)
                raw = crng.randint(
                    0, 256,
                    (q.calib_batches * q.calib_batch_size,
                     cfg.data.image_size, cfg.data.image_size, 3),
                ).astype(np.uint8)
                calib = quant.normalize_reference(raw, cfg.data.mean, cfg.data.std)
            export_checkpoint(
                cfg.serve.export_from, bundle_dir, use_ema=cfg.serve.use_ema,
                quant_weights=cfg.serve.quant.weights, calib_images=calib,
                int8_top1_min=cfg.serve.quant.int8_top1_min,
            )
            log.log(f"exported {cfg.serve.export_from} -> {bundle_dir}"
                    + (" (int8 weights, parity-gated)" if calib is not None else ""))
            result["bundle"] = bundle_dir
        # multi-model zoo (serve.zoo.models set): N named bundles behind one
        # engine/admission edge, each request picking its tenant via X-Model
        zoo = None
        if cfg.serve.zoo.models:
            from ..serve.zoo import ModelZoo
            zoo = ModelZoo.from_config(cfg.serve.zoo)
            log.log(f"zoo: serving {', '.join(zoo.models)} (default {zoo.default})")
        if not bundle_dir and zoo is None:
            raise ValueError(
                "serve: needs serve.bundle, serve.zoo.models, and/or serve.export_from")

        mesh = mesh_lib.make_mesh(cfg.dist.num_devices) if cfg.serve.data_parallel else None
        eng_kw = dict(
            buckets=cfg.serve.buckets,
            compute_dtype=cfg.serve.compute_dtype,
            mesh=mesh,
            donate_input=cfg.serve.donate_input,
            image_size=cfg.data.image_size,
            image_sizes=cfg.serve.image_sizes,
            fuse_ladder=cfg.serve.fuse_chunks.ladder if cfg.serve.fuse_chunks.enable else (),
            offladder_cache=cfg.serve.offladder_cache,
            overlap_staging=cfg.serve.overlap.enable,
            staging_slots=cfg.serve.overlap.staging_slots,
            wire=cfg.serve.quant.wire,
            wire_mean=cfg.data.mean,
            wire_std=cfg.data.std,
            # device-resident request ring (serve/ring.py): one masked-scan
            # dispatch per steady-state window. Gated off under the mesh
            # here (the engine would refuse the combination) — the same
            # per-chunk fallback rule fusion follows under data_parallel
            ring_slots=cfg.serve.ring.slots
            if (cfg.serve.ring.enable and mesh is None) else 0,
        )
        if zoo is not None:
            engine = InferenceEngine(**zoo.engine_kwargs(), **eng_kw)
        else:
            bundle = load_bundle(bundle_dir)
            engine = InferenceEngine(bundle, **eng_kw)
        # quantization mode rides the build_info family (/metrics, /varz):
        # a scraped fleet can group replicas by the bytes they serve with
        reg.set_build_info({**obs_device.build_info(), "quant_mode": engine.quant_mode})
        if cfg.serve.warmup:
            t0 = time.perf_counter()
            engine.warmup()
            log.log(
                f"warmup: compiled buckets {engine.buckets} x sizes {engine.image_sizes}"
                + (f" + fused K {engine.fuse_ladder}" if engine.fuse_ladder else "")
                + f" in {time.perf_counter() - t0:.1f}s"
            )
        engine = FaultyEngine.from_config(engine, cfg.serve.faults)
        if cfg.serve.faults.enable:
            log.log(
                f"CHAOS: fault injection on (seed={cfg.serve.faults.seed}, "
                f"failure_rate={cfg.serve.faults.failure_rate}, "
                f"fail_first_n={cfg.serve.faults.fail_first_n})"
            )
        if cfg.serve.requests > 0:
            batcher = _make_batcher(cfg, engine)
            batcher.start()
            try:
                result.update(_drive_load(cfg, batcher, cfg.data.image_size, log))
            finally:
                batcher.stop()
            if result["failed"] or result["client_crashes"]:
                # the counts ride the exception; the finally below still
                # writes obs_registry.json for the post-mortem
                raise LoadFailed(result)
        if cfg.serve.listen.enable:
            result.update(_listen(cfg, engine, log, reg, tracer, zoo=zoo))
        return result
    finally:
        if tracer.enabled and cfg.train.log_dir and is_coord:
            path = tracer.write(os.path.join(cfg.train.log_dir, "obs_trace.json"))
            log.log(f"span trace -> {path}")
        if is_coord and cfg.train.log_dir:
            os.makedirs(cfg.train.log_dir, exist_ok=True)
            with open(os.path.join(cfg.train.log_dir, "obs_registry.json"), "w") as f:
                json.dump(reg.snapshot(), f, indent=1, sort_keys=True)
        log.close()


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    # `--listen` is sugar for serve.listen.enable=true (the front-door mode
    # named by ROADMAP item 1); everything else stays app:/key=value
    argv = ["serve.listen.enable=true" if a == "--listen" else a for a in argv]
    cfg = parse_cli(argv)
    return run(cfg)


if __name__ == "__main__":
    main()
