#!/usr/bin/env python
"""Serving benchmark: latency/QPS per bucket + pipelined/bf16/chaos A/Bs.

Prints exactly ONE JSON line on stdout (metric / value / unit / provenance) and
optionally writes it to a BENCH_SERVE_*.json via --out. It measures on the
chip or fails: with no TPU it exits non-zero before measuring, and a
measurement that raises emits ``value: null`` with an ``error`` field AND
exits non-zero. ``--cpu-rehearsal`` asks, explicitly, for a run on whatever
backend there is: what such a run COUNTS (dispatches, bytes, misroutes,
bitwise verdicts) stands, and its rates are XLA:CPU timings, so its headline
rides under a ``cpu_rehearsal_`` metric name and a unit that says so —
never under a device metric's name. (``--partition`` is jax-free: a
transport measurement on loopback sockets, the same on any host.)

One process per chip: the ``--fleet``, ``--zoo`` and ``--overload`` parents
never initialise a JAX backend while their replica subprocesses need the
device — bundles are exported by a child that exits first, and what the
parent runs in-process runs after the fleet has stopped.

Four measurements per run:

1. **direct** — engine.predict latency per (bucket, image_size), exact-bucket
   batches: p50/p99 ms + QPS (the BENCH_SERVE_r01 shape, now per size).
2. **concurrent-submit A/B** — closed-loop client threads submitting single
   images through the real batcher, once through the legacy sync
   MicroBatcher and once through the PipelinedBatcher (serve/pipeline.py):
   per-(bucket, size) ``qps_sync`` vs ``qps_pipelined``. This measures the
   tentpole: continuous batching + async double-buffered dispatch hiding
   host collect/stage time behind device compute.
3. **fp32-vs-bf16 A/B** — a second engine with compute_dtype=bfloat16,
   direct QPS per bucket plus the measured max |logit delta| vs fp32
   against the pinned BF16_PARITY_ATOL (serve/engine.py).
4. **chained-vs-fused A/B** (``--fused``): whole requests of K max-bucket chunks served
   once through the per-chunk path (K dispatches, host staging between each)
   and once through the fused multi-chunk executables (serve/engine.py
   ``fuse_ladder``: ONE ``lax.scan`` dispatch per ladder piece). Per K:
   dispatches/request (the structural claim — 1 for on-ladder K), p50/p99,
   QPS, speedup, and the bitwise-parity check; plus the CPU-rehearsal caveat
   recorded in the artifact (on 1 core the dispatch boundary is nearly free,
   so the speedup may be ~flat — the dispatch-count drop is the pinned win).
5. **structural sweep** (``--structural``) — ONE interleaved sweep across
   the five serving structures at a saturated bucket: **sync** (blocking
   collect->predict cycle), **pipelined** (async in-flight window),
   **fused** (coalesced overflow rides the lax.scan executables),
   **overlapped** (fence-tracked slot staging with async H2D + back-to-back
   runs: > 1 dispatch per completion wake-up, serve/pipeline.py), and
   **ring** (device-resident request ring, serve/ring.py: a window of up
   to R staged max-bucket slots consumed by ONE masked-scan dispatch).
   Rounds interleave mode-by-mode so box drift hits all five alike; per
   mode the row carries median QPS, fill, dispatches/request, the
   ``serve.dispatches_per_wakeup`` registry delta (the back-to-back
   structural claim — None for sync, 1.0 for per-batch pipelining; a ring
   window is ONE piece, so the per-batch [1, 2] bound does not apply), the
   steady-state ``serve.achieved_flops_per_s`` window (dispatched cost
   FLOPs ÷ measured run seconds) next to the single-dispatch reference,
   ring window counts, and registry-math latency quantiles. The sweep also
   pins the deterministic ``ring_probe``: a saturated R-slot window is
   exactly ONE ``serve.dispatch_seconds`` observation, bitwise vs the
   per-batch path. Emits the BENCH_SERVE_r12 shape (r05 + the ring arm).
6. **chaos A/B** — an OPEN-LOOP Poisson load generator (arrivals fire on
   schedule regardless of completions — closed loops hide overload) drives
   mixed priorities (interactive/batch/best_effort via serve/admission.py)
   and mixed image sizes through the pipelined batcher twice: a healthy
   round and a faulty round (serve/faults.py: seeded failure rate + latency
   spikes at the completion edge). Per class: submitted / completed /
   rejected / shed / failed / p50 / p99, plus retry, injected-fault,
   rejection-cause, and breaker accounting from the obs registry deltas —
   and the invariant that EVERY request resolved (``unresolved`` must be
   0). Both rounds share one arrival schedule (same seed), so the delta is
   the injected faults, not the load draw.

8. **quantized-serving A/B** (``--quant``) — ONE interleaved sweep over the
   three serving precisions per bucket: **f32** (the status quo),
   **uint8-wire** (raw pixels on the wire, device denorm), and **int8**
   (uint8 wire + post-training int8 weights). Per mode: median-of-rounds
   QPS/p50/p99 plus the byte instruments from registry math — per-request
   ``serve.h2d_bytes`` (the uint8 wire moves EXACTLY 1/4 of the f32 bytes,
   on any host) and ``serve.dispatched_bytes`` — and the parity verdicts:
   zero-mean denorm bitwise, mean/std wire delta vs the configured atol,
   and the int8 export's gated top-1 agreement. Emits the BENCH_SERVE_r07
   shape.

The model is random-init + synthetic BN stats, folded through the real
serve/export transform and dispatched through the real AOT engine — the
numbers measure the serving path (compile, pad, dispatch, device_get), which
does not depend on trained weight values.

7. **replica fleet** (``--fleet``, standalone mode) — a REAL fleet of N
   ``cli/serve.py`` replica subprocesses behind the router tier
   (serve/router.py), measured three ways on shared seeded schedules:
   hedged-vs-unhedged tail A/B against a latency-injected straggler
   replica (``serve.hedges``/``serve.hedge_wins`` + p99 delta), a kill -9
   availability round (every submitted request must resolve as completed
   or typed-rejected, the supervisor must restart the corpse), and the
   autoscaler's N-over-time trace across a diurnal low/high/low open-loop
   schedule (cooldown respected). Emits the BENCH_SERVE_r06 shape.

10. **partition** (``--partition``, standalone mode, jax-free) — the
   multi-host partition-containment acceptance (serve/netchaos.py): N
   in-process echo replicas each behind a seeded socket-level fault proxy,
   one router over the proxy addresses. Seeded blackhole / reset /
   half-open / flap rounds inject at the SOCKET level a third of the way
   in and heal at two thirds, measuring detection time (fault onset ->
   ejection, stamped by a counter watcher), client-visible error rate
   (the contract is ZERO — transport retry absorbs every shape), and
   recovery (heal -> fully routable through the probation); then the
   TTL-lease membership round: a leased replica joins by heartbeat,
   silently vanishes (heartbeat stops + link blackholed), and must be
   REMOVED by lease expiry within TTL + one poll sweep. Emits the
   BENCH_SERVE_r09 shape.

11. **multi-model zoo** (``--zoo``, standalone mode) — the zoo/cascade
   acceptance (serve/zoo.py, serve/cascade.py): ONE 2-replica
   model-sharded fleet (slot 0 serves the int8 'small' tier, slot 1 the
   f32 'big' tier via per-slot ``serve.zoo.models`` assignments, placement
   advertised to the model-aware router) A/B'd three ways over ONE seeded
   trace: **big_only** (every request pinned ``X-Model: big`` — the
   one-model-per-fleet baseline), **sharded** (seeded 50/50 pins; the
   per-replica ``serve.model_requests.{model}`` deltas must show ZERO
   misroutes and the books zero 5xx), and **cascade** (unqualified
   submits: the small tier answers confident requests, low-margin ones
   re-submit to the big tier at the router). Pinned: escalations > 0 AND
   answered_small > 0 (the threshold calibrates to the trace's median
   margin), every cascade answer bitwise-matches exactly one of the two
   per-image explicit-pin references (escalated answers EQUAL the
   big-only arm's), and the fleet-wide dispatched-FLOPs/request mean of
   the cascade arm sits STRICTLY below the big-only arm's (the cost
   proxy: per-replica ``serve.dispatched_flops`` deltas). Emits the
   BENCH_SERVE_r11 shape.

9. **overload** (``--overload``, standalone mode) — the brownout ladder's
   acceptance experiment (serve/brownout.py): ONE seeded open-loop Poisson
   storm at ``--overload-multiple`` x the measured closed-loop capacity
   (the engine paced by a seeded per-dispatch latency floor so capacity is
   box-independent), played through fresh batcher+admission stacks twice —
   brownout OFF vs ON. Pinned: interactive availability ON > OFF, zero
   unresolved futures in both arms, the ladder stepping up during the
   storm AND fully recovering to L0 after it. Then the GRAY-FAILURE round:
   a real fleet with a latency-injected (never crashing) straggler, soft
   ejection armed mid-round — time-to-eject from the arming instant, and
   the p99 of requests submitted after the ejection vs before (the
   submit-time split makes the recovery claim routing-honest). Emits the
   BENCH_SERVE_r08 shape.

Usage: python scripts/serve_bench.py [--arch mobilenet_v3_large]
           [--image-sizes 224] [--buckets 1,8,32] [--iters 10]
           [--concurrent-iters 6] [--ab-iters 5] [--no-bf16]
           [--fused] [--fuse-ladder 2,4] [--fused-iters 8]
           [--structural] [--structural-rounds 3]
           [--quant] [--quant-iters 5] [--quant-rounds 3]
           [--quant-top1-min 0.9]
           [--chaos-requests 80] [--chaos-qps 0] [--chaos-fault-rate 0.05]
           [--no-chaos] [--out f.json]
       python scripts/serve_bench.py --fleet [--fleet-replicas 2]
           [--fleet-requests 40] [--fleet-qps 0] [--fleet-straggler-ms 400]
           [--fleet-phase-s 5,20,10] [--fleet-seed 0] [--out f.json]
       python scripts/serve_bench.py --overload [--overload-storm-s 5]
           [--overload-multiple 3] [--overload-pace-ms 20]
           [--overload-replicas 2] [--overload-gray-requests 60]
           [--overload-straggler-ms 300] [--overload-seed 0] [--out f.json]
       python scripts/serve_bench.py --partition [--partition-replicas 3]
           [--partition-requests 120] [--partition-qps 30]
           [--partition-poll-s 0.1] [--partition-connect-timeout-s 0.4]
           [--partition-read-timeout-s 2.0] [--partition-lease-ttl-s 1.5]
           [--partition-seed 0] [--out f.json]
       python scripts/serve_bench.py --zoo [--zoo-requests 48]
           [--zoo-qps 0] [--zoo-threshold -1] [--zoo-int8-top1-min 0.5]
           [--zoo-seed 0] [--out f.json]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(int(round(q * (len(sorted_vals) - 1))), len(sorted_vals) - 1)
    return sorted_vals[idx]


# contract-test model presets (anything else is an arch name from models/zoo.py)
_MODEL_PRESETS = {
    "tiny": dict(arch="mobilenet_v2", num_classes=16, dropout=0.0,
                 block_specs=[{"t": 2, "c": 8, "n": 1, "s": 2}, {"t": 2, "c": 16, "n": 1, "s": 2}]),
    # the zoo's big tier at the tiny preset: deeper/wider than "tiny", so
    # the cascade's FLOPs win is structural, not noise
    "tiny_big": dict(arch="mobilenet_v2", num_classes=16, dropout=0.0,
                     block_specs=[{"t": 4, "c": 24, "n": 2, "s": 2},
                                  {"t": 4, "c": 48, "n": 2, "s": 2},
                                  {"t": 4, "c": 96, "n": 1, "s": 1}]),
}


def _model_config(name):
    from yet_another_mobilenet_series_tpu.config import ModelConfig

    return ModelConfig(**_MODEL_PRESETS[name]) if name in _MODEL_PRESETS else ModelConfig(arch=name)


def _export_child_main(jobs_json: str) -> int:
    """``--export-child``: build and export the bundles a fleet-mode parent
    asked for, then exit — releasing the device before any replica starts."""
    import jax
    import numpy as np

    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.serve.export import export_bundle

    for job in json.loads(jobs_json):
        net = get_model(_model_config(job["model"]), job["image_size"])
        params, state = net.init(jax.random.PRNGKey(job["seed"]))
        kw = dict(job.get("export_kwargs") or {})
        if job.get("calib_npy"):
            kw["calib_images"] = np.load(job["calib_npy"])
        export_bundle(net, params, state, job["out"], **kw)
    return 0


def _export_bundles_in_child(jobs: list[dict]) -> None:
    """Random-init + export ``jobs`` in a child process that exits. A chip
    belongs to one process at a time: a fleet-mode parent that called
    ``net.init`` itself would hold the chip its replicas need."""
    import subprocess

    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--export-child", json.dumps(jobs)],
        capture_output=True, text=True, timeout=900,
    )
    if r.returncode != 0:
        raise RuntimeError(f"bundle export child failed rc={r.returncode}: {r.stderr[-800:]}")


def _parent_must_be_off_the_backend() -> None:
    """Called right before a fleet-mode parent starts its replicas: a parent
    that has initialised a JAX backend holds the chip on a TPU host, and its
    replicas would hang in backend init. Fail here, with the reason."""
    from scripts.provenance import backend_initialised

    if backend_initialised():
        raise RuntimeError("this parent initialised a JAX backend before its replicas "
                           "started; on a TPU host it now holds the chip they need")


def _hist_delta_quantiles(name, counts_before):
    """p50/p95/p99 (ms) of one measured WINDOW of a registry histogram:
    bucket-count deltas against the pre-window snapshot, estimated through
    the registry's own interpolation (obs.registry.quantiles_from_counts) —
    the bench reports the same math /metrics scrapes, not its own
    percentile-of-a-list."""
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry, quantiles_from_counts

    h = get_registry().histogram(name)
    counts = [a - b for a, b in zip(h.bucket_counts(), counts_before)]
    p50, p95, p99 = quantiles_from_counts(h.bounds, counts, (0.5, 0.95, 0.99))
    return {
        "count": int(sum(counts)),
        "p50_ms": round(p50 * 1e3, 3),
        "p95_ms": round(p95 * 1e3, 3),
        "p99_ms": round(p99 * 1e3, 3),
    }


def _hist_counts(name):
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry

    return get_registry().histogram(name).bucket_counts()


def _direct_row(engine, batch, size, iters, rng):
    """Exact-bucket engine.predict latency: one untimed page-in, then iters.
    Client-side wall p50/p99 plus the registry's own bucketed quantiles of
    the same window (serve.run_seconds deltas) ride in every row."""
    x = rng.normal(0, 1, (batch, size, size, 3)).astype("float32")
    engine.predict(x)
    run_counts0 = _hist_counts("serve.run_seconds")
    lat = []
    for _ in range(iters):
        t1 = time.perf_counter()
        engine.predict(x)
        lat.append(time.perf_counter() - t1)
    reg_q = _hist_delta_quantiles("serve.run_seconds", run_counts0)
    lat.sort()
    mean = sum(lat) / len(lat)
    return {
        "batch": batch,
        "image_size": size,
        "p50_ms": round(_percentile(lat, 0.50) * 1e3, 3),
        "p99_ms": round(_percentile(lat, 0.99) * 1e3, 3),
        "p50_ms_registry": reg_q["p50_ms"],
        "p95_ms_registry": reg_q["p95_ms"],
        "p99_ms_registry": reg_q["p99_ms"],
        "qps": round(batch / mean, 2),
    }


def _drive_concurrent(batcher, image, n_requests, n_clients):
    """Closed-loop clients: each submits one image, waits, repeats. Returns
    (qps, sorted latencies). The batcher must already be started."""
    lock = threading.Lock()
    left = [n_requests]
    lat: list[float] = []

    def client():
        while True:
            with lock:
                if left[0] <= 0:
                    return
                left[0] -= 1
            t0 = time.perf_counter()
            fut = batcher.submit(image)
            fut.result(timeout=300)
            with lock:
                lat.append(time.perf_counter() - t0)

    threads = [threading.Thread(target=client, daemon=True) for _ in range(n_clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    lat.sort()
    return (len(lat) / wall if wall > 0 else 0.0), lat


def _concurrent_row(engine, batch, size, conc_iters, max_inflight, rng):
    """Sync-vs-pipelined QPS through the real batchers at max_batch=batch.

    2*batch closed-loop clients drive both batchers (sharing one warm
    engine) in INTERLEAVED rounds — sync, pipelined, sync, pipelined... —
    and the reported QPS is the per-mode MEDIAN of 5 rounds: on a shared
    box, minute-scale CPU drift is bigger than the effect under test;
    interleaving makes drift hit both modes alike, and the median (unlike
    best-of or mean) ignores the occasional round that lands in a lucky or
    throttled scheduler window. Per-round arrays are recorded in the
    artifact so the spread is visible. The request count per round is
    floored (a 12-request window is pure scheduler noise) and capped (the
    biggest bucket would otherwise dominate the whole run).
    ``avg_fill_*`` (serve.batch_size histogram deltas) says how full the
    dispatched buckets actually were — fill < 1 means padded dead rows, a
    batching-policy failure the QPS numbers would otherwise hide."""
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.serve.batcher import MicroBatcher
    from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher

    image = rng.normal(0, 1, (size, size, 3)).astype("float32")
    n_clients = min(max(2 * batch, 4), 64)
    n_requests = min(max(conc_iters * batch, 48), 96)
    rounds = 5
    # a long linger fills buckets; the pipelined path hides it behind compute
    common = dict(max_batch=batch, max_wait_ms=10.0, queue_depth=max(64, 4 * batch))
    reg = get_registry()
    row = {"batch": batch, "image_size": size, "requests": n_requests, "clients": n_clients,
           "rounds": rounds}
    batchers = {
        "sync": MicroBatcher(engine.predict, **common).start(),
        "pipelined": PipelinedBatcher(engine, max_inflight=max_inflight, **common).start(),
    }
    runs = {m: [] for m in batchers}  # (qps, lat) per round
    fills = {m: [] for m in batchers}
    try:
        for b in batchers.values():  # warm both paths
            _drive_concurrent(b, image, min(2 * batch, n_requests), n_clients)
        for _ in range(rounds):
            for mode, b in batchers.items():
                s0 = reg.snapshot()
                qps, lat = _drive_concurrent(b, image, n_requests, n_clients)
                s1 = reg.snapshot()
                d_count = s1["serve.batch_size.count"] - s0["serve.batch_size.count"]
                d_sum = s1["serve.batch_size.sum"] - s0["serve.batch_size.sum"]
                fills[mode].append(d_sum / d_count / batch if d_count else 0.0)
                runs[mode].append((qps, lat))
    finally:
        for b in batchers.values():
            b.stop()
    for mode in batchers:
        ordered = sorted(runs[mode], key=lambda r: r[0])
        med_qps, med_lat = ordered[len(ordered) // 2]
        row[f"qps_{mode}"] = round(med_qps, 2)
        row[f"qps_rounds_{mode}"] = [round(q, 2) for q, _ in runs[mode]]
        row[f"p99_ms_{mode}"] = round(_percentile(med_lat, 0.99) * 1e3, 3)
        row[f"avg_fill_{mode}"] = round(sum(fills[mode]) / len(fills[mode]), 3)
    row["pipelined_speedup"] = round(row["qps_pipelined"] / row["qps_sync"], 4) if row["qps_sync"] else None
    return row


# recorded in every fused A/B artifact, the way r02 recorded the pipelined
# caveat: the structural claim a 1-core box CAN pin is the dispatch count
_FUSED_CPU_CAVEAT = (
    "cpu_rehearsal: on a 1-core host the per-dispatch boundary costs little "
    "(host staging and XLA 'device' compute share the core), so the fused "
    "speedup may be ~flat here; the pinned structural win is "
    "dispatches_per_request dropping to 1 for on-ladder K (bitwise-identical "
    "logits). The throughput claim is an accelerator measurement — ROADMAP "
    "item 1, same caveat discipline as BENCH_SERVE_r02."
)


def _fused_ab(chained, fused, size, iters, rng):
    """Chained (per-chunk) vs fused (lax.scan) whole-request serving: same
    bundle, same buckets, K max-bucket chunks per request for every K on the
    fuse ladder plus one off-ladder K (decomposes into ladder pieces). The
    dispatch count per request comes from serve.dispatch_seconds.count
    registry deltas — the structural measurement; latency/QPS ride along."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.obs.registry import get_registry

    reg = get_registry()
    cap = fused.buckets[-1]
    ladder = list(fused.fuse_ladder)
    off_k = next(k for k in range(2, max(ladder) + 2) if k not in ladder)
    rows = []
    for k in ladder + [off_k]:
        n = k * cap
        x = rng.normal(0, 1, (n, size, size, 3)).astype("float32")
        ref = chained.predict(x)
        row = {"k": k, "rows": n, "on_ladder": k in ladder,
               "bitwise_ok": bool(np.array_equal(fused.predict(x), ref))}
        for label, eng in (("chained", chained), ("fused", fused)):
            eng.predict(x)  # untimed page-in
            s0 = reg.snapshot()
            lat = []
            for _ in range(iters):
                t0 = time.perf_counter()
                eng.predict(x)
                lat.append(time.perf_counter() - t0)
            s1 = reg.snapshot()
            lat.sort()
            mean = sum(lat) / len(lat)
            row[f"p50_ms_{label}"] = round(_percentile(lat, 0.50) * 1e3, 3)
            row[f"p99_ms_{label}"] = round(_percentile(lat, 0.99) * 1e3, 3)
            row[f"qps_{label}"] = round(n / mean, 2)
            row[f"dispatches_per_request_{label}"] = round(
                (s1["serve.dispatch_seconds.count"] - s0["serve.dispatch_seconds.count"]) / iters, 3)
        row["fused_speedup"] = (
            round(row["qps_fused"] / row["qps_chained"], 4) if row["qps_chained"] else None)
        rows.append(row)
    return {
        "ladder": ladder,
        "off_ladder_k": off_k,
        "max_bucket": cap,
        "image_size": size,
        "per_k": rows,
        "peak_speedup": max(r["fused_speedup"] for r in rows),
        "cpu_rehearsal_note": _FUSED_CPU_CAVEAT,
    }


_STRUCTURAL_CPU_CAVEAT = (
    "cpu_rehearsal: host staging/collect work and XLA 'device' compute share "
    "the core(s) on this box, so overlapped staging and back-to-back dispatch "
    "cannot add throughput here (QPS columns may be ~flat or slightly "
    "negative). The pinned structural wins are dispatches_per_wakeup > 1 on "
    "the saturated bucket, bitwise-identical logits, and the dispatch/ "
    "transfer accounting; for the ring arm they are the deterministic "
    "one-dispatch window probe (a saturated R-slot window == ONE "
    "serve.dispatch_seconds observation, registry-delta counted), "
    "serve.ring_dispatches > 0 under the driven burst, and bitwise parity "
    "vs the per-batch path. The throughput claim is an accelerator "
    "measurement — ROADMAP item 2's hardware rung, same caveat discipline "
    "as r02/r04."
)


def _structural_sweep(make_engine, size, *, rounds, conc_iters, max_inflight,
                      staging_slots, run_max, fuse_ladder, rng,
                      ring_slots=4, ring_min_fill=0.5):
    """One interleaved sweep across the five serving structures on a
    saturated bucket (docs/SERVING.md "Overlapped staging" and
    "Device-resident ring"):

    - ``sync``       MicroBatcher: blocking collect -> predict -> resolve
    - ``pipelined``  PipelinedBatcher(run_max=1), chained engine
    - ``fused``      PipelinedBatcher(run_max=1), fused-scan engine
    - ``overlapped`` PipelinedBatcher(run_max), overlapped-staging fused
                     engine — the device-resident steady state
    - ``ring``       PipelinedBatcher over a ring-mode overlapped engine:
                     saturated windows of up to ``ring_slots`` staged
                     max-bucket slots consumed by ONE masked-scan dispatch

    All share ``max_batch = 2 * max_bucket`` so every saturated coalesced
    group exceeds the biggest bucket (the fused/overlapped modes serve it
    as ONE engine call). Rounds interleave mode-by-mode so box drift hits
    all four alike; median-of-rounds QPS like the r02 A/B. Per mode the
    row also carries the registry-delta instruments the structural claims
    are read from: dispatches/request, dispatches-per-wakeup (None for
    sync — the MicroBatcher has no completion thread), steady-state
    achieved FLOPs/s, and the same window's bucketed latency quantiles."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.obs import device as obs_device
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.serve.batcher import MicroBatcher
    from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher

    reg = get_registry()
    eng_chained = make_engine("float32")
    eng_fused = make_engine("float32", fuse=fuse_ladder)
    eng_overlap = make_engine("float32", fuse=fuse_ladder, overlap=True,
                              staging_slots=staging_slots)
    eng_ring = make_engine("float32", overlap=True, staging_slots=staging_slots,
                           ring_slots=ring_slots)
    for e in (eng_chained, eng_fused, eng_overlap, eng_ring):
        e.warmup()
    cap = eng_chained.buckets[-1]
    max_batch = 2 * cap
    # saturation by construction: with the window holding 2 full batches in
    # flight, 3 x max_batch closed-loop clients keep >= max_batch requests
    # queued — the back-to-back condition — for the whole round
    n_clients = 3 * max_batch
    n_requests = min(max(conc_iters * max_batch, 2 * n_clients), 384)
    image = rng.normal(0, 1, (size, size, 3)).astype("float32")
    # bitwise parity across the whole structural ladder, one oversized batch
    xp = rng.normal(0, 1, (max_batch, size, size, 3)).astype("float32")
    ref = eng_chained.predict(xp)
    bitwise_ok = bool(
        np.array_equal(eng_fused.predict(xp), ref)
        and np.array_equal(eng_overlap.predict(xp), ref)
        and np.array_equal(eng_ring.predict(xp), ref)  # per-batch fallback path
    )
    # the ring's headline, pinned deterministically before the driven rounds:
    # a saturated window of R full max-bucket slots is exactly ONE
    # serve.dispatch_seconds observation (registry-delta counted), fill 1.0,
    # and its drained logits are bitwise-identical to the per-batch path
    xr = rng.normal(0, 1, (ring_slots * cap, size, size, 3)).astype("float32")
    ring_ref = np.concatenate(
        [eng_chained.predict(np.ascontiguousarray(xr[i * cap:(i + 1) * cap]))
         for i in range(ring_slots)])
    s0 = reg.snapshot()
    entries = [eng_ring.ring_stage(np.ascontiguousarray(xr[i * cap:(i + 1) * cap]))
               for i in range(ring_slots)]
    ring_out = eng_ring.ring_dispatch(entries).result()
    s1 = reg.snapshot()
    ring_probe = {
        "slots": ring_slots,
        "rows": int(ring_slots * cap),
        "dispatch_seconds_count_delta": int(
            s1.get("serve.dispatch_seconds.count", 0)
            - s0.get("serve.dispatch_seconds.count", 0)),
        "ring_dispatches_delta": int(
            s1.get("serve.ring_dispatches", 0) - s0.get("serve.ring_dispatches", 0)),
        "fill": float(s1.get("serve.ring_fill", 0.0)),
        "bitwise_ok": bool(np.array_equal(ring_out, ring_ref)),
    }
    # single-dispatch reference for the efficiency column: cost FLOPs of the
    # full max bucket over its measured direct latency (one warm predict)
    xb = rng.normal(0, 1, (cap, size, size, 3)).astype("float32")
    eng_chained.predict(xb)
    lat = []
    for _ in range(5):
        t0 = time.perf_counter()
        eng_chained.predict(xb)
        lat.append(time.perf_counter() - t0)
    lat.sort()
    flops_1 = obs_device.flops_for(f"serve_b{cap}_s{size}_k1")
    single_dispatch_ref = flops_1 / _percentile(lat, 0.5) if lat[0] > 0 else 0.0

    common = dict(max_batch=max_batch, max_wait_ms=10.0, queue_depth=max(256, 8 * max_batch))
    batchers = {
        "sync": MicroBatcher(eng_chained.predict, **common).start(),
        "pipelined": PipelinedBatcher(eng_chained, max_inflight=max_inflight, **common).start(),
        "fused": PipelinedBatcher(eng_fused, max_inflight=max_inflight, **common).start(),
        "overlapped": PipelinedBatcher(
            eng_overlap, max_inflight=max_inflight, run_max=run_max, **common
        ).start(),
        "ring": PipelinedBatcher(
            eng_ring, max_inflight=max_inflight, run_max=run_max,
            ring_min_fill=ring_min_fill, **common
        ).start(),
    }
    runs = {m: [] for m in batchers}  # per round: (qps, lat, deltas dict)
    try:
        for b in batchers.values():  # warm every path off the measured window
            _drive_concurrent(b, image, min(2 * max_batch, n_requests), n_clients)
        for _ in range(rounds):
            for mode, b in batchers.items():
                run_counts0 = _hist_counts("serve.run_seconds")
                s0 = reg.snapshot()
                qps, lat = _drive_concurrent(b, image, n_requests, n_clients)
                s1 = reg.snapshot()
                d = {k: s1.get(k, 0) - s0.get(k, 0) for k in (
                    "serve.dispatch_seconds.count", "serve.batch_size.count",
                    "serve.batch_size.sum", "serve.dispatches_per_wakeup.count",
                    "serve.dispatches_per_wakeup.sum", "serve.dispatched_flops",
                    "serve.dispatched_bytes", "serve.run_seconds.sum",
                    "serve.ring_dispatches", "serve.ring_slots_per_dispatch.count",
                    "serve.ring_slots_per_dispatch.sum",
                )}
                d["registry_q"] = _hist_delta_quantiles("serve.run_seconds", run_counts0)
                runs[mode].append((qps, lat, d))
    finally:
        for b in batchers.values():
            b.stop()
    modes = {}
    for mode, rows in runs.items():
        ordered = sorted(rows, key=lambda r: r[0])
        med_qps, med_lat, _ = ordered[len(ordered) // 2]
        # instruments sum over ALL rounds: the steady-state windows, not one
        # lucky round, back the structural claims
        tot = {k: sum(r[2][k] for r in rows) for k in rows[0][2] if k != "registry_q"}
        reg_q = ordered[len(ordered) // 2][2]["registry_q"]
        dispatches = tot["serve.dispatch_seconds.count"]
        batches = tot["serve.batch_size.count"]
        wakeups = tot["serve.dispatches_per_wakeup.count"]
        modes[mode] = {
            "qps": round(med_qps, 2),
            "qps_rounds": [round(q, 2) for q, _, _ in rows],
            "p99_ms": round(_percentile(med_lat, 0.99) * 1e3, 3),
            "p50_ms_registry": reg_q["p50_ms"],
            "p99_ms_registry": reg_q["p99_ms"],
            "avg_fill": round(tot["serve.batch_size.sum"] / batches / max_batch, 3) if batches else 0.0,
            "dispatches_per_request": round(dispatches / (rounds * n_requests), 4),
            # None for sync: the MicroBatcher has no completion wake-ups
            "dispatches_per_wakeup": (
                round(tot["serve.dispatches_per_wakeup.sum"] / wakeups, 4) if wakeups else None
            ),
            "dispatched_gflops": round(tot["serve.dispatched_flops"] / 1e9, 3),
            "dispatched_gbytes": round(tot["serve.dispatched_bytes"] / 1e9, 3),
            # the steady-state dispatch-efficiency window (the same math the
            # serve.achieved_flops_per_s pull gauge exposes, but delta-scoped
            # to this mode's rounds)
            "achieved_flops_per_s": round(
                tot["serve.dispatched_flops"] / tot["serve.run_seconds.sum"], 1
            ) if tot["serve.run_seconds.sum"] > 0 else 0.0,
            # ring instruments: windows consumed + average staged slots per
            # window (identically 0/None for the four per-batch arms)
            "ring_windows": int(tot["serve.ring_dispatches"]),
            "ring_slots_per_window": (
                round(tot["serve.ring_slots_per_dispatch.sum"]
                      / tot["serve.ring_slots_per_dispatch.count"], 3)
                if tot["serve.ring_slots_per_dispatch.count"] else None
            ),
        }
    return {
        "image_size": size,
        "max_bucket": cap,
        "max_batch": max_batch,
        "clients": n_clients,
        "requests_per_round": n_requests,
        "rounds": rounds,
        "max_inflight": max_inflight,
        "run_max": run_max,
        "staging_slots": staging_slots,
        "fuse_ladder": list(fuse_ladder),
        "bitwise_ok": bitwise_ok,
        "single_dispatch_achieved_flops_per_s": round(single_dispatch_ref, 1),
        "ring_slots": ring_slots,
        "ring_min_fill": ring_min_fill,
        "ring_probe": ring_probe,
        "modes": modes,
        "overlapped_speedup_vs_sync": (
            round(modes["overlapped"]["qps"] / modes["sync"]["qps"], 4)
            if modes["sync"]["qps"] else None
        ),
        "ring_speedup_vs_sync": (
            round(modes["ring"]["qps"] / modes["sync"]["qps"], 4)
            if modes["sync"]["qps"] else None
        ),
        "cpu_rehearsal_note": _STRUCTURAL_CPU_CAVEAT,
    }


_QUANT_CPU_CAVEAT = (
    "cpu_rehearsal: QPS deltas between the wire modes are contention-noise on "
    "a 1-core box (the forward dominates; the transfer it shrinks is nearly "
    "free host-to-host). Unlike the overlap rounds, though, the HEADLINE "
    "claim here does not need an accelerator: per-request serve.h2d_bytes is "
    "registry math — the uint8 wire moves exactly 1/4 of the f32 wire's "
    "bytes on ANY host — and the parity verdicts (bitwise for the zero-mean "
    "denorm, measured max-abs delta under the configured atol otherwise, "
    "int8 top-1 agreement over the gate) are host-independent. The "
    "throughput win lands where H2D and HBM are real — the ROADMAP item 5 "
    "hardware rung. Note: random-init logits are a WORST CASE for top-1 "
    "agreement (near-ties everywhere, no trained margins), so the bench "
    "gate is configured below the production default."
)


def _quant_ab(net, folded, buckets, size, iters, rounds, rng, *,
              mean, std, top1_min):
    """The --quant measurement: ONE interleaved sweep over the three serving
    precisions — f32 (wire f32, weights f32), uint8-wire (wire u8, weights
    f32), and int8 (wire u8, weights int8) — at every bucket. Per mode:
    median-of-rounds QPS + p50/p99, per-request serve.h2d_bytes and
    serve.dispatched_bytes registry deltas (the transferred-byte and
    cost-byte instruments), and the parity verdicts: the zero-mean bitwise
    check, the mean/std wire delta vs the configured atol, and the int8
    export's gated top-1 agreement (serve/quant.py)."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.config import QuantConfig
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.serve import quant
    from yet_another_mobilenet_series_tpu.serve.engine import InferenceEngine
    from yet_another_mobilenet_series_tpu.serve.export import InferenceBundle

    wire_atol = QuantConfig().wire_atol  # the configured (production) gate
    reg = get_registry()
    bundle_f32 = InferenceBundle(net=net, params=folded, meta={})
    # the int8 export pass, gated exactly as cli/serve.py would run it:
    # seeded synthetic raw pixels normalized with the pipeline's mean/std
    calib_raw = rng.randint(0, 256, (32, size, size, 3)).astype(np.uint8)
    calib = quant.normalize_reference(calib_raw, mean, std)
    quantized, int8_report = quant.calibrate_and_quantize(
        net, folded, calib, top1_min=top1_min)
    bundle_int8 = InferenceBundle(net=net, params=quantized, meta={"quant": int8_report})

    common = dict(buckets=buckets, image_size=size, image_sizes=(size,), fuse_ladder=())
    engines = {
        "f32": InferenceEngine(bundle_f32, **common),
        "uint8_wire": InferenceEngine(bundle_f32, wire="uint8", wire_mean=mean,
                                      wire_std=std, **common),
        "int8": InferenceEngine(bundle_int8, wire="uint8", wire_mean=mean,
                                wire_std=std, **common),
    }
    for e in engines.values():
        e.warmup()

    # parity verdicts, all on one raw batch at the largest bucket
    cap = buckets[-1]
    raw = rng.randint(0, 256, (cap, size, size, 3)).astype(np.uint8)
    norm = quant.normalize_reference(raw, mean, std)
    ref = engines["f32"].predict(norm)
    got_u8 = engines["uint8_wire"].predict(raw)
    wire_delta = float(np.max(np.abs(got_u8 - ref)))
    # the bitwise regime: a zero-mean denorm is a single per-channel
    # multiply — pinned here with a dedicated identity-norm engine pair
    e_id_u8 = InferenceEngine(bundle_f32, wire="uint8", **common)
    id_bitwise = bool(np.array_equal(
        e_id_u8.predict(raw), engines["f32"].predict(quant.normalize_reference(raw))))
    got_int8 = engines["int8"].predict(raw)
    int8_top1 = float(np.mean(np.argmax(got_int8, -1) == np.argmax(ref, -1)))

    inputs = {
        "f32": {b: np.ascontiguousarray(norm[:b]) if b <= cap else None for b in buckets},
        "uint8_wire": {b: np.ascontiguousarray(raw[:b]) for b in buckets},
    }
    inputs["int8"] = inputs["uint8_wire"]
    per_bucket = []
    mode_tot = {m: {"h2d": 0.0, "cost": 0.0, "requests": 0} for m in engines}
    for b in buckets:
        row = {"batch": b}
        runs = {m: [] for m in engines}
        for e, x in ((engines[m], inputs[m][b]) for m in engines):
            e.predict(x)  # untimed page-in per mode
        for _ in range(rounds):
            for m, e in engines.items():  # interleaved: drift hits all alike
                x = inputs[m][b]
                s0 = reg.snapshot()
                lat = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    e.predict(x)
                    lat.append(time.perf_counter() - t0)
                s1 = reg.snapshot()
                lat.sort()
                runs[m].append((b / (sum(lat) / len(lat)), lat))
                mode_tot[m]["h2d"] += s1.get("serve.h2d_bytes", 0) - s0.get("serve.h2d_bytes", 0)
                mode_tot[m]["cost"] += (
                    s1.get("serve.dispatched_bytes", 0) - s0.get("serve.dispatched_bytes", 0))
                mode_tot[m]["requests"] += iters
        for m in engines:
            ordered = sorted(runs[m], key=lambda r: r[0])
            qps, lat = ordered[len(ordered) // 2]
            row[f"qps_{m}"] = round(qps, 2)
            row[f"p50_ms_{m}"] = round(_percentile(lat, 0.50) * 1e3, 3)
            row[f"p99_ms_{m}"] = round(_percentile(lat, 0.99) * 1e3, 3)
        per_bucket.append(row)

    modes = {}
    for m, e in engines.items():
        t = mode_tot[m]
        modes[m] = {
            "quant_mode": e.quant_mode,  # the build_info label this mode serves under
            "h2d_bytes_per_request": round(t["h2d"] / t["requests"], 1),
            "dispatched_bytes_per_request": round(t["cost"] / t["requests"], 1),
        }
    wire_ratio = (modes["f32"]["h2d_bytes_per_request"]
                  / modes["uint8_wire"]["h2d_bytes_per_request"])
    return {
        "image_size": size,
        "buckets": list(buckets),
        "rounds": rounds,
        "iters_per_round": iters,
        "mean": list(mean),
        "std": list(std),
        "per_bucket": per_bucket,
        "modes": modes,
        # the headline: transferred bytes per request, registry math. The
        # cost-analysis dispatched_bytes columns above are a COMPUTE-traffic
        # metric (they count the in-program dequant intermediates too), so
        # the residency win reads from int8_export.resident_shrink and the
        # transfer win from this ratio — docs/OBSERVABILITY.md.
        "wire_bytes_ratio": round(wire_ratio, 4),
        "parity": {
            "identity_norm_bitwise": id_bitwise,
            "wire_max_abs_logit_delta": round(wire_delta, 9),
            "wire_atol": wire_atol,
            "wire_parity_ok": wire_delta <= wire_atol,
            "int8_top1_agreement_calib": int8_report["top1_agreement"],
            "int8_top1_agreement_heldout": int8_top1,
            "int8_top1_min": top1_min,
        },
        "int8_export": {
            "quantized_tensors": int8_report["quantized_tensors"],
            "bytes_f32": int8_report["bytes_f32"],
            "bytes_int8": int8_report["bytes_int8"],
            "resident_shrink": round(
                int8_report["bytes_f32"] / int8_report["bytes_int8"], 4),
            "max_abs_logit_delta_calib": int8_report["max_abs_logit_delta"],
            "calib_images": int8_report["calib"]["images"],
        },
        "cpu_rehearsal_note": _QUANT_CPU_CAVEAT,
    }


_FLEET_CPU_CAVEAT = (
    "cpu_rehearsal: router, replicas, and load generator share this box's "
    "core(s), so absolute QPS and latency are contention-dominated. The "
    "pinned structural claims are the availability/accounting invariants "
    "(every submitted request resolves; a kill -9 costs retries+ejection, "
    "not client-visible failures), hedging firing at the measured-p-quantile "
    "timer with wins counted, and the autoscaler trace rising and falling "
    "with cooldown respected. Absolute fleet throughput is an accelerator "
    "measurement — same caveat discipline as r02/r04/r05."
)


def _fleet_round(router, image, *, n_requests, target_qps, seed,
                 mid_hook=None, mid_at=None, result_timeout_s=120.0):
    """One open-loop Poisson round through the fleet router. Arrivals fire
    on schedule regardless of completions; EVERY future is resolved at the
    end (a hang shows as ``unresolved`` > 0, never a stuck bench).
    ``mid_hook`` fires once before request index ``mid_at`` — the kill -9
    injection point."""
    from concurrent.futures import TimeoutError as FutTimeout

    import numpy as np

    from yet_another_mobilenet_series_tpu.serve.client import ClientHTTPError

    rs = np.random.RandomState(seed)
    gaps = rs.exponential(1.0 / target_qps, size=n_requests)
    pending = []
    lat = []
    lat_lock = threading.Lock()

    def _stamp(t0):
        # latency is stamped AT resolution (done callback), not when the
        # collector loop gets around to the future — otherwise every number
        # silently includes the remainder of the arrival schedule
        def cb(fut):
            if fut.exception() is None:
                with lat_lock:
                    lat.append(time.perf_counter() - t0)
        return cb

    t_start = time.perf_counter()
    t_next = t_start
    for i in range(n_requests):
        t_next += gaps[i]
        delay = t_next - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if mid_hook is not None and i == mid_at:
            mid_hook()
            mid_hook = None
        t0 = time.perf_counter()
        fut = router.submit(image)
        fut.add_done_callback(_stamp(t0))
        pending.append(fut)
    out = {"submitted": n_requests, "completed": 0, "rejected": 0, "failed": 0,
           "unresolved": 0}
    for fut in pending:
        try:
            fut.result(timeout=result_timeout_s)
            out["completed"] += 1
        except FutTimeout:
            out["unresolved"] += 1  # a real hang: the router broke its contract
        except ClientHTTPError as e:
            out["rejected" if e.status < 500 else "failed"] += 1
        except Exception:  # noqa: BLE001 — typed route failure
            out["failed"] += 1
    wall = time.perf_counter() - t_start
    lat.sort()
    out.update({
        "wall_s": round(wall, 3),
        "qps": round(out["completed"] / wall, 2) if wall else 0.0,
        "p50_ms": round(_percentile(lat, 0.50) * 1e3, 3),
        "p99_ms": round(_percentile(lat, 0.99) * 1e3, 3),
    })
    return out


def _fleet_registry_delta(reg, s0, keys):
    s1 = reg.snapshot()
    return {k.split(".", 1)[1]: int(s1.get(k, 0) - s0.get(k, 0)) for k in keys}


_FLEET_AB_KEYS = ("serve.hedges", "serve.hedge_wins", "serve.hedge_wasted",
                  "fleet.routed", "fleet.route_retries")
_FLEET_KILL_KEYS = ("fleet.route_retries", "fleet.ejections", "fleet.readmissions",
                    "fleet.restarts", "fleet.chaos_kills", "serve.hedges")


def measure_fleet(arch, image_size, buckets, *, replicas, requests, target_qps,
                  straggler_ms, seed, phase_s, log_root):
    """The ``--fleet`` measurement: a real fleet of cli/serve.py replica
    subprocesses behind the router tier (serve/router.py), exercised three
    ways on shared seeded schedules:

    1. **hedged vs unhedged A/B** — one straggler replica (highest slot)
       carries seeded injected completion latency (serve/faults.py), both
       rounds share one Poisson arrival schedule, and the hedged round arms
       the p-quantile timer (serve/hedge.py): ``serve.hedges`` fired,
       ``serve.hedge_wins`` first-answer wins, tail delta recorded.
    2. **kill -9 availability** — mid-round SIGKILL of a serving replica;
       the router's transport retry + ejection must account for EVERY
       submitted request as completed or typed-rejected (failed == 0, no
       client ever hangs), and the supervisor must restart the corpse.
    3. **autoscaler diurnal trace** — the fleet scales to 1, the straggler
       drains away, and a low/high/low open-loop schedule drives the
       Autoscaler (tail-latency + queue-depth signals, cooldown
       hysteresis): the N-over-time trace must rise under the peak and
       fall after it.
    """
    import numpy as np

    from yet_another_mobilenet_series_tpu.cli.fleet import FleetSupervisor
    from yet_another_mobilenet_series_tpu.obs.fleet import FleetFederation, FlightRecorder
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry, quantiles_from_counts
    from yet_another_mobilenet_series_tpu.serve.autoscale import Autoscaler
    from yet_another_mobilenet_series_tpu.serve.hedge import Hedger
    from yet_another_mobilenet_series_tpu.serve.router import Router
    from yet_another_mobilenet_series_tpu.serve.signals import SLOTracker

    reg = get_registry()
    bundle_dir = os.path.join(log_root, "bundle")
    _export_bundles_in_child([{"model": arch, "image_size": image_size, "seed": 0,
                               "out": bundle_dir}])

    replica_argv = [
        f"serve.bundle={bundle_dir}",
        f"data.image_size={image_size}",
        f"serve.buckets=[{','.join(str(b) for b in buckets)}]",
        "serve.max_wait_ms=2.0",
        "serve.drain_timeout_s=10",
    ]
    straggler_slot = replicas - 1
    per_slot = {straggler_slot: [
        "serve.faults.enable=true",
        f"serve.faults.latency_ms={straggler_ms}",
        "serve.faults.latency_rate=0.3",
        "serve.faults.fail_at=result",
        f"serve.faults.seed={seed + 7}",
    ]}
    class _StderrLog:
        # the bench contract owns stdout (ONE JSON line); supervisor
        # progress goes to stderr like every other bench diagnostic
        def log(self, msg):
            print(msg, file=sys.stderr, flush=True)

    router = Router(poll_interval_s=0.25, eject_failures=2, route_attempts=3,
                    client_timeout_s=60.0, seed=seed).start()
    # fleet observability under measurement: the recorder hears every router
    # event from request #1 (the kill round's ejection is the incident
    # trigger), the federation scrapes on the bench's schedule (the bench IS
    # the single owner cli/fleet.py's main loop would otherwise be)
    recorder = FlightRecorder(log_root, min_interval_s=0.0)
    router.set_event_sink(recorder.record)
    federation = FleetFederation(router.backends, slo=SLOTracker(),
                                 recorder=recorder)
    fleet = FleetSupervisor(
        replica_argv=replica_argv, log_dir=log_root, replicas=replicas,
        per_slot_argv=per_slot, spawn_timeout_s=240.0, drain_timeout_s=30.0,
        on_change=router.set_backends, logger=_StderrLog(),
    )
    rng = np.random.RandomState(seed)
    image = rng.normal(0, 1, (image_size, image_size, 3)).astype("float32")
    out = {"replicas": replicas, "image_size": image_size, "seed": seed,
           "straggler": {"slot": straggler_slot, "latency_ms": straggler_ms,
                         "latency_rate": 0.3}}
    try:
        _parent_must_be_off_the_backend()
        t0 = time.perf_counter()
        fleet.start()
        out["spawn_s"] = round(time.perf_counter() - t0, 2)

        # warm + calibrate: sequential closed-loop requests teach the router
        # latency histogram (the hedge timer's input) and give the pacing
        # p50. The timer quantile sits BELOW the straggler's hit rate
        # (~0.5 routing share x 0.3 injection) so the timer derives from the
        # fast cluster and fires well inside the injected stall.
        hedger = Hedger(quantile=0.8, min_samples=20, min_timer_ms=10.0)
        warm_lat = []
        for _ in range(40):
            t1 = time.perf_counter()
            router.submit(image).result(timeout=60)
            warm_lat.append(time.perf_counter() - t1)
        warm_lat.sort()
        p50_s = max(_percentile(warm_lat, 0.5), 1e-3)
        if target_qps <= 0:
            # well below the box's capacity: the A/B must measure the
            # straggler's tail, not open-loop queueing (which hedging
            # rightly cannot fix)
            target_qps = max(2.0, 0.35 / p50_s)
        out["warm_p50_ms"] = round(p50_s * 1e3, 3)
        out["target_qps"] = round(target_qps, 2)
        timer_s = hedger.timer_s("interactive")
        out["hedge_timer_ms"] = round(timer_s * 1e3, 3) if timer_s is not None else None

        # 1. hedged vs unhedged on one shared seeded schedule
        ab = {}
        for mode, h in (("unhedged", None), ("hedged", hedger)):
            router.set_hedger(h)
            s0 = reg.snapshot()
            rnd = _fleet_round(router, image, n_requests=requests,
                               target_qps=target_qps, seed=seed)
            # a hedge-won request's PRIMARY may still be inside the
            # straggler's stall: let the losers' late answers land (and be
            # counted dropped) before the delta is read
            time.sleep(2.5 * straggler_ms / 1e3)
            rnd.update(_fleet_registry_delta(reg, s0, _FLEET_AB_KEYS))
            ab[mode] = rnd
        router.set_hedger(None)
        ab["p99_ms_unhedged"] = ab["unhedged"]["p99_ms"]
        ab["p99_ms_hedged"] = ab["hedged"]["p99_ms"]
        ab["hedged_tail_speedup"] = (
            round(ab["unhedged"]["p99_ms"] / ab["hedged"]["p99_ms"], 4)
            if ab["hedged"]["p99_ms"] else None
        )
        out["hedge_ab"] = ab

        # 1b. federation correctness on live replicas: one scrape pins the
        # window baseline, a seeded round generates completions, and the
        # federated windowed p99 must EQUAL the pooled per-replica reference
        # recomputed here from THE SAME scraped documents — independent
        # delta/reset math, same quantiles_from_counts interpolation. Any
        # drift is a federation bug, not noise, so it raises.
        federation.scrape_once()
        docs0 = federation.last_varz()
        obs_rnd = _fleet_round(router, image, n_requests=max(30, requests // 2),
                               target_qps=target_qps, seed=seed + 11)
        federation.scrape_once()
        docs1 = federation.last_varz()
        fam = "serve.latency_seconds.interactive"
        pooled, bounds = None, None
        for key, doc in docs1.items():
            st = (doc.get("histograms") or {}).get(fam)
            if st is None:
                continue
            cur = [int(c) for c in st["counts"]]
            prev_st = ((docs0.get(key) or {}).get("histograms") or {}).get(fam)
            prev = [int(c) for c in prev_st["counts"]] if prev_st else None
            if prev is None or len(prev) != len(cur):
                delta = cur
            else:
                delta = [c - p for c, p in zip(cur, prev)]
                if any(d < 0 for d in delta):
                    delta = cur  # replica restarted: its whole history is the delta
            bounds = st["bounds"]
            pooled = delta if pooled is None else [a + d for a, d in zip(pooled, delta)]
        if pooled and sum(pooled):
            (pooled_p99_s,) = quantiles_from_counts(bounds, pooled, (0.99,))
        else:
            pooled_p99_s = 0.0
        fed_p99_s = reg.gauge("fleet.window_p99_seconds.interactive").value
        if abs(fed_p99_s - pooled_p99_s) > 1e-9:
            raise AssertionError(
                f"federated p99 {fed_p99_s} != pooled reference {pooled_p99_s}")
        obs = {
            "round": obs_rnd,
            "federated_p99_ms": round(fed_p99_s * 1e3, 3),
            "pooled_p99_ms": round(pooled_p99_s * 1e3, 3),
            "p99_match": True,
            "federated_replicas": len(docs1),
            "slo": federation.snapshot().get("slo"),
        }
        out["obs"] = obs

        # federation overhead on the submit path: the scrape loop hammers at
        # a cadence ~10x tighter than any real poll interval while
        # sequential submits measure p50. On this contention-dominated box
        # the delta is an upper bound (scraper and submitter share cores);
        # the structural claim is that the scrape never holds the router
        # lock, and the docs record the rehearsal number with that caveat.
        def _p50_submit(n=40):
            ts = []
            for _ in range(n):
                t1 = time.perf_counter()
                router.submit(image).result(timeout=60)
                ts.append(time.perf_counter() - t1)
            ts.sort()
            return max(_percentile(ts, 0.5), 1e-9)

        base_p50 = _p50_submit()
        stop_scrape = threading.Event()

        def _hammer():
            while not stop_scrape.is_set():
                federation.scrape_once()
                time.sleep(0.02)

        th = threading.Thread(target=_hammer, name="bench-scrape-hammer", daemon=True)
        th.start()
        try:
            scraped_p50 = _p50_submit()
        finally:
            stop_scrape.set()
            th.join(timeout=10)
        obs["submit_p50_ms"] = round(base_p50 * 1e3, 3)
        obs["submit_p50_ms_under_scrape"] = round(scraped_p50 * 1e3, 3)
        obs["federation_overhead_pct"] = round(
            (scraped_p50 - base_p50) / base_p50 * 100.0, 2)
        # the production-shaped number: mean scrape cost amortized over the
        # DEFAULT cadence (the router poll interval the supervisor rides,
        # config.py FleetObsConfig) — duty cycle, the fraction of wall time
        # federation occupies at all, an upper bound on submit inflation
        scrape_st = reg.histogram("fleet.scrape_seconds").state()
        scrape_mean_s = scrape_st["sum"] / max(scrape_st["count"], 1)
        cadence_s = 0.25  # serve.fleet.poll_interval_s default
        obs["scrape_mean_ms"] = round(scrape_mean_s * 1e3, 3)
        obs["amortized_overhead_pct"] = round(scrape_mean_s / cadence_s * 100.0, 3)

        # 2. kill -9 a serving (non-straggler) replica mid-round: the books
        # must balance with zero client-visible failures, and the
        # supervisor must restart the corpse
        s0 = reg.snapshot()

        def _chaos_kill():
            # the injector announces its own fault to the flight recorder:
            # arming here is deterministic, where the router-side ejection
            # trigger races the supervisor's set_backends (which usually
            # removes the corpse before enough failures accrue to eject)
            recorder.trigger("chaos_kill")
            fleet.kill_replica(slot=0, sig=signal.SIGKILL)

        kill = _fleet_round(
            router, image, n_requests=requests, target_qps=target_qps, seed=seed + 1,
            mid_at=requests // 3,
            mid_hook=_chaos_kill,
        )
        # bounded wait for the restart to land (counts fleet.restarts)
        deadline = time.monotonic() + 120
        while len(fleet.addresses()) < replicas and time.monotonic() < deadline:
            time.sleep(0.25)
        kill.update(_fleet_registry_delta(reg, s0, _FLEET_KILL_KEYS))
        kill["replicas_after_restart"] = len(fleet.addresses())
        out["kill"] = kill

        # the chaos trigger armed the flight recorder (plus any natural
        # ejection event in the ring): one more scrape for a fresh federated
        # snapshot, then the dump — the incident artifact (event ring +
        # fleet snapshot + per-replica /varz) the round pins
        federation.scrape_once()
        incident = recorder.maybe_dump(federation)
        obs["incident"] = os.path.basename(incident) if incident else None
        if incident:
            with open(incident) as f:
                idoc = json.load(f)
            obs["incident_reason"] = idoc["reason"]
            obs["incident_events"] = len(idoc["events"])
            obs["incident_has_fleet_snapshot"] = "fleet" in idoc and "replica_varz" in idoc

        # 3. autoscaler over a diurnal low/high/low open-loop schedule,
        # starting from one clean replica (the straggler drains first).
        # Thresholds calibrate off the A/B round's OPEN-LOOP p50 — the
        # sequential warm p50 is dominated by per-request HTTP overhead the
        # concurrent path pipelines away, so it would set the bar far above
        # anything the peak can reach.
        fleet.scale_to(1)
        router.poll_once()
        ab_p50_ms = max(ab["unhedged"]["p50_ms"], 1.0)
        low_s, high_s, trough_s = phase_s
        autoscaler = Autoscaler(
            fleet, router,
            min_replicas=1, max_replicas=min(replicas + 1, 3),
            interval_s=0.4, cooldown_s=1.5,
            # the dead band separates this box's measured light-traffic
            # windows (~5-10ms p99) from its saturated ones (>= ~50ms,
            # often seconds): up above the idle ceiling, down below it
            up_p99_ms=max(6.0 * ab_p50_ms, 30.0),
            down_p99_ms=max(2.5 * ab_p50_ms, 12.0),
            up_queue_depth=2.0, down_queue_depth=1.0,
        ).start()
        # the peak must EXCEED what the box can serve (router + replicas +
        # load gen share its cores), so the latency windows really rise
        phases = [(0.4 * target_qps, low_s),
                  (12.0 * target_qps, high_s),
                  (0.4 * target_qps, trough_s)]
        diurnal = []
        for i, (qps, dur) in enumerate(phases):
            n = max(4, int(qps * dur))
            rnd = _fleet_round(router, image, n_requests=n, target_qps=qps,
                               seed=seed + 2 + i)
            diurnal.append({"phase": ("low", "high", "trough")[i],
                            "target_qps": round(qps, 2), **rnd})
        # let the trough's relaxed signals finish the scale-down
        settle_until = time.monotonic() + 3 * autoscaler._cooldown_s
        while time.monotonic() < settle_until:
            time.sleep(0.3)
        autoscaler.stop()
        trace = autoscaler.trace
        ns = [r["n"] for r in trace]
        action_ts = [r["t"] for r in trace if r["action"] != "hold"]
        out["autoscale"] = {
            "min_replicas": autoscaler.min_replicas,
            "max_replicas": autoscaler.max_replicas,
            "cooldown_s": autoscaler._cooldown_s,
            "phases": diurnal,
            "trace": trace,
            "n_start": ns[0] if ns else None,
            "n_peak": max(ns) if ns else None,
            "n_end": ns[-1] if ns else None,
            "actions": [r for r in trace if r["action"] != "hold"],
            "cooldown_respected": all(
                b - a >= 0.9 * autoscaler._cooldown_s
                for a, b in zip(action_ts, action_ts[1:])
            ),
        }
        out["cpu_rehearsal_note"] = _FLEET_CPU_CAVEAT
        return out
    finally:
        router.stop()
        fleet.stop()


_ZOO_CPU_CAVEAT = (
    "cpu_rehearsal: both replicas, the router, the cascade policy, and the "
    "load generator share this box's core(s), so absolute latency/QPS are "
    "contention-dominated. The pinned structural claims are "
    "host-independent: the model-sharded arm shows ZERO misroutes (per-"
    "replica serve.model_requests deltas) and zero 5xx on the same seeded "
    "trace; the cascade arm escalates > 0 requests, every answer is "
    "bitwise one of the two per-image references (escalated answers EQUAL "
    "the big-only arm's), and its fleet-wide dispatched-FLOPs/request mean "
    "sits strictly below the big-only arm's. Wall-clock speedups are an "
    "accelerator measurement — same caveat discipline as r06/r07."
)


def _zoo_scrape_flops(router):
    """Sum ``serve.dispatched_flops`` across every replica's /varz registry
    snapshot. Dispatch cost is engine-side (per replica process), so per-arm
    deltas of this sum are the fleet-wide dispatched cost — the cascade's
    cost-proxy instrument."""
    total, per = 0.0, {}
    for key, client in router.backends():
        _status, doc = client.varz(timeout_s=10.0)
        v = float(((doc or {}).get("metrics") or {}).get("serve.dispatched_flops", 0))
        per[key] = v
        total += v
    return total, per


def _zoo_scrape_model_requests(router, models):
    """Per-replica ``serve.model_requests.{model}`` counters — the misroute
    instrument: on a model-sharded fleet a replica must never count a
    request for a model it does not serve."""
    per = {}
    for key, client in router.backends():
        _status, doc = client.varz(timeout_s=10.0)
        met = (doc or {}).get("metrics") or {}
        per[key] = {m: int(met.get(f"serve.model_requests.{m}", 0)) for m in models}
    return per


def _zoo_round(submit, images, models, *, target_qps, seed, result_timeout_s=120.0):
    """One open-loop Poisson round over a FIXED per-index plan: request i
    submits ``images[i]`` pinned to ``models[i]`` (None = unqualified — the
    cascade decides the tier). Latency stamps at resolution like
    ``_fleet_round``; answers come back INDEXED so the caller can check
    every one bitwise against its per-image reference."""
    from concurrent.futures import TimeoutError as FutTimeout

    import numpy as np

    from yet_another_mobilenet_series_tpu.serve.client import ClientHTTPError

    rs = np.random.RandomState(seed)
    n = len(images)
    gaps = rs.exponential(1.0 / target_qps, size=n)
    pending = []
    lat = {}
    lat_lock = threading.Lock()

    def _stamp(i, t0):
        def cb(fut):
            if fut.exception() is None:
                with lat_lock:
                    lat[i] = time.perf_counter() - t0
        return cb

    t_start = time.perf_counter()
    t_next = t_start
    for i in range(n):
        t_next += gaps[i]
        delay = t_next - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t0 = time.perf_counter()
        fut = submit(images[i], models[i])
        fut.add_done_callback(_stamp(i, t0))
        pending.append(fut)
    out = {"submitted": n, "completed": 0, "rejected": 0, "failed": 0,
           "unresolved": 0}
    answers = [None] * n
    for i, fut in enumerate(pending):
        try:
            answers[i] = np.asarray(fut.result(timeout=result_timeout_s))
            out["completed"] += 1
        except FutTimeout:
            out["unresolved"] += 1  # a real hang: the tier broke its contract
        except ClientHTTPError as e:
            out["rejected" if e.status < 500 else "failed"] += 1
        except Exception:  # noqa: BLE001 — typed route failure
            out["failed"] += 1
    wall = time.perf_counter() - t_start
    per_model = {}
    for i, m in enumerate(models):
        if i in lat:
            per_model.setdefault(m or "cascade", []).append(lat[i])
    all_lat = sorted(lat.values())
    out.update({
        "wall_s": round(wall, 3),
        "qps": round(out["completed"] / wall, 2) if wall else 0.0,
        "p50_ms": round(_percentile(all_lat, 0.50) * 1e3, 3),
        "p99_ms": round(_percentile(all_lat, 0.99) * 1e3, 3),
        "per_model": {
            m: {"n": len(v),
                "p50_ms": round(_percentile(sorted(v), 0.50) * 1e3, 3),
                "p99_ms": round(_percentile(sorted(v), 0.99) * 1e3, 3)}
            for m, v in sorted(per_model.items())
        },
    })
    return out, answers


def measure_zoo(arch, image_size, *, requests, target_qps, seed, threshold,
                int8_top1_min, log_root):
    """The ``--zoo`` measurement: ONE 2-replica model-sharded fleet — slot 0
    serves the int8 'small' tier, slot 1 the f32 'big' tier, via per-slot
    ``serve.zoo.models`` assignments with the placement advertised to the
    router — A/B'd three ways over ONE seeded trace of images:

    1. **big_only** — every request pinned ``X-Model: big``: the
       one-model-per-fleet cost/latency baseline.
    2. **sharded** — a seeded 50/50 model-pin mix through the model-aware
       pick; per-replica ``serve.model_requests.{model}`` deltas must show
       ZERO misroutes and the books must show zero 5xx.
    3. **cascade** — unqualified submits through serve/cascade.py: the
       small tier answers confident requests, low-margin ones re-submit to
       the big tier. Escalations must be > 0, every answer must be bitwise
       one of the two per-image references (escalated answers EQUAL the
       big-only arm's), and the fleet-wide dispatched-FLOPs/request mean
       must sit STRICTLY below the big_only arm's.

    The threshold defaults to the MEDIAN reference margin, so both cascade
    outcomes (answered-small and escalated) are populated by construction.
    Buckets are pinned to [1] so every arm's answers are bitwise-comparable
    against the explicit-pin reference pass by construction (no padding
    variation between arms)."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.cli.fleet import FleetSupervisor
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.serve.cascade import CascadeTier, softmax_margin
    from yet_another_mobilenet_series_tpu.serve.router import Router

    reg = get_registry()
    rng = np.random.RandomState(seed)
    # two genuinely different cost tiers: the small tier is the contract-test
    # tiny preset (int8 weights), the big tier is deeper/wider so the
    # cascade's FLOPs win is structural, not noise
    calib_npy = os.path.join(log_root, "calib.npy")
    np.save(calib_npy, rng.normal(0, 1, (8, image_size, image_size, 3)).astype("float32"))
    small_dir = os.path.join(log_root, "small")
    big_dir = os.path.join(log_root, "big")
    _export_bundles_in_child([
        {"model": "tiny", "image_size": image_size, "seed": seed, "out": small_dir,
         "calib_npy": calib_npy,
         "export_kwargs": {"model_name": "small", "quant_weights": "int8",
                           "int8_top1_min": int8_top1_min}},
        {"model": "tiny_big" if arch == "tiny" else arch, "image_size": image_size,
         "seed": seed + 1, "out": big_dir, "export_kwargs": {"model_name": "big"}},
    ])

    def _meta(d):
        with open(os.path.join(d, "meta.json")) as f:
            return json.load(f)

    small_meta, big_meta = _meta(small_dir), _meta(big_dir)

    base_argv = [
        f"data.image_size={image_size}",
        "serve.buckets=[1]",  # bucket-1 everywhere: bitwise identity by construction
        "serve.max_wait_ms=1.0",
        "serve.drain_timeout_s=10",
    ]
    # model-sharded placement: each slot serves exactly one tenant — the
    # per-slot argv is the same shape cli/fleet.py slot_overrides() emits
    per_slot = {
        0: [f"serve.zoo.models=small={small_dir}", "serve.zoo.default=small"],
        1: [f"serve.zoo.models=big={big_dir}", "serve.zoo.default=big"],
    }
    slot_adverts = {0: {"small": small_meta.get("digest", "")},
                    1: {"big": big_meta.get("digest", "")}}

    class _StderrLog:
        # the bench contract owns stdout (ONE JSON line)
        def log(self, msg):
            print(msg, file=sys.stderr, flush=True)

    router = Router(poll_interval_s=0.25, eject_failures=2, route_attempts=3,
                    client_timeout_s=60.0, seed=seed).start()
    fleet_ref = {}

    def _on_change(addrs):
        # membership AND placement ride every supervisor notification: the
        # router learns which tenant each address serves (digest-stamped),
        # exactly what cli/fleet.py's placement wiring does
        router.set_backends(addrs)
        fleet = fleet_ref.get("fleet")
        if fleet is None:
            return
        assignments = {}
        for r in fleet.replicas():
            addr = r["addr"]
            if addr is not None:
                key = f"{addr['host']}:{addr['port']}"
                assignments[key] = slot_adverts[r["slot"] % 2]
        router.set_backend_models(assignments)

    fleet = FleetSupervisor(
        replica_argv=base_argv, log_dir=log_root, replicas=2,
        per_slot_argv=per_slot, spawn_timeout_s=240.0, drain_timeout_s=30.0,
        on_change=_on_change, logger=_StderrLog(),
    )
    fleet_ref["fleet"] = fleet
    out = {"replicas": 2, "image_size": image_size, "seed": seed,
           "requests": requests,
           "models": {
               "small": {"weights": "int8",
                         "digest": small_meta.get("digest", "")[:12],
                         "int8_top1": (small_meta.get("quant") or {}).get("top1_agreement")},
               "big": {"weights": "float32",
                       "digest": big_meta.get("digest", "")[:12]},
           }}
    try:
        _parent_must_be_off_the_backend()
        t0 = time.perf_counter()
        fleet.start()
        out["spawn_s"] = round(time.perf_counter() - t0, 2)
        slot_addr = {r["slot"]: r["addr"] for r in fleet.replicas()
                     if r["addr"] is not None}
        small_key = f"{slot_addr[0]['host']}:{slot_addr[0]['port']}"
        big_key = f"{slot_addr[1]['host']}:{slot_addr[1]['port']}"
        out["placement"] = {small_key: ["small"], big_key: ["big"]}

        images = [rng.normal(0, 1, (image_size, image_size, 3)).astype("float32")
                  for _ in range(requests)]

        # reference pass: every trace image answered by BOTH tiers via
        # explicit pins — the per-image bitwise references for all three
        # arms, and the margins that calibrate the cascade threshold
        refs_small, refs_big, margins, warm_lat = [], [], [], []
        for img in images:
            t1 = time.perf_counter()
            r = router.submit(img, model="small").result(timeout=120)
            warm_lat.append(time.perf_counter() - t1)
            refs_small.append(np.asarray(r))
            margins.append(softmax_margin(r))
        for img in images:
            refs_big.append(np.asarray(
                router.submit(img, model="big").result(timeout=120)))
        if threshold is None or threshold < 0:
            # the median margin splits the trace: ~half answer small, ~half
            # escalate — both cascade outcomes populated by construction
            threshold = float(np.median(margins))
        out["threshold"] = round(threshold, 6)
        out["margins"] = {"min": round(float(np.min(margins)), 6),
                          "median": round(float(np.median(margins)), 6),
                          "max": round(float(np.max(margins)), 6)}
        warm_lat.sort()
        p50_s = max(_percentile(warm_lat, 0.5), 1e-3)
        if target_qps <= 0:
            target_qps = max(2.0, 0.35 / p50_s)
        out["target_qps"] = round(target_qps, 2)

        arms = {}
        # arm 1: one-model-per-fleet baseline — everything pinned big
        f0, _ = _zoo_scrape_flops(router)
        rnd, ans = _zoo_round(lambda img, m: router.submit(img, model=m),
                              images, ["big"] * requests,
                              target_qps=target_qps, seed=seed + 2)
        f1, _ = _zoo_scrape_flops(router)
        rnd["flops_per_request"] = (f1 - f0) / max(rnd["completed"], 1)
        rnd["bitwise_match_big"] = all(
            a is not None and np.array_equal(a, refs_big[i])
            for i, a in enumerate(ans))
        arms["big_only"] = rnd

        # arm 2: model-sharded 50/50 pins — the zero-misroute/zero-5xx claim
        mix_rs = np.random.RandomState(seed + 3)
        mix = ["small" if mix_rs.rand() < 0.5 else "big" for _ in range(requests)]
        mix[0], mix[1] = "small", "big"  # both tenants always present
        mr0 = _zoo_scrape_model_requests(router, ("small", "big"))
        f0, _ = _zoo_scrape_flops(router)
        rnd, ans = _zoo_round(lambda img, m: router.submit(img, model=m),
                              images, mix, target_qps=target_qps, seed=seed + 4)
        f1, _ = _zoo_scrape_flops(router)
        mr1 = _zoo_scrape_model_requests(router, ("small", "big"))
        rnd["flops_per_request"] = (f1 - f0) / max(rnd["completed"], 1)
        rnd["mix"] = {"small": mix.count("small"), "big": mix.count("big")}
        # a misroute is a request METERED on the replica that does not
        # serve its model — admission counts serve.model_requests.{m} at
        # the replica door, so the cross deltas must both be zero
        rnd["misroutes"] = (
            (mr1[small_key]["big"] - mr0[small_key]["big"])
            + (mr1[big_key]["small"] - mr0[big_key]["small"]))
        rnd["bitwise_match"] = all(
            a is not None and np.array_equal(
                a, (refs_small if mix[i] == "small" else refs_big)[i])
            for i, a in enumerate(ans))
        arms["sharded"] = rnd
        if rnd["misroutes"] != 0 or rnd["failed"] != 0 or rnd["unresolved"] != 0:
            raise AssertionError(
                f"sharded arm broke placement: misroutes={rnd['misroutes']} "
                f"failed={rnd['failed']} unresolved={rnd['unresolved']}")

        # arm 3: the confidence cascade over the SAME sharded fleet
        tier = CascadeTier(router, small="small", big="big", threshold=threshold)
        s0 = reg.snapshot()
        f0, _ = _zoo_scrape_flops(router)
        rnd, ans = _zoo_round(lambda img, _m: tier.submit(img), images,
                              [None] * requests, target_qps=target_qps,
                              seed=seed + 5)
        f1, _ = _zoo_scrape_flops(router)
        s1 = reg.snapshot()

        def _d(key):
            return int(s1.get(key, 0) - s0.get(key, 0))

        esc = _d("serve.cascade.escalations")
        rnd["escalations"] = esc
        rnd["answered_small"] = _d("serve.cascade.answered_small")
        rnd["deadline_skips"] = _d("serve.cascade.deadline_skips")
        rnd["escalation_failures"] = _d("serve.cascade.escalation_failures")
        decided = esc + rnd["answered_small"]
        rnd["escalation_rate"] = round(esc / decided, 4) if decided else 0.0
        rnd["flops_per_request"] = (f1 - f0) / max(rnd["completed"], 1)
        # bitwise discipline: every answer must equal EXACTLY one of the two
        # per-image references, and the big-matches must equal the counted
        # escalations (minus any failures, which must be zero anyway)
        esc_matches = small_matches = mismatches = 0
        for i, a in enumerate(ans):
            if a is None:
                continue
            if np.array_equal(a, refs_small[i]):
                small_matches += 1
            elif np.array_equal(a, refs_big[i]):
                esc_matches += 1
            else:
                mismatches += 1
        rnd["answers_big_bitwise"] = esc_matches
        rnd["answers_small_bitwise"] = small_matches
        rnd["answer_mismatches"] = mismatches
        rnd["escalated_bitwise_match_big_only"] = (
            mismatches == 0 and esc_matches == esc - rnd["escalation_failures"])
        arms["cascade"] = rnd
        if esc <= 0 or rnd["answered_small"] <= 0:
            raise AssertionError(
                f"cascade did not split the trace: escalations={esc} "
                f"answered_small={rnd['answered_small']}")
        if mismatches:
            raise AssertionError(
                f"{mismatches} cascade answers matched NEITHER reference")

        out["arms"] = arms
        big_fpr = arms["big_only"]["flops_per_request"]
        ratio = (arms["cascade"]["flops_per_request"] / big_fpr) if big_fpr else None
        out["cost"] = {
            "big_only_flops_per_request": round(big_fpr, 1),
            "sharded_flops_per_request": round(arms["sharded"]["flops_per_request"], 1),
            "cascade_flops_per_request": round(arms["cascade"]["flops_per_request"], 1),
            "cascade_vs_big_only": round(ratio, 4) if ratio is not None else None,
        }
        # the acceptance criterion the whole subsystem exists for: at ~half
        # escalation rate the blended cost must beat all-big STRICTLY
        if ratio is None or ratio >= 1.0:
            raise AssertionError(
                f"cascade flops/request did not beat big-only: ratio={ratio}")
        for arm in arms.values():
            if arm["unresolved"]:
                raise AssertionError("a zoo arm left futures unresolved")
        out["cpu_rehearsal_note"] = _ZOO_CPU_CAVEAT
        return out
    finally:
        router.stop()
        fleet.stop()


_OVERLOAD_CPU_CAVEAT = (
    "cpu_rehearsal: engine, batcher, controller, and load generator share "
    "this box's core(s), so absolute QPS/latency are contention-dominated. "
    "The pinned structural claims are host-independent: interactive-class "
    "availability under the SAME seeded 3x-capacity storm is higher with "
    "the brownout ladder on than off, the ladder steps up during the storm "
    "and fully recovers to L0 after it, every submitted future resolves "
    "(zero unresolved), and the gray-failure round shows the latency-based "
    "soft ejection firing within the configured window followed by tail "
    "recovery. Absolute capacity is an accelerator measurement — the same "
    "caveat discipline as r02/r04/r05/r06."
)

_OVERLOAD_CLASS_MIX = {"interactive": 0.4, "batch": 0.2, "best_effort": 0.4}


def _overload_round(admission, images, *, seed, n_requests, target_qps,
                    deadline_ms_by_class):
    """One open-loop Poisson storm through an admission controller. Same
    discipline as ``_chaos_round``: pre-drawn arrivals fire on schedule,
    EVERY future resolves (a hang is ``unresolved`` > 0), per-class books
    balance. Latencies are stamped at resolution via callbacks so the p99
    does not silently include the tail of the arrival schedule."""
    from concurrent.futures import TimeoutError as FutTimeout

    import numpy as np

    from yet_another_mobilenet_series_tpu.serve.batcher import DeadlineExceeded, DrainTimeout

    rs = np.random.RandomState(seed)
    classes, probs = zip(*sorted(_OVERLOAD_CLASS_MIX.items()))
    draws_cls = [classes[i] for i in rs.choice(len(classes), size=n_requests, p=probs)]
    gaps = rs.exponential(1.0 / target_qps, size=n_requests)
    stats = {c: {"submitted": 0, "completed": 0, "rejected": 0, "shed": 0, "failed": 0}
             for c in classes}
    lat = {c: [] for c in classes}
    lat_lock = threading.Lock()
    pending = []
    t_start = time.perf_counter()
    t_next = t_start
    for i in range(n_requests):
        t_next += gaps[i]
        delay = t_next - time.perf_counter()
        if delay > 0:
            time.sleep(delay)  # open loop: the schedule paces us, not completions
        cls = draws_cls[i]
        stats[cls]["submitted"] += 1
        t0 = time.perf_counter()
        try:
            fut = admission.submit(images[cls], priority=cls,
                                   deadline_ms=deadline_ms_by_class.get(cls))
        except Exception:  # noqa: BLE001 — typed arrival rejection (quota/brownout/deadline)
            stats[cls]["rejected"] += 1
            continue

        def _stamp(fut, cls=cls, t0=t0):
            if fut.exception() is None:
                with lat_lock:
                    lat[cls].append(time.perf_counter() - t0)

        fut.add_done_callback(_stamp)
        pending.append((cls, fut))
    unresolved = 0
    for cls, fut in pending:
        try:
            fut.result(timeout=300)
            stats[cls]["completed"] += 1
        except (DeadlineExceeded, DrainTimeout):
            stats[cls]["shed"] += 1
        except FutTimeout:
            unresolved += 1  # a real hang: the no-client-ever-hangs invariant broke
        except Exception:  # noqa: BLE001 — typed rejection or engine failure
            stats[cls]["failed"] += 1
    wall = time.perf_counter() - t_start
    out = {"wall_s": round(wall, 3), "unresolved": unresolved, "classes": {}}
    for cls in classes:
        s = stats[cls]
        ls = sorted(lat[cls])
        avail = s["completed"] / s["submitted"] if s["submitted"] else None
        out["classes"][cls] = {
            **s,
            "availability": round(avail, 4) if avail is not None else None,
            "p50_ms": round(_percentile(ls, 0.50) * 1e3, 3),
            "p99_ms": round(_percentile(ls, 0.99) * 1e3, 3),
        }
    return out


def measure_overload(arch, image_size, buckets, *, storm_s, multiple, seed,
                     pace_ms, replicas, gray_requests, straggler_ms, log_root):
    """The ``--overload`` measurement, two halves. The fleet half runs FIRST
    and the in-process half after it has stopped: the in-process engine
    initialises this process's backend, and a parent that holds the chip
    leaves none for replica subprocesses (the artifact's sections keep
    their names; only the running order follows the device).

    1. **brownout A/B** (in-process): ONE seeded open-loop Poisson storm at
       ``multiple`` x the measured closed-loop capacity, run twice through
       fresh batcher+admission stacks — brownout OFF vs ON — with
       interactive deadlines derived from the warm p50. The engine is
       PACED (seeded FaultyEngine latency floor of ``pace_ms`` per
       dispatch) so capacity is deterministic on any box — a tiny model on
       a fast host would otherwise absorb any finite storm before the
       ladder could tick. The pinned claim: interactive availability
       (completed/submitted) is higher with the ladder on, the ladder
       steps up under the storm and fully recovers to L0 after it, and
       nothing hangs in either arm.
    2. **gray-failure round** (real fleet): replica subprocesses behind the
       router, the highest slot latency-injected (slow-but-alive, never
       crashing). Soft ejection is armed at the round start (a known t0),
       so time-to-eject is measured, and completion-stamped latencies
       split at the ejection instant pin the tail recovering after it.
    """
    import numpy as np

    from yet_another_mobilenet_series_tpu.cli.fleet import FleetSupervisor
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.serve.admission import AdmissionController
    from yet_another_mobilenet_series_tpu.serve.brownout import BrownoutController
    from yet_another_mobilenet_series_tpu.serve.engine import InferenceEngine
    from yet_another_mobilenet_series_tpu.serve.export import load_bundle
    from yet_another_mobilenet_series_tpu.serve.faults import FaultyEngine
    from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher
    from yet_another_mobilenet_series_tpu.serve.router import Router
    from yet_another_mobilenet_series_tpu.serve.signals import SignalReader

    reg = get_registry()
    rng = np.random.RandomState(seed)
    images = {c: rng.normal(0, 1, (image_size, image_size, 3)).astype("float32")
              for c in _OVERLOAD_CLASS_MIX}
    max_batch = max(buckets)
    out = {"image_size": image_size, "seed": seed, "storm_s": storm_s,
           "pace_ms": pace_ms, "class_mix": dict(_OVERLOAD_CLASS_MIX)}
    # -- gray failure: slow-but-alive replica, soft ejection + recovery ------
    bundle_dir = os.path.join(log_root, "bundle")
    _export_bundles_in_child([{"model": arch, "image_size": image_size, "seed": 0,
                               "out": bundle_dir}])
    replica_argv = [
        f"serve.bundle={bundle_dir}",
        f"data.image_size={image_size}",
        f"serve.buckets=[{','.join(str(x) for x in buckets)}]",
        "serve.max_wait_ms=2.0",
        "serve.drain_timeout_s=10",
    ]
    straggler_slot = replicas - 1
    per_slot = {straggler_slot: [
        "serve.faults.enable=true",
        f"serve.faults.latency_ms={straggler_ms}",
        "serve.faults.latency_rate=1.0",  # EVERY dispatch is slow: gray, not flaky
        "serve.faults.fail_at=result",
        f"serve.faults.seed={seed + 7}",
    ]}

    class _StderrLog:
        def log(self, msg):
            print(msg, file=sys.stderr, flush=True)

    # soft ejection configured but DISARMED for the warm phase: arming it at
    # the round start gives time-to-eject a known zero point
    router = Router(poll_interval_s=0.25, eject_failures=2, route_attempts=3,
                    client_timeout_s=60.0, seed=seed,
                    slow_eject=False, slow_factor=3.0, slow_eject_after=3,
                    slow_cooldown_s=60.0, slow_min_ms=1.0)
    fleet = FleetSupervisor(
        replica_argv=replica_argv, log_dir=log_root, replicas=replicas,
        per_slot_argv=per_slot, spawn_timeout_s=240.0, drain_timeout_s=30.0,
        on_change=router.set_backends, logger=_StderrLog(),
    )
    gray = {"replicas": replicas, "straggler": {"slot": straggler_slot,
                                                "latency_ms": straggler_ms,
                                                "latency_rate": 1.0}}
    try:
        _parent_must_be_off_the_backend()
        t0 = time.perf_counter()
        fleet.start()
        router.start()
        gray["spawn_s"] = round(time.perf_counter() - t0, 2)
        img = images["interactive"]
        warm = []
        for _ in range(24):  # teaches every replica's per-leg EWMA
            t1 = time.perf_counter()
            router.submit(img).result(timeout=60)
            warm.append(time.perf_counter() - t1)
        warm.sort()
        healthy_p50_s = max(warm[len(warm) // 4], 1e-3)  # lower quartile ~ healthy replicas
        # capped well below capacity: this round measures DETECTION and the
        # tail, not throughput — the round must outlast eject + recovery
        gray_qps = min(max(3.0, 0.4 / healthy_p50_s), 20.0)
        gray["target_qps"] = round(gray_qps, 2)
        s_before = reg.snapshot()
        slow0 = s_before.get("fleet.slow_ejections", 0)
        eject0 = s_before.get("fleet.ejections", 0)
        armed = {}  # set mid-round: the detector's zero point
        eject_at = {}

        def _watch():
            while "t" not in eject_at:
                t_armed = armed.get("t")
                if t_armed is not None and time.perf_counter() - t_armed > 120:
                    return
                if reg.snapshot().get("fleet.slow_ejections", 0) > slow0:
                    eject_at["t"] = time.perf_counter()
                    return
                time.sleep(0.05)

        watcher = threading.Thread(target=_watch, daemon=True)
        watcher.start()
        lat_rows = []
        lat_lock = threading.Lock()
        rs = np.random.RandomState(seed + 9)
        gaps = rs.exponential(1.0 / gray_qps, size=gray_requests)
        pending = []
        t_next = time.perf_counter()
        for i in range(gray_requests):
            t_next += gaps[i]
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            if i == gray_requests // 3 and "t" not in armed:
                # arm the detector MID-round: the first third measures the
                # straggler-poisoned tail, then time-to-eject runs from here
                armed["t"] = time.perf_counter()
                router.set_slow_ejection(True)
            t1 = time.perf_counter()
            fut = router.submit(img)

            def _stamp(fut, t1=t1):
                # keyed by SUBMIT time: a request submitted after the
                # ejection can only have been routed to healthy replicas,
                # so the before/after split is routing-honest even for
                # straggler-queued requests completing late
                if fut.exception() is None:
                    with lat_lock:
                        lat_rows.append((t1, time.perf_counter() - t1))

            fut.add_done_callback(_stamp)
            pending.append(fut)
        unresolved = failed = 0
        for fut in pending:
            try:
                fut.result(timeout=120)
            except Exception as e:  # noqa: BLE001 — typed verdicts; hangs counted apart
                from concurrent.futures import TimeoutError as FutTimeout

                if isinstance(e, FutTimeout):
                    unresolved += 1
                else:
                    failed += 1
        watcher.join(timeout=5)
        t_eject = eject_at.get("t")
        t_armed = armed.get("t")
        s_end = reg.snapshot()
        gray.update({
            "submitted": gray_requests,
            "completed": len(lat_rows),
            "failed": failed,
            "unresolved": unresolved,
            "slow_ejections": int(s_end.get("fleet.slow_ejections", 0) - slow0),
            "ejections_total": int(s_end.get("fleet.ejections", 0) - eject0),
            "time_to_eject_s": (round(t_eject - t_armed, 3)
                                if t_eject is not None and t_armed is not None else None),
        })
        if t_eject is not None:
            before = sorted(d for t, d in lat_rows if t <= t_eject)
            after = sorted(d for t, d in lat_rows if t > t_eject)
            gray["p99_ms_before_eject"] = round(_percentile(before, 0.99) * 1e3, 3)
            gray["p99_ms_after_eject"] = round(_percentile(after, 0.99) * 1e3, 3)
            gray["post_eject_samples"] = len(after)
            gray["tail_recovery"] = (
                round(gray["p99_ms_before_eject"] / gray["p99_ms_after_eject"], 3)
                if gray["p99_ms_after_eject"] else None
            )
        out["gray"] = gray
    finally:
        router.stop()
        fleet.stop()

    # -- brownout A/B, in-process: the fleet is gone, this process may now
    # take the device; it serves the bundle the replicas served
    engine = InferenceEngine(load_bundle(bundle_dir), buckets=buckets, image_size=image_size)
    engine.warmup()
    # deterministic capacity ceiling: every dispatch pays pace_ms at sync,
    # so "3x capacity" means the same storm on a laptop and a server
    paced = FaultyEngine(engine, seed=seed, latency_s=pace_ms / 1e3, latency_rate=1.0)

    def _stack():
        b = PipelinedBatcher(paced, max_batch=max_batch, max_wait_ms=5.0,
                             queue_depth=128, drain_timeout_s=60.0).start()
        a = AdmissionController(b, max_retries=1, retry_backoff_ms=5.0,
                                breaker_threshold=50, breaker_cooldown_s=0.5, seed=seed)
        return b, a

    # -- capacity calibration (closed loop, brownout off) --------------------
    b, a = _stack()
    warm_lat = []
    n_warm, n_clients = 48, max_batch

    def _warm_client(n):
        img = images["interactive"]
        for _ in range(n):
            t0 = time.perf_counter()
            a.submit(img, priority="interactive").result(timeout=60)
            warm_lat.append(time.perf_counter() - t0)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=_warm_client, args=(n_warm // n_clients,), daemon=True)
               for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    warm_wall = time.perf_counter() - t0
    b.stop()
    warm_lat.sort()
    capacity_qps = len(warm_lat) / warm_wall if warm_wall > 0 else 1.0
    p50_ms = max(_percentile(warm_lat, 0.5) * 1e3, 0.5)
    storm_qps = multiple * capacity_qps
    # duration-driven storm: the ladder needs seconds of sustained overload
    # to climb, so the request count follows the rate, not vice versa
    requests = max(40, int(storm_qps * storm_s))
    out["requests"] = requests
    # interactive deadline: far above the healthy latency, far below what a
    # sustained 3x backlog produces — the availability instrument
    interactive_deadline_ms = max(8.0 * p50_ms, 100.0)
    deadlines = {"interactive": interactive_deadline_ms}
    out["capacity"] = {
        "closed_loop_qps": round(capacity_qps, 2), "clients": n_clients,
        "warm_p50_ms": round(p50_ms, 3), "storm_qps": round(storm_qps, 2),
        "multiple": multiple,
        "interactive_deadline_ms": round(interactive_deadline_ms, 1),
    }

    # -- the A/B: one seeded storm, brownout off vs on -----------------------
    arms = {}
    for mode in ("off", "on"):
        b, a = _stack()
        controller = None
        if mode == "on":
            controller = BrownoutController(
                SignalReader(latency_family="serve.latency_seconds",
                             signal_class="interactive",
                             queue_depth_fn=a.queued_total),
                (b, a),
                interval_s=0.1,
                up_p99_ms=max(4.0 * p50_ms, 40.0),
                down_p99_ms=max(1.5 * p50_ms, 10.0),
                up_queue_depth=1.5 * max_batch,
                down_queue_depth=0.5 * max_batch,
                hold_up_s=0.3, cooldown_s=0.5,
                retry_after_s=1.0,
                # stdout is the ONE-JSON-line artifact: transitions -> stderr
                log_fn=lambda m: print(m, file=sys.stderr, flush=True),
            ).start()
        s0 = reg.snapshot()
        rnd = _overload_round(a, images, seed=seed + 1, n_requests=requests,
                              target_qps=storm_qps, deadline_ms_by_class=deadlines)
        s1 = reg.snapshot()
        rnd["shed_at_door_brownout"] = int(s1.get("serve.rejected_brownout", 0)
                                           - s0.get("serve.rejected_brownout", 0))
        if controller is not None:
            # recovery: idle windows are relaxed; one level per cooldown
            settle_until = time.monotonic() + 6 * controller._cooldown_s + 2.0
            while controller.level > 0 and time.monotonic() < settle_until:
                time.sleep(0.1)
            trace = controller.trace
            controller.stop()
            rnd["brownout"] = {
                "peak_level": max((r["level"] for r in trace), default=0),
                "final_level": trace[-1]["level"] if trace else None,
                "recovered_to_l0": bool(trace and trace[-1]["level"] == 0),
                "transitions_up": sum(1 for r in trace if r["action"] == "up"),
                "transitions_down": sum(1 for r in trace if r["action"] == "down"),
                "trace": trace,
            }
        b.stop()
        arms[mode] = rnd
    out["storm"] = {
        "off": arms["off"], "on": arms["on"],
        "interactive_availability_off": arms["off"]["classes"]["interactive"]["availability"],
        "interactive_availability_on": arms["on"]["classes"]["interactive"]["availability"],
    }
    out["cpu_rehearsal_note"] = _OVERLOAD_CPU_CAVEAT
    return out


_PARTITION_CPU_CAVEAT = (
    "cpu_rehearsal: router, replicas, proxies, and the load generator share "
    "this box's core(s), so absolute latency/QPS are contention-dominated. "
    "The pinned structural claims are host-independent: under each seeded "
    "partition shape injected at the SOCKET level (netchaos proxy) every "
    "submitted request resolves as completed or typed-rejected with zero "
    "failures, the blackholed replica is ejected within the poll-budget "
    "bound (eject_failures x (poll interval + connect budget) + slack) "
    "rather than the read timeout, the healed link readmits after its "
    "probation, and a silently-vanished leased backend is REMOVED within "
    "TTL + one poll sweep. Replica count and absolute rates are a real "
    "multi-host measurement — the same caveat discipline as r02..r08."
)


def _partition_round(router, image, *, n_requests, target_qps, seed,
                     hooks=(), result_timeout_s=60.0):
    """One open-loop Poisson round through the fleet router with indexed
    ``hooks`` [(idx, fn), ...] fired just before their request index (the
    fault-onset / heal injection points). Every future resolves at the end
    — a hang is ``unresolved`` > 0, never a stuck bench; latencies stamp at
    resolution via callbacks."""
    from concurrent.futures import TimeoutError as FutTimeout

    import numpy as np

    from yet_another_mobilenet_series_tpu.serve.client import ClientHTTPError

    rs = np.random.RandomState(seed)
    gaps = rs.exponential(1.0 / target_qps, size=n_requests)
    hooks = sorted(hooks)
    pending = []
    lat = []
    lat_lock = threading.Lock()

    def _stamp(t0):
        def cb(fut):
            if fut.exception() is None:
                with lat_lock:
                    lat.append(time.perf_counter() - t0)
        return cb

    t_start = time.perf_counter()
    t_next = t_start
    hook_i = 0
    for i in range(n_requests):
        t_next += gaps[i]
        delay = t_next - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        while hook_i < len(hooks) and i >= hooks[hook_i][0]:
            hooks[hook_i][1]()
            hook_i += 1
        t0 = time.perf_counter()
        fut = router.submit(image)
        fut.add_done_callback(_stamp(t0))
        pending.append(fut)
    while hook_i < len(hooks):  # a heal indexed past the end still fires
        hooks[hook_i][1]()
        hook_i += 1
    out = {"submitted": n_requests, "completed": 0, "rejected": 0, "failed": 0,
           "unresolved": 0}
    for fut in pending:
        try:
            fut.result(timeout=result_timeout_s)
            out["completed"] += 1
        except FutTimeout:
            out["unresolved"] += 1  # a real hang: the router broke its contract
        except ClientHTTPError as e:
            out["rejected" if e.status < 500 else "failed"] += 1
        except Exception:  # noqa: BLE001 — typed route failure = client-visible
            out["failed"] += 1
    wall = time.perf_counter() - t_start
    lat.sort()
    out.update({
        "wall_s": round(wall, 3),
        "qps": round(out["completed"] / wall, 2) if wall else 0.0,
        "p50_ms": round(_percentile(lat, 0.50) * 1e3, 3),
        "p99_ms": round(_percentile(lat, 0.99) * 1e3, 3),
    })
    return out


_PARTITION_ROUND_KEYS = ("fleet.route_retries", "fleet.ejections", "fleet.readmissions",
                         "fleet.partition_ejections", "serve.client.connect_timeouts")


def measure_partition(*, replicas, requests, target_qps, seed, poll_interval_s,
                      eject_failures, connect_timeout_s, read_timeout_s,
                      eject_cooldown_s, lease_ttl_s, flap_period_s, flap_down_s):
    """The ``--partition`` measurement (the r09 shape): N in-process echo
    replicas (real Frontend + pipelined batcher over a trivial engine — no
    jax, so the round measures the TRANSPORT, not a model), each behind its
    own seeded netchaos proxy, one fleet router over the proxy addresses.

    Four seeded fault rounds on one schedule family — ``blackhole``,
    ``reset``, ``half_open``, ``flap`` — each injecting its shape at the
    socket level a third of the way in and healing at two thirds, measuring
    DETECTION (fault onset -> ejection, stamped by a counter watcher, never
    by the submit loop), client-visible error rate (the contract is ZERO:
    transport retry absorbs every shape), and RECOVERY (heal -> fully
    routable again, through the post-ejection probation). Then the
    ``membership`` round: a leased replica joins via /register-style
    heartbeats, vanishes silently (heartbeat stops + link blackholed), and
    must be REMOVED by lease expiry within TTL + one poll sweep while
    traffic keeps answering."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.serve.admission import AdmissionController
    from yet_another_mobilenet_series_tpu.serve.frontend import Frontend
    from yet_another_mobilenet_series_tpu.serve.netchaos import NetChaosProxy
    from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher
    from yet_another_mobilenet_series_tpu.serve.router import Router

    reg = get_registry()

    class _EchoEngine:
        def predict_async(self, images):
            class _H:
                def result(_self):
                    return images[:, 0, 0, :1].astype(np.float32)

            return _H()

        def predict(self, images):
            return self.predict_async(images).result()

    def echo_replica(tag):
        b = PipelinedBatcher(_EchoEngine(), max_batch=8, max_wait_ms=1.0,
                             queue_depth=256, drain_timeout_s=5.0).start()
        fe = Frontend(AdmissionController(b), port=0, replica_id=tag).start()
        return b, fe

    stacks = [echo_replica(f"p{i}") for i in range(replicas)]
    proxies = [NetChaosProxy("127.0.0.1", fe.port, seed=seed + i).start()
               for i, (_, fe) in enumerate(stacks)]
    router = Router(
        [p.addr for p in proxies],
        poll_interval_s=poll_interval_s, eject_failures=eject_failures,
        route_attempts=replicas + 1, client_timeout_s=read_timeout_s,
        connect_timeout_s=connect_timeout_s, eject_cooldown_s=eject_cooldown_s,
        lease_ttl_s=lease_ttl_s, seed=seed,
    ).start()
    poll_read_s = max(connect_timeout_s, 2 * poll_interval_s)
    # the acceptance bound: ejection within the POLL budget (+ slack for a
    # loaded 1-core box), provably far below the read timeout
    detect_bound_s = eject_failures * (poll_interval_s + poll_read_s) + 2.0
    # the fault window must OUTLAST the expected detection (else the heal
    # races the ejection and the round measures nothing), and the round
    # must outlast lead + window + a recovery tail — auto-extend requests
    # so operator-tuned rates cannot produce a degenerate round
    window_s = eject_failures * (poll_interval_s + poll_read_s) + 0.6
    flap_window_s = max(window_s, 2.2 * flap_period_s)
    lead_s, tail_s = 1.0, 2.5
    requests = max(requests, int(target_qps * (lead_s + flap_window_s + tail_s)) + 1)
    out = {
        "replicas": replicas, "seed": seed, "requests_per_round": requests,
        "target_qps": target_qps,
        "config": {
            "poll_interval_s": poll_interval_s, "eject_failures": eject_failures,
            "connect_timeout_s": connect_timeout_s, "read_timeout_s": read_timeout_s,
            "poll_read_s": poll_read_s, "eject_cooldown_s": eject_cooldown_s,
            "lease_ttl_s": lease_ttl_s,
            "flap_period_s": flap_period_s, "flap_down_s": flap_down_s,
        },
        "detect_bound_s": round(detect_bound_s, 3),
    }
    image = np.full((8, 8, 3), 3.0, np.float32)

    def watch_counter(key, baseline, holder, stamp_key, t0, timeout_s=60.0):
        def watch():
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if reg.snapshot().get(key, 0) > baseline:
                    holder[stamp_key] = time.perf_counter() - t0
                    return
                time.sleep(0.02)

        t = threading.Thread(target=watch, daemon=True)
        t.start()
        return t

    def watch_routable(n, holder, stamp_key, t_holder, heal_key, timeout_s=60.0):
        """Stamps recovery: the first instant ALL n replicas are routable
        again AFTER the heal hook has fired (t_holder[heal_key])."""
        def watch():
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                t_heal = t_holder.get(heal_key)
                if t_heal is not None and router.n_routable() >= n:
                    holder[stamp_key] = time.perf_counter() - t_heal
                    return
                time.sleep(0.02)

        t = threading.Thread(target=watch, daemon=True)
        t.start()
        return t

    try:
        # warm: every replica learns its keep-alive path, the router polls
        for _ in range(3 * replicas):
            router.submit(image).result(timeout=30)

        rounds = {}
        shapes = ("blackhole", "reset", "half_open", "flap")
        for r_i, shape in enumerate(shapes):
            victim = proxies[r_i % replicas]
            s0 = reg.snapshot()
            stamps: dict = {}
            rnd_extra: dict = {}
            t_round0 = time.perf_counter()
            this_window = flap_window_s if shape == "flap" else window_s

            def heal(victim=victim, stamps=stamps, t_round0=t_round0):
                stamps["heal_at"] = time.perf_counter() - t_round0
                stamps["_t_heal"] = time.perf_counter()
                victim.clear()

            def inject(shape=shape, victim=victim, stamps=stamps,
                       t_round0=t_round0, s0=s0, heal=heal, this_window=this_window):
                stamps["fault_at"] = time.perf_counter() - t_round0
                stamps["_t_fault"] = time.perf_counter()
                if shape == "flap":
                    victim.set_fault(None, flap_period_s=flap_period_s,
                                     flap_down_s=flap_down_s)
                else:
                    victim.set_fault(shape)
                # detection stamps come from a counter watcher, never from a
                # submit loop that itself blocks on the faulted leg; the
                # heal rides a TIMER sized to the detection budget so it
                # can never race the ejection it is there to measure
                stamps["_watch"] = watch_counter(
                    "fleet.ejections", s0.get("fleet.ejections", 0),
                    stamps, "detection_s", stamps["_t_fault"])
                t = threading.Timer(this_window, heal)
                t.daemon = True
                t.start()
                stamps["_heal_timer"] = t

            recovery_watch = watch_routable(replicas, rnd_extra, "recovery_s",
                                            stamps, "_t_heal")
            rnd = _partition_round(
                router, image, n_requests=requests, target_qps=target_qps,
                seed=seed + 11 * (r_i + 1),
                hooks=[(max(1, int(lead_s * target_qps)), inject)],
            )
            w = stamps.pop("_watch", None)
            if w is not None:
                w.join(timeout=30)
            timer = stamps.pop("_heal_timer", None)
            if timer is not None:
                timer.join(timeout=2 * this_window + 5)
            recovery_watch.join(timeout=60)
            # converge back BEFORE reading the delta: each round's books
            # then include its own readmission instead of bleeding it into
            # the next round's baseline
            deadline = time.monotonic() + 30
            while router.n_routable() < replicas and time.monotonic() < deadline:
                time.sleep(0.05)
            rnd.update(_fleet_registry_delta(reg, s0, _PARTITION_ROUND_KEYS))
            rnd["fault_at_s"] = round(stamps.get("fault_at", 0.0), 3)
            rnd["heal_at_s"] = round(stamps.get("heal_at", 0.0), 3)
            rnd["detection_s"] = (round(stamps["detection_s"], 3)
                                  if "detection_s" in stamps else None)
            rnd["recovery_s"] = (round(rnd_extra["recovery_s"], 3)
                                 if "recovery_s" in rnd_extra else None)
            rnd["routable_after"] = router.n_routable()
            rounds[shape] = rnd
        out["rounds"] = rounds

        # -- membership: a leased replica joins, vanishes, expires out ------
        b_d, fe_d = echo_replica("leased")
        proxy_d = NetChaosProxy("127.0.0.1", fe_d.port, seed=seed + 99).start()
        s0 = reg.snapshot()
        mem: dict = {}
        router.register(*proxy_d.addr, ttl_s=lease_ttl_s, replica_id="leased")
        renewing = threading.Event()
        renewing.set()

        def renew_loop():
            while renewing.is_set():
                try:
                    router.register(*proxy_d.addr, ttl_s=lease_ttl_s)
                except Exception:  # noqa: BLE001 — bench heartbeat best-effort
                    pass
                time.sleep(lease_ttl_s / 3.0)

        renew_thread = threading.Thread(target=renew_loop, daemon=True)
        renew_thread.start()
        deadline = time.monotonic() + 30
        while router.n_routable() < replicas + 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        mem["joined"] = router.n_routable() == replicas + 1
        stamps_m: dict = {}

        def vanish():
            # silently gone: the heartbeat stops AND the link blackholes —
            # nothing will ever refuse a connection or send a FIN. Only the
            # lease can remove this backend.
            stamps_m["_t_vanish"] = time.perf_counter()
            renewing.clear()
            proxy_d.set_fault("blackhole")
            stamps_m["_watch"] = watch_counter(
                "fleet.lease_expirations", s0.get("fleet.lease_expirations", 0),
                stamps_m, "removed_s", stamps_m["_t_vanish"])

        rnd = _partition_round(
            router, image, n_requests=requests, target_qps=target_qps,
            seed=seed + 77, hooks=[(requests // 3, vanish)],
        )
        w = stamps_m.pop("_watch", None)
        if w is not None:
            w.join(timeout=30)
        renew_thread.join(timeout=5)
        rnd.update(_fleet_registry_delta(
            reg, s0, ("fleet.registrations", "fleet.lease_renewals",
                      "fleet.lease_expirations", "fleet.route_retries")))
        rnd["joined"] = mem["joined"]
        rnd["removed_s"] = (round(stamps_m["removed_s"], 3)
                            if "removed_s" in stamps_m else None)
        # removal bound: the TTL plus one jittered poll sweep plus slack
        rnd["removal_bound_s"] = round(lease_ttl_s + 1.2 * poll_interval_s + 2.0, 3)
        rnd["total_after"] = len(router.replicas_state())
        out["membership"] = rnd
        out["cpu_rehearsal_note"] = _PARTITION_CPU_CAVEAT
        return out
    finally:
        router.stop()
        for p in proxies:
            p.stop()
        try:
            proxy_d.stop()
            fe_d.stop()
            b_d.stop()
        except NameError:
            pass
        for b, fe in stacks:
            fe.stop()
            b.stop()


_CHAOS_CLASS_MIX = {"interactive": 0.5, "batch": 0.3, "best_effort": 0.2}


def _chaos_round(engine, image_sizes, *, seed, n_requests, target_qps,
                 deadline_ms_by_class, fault_kwargs=None, max_retries=2):
    """One open-loop Poisson round through batcher + admission control.

    Arrivals are pre-drawn from the seed (both A/B rounds share them), fire
    on schedule regardless of completions, and every request is resolved at
    the end — a hang shows up as ``unresolved`` > 0, never a stuck bench."""
    from concurrent.futures import TimeoutError as FutTimeout

    import numpy as np

    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.serve.admission import AdmissionController
    from yet_another_mobilenet_series_tpu.serve.batcher import DeadlineExceeded, DrainTimeout
    from yet_another_mobilenet_series_tpu.serve.faults import FaultyEngine
    from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher

    reg = get_registry()
    if fault_kwargs:
        engine = FaultyEngine(engine, **fault_kwargs)
    batcher = PipelinedBatcher(
        engine, max_batch=8, max_wait_ms=5.0, queue_depth=256, drain_timeout_s=60.0
    ).start()
    admission = AdmissionController(
        batcher, max_retries=max_retries, retry_backoff_ms=5.0,
        breaker_threshold=10, breaker_cooldown_s=0.5, seed=seed,
    )
    rs = np.random.RandomState(seed)
    classes, probs = zip(*sorted(_CHAOS_CLASS_MIX.items()))
    draws_cls = [classes[i] for i in rs.choice(len(classes), size=n_requests, p=probs)]
    draws_size = [image_sizes[i] for i in rs.randint(0, len(image_sizes), size=n_requests)]
    gaps = rs.exponential(1.0 / target_qps, size=n_requests)
    images = {s: rs.normal(0, 1, (s, s, 3)).astype("float32") for s in image_sizes}

    stats = {c: {"submitted": 0, "completed": 0, "rejected": 0, "shed": 0, "failed": 0,
                 "latencies": []} for c in classes}
    pending = []
    lat_counts0 = {c: _hist_counts(f"serve.latency_seconds.{c}") for c in classes}
    s0 = reg.snapshot()
    t_start = time.perf_counter()
    t_next = t_start
    for i in range(n_requests):
        t_next += gaps[i]
        delay = t_next - time.perf_counter()
        if delay > 0:
            time.sleep(delay)  # open loop: the schedule, not completions, paces us
        cls = draws_cls[i]
        stats[cls]["submitted"] += 1
        t0 = time.perf_counter()
        try:
            fut = admission.submit(
                images[draws_size[i]], priority=cls,
                deadline_ms=deadline_ms_by_class.get(cls),
            )
        except Exception:  # noqa: BLE001 — typed arrival rejection (quota/breaker/deadline)
            stats[cls]["rejected"] += 1
            continue
        pending.append((cls, t0, fut))
    unresolved = 0
    for cls, t0, fut in pending:
        try:
            fut.result(timeout=300)
            stats[cls]["completed"] += 1
            stats[cls]["latencies"].append(time.perf_counter() - t0)
        except (DeadlineExceeded, DrainTimeout):
            stats[cls]["shed"] += 1
        except FutTimeout:
            unresolved += 1  # a real hang: the no-client-ever-hangs invariant broke
        except Exception:  # noqa: BLE001 — typed engine failure (injected or real)
            stats[cls]["failed"] += 1
    wall = time.perf_counter() - t_start
    batcher.stop()
    s1 = reg.snapshot()

    def delta(key):
        return s1.get(key, 0) - s0.get(key, 0)

    out = {
        "wall_s": round(wall, 3),
        "qps": round(sum(s["completed"] for s in stats.values()) / wall, 2) if wall else 0.0,
        "unresolved": unresolved,
        "retries": delta("serve.retries"),
        "injected_failures": delta("serve.faults.failures"),
        "injected_delays": delta("serve.faults.delays"),
        "breaker_opens": delta("serve.breaker_opens"),
        "rejected_total": delta("serve.rejected"),
        "rejected_deadline": delta("serve.rejected_deadline"),
        "rejected_class_full": delta("serve.rejected_class_full"),
        "rejected_breaker": delta("serve.rejected_breaker"),
        "rejected_queue_full": delta("serve.rejected_full"),
        "shed_deadline": delta("serve.shed_deadline"),
        "classes": {},
    }
    for cls in classes:
        s = stats[cls]
        lat = sorted(s.pop("latencies"))
        out["classes"][cls] = {
            **s,
            "p50_ms": round(_percentile(lat, 0.50) * 1e3, 3),
            "p99_ms": round(_percentile(lat, 0.99) * 1e3, 3),
            # the same window's quantiles as the registry's bucketed
            # histograms saw it (admission-side submit->resolution)
            "registry_quantiles": _hist_delta_quantiles(
                f"serve.latency_seconds.{cls}", lat_counts0[cls]),
            "qps": round(s["completed"] / wall, 2) if wall else 0.0,
        }
    return out


def _chaos_ab(engine, image_sizes, direct_rows, *, seed, n_requests, target_qps, fault_rate):
    """Healthy vs fault-injected open-loop rounds (one arrival schedule)."""
    base_size = image_sizes[0]
    t1_s = next(
        (r["p50_ms"] / 1e3 for r in direct_rows if r["batch"] == min(x["batch"] for x in direct_rows)
         and r["image_size"] == base_size),
        0.05,
    ) or 0.05
    if target_qps <= 0:
        # auto: what serial single-image serving would sustain — the batcher
        # absorbs it; the faulty round then shows what the faults cost
        target_qps = max(2.0, 1.0 / t1_s)
    deadline_ms_by_class = {
        "interactive": max(50.0, 40 * t1_s * 1e3),  # tight-ish: sheds under spikes
        "batch": max(500.0, 200 * t1_s * 1e3),
        # best_effort carries no deadline: it sheds via class quota instead
    }
    fault_kwargs = {
        "seed": seed,
        "failure_rate": fault_rate,
        "fail_at": "result",  # the completion edge, where retries must reach
        "latency_s": 3 * t1_s,
        "latency_rate": fault_rate,
    }
    common = dict(seed=seed, n_requests=n_requests, target_qps=target_qps,
                  deadline_ms_by_class=deadline_ms_by_class)
    return {
        "requests": n_requests,
        "target_qps": round(target_qps, 2),
        "seed": seed,
        "class_mix": _CHAOS_CLASS_MIX,
        "deadline_ms": {k: round(v, 1) for k, v in deadline_ms_by_class.items()},
        "fault": {"failure_rate": fault_rate, "latency_ms": round(3 * t1_s * 1e3, 1),
                  "latency_rate": fault_rate, "fail_at": "result"},
        "healthy": _chaos_round(engine, image_sizes, **common),
        "faulty": _chaos_round(engine, image_sizes, fault_kwargs=fault_kwargs, **common),
    }


def measure(arch, image_sizes, buckets, iters, conc_iters, ab_iters, max_inflight, with_bf16,
            chaos_requests=0, chaos_qps=0.0, chaos_fault_rate=0.05, chaos_seed=0,
            fuse_ladder=(), fused_iters=8, structural=False, structural_rounds=3,
            quant=False, quant_iters=5, quant_rounds=3, quant_top1_min=0.9):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.serve.engine import BF16_PARITY_ATOL, InferenceEngine
    from yet_another_mobilenet_series_tpu.serve.export import InferenceBundle, fold_network
    from yet_another_mobilenet_series_tpu.utils import compile_cache

    compile_cache.configure()
    base_size = image_sizes[0]
    net = get_model(_model_config(arch), base_size)
    params, state = net.init(jax.random.PRNGKey(0))
    # non-trivial BN running stats (fresh init is mean=0/var=1): a fold of
    # the identity affine collapses random-init logits to ~1e-11, which
    # would make the bf16-vs-fp32 parity delta degenerate
    leaves, treedef = jax.tree.flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    state = jax.tree.unflatten(
        treedef,
        [l + 0.1 * jnp.abs(jax.random.normal(k, l.shape)) + 0.01 for l, k in zip(leaves, keys)],
    )
    bundle = InferenceBundle(net=net, params=fold_network(net, params, state), meta={})

    def make_engine(dtype, fuse=(), overlap=False, staging_slots=2, ring_slots=0):
        return InferenceEngine(bundle, buckets=buckets, compute_dtype=dtype,
                               image_size=base_size, image_sizes=image_sizes,
                               fuse_ladder=fuse, overlap_staging=overlap,
                               staging_slots=staging_slots, ring_slots=ring_slots)

    # the baseline engine stays CHAINED (fuse_ladder=()) so direct /
    # concurrent / chaos rows keep their r01-r03 meaning; the fused engine
    # below exists only for the chained-vs-fused A/B
    engine = make_engine("float32")
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0

    rng = np.random.RandomState(0)
    direct_rows = [
        _direct_row(engine, b, s, iters, rng) for s in engine.image_sizes for b in engine.buckets
    ]
    concurrent_rows = [
        _concurrent_row(engine, b, s, conc_iters, max_inflight, rng)
        for s in engine.image_sizes for b in engine.buckets
    ]
    peak_sync = max(r["qps_sync"] for r in concurrent_rows)
    peak_pipe = max(r["qps_pipelined"] for r in concurrent_rows)
    ab = {
        "pipelined_vs_sync": {
            "peak_qps_sync": peak_sync,
            "peak_qps_pipelined": peak_pipe,
            "peak_speedup": round(peak_pipe / peak_sync, 4) if peak_sync else None,
        }
    }
    if with_bf16:
        bf16 = make_engine("bfloat16")
        bf16.warmup()
        bf16_rows = [_direct_row(bf16, b, base_size, ab_iters, rng) for b in bf16.buckets]
        # parity on one fixed batch at the largest bucket: the measured
        # delta every artifact carries, judged against the pinned tolerance
        xp = rng.normal(0, 1, (buckets[-1], base_size, base_size, 3)).astype("float32")
        ref = engine.predict(xp)
        delta = float(np.max(np.abs(bf16.predict(xp) - ref)))
        logit_scale = float(np.mean(np.abs(ref)))
        fp32_by_bucket = {r["batch"]: r["qps"] for r in direct_rows if r["image_size"] == base_size}
        peak_fp32 = max(fp32_by_bucket.values())
        peak_bf16 = max(r["qps"] for r in bf16_rows)
        ab["bf16_vs_fp32"] = {
            "buckets": [
                {"batch": r["batch"], "qps_bf16": r["qps"], "qps_fp32": fp32_by_bucket[r["batch"]]}
                for r in bf16_rows
            ],
            "peak_qps_fp32": peak_fp32,
            "peak_qps_bf16": peak_bf16,
            "peak_speedup": round(peak_bf16 / peak_fp32, 4) if peak_fp32 else None,
            "max_abs_logit_delta": round(delta, 6),
            "mean_abs_logit": round(logit_scale, 6),
            "parity_atol": BF16_PARITY_ATOL,
            "parity_ok": delta <= BF16_PARITY_ATOL,
        }
    if fuse_ladder:
        eng_fused = make_engine("float32", fuse=fuse_ladder)
        eng_fused.warmup()
        ab["fused_vs_chained"] = _fused_ab(engine, eng_fused, base_size, fused_iters, rng)
    if structural:
        ab["structural_sweep"] = _structural_sweep(
            make_engine, base_size, rounds=max(1, structural_rounds),
            conc_iters=conc_iters, max_inflight=max_inflight, staging_slots=2,
            run_max=4, fuse_ladder=fuse_ladder or (2, 4), rng=rng,
        )
    if quant:
        # the pipeline's ImageNet normalization constants: the realistic
        # (nonzero-mean, delta-gated) denorm; the zero-mean bitwise regime
        # is pinned inside the A/B with its own engine pair
        from yet_another_mobilenet_series_tpu.config import DataConfig

        dc = DataConfig()
        ab["quant"] = _quant_ab(
            net, bundle.params, buckets, base_size, max(1, quant_iters),
            max(1, quant_rounds), rng, mean=dc.mean, std=dc.std,
            top1_min=quant_top1_min,
        )
    chaos = None
    if chaos_requests > 0:
        chaos = _chaos_ab(
            engine, list(engine.image_sizes), direct_rows,
            seed=chaos_seed, n_requests=chaos_requests,
            target_qps=chaos_qps, fault_rate=chaos_fault_rate,
        )
    # whole-run quantiles straight from the registry snapshot (the same
    # .p50/.p95/.p99 columns obs_registry.json and /varz carry): every
    # serving histogram that saw data, keyed by registry name
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry

    snap = get_registry().snapshot()
    registry_quantiles = {
        k[: -len(".count")]: {
            "count": snap[k],
            "p50": snap.get(f"{k[:-len('.count')]}.p50", 0.0),
            "p95": snap.get(f"{k[:-len('.count')]}.p95", 0.0),
            "p99": snap.get(f"{k[:-len('.count')]}.p99", 0.0),
        }
        for k in snap
        if k.startswith("serve.") and k.endswith(".count") and snap[k] > 0
    }
    from scripts.provenance import provenance

    dev = jax.devices()[0]
    out = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_chips": len(jax.devices()),
        # shared provenance stamp (scripts/provenance.py): jax/jaxlib versions +
        # cpu-rehearsal flag, so every serving artifact is attributable
        "provenance": provenance(),
        "warmup_compile_s": round(warmup_s, 2),
        "buckets": direct_rows,
        "concurrent": concurrent_rows,
        "ab": ab,
        "registry_quantiles": registry_quantiles,
        "peak_qps": max([peak_pipe, peak_sync] + [r["qps"] for r in direct_rows]),
    }
    if chaos is not None:
        out["chaos"] = chaos
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="mobilenet_v3_large")
    ap.add_argument("--image-sizes", default="224", help="comma ladder; first entry is the base size")
    ap.add_argument("--buckets", default="1,8,32")
    ap.add_argument("--iters", type=int, default=10, help="direct-mode timed predicts per bucket")
    ap.add_argument("--concurrent-iters", type=int, default=6,
                    help="concurrent mode drives max(iters*batch, 32) requests per bucket and mode")
    ap.add_argument("--ab-iters", type=int, default=5, help="bf16 direct-mode iters per bucket")
    ap.add_argument("--max-inflight", type=int, default=2,
                    help="pipelined window; 1 = pure double buffering (stage||compute, no "
                         "concurrent executions — best when host and device share cores)")
    ap.add_argument("--no-bf16", action="store_true", help="skip the fp32-vs-bf16 A/B")
    ap.add_argument("--fused", action="store_true",
                    help="run the chained-vs-fused A/B (whole requests of K max-bucket "
                         "chunks; per-chunk dispatch loop vs ONE fused lax.scan dispatch)")
    ap.add_argument("--fuse-ladder", default="2,4",
                    help="chunk-count ladder for the fused engine (serve.fuse_chunks.ladder)")
    ap.add_argument("--fused-iters", type=int, default=8,
                    help="timed whole-request predicts per K and mode in the fused A/B")
    ap.add_argument("--structural", action="store_true",
                    help="run the interleaved structural sweep: sync vs pipelined vs "
                         "fused vs overlapped on a saturated bucket (dispatches-per-"
                         "wakeup + steady-state achieved-FLOPS deltas — the r05 shape)")
    ap.add_argument("--structural-rounds", type=int, default=3,
                    help="interleaved rounds per mode in the structural sweep")
    ap.add_argument("--quant", action="store_true",
                    help="run the quantized-serving A/B: one interleaved f32 / "
                         "uint8-wire / int8 sweep per bucket with per-request "
                         "serve.h2d_bytes + serve.dispatched_bytes registry "
                         "deltas and the parity verdicts (the r07 shape)")
    ap.add_argument("--quant-iters", type=int, default=5,
                    help="timed predicts per bucket, mode, and round in the quant A/B")
    ap.add_argument("--quant-rounds", type=int, default=3,
                    help="interleaved rounds per mode in the quant A/B")
    ap.add_argument("--quant-top1-min", type=float, default=0.9,
                    help="int8 top-1 agreement gate for the bench's random-init "
                         "model (BELOW the 0.98 production default: random-init "
                         "logits are near-ties, the worst case for argmax "
                         "stability — the caveat is recorded in the artifact)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the REPLICA-FLEET measurement instead of the single-"
                         "process suites: N cli/serve.py replica subprocesses behind "
                         "the router tier — hedged-vs-unhedged A/B, kill -9 "
                         "availability round, autoscaler diurnal trace (the r06 shape)")
    ap.add_argument("--fleet-replicas", type=int, default=2,
                    help="initial replica count (the straggler is the highest slot)")
    ap.add_argument("--fleet-requests", type=int, default=40,
                    help="open-loop requests per fleet round (A/B and kill)")
    ap.add_argument("--fleet-qps", type=float, default=0.0,
                    help="open-loop arrival rate; 0 = auto from the measured p50")
    ap.add_argument("--fleet-straggler-ms", type=float, default=400.0,
                    help="injected completion latency on the straggler replica")
    ap.add_argument("--fleet-phase-s", default="5,20,10",
                    help="low,high,trough durations (s) of the autoscaler's diurnal schedule")
    ap.add_argument("--fleet-seed", type=int, default=0)
    ap.add_argument("--zoo", action="store_true",
                    help="run the multi-model ZOO measurement instead of the "
                         "single-process suites: a 2-replica model-sharded "
                         "fleet (slot 0 int8 small tier, slot 1 f32 big "
                         "tier) A/B'd three ways on one seeded trace — "
                         "big-only baseline, sharded 50/50 pins (zero "
                         "misroutes/5xx), and the confidence cascade "
                         "(escalations > 0, bitwise answers, dispatched-"
                         "FLOPs/request strictly below big-only)")
    ap.add_argument("--zoo-requests", type=int, default=48,
                    help="trace length: requests per zoo arm (each arm "
                         "replays the SAME seeded trace)")
    ap.add_argument("--zoo-qps", type=float, default=0.0,
                    help="open-loop arrival rate per arm; 0 = auto from the "
                         "measured small-tier p50")
    ap.add_argument("--zoo-threshold", type=float, default=-1.0,
                    help="cascade escalation threshold on the top-1 softmax "
                         "margin; < 0 = calibrate to the trace's MEDIAN "
                         "reference margin (both outcomes populated)")
    ap.add_argument("--zoo-int8-top1-min", type=float, default=0.5,
                    help="int8 export agreement gate for the small tier "
                         "(random weights/trace: lower than the production "
                         "0.98 default)")
    ap.add_argument("--zoo-seed", type=int, default=0)
    ap.add_argument("--overload", action="store_true",
                    help="run the OVERLOAD measurement instead of the single-"
                         "process suites: brownout-off vs brownout-on on one "
                         "seeded 3x-capacity open-loop storm (in-process), plus "
                         "a gray-failure fleet round measuring time-to-soft-"
                         "eject and tail recovery (the r08 shape)")
    ap.add_argument("--overload-storm-s", type=float, default=5.0,
                    help="duration of each storm arm (requests = rate x duration)")
    ap.add_argument("--overload-multiple", type=float, default=3.0,
                    help="storm arrival rate as a multiple of measured capacity")
    ap.add_argument("--overload-pace-ms", type=float, default=20.0,
                    help="seeded per-dispatch latency floor pacing the engine so "
                         "capacity (and thus the storm) is box-independent")
    ap.add_argument("--overload-replicas", type=int, default=2,
                    help="fleet size for the gray-failure round (straggler is the "
                         "highest slot)")
    ap.add_argument("--overload-gray-requests", type=int, default=60,
                    help="open-loop requests in the gray-failure round")
    ap.add_argument("--overload-straggler-ms", type=float, default=300.0,
                    help="injected completion latency on the gray straggler")
    ap.add_argument("--overload-seed", type=int, default=0)
    ap.add_argument("--partition", action="store_true",
                    help="run the PARTITION measurement instead of the single-"
                         "process suites: in-process echo replicas behind "
                         "netchaos proxies, seeded blackhole/reset/half_open/"
                         "flap rounds measuring detection, client-visible "
                         "error rate (must be zero), and recovery, plus the "
                         "TTL-lease membership round (the r09 shape). No jax.")
    ap.add_argument("--partition-replicas", type=int, default=3)
    ap.add_argument("--partition-requests", type=int, default=120,
                    help="open-loop requests per partition round")
    ap.add_argument("--partition-qps", type=float, default=30.0,
                    help="open-loop arrival rate per partition round")
    ap.add_argument("--partition-poll-s", type=float, default=0.1,
                    help="router health-poll interval for the partition rounds")
    ap.add_argument("--partition-connect-timeout-s", type=float, default=0.4,
                    help="client TCP-handshake budget (also bounds poll reads)")
    ap.add_argument("--partition-read-timeout-s", type=float, default=2.0,
                    help="client read budget (leg timeout) — detection must "
                         "beat this, proving ejection rides the poll budget")
    ap.add_argument("--partition-lease-ttl-s", type=float, default=1.5,
                    help="lease TTL for the membership round")
    ap.add_argument("--partition-seed", type=int, default=0)
    ap.add_argument("--chaos-requests", type=int, default=80,
                    help="open-loop Poisson requests per chaos round (healthy + faulty)")
    ap.add_argument("--chaos-qps", type=float, default=0.0,
                    help="open-loop arrival rate; 0 = auto from the measured single-image p50")
    ap.add_argument("--chaos-fault-rate", type=float, default=0.05,
                    help="injected failure AND latency-spike probability in the faulty round")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for arrivals, class/size mix, and the fault schedule")
    ap.add_argument("--no-chaos", action="store_true", help="skip the chaos A/B")
    ap.add_argument("--out", default="", help="also write the JSON artifact here")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="accept a host without a TPU: counts stand, rates are XLA:CPU "
                         "timings and the headline is named cpu_rehearsal_*")
    ap.add_argument("--export-child", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.export_child:
        return _export_child_main(args.export_child)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    image_sizes = tuple(int(s) for s in args.image_sizes.split(","))

    def finish(out: dict) -> int:
        """ONE JSON line (+ --out copy); non-zero when the measurement raised."""
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 1 if "error" in out else 0

    def headline(metric: str, unit: str) -> dict:
        """A rate's name and unit: on a --cpu-rehearsal run both say so."""
        if args.cpu_rehearsal:
            return {"metric": f"cpu_rehearsal_{metric}",
                    "unit": f"{unit} on XLA:CPU (rehearsal: not a device rate)"}
        return {"metric": metric, "unit": unit}

    if not args.partition and not args.cpu_rehearsal:
        # found WITHOUT initialising a backend (device nodes + JAX_PLATFORMS):
        # the fleet-mode parents must leave the chip to their replicas
        from yet_another_mobilenet_series_tpu.cli.fleet import host_tpu_chips

        if not host_tpu_chips():
            raise SystemExit("serve_bench: no TPU on this host (or JAX_PLATFORMS keeps jax off "
                             "it): this benchmark measures on the chip or fails; "
                             "--cpu-rehearsal asks for a run whose rates are not device rates")

    if args.partition:
        # standalone like --fleet/--overload, but jax-free end to end: the
        # replicas are echo frontends, because the measurement is the
        # TRANSPORT (detection/containment/recovery), not a model
        out = {
            "metric": "partition_blackhole_detect_seconds",
            "value": None,
            "unit": "seconds",
            "vs_baseline": None,
            "vs_baseline_note": ("the implicit baseline is the read timeout: without "
                                 "the connect/read split and poll-budget ejection a "
                                 "blackholed replica pins legs for read_timeout_s"),
            "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        try:
            m = measure_partition(
                replicas=max(2, args.partition_replicas),
                requests=max(30, args.partition_requests),
                target_qps=max(5.0, args.partition_qps),
                seed=args.partition_seed,
                poll_interval_s=args.partition_poll_s,
                eject_failures=2,
                connect_timeout_s=args.partition_connect_timeout_s,
                read_timeout_s=args.partition_read_timeout_s,
                eject_cooldown_s=0.3,
                lease_ttl_s=args.partition_lease_ttl_s,
                flap_period_s=1.0,
                flap_down_s=0.5,
            )
            from scripts.provenance import provenance

            # no backend is ever touched: a loopback rehearsal by
            # construction (the real multi-host run is the ROADMAP rung)
            out.update({"platform": "cpu", "provenance": provenance(cpu_rehearsal=True),
                        "partition": m})
            out["value"] = m["rounds"]["blackhole"]["detection_s"]
        except Exception as e:  # noqa: BLE001 — structured error line, non-zero exit
            out["error"] = f"{type(e).__name__}: {e}"
        return finish(out)

    if args.overload:
        # standalone like --fleet: the storm arms own their batcher stacks
        # and the gray round owns replica subprocesses
        import shutil
        import tempfile

        out = {
            "metric": f"{args.arch}_overload_interactive_availability",
            "value": None,
            "unit": "completed/submitted",
            "vs_baseline": None,
            "vs_baseline_note": "the A/B is internal: brownout-off is the baseline arm",
            "image_size": image_sizes[0],
            "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        log_root = tempfile.mkdtemp(prefix="serve_bench_overload_")
        try:
            m = measure_overload(
                args.arch, image_sizes[0], buckets,
                storm_s=max(1.0, args.overload_storm_s),
                multiple=max(1.5, args.overload_multiple),
                pace_ms=max(1.0, args.overload_pace_ms),
                seed=args.overload_seed,
                replicas=max(2, args.overload_replicas),
                gray_requests=max(20, args.overload_gray_requests),
                straggler_ms=args.overload_straggler_ms,
                log_root=log_root,
            )
            import jax

            from scripts.provenance import provenance

            dev = jax.devices()[0]
            out.update({"platform": dev.platform, "device_kind": dev.device_kind,
                        "provenance": provenance(), "overload": m})
            out["value"] = m["storm"]["interactive_availability_on"]
            shutil.rmtree(log_root, ignore_errors=True)
        except Exception as e:  # noqa: BLE001 — structured error line, non-zero exit
            out["error"] = f"{type(e).__name__}: {e} (replica logs under {log_root})"
        return finish(out)

    if args.zoo:
        # standalone like --fleet: the zoo arms share one model-sharded
        # replica fleet, so the single-process suites would only add
        # redundant compile time to the artifact
        import shutil
        import tempfile

        out = {
            "metric": f"{args.arch}_zoo_cascade_flops_vs_big_only",
            "value": None,
            "unit": "cascade/big_only dispatched-FLOPs per request",
            "vs_baseline": None,
            "vs_baseline_note": ("the A/B is internal: the big-only arm "
                                 "(one-model-per-fleet) is the baseline; "
                                 "value < 1.0 is the cascade's cost win"),
            "image_size": image_sizes[0],
            "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        log_root = tempfile.mkdtemp(prefix="serve_bench_zoo_")
        try:
            m = measure_zoo(
                args.arch, image_sizes[0],
                requests=max(12, args.zoo_requests),
                target_qps=args.zoo_qps,
                seed=args.zoo_seed,
                threshold=args.zoo_threshold,
                int8_top1_min=args.zoo_int8_top1_min,
                log_root=log_root,
            )
            import jax

            from scripts.provenance import provenance

            dev = jax.devices()[0]
            out.update({"platform": dev.platform, "device_kind": dev.device_kind,
                        "provenance": provenance(), "zoo": m})
            out["value"] = m["cost"]["cascade_vs_big_only"]
            shutil.rmtree(log_root, ignore_errors=True)
        except Exception as e:  # noqa: BLE001 — structured error line, non-zero exit
            out["error"] = f"{type(e).__name__}: {e} (replica logs under {log_root})"
        return finish(out)

    if args.fleet:
        # the fleet measurement is standalone: replica subprocesses own the
        # engines, so the single-process suites would only add minutes of
        # redundant compile time to the artifact
        import shutil
        import tempfile

        out = {
            **headline(f"{args.arch}_fleet_requests_per_sec", "requests/sec"),
            "value": None,
            "vs_baseline": None,
            "vs_baseline_note": "first fleet round; single-replica rows live in BENCH_SERVE_r01..r05",
            "image_size": image_sizes[0],
            "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        log_root = tempfile.mkdtemp(prefix="serve_bench_fleet_")
        try:
            m = measure_fleet(
                args.arch, image_sizes[0], buckets,
                replicas=max(2, args.fleet_replicas),
                requests=max(10, args.fleet_requests),
                target_qps=args.fleet_qps,
                straggler_ms=args.fleet_straggler_ms,
                seed=args.fleet_seed,
                phase_s=tuple(float(s) for s in args.fleet_phase_s.split(",")),
                log_root=log_root,
            )
            import jax

            from scripts.provenance import provenance

            dev = jax.devices()[0]
            out.update({"platform": dev.platform, "device_kind": dev.device_kind,
                        "provenance": provenance(), "fleet": m})
            out["value"] = m["hedge_ab"]["unhedged"]["qps"]
            shutil.rmtree(log_root, ignore_errors=True)
        except Exception as e:  # noqa: BLE001 — structured error line, non-zero exit
            out["error"] = f"{type(e).__name__}: {e} (replica logs under {log_root})"
        return finish(out)

    out = {
        **headline(f"{args.arch}_serve_images_per_sec", "images/sec"),
        "value": None,
        "vs_baseline": None,
        "vs_baseline_note": "BENCH_SERVE_r01 predates the concurrent-submit mode; direct rows are comparable",
        "image_size": image_sizes[0],
        "image_sizes": list(image_sizes),
        "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    try:
        m = measure(args.arch, image_sizes, buckets, max(1, args.iters),
                    max(1, args.concurrent_iters), max(1, args.ab_iters),
                    max(1, args.max_inflight), not args.no_bf16,
                    chaos_requests=0 if args.no_chaos else max(1, args.chaos_requests),
                    chaos_qps=args.chaos_qps, chaos_fault_rate=args.chaos_fault_rate,
                    chaos_seed=args.chaos_seed,
                    fuse_ladder=tuple(int(k) for k in args.fuse_ladder.split(",")) if args.fused else (),
                    fused_iters=max(1, args.fused_iters),
                    structural=args.structural,
                    structural_rounds=args.structural_rounds,
                    quant=args.quant, quant_iters=args.quant_iters,
                    quant_rounds=args.quant_rounds,
                    quant_top1_min=args.quant_top1_min)
        out.update(m)
        out["value"] = m["peak_qps"]
    except Exception as e:  # noqa: BLE001 — structured error line, non-zero exit
        out["error"] = f"{type(e).__name__}: {e}"
    return finish(out)


if __name__ == "__main__":
    sys.exit(main())
