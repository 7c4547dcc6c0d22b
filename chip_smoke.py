#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user types, in ONE
process (a chip belongs to one process at a time; nothing here starts a
child that needs the device):

    cli.train.main   MobileNetV3-Large 1.0x / 1000 classes / 224 px / bf16,
                     256 images per chip on every visible chip, fake data:
                     a few dozen steps, one eval pass, one checkpoint
    cli.serve.main   serve.export_from=<that checkpoint>: fold + export, AOT
                     warm-up of the shipped bucket ladder (buckets, fuse_chunks
                     and overlap exactly as apps/serve_mobilenet_v3.yml ships)
    cli.serve.main   the same bundle under a closed-loop load of single-image
                     requests, then --listen: POST /predict and GET /healthz
                     over loopback, stopped by SIGTERM like an operator would

and, outside the main path, compiles the repository's one Pallas kernel for
the device (``interpret=False``) at two real MobileNetV3-Large shapes.

It fails (non-zero, reason on stderr, no result line) when JAX finds no TPU,
when any phase fails, and when any phase was skipped. What it checks:

- training: the expected step count, finite loss every window, a replica
  checksum of zero divergence (parallel/dp.py), the step compiled exactly
  once and nothing compiled from the second log boundary on, 256 rows of
  the batch on every device, device memory in
  use on every device and about equal, an eval pass over every eval image,
  a checkpoint at the last step;
- serving: every request of the load completed (none shed, rejected, failed
  or crashed), no compilation once requests flow, /healthz closed-breaker
  200, and served logits equal to a direct ``jit`` of the same folded
  forward ON THE SAME DEVICE to ``SERVE_RTOL`` (see there);
- the kernel against ``_reference_fwd`` on the chip to ``KERNEL_RTOL``.

Set-up (compile) time is reported apart from steady time for each phase,
with the persistent compilation cache's hits and misses (utils/
compile_cache.py). Run twice with the cache directory kept, the second run
reports its own warm set-up beside the first run's cold one. Its times are
SMOKE numbers — fake data generated on the host, a few dozen steps — not
benchmark results.

    python chip_smoke.py                # on a TPU host; fails anywhere else
    python chip_smoke.py --rehearsal    # explicit: same path, toy size, any
                                        # platform; proves control flow only

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import math
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
from dataclasses import dataclass

REPO = os.path.dirname(os.path.abspath(__file__))
APPS = os.path.join(REPO, "yet_another_mobilenet_series_tpu", "apps")

# Served logits vs a direct jit of the same folded forward, same params,
# same compute dtype, SAME DEVICE. f32 convolutions at default precision on
# a TPU multiply in bf16, so chip-vs-CPU would differ at the 1e-2 level and
# prove nothing; chip-vs-chip the two programs differ only in batch padding
# and buffer donation, i.e. at most in tiling and f32 accumulation order
# (expected ~1e-6 of the logit scale; measured bitwise equal on a v5e, PR
# 22). 1e-3 of the largest |logit| leaves three orders of margin and still
# catches a wrong weight, a wrong normalisation or a row mix-up, which move
# logits by their own size.
SERVE_RTOL = 1e-3
# The kernel does exact f32 VPU arithmetic; the reference convolution is run
# at HIGHEST precision for the comparison (at default precision XLA's own
# conv is the imprecise side: 1.1e-2 abs on values of 6, measured PR 22).
# Both f32 with f32 accumulation over <= 49 taps: 1e-4 of the largest
# |output| is ~100 ulp of headroom.
KERNEL_RTOL = 1e-4
# (hw_in, channels, k, stride, act): MobileNetV3-Large block 13's depthwise
# (stride 2, C=672 > 128 so the channel axis blocks) and block 5's (stride 1)
KERNEL_SHAPES = ((14, 672, 5, 2, "hswish"), (28, 120, 5, 1, "relu"))

DEADLINE_S = 1150  # the driver allows 1200 s; die loudly with stacks before that


@dataclass(frozen=True)
class Size:
    image: int
    per_chip_batch: int
    steps: int
    log_every: int
    eval_size: int
    eval_batch: int
    requests: int
    clients: int
    posts: int
    model: tuple[str, ...]  # extra key=value overrides for the model


FULL = Size(image=224, per_chip_batch=256, steps=30, log_every=5, eval_size=500,
            eval_batch=250, requests=512, clients=16, posts=4, model=())
# toy: two MobileNetV3 rows (one with SE, one hswish), 16 classes, 32 px
REHEARSAL = Size(image=32, per_chip_batch=4, steps=6, log_every=2, eval_size=8,
                 eval_batch=8, requests=48, clients=4, posts=2, model=(
                     "model.block_specs=[{exp: 16, c: 16, n: 1, s: 2, k: 3, act: relu}, "
                     "{exp: 48, c: 24, n: 1, s: 2, k: 5, act: hswish, se: 0.25}]",
                     "model.num_classes=16"))


class SmokeFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def need(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_memory(jax) -> list[dict]:
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def train_phase(size: Size, n_dev: int, work: str, clog, registry, on_tpu: bool,
                extra: list[str]) -> dict:
    from yet_another_mobilenet_series_tpu.ckpt.manager import CheckpointManager
    from yet_another_mobilenet_series_tpu.cli import train as cli_train

    batch = size.per_chip_batch * n_dev
    log_dir = os.path.join(work, "train")
    argv = [
        f"app:{APPS}/mobilenet_v3_large.yml", *size.model,
        "data.dataset=fake", f"data.image_size={size.image}",
        f"data.fake_train_size={batch * size.steps}", f"data.fake_eval_size={size.eval_size}",
        # every visible chip, SyncBN across them (the app pins one device)
        "dist.num_devices=0", "dist.sync_bn=true",
        f"train.batch_size={batch}", f"train.eval_batch_size={size.eval_batch}",
        "train.epochs=1", f"train.log_every={size.log_every}",
        # parallel/dp.py's cross-replica parameter checksum at every log
        # boundary: a replica that diverged raises inside the run
        f"train.param_checksum_every={size.log_every}",
        # host spans on: the compile watch reads which one a compile happened in
        "obs.trace=true",
        "train.resume=false", f"train.log_dir={log_dir}", *extra,
    ]
    say("train: cli.train.main " + " ".join(a for a in argv if not a.startswith("model.block_specs")))
    registry.gauge("train.step").set(0)  # the stamp the compile watch reads; stale if the process trained before
    mark = clog.mark()
    t0 = time.perf_counter()
    result = cli_train.main(argv)
    wall = time.perf_counter() - t0
    comp = clog.since(mark)

    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    train_rows = [r for r in rows if "train/loss" in r]
    need(len(train_rows) == size.steps // size.log_every and train_rows[-1]["step"] == size.steps,
         f"train: expected {size.steps} steps logged every {size.log_every}, "
         f"got rows at steps {[r['step'] for r in train_rows]}")
    losses = [r["train/loss"] for r in train_rows]
    need(all(math.isfinite(v) for v in losses) and all(r["train/finite"] == 1.0 for r in train_rows),
         f"train: non-finite loss in {losses}")
    need(result.get("eval_n") == size.eval_size and math.isfinite(result.get("eval_loss", math.nan)),
         f"train: eval pass incomplete or non-finite: {result}")
    mgr = CheckpointManager(os.path.join(log_dir, "ckpt"))
    try:
        saved = list(mgr.all_steps())
    finally:
        mgr.close()
    need(size.steps in saved, f"train: no checkpoint at step {size.steps} (found {saved})")

    # the step program compiles inside its first dispatch and never again...
    in_step = [c for c in comp["events"] if "dispatch/train_step" in c["open"]]
    need(len(in_step) == 1, f"train: the step compiled {len(in_step)} times, expected once "
         f"(compilations inside dispatch/train_step spans: {in_step})")
    # ...and NOTHING compiles from the second log boundary to the last one
    # (the first boundary compiles the replica checksum; eval and the
    # checkpoint gather compile after the last)
    late = [c for c in comp["events"] if 2 * size.log_every <= c["train_step"] < size.steps]
    need(not late, f"train: {len(late)} compilation(s) in the steady windows: {late}")

    # where the work was, from the run's own gauges (last train row)
    last = train_rows[-1]
    rows_per_dev = {i: last.get(f"obs/train.batch_rows.d{i}") for i in range(n_dev)}
    need(all(v == size.per_chip_batch for v in rows_per_dev.values()),
         f"train: batch rows per device {rows_per_dev}, expected {size.per_chip_batch} on each")
    in_use = {i: last.get(f"obs/device.bytes_in_use.d{i}") for i in range(n_dev)}
    if on_tpu:
        need(all(v and v > 0 for v in in_use.values()),
             f"train: device memory not in use on every device: {in_use}")
        lo, hi = min(in_use.values()), max(in_use.values())
        need(hi <= 1.25 * lo, f"train: device memory uneven across devices: {in_use}")

    ms = [1e3 * batch / r["train/images_per_sec"] for r in train_rows]
    steady = sorted(ms[1:])[len(ms[1:]) // 2]
    out = {
        "global_batch": batch, "steps": size.steps, "wall_s": round(wall, 1),
        "first_window_s": round(ms[0] * size.log_every / 1e3, 1),
        "setup_s": round((ms[0] - steady) * size.log_every / 1e3, 1),
        "steady_ms_per_step": round(steady, 1),
        "window_ms_per_step": [round(v, 1) for v in ms],
        "loss_first_last": [round(losses[0], 4), round(losses[-1], 4)],
        "eval": {k: result[k] for k in ("eval_n", "eval_loss", "eval_top1")},
        "obs_compiles": last.get("obs/obs.compiles"),
        "step_compiles": len(in_step), "step_compile_s": in_step[0]["compile_s"],
        # the step's own split, and the whole phase's (the watch: trace, lowering, compile, the collector)
        "step_trace_s": in_step[0]["trace_s"], "step_lower_s": in_step[0]["lower_s"],
        **{k: comp[k] for k in ("trace_s", "lower_s", "gc_s")},
        "compiles_in_steady_windows": len(late),
        "batch_rows_per_device": rows_per_dev,
        "bytes_in_use_per_device": in_use,
        "peak_bytes_per_device": {i: last.get(f"obs/device.peak_bytes_in_use.d{i}")
                                  for i in range(n_dev)},
        **{k: comp[k] for k in ("compiles", "compile_s", "cache_hits", "cache_misses")},
    }
    say(f"train: {size.steps} steps of {batch} ({size.per_chip_batch}/chip x {n_dev}), "
        f"set-up {out['setup_s']} s (first window {out['first_window_s']} s; "
        f"{comp['compiles']} compiles {comp['compile_s']} s, cache {comp['cache_hits']} hit / "
        f"{comp['cache_misses']} miss), steady {out['steady_ms_per_step']} ms/step (smoke), "
        f"step compiled once (trace {in_step[0]['trace_s']:.2f} s, lower {in_step[0]['lower_s']:.2f} s, "
        f"compile {in_step[0]['compile_s']:.2f} s, cache {in_step[0]['cache']}; the phase's collector "
        f"{comp['gc_s']} s), 0 compilations in steps "
        f"{2 * size.log_every}..{size.steps}, loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
        f"eval n={result['eval_n']} loss {result['eval_loss']:.3f}, checkpoint at {size.steps}")
    say(f"train: per device rows {rows_per_dev} bytes_in_use {in_use}")
    return out


def export_phase(size: Size, work: str, clog, registry) -> dict:
    from yet_another_mobilenet_series_tpu.cli import serve as cli_serve

    argv = [
        f"app:{APPS}/serve_mobilenet_v3.yml", f"data.image_size={size.image}",
        f"serve.export_from={os.path.join(work, 'train', 'ckpt')}",
        f"serve.bundle={os.path.join(work, 'bundle')}",
        f"train.log_dir={os.path.join(work, 'export')}", "serve.requests=0",
    ]
    say("export: cli.serve.main " + " ".join(argv))
    mark, c0 = clog.mark(), registry.counter("obs.compiles").value
    t0 = time.perf_counter()
    result = cli_serve.main(argv)
    wall = time.perf_counter() - t0
    comp = clog.since(mark)
    need(result.get("bundle") and os.path.exists(os.path.join(result["bundle"], "spec.json")),
         f"export: no bundle written: {result}")
    executables = int(registry.counter("obs.compiles").value - c0)
    need(executables >= 5, f"export: warm-up compiled {executables} executables, "
         "expected the 3 buckets + 2 fused K of the shipped ladder")
    out = {"wall_s": round(wall, 1), "executables": executables,
           **{k: comp[k] for k in ("compiles", "compile_s", "cache_hits", "cache_misses")}}
    say(f"export+warm-up: {wall:.1f} s set-up, {executables} serving executables "
        f"({comp['compiles']} compiles {comp['compile_s']} s, cache {comp['cache_hits']} hit / "
        f"{comp['cache_misses']} miss)")
    return out


def serve_phase(size: Size, work: str, clog, registry) -> tuple[dict, list]:
    """The bundle under load, then the front door. cli.serve.main runs in
    this (the main) thread and owns SIGTERM; a helper thread plays the
    operator: waits for the bound address, POSTs, checks health, SIGTERMs."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.cli import serve as cli_serve
    from yet_another_mobilenet_series_tpu.serve.client import ReplicaClient

    log_dir = os.path.join(work, "serving")
    addr_path = os.path.join(log_dir, "listen_addr.json")
    rng = np.random.RandomState(22)
    images = [rng.normal(0, 1, (size.image, size.image, 3)).astype(np.float32)
              for _ in range(size.posts)]
    door: dict = {"logits": [], "error": None}
    gone = threading.Event()  # cli.serve.main returned or raised: nobody to signal

    def operator():
        try:
            while not os.path.exists(addr_path):
                if gone.wait(0.05):
                    return
            with open(addr_path) as f:
                addr = json.load(f)
            door["compiles_at_bind"] = registry.counter("obs.compiles").value
            client = ReplicaClient.from_addr(addr, timeout_s=120.0)
            try:
                for img in images:
                    door["logits"].append(client.predict(img, priority="interactive"))
                door["healthz"] = client.healthz()
            finally:
                client.close()
        except Exception as e:  # noqa: BLE001 — reported by the main thread
            door["error"] = f"{type(e).__name__}: {e}"
        finally:
            if not gone.is_set():
                os.kill(os.getpid(), signal.SIGTERM)  # what an operator sends

    argv = [
        f"app:{APPS}/serve_mobilenet_v3.yml", f"data.image_size={size.image}",
        f"serve.bundle={os.path.join(work, 'bundle')}", f"train.log_dir={log_dir}",
        f"serve.requests={size.requests}", f"serve.clients={size.clients}", "--listen",
    ]
    say("serve: cli.serve.main " + " ".join(argv))
    prev = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    mark, c0 = clog.mark(), registry.counter("obs.compiles").value
    r0 = registry.counter("serve.requests").value
    helper = threading.Thread(target=operator, name="chip-smoke-operator", daemon=True)
    helper.start()
    t0 = time.perf_counter()
    try:
        result = cli_serve.main(argv)
    finally:
        gone.set()
        for s, h in prev.items():
            signal.signal(s, h)
    wall = time.perf_counter() - t0
    helper.join(timeout=30)
    comp = clog.since(mark)

    need(result.get("completed") == size.requests and not any(
        result.get(k) for k in ("shed", "rejected_full", "failed", "client_crashes")),
        f"serve: not every request completed: {result}")
    under_load = [c for c in comp["events"] if c["serve_requests"] > r0]
    need(not under_load, f"serve: {len(under_load)} compilation(s) once requests flowed: {under_load}")
    warm = int(door["compiles_at_bind"] - c0) if "compiles_at_bind" in door else None
    moved = int(registry.counter("obs.compiles").value - c0)
    need(warm is not None and moved == warm,
         f"serve: obs.compiles moved from {warm} to {moved} while the front door served")
    need(door["error"] is None, f"listen: {door['error']}")
    need(len(door["logits"]) == size.posts, f"listen: {len(door['logits'])}/{size.posts} POSTs answered")
    status, health = door["healthz"]
    need(status == 200 and health.get("breaker") == "closed", f"listen: /healthz {status} {health}")
    need(result.get("listened") and not result.get("drain_timeouts"),
         f"listen: drain was not clean: {result}")
    setup = wall - result["wall_s"] - result["drain_s"]
    out = {
        "wall_s": round(wall, 1), "setup_s": round(setup, 1),
        "requests": size.requests, "completed": result["completed"],
        "load_wall_s": round(result["wall_s"], 2), "requests_per_s": round(result["qps"], 1),
        "p50_ms": round(result["p50_ms"], 2), "p99_ms": round(result["p99_ms"], 2),
        "posts": len(door["logits"]), "healthz": status, "drain_s": round(result["drain_s"], 2),
        "obs_compiles_warmup": warm, "obs_compiles_after": moved,
        **{k: comp[k] for k in ("compiles", "compile_s", "cache_hits", "cache_misses")},
    }
    say(f"serve: second warm-up + start {out['setup_s']} s ({comp['compiles']} compiles "
        f"{comp['compile_s']} s, cache {comp['cache_hits']} hit / {comp['cache_misses']} miss); "
        f"load {result['completed']}/{size.requests} completed, 0 shed/rejected/failed, "
        f"{out['requests_per_s']} requests/s, p50 {out['p50_ms']} ms, p99 {out['p99_ms']} ms "
        f"(smoke; {size.clients} closed-loop clients); obs.compiles {warm} -> {moved}")
    say(f"listen: {out['posts']} POST /predict answered, GET /healthz {status} "
        f"breaker {health.get('breaker')}, SIGTERM drain {out['drain_s']} s clean")
    return out, list(zip(images, door["logits"]))


def reference_phase(work: str, served: list) -> dict:
    """Served logits vs a direct jit of the same folded forward on the same
    device (SERVE_RTOL says why that is the comparison)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from yet_another_mobilenet_series_tpu.serve.export import apply_folded, load_bundle

    bundle = load_bundle(os.path.join(work, "bundle"))
    direct = jax.jit(lambda p, x: apply_folded(bundle.net, p, x, compute_dtype=jnp.float32))
    worst = scale = 0.0
    for img, logits in served:
        ref = np.asarray(direct(bundle.params, img[None]))[0]
        need(logits.shape == ref.shape and np.all(np.isfinite(logits)),
             f"reference: served logits shape {logits.shape} vs {ref.shape}, or not finite")
        worst = max(worst, float(np.max(np.abs(logits - ref))))
        scale = max(scale, float(np.max(np.abs(ref))))
    need(scale > 0 and worst <= SERVE_RTOL * scale,
         f"reference: served logits differ from the direct jit by {worst:.3e} "
         f"(largest |logit| {scale:.3e}, bound {SERVE_RTOL:g} of it)")
    say(f"reference: {len(served)} served logit rows of {ref.shape[0]} classes vs direct jit on "
        f"{jax.devices()[0].device_kind}: max |diff| {worst:.3e} on |logit| <= {scale:.3e} "
        f"(bound {SERVE_RTOL:g} relative)")
    return {"rows": len(served), "classes": int(ref.shape[0]), "max_abs_diff": worst,
            "max_abs_logit": scale, "rtol": SERVE_RTOL}


def kernel_phase() -> dict:
    """ops/pallas_kernels.fused_depthwise_inference compiled by Mosaic for
    this device — never the interpreter — against _reference_fwd."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from yet_another_mobilenet_series_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    rows = []
    for hw, c, k, s, act in KERNEL_SHAPES:
        x = jnp.asarray(rng.normal(0, 1, (8, hw, hw, c)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 0.2, (k, k, c)), jnp.float32)
        scale = jnp.asarray(rng.uniform(0.5, 1.5, (c,)), jnp.float32)
        shift = jnp.asarray(rng.normal(0, 0.1, (c,)), jnp.float32)
        mask = jnp.ones((c,), jnp.float32).at[::5].set(0.0)
        t0 = time.perf_counter()
        y = np.asarray(pk.fused_depthwise_inference(x, w, scale, shift, mask, s, act, False))
        secs = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(
                lambda *a: pk._reference_fwd(*a, stride=s, act=act))(x, w, scale, shift, mask))
        diff, top = float(np.max(np.abs(y - ref))), float(np.max(np.abs(ref)))
        need(y.shape == ref.shape and diff <= KERNEL_RTOL * top,
             f"kernel: {(hw, c, k, s, act)} differs from _reference_fwd by {diff:.3e} "
             f"on |y| <= {top:.3e} (bound {KERNEL_RTOL:g} relative)")
        rows.append({"hw": hw, "c": c, "k": k, "stride": s, "act": act,
                     "max_abs_diff": diff, "max_abs": top, "compile_and_run_s": round(secs, 2)})
        say(f"kernel: fused_depthwise_inference interpret=False hw={hw} c={c} k={k} s={s} {act}: "
            f"compiled+ran in {secs:.1f} s, max |diff| {diff:.2e} on |y| <= {top:.2f} vs "
            f"_reference_fwd at highest precision")
    return {"shapes": rows, "rtol": KERNEL_RTOL}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy size on whatever platform JAX has: a check of control flow, "
                         "never of the chip (never entered unless asked for)")
    ap.add_argument("--train", action="append", default=[], metavar="KEY=VALUE",
                    help="extra cli.train override, e.g. dist.shard_optimizer=true")
    ap.add_argument("--only-train", action="store_true",
                    help="builder's partial run (the four-chip check): training phase only; "
                         "prints its report and exits 2 WITHOUT the ok line")
    ap.add_argument("--out", default="", help="also write the full report JSON here")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True, file=sys.__stderr__)
    try:
        return run(args)
    finally:
        faulthandler.cancel_dump_traceback_later()


def run(args) -> int:
    try:
        import jax

        from yet_another_mobilenet_series_tpu.obs.device import install_compile_watch
        from yet_another_mobilenet_series_tpu.obs.registry import get_registry
        from yet_another_mobilenet_series_tpu.utils import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the program is not importable from {REPO}: {e}", file=sys.stderr)
        return 1
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(jax.devices())}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearsal:
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is {device['platform']!r} "
              f"({device['kind']}). This check proves the program runs on the chip and "
              "does not fall back; --rehearsal runs a toy size here instead.", file=sys.stderr)
        return 1
    size = REHEARSAL if args.rehearsal else FULL
    mode = "REHEARSAL (toy size; proves control flow, says nothing about the chip)" \
        if args.rehearsal else "full size"

    from importlib import metadata

    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            versions[pkg] = "not installed"
    cache_dir = compile_cache.configure()  # None: held to the CPU, cache off
    cache_on = bool(cache_dir) and bool(jax.config.jax_enable_compilation_cache)
    entries = len(os.listdir(cache_dir)) if cache_on and os.path.isdir(cache_dir) else 0
    say(f"{mode}: platform {device['platform']}, device_kind {device['kind']}, "
        f"{device['count']} device(s); jax {versions['jax']}, jaxlib {versions['jaxlib']}, "
        f"libtpu {versions['libtpu']}")
    say(f"compile cache: {cache_dir} ({'off' if not cache_on else 'cold' if not entries else 'warm'}, "
        f"{entries} entries at start)")

    registry = get_registry()
    # the program's own compile watch (obs/device.py): every compile stamped
    # with the open host spans, the train step and the request count, which
    # turns 'steps after the first cause no compilation' into a count
    clog = install_compile_watch()
    report: dict = {"rehearsal": args.rehearsal, "device": device, "versions": versions,
                    "compile_cache": {"dir": cache_dir, "entries_at_start": entries,
                                      "enabled": cache_on}}
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    t_start = time.perf_counter()
    try:
        report["train"] = train_phase(size, device["count"], work, clog, registry, on_tpu,
                                      args.train)
        if not args.only_train:
            report["export"] = export_phase(size, work, clog, registry)
            report["serve"], served = serve_phase(size, work, clog, registry)
            report["reference"] = reference_phase(work, served)
            if on_tpu:
                report["kernel"] = kernel_phase()
            else:
                # rehearsal off-chip only (the full run has returned above
                # without a TPU): Mosaic compiles for a TPU or not at all,
                # and the interpreter is not allowed to stand in
                say("kernel: NOT RUN — this rehearsal is not on a TPU")
        report["memory"] = device_memory(jax)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    report["wall_s"] = round(time.perf_counter() - t_start, 1)

    if on_tpu:
        peak = max(m["peak_bytes_in_use"] for m in report["memory"])
        say(f"peak HBM (memory_stats peak_bytes_in_use, max over devices): {peak / 2**30:.2f} GiB of "
            f"{report['memory'][0]['bytes_limit'] / 2**30:.2f} GiB")
    setup = {"train_s": report["train"]["setup_s"], "cache_entries_at_start": entries}
    if not args.only_train:
        setup.update(export_s=report["export"]["wall_s"], serve_s=report["serve"]["setup_s"])
    report["setup"] = setup
    say(f"set-up this run ({'warm' if entries else 'cold'} cache): {setup}")
    if cache_on and os.path.isdir(cache_dir):
        # cold beside warm: the set-up of the previous run that used this
        # cache directory is kept in it, keyed by what was run
        state_path = os.path.join(cache_dir, "chip_smoke_setup.json")
        key = f"{'rehearsal' if args.rehearsal else 'full'}-{device['kind']}-{device['count']}" \
              f"{'-train' if args.only_train else ''}{''.join(sorted(args.train))}"
        try:
            with open(state_path) as f:
                state = json.load(f)
        except (OSError, ValueError):
            state = {}
        if key in state:
            say(f"set-up of the previous run on this cache directory: {state[key]}")
            report["setup_previous_run"] = state[key]
        state[key] = setup
        with open(state_path, "w") as f:
            json.dump(state, f)
    say(f"total {report['wall_s']} s")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, default=float)
    if args.only_train:
        say("partial run (--only-train): phases export, serve, reference and kernel were "
            "SKIPPED, so this is not a pass")
        return 2
    last = {"ok": True, "device": device}
    if args.rehearsal:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
