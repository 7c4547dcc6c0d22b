"""Headline benchmark: MobileNetV3-Large ImageNet training throughput,
images/sec/chip (the tracked metric, BASELINE.json:2), plus MFU.

Measures the full fused training step — forward, backward, RMSProp+WD update,
EMA, label-smoothed CE — in bfloat16 at 224x224 on device-resident data, so
the number is the model/step ceiling of SURVEY.md §3.1's hot loop (host input
throughput is benchmarked separately by the data pipeline).

ONE process that measures on the chip or fails. It prints exactly one JSON
line on stdout:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "platform": "tpu",
   "device_kind": ..., "mfu": ..., ...}
and exits 0 — or, when JAX finds no TPU, when the device_kind has no entry in
the peak table, or when anything raises, it prints NO result and exits
non-zero with the reason on stderr. There is no fallback to the CPU: a CPU
timing under a device metric's name is worse than no number.

``--cpu`` is an explicit smoke of the script's control flow at a toy size on
the CPU backend. Its output line carries no rate, no unit and no MFU — only
that the steps ran and the loss was finite — so it cannot be mistaken for a
measurement.

vs_baseline: BASELINE.json ships "published": {} (no reference numbers were
recoverable — see SURVEY.md provenance warning), so vs_baseline is null until
a real reference measurement exists.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

VS_BASELINE_NOTE = (
    "null: BASELINE.json publishes no reference throughput and the reference "
    "mount is empty; no real divisor exists (an assumed ~1000 img/s/chip "
    "V100-class figure was dropped as noise)"
)

# Dense peak bf16 FLOPs/s per chip, by device_kind substring (Google Cloud
# TPU documentation, per-chip figures). A device that is not here is an
# error, not a default: an MFU against a guessed peak is not a measurement.
PEAK_FLOPS_BY_KIND = [
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

REPO_DIR = os.path.dirname(os.path.abspath(__file__))
# Measured-winner step config (train/tuning.py documents the format; the
# same file a training run adopts through train.tuning_file). `python
# bench.py` picks it up with no extra flags so the headline artifact reflects
# the repo's best-known configuration; BENCH_TUNING_PATH points elsewhere.
TUNING_PATH = os.environ.get("BENCH_TUNING_PATH") or os.path.join(REPO_DIR, "BENCH_TUNING.json")


def backend_initialised() -> bool:
    """True when THIS process has started a JAX backend (and so, on a TPU
    host, holds the chip). Reads module state only: asking jax for its
    devices would start the backend this exists to detect."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and xb.backends_are_initialized()


def provenance(cpu_rehearsal: bool | None = None) -> dict:
    """Shared bench-artifact provenance stamp: jax/jaxlib versions, python,
    platform/device kind, and the cpu-rehearsal flag — every bench/table
    artifact (serve_bench, train_chaos, latency_table, the headline worker)
    carries this block so a number can always be attributed to the software
    and hardware that produced it.

    Version lookup goes through importlib.metadata, NOT ``import jax``, and
    platform/device fields are read ONLY from a backend this process has
    already initialised: a parent whose replicas or children need the chip
    (serve_bench --fleet, train_chaos) may have jax imported, and asking it
    for its devices here would take the chip from them. ``cpu_rehearsal``
    defaults to "the backend is cpu" and can be forced by callers that know
    (train_chaos pins True)."""
    from importlib import metadata

    info: dict = {"python": ".".join(str(v) for v in sys.version_info[:3])}
    for pkg in ("jax", "jaxlib"):
        try:
            info[f"{pkg}_version"] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            info[f"{pkg}_version"] = None
    if backend_initialised():
        j = sys.modules["jax"]
        devs = j.devices()
        info["platform"] = j.default_backend()
        info["device_kind"] = devs[0].device_kind
        info["n_devices"] = len(devs)
    if cpu_rehearsal is None:
        cpu_rehearsal = info.get("platform") == "cpu"
    info["cpu_rehearsal"] = bool(cpu_rehearsal)
    return info


def stamp_provenance(artifact: dict, cpu_rehearsal: bool | None = None) -> dict:
    """Attach the provenance block in place (and return the artifact)."""
    artifact["provenance"] = provenance(cpu_rehearsal)
    return artifact


def apply_flags_env(env: dict, flags_str: str) -> dict:
    """Merge a validated flag string into env (XLA_FLAGS / LIBTPU_INIT_ARGS,
    appended — never overwritten). One implementation for both the headline
    bench and bench_bn's sweep, so the merge semantics cannot drift. Importing
    the package imports jax but initialises no backend, which is when these
    variables are read."""
    from yet_another_mobilenet_series_tpu.train.tuning import partition_flags

    xla, libtpu = partition_flags(flags_str)
    if xla:
        env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {xla}".strip()
    if libtpu:
        env["LIBTPU_INIT_ARGS"] = f"{env.get('LIBTPU_INIT_ARGS', '')} {libtpu}".strip()
    return env


def read_tuning_flags() -> str:
    """Measured-winner XLA flags from the tuning file (raw JSON only: they
    must be in the environment BEFORE jax is imported). Returns "" unless a
    valid non-empty 'flags' string is present."""
    try:
        with open(TUNING_PATH) as f:
            raw = json.load(f)
        flags = raw.get("flags", "")
        if not isinstance(flags, str):
            raise ValueError("flags must be a string")
        apply_flags_env({}, flags)  # validates token shape
        return flags
    except FileNotFoundError:
        return ""
    except (OSError, ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
        log(f"tuning: ignoring flags from malformed {TUNING_PATH}: {e}")
        return ""


def load_tuning() -> dict:
    """Best-measured step config, or {} (the exact/no-remat parity baseline).
    A malformed tuning file must never take the headline bench down — it is
    an aux artifact; fall back to the baseline and say so on stderr. Every
    value is validated here (not just parsed). Imports the package (hence
    jax); validation is single-sourced in
    train/tuning.py so bench and the production CLI (train.tuning_file)
    can never disagree about well-formedness."""
    from yet_another_mobilenet_series_tpu.train.tuning import validate_tuning

    try:
        with open(TUNING_PATH) as f:
            raw = json.load(f)
        tuning = validate_tuning(raw)
        if not tuning:
            # a file with no tuning keys is the baseline, not a winner —
            # returning a truthy dict here would stamp a bogus tuning_source
            return {}
        tuning["source"] = raw.get("source")
        return tuning
    except FileNotFoundError:
        return {}
    except (OSError, ValueError, KeyError, TypeError) as e:
        log(f"tuning: ignoring malformed {TUNING_PATH}: {e}")
        return {}


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def peak_flops_for(device_kind: str) -> float:
    kind = device_kind.lower()
    for sub, flops in PEAK_FLOPS_BY_KIND:
        if sub in kind:
            return flops
    raise KeyError(
        f"device_kind {device_kind!r} is not in bench.PEAK_FLOPS_BY_KIND: add its "
        "published per-chip bf16 peak (with the source) before reporting an MFU on it"
    )


# --------------------------------------------------------------------------
# the measurement
# --------------------------------------------------------------------------


def measure(cpu_smoke: bool) -> dict:
    import jax

    from yet_another_mobilenet_series_tpu.utils import compile_cache
    from yet_another_mobilenet_series_tpu.utils.benchkit import build_train_fixture, sync
    from yet_another_mobilenet_series_tpu.utils.profiling import profile_network

    compile_cache.configure()
    platform = jax.default_backend()
    n_chips = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    if not cpu_smoke and platform != "tpu":
        raise SystemExit(
            f"bench: no TPU: jax.default_backend() is {platform!r} ({device_kind}). This "
            "benchmark measures on the chip or fails; `--cpu` is a control-flow smoke "
            "that reports no rate."
        )
    # sizes follow the FLAG, never the platform found: batch sized for one
    # v5e-class chip, scaled with the mesh. On HBM pressure the fallback
    # loop halves the batch (and finally enables activation remat); the
    # artifact records the batch that actually ran.
    per_chip_batch, image_size, iters = (8, 64, 5) if cpu_smoke else (256, 224, 20)
    peak = None if cpu_smoke else peak_flops_for(device_kind)  # unknown device: fail before measuring
    batch = per_chip_batch * n_chips
    log(f"bench: {platform} ({device_kind}) x{n_chips}, global batch {batch}, image {image_size}")

    tuning = load_tuning()
    if tuning:
        log(f"bench: measured-winner tuning from {TUNING_PATH}: {tuning}")
    bn_mode = tuning.get("bn_mode", "exact")
    conv1x1_dot = bool(tuning.get("conv1x1_dot", False))
    remat_policy = tuning.get("remat_policy", "full")
    base_remat = bool(tuning.get("remat", False))

    key = jax.random.PRNGKey(0)
    # OOM ladder: first shrink batch under the tuned config, then fall back
    # to full remat (the most memory-conservative policy — a tuned
    # save_conv keeps activations the last-resort rung must not), deduped
    # so a tuned remat=True doesn't recompile an identical rung.
    attempts = []
    for cand in [(batch, base_remat, remat_policy), (batch // 2, base_remat, remat_policy),
                 (batch // 2, True, "full"), (batch // 4, True, "full")]:
        if cand not in attempts:
            attempts.append(cand)
    step_fn = ts = b = net = None
    used_remat, used_policy = base_remat, remat_policy
    compile_s = 0.0
    for try_batch, remat, policy in attempts:
        try:
            step_fn, ts, b, net = build_train_fixture(
                try_batch, image_size, remat=remat, remat_policy=policy,
                bn_mode=bn_mode, conv1x1_dot=conv1x1_dot)
            t0 = time.perf_counter()
            ts, metrics = step_fn(ts, b, key)
            sync(metrics["loss"])
            compile_s = time.perf_counter() - t0
            batch = try_batch
            used_remat, used_policy = remat, policy
            log(f"batch {batch} remat={remat}/{policy}: compile+first step {compile_s:.1f}s")
            break
        except Exception as e:  # XlaRuntimeError RESOURCE_EXHAUSTED etc.
            if "RESOURCE_EXHAUSTED" not in str(e) and "Out of memory" not in str(e):
                raise
            log(f"batch {try_batch} remat={remat} OOM; falling back")
            # drop the failed attempt's device buffers BEFORE rebuilding, or
            # they stay pinned in HBM and the smaller attempt OOMs too
            step_fn = ts = b = None
    if step_fn is None:
        raise RuntimeError("all batch-size fallbacks exhausted")
    # profile the SAME spec the fixture built (single source for the arch)
    total_macs = profile_network(net, image_size).total_macs

    # warmup
    for _ in range(3):
        ts, metrics = step_fn(ts, b, key)
    sync(metrics["loss"])

    k_dispatch = tuning.get("steps_per_dispatch", 1)  # validated int (load_tuning)
    if k_dispatch > 1:
        # measure the ADOPTED production dispatch mode: k steps per jit call
        # (cli/train.py steps_per_dispatch) — same step math, amortized
        # host-dispatch latency (the delta bench_bn's --dispatch-probe measured)
        from yet_another_mobilenet_series_tpu.parallel.dp import make_grouped_train_step

        gstep = make_grouped_train_step(step_fn, k_dispatch)
        batches = (b,) * k_dispatch
        groups = max(iters // k_dispatch, 1)
        iters = groups * k_dispatch
        ts, mets = gstep(ts, batches, key)  # compile + warm the grouped program
        sync(mets[-1]["loss"])
        t0 = time.perf_counter()
        for _ in range(groups):
            ts, mets = gstep(ts, batches, key)
        loss = sync(mets[-1]["loss"])
    else:
        t0 = time.perf_counter()
        for _ in range(iters):
            ts, metrics = step_fn(ts, b, key)
        loss = sync(metrics["loss"])
    dt = time.perf_counter() - t0

    common = {
        "platform": platform,
        "device_kind": device_kind,
        "n_chips": n_chips,
        "batch_per_chip": batch // n_chips,
        "image_size": image_size,
        "step_config": {
            # used_*, not the tuned request: the OOM ladder may have turned
            # remat on / forced policy to full, and the artifact must
            # describe what actually ran
            "bn_mode": bn_mode, "remat": used_remat, "remat_policy": used_policy,
            "conv1x1_dot": conv1x1_dot, "steps_per_dispatch": k_dispatch,
            "tuning_source": tuning.get("source"),
            # what the process actually ran under (tuned flags arrive via env)
            "xla_flags_env": os.environ.get("XLA_FLAGS", ""),
            "libtpu_init_args_env": os.environ.get("LIBTPU_INIT_ARGS", ""),
        },
        "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "provenance": provenance(),
    }
    if cpu_smoke:
        # no rate, no unit, no MFU: nothing here can pass for a device number
        if not math.isfinite(loss):
            raise FloatingPointError("non-finite loss in the cpu smoke")
        return {"metric": "bench_cpu_control_flow_smoke", "value": None,
                "steps_run": iters + 4, "loss_finite": True, **common}

    img_s = batch * iters / dt
    img_s_chip = img_s / n_chips
    log(f"steady: {dt/iters*1000:.1f} ms/step, {img_s:.0f} img/s total")
    # MFU, both conventions so consumers can't misread which one this is:
    # mfu counts the train step's actual FLOPs (fwd + ~2x for bwd, 2 FLOPs/MAC
    # = 6*MACs); mfu_fwd_only is the 2*MACs variant some checkers use.
    return {
        "metric": "mobilenet_v3_large_train_images_per_sec_per_chip",
        "value": round(img_s_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "vs_baseline_note": VS_BASELINE_NOTE,
        "ms_per_step": round(dt / iters * 1000, 2),
        "compile_and_first_step_s": round(compile_s, 1),
        "model_fwd_macs": total_macs,
        "mfu": round(6 * total_macs * img_s_chip / peak, 4),
        "mfu_formula": "6*fwd_macs*img_s_chip/peak_bf16_flops (train fwd+bwd)",
        "mfu_fwd_only": round(2 * total_macs * img_s_chip / peak, 4),
        "peak_bf16_flops": peak,
        **common,
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    unknown = [a for a in argv if a != "--cpu"]
    if unknown:
        raise SystemExit(f"bench: unknown argument(s) {unknown}; usage: bench.py [--cpu]")
    cpu_smoke = "--cpu" in argv
    # environment first: both are read once, when jax initialises its backend
    if cpu_smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        flags = read_tuning_flags()
        if flags:
            apply_flags_env(os.environ, flags)
            log(f"bench: tuned flags {flags!r} -> env")
    print(json.dumps(measure(cpu_smoke)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
