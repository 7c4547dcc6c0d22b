"""A/B the BatchNorm normalize variants (train.bn_mode) on the full
MobileNetV3-L train step — the attack on the 52% BN-stat-reduction share of
the pre-PR-1 TPU trace (ROADMAP.md, Queue 1 item 2).

Variants (ops/layers.py BatchNorm.apply):
  exact   — f32 (x - mean)*scale + beta, the reference-parity baseline
  folded  — precomputed f32 per-channel scale/bias, single FMA
  compute — scale/bias cast to the compute dtype, FMA fully in bf16
each optionally under train.remat (activation rematerialization), which
changes what XLA materializes between the forward stat-reduces and the
backward companions.

Measurement methodology: iterations are naturally chained (TrainState
threads through), and every timed region ends with a device_get of a scalar
that depends on the work (utils/benchkit.sync) — dispatch is asynchronous,
so a region that does not end in such a read times the enqueue.

Measures on the chip or fails: with no TPU, or when a variant or the
dispatch probe raises, it exits non-zero (the rows measured before the
failure are kept in --out).

Usage: python scripts/bench_bn.py [--batch 256] [--iters 20] [--out FILE]
Prints one JSON line to stdout; table to stderr.

--xla-flags-sweep (VERDICT r3 #7): instead of the variant A/B, re-time ONE
variant (the BENCH_TUNING.json winner, else exact:0) under each entry of a
curated XLA/libtpu flag list, one subprocess per flag set (flags must be in
the env before any backend touch). Generic --xla_* tokens go to XLA_FLAGS;
--xla_tpu_* tokens go to LIBTPU_INIT_ARGS (the host XLA build aborts on
them — train/tuning.partition_flags documents the probe). A flag set the child
aborts on is recorded as an error row, not a sweep failure; a failed
BASELINE (no-flags) row fails the sweep, since nothing can be compared
without it. The parent never touches a backend — each child needs the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def log(msg):
    print(msg, file=sys.stderr, flush=True)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Curated single-chip flag sets (the post-A/B lever). "" is the mandatory
# baseline. scoped_vmem sizes the
# fusion vmem budget (larger => bigger fusions around the BN reduces);
# latency_hiding_scheduler=false and rwb_fusion=false toggle the two
# schedule/fusion passes most likely to interact with the reduce-dominated
# profile (ROADMAP.md's table of pre-PR-1 chip numbers).
DEFAULT_FLAG_SETS = (
    ";--xla_tpu_scoped_vmem_limit_kib=65536"
    ";--xla_tpu_scoped_vmem_limit_kib=98304"
    ";--xla_tpu_enable_latency_hiding_scheduler=false"
    ";--xla_tpu_rwb_fusion=false"
)


def _variant_token_from_tuning() -> str:
    """BENCH_TUNING.json winner as a --variants token, else the baseline."""
    from bench import TUNING_PATH  # single source for the tuning-file path

    try:
        with open(TUNING_PATH) as f:
            raw = json.load(f)
        mode = raw.get("bn_mode", "exact")
        if raw.get("remat", False):
            remat_tok = "save_conv" if raw.get("remat_policy") == "save_conv" else "full"
        else:
            remat_tok = "0"
        return f"{mode}:{remat_tok}" + (":dot" if raw.get("conv1x1_dot") else "")
    except (OSError, json.JSONDecodeError, AttributeError, TypeError):
        return "exact:0"


def run_sweep(args) -> int:
    """Supervisor for the flag sweep: one child bench_bn per flag set.

    Children time the single tuned variant; rows persist incrementally (a
    sweep that dies half way keeps its completed rows). This process never
    touches a backend itself: a chip belongs to one process at a time, and
    each child needs it. Returns the exit code: non-zero when the no-flags
    baseline could not be measured."""
    from bench import apply_flags_env

    token = _variant_token_from_tuning()
    flag_sets = [s.strip() for s in args.flag_sets.split(";")]
    if "" in flag_sets:
        flag_sets.insert(0, flag_sets.pop(flag_sets.index("")))
    else:
        flag_sets.insert(0, "")  # baseline is mandatory: vs_noflags needs it
    log(f"sweep: variant {token!r}, {len(flag_sets)} flag sets")

    rows = []
    def emit(partial: bool):
        base = next((r for r in rows if r["flags"] == "" and "ms_per_step" in r), None)
        for r in rows:
            if base and "ms_per_step" in r:
                r["vs_noflags"] = round(base["ms_per_step"] / r["ms_per_step"], 3)
        out = {
            "bench": "xla_flags_sweep", "variant": token,
            "batch": args.batch, "image_size": args.image_size, "iters": args.iters,
            "flag_sets_completed": len(rows), "flag_sets_planned": len(flag_sets),
            "partial": partial, "rows": rows,
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        return out

    tmp_out = (args.out or os.path.join(REPO, "BENCH_XLA.json")) + ".child"
    for fs in flag_sets:
        try:
            env = apply_flags_env(os.environ.copy(), fs)
        except ValueError as e:  # malformed token: error row, not a sweep abort
            rows.append({"flags": fs, "error": str(e)})
            emit(partial=True)
            continue
        cmd = [sys.executable, os.path.abspath(__file__), "--variants", token,
               "--batch", str(args.batch), "--iters", str(args.iters),
               "--image-size", str(args.image_size), "--out", tmp_out,
               # label the child artifact as what it IS: a flag-set child of
               # the XLA sweep, not a variant A/B — tooling that globs
               # BENCH_*.json must not misparse a leftover intermediate
               # (ADVICE r5 low)
               "--bench-label", "xla_flags_sweep_child"]
        log(f"sweep: flags {fs!r} starting")
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.child_timeout, cwd=REPO, env=env)
        except subprocess.TimeoutExpired:
            # a hung child here means the window died; keep what we have
            rows.append({"flags": fs, "error": f"child timed out after {args.child_timeout}s"})
            emit(partial=True)
            continue
        row = None
        if r.returncode == 0:
            try:
                with open(tmp_out) as f:
                    child = json.load(f)
                if child.get("partial") is False and child["rows"]:
                    c = child["rows"][0]
                    row = {"flags": fs, "platform": child.get("platform"),
                           "batch": child.get("batch"), "image_size": child.get("image_size"),
                           "ms_per_step": c["ms_per_step"],
                           "img_s_per_chip": c["img_s_per_chip"],
                           "compile_s": c["compile_s"], "loss": c["loss"]}
            except (OSError, json.JSONDecodeError, KeyError, IndexError):
                pass
        if row is None:
            # unknown-flag aborts land here (fast fatal before any backend
            # retry), alongside genuine child failures — keep the evidence
            row = {"flags": fs, "error": f"child rc={r.returncode}: {r.stderr[-300:]}"}
            log(f"sweep: flags {fs!r} FAILED rc={r.returncode}")
        else:
            log(f"sweep: flags {fs!r}: {row['ms_per_step']} ms/step")
        rows.append(row)
        emit(partial=True)
    try:
        os.remove(tmp_out)
    except FileNotFoundError:
        pass
    print(json.dumps(emit(partial=False)), flush=True)
    if "ms_per_step" not in rows[0]:
        log(f"sweep: the no-flags baseline failed: {rows[0].get('error')}")
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--out", default="")
    ap.add_argument("--dispatch-probe", action="store_true",
                    help="after the variants, time the exact/no-remat config "
                         "both as chained per-step dispatches and as ONE "
                         "lax.scan(iters) dispatch; the delta is the per-step "
                         "host-dispatch latency the chained methodology "
                         "includes and the MFU math should know about")
    ap.add_argument("--xla-flags-sweep", action="store_true",
                    help="sweep --flag-sets over the BENCH_TUNING.json winner "
                         "(one child process per flag set) instead of the variant A/B")
    ap.add_argument("--flag-sets", default=DEFAULT_FLAG_SETS,
                    help="semicolon-separated flag strings for --xla-flags-sweep; "
                         "'' (the no-flags baseline) is always run first")
    ap.add_argument("--child-timeout", type=int, default=1500,
                    help="per-flag-set child budget in --xla-flags-sweep")
    ap.add_argument("--bench-label", default="bn_mode_train_step_ab",
                    help="'bench' field written into the artifact; the sweep "
                         "supervisor sets xla_flags_sweep_child on its children "
                         "so intermediates can't be mistaken for a variant A/B")
    ap.add_argument(
        "--variants",
        default="exact:0,folded:0,compute:0,fused_vjp:0,sdot:0,compute_sdot:0,exact:full,exact:save_conv,compute:save_conv,exact:0:dot,sdot:0:dot",
        help="comma list of bn_mode:remat[:dot] where remat is 0 (off), "
             "1/full (jax.checkpoint), or save_conv (keep MXU outputs, "
             "recompute BN/act chains); a trailing ':dot' lowers 1x1 convs "
             "as explicit matmuls (train.conv1x1_dot)",
    )
    args = ap.parse_args()

    if args.xla_flags_sweep:
        # supervisor mode: children own every backend touch
        return run_sweep(args)

    # all tokens validated before ANY backend touch or variant run — a typo
    # must fail in milliseconds, not mid-sweep in a budgeted chip call
    from yet_another_mobilenet_series_tpu.ops.layers import BN_MODES

    variants = []
    for spec_str in args.variants.split(","):
        parts = spec_str.strip().split(":")
        if len(parts) < 2:
            raise SystemExit(f"malformed variant {spec_str.strip()!r} (expected bn_mode:remat[:dot])")
        mode, remat_s = parts[0], parts[1]
        extra = parts[2:]
        if mode not in BN_MODES:
            raise SystemExit(f"unknown bn_mode token {mode!r} in --variants (valid: {BN_MODES})")
        if remat_s not in ("0", "1", "full", "save_conv"):
            raise SystemExit(f"unknown remat token {remat_s!r} in --variants (use 0, 1, full, or save_conv)")
        if extra not in ([], ["dot"]):
            raise SystemExit(f"unknown trailing token(s) {extra!r} in --variants (only ':dot' is valid)")
        variants.append((mode, remat_s != "0", remat_s if remat_s == "save_conv" else "full", bool(extra)))

    if args.out:
        # writability must fail in milliseconds too, not after the first
        # variant ("a": never truncates a previous partial artifact)
        open(args.out, "a").close()

    from yet_another_mobilenet_series_tpu.utils import compile_cache
    from yet_another_mobilenet_series_tpu.utils.benchkit import build_train_fixture, sync

    compile_cache.configure()
    platform = jax.default_backend()
    kind = jax.devices()[0].device_kind
    if platform != "tpu":
        raise SystemExit(f"bench_bn: no TPU: jax.default_backend() is {platform!r} ({kind}); "
                         "this A/B measures on the chip or fails")
    log(f"bench_bn: {platform} ({kind}), batch {args.batch}, image {args.image_size}, {args.iters} iters")

    key = jax.random.PRNGKey(0)
    rows = []
    def emit(partial: bool):
        """Persist what's measured SO FAR: a crash mid-sweep must not
        discard completed rows."""
        base = next(
            (r for r in rows if r["bn_mode"] == "exact" and r["remat"] == "off" and not r["conv1x1_dot"]),
            None,
        )
        for r in rows:
            if base:
                r["vs_exact"] = round(base["ms_per_step"] / r["ms_per_step"], 3)
        out = {
            "bench": args.bench_label, "platform": platform, "device_kind": kind,
            "batch": args.batch, "image_size": args.image_size, "iters": args.iters,
            "dtype": "bfloat16",
            "variants_completed": len(rows),
            "variants_planned": len(variants) + (1 if args.dispatch_probe else 0),
            "partial": partial,
            "method": "chained train steps, device_get(loss) barrier (utils/benchkit.sync)",
            "rows": rows,
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        return out

    for mode, remat, policy, dot in variants:
        step_fn, ts, b, _ = build_train_fixture(
            args.batch, args.image_size, remat=remat, remat_policy=policy, bn_mode=mode,
            conv1x1_dot=dot,
        )
        t0 = time.perf_counter()
        ts, metrics = step_fn(ts, b, key)
        sync(metrics["loss"])
        compile_s = time.perf_counter() - t0
        for _ in range(3):
            ts, metrics = step_fn(ts, b, key)
        sync(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(args.iters):
            ts, metrics = step_fn(ts, b, key)
        loss = sync(metrics["loss"])
        dt = (time.perf_counter() - t0) / args.iters
        img_s = args.batch / dt
        remat_label = "off" if not remat else policy
        rows.append({
            "bn_mode": mode, "remat": remat_label, "conv1x1_dot": dot,
            "ms_per_step": round(dt * 1e3, 2),
            "img_s_per_chip": round(img_s / len(jax.devices()), 1),
            "compile_s": round(compile_s, 1), "loss": round(loss, 4),
        })
        log(f"  bn_mode={mode:<8} remat={remat_label:<9} dot={int(dot)}: {dt*1e3:8.2f} ms/step, "
            f"{img_s:8.0f} img/s, loss {loss:.4f} (compile {compile_s:.0f}s)")
        if len(rows) < len(variants):
            emit(partial=True)
        # free the variant's buffers before building the next one
        step_fn = ts = b = None

    # secure the complete A/B artifact BEFORE the diagnostic probe: a probe
    # failure (OOM from the un-donated scan state) must not void the
    # measured variants — they are on disk before it raises
    emit(partial=False)
    if args.dispatch_probe:
        rows.append(_dispatch_probe(args, build_train_fixture, sync))

    print(json.dumps(emit(partial=False)), flush=True)
    return 0


def _dispatch_probe(args, build_train_fixture, sync):
    """One scan-of-steps dispatch vs per-step chained dispatches, same
    exact/no-remat config. The scan number is device-only time; chained −
    scan ≈ the per-step host-dispatch overhead baked into every chained
    measurement (and into the headline MFU denominator). The row's bn_mode
    is deliberately NOT a valid mode token so nothing that adopts a winner
    from these rows can pick it."""
    import jax
    from jax import lax

    key = jax.random.PRNGKey(0)
    step_fn, ts, b, _ = build_train_fixture(args.batch, args.image_size)

    def scan_n(ts, b, rng):
        def body(carry, _):
            new_ts, metrics = step_fn(carry, b, rng)  # jitted fn inlines under trace
            return new_ts, metrics["loss"]
        return lax.scan(body, ts, None, length=args.iters)

    # scan FIRST: step_fn donates its TrainState argument, so the chained
    # loop must only run once the scan is done with `ts` (scan_jit itself
    # does not donate; the inlined step's donation is ignored under trace)
    scan_jit = jax.jit(scan_n)
    ts2, losses = scan_jit(ts, b, key)  # compile + first scan
    sync(losses[-1])
    t0 = time.perf_counter()
    ts2, losses = scan_jit(ts2, b, key)
    loss = sync(losses[-1])
    ms_scan = (time.perf_counter() - t0) / args.iters * 1e3

    # chained baseline (same methodology as the variant rows, INCLUDING the
    # 3-step warmup — first post-compile steps run slow, and an unwarmed
    # chained number would inflate the dispatch tax the probe exists to
    # measure)
    ts1, metrics = step_fn(ts, b, key)
    sync(metrics["loss"])
    for _ in range(3):
        ts1, metrics = step_fn(ts1, b, key)
    sync(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(args.iters):
        ts1, metrics = step_fn(ts1, b, key)
    sync(metrics["loss"])
    ms_chain = (time.perf_counter() - t0) / args.iters * 1e3
    log(f"  dispatch probe: chained {ms_chain:.2f} ms/step vs scan {ms_scan:.2f} ms/step "
        f"-> {ms_chain - ms_scan:+.2f} ms/step dispatch tax")
    return {
        "bn_mode": f"exact[scan{args.iters}]", "remat": "off", "conv1x1_dot": False,
        "ms_per_step": round(ms_scan, 2), "ms_per_step_chained": round(ms_chain, 2),
        "dispatch_tax_ms": round(ms_chain - ms_scan, 2), "loss": round(loss, 4),
        "img_s_per_chip": round(args.batch / ms_scan * 1e3 / len(jax.devices()), 1),
        "note": "scan row is device-only time; not an adoptable variant",
    }


if __name__ == "__main__":
    sys.exit(main())
