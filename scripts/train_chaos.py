#!/usr/bin/env python
"""Training chaos round: corrupt records + an injected NaN step + a SIGTERM
preemption, then a resume — the serve_bench chaos A/B's training twin.

Prints exactly ONE JSON line on stdout in serve_bench.py's artifact shape (a
failed round emits ``value: null`` with an ``error`` field and exits
non-zero) and optionally writes it via --out. A drill of what the program
COUNTS, not a device measurement: the children are held to the CPU backend
(8 host devices) on any host and this parent never imports jax, so it needs
no chip and takes none. Two rounds, both SUBPROCESSES of cli.train on the
tiny fake-data config so the artifact reflects the real entry point end to end:

1. **chaos round** — ``train.faults`` injects a seeded corrupt-record rate
   (the resilience wrapper must skip and count them), one NaN step (the
   train.guard rollback must skip and count it), and ``kill_at_step`` sends
   the process a real SIGTERM mid-epoch. The process must exit 0 after a
   final SYNCHRONOUS checkpoint, leaving ``preempt_marker.json`` and its
   registry counters in ``obs_registry.json``.
2. **resume round** — the same config with faults off resumes
   (``train.resume`` default) from the marker's step — NOT from zero — and
   trains to completion; the artifact records the killed/resumed steps and
   the loss on both sides of the kill so trajectory continuity is auditable.

The headline ``value`` is the resumed-run step count recovered past the kill
point — > 0 is the survival claim.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _child_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(flags + ["--xla_force_host_platform_device_count=8"])
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    return env


def _base_overrides(log_dir: str) -> list[str]:
    return [
        "data.dataset=fake", "data.image_size=16", "data.fake_train_size=256",
        "data.fake_eval_size=32", "data.fake_num_classes=4",
        "model.arch=mobilenet_v2", "model.num_classes=4", "model.dropout=0.0",
        "model.block_specs=[{t: 2, c: 8, n: 1, s: 2}]",
        "optim.optimizer=sgd", "optim.momentum=0.9", "optim.weight_decay=0.0",
        "schedule.schedule=constant", "schedule.base_lr=0.05",
        "schedule.scale_by_batch=false", "schedule.warmup_epochs=0.0",
        "ema.enable=false",
        "train.batch_size=16", "train.eval_batch_size=16", "train.epochs=2",
        "train.log_every=2", "train.compute_dtype=float32",
        "train.eval_every_epochs=0", "train.checkpoint_every_epochs=1",
        f"train.log_dir={log_dir}",
        "train.guard.enable=true", "train.guard.max_skipped_steps=4",
        "dist.num_devices=8",
    ]


def _run_child(overrides: list[str], timeout_s: float) -> subprocess.CompletedProcess:
    cmd = [sys.executable, "-m", "yet_another_mobilenet_series_tpu.cli.train"] + overrides
    return subprocess.run(cmd, capture_output=True, text=True, timeout=timeout_s,
                          cwd=REPO, env=_child_env())


def _losses(log_dir: str) -> list[tuple[int, float]]:
    out = []
    try:
        with open(os.path.join(log_dir, "metrics.jsonl")) as f:
            for line in f:
                row = json.loads(line)
                if "train/loss" in row:
                    out.append((int(row["step"]), float(row["train/loss"])))
    except (OSError, ValueError):
        pass
    return out


def _registry(log_dir: str) -> dict:
    try:
        with open(os.path.join(log_dir, "obs_registry.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def run_chaos(log_dir: str, timeout_s: float) -> dict:
    # steps/epoch = 256/16 = 16; kill mid-epoch-1 (the injector indexes
    # PULLS, which lead the step loop by the prefetch depth)
    chaos_over = _base_overrides(log_dir) + [
        "train.faults.enable=true", "train.faults.seed=7",
        "train.faults.corrupt_record_rate=0.08",
        "train.faults.nan_at_steps=[5]",
        "train.faults.kill_at_step=10",
    ]
    proc = _run_child(chaos_over, timeout_s)
    detail: dict = {"exit_code": proc.returncode}
    if proc.returncode != 0:
        raise RuntimeError(
            f"chaos round exited {proc.returncode}: {proc.stderr[-800:]}")
    marker_path = os.path.join(log_dir, "preempt_marker.json")
    if not os.path.exists(marker_path):
        raise RuntimeError("chaos round left no preempt_marker.json "
                           f"(stdout tail: {proc.stdout[-400:]})")
    with open(marker_path) as f:
        marker = json.load(f)
    reg = _registry(log_dir)
    losses = _losses(log_dir)
    detail.update(
        killed_step=int(marker["step"]),
        reason=marker.get("reason"),
        corrupt_records=reg.get("data.corrupt_records", 0),
        injected_corrupt=reg.get("train.faults.corrupt_records", 0),
        injected_nan_steps=reg.get("train.faults.nan_steps", 0),
        skipped_steps=reg.get("train.skipped_steps", 0),
        nonfinite_events=reg.get("train.nonfinite_events", 0),
        preemptions=reg.get("train.preemptions", 0),
        loss_before_kill=losses[-1][1] if losses else None,
        health_abort=os.path.exists(os.path.join(log_dir, "train_health.json")),
    )
    return detail


def run_resume(log_dir: str, killed_step: int, timeout_s: float) -> dict:
    proc = _run_child(_base_overrides(log_dir), timeout_s)
    if proc.returncode != 0:
        raise RuntimeError(
            f"resume round exited {proc.returncode}: {proc.stderr[-800:]}")
    m = re.search(r"resumed at step (\d+)", proc.stdout)
    if not m:
        raise RuntimeError("resume round never resumed "
                           f"(stdout tail: {proc.stdout[-400:]})")
    resumed_step = int(m.group(1))
    losses = _losses(log_dir)
    after = [l for s, l in losses if s > killed_step]
    reg = _registry(log_dir)
    return {
        "exit_code": proc.returncode,
        "resumed_step": resumed_step,
        "marker_consumed": not os.path.exists(os.path.join(log_dir, "preempt_marker.json")),
        "final_step": losses[-1][0] if losses else None,
        "loss_after_resume": after[0] if after else None,
        "final_loss": after[-1] if after else None,
        "restore_fallbacks": reg.get("ckpt.restore_fallbacks", 0),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="")
    ap.add_argument("--log-dir", default="", help="default: a fresh temp dir")
    ap.add_argument("--timeout-s", type=float, default=240.0, help="per child run")
    args = ap.parse_args(argv)

    if args.log_dir:
        log_dir = args.log_dir
        os.makedirs(log_dir, exist_ok=True)
    else:
        import tempfile

        log_dir = tempfile.mkdtemp(prefix="yamt_train_chaos_")

    from scripts.provenance import provenance

    artifact = {
        "metric": "train_chaos_recovered_steps",
        "value": None,
        "unit": "steps",
        "vs_baseline": None,
        "platform": "cpu",
        "log_dir": log_dir,
        # shared provenance stamp (scripts/provenance.py). cpu_rehearsal is pinned:
        # the children run under JAX_PLATFORMS=cpu and this parent process
        # never imports jax, so the stamp cannot infer it
        "provenance": provenance(cpu_rehearsal=True),
    }
    try:
        chaos = run_chaos(log_dir, args.timeout_s)
        resume = run_resume(log_dir, chaos["killed_step"], args.timeout_s)
        artifact["chaos"] = chaos
        artifact["resume"] = resume
        if resume["final_step"] is not None:
            artifact["value"] = float(resume["final_step"] - resume["resumed_step"])
    except (RuntimeError, subprocess.TimeoutExpired, OSError, ValueError) as e:
        artifact["error"] = f"{type(e).__name__}: {e}"

    line = json.dumps(artifact)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 1 if "error" in artifact else 0


if __name__ == "__main__":
    # the artifact contract: a SIGTERM'd driver still gets the artifact
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    sys.exit(main())
