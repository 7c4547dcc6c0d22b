#!/usr/bin/env python
"""Input-pipeline throughput benchmark (SURVEY.md §7 hard part 4: host decode
can bottleneck a ≤8h/350-epoch run — 'measure images/sec/chip headroom
early'). Measures images/sec of each available pipeline in isolation (no
device compute), so it can be compared against the benchmark's model-step
images/sec/chip (benchmark/run.py) to see which side bounds a training run.

Usage:
  python scripts/bench_input.py --pipeline fake                 # tf.data synthetic
  python scripts/bench_input.py --pipeline tfrecord --data-dir /data/tfr
  python scripts/bench_input.py --pipeline native --data-dir /data/imagefolder
Prints one JSON line per measured pipeline.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(name: str, it, batch: int, batches: int, warmup: int = 3) -> dict:
    for _ in range(warmup):
        next(it)
    t0 = time.perf_counter()
    for _ in range(batches):
        next(it)
    dt = time.perf_counter() - t0
    out = {"pipeline": name, "images_per_sec": round(batch * batches / dt, 1), "batch": batch, "batches": batches}
    print(json.dumps(out), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pipeline", choices=["fake", "tfrecord", "native"], required=True)
    ap.add_argument("--data-dir", default="")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--threads", type=int, default=os.cpu_count() or 8)
    ap.add_argument("--deterministic", action="store_true",
                    help="tfrecord only: data.deterministic_input=True (record-exact "
                         "resume via single-stream deterministic interleave) — measures "
                         "the throughput price of the production resume-exactness switch")
    ap.add_argument("--transfer-uint8", action="store_true",
                    help="tfrecord/native: data.transfer_uint8=True (u8 on the wire, "
                         "in-step device normalize) — host-side cost/saving of the "
                         "4x transfer-volume lever")
    args = ap.parse_args()
    if args.deterministic and args.pipeline != "tfrecord":
        ap.error("--deterministic only applies to --pipeline tfrecord")
    if args.transfer_uint8 and args.pipeline == "fake":
        ap.error("--transfer-uint8 needs a real-JPEG pipeline (tfrecord or native)")

    from yet_another_mobilenet_series_tpu.config import DataConfig
    from yet_another_mobilenet_series_tpu.data import make_train_source

    if args.pipeline == "fake":
        cfg = DataConfig(dataset="fake", image_size=args.image_size, fake_num_classes=1000,
                         fake_train_size=max(args.batch * 4, 1024))
    elif args.pipeline == "tfrecord":
        cfg = DataConfig(dataset="imagenet", data_dir=args.data_dir, image_size=args.image_size,
                         decode_threads=args.threads,
                         deterministic_input=args.deterministic,
                         transfer_uint8=args.transfer_uint8)
    else:
        cfg = DataConfig(dataset="folder", loader="native", data_dir=args.data_dir,
                         image_size=args.image_size, decode_threads=args.threads,
                         transfer_uint8=args.transfer_uint8)
    it = make_train_source(cfg, args.batch, seed=0)
    name = (args.pipeline + ("+deterministic" if args.deterministic else "")
            + ("+uint8" if args.transfer_uint8 else ""))
    measure(name, it, args.batch, args.batches)


if __name__ == "__main__":
    main()
