#!/usr/bin/env python
"""Measured per-block latency table for latency-aware NAS (ROADMAP item 3).

FLOPs is a poor proxy for measured latency (PAPERS.md: FLASH arXiv
2108.00568, LANA arXiv 2107.10624), so this benches every DISTINCT block
configuration of a network — (in/out channels, expanded width, kernel split,
stride, SE, input resolution) — at several expanded-channel width fractions,
through the same AOT path the serving engine uses
(``jit(...).lower().compile()`` via obs/device.timed_compile, so compile
time and cost_analysis flops/bytes are recorded for every entry too), and
writes a ``LATENCY_TABLE_*.json`` artifact. ``nas/latency.py`` loads it and
turns the (alive channels -> seconds) ladders into per-atom marginal-latency
cost vectors; ``prune.cost="latency_table"`` swaps them into the AtomNAS
penalty — the search then optimizes what the serving fleet actually pays.

Artifact contract: exactly ONE JSON line on stdout, optional ``--out``
copy, provenance-stamped (scripts/provenance.py: jax/jaxlib versions,
platform, device kind, cpu-rehearsal flag). It measures
on the chip or fails: with no TPU, or when the measurement raises, it prints
no table and exits non-zero. ``--cpu-rehearsal`` asks, explicitly, for a
table of XLA:CPU timings — it checks the table's plumbing (the checked-in
``LATENCY_TABLE_r01_cpu_rehearsal.json`` is one, stamped ``cpu_rehearsal:
true``) and must not steer a search meant for the chip: a search against it
penalises CPU time (ROADMAP Queue 2 item 6).

Usage: python scripts/latency_table.py [--arch mobilenet_v3_large]
           [--image-sizes 224] [--widths 0.375,0.6875,1.0] [--batch 8]
           [--iters 12] [--out LATENCY_TABLE.json] [--cpu-rehearsal]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _width_variant(spec, width: float):
    """The block at ``width`` x expanded channels (>= one channel per kernel
    branch), channels re-split across kernel branches the same way the
    supernet builder splits them — the shape a width-pruned block actually
    runs at. SE width stays fixed: masking prunes expanded channels, not the
    SE bottleneck (nas/masking.py semantics)."""
    from yet_another_mobilenet_series_tpu.models.specs import _split_groups

    e = max(len(spec.kernel_sizes), int(round(spec.expanded_channels * width)))
    return dataclasses.replace(
        spec, expanded_channels=e, group_channels=_split_groups(e, spec.kernel_sizes),
        force_expand=True,
    )


def bench_block(spec, image_size: int, widths, batch: int, iters: int) -> dict:
    """One table entry: the block's eval forward AOT-compiled and timed at
    each width. Serve-engine idiom — AOT ``lower().compile()`` through
    obs/device.timed_compile (compile + cost accounting recorded per width),
    one untimed page-in, then ``iters`` timed back-to-back runs off one
    device-resident input (no donation: the timed loop reuses the buffer,
    and a per-iter allocation would pollute the device measurement) with one
    hard sync at the end, so the number is steady-state device latency."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.nas.latency import block_key
    from yet_another_mobilenet_series_tpu.obs import device as obs_device

    key = block_key(spec, image_size)
    alive, lat, compile_s, flops = [], [], [], []
    for w in sorted(widths):
        blk = _width_variant(spec, w)
        params, state = blk.init(jax.random.PRNGKey(0))

        def run(p, s, x):
            return blk.apply(p, s, x, train=False)[0]

        x_shape = jax.ShapeDtypeStruct((batch, image_size, image_size, spec.in_channels), jnp.float32)
        t0 = time.perf_counter()
        exe = obs_device.timed_compile(
            jax.jit(run).lower(params, state, x_shape),
            f"latbl_{key}_w{blk.expanded_channels}",
        )
        compile_s.append(round(time.perf_counter() - t0, 4))
        x = jnp.zeros((batch, image_size, image_size, spec.in_channels), jnp.float32)
        exe(params, state, x).block_until_ready()  # untimed page-in
        t0 = time.perf_counter()
        for _ in range(iters):
            y = exe(params, state, x)
        y.block_until_ready()
        lat.append((time.perf_counter() - t0) / (iters * batch))  # s / image
        alive.append(blk.expanded_channels)
        flops.append(obs_device.flops_for(f"latbl_{key}_w{blk.expanded_channels}"))
    return {
        "key": key,
        "in_channels": spec.in_channels,
        "out_channels": spec.out_channels,
        "expanded_channels": spec.expanded_channels,
        "kernel_sizes": list(spec.kernel_sizes),
        "stride": spec.stride,
        "se_channels": spec.se_channels,
        "image_size": image_size,
        "alive_channels": alive,
        "latency_s": [round(v, 9) for v in lat],
        "cost_flops": flops,
        "compile_s": compile_s,
    }


def build_table(net, image_sizes, widths, batch: int, iters: int,
                log=lambda msg: None) -> list[dict]:
    """Every DISTINCT block signature of ``net`` x every image size, deduped
    by table key (repeated stages share one measurement)."""
    from yet_another_mobilenet_series_tpu.nas.latency import block_input_sizes, block_key

    entries: dict[str, dict] = {}
    for image_size in image_sizes:
        sizes = block_input_sizes(net, image_size)
        for i, blk in enumerate(net.blocks):
            key = block_key(blk, sizes[i])
            if key in entries:
                continue
            t0 = time.perf_counter()
            entries[key] = bench_block(blk, sizes[i], widths, batch, iters)
            log(f"[{len(entries)}] {key}: "
                f"{[round(v * 1e6, 1) for v in entries[key]['latency_s']]} µs/img "
                f"({time.perf_counter() - t0:.1f}s)")
    return list(entries.values())


def measure(arch: str, image_sizes, widths, batch: int, iters: int) -> dict:
    import jax

    from yet_another_mobilenet_series_tpu.config import ModelConfig
    from yet_another_mobilenet_series_tpu.models import get_model

    if arch == "tiny":  # contract-test preset: 2 distinct blocks
        mc = ModelConfig(arch="mobilenet_v2", num_classes=8, dropout=0.0,
                         block_specs=[{"t": 2, "c": 8, "n": 1, "s": 2, "k": [3, 5]},
                                      {"t": 2, "c": 16, "n": 1, "s": 2}])
    else:
        mc = ModelConfig(arch=arch)
    base = image_sizes[0]
    net = get_model(mc, base)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    entries = build_table(net, image_sizes, widths, batch, iters, log=log)
    dev = jax.devices()[0]
    return {
        "arch": arch,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "image_sizes": list(image_sizes),
        "widths": list(widths),
        "batch": batch,
        "iters": iters,
        "entries": entries,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="mobilenet_v3_large")
    ap.add_argument("--image-sizes", default="224", help="comma ladder of NETWORK input sizes")
    ap.add_argument("--widths", default="0.375,0.6875,1.0",
                    help="expanded-channel width fractions per block (>=2 for a slope fit)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=12, help="timed runs per (block, width)")
    ap.add_argument("--out", default="", help="also write the JSON artifact here")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="accept a non-TPU backend: a table of XLA:CPU timings that "
                         "checks the plumbing and is stamped cpu_rehearsal")
    args = ap.parse_args(argv)
    widths = tuple(float(w) for w in args.widths.split(","))
    image_sizes = tuple(int(s) for s in args.image_sizes.split(","))
    if len(widths) < 2:
        raise SystemExit("latency_table: need >= 2 widths to fit a latency-vs-channels slope")

    import jax

    from yet_another_mobilenet_series_tpu.utils import compile_cache
    from scripts.provenance import provenance

    compile_cache.configure()
    platform = jax.default_backend()
    if platform != "tpu" and not args.cpu_rehearsal:
        raise SystemExit(f"latency_table: no TPU: jax.default_backend() is {platform!r}; the "
                         "table is measured on the chip (--cpu-rehearsal asks for a CPU one)")

    out = {
        "metric": f"{args.arch}_block_latency_table",
        "value": None,
        "unit": "entries",
        "vs_baseline": None,
        "vs_baseline_note": "a lookup-table artifact, not a throughput headline",
        "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    out.update(measure(args.arch, image_sizes, widths, max(1, args.batch), max(1, args.iters)))
    out["value"] = float(len(out["entries"]))
    out["provenance"] = provenance()
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
