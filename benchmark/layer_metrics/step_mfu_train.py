"""step.mfu.train: model FLOP/s utilisation of the train step, in percent of
the chip's published bf16 peak: forward MACs per image (macs.py) x 6 x images
per second per chip / peak (peaks.json). An end-to-end utilisation: it says
nothing about one kernel's roofline share or about idle time."""

from __future__ import annotations

from benchmark import harness, macs


def peak_bf16_flops(device_kind: str) -> float:
    for chip in harness.load_json("benchmark/peaks.json")["chips"]:
        if any(s in device_kind.lower() for s in chip["device_kind_contains"]):
            return float(chip["bf16_flops_per_s"])
    raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json: add its published "
                   "per-chip peaks, with the source, before reporting a utilisation on it")


def read(ctx):
    if ctx.devices[0].platform != "tpu":
        return None  # a CPU rehearsal has no peak to be a share of
    facts = ctx.result["facts"]
    flops_per_s = macs.TRAIN_FLOPS_PER_MAC * facts["macs_per_image"] * facts["images_per_s_per_chip"]
    return 100.0 * flops_per_s / peak_bf16_flops(ctx.devices[0].device_kind)
