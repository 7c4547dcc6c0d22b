"""ssd.conv_hbm_roofline_pct.train: the Mamba-2 xBC convolution kernels' share
of the HBM roofline, in percent: the bytes their calls must move
(benchmark/roofline_ssd.py, from the cell's shapes) over their device time x
the chip's published HBM bandwidth (benchmark/peaks.json).

The calls and their time are the traced stretch's own: device 0's
synchronous ops inside whole executions of the step (step_scopes_train.py
`step_ops`) whose instruction is `ssd_conv_fwd.N` or `ssd_conv_bwd.N` (the
kernels' names: ops/lm_kda_kernels.py names a Mamba-2 site's pair after its
scope). Each call counts its own bytes, so the share holds whatever number of
calls a step makes (the layer checkpoint's second forward included).

Returns None, and the line leaves the metric out, where there is nothing to
read: no device trace (a CPU rehearsal), no whole step in the stretch, or no
such kernel in it (a program or a model without them).
"""

from __future__ import annotations

from benchmark import harness, roofline_ssd
from benchmark.layer_metrics import step_scopes_train

KERNELS = {"fwd": "ssd_conv_fwd", "bwd": "ssd_conv_bwd"}


def hbm_bytes_per_s(device_kind: str) -> float:
    for chip in harness.load_json("benchmark/peaks.json")["chips"]:
        if any(s in device_kind.lower() for s in chip["device_kind_contains"]):
            return float(chip["hbm_bytes_per_s"])
    raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json: add its published "
                   "per-chip peaks, with the source, before reporting a roofline share on it")


def share(ops, batch: int, seq: int, channels: int, taps: int, bandwidth: float) -> float | None:
    """Percent of the roofline over (instruction, duration_ns) ops; None without a kernel among them."""
    need = roofline_ssd.conv_bytes(batch, seq, channels, taps)
    moved = spent_ns = 0.0
    for name, duration in ops:
        for phase, kernel in KERNELS.items():
            if name == kernel or name.startswith(kernel + "."):
                moved += need[phase]
                spent_ns += duration
    return 100.0 * moved / (spent_ns * 1e-9 * bandwidth) if spent_ns > 0 else None


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    found = step_scopes_train.step_ops(ctx.trace)
    if found is None:
        return None
    config = ctx.config
    channels = config["mamba_n_heads"] * config["mamba_d_head"] + 2 * config["mamba_d_state"]  # x, ONE group of B and C
    batch = int(ctx.traffic["sequences_per_chip"])
    return share(found[0], batch, int(ctx.traffic["seq_len"]), channels, config["mamba_d_conv"],
                 hbm_bytes_per_s(ctx.devices[0].device_kind))
