"""attn.window_mxu_roofline_pct.train: the sliding window's attention kernels'
share of the MXU roofline, in percent: the FLOPs their calls must do
(benchmark/roofline_window.py, from the cell's shapes: only the pairs the
window admits) over their device time x the chip's published bf16 peak
(benchmark/peaks.json).

The calls and their time are the traced stretch's own: device 0's
synchronous ops inside whole executions of the step (step_scopes_train.py
`step_ops`) whose instruction is `window_attention_fwd.N` or
`window_attention_bwd.N` (the kernels' names, ops/lm_attention_kernels.py).
Each call counts its own FLOPs, so the share holds whatever number of calls
a step makes. A call is one sliding layer, all its heads: the heads are the
configuration's `num_attention_heads_per_layer` of its sliding layers, one
count for all of them (a configuration whose sliding layers differ in heads
is not read).

Returns None, and the line leaves the metric out, where there is nothing to
read: no device trace (a CPU rehearsal), no whole step in the stretch, no
sliding layer in the configuration, or no such kernel in the stretch (a
program or a model without them).
"""

from __future__ import annotations

from benchmark import roofline_window
from benchmark.layer_metrics import step_mfu_train, step_scopes_train

KERNELS = {"fwd": "window_attention_fwd", "bwd": "window_attention_bwd"}


def share(ops, batch: int, heads: int, seq: int, head_dim: int, window: int, peak: float) -> float | None:
    """Percent of the roofline over (instruction, duration_ns) ops; None without a kernel among them."""
    need = roofline_window.window_flops(batch, heads, seq, head_dim, window)
    done = spent_ns = 0.0
    for name, duration in ops:
        for phase, kernel in KERNELS.items():
            if name == kernel or name.startswith(kernel + "."):
                done += need[phase]
                spent_ns += duration
    return 100.0 * done / (spent_ns * 1e-9 * peak) if spent_ns > 0 else None


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    config = ctx.config
    heads = {n for n, kind in zip(config.get("num_attention_heads_per_layer", []), config.get("layer_types", []))
             if kind == "sliding_attention"}
    if len(heads) != 1 or not config.get("sliding_window"):
        return None
    found = step_scopes_train.step_ops(ctx.trace)
    if found is None:
        return None
    return share(found[0], int(ctx.traffic["sequences_per_chip"]), heads.pop(), int(ctx.traffic["seq_len"]),
                 config["head_dim"], config["sliding_window"], step_mfu_train.peak_bf16_flops(ctx.devices[0].device_kind))
