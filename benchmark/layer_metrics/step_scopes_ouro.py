"""The two lm.*.train times of a looped model's cell: device time inside the
compiled train step under the exit gate's scope and under the norm and
residual scopes (yet_another_mobilenet_series_tpu/obs/scopes.py: exit_gate,
norm, residual).

Nothing is measured or compiled here. step_scopes_lm.py computes the whole
scope x phase table of a run once (its `metric` leaves it on
`ctx.step_scopes_lm`, and prints it as the `step_scopes_lm` commentary line);
this reader sums rows of that table, as step_scopes_kda.py does:

- `lm.exit_gate_ms.train`: the gate's projection after every loop step, the
  exit distribution, the expected loss and the entropy term, forward and
  backward;
- `lm.norm_residual_ms.train`: every RMSNorm (four a block application and one
  a loop step: the sandwich) and every residual add: bandwidth-bound work
  beside the matmuls, which none of the six `lm.*` times holds.

Returns None, and the line leaves the metric out, where there is nothing to
read: no table (a CPU rehearsal, no whole step in the stretch, a program
without the token family) or a table without an `exit_gate` row (a program,
or a model, without the loop).
"""

from __future__ import annotations

from benchmark.layer_metrics import step_scopes_lm

# metric -> the scopes it sums, every phase
METRICS = {
    "lm.exit_gate_ms.train": ("exit_gate",),
    "lm.norm_residual_ms.train": ("norm", "residual"),
}


def metric(ctx, name: str):
    if not hasattr(ctx, "step_scopes_lm"):
        step_scopes_lm.metric(ctx, step_scopes_lm.UNSCOPED_SHARE)  # makes the table, on a run's first call, and keeps it
    found = ctx.step_scopes_lm
    if found is None:
        return None
    rows = found["table"]["ms_per_step"]  # {"<scope>.<phase>": ms a step}
    if not any(key.startswith("exit_gate.") for key in rows):
        return None
    return sum(ms for key, ms in rows.items() if key.rsplit(".", 1)[0] in METRICS[name])
