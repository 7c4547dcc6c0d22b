"""lm.attn_window_ms.train: device time a step under the scope `attn_window`
(yet_another_mobilenet_series_tpu/obs/scopes.py): a sliding-window layer's
attention core (the window's two kernels, or the loops with its bounds, and
the key/value heads' repeat before them), forward and backward. The causal
layers' core stays in `lm.attn_core_ms.train`, so the two part the
attention's time by the kind of layer.

Nothing is measured or compiled here: step_scopes_lm.py computes the whole
scope x phase table of a run once (its `metric` leaves it on
`ctx.step_scopes_lm`); this reader sums the `attn_window` rows of that table.

Returns None, and the line leaves the metric out, where there is nothing to
read: no table (a CPU rehearsal, no whole step in the stretch, a program
without the token family) or a table without an `attn_window` row (a
program, or a model, without a sliding window).
"""

from __future__ import annotations

from benchmark.layer_metrics import step_scopes_lm

SCOPE = "attn_window"


def read(ctx):
    if not hasattr(ctx, "step_scopes_lm"):
        step_scopes_lm.metric(ctx, step_scopes_lm.UNSCOPED_SHARE)  # makes the table, on a run's first call, and keeps it
    found = ctx.step_scopes_lm
    if found is None:
        return None
    rows = [ms for key, ms in found["table"]["ms_per_step"].items() if key.rsplit(".", 1)[0] == SCOPE]
    return sum(rows) if rows else None
