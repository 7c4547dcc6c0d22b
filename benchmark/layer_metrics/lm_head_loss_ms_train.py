"""lm.head_loss_ms.train: see step_scopes_lm.py, which computes every scope metric of a run once."""

from benchmark.layer_metrics import step_scopes_lm


def read(ctx):
    return step_scopes_lm.metric(ctx, "lm.head_loss_ms.train")
