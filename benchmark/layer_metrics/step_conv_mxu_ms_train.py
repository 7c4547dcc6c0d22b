"""step.conv_mxu_ms.train: see step_scopes_train.py, which computes every scope metric of a run once."""

from benchmark.layer_metrics import step_scopes_train


def read(ctx):
    return step_scopes_train.metric(ctx, "step.conv_mxu_ms.train")
