"""The step.*.train metrics that read the program's named scopes
(yet_another_mobilenet_series_tpu/obs/scopes.py): device time INSIDE the
compiled train step by what the model's code calls the work (BN statistics,
depthwise convs, the MXU convs, the update), not by XLA's fusion numbers.

Device 0, whole executions of the step inside the traced stretch only: the
summed duration of every synchronous op on the `XLA Ops` line whose HLO
instruction resolves to the scope, divided by the number of whole steps.
`step.unscoped_share.train` is the share of that op time which resolved to no
listed scope (compiler-made copies, the rng fold, and ALL of it where the
executable carries no names: one read from a compile cache an older checkout
filled), so a silent mis-read is itself a number.

The join is by instruction name. A device event's name is the instruction's
HLO text (`%convert_reduce_fusion.15 = ... fusion(...)`); the instruction ->
(scope, phase) table comes from the step lowered and compiled AGAIN here,
after the window, from the cell's configuration through the program's own
constructors exactly as runners/train_resident.py builds it (the runner keeps
its `Compiled` to itself): one trace and one read from the compile cache, in
`--trace 1` runs only, outside the window and `setup_s`.

Computed once a run and kept on the context; each metric's own module
(`step_bn_fwd_ms_train.py`, ...) is three lines that ask for its name. The
whole table (every scope x phase, ms a step and share; and, because XLA:TPU
fuses a convolution with the BatchNorm sums around it and names the fusion
after the convolution, the time of the ops that CONTAIN each scope's
reductions) goes out on an earlier stdout line as commentary, like
`setup_phases`.

Returns None, and the line leaves the metric out, where there is nothing to
read: no device plane (a CPU rehearsal), no whole step in the stretch, or a
program without obs/scopes.py (the parent commit of the PR that added it).
"""

from __future__ import annotations

import time

from benchmark import harness, trace_reduce

# metric -> (scopes, phases it sums; None = every phase)
METRICS = {
    "step.bn_fwd_ms.train": (("bn_stats", "bn_apply"), ("fwd",)),
    "step.bn_bwd_ms.train": (("bn_stats", "bn_apply"), ("bwd",)),
    "step.conv_dw_ms.train": (("conv_dw",), None),
    "step.conv_mxu_ms.train": (("conv_pw", "conv_full", "dense"), None),
    "step.update_ms.train": (("loss", "optim", "ema", "nas_penalty"), None),
}
UNSCOPED_SHARE = "step.unscoped_share.train"


def step_ops(trace, device: int = 0):
    """((instruction name, duration_ns) of every synchronous op inside the
    whole executions of the step on one device, number of those executions,
    busy_s of the device over the same stretch); None without one."""
    runs = trace_reduce._program_runs(trace, device, None, whole=True)
    if not runs:
        return None
    span = min(s for _, s, _ in runs), max(s + d for _, s, d in runs)
    ops = [e for e in trace_reduce.clip(trace.devices[device].get(trace_reduce.OPS_LINE, []), span)
           if not trace_reduce.is_async_start(e[0])]
    return [(trace_reduce.op_name(n), d) for n, _, d in ops], len(runs), trace_reduce.busy_s(ops)


def compiled_step_text(ctx) -> str:
    """The cell's train step, built as runners/train_resident.py builds it
    (same configuration, mesh, shardings and argument shapes, so the same
    program: a read from the compile cache), as compiled HLO text."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    config, chips = ctx.config, ctx.chips
    batch = int(config["per_chip_batch"]) * chips
    image_size = int(config["image_size"])
    overrides = {"train.batch_size": batch, "dist.num_devices": chips, **config.get("overrides", {})}
    cfg = harness.load_app_config(config["train_app"], overrides)
    net = get_model(cfg.model, image_size)
    mesh = mesh_lib.make_mesh(chips, devices=ctx.devices)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, batch, max(cfg.data.num_train_examples // batch, 1),
                                       cfg.train.epochs)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step_fn = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh, params_example=params_example)

    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
    key = harness.seed_key(ctx.seed)
    # shapes only: nothing is initialised or placed a second time
    ts = jax.jit(lambda k: steps.init_train_state(net, cfg, optimizer, harness.init_key(k)),
                 out_shardings=replicated).eval_shape(key)
    b = {"image": jax.ShapeDtypeStruct((batch, image_size, image_size, 3), jnp.float32, sharding=sharded),
         "label": jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sharded)}
    rng = jax.random.fold_in(jnp.asarray(key), 2)
    return step_fn.lower(ts, b, rng).compile().as_text()


def compute(ctx) -> dict | None:
    """{"metrics": {name: value}, "table": commentary} for this run, or None."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    try:
        from yet_another_mobilenet_series_tpu.obs import scopes
    except ImportError:
        return None  # a program from before the scopes: nothing to read
    found = step_ops(ctx.trace)
    if found is None:
        return None
    ops, n_steps, busy_s = found
    t0 = time.perf_counter()
    parsed = scopes.parse_hlo(compiled_step_text(ctx))
    table = scopes.scope_table(parsed)
    table_s = time.perf_counter() - t0
    by = scopes.time_by_scope(ops, table)
    total = sum(by.values())
    if total <= 0:
        return None
    per_step_ms = {key: v / n_steps / 1e6 for key, v in by.items()}
    metrics = {}
    for name, (names, phases) in METRICS.items():
        metrics[name] = sum(v for (sc, ph), v in per_step_ms.items()
                            if sc in names and (phases is None or ph in phases))
    metrics[UNSCOPED_SHARE] = 100.0 * scopes.unscoped_share(by)
    rows = sorted(per_step_ms.items(), key=lambda kv: -kv[1])
    return {"metrics": metrics, "table": {
        "device": 0, "whole_steps": n_steps, "table_build_s": table_s,
        "op_ms_per_step": total / n_steps / 1e6, "busy_ms_per_step": 1e3 * busy_s / n_steps,
        "ms_per_step": {f"{sc}.{ph}": ms for (sc, ph), ms in rows},
        "share_pct": {f"{sc}.{ph}": 100.0 * ms * n_steps * 1e6 / total for (sc, ph), ms in rows},
        # a conv's fusion carries the BatchNorm sums around it: the time of the ops
        # that contain each scope's reductions or contractions (rows overlap)
        "containing_ms_per_step": {sc: v / n_steps / 1e6 for sc, v in sorted(
            scopes.time_containing(ops, table, scopes.scopes_inside(parsed)).items(), key=lambda kv: -kv[1])}}}


def metric(ctx, name: str):
    """One metric of this module by name; the table is computed, and printed
    as a commentary line, on the first call of a run."""
    if not hasattr(ctx, "step_scopes"):
        ctx.step_scopes = compute(ctx)
        if ctx.step_scopes is not None:
            harness.emit({"step_scopes": ctx.step_scopes["table"]})
    return None if ctx.step_scopes is None else ctx.step_scopes["metrics"][name]
