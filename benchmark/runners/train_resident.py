"""Runner `train_resident`: the program's full train step (forward, backward,
optimizer, EMA, label-smoothed CE, dropout) on a device-resident batch, steps
dispatched back to back on 1 or 4 chips. The input pipeline is deliberately
absent: this is the compiled step's cell.

The method is bench.py's and utils/benchkit.py:build_train_fixture's, copied
so that no later PR can move it, with three differences: the recipe is READ
from the app file the configuration names (so what the app ships is what is
measured; no tuning file), the state and the batch are made on the device
from --seed in one jitted call each, and the clock is read at a lagged sync
every `sync_every` steps so the device never waits for the host.

It imports the program's step, optimizer, schedule, mesh and model modules,
never cli.train / ckpt / data (orbax, google-cloud-logging, TensorFlow: 31 s
of PR 23's set-up).
"""

from __future__ import annotations

import collections
import math
import time

from benchmark import harness


def run(ctx) -> dict:
    """ctx: run.Context. Returns {"end_to_end": {...}, "facts": {...},
    "attempted", "failed", "correct", "t_window_start"}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.models.serialize import network_to_dict
    from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    from benchmark import macs

    config, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    ctx.phases.done("import_program")

    per_chip = int(config["per_chip_batch"])
    batch = per_chip * chips
    image_size = int(config["image_size"])
    overrides = {"train.batch_size": batch, "dist.num_devices": chips, **config.get("overrides", {})}
    cfg = harness.load_app_config(config["train_app"], overrides)
    for key, have in (("arch", cfg.model.arch), ("width_mult", cfg.model.width_mult),
                      ("num_classes", cfg.model.num_classes), ("image_size", cfg.data.image_size),
                      ("compute_dtype", cfg.train.compute_dtype)):
        if not ctx.rehearsal and config[key] != have:
            raise SystemExit(f"benchmark: {config['train_app']} now has {key}={have!r}, the "
                             f"configuration file says {config[key]!r}: this cell measures the file's")
    net = get_model(cfg.model, image_size)
    spec = network_to_dict(net)
    macs_per_image = macs.forward_macs(spec, image_size)
    if not ctx.rehearsal and macs_per_image != config["macs_per_image"]:
        raise SystemExit(f"benchmark: the network built from {config['train_app']} has "
                         f"{macs_per_image} MACs, the configuration file says {config['macs_per_image']}")

    mesh = mesh_lib.make_mesh(chips, devices=ctx.devices)
    steps_per_epoch = max(cfg.data.num_train_examples // batch, 1)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, batch, steps_per_epoch, cfg.train.epochs)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step_fn = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh, params_example=params_example)
    ctx.phases.done("build_trainer")

    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
    key = harness.seed_key(ctx.seed)

    # ONE program each, the seed an argument: hundreds of eager dispatches
    # (net.init leaf by leaf: 11 s of PR 23's set-up) become one cached call
    init_state = jax.jit(lambda k: steps.init_train_state(net, cfg, optimizer, harness.init_key(k)),
                         out_shardings=replicated)

    def make_batch(k):
        k_img, k_lab = jax.random.split(jax.random.fold_in(harness.init_key(k), 1))
        return {"image": jax.random.normal(k_img, (batch, image_size, image_size, 3), jnp.float32),
                "label": jax.random.randint(k_lab, (batch,), 0, cfg.model.num_classes, jnp.int32)}

    ts = init_state(key)
    jax.block_until_ready(ts)
    ctx.phases.done("init_state")
    b = jax.jit(make_batch, out_shardings=sharded)(key)
    jax.block_until_ready(b)
    ctx.phases.done("make_batch")

    rng = jax.random.fold_in(jnp.asarray(key), 2)  # the step's dropout stream, a raw key like PRNGKey's
    # compiled ahead of the first call (one compile, or one read from the
    # cache, either way) so that the program's own account of its temporaries
    # can be read: the allocator's statistics leave them out (harness.device_facts)
    step_fn = step_fn.lower(ts, b, rng).compile()
    program_temp_bytes = int(step_fn.memory_analysis().temp_size_in_bytes)
    ts, metrics = step_fn(ts, b, rng)
    first_loss = float(jax.device_get(metrics["loss"]))
    ctx.phases.done("first_step")
    for _ in range(int(traffic.get("warm_steps", 2))):
        ts, metrics = step_fn(ts, b, rng)
    jax.block_until_ready(metrics["loss"])
    step0 = int(jax.device_get(ts.step))
    ctx.phases.done("warm_steps")

    # ---- the window -------------------------------------------------------
    sync_every = int(traffic.get("sync_every", 8))
    lag = int(traffic.get("sync_lag", 2))
    losses: list = []
    pending: collections.deque = collections.deque()
    spans = ctx.spans
    ctx.window_opens()
    t0 = time.perf_counter()
    n = 0
    while True:
        with spans.span("dispatch"):
            ts, metrics = step_fn(ts, b, rng)
        n += 1
        losses.append(metrics["loss"])
        pending.append(metrics["loss"])
        if n % sync_every == 0:
            # the clock is read behind a sync `lag` steps back: the queue the
            # device works from is never empty, and the host never runs
            # further ahead than sync_every + lag steps
            while len(pending) > lag + 1:
                pending.popleft()
            with spans.span("sync"):
                jax.block_until_ready(pending[0])
            elapsed = time.perf_counter() - t0
            ctx.tick(elapsed)
            if elapsed >= ctx.seconds:
                break
    with spans.span("sync"):
        jax.block_until_ready(metrics["loss"])
    t1 = time.perf_counter()
    ctx.window_closes()
    # ----------------------------------------------------------------------

    window_s = t1 - t0
    loss_values = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(loss_values)))
    advanced = int(jax.device_get(ts.step)) - step0
    expect = math.log(cfg.model.num_classes)
    checks = {
        "losses_finite": failed == 0,
        # uniform logits at initialisation: CE = ln(classes), smoothing or not
        "first_loss_near_ln_classes": abs(first_loss - expect) <= 0.005 * expect,
        # warm-up steps: the loss need not fall, it must not run away
        "loss_not_above_first": bool(np.all(loss_values <= 1.01 * first_loss)),
        "step_counter_advanced_by_attempted": advanced == n,
    }
    facts = {"first_loss": first_loss, "last_loss": float(loss_values[-1]), "steps": n,
             "window_s": window_s, "global_batch": batch, "per_chip_batch": per_chip, "chips": chips,
             "arch": cfg.model.arch, "image_size": image_size, "compute_dtype": cfg.train.compute_dtype,
             "sync_bn": cfg.dist.sync_bn, "macs_per_image": macs_per_image,
             "program_temp_bytes": program_temp_bytes,
             "step_ms_host": 1e3 * window_s / n}
    if chips > 1:
        # replicated state must be bit-identical on every chip
        divergence = float(jax.device_get(dp.make_replica_sync_check(mesh)(ts.params)))
        checks["replicas_identical"] = divergence == 0.0
        facts["replica_divergence"] = divergence
    facts["checks"] = checks
    images_per_s_per_chip = n * per_chip / window_s
    facts["images_per_s_per_chip"] = images_per_s_per_chip
    return {"end_to_end": {"train_images_per_s_per_chip": images_per_s_per_chip},
            "facts": facts, "attempted": n, "failed": failed, "correct": all(checks.values()),
            "t_window_start": t0}
