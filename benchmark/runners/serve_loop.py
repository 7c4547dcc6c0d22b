"""Runner `serve_loop`: seeded weights -> export -> engine -> batcher, built as
cli/serve.py:run builds them from the serving app file, then load from
loadgen.py submitted in-process through `batcher.submit` (the HTTP front door
is a layer for a later cell).

Copied from cli/serve.py (`run`'s engine arguments, `_make_batcher`) because
importing cli.serve drags in the logger and the front end; if the program
changes how it wires its engine, these two blocks must follow (PERF.md, Open
questions). Everything it measures with is the benchmark's own: the
generator, the clock, the reference.
"""

from __future__ import annotations

import os
import tempfile
import time

from benchmark import harness, loadgen, reference


def seeded_serving_weights(model_cfg, image_size: int, calib_rows: int = 32):
    """make(key) -> (params, state), ONE jitted program from the seed.

    A random-init network answers ~0 and ignores its input: `net.init`
    zero-initialises the last BN scale of every residual branch, draws the
    classifier at std 0.01, and leaves the running statistics at (0, 1) while
    the activations shrink layer by layer (PR 23 read a largest logit of
    5e-10, then 0.066; with random statistics the logits do not depend on the
    image at all). So the weights are made to look trained: BN scales in
    U(0.5, 1.5) and shifts in N(0, 0.1), the two dense layers at
    1/sqrt(fan_in), and the running statistics set to the batch statistics of
    one seeded batch (a train-mode forward of the same network with BN
    momentum 1). Logits are then of order 1 and every layer and every pixel
    moves them."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.models import get_model

    net = get_model(model_cfg, image_size)
    calib_net = get_model(dataclasses.replace(model_cfg, bn_momentum=1.0, dropout=0.0, drop_connect=0.0),
                          image_size)

    def make(key):
        key = harness.init_key(key)
        params, state = net.init(key)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name, k = path[-1].key, jax.random.fold_in(key, 100 + i)
            if name == "gamma":
                leaf = jax.random.uniform(k, leaf.shape, leaf.dtype, 0.5, 1.5)
            elif name == "beta":
                leaf = 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
            elif name == "w" and path[0].key in ("feature", "classifier"):
                leaf = jax.random.normal(k, leaf.shape, leaf.dtype) * leaf.shape[0] ** -0.5
            out.append(leaf)
        params = jax.tree_util.tree_unflatten(treedef, out)
        x = jax.random.normal(jax.random.fold_in(key, 3), (calib_rows, image_size, image_size, 3), jnp.float32)
        _, state = calib_net.apply(params, state, x, train=True)
        return params, state

    return net, jax.jit(make)


def build_engine(cfg, bundle):
    """cli/serve.py:run's engine, argument for argument."""
    from yet_another_mobilenet_series_tpu.parallel import mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.serve.engine import InferenceEngine

    mesh = mesh_lib.make_mesh(cfg.dist.num_devices) if cfg.serve.data_parallel else None
    return InferenceEngine(
        bundle,
        buckets=cfg.serve.buckets,
        compute_dtype=cfg.serve.compute_dtype,
        mesh=mesh,
        donate_input=cfg.serve.donate_input,
        image_size=cfg.data.image_size,
        image_sizes=cfg.serve.image_sizes,
        fuse_ladder=cfg.serve.fuse_chunks.ladder if cfg.serve.fuse_chunks.enable else (),
        offladder_cache=cfg.serve.offladder_cache,
        overlap_staging=cfg.serve.overlap.enable,
        staging_slots=cfg.serve.overlap.staging_slots,
        wire=cfg.serve.quant.wire,
        wire_mean=cfg.data.mean,
        wire_std=cfg.data.std,
        ring_slots=cfg.serve.ring.slots if (cfg.serve.ring.enable and mesh is None) else 0,
    )


def build_batcher(cfg, engine):
    """cli/serve.py:_make_batcher, argument for argument."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.serve.batcher import MicroBatcher
    from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher

    common = dict(
        max_batch=cfg.serve.max_batch,
        max_wait_ms=cfg.serve.max_wait_ms,
        queue_depth=cfg.serve.queue_depth,
        default_deadline_ms=cfg.serve.deadline_ms,
        drain_timeout_s=cfg.serve.drain_timeout_s,
        wire_dtype=getattr(engine, "wire_np_dtype", np.float32),
    )
    if cfg.serve.pipelined:
        return PipelinedBatcher(
            engine,
            max_inflight=cfg.serve.max_inflight,
            run_max=cfg.serve.overlap.run_max if cfg.serve.overlap.enable else 1,
            ring_min_fill=cfg.serve.ring.min_fill,
            **common,
        )
    return MicroBatcher(engine.predict, **common)


def run(ctx) -> dict:
    import jax
    import numpy as np

    from yet_another_mobilenet_series_tpu.serve.export import export_bundle, load_bundle

    config, traffic = ctx.config, ctx.traffic
    ctx.phases.done("import_program")

    image_size = int(config["image_size"])
    model_cfg = harness.load_app_config(config["train_app"], config.get("overrides", {})).model
    overrides = {"data.image_size": image_size, **traffic.get("serve_overrides", {})}
    cfg = harness.load_app_config(config["serve_app"], overrides)
    key = harness.seed_key(ctx.seed)
    net, make_weights = seeded_serving_weights(model_cfg, image_size)
    params, state = jax.device_get(make_weights(key))
    ctx.phases.done("init_weights")

    work = tempfile.TemporaryDirectory(prefix="bench_bundle_")
    bundle_dir = export_bundle(net, params, state, os.path.join(work.name, "bundle"))
    engine = build_engine(cfg, load_bundle(bundle_dir))
    ctx.phases.done("export_and_load")
    if cfg.serve.warmup:
        engine.warmup()
    ctx.phases.done("engine_warmup")
    batcher = build_batcher(cfg, engine)
    batcher.start()

    # the image pool: host arrays in numpy's own memory, as a caller's are. NOT
    # pulled from the device: an array that `device_get` hands back lives in the
    # runtime's transfer buffer, and the engine's staging copy out of it ran at
    # 250 MB/s (85 ms a 32-row batch, 576 images/s: my chip run, PR 24)
    pool_n = int(traffic["pool"])
    sizes = [int(s) for s in traffic.get("image_sizes", [image_size])]
    pool_rng = np.random.default_rng(ctx.seed)
    images: list = []
    for size in sizes:
        images += list(pool_rng.standard_normal((pool_n // len(sizes), size, size, 3), dtype=np.float32))
    ctx.phases.done("image_pool")

    try:
        warm_s = float(traffic.get("warm_s", 1.0))
        gen = loadgen.LoadGen(batcher.submit, images, traffic, ctx.seed,
                              duration_s=warm_s + ctx.seconds, spans=ctx.spans)
        t_gen = gen.start()
        t0 = t_gen + warm_s
        _sleep_until(t0)
        ctx.phases.done("warm_traffic")
        ctx.window_opens()
        # ---- the window: the generator thread drives, this one only waits
        while True:
            elapsed = time.perf_counter() - t0
            ctx.tick(elapsed)
            if elapsed >= ctx.seconds:
                break
            time.sleep(min(0.05, ctx.seconds - elapsed))
        ctx.window_closes()
        records = gen.join(drain_s=cfg.serve.drain_timeout_s or 10.0)
        w = records.window(warm_s, warm_s + ctx.seconds)
        unanswered = sum(d is None for d in records.done)

        # ---- correctness, outside the window: rows served through the same
        # batcher against the plain float32 reference on the same device
        rows = int(traffic.get("check_rows", 64))
        spec, weights = reference.load_bundle_files(bundle_dir)
        ref_fn = jax.jit(lambda w_, x: reference.forward(spec, w_, x))
        served, ref = [], []
        per_size = len(images) // len(sizes)
        for j in range(len(sizes)):  # one reference call per image size
            group = np.stack(images[j * per_size: j * per_size + max(rows // len(sizes), 1)])
            served.append(np.stack([f.result(timeout=60) for f in [batcher.submit(im) for im in group]]))
            ref.append(np.asarray(jax.device_get(ref_fn(weights, group))))
        verdict = reference.compare(np.concatenate(served), np.concatenate(ref))
    finally:
        batcher.stop()
        work.cleanup()

    checks = {"reference_within_tolerance": verdict["ok"],
              "some_request_answered": w["completed"] > 0,
              "none_left_unanswered": unanswered == 0}
    facts = {"loop": traffic["loop"], "clients": traffic.get("clients"), "rate_per_s": traffic.get("rate_per_s"),
             "pool": pool_n, "image_sizes": sizes, "warm_s": warm_s,
             "buckets": list(engine.buckets), "fuse_ladder": list(engine.fuse_ladder),
             "max_batch": cfg.serve.max_batch, "max_wait_ms": cfg.serve.max_wait_ms,
             "max_inflight": cfg.serve.max_inflight, "queue_depth": cfg.serve.queue_depth,
             "compute_dtype": cfg.serve.compute_dtype, "wire": cfg.serve.quant.wire,
             "arch": model_cfg.arch, "image_size": image_size,
             "completed": w["completed"], "images_per_s": w["per_s"],
             "p50_ms": w["p50_ms"], "p95_ms": w["p95_ms"], "p99_ms": w["p99_ms"],
             "late_p95_ms": w["late_p95_ms"], "unanswered_at_end": unanswered,
             "reference": verdict, "checks": checks}
    end_to_end = {"serve_images_per_s": w["per_s"], "serve_p95_ms": w["p95_ms"]}
    return {"end_to_end": end_to_end, "facts": facts, "attempted": w["attempted"], "failed": w["failed"],
            "correct": all(checks.values()), "t_window_start": t0}


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))
