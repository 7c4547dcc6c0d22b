"""Training/eval entry point — the reference train.py rebuilt for TPU
(SURVEY.md §3.1): ``python -m yet_another_mobilenet_series_tpu.cli.train
app:<yaml> [key=value ...]``.

Owns the epoch/step loops, validation on EMA shadow weights, checkpoint
save/resume (pruned-shape-first), the AtomNAS shrink schedule (in-jit mask
refresh at fine cadence + physical rematerialization at coarse cadence), and
throughput/accuracy logging. Everything inside the step is one compiled XLA
program (train/steps.py + parallel/dp.py).

Runtime telemetry (obs/, docs/OBSERVABILITY.md) wraps the loop without
touching the compiled step: spans time every host-side phase (data fetch,
dispatch, syncs, prune, eval, checkpoint, rebuilds), the metrics registry
rides into every scalars row, and the stall watchdog turns a hung collective
or a stalled input pipeline into a hang_report.json instead of a silent
death.

Survivability (the training-side robustness layer, README "Preemption &
resume"): SIGTERM/SIGINT triggers a final SYNCHRONOUS checkpoint and a
clean exit with a resume marker instead of losing the epoch; restore walks
back through older checkpoints when the latest is corrupt or half-written
(digest-verified, ckpt/manager.py); train.guard skips-and-rolls-back
bounded non-finite steps (train/guard.py); the data stream skips corrupt
records with bounded abort (data/pipeline.py); and train.faults injects all
of the above deterministically (train/faults.py, scripts/train_chaos.py).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

import jax
import numpy as np

from ..ckpt.manager import CheckpointCorrupt, CheckpointManager
from ..config import Config, parse_cli
from .. import data as data_lib
from ..models import TokenModel, get_model
from ..models.specs import Network
from ..nas import masking, penalty, rematerialize
from ..obs import device as obs_device
from ..obs import registry as obs_registry
from ..obs import scopes as obs_scopes
from ..obs import trace as obs_trace
from ..obs.watchdog import StallWatchdog
from ..parallel import dp, mesh as mesh_lib
from ..train import optim, schedules, steps
from ..utils import compile_cache
from ..utils.cadence import StepCadence
from ..utils.logging import Logger
from ..utils.meters import MetricLogger, format_metrics
from ..utils.profiling import profile_network


# written next to the checkpoint on a clean preemption exit; consumed (and
# removed) by the next resumed run. Schedulers/operators can poll it to tell
# "checkpointed and exited on purpose" from "died".
PREEMPT_MARKER_NAME = "preempt_marker.json"


def _dataset_sizes(cfg: Config) -> tuple[int, int]:
    if cfg.data.dataset == "fake":
        return cfg.data.fake_train_size, cfg.data.fake_eval_size
    return cfg.data.num_train_examples, cfg.data.num_eval_examples


class Trainer:
    """Builds and owns all step functions; rebuilt wholesale on
    rematerialization (shapes changed => everything re-jits)."""

    def __init__(self, cfg: Config, net: Network, mesh, log: Logger):
        self.cfg = cfg
        self.net = net
        self.mesh = mesh
        self.log = log
        n_train, _ = _dataset_sizes(cfg)
        self.steps_per_epoch = max(n_train // cfg.train.batch_size, 1)
        self.lr_fn = schedules.make_lr_schedule(
            cfg.schedule, cfg.train.batch_size, self.steps_per_epoch, cfg.train.epochs
        )
        self.params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
        self.optimizer = optim.make_optimizer(
            cfg.optim, self.lr_fn, self.params_example,
            shard_axis=mesh_lib.DATA_AXIS if cfg.dist.shard_optimizer else None,
        )
        self.penalty_fn = (
            penalty.make_penalty_fn(net, cfg.prune, self.steps_per_epoch) if cfg.prune.enable else None
        )
        self.train_step = dp.make_dp_train_step(
            net, cfg, self.optimizer, self.lr_fn, mesh,
            penalty_fn=self.penalty_fn, params_example=self.params_example,
            clip_shard_aware=cfg.dist.shard_optimizer,  # optimizer built with shard_axis above
        )
        self.eval_step = dp.make_dp_eval_step(net, cfg, mesh)
        # the complete per-cadence prune event (reached check + adaptive rho
        # + mask update) as ONE device program
        self.prune_stop_step = int(cfg.prune.stop_epoch_frac * cfg.train.epochs * self.steps_per_epoch)
        self.prune_event = (
            jax.jit(masking.make_prune_event(net, cfg.prune, self.prune_stop_step))
            if cfg.prune.enable else None
        )
        self.sync_check = dp.make_replica_sync_check(mesh)
        if cfg.dist.shard_optimizer:
            from ..parallel import zero

            # jitted ONCE: a fresh jax.jit per checkpoint would retrace the
            # full gather program every save
            self._gather_opt = jax.jit(zero.gather_opt_state)

    def init_state(self, rng) -> steps.TrainState:
        zero_opt = self.cfg.dist.shard_optimizer
        ts = steps.init_train_state(self.net, self.cfg, self.optimizer, rng, with_opt=not zero_opt)
        if self.cfg.prune.enable:
            ts = ts.replace(masks=masking.init_masks(self.net))
        ts = mesh_lib.replicate(ts, self.mesh)
        if zero_opt:
            from ..parallel import zero

            ts = ts.replace(opt_state=zero.init_opt_state(self.optimizer, ts.params, self.mesh))
        return ts

    def abstract_state(self) -> steps.TrainState:
        """Shape/dtype skeleton of the CHECKPOINT format (ckpt phase 2).

        Checkpoints always carry the optimizer state params-shaped and
        replicated — even under ZeRO — so they are portable across chip
        counts (train on 8 chips, resume on 256) and multi-host saves never
        need a cross-host device_get. The flat sharded form exists only
        inside the live mesh (parallel/zero.py)."""

        def build():
            ts = steps.init_train_state(self.net, self.cfg, self.optimizer, jax.random.PRNGKey(0))
            if self.cfg.prune.enable:
                ts = ts.replace(masks=masking.init_masks(self.net))
            return ts

        return jax.eval_shape(build)

    def place_state(self, ts: steps.TrainState) -> steps.TrainState:
        """Puts a checkpoint-format TrainState onto the mesh: everything
        replicated; under ZeRO the params-shaped optimizer state is scattered
        to this mesh's flat shards (any chip count)."""
        if self.cfg.dist.shard_optimizer:
            from ..parallel import zero

            opt = ts.opt_state
            ts = mesh_lib.replicate(ts.replace(opt_state=None), self.mesh)
            return ts.replace(opt_state=zero.scatter_opt_state(opt, ts.params, self.mesh))
        return mesh_lib.replicate(ts, self.mesh)

    def checkpoint_view(self, ts: steps.TrainState) -> steps.TrainState:
        """Converts a live TrainState to the checkpoint format (gathers the
        ZeRO flat shards back to params-shaped; identity otherwise)."""
        if self.cfg.dist.shard_optimizer:
            return ts.replace(opt_state=self._gather_opt(ts.opt_state, ts.params))
        return ts


class _Preemption:
    """SIGTERM/SIGINT -> cooperative stop flag. The loop checks ``requested``
    at step boundaries and exits through the final-synchronous-checkpoint
    path (a preemption loses at most the in-flight step, not the epoch).

    Handlers install only in the main thread (embedded/test runs keep their
    own); the previous handlers are restored on uninstall so an in-process
    caller (pytest) is left untouched. Multi-host note: the scheduler
    delivers the signal to every host and the loops run in lockstep, so all
    hosts reach the same collective save — the same assumption Orbax's own
    preemption handling makes."""

    def __init__(self, log: Logger):
        self._log = log
        self.requested = False
        self.reason = ""
        self._prev: dict = {}

    def _handle(self, signum, frame):
        self.requested = True
        self.reason = signal.Signals(signum).name
        self._log.log(f"{self.reason} received: will checkpoint and exit at the "
                      "next step boundary")

    def install(self) -> "_Preemption":
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev[sig] = signal.signal(sig, self._handle)
            except ValueError:
                break  # not the main thread: cooperative flag only
        return self

    def uninstall(self) -> None:
        for sig, prev in self._prev.items():
            try:
                signal.signal(sig, prev)
            except ValueError:
                pass  # uninstall from a non-main thread: nothing was installed
        self._prev.clear()


def _restore_tree(ckpt: CheckpointManager, step: int, abstract: dict, log: Logger):
    """restore_tree with the NARROW legacy-rho_mult retry: the old bare
    ``except Exception`` retried EVERY failure as a legacy checkpoint, which
    masked genuine corruption as a shape quirk. Now the retry happens only
    when the saved tree demonstrably lacks the rho_mult item (or its
    metadata is unreadable — the pre-metadata behavior, kept for old saves);
    digest mismatches and failures of a checkpoint that HAS the item
    propagate to the fallback walk with their cause logged."""
    import jax.numpy as jnp

    try:
        return ckpt.restore_tree(step, abstract)
    except CheckpointCorrupt:
        raise  # verified corruption is never a legacy-layout quirk
    except Exception as e:  # noqa: BLE001 — orbax raises bare ValueError
        if "rho_mult" not in abstract or abstract["rho_mult"] is None:
            raise
        saved = ckpt.tree_keys(step)
        if saved is not None and "rho_mult" in saved:
            # the item exists on disk: this failure is corruption or a real
            # shape mismatch, not the pre-rho_mult layout
            log.log(f"restore at step {step} failed ({type(e).__name__}: {e}); "
                    "saved tree HAS rho_mult, so this is not a legacy checkpoint")
            raise
        # legacy checkpoint written before TrainState grew rho_mult: restore
        # without it and inject the neutral multiplier
        log.log(f"restore with rho_mult failed ({type(e).__name__}); retrying as legacy checkpoint")
        tree = ckpt.restore_tree(step, {k: v for k, v in abstract.items() if k != "rho_mult"})
        tree["rho_mult"] = jnp.ones((), jnp.float32)
        return tree


def _restore(ckpt: CheckpointManager, cfg: Config, mesh, log: Logger):
    """Two-phase resume (SURVEY.md §3.5): spec -> rebuild at pruned shape ->
    weights. Returns (trainer, ts, extra) or None when no checkpoint exists.

    Crash-consistent: candidates are tried NEWEST FIRST and a step whose
    spec sidecar is unreadable, whose tree fails to restore, or whose bytes
    fail digest verification (ckpt/manager.py) is logged, counted
    (``ckpt.restore_fallbacks``), and SKIPPED in favor of the previous step
    — a preemption mid-save costs one checkpoint interval, not the run.
    Raises only when checkpoints exist but none restores."""
    candidates = ckpt.all_steps()
    if not candidates:
        return None
    last_err = None
    for i, step in enumerate(candidates):
        if i:
            obs_registry.get_registry().counter("ckpt.restore_fallbacks").inc()
            log.log(f"falling back to checkpoint step {step}")
        try:
            spec = ckpt.restore_spec(step)
        except Exception as e:  # noqa: BLE001 — a torn sidecar must not end resume
            log.log(f"checkpoint step {step}: spec sidecar unreadable "
                    f"({type(e).__name__}: {e})")
            last_err = e
            continue
        _, net, extra = spec
        trainer = Trainer(cfg, net, mesh, log)
        abstract = steps.train_state_to_dict(trainer.abstract_state())
        try:
            tree = _restore_tree(ckpt, step, abstract, log)
        except Exception as e:  # noqa: BLE001 — corrupt tree: walk back one step
            log.log(f"checkpoint step {step}: tree restore failed "
                    f"({type(e).__name__}: {e})")
            last_err = e
            continue
        ts = trainer.place_state(steps.TrainState(**tree))
        return trainer, ts, extra
    raise RuntimeError(
        f"no restorable checkpoint: all {len(candidates)} candidate step(s) "
        f"{candidates} failed — see the per-step causes above"
    ) from last_err


def evaluate(trainer: Trainer, ts: steps.TrainState, cfg: Config, *, use_ema=True,
             watchdog: StallWatchdog | None = None) -> dict:
    """Validation pass on the EMA shadow weights (reference: eval-on-shadow,
    SURVEY.md §2 #8); falls back to the live weights when EMA is off.

    ONE host sync per pass: per-batch metrics accumulate as lazy device
    arrays (the eval_step outputs stay un-read, so dispatch keeps running
    ahead) and a single device_get lands at the end — the previous
    per-batch ``float(m[k])`` forced four host round-trips every step."""
    tracer = obs_trace.get_tracer()
    params = ts.ema_params if (use_ema and cfg.ema.enable) else ts.params
    state = ts.ema_state if (use_ema and cfg.ema.enable) else ts.state
    # eval_batch_size is GLOBAL (matching train's batch_size semantics):
    # round up to device divisibility, then give each host its share —
    # per-device eval memory stays constant as host count grows (padding
    # rows carry label=-1 and are masked out of every count)
    n_dev = trainer.mesh.size
    per_device = -(-cfg.train.eval_batch_size // n_dev)
    local_eval = per_device * (n_dev // jax.process_count())
    batches = data_lib.make_eval_source(cfg.data, local_eval, jax.process_index(), jax.process_count())
    totals = None
    with tracer.span("eval/pass", "eval"):
        for batch in batches:
            with tracer.span("eval/batch", "eval"):
                b = mesh_lib.shard_batch(batch, trainer.mesh)
                m = trainer.eval_step(params, state, b, ts.masks)
            totals = m if totals is None else jax.tree.map(lambda a, b_: a + b_, totals, m)
            if watchdog is not None:
                watchdog.arm(phase="eval")
        with tracer.span("sync/eval_gather", "sync"):
            host = (
                jax.device_get(totals) if totals is not None
                else {"top1": 0.0, "top5": 0.0, "n": 0.0, "loss_sum": 0.0}
            )
    obs_registry.get_registry().counter("eval.passes").inc()
    n = max(float(host["n"]), 1.0)
    return {
        "top1": float(host["top1"]) / n, "top5": float(host["top5"]) / n,
        "loss": float(host["loss_sum"]) / n, "n": int(float(host["n"])),
    }


def _maybe_rematerialize(trainer: Trainer, ts: steps.TrainState, log: Logger):
    """Physical shrink at coarse cadence (SURVEY.md §3.2 TPU translation).
    Returns (trainer, ts) — possibly rebuilt."""
    cfg = trainer.cfg
    summary = masking.mask_summary(trainer.net, ts.masks)
    if summary["alive_atoms"] == summary["total_atoms"]:
        return trainer, ts  # nothing died; skip the recompile
    # checkpoint_view: remat's channel slicers need the optimizer state in
    # params shape, not ZeRO's flat shards
    host_ts = jax.device_get(trainer.checkpoint_view(ts))
    masks = {k: np.asarray(v) for k, v in host_ts.masks.items()}
    new_net, new_p, new_s, new_masks, extras, report = rematerialize.rematerialize(
        trainer.net, host_ts.params, host_ts.state, masks,
        opt_state=host_ts.opt_state, ema_params=host_ts.ema_params, ema_state=host_ts.ema_state,
    )
    log.log(
        f"rematerialize: atoms {report.atoms_before}->{report.atoms_after}, "
        f"dropped blocks {report.dropped_blocks}, "
        f"MACs {profile_network(trainer.net).total_macs/1e6:.1f}M->{profile_network(new_net).total_macs/1e6:.1f}M"
    )
    new_trainer = Trainer(cfg, new_net, trainer.mesh, log)
    new_ts = steps.TrainState(
        step=host_ts.step, params=new_p, state=new_s, opt_state=extras["opt_state"],
        ema_params=extras.get("ema_params"), ema_state=extras.get("ema_state"), masks=new_masks,
        rho_mult=host_ts.rho_mult,
    )
    return new_trainer, new_trainer.place_state(new_ts)


def _init_or_warm_start(cfg: Config, net: Network, mesh, log: Logger, rng):
    """Fresh TrainState — or, when train.pretrained / train.torch_pretrained
    is set on a non-resumed training run, a warm start: weights (+ BN stats,
    + masks for a pruned source) from the source checkpoint, with a FRESH
    optimizer/step/EMA-shadow (finetune semantics — the reference's
    pretrained-init path, SURVEY.md §3.3)."""
    if cfg.train.torch_pretrained:
        from ..ckpt.torch_import import load_torch_checkpoint

        import jax.numpy as jnp

        params, state = load_torch_checkpoint(cfg.train.torch_pretrained, net)
        trainer = Trainer(cfg, net, mesh, log)
        ts = trainer.init_state(rng)
        rep = lambda t: mesh_lib.replicate(t, mesh)  # noqa: E731
        # EMA shadow must be a real copy, never an alias of the live buffers
        # (aliasing breaks donation of the TrainState)
        ts = ts.replace(
            params=rep(params), state=rep(state),
            ema_params=rep(jax.tree.map(jnp.copy, params)) if cfg.ema.enable else None,
            ema_state=rep(jax.tree.map(jnp.copy, state)) if cfg.ema.enable else None,
        )
        log.log(f"warm start from torch checkpoint {cfg.train.torch_pretrained}")
        return trainer, ts
    if cfg.train.pretrained:
        import jax.numpy as jnp

        mgr = CheckpointManager(cfg.train.pretrained, barrier_prefix="warmstart")
        src = _restore(mgr, cfg, mesh, log)
        mgr.close()
        if src is None:
            raise FileNotFoundError(f"train.pretrained={cfg.train.pretrained!r} holds no checkpoint")
        trainer, src_ts, _ = src  # trainer is built on the source's (possibly pruned) net
        ts = trainer.init_state(rng)
        copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
        ts = ts.replace(
            params=src_ts.params, state=src_ts.state, masks=src_ts.masks,
            ema_params=copy(src_ts.params) if cfg.ema.enable else None,
            ema_state=copy(src_ts.state) if cfg.ema.enable else None,
        )
        log.log(f"warm start from checkpoint {cfg.train.pretrained} (step {int(src_ts.step)} weights, fresh optimizer)")
        return trainer, ts
    trainer = Trainer(cfg, net, mesh, log)
    return trainer, trainer.init_state(rng)


def run(cfg: Config) -> dict:
    import dataclasses as dc

    compile_cache.configure()  # before the first compile (config only: no backend touch)
    if cfg.dist.multihost:
        # multi-host rendezvous: the reference's torch.distributed env://
        # init; on TPU pods the coordinator/process env is auto-discovered.
        jax.distributed.initialize()
    if cfg.data.dataset == "fake" and cfg.data.fake_num_classes is None:
        cfg = dc.replace(cfg, data=dc.replace(cfg.data, fake_num_classes=cfg.model.num_classes))
    if cfg.data.loader == "tokens" and not cfg.data.seq_len:
        cfg = dc.replace(cfg, data=dc.replace(cfg.data, seq_len=cfg.model.lm.seq_len))
    is_coord = mesh_lib.is_coordinator()
    log = Logger(cfg.train.log_dir, enabled=is_coord, tensorboard=bool(cfg.train.log_dir))
    mesh = mesh_lib.make_mesh(cfg.dist.num_devices)
    log.log(f"devices: {mesh.size} ({jax.devices()[0].platform}), hosts: {jax.process_count()}")

    # ---- runtime telemetry (obs/, docs/OBSERVABILITY.md) ----
    # registry snapshots ride into every scalars row; the span tracer and
    # stall watchdog are coordinator-only opt-ins (cfg.obs)
    reg = obs_registry.get_registry()
    if cfg.obs.histogram_buckets:
        # before any training histogram exists: the ladder applies at creation
        reg.set_default_buckets(cfg.obs.histogram_buckets)
    # device telemetry (obs/device.py): version attribution + HBM/RSS pull
    # gauges — read only when a snapshot is taken (the log cadence), so they
    # ride every scalars row, hang report, and train_health dump for free
    reg.set_build_info(obs_device.build_info())
    obs_device.install_memory_gauges(reg)
    log.set_registry(reg)
    tracer = obs_trace.configure(
        enabled=bool(cfg.obs.trace) and is_coord, ring_size=cfg.obs.trace_ring_size
    )
    watchdog: StallWatchdog | None = None
    if cfg.obs.watchdog_deadline_s > 0 and is_coord and cfg.train.log_dir:
        watchdog = StallWatchdog(
            cfg.train.log_dir, cfg.obs.watchdog_deadline_s, tracer=tracer, registry=reg,
            poll_s=cfg.obs.watchdog_poll_s, logger=log,
        )
        watchdog.start()

    try:
        return _run_impl(cfg, log, mesh, is_coord, tracer, watchdog)
    finally:
        # flush telemetry on EVERY exit — a crash mid-epoch is exactly when
        # the trace and counters matter most
        if watchdog is not None:
            watchdog.stop()
        if tracer.enabled and cfg.train.log_dir and is_coord:
            path = tracer.write(os.path.join(cfg.train.log_dir, "obs_trace.json"))
            log.log(f"span trace -> {path} (open in ui.perfetto.dev or chrome://tracing)")
        if is_coord and cfg.train.log_dir:
            snap_path = os.path.join(cfg.train.log_dir, "obs_registry.json")
            with open(snap_path, "w") as f:
                json.dump(reg.snapshot(), f, indent=1, sort_keys=True)
        log.close()


def _run_impl(cfg: Config, log: Logger, mesh, is_coord: bool, tracer, watchdog) -> dict:
    net = get_model(cfg.model, cfg.data.image_size)
    if isinstance(net, TokenModel):
        held = (f"{net.experts_held} of {net.lm.n_routed_experts} experts a layer, share {net.lm.expert_share_index} of "
                f"{net.lm.expert_shares}" if net.expert_sites else
                f"no expert layer; {net.lm.num_hidden_layers} layers run {net.loop_steps} times a step")
        log.log(f"model {net.arch}: {net.param_count()/1e6:.2f}M params held here "
                f"({held}; {net.vocab} vocabulary rows; {net.lm.seq_len} tokens a sequence)")
    else:
        prof = profile_network(net)
        arch_name = cfg.model.network_spec or f"{cfg.model.arch} x{cfg.model.width_mult}"
        log.log(f"model {arch_name}: {prof.total_params/1e6:.2f}M params, {prof.total_macs/1e6:.1f}M MACs")
    reg = obs_registry.get_registry()

    ckpt = CheckpointManager(
        cfg.train.log_dir + "/ckpt", max_to_keep=cfg.train.max_checkpoints,
        barrier_prefix="periodic",
    )
    # the best-checkpoint manager is created lazily on the first new-best
    # eval, inside _train_or_eval; the shared box lets the finally below see
    # it on every exit path
    best_box: list[CheckpointManager] = []
    try:
        return _train_or_eval(cfg, net, log, mesh, is_coord, tracer, watchdog, ckpt, best_box)
    finally:
        # EVERY exit path — normal, KeyboardInterrupt, any raise — waits for
        # in-flight async saves BEFORE closing, so a checkpoint is never
        # abandoned half-written (the crash-consistency contract resume
        # relies on); a failed wait is logged, never allowed to mask the
        # original exception
        for mgr in (best_box[0] if best_box else None, ckpt):
            if mgr is None:
                continue
            try:
                mgr.wait()
            except Exception as e:  # noqa: BLE001 — shutdown must reach close()
                log.log(f"checkpoint wait on shutdown failed ({type(e).__name__}: {e})")
            try:
                mgr.close()
            except Exception as e:  # noqa: BLE001 — best-effort shutdown
                log.log(f"checkpoint close on shutdown failed ({type(e).__name__}: {e})")


def _record_step_cost(trainer: Trainer, ts, batch, rng, reg, tracer, log: Logger,
                      first_dispatch_s: float, watched: dict, scope_table_dir: str = "") -> None:
    """Device-cost accounting for the compiled train step (obs/device.py):
    the first dispatch's host wall time (≈ trace + compile under async
    dispatch — the run never blocks on device execution here) lands in
    ``obs.compile_seconds``; ``watched`` is what the compile watch saw across
    that dispatch (``CompileWatch.since``), and the log line says what the
    time was made of. A one-time re-lower of the step records its
    cost_analysis FLOPs/bytes into the ``train_step`` cost gauges. Lowering
    traces but does NOT compile, so the one-off cost is seconds of host
    time per trainer build — amortized to noise over a run. Telemetry only:
    any failure is logged and swallowed, never fatal.

    ``scope_table_dir`` (the profiler window's trace directory, when one was
    asked for): the same ``Lowered`` is compiled once more — a read from the
    persistent cache on a chip — and the step's instruction -> (scope, phase)
    table (obs/scopes.py) written there as ``scope_table.json``, which
    scripts/trace_ops.py joins to the window's device events by name."""
    reg.histogram("obs.compile_seconds").observe(first_dispatch_s)
    reg.counter("obs.compiles").inc()
    # where the work is: rows of this batch resident on each local device.
    # Read beside device.bytes_in_use.d<i>, it tells a data-parallel step
    # from one that runs on a single chip of the mesh.
    for shard in jax.tree.leaves(batch)[0].addressable_shards:
        reg.gauge(f"train.batch_rows.d{shard.device.id}").set(shard.data.shape[0])
    try:
        with tracer.span("dispatch/cost_analysis", "dispatch"):
            lowered = trainer.train_step.lower(ts, batch, rng)
        cost = obs_device.record_cost(
            "train_step", lowered, compile_seconds=first_dispatch_s, registry=reg)
    except Exception as e:  # noqa: BLE001 — cost telemetry must never end a run
        log.log(f"train step cost_analysis unavailable ({type(e).__name__}: {e})")
        return
    if cost.get("flops"):
        read_from_cache = watched["cache_hits"] and not watched["cache_misses"]
        backend = (f"cache read {watched['cache_read_s']:.2f}" if read_from_cache
                   else f"compile {watched['compile_s']:.2f}")
        log.log(
            f"train step cost_analysis: {cost['flops'] / 1e9:.3f} GFLOP, "
            f"{cost.get('bytes', 0) / 1e6:.1f} MB accessed per step "
            f"(first dispatch {first_dispatch_s:.1f}s: trace {watched['trace_s']:.2f} + lower "
            f"{watched['lower_s']:.2f} + {backend} s, the collector {watched['gc_s']:.2f} s of it)"
        )
    if scope_table_dir:
        try:
            with tracer.span("dispatch/cost_analysis", "dispatch"):
                path = obs_scopes.write_scope_table(scope_table_dir, lowered.compile())
            log.log(f"train step scope table -> {path}")
        except Exception as e:  # noqa: BLE001 — telemetry must never end a run
            log.log(f"train step scope table unavailable ({type(e).__name__}: {e})")


def _train_or_eval(cfg: Config, net: Network, log: Logger, mesh, is_coord: bool, tracer,
                   watchdog, ckpt: CheckpointManager, best_box: list) -> dict:
    # ---- eval-only path (acceptance config #1) ----
    if cfg.train.test_only:
        if cfg.train.torch_pretrained:
            # real pretrained torch weights — the "proves the model grammar
            # against real weights" milestone (SURVEY.md §7 stage 2); shares
            # the warm-start import path (EMA shadow = imported weights)
            trainer, ts = _init_or_warm_start(cfg, net, mesh, log, jax.random.PRNGKey(cfg.train.seed))
        else:
            src = cfg.train.pretrained or cfg.train.log_dir + "/ckpt"
            mgr = CheckpointManager(src, barrier_prefix="restore") if cfg.train.pretrained else ckpt
            restored = _restore(mgr, cfg, mesh, log)
            if mgr is not ckpt:
                mgr.close()
            if restored is None:
                log.log("no checkpoint found; evaluating fresh init (smoke mode)")
                trainer = Trainer(cfg, net, mesh, log)
                ts = trainer.init_state(jax.random.PRNGKey(cfg.train.seed))
            else:
                trainer, ts, _ = restored
        result = evaluate(trainer, ts, cfg, watchdog=watchdog)
        log.log(format_metrics("eval:", result))
        return result

    # ---- training path ----
    reg = obs_registry.get_registry()
    rng = jax.random.PRNGKey(cfg.train.seed)
    restored = _restore(ckpt, cfg, mesh, log) if cfg.train.resume else None
    start_epoch = 0.0
    if restored is not None:
        trainer, ts, extra = restored
        start_epoch = float(extra.get("epoch", int(ts.step) / trainer.steps_per_epoch))
        log.log(f"resumed at step {int(ts.step)} (epoch {start_epoch:.2f})")
        marker = os.path.join(cfg.train.log_dir, PREEMPT_MARKER_NAME)
        if is_coord and os.path.exists(marker):
            # the marker's job (tell the scheduler/operator a clean resume
            # point exists) is done once the resume actually happened
            os.remove(marker)
            log.log("preemption resume marker consumed")
    else:
        log.mark_fresh_run()  # truncate metrics.jsonl: steps restart at 0
        trainer, ts = _init_or_warm_start(cfg, net, mesh, log, rng)

    start_step = int(ts.step)
    local_batch = mesh_lib.local_batch_slice(cfg.train.batch_size, mesh)
    if cfg.train.faults.enable:
        # seeded train-side chaos (train/faults.py): wraps the RAW stream so
        # injected corrupt records travel the real resilience path
        from ..train.faults import FaultyTrainSource

        train_src = data_lib.make_train_source(
            cfg.data, local_batch, cfg.train.seed, jax.process_index(), jax.process_count(),
            start_step=start_step,
            inject=lambda it: FaultyTrainSource.from_config(it, cfg.train.faults,
                                                            start_step=start_step),
        )
    else:
        train_src = data_lib.make_train_source(
            cfg.data, local_batch, cfg.train.seed, jax.process_index(), jax.process_count(),
            # resume continues the data order at the restored step (each
            # global step consumed exactly one local batch per host)
            start_step=start_step,
        )
    train_iter = mesh_lib.prefetch_to_mesh(train_src, mesh, depth=cfg.data.device_prefetch)

    # step health guard (train/guard.py): the device half is already wrapped
    # into the compiled step (parallel/dp.py); this is the host accounting
    guard = None
    if cfg.train.guard.enable:
        from ..train.guard import StepGuard

        guard = StepGuard(cfg.train.guard, cfg.train.log_dir if is_coord else None, log)
        if watchdog is not None:
            watchdog.register_info("train_guard", guard.info)

    preempt = _Preemption(log).install()
    preempted = False

    total_epochs = cfg.train.epochs
    spe = trainer.steps_per_epoch
    metric_log = MetricLogger()
    eval_result: dict = {}
    epoch = start_epoch
    best_top1 = float(restored[2].get("best_top1", 0.0)) if restored is not None else 0.0
    host_step = int(ts.step)  # one sync at (re)start, then host-side counting
    trace_active = False
    # integer-step cadences (exact boundaries under fractional epochs/resume)
    eval_cad = StepCadence(cfg.train.eval_every_epochs, spe, host_step)
    ckpt_cad = StepCadence(cfg.train.checkpoint_every_epochs, spe, host_step)
    remat_cad = StepCadence(cfg.prune.remat_epochs, spe, host_step)
    best_ckpt: CheckpointManager | None = None  # created on first new-best eval

    # device-cost accounting fires once per compiled step program: on the
    # first dispatch, and again after a rematerialize rebuild (new shapes =>
    # new executable => new cost)
    cost_recorded = not is_coord
    compile_watch = obs_device.install_compile_watch()  # compile_cache.configure() put it in: this is the handle

    try:
        while epoch < total_epochs:
            epoch_steps = min(spe, max(int((total_epochs - epoch) * spe), 1))
            t_epoch = time.perf_counter()
            steps_done = 0
            while steps_done < epoch_steps:
                if preempt.requested:
                    preempted = True
                    break
                with tracer.span("data/next", "data"):
                    b = next(train_iter)  # already on-mesh (prefetch_to_mesh)
                t_dispatch0 = time.perf_counter()
                watch_mark = None if cost_recorded else compile_watch.mark()
                with tracer.span("dispatch/train_step", "dispatch"):
                    ts, metrics = trainer.train_step(ts, b, rng)
                if not cost_recorded:
                    cost_recorded = True
                    _record_step_cost(
                        trainer, ts, b, rng, reg, tracer, log,
                        time.perf_counter() - t_dispatch0, compile_watch.since(watch_mark),
                        scope_table_dir=cfg.train.log_dir + "/trace" if cfg.train.profile_start_step else "")
                steps_done += 1
                # the metrics entries are lazy device arrays: nothing below
                # syncs unless a cadence fires. The counter is the host's own:
                # int(ts.step) would sync the host with the device every step
                # and stall async dispatch
                host_step += 1
                step_i = host_step
                metric_log.update(metrics, batch_images=cfg.train.batch_size)
                if guard is not None:
                    guard.observe(step_i, metrics)  # lazy stash; no sync
                if watchdog is not None:
                    watchdog.arm(step_i)

                if cfg.train.profile_start_step and is_coord:
                    if step_i == cfg.train.profile_start_step:
                        # stop is finally-guaranteed (YAMT013): the close
                        # below runs in a finally, and the loop's outer
                        # finally flushes a window still open on ANY exit
                        jax.profiler.start_trace(cfg.train.log_dir + "/trace")
                        trace_active = True
                    elif trace_active and step_i >= cfg.train.profile_start_step + cfg.train.profile_num_steps:
                        try:
                            # barrier before closing the trace: dispatch is
                            # async, so without a device->host read of a
                            # value that depends on the last step the window
                            # would close while that step is still running
                            jax.device_get(metrics["loss"])
                        finally:
                            # a failed barrier sync must still close the
                            # window HERE (the old code left it running
                            # until the outer finally, capturing the whole
                            # unwind into the trace)
                            jax.profiler.stop_trace()
                            trace_active = False
                        log.log(f"profiler trace captured to {cfg.train.log_dir}/trace")

                if (
                    trainer.prune_event is not None
                    and step_i % cfg.prune.mask_interval == 0
                    and step_i <= trainer.prune_stop_step
                ):
                    # the whole event (reached-target check via in-jit
                    # effective MACs, adaptive-rho feedback — SURVEY.md
                    # §2 #11, conditional mask update) runs on device;
                    # the host gate above only skips the off-cadence
                    # dispatches (the event's own step gate is true
                    # exactly when this condition is)
                    with tracer.span("prune/mask_event", "prune", step=step_i):
                        masks, rho_mult = trainer.prune_event(
                            ts.params, ts.masks, ts.rho_mult, ts.step)
                        ts = ts.replace(masks=masks, rho_mult=rho_mult)

                if step_i % cfg.train.log_every == 0:
                    # the log-boundary host sync: snapshot float()s every
                    # pending metric (blocks on the last dispatched step)
                    with tracer.span("sync/log_metrics", "sync", step=step_i):
                        snap = metric_log.snapshot_and_reset(num_chips=trainer.mesh.size)
                    reg.gauge("train.step").set(step_i)
                    if isinstance(trainer.net, TokenModel):
                        # a sequence is this loop's "image"; the expert layer's counters
                        # (ops/lm.py) and a looped model's exit statistics are step scalars like any other
                        reg.gauge("train.tokens_per_s").set(
                            snap.get("images_per_sec", 0.0) * trainer.net.lm.seq_len)
                        for name in ("moe_assignments_here", "moe_load_max_over_mean", "moe_dropped", "moe_bounded_sites",
                                     "kda_min_chunk_log_decay", "ssd_min_chunk_log_decay", "expected_exit_step",
                                     "exit_p_last", "exit_entropy"):
                            if name in snap:
                                reg.gauge("train." + name).set(snap[name])
                    if cfg.prune.enable:
                        snap["effective_macs"] = masking.mask_summary(trainer.net, ts.masks)["effective_macs"]
                        if cfg.prune.rho_schedule == "adaptive":
                            # adaptation lives on device now; one host
                            # sync per log boundary, not per event
                            with tracer.span("sync/rho_mult", "sync"):
                                snap["rho_mult"] = float(jax.device_get(ts.rho_mult))
                            reg.counter("train.forced_host_syncs").inc()
                    # (decode failures now flow through the registry: the
                    # native loader registers a data.decode_failures pull
                    # gauge that every scalars row snapshots)
                    log.log(format_metrics(f"step {step_i}:", snap))
                    log.scalars(step_i, snap, "train/")
                    if guard is not None:
                        # the guard already rolled back any non-finite
                        # step on device; here it counts the skips and
                        # enforces the budget (train/guard.py) — may
                        # raise TrainHealthError with train_health.json
                        guard.check(step_i)
                    elif snap.get("finite", 1.0) < 1.0:
                        log.error("non-finite loss detected; aborting")
                        raise FloatingPointError("non-finite loss")
                if cfg.train.check_finite_every and step_i % cfg.train.check_finite_every == 0:
                    # forced host sync — a debug guard, off by default
                    with tracer.span("sync/finite_check", "sync", step=step_i):
                        finite = float(metrics["finite"])
                    reg.counter("train.forced_host_syncs").inc()
                    if finite < 1.0:
                        log.error(f"non-finite loss at step {step_i}")
                        raise FloatingPointError("non-finite loss")
                if cfg.train.param_checksum_every and step_i % cfg.train.param_checksum_every == 0:
                    with tracer.span("sync/replica_checksum", "sync", step=step_i):
                        div = float(trainer.sync_check(ts.params))
                    reg.counter("train.forced_host_syncs").inc()
                    if div != 0.0:
                        log.error(f"replica divergence {div} at step {step_i}")
                        raise RuntimeError("replica divergence")
            if preempted:
                epoch = host_step / spe  # exact mid-epoch position
                log.log(f"preemption ({preempt.reason}): stopping at step {host_step} "
                        f"(epoch {epoch:.2f})")
                break
            epoch += epoch_steps / spe
            log.log(f"epoch {epoch:.2f} done in {time.perf_counter()-t_epoch:.1f}s")

            # coarse-cadence physical shrink (recompile paid here, not per-step)
            if cfg.prune.enable and remat_cad.due(host_step):
                old_trainer = trainer
                with tracer.span("rebuild/rematerialize", "rebuild", step=host_step):
                    trainer, ts = _maybe_rematerialize(trainer, ts, log)
                if trainer is not old_trainer:
                    reg.counter("train.rebuilds").inc()
                    cost_recorded = not is_coord  # new executable: re-account its cost
                if watchdog is not None:
                    watchdog.arm(host_step, phase="rematerialize")

            # final eval AND final checkpoint always run, symmetrically, even
            # with the periodic knobs set to 0
            final = epoch >= total_epochs
            if eval_cad.due(host_step) or final:
                eval_result = evaluate(trainer, ts, cfg, watchdog=watchdog)
                if eval_result["top1"] > best_top1:  # reference: best-acc tracking
                    best_top1 = eval_result["top1"]
                    if cfg.train.keep_best:
                        # single-slot best checkpoint (reference: best.pth) —
                        # separate dir so resume always uses the latest while
                        # the best stays evaluable via train.pretrained
                        if best_ckpt is None:
                            best_ckpt = CheckpointManager(
                                cfg.train.log_dir + "/ckpt_best", max_to_keep=1, barrier_prefix="best"
                            )
                            best_box.append(best_ckpt)  # shutdown wait/close (_run_impl)
                        best_ckpt.save(
                            int(ts.step), trainer.net, jax.device_get(trainer.checkpoint_view(ts)),
                            extra={"epoch": epoch, "best_top1": best_top1},
                        )
                eval_result["best_top1"] = best_top1
                log.log(format_metrics(f"eval @ epoch {epoch:.2f}:", eval_result))
                log.scalars(int(ts.step), eval_result, "eval/")
                if watchdog is not None:
                    watchdog.arm(host_step, phase="eval")

            if ckpt_cad.due(host_step) or final:
                # orbax coordinates multi-host saves internally; every process
                # calls in. device_get: the async save must not read buffers
                # the next step will donate. checkpoint_view makes the tree
                # fully replicated first, so the host copy is multi-host-safe.
                ckpt.save(
                    int(ts.step), trainer.net, jax.device_get(trainer.checkpoint_view(ts)),
                    extra={"epoch": epoch, "best_top1": best_top1},
                )
                if watchdog is not None:
                    watchdog.arm(host_step, phase="checkpoint")

    finally:
        preempt.uninstall()
        if trace_active:
            # training ended (or raised) inside the capture window: flush
            # the trace rather than losing it — and never let a failing
            # stop mask the exception that got us here
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — best-effort flush on unwind
                log.log(f"profiler stop on exit failed ({type(e).__name__}: {e})")

    if guard is not None:
        guard.check(host_step)  # flush verdicts the last log window missed

    if preempted:
        # final SYNCHRONOUS checkpoint: save, then WAIT — the process exits
        # right after, so an async enqueue alone could be reaped half-written
        # (exactly the torn state the digest sidecar would then reject)
        log.log(f"preemption checkpoint: saving step {host_step} synchronously")
        ckpt.save(
            host_step, trainer.net, jax.device_get(trainer.checkpoint_view(ts)),
            extra={"epoch": epoch, "best_top1": best_top1, "preempted": True},
        )
        ckpt.wait()
        reg.counter("train.preemptions").inc()
        if is_coord:
            marker = {
                "step": host_step,
                "epoch": epoch,
                "reason": preempt.reason,
                "checkpoint_dir": cfg.train.log_dir + "/ckpt",
            }
            marker_path = os.path.join(cfg.train.log_dir, PREEMPT_MARKER_NAME)
            tmp = f"{marker_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(marker, f, indent=1)
            os.replace(tmp, marker_path)
            log.log(f"resume marker -> {marker_path}; restart with train.resume=true "
                    "to continue from here")
        final = {"epoch": epoch, "step": host_step, "preempted": True,
                 **{f"eval_{k}": v for k, v in eval_result.items()}}
        log.log(format_metrics("preempted:", final))
        return final

    if cfg.prune.enable:
        # apply any remaining masks physically and emit the searched result
        # as a standalone spec (reference: 'final architecture == surviving
        # channels; emit as block-spec', SURVEY.md §3.2)
        with tracer.span("rebuild/rematerialize", "rebuild", step=host_step):
            trainer, ts = _maybe_rematerialize(trainer, ts, log)
        from ..models.serialize import network_to_dict

        prof_final = profile_network(trainer.net)
        if is_coord:
            payload = {
                "network": network_to_dict(trainer.net),
                "macs": int(prof_final.total_macs),
                "params": int(prof_final.total_params),
                "step": int(ts.step),
            }
            path = os.path.join(cfg.train.log_dir, "searched_arch.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=1)
            log.log(
                f"searched architecture -> {path} "
                f"({prof_final.total_macs/1e6:.1f}M MACs, {prof_final.total_params/1e6:.2f}M params)"
            )

    # manager wait+close happens in _run_impl's finally — on THIS path and on
    # every error path, wait always precedes close (an in-flight async save
    # is never abandoned half-written)
    final = {"epoch": epoch, **{f"eval_{k}": v for k, v in eval_result.items()}}
    log.log(format_metrics("done:", final))
    return final


def main(argv=None):
    cfg = parse_cli(sys.argv[1:] if argv is None else argv)
    return run(cfg)


if __name__ == "__main__":
    main()
