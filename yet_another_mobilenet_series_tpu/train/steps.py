"""The jitted train/eval step (reference: train.py run_one_epoch inner loop,
SURVEY.md §3.1).

The reference's per-step sequence — forward, CE+penalty, backward, DDP
allreduce, optimizer step, LR step, EMA update — becomes ONE XLA program:
grads are pmean'd over the 'data' mesh axis inside the step (replacing NCCL
bucketed allreduce), BN stats psum via axis_name (replacing apex SyncBN), and
the EMA/LR updates are fused in (replacing the Python-side loop bodies).
"""

from __future__ import annotations

from typing import Any, Callable, Mapping

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax import lax

from ..config import Config
from ..models.lm import TokenModel
from ..models.specs import Network
from ..obs.registry import get_registry
from ..obs.scopes import scope
from .ema import ema_update
from .losses import cross_entropy_label_smooth, topk_correct


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    state: Any  # BN running stats; a token model's router biases
    opt_state: Any
    ema_params: Any  # None when EMA disabled
    ema_state: Any
    masks: Any  # {} when pruning disabled; {block_idx(str): (expanded,)} else
    # adaptive rho multiplier (nas/penalty.py); None when pruning disabled.
    # Lives in TrainState so adaptation survives checkpoint/resume.
    rho_mult: Any = None


# single source of truth for the checkpoint tree layout (ckpt/manager.py and
# resume both build from this; adding a TrainState field updates every site)
TRAIN_STATE_FIELDS = ("step", "params", "state", "opt_state", "ema_params", "ema_state", "masks", "rho_mult")


def train_state_to_dict(ts: TrainState) -> dict:
    return {k: getattr(ts, k) for k in TRAIN_STATE_FIELDS}


def init_train_state(
    net: Network | TokenModel, cfg: Config, optimizer: optax.GradientTransformation, rng, *, with_opt: bool = True
) -> TrainState:
    """with_opt=False leaves opt_state None — the ZeRO path builds its
    sharded accumulators on the mesh instead (parallel/zero.py)."""
    params, state = net.init(rng)
    opt_state = optimizer.init(params) if with_opt else None
    # Real copies: the shadow must not alias the live buffers (aliasing breaks
    # buffer donation of the whole TrainState).
    ema_p = jax.tree.map(jnp.copy, params) if cfg.ema.enable else None
    ema_s = jax.tree.map(jnp.copy, state) if cfg.ema.enable else None
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        state=state,
        opt_state=opt_state,
        ema_params=ema_p,
        ema_state=ema_s,
        masks={},
        rho_mult=jnp.ones((), jnp.float32) if cfg.prune.enable else None,
    )


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def _input_normalizer(cfg: Config):
    """Returns prep(image) -> compute-dtype array. Under
    data.transfer_uint8 the pipeline ships raw uint8 pixels (4x less
    host->device volume; DataConfig comment has the bandwidth math) and
    THIS applies the identical f32 normalize expression the host path uses
    (pipeline._normalize) on device, where XLA fuses it into the first
    conv's input chain. f32 sub/div are exactly rounded IEEE ops, so for
    the same u8 input the two paths agree bitwise; the only path delta is
    the u8 rounding of post-augment float pixels (<=0.5/255, pinned by
    tests/test_data.py)."""
    compute_dtype = _dtype(cfg.train.compute_dtype)
    if not cfg.data.transfer_uint8:
        return lambda image: image.astype(compute_dtype)
    mean = jnp.asarray(cfg.data.mean, jnp.float32)
    std = jnp.asarray(cfg.data.std, jnp.float32)

    def prep(image):
        x = image.astype(jnp.float32) / 255.0
        return ((x - mean) / std).astype(compute_dtype)

    return prep


def make_batch_mixer(cfg: Config):
    """Mixup/CutMix as an IN-STEP device op (beyond reference parity).

    GPU codebases mix on the host dataloader; here the mix lives inside the
    jitted step — zero host cost, fused by XLA, and under shard_map each
    replica draws a decorrelated permutation of its LOCAL shard (the step
    rng already folds in the axis index, parallel/dp.py), which is the
    standard device-local mixup. Returns None when both alphas are 0, so
    disabled configs keep the exact pre-mixup program.

    mix(rng, x, labels) -> (x_mixed, labels_b, lam): per-batch lam ~
    Beta(alpha, alpha); CutMix pastes a (H*sqrt(1-lam), W*sqrt(1-lam)) box
    from the permuted batch, clipped at the borders, and returns lam
    ADJUSTED to the actual pasted area (arXiv:1905.04899 §3.1). When both
    alphas are set, each step picks one with p=0.5 (the timm convention).
    """
    m_a, c_a = cfg.optim.mixup_alpha, cfg.optim.cutmix_alpha
    if m_a < 0 or c_a < 0:
        raise ValueError(f"mixup/cutmix alphas must be >= 0, got {m_a}/{c_a}")
    if m_a == 0 and c_a == 0:
        return None

    def mix(rng, x, labels):
        r_sel, r_lam_m, r_lam_c, r_perm, r_box = jax.random.split(rng, 5)
        n, h, w = x.shape[0], x.shape[1], x.shape[2]
        perm = jax.random.permutation(r_perm, n)
        x_b, y_b = x[perm], labels[perm]

        use_cutmix = (
            jax.random.bernoulli(r_sel, 0.5)
            if (m_a > 0 and c_a > 0)
            else jnp.asarray(c_a > 0)
        )

        # mixup half
        lam_m = jax.random.beta(r_lam_m, m_a, m_a) if m_a > 0 else jnp.float32(1.0)
        x_mix = lam_m.astype(x.dtype) * x + (1.0 - lam_m).astype(x.dtype) * x_b

        # cutmix half: box centered uniformly, side = dim * sqrt(1 - lam)
        lam_c = jax.random.beta(r_lam_c, c_a, c_a) if c_a > 0 else jnp.float32(1.0)
        cut = jnp.sqrt(1.0 - lam_c)
        rh, rw = jnp.round(h * cut), jnp.round(w * cut)
        cy = jax.random.randint(r_box, (), 0, h)
        cx = jax.random.fold_in(r_box, 1)
        cx = jax.random.randint(cx, (), 0, w)
        iy = jnp.arange(h)[None, :, None, None]
        ix = jnp.arange(w)[None, None, :, None]
        in_box = (
            (iy >= cy - rh // 2) & (iy < cy + (rh + 1) // 2)
            & (ix >= cx - rw // 2) & (ix < cx + (rw + 1) // 2)
        )
        x_cut = jnp.where(in_box, x_b, x)
        # actual pasted fraction (border clipping makes it < (1-lam_c))
        frac = jnp.mean(in_box.astype(jnp.float32))
        lam_cut = 1.0 - frac

        x_out = jnp.where(use_cutmix, x_cut, x_mix)
        lam = jnp.where(use_cutmix, lam_cut, lam_m).astype(jnp.float32)
        return x_out, y_b, lam

    return mix


def _image_loss(net: Network, cfg: Config, axis_name: str | None, penalty_fn):
    """The CNN family's loss and its reported scalars: `loss_fn(params, state,
    batch, masks, rho_mult, step, rng) -> (loss, (new_state, aux))` and
    `report(aux, batch, grads) -> scalars`."""
    compute_dtype = _dtype(cfg.train.compute_dtype)
    # dist.sync_bn=False: per-replica batch statistics in the NORMALIZATION
    # (grad allreduce still uses axis_name) — the reference's non-SyncBN DDP
    # mode. DDP broadcasts rank 0's buffers, so the updated running stats are
    # explicitly broadcast from device 0 in the step; without that the
    # "replicated" state would silently diverge across replicas (and hosts).
    bn_axis = axis_name if cfg.dist.sync_bn else None

    def forward(params, state, image, masks, rng):
        imasks = {int(k): v for k, v in masks.items()} or None
        return net.apply(
            params,
            state,
            image,
            train=True,
            axis_name=bn_axis,
            compute_dtype=compute_dtype,
            masks=imasks,
            rng=rng,
        )

    # how often ops/layers.py's conv + BN pair engages in this step: decided
    # there from each site's shape alone, reported here
    pairs, eligible = net.conv_bn_pair_sites()
    get_registry().gauge("train.conv_bn_pairs").set(pairs)
    get_registry().gauge("train.conv_bn_pair_eligible").set(eligible)
    if cfg.train.remat:
        # recompute activations during backward: HBM for FLOPs
        # (jax.checkpoint; SURVEY.md §0 HBM-bandwidth note)
        forward = jax.checkpoint(forward)

    prep_input = _input_normalizer(cfg)
    mixer = make_batch_mixer(cfg)

    # The named scopes below and in ops/ are the step's half of obs/scopes.py:
    # metadata only, they name the compiled step's operations for a device
    # trace and add none.
    def loss_fn(params, state, batch, masks, rho_mult, step, rng):
        with scope("input"):
            x = prep_input(batch["image"])
            if mixer is not None:
                # distinct stream from the forward's dropout/drop-path rngs
                # (blocks fold small indices, classifier uses the raw key)
                x, label_b, lam = mixer(jax.random.fold_in(rng, 0x6D6978), x, batch["label"])
        logits, new_state = forward(params, state, x, masks, rng)
        with scope("loss"):
            ce = cross_entropy_label_smooth(logits, batch["label"], cfg.optim.label_smoothing)
            if mixer is not None:
                # CE is linear in the target distribution, so the convex label
                # combination IS the convex loss combination (smoothing included)
                ce = lam * ce + (1.0 - lam) * cross_entropy_label_smooth(
                    logits, label_b, cfg.optim.label_smoothing)
        if penalty_fn is not None:
            with scope("nas_penalty"):
                pen = penalty_fn(params, masks, rho_mult=rho_mult, step=step)
        else:
            pen = jnp.zeros((), jnp.float32)
        return ce + pen, (new_state, (logits, ce, pen))

    def report(aux, batch, grads):
        logits, ce, pen = aux
        correct = topk_correct(logits, batch["label"], ks=(1,))["top1"]
        n = jnp.asarray(logits.shape[0], jnp.float32)
        return {"ce": ce, "penalty": pen, "top1": correct / n}

    return loss_fn, report, bn_axis


def _token_loss(net: TokenModel, cfg: Config, axis_name: str | None, platform: str | None):
    """The token family's loss (models/lm.py `TokenModel.loss`) in the same
    two pieces. Its state (router biases) is made identical across replicas
    inside the loss, so there is no axis the step would have to repair."""
    compute_dtype = _dtype(cfg.train.compute_dtype)
    if cfg.prune.enable or cfg.optim.mixup_alpha or cfg.optim.cutmix_alpha:
        raise ValueError(f"model.arch {net.arch!r} is a token model: prune.enable, mixup and cutmix are image-only")
    # how many softmax-attention layers this step lowers through ops/lm_attention.py's fused kernels. A
    # PREDICTION of the lowering, not a reading of it: ops/lm.py decides from each call's shapes (the
    # same predicate) and the platform the step is in fact lowered for; here `platform` stands for that
    sites, fitting = net.attention_sites(compute_dtype)
    get_registry().gauge("train.attn_sites").set(sites)
    on_tpu = (platform or jax.default_backend()) == "tpu"
    get_registry().gauge("train.attn_fused_sites").set(fitting if on_tpu else 0)
    # every block is one mixer under one layer checkpoint, which keeps, by name and whatever the lowering, an
    # attention's output and row log-sum-exp, a KDA scan's output and states (models/lm.py `forward`): all of
    # them, on every platform
    get_registry().gauge("train.attn_kept_sites").set(sites)
    # of those, the layers within a sliding window, and those whose windowed core goes through the window's kernels
    get_registry().gauge("train.attn_window_sites").set(net.window_sites)
    get_registry().gauge("train.attn_window_fused_sites").set(net.window_fitting_sites(compute_dtype) if on_tpu else 0)
    get_registry().gauge("train.kda_sites").set(net.kda_sites)
    get_registry().gauge("train.kda_kept_sites").set(net.kda_sites)
    # the KDA layers whose in-chunk work this step lowers through ops/lm_kda_kernels.py's two fused kernels: the same
    # kind of prediction as `train.attn_fused_sites` (ops/lm_kda.py `fuses` on the shapes, `platform` for the lowering)
    get_registry().gauge("train.kda_fused_sites").set(net.kda_fitting_sites(compute_dtype) if on_tpu else 0)
    # and those whose three short convolutions (with q's and k's L2 norms) go through its two conv kernels (`conv_fuses`)
    get_registry().gauge("train.kda_conv_fused_sites").set(net.kda_conv_fitting_sites(compute_dtype) if on_tpu else 0)
    # and those whose scan over the chunks goes through its two scan kernels (the same shapes as the in-chunk work's)
    get_registry().gauge("train.kda_scan_fused_sites").set(net.kda_fitting_sites(compute_dtype) if on_tpu else 0)
    # the Mamba-2 layers (ops/lm_mamba.py), all of whose chunk-boundary states the layer checkpoint keeps by name, and
    # those whose xBC convolution goes through the conv kernels (a prediction, as `train.kda_conv_fused_sites` is)
    get_registry().gauge("train.ssd_sites").set(net.ssd_sites)
    get_registry().gauge("train.ssd_kept_sites").set(net.ssd_sites)
    get_registry().gauge("train.ssd_conv_fused_sites").set(net.ssd_conv_fitting_sites(compute_dtype) if on_tpu else 0)
    # and those whose in-chunk SSD work goes through ops/lm_mamba_kernels.py's two kernels (`lm_mamba.fuses`)
    get_registry().gauge("train.ssd_fused_sites").set(net.ssd_fitting_sites(compute_dtype) if on_tpu else 0)
    # the expert layers, each one `lax.cond` between the rows it holds and every assignment (ops/lm.py)
    get_registry().gauge("train.moe_sites").set(net.expert_sites)
    # a looped model runs its layers `loop_steps` times a step with the same weights: the sites above are LAYERS,
    # the checkpoints' kept tensors are counted by applications (1 and the number of blocks for every other arch)
    get_registry().gauge("train.loop_steps").set(net.loop_steps)
    get_registry().gauge("train.layer_applications").set(net.layer_applications)

    def loss_fn(params, state, batch, masks, rho_mult, step, rng):
        # read where the step is traced, from one replica's batch: the rows of a site's bounded branch
        get_registry().gauge("train.moe_capacity_rows").set(net.expert_capacity_rows(batch["tokens"].shape[0]))
        return net.loss(params, state, batch, compute_dtype=compute_dtype, axis_name=axis_name)

    def report(aux, batch, grads):
        return {**aux, **net.grad_scalars(grads)}

    return loss_fn, report, axis_name


def make_train_step(
    net: Network | TokenModel,
    cfg: Config,
    optimizer: optax.GradientTransformation,
    lr_fn: Callable,
    *,
    axis_name: str | None = None,
    penalty_fn: Callable[[Any, Mapping[str, Any]], jax.Array] | None = None,
    sharded_update: Callable | None = None,
    platform: str | None = None,
):
    """Returns step_fn(ts, batch, rng) -> (ts, metrics): ONE skeleton
    (gradients, their sync, the optimizer, EMA, the reported scalars) around
    the family's loss, `(loss, (new_state, aux))`: `_image_loss` for a
    `Network` (``batch`` = {'image': (N,H,W,C), 'label': (N,)}), `_token_loss`
    for a `TokenModel` ({'tokens': (N, seq_len + 2)}), already on device.

    ``penalty_fn(params, masks)`` is the AtomNAS FLOPs-weighted BN-gamma L1
    hook (SURVEY.md §3.2); None for plain training.

    ``sharded_update(grads_local, opt_state_shard, params)`` replaces the
    replicated pmean+optax update with the ZeRO cross-replica sharded update
    (parallel/zero.py); it receives un-averaged local grads (the mean rides
    the psum_scatter).

    ``platform`` is that of the devices the step will be lowered for (the
    mesh's, which parallel/dp.py hands in; the default backend's when left
    out). It moves nothing in the step: the gauges that PREDICT which
    lowering a platform-dependent piece takes read it
    (`train.attn_fused_sites`: the attention layers through the kernels of
    ops/lm_attention.py; `train.kda_fused_sites` / `train.kda_conv_fused_sites` /
    `train.kda_scan_fused_sites`: the KDA layers whose in-chunk work / short
    convolutions / scan over the chunks go through those of ops/lm_kda.py; `train.ssd_conv_fused_sites` / `train.ssd_fused_sites`: the
    Mamba-2 layers whose xBC convolution / in-chunk SSD work does, the latter
    through ops/lm_mamba_kernels.py). A step built without
    it and then compiled ahead of time for another platform than the default
    backend's reports the default backend's count.
    """
    if isinstance(net, TokenModel):
        loss_fn, report, bn_axis = _token_loss(net, cfg, axis_name, platform)
    else:
        loss_fn, report, bn_axis = _image_loss(net, cfg, axis_name, penalty_fn)

    def step_fn(ts: TrainState, batch, rng):
        rng = jax.random.fold_in(rng, ts.step)
        (loss, (new_state, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            ts.params, ts.state, batch, ts.masks, ts.rho_mult, ts.step, rng
        )
        if axis_name is not None and bn_axis is None:
            # non-SyncBN mode: restore the replication invariant by
            # broadcasting device 0's updated running stats (DDP rank-0
            # buffer semantics, globally — incl. multi-host)
            idx = lax.axis_index(axis_name)
            with scope("syncbn"):
                new_state = jax.tree.map(
                    lambda s: lax.psum(jnp.where(idx == 0, s, jnp.zeros_like(s)), axis_name), new_state
                )
        if sharded_update is not None:
            new_params, new_opt_state, grad_norm = sharded_update(grads, ts.opt_state, ts.params)
        else:
            if axis_name is not None:
                with scope("grad_sync"):
                    grads = lax.pmean(grads, axis_name)
            with scope("optim"):
                updates, new_opt_state = optimizer.update(grads, ts.opt_state, ts.params)
                new_params = optax.apply_updates(ts.params, updates)
                grad_norm = optax.global_norm(grads)
        with scope("ema"):
            new_ema_p = ema_update(cfg.ema, ts.ema_params, new_params, ts.step) if cfg.ema.enable else None
            new_ema_s = ema_update(cfg.ema, ts.ema_state, new_state, ts.step) if cfg.ema.enable else None

        # the step's reported scalars are part of `loss`
        with scope("loss"):
            metrics = {
                "loss": loss,
                **report(aux, batch, grads),
                "lr": lr_fn(ts.step),
                "grad_norm": grad_norm,
                "finite": jnp.isfinite(loss).astype(jnp.float32),
            }
            if axis_name is not None:
                metrics = {k: lax.pmean(v, axis_name) for k, v in metrics.items()}
        new_ts = ts.replace(
            step=ts.step + 1,
            params=new_params,
            state=new_state,
            opt_state=new_opt_state,
            ema_params=new_ema_p,
            ema_state=new_ema_s,
        )
        return new_ts, metrics

    return step_fn


def make_eval_step(net: Network | TokenModel, cfg: Config, *, axis_name: str | None = None):
    """Returns eval_fn(params, state, batch, masks) -> summed metric counts
    {'top1','top5','n','loss_sum'} — allreduce-able AverageMeter counts
    (SURVEY.md §2 #13). Runs on EMA shadow weights when the caller passes
    them (reference: eval-on-shadow, SURVEY.md §2 #8)."""
    compute_dtype = _dtype(cfg.train.compute_dtype)
    if isinstance(net, TokenModel):
        # the main head's next-token counts, in the same four sums

        def eval_tokens(params, state, batch, masks):
            metrics = net.eval_counts(params, state, batch, compute_dtype=compute_dtype)
            if axis_name is not None:
                metrics = {k: lax.psum(v, axis_name) for k, v in metrics.items()}
            return metrics

        return eval_tokens
    prep_input = _input_normalizer(cfg)

    def eval_fn(params, state, batch, masks):
        imasks = {int(k): v for k, v in masks.items()} or None
        logits, _ = net.apply(
            params,
            state,
            prep_input(batch["image"]),
            train=False,
            compute_dtype=compute_dtype,
            masks=imasks,
        )
        labels = batch["label"]
        # padded examples carry label -1: mask them out of every count
        valid = (labels >= 0).astype(jnp.float32)
        safe_labels = jnp.maximum(labels, 0)
        k = min(5, logits.shape[-1])
        _, pred = lax.top_k(logits, k)
        hit = (pred == safe_labels[:, None]) & (valid[:, None] > 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe_labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
        metrics = {
            "top1": jnp.sum(hit[:, :1]).astype(jnp.float32),
            "top5": jnp.sum(hit).astype(jnp.float32),
            "n": jnp.sum(valid),
            "loss_sum": jnp.sum(nll * valid),
        }
        if axis_name is not None:
            metrics = {k: lax.psum(v, axis_name) for k, v in metrics.items()}
        return metrics

    return eval_fn
