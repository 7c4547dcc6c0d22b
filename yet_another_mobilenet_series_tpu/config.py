"""Typed, immutable experiment configuration.

Replaces the reference's ``utils/config.py`` global-``FLAGS`` AttrDict
(SURVEY.md §2 #2) with frozen dataclasses passed explicitly.  The YAML surface
stays reference-compatible in spirit:

- experiments live in ``apps/*.yml`` and are selected with an ``app:<path>``
  CLI argument,
- a YAML file may inherit from another via a top-level ``_base_: <relpath>``
  key (deep-merged, child wins),
- remaining CLI args of the form ``a.b.c=value`` override individual keys.

Unknown keys are an error — silent typos in a 350-epoch run are expensive.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field, fields
from typing import Any, Mapping, Sequence

import yaml

# ---------------------------------------------------------------------------
# YAML loading with _base_ inheritance
# ---------------------------------------------------------------------------


def _deep_merge(base: dict, override: dict) -> dict:
    """Recursively merge ``override`` into ``base`` (override wins)."""
    out = dict(base)
    for k, v in override.items():
        if k in out and isinstance(out[k], dict) and isinstance(v, dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def load_yaml(path: str, _seen: tuple = ()) -> dict:
    """Load a YAML file, resolving ``_base_`` inheritance chains."""
    path = os.path.abspath(path)
    if path in _seen:
        raise ValueError(f"circular _base_ inheritance: {path}")
    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    if not isinstance(raw, dict):
        raise ValueError(f"{path}: top-level YAML must be a mapping")
    base_rel = raw.pop("_base_", None)
    if base_rel is not None:
        base_path = os.path.join(os.path.dirname(path), base_rel)
        base = load_yaml(base_path, _seen + (path,))
        raw = _deep_merge(base, raw)
    return raw


# ---------------------------------------------------------------------------
# Config schema
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Architecture selection.

    ``arch`` names a built-in block-spec (models/zoo.py); ``block_specs``
    overrides it with an explicit list (the reference expressed searched /
    supernet architectures as YAML block-spec lists, SURVEY.md §2 #5 #14).
    """

    arch: str = "mobilenet_v2"
    num_classes: int = 1000
    width_mult: float = 1.0
    dropout: float = 0.2
    # Explicit block specs override `arch`. Each entry is a mapping accepted
    # by models.specs.BlockSpec.from_dict.
    block_specs: Sequence[Mapping[str, Any]] | None = None
    # Path to a serialized Network (e.g. a search run's searched_arch.json);
    # overrides arch/block_specs entirely — this is how an emitted AtomNAS
    # result is trained/evaluated as a standalone model.
    network_spec: str = ""
    # Stem / head channel overrides (None = arch default).
    # EXACT final widths when set — exempt from width_mult scaling
    # (models/specs.py build_network); None = the arch default, scaled
    stem_channels: int | None = None
    head_channels: int | None = None
    feature_channels: int | None = None
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    # Stochastic-depth max rate (EfficientNet drop_connect); None = the
    # arch's default (0 everywhere except efficientnet_b0's paper 0.2).
    # Per-block rates ramp linearly with depth (models/specs.py).
    drop_connect: float | None = None
    # Overrides the arch's default activation when set (e.g. swish for the
    # AtomNAS "+" variants); None = keep the arch's own default.
    active_fn: str | None = None
    # If true, classifier bias is zero-initialized (standard).
    dtype: str = "float32"  # param dtype; compute may be bf16 (train.compute_dtype)
    # shapes of a token model; read only by the archs of models/lm.py
    lm: LMConfig = field(default_factory=lambda: LMConfig())


@dataclass(frozen=True)
class LinearAttnConfig:
    """``model.lm.linear_attn_config``, as ``kimi_linear``'s ``config.json``
    has it: which layers mix by Kimi Delta Attention (ops/lm_kda.py) and which
    by latent attention, NUMBERED FROM 1 as the source numbers them, and the
    KDA heads. Layers beyond ``num_hidden_layers`` (a cut holds the first few)
    are not read. Empty lists: every layer is latent attention."""

    kda_layers: Sequence[int] = ()
    full_attn_layers: Sequence[int] = ()
    head_dim: int = 128  # of q, k and v alike: a head's state is head_dim x head_dim
    num_heads: int = 32
    short_conv_kernel_size: int = 4


@dataclass(frozen=True)
class RopeSpec:
    """One layer type's rotary embedding, under the keys of a published
    ``rope_parameters`` entry (``laguna``; ops/lm.py ``rope_tables_of``):
    ``default`` rotates at ``rope_theta``; ``yarn`` (arXiv:2309.00071) blends
    each frequency between itself and itself / ``factor`` by a ramp between
    the rotation counts ``beta_fast`` and ``beta_slow`` over
    ``original_max_position_embeddings`` positions, and scales cos and sin by
    ``attention_factor``. The first ``partial_rotary_factor`` of a head's
    channels are rotated, the rest pass through."""

    rope_type: str = "default"  # default | yarn
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class RopeParameters:
    """``model.lm.rope_parameters``: a rotary embedding by layer type, as
    ``laguna``'s ``config.json`` has it (the layer's type is its
    ``layer_types`` entry)."""

    full_attention: RopeSpec = field(default_factory=RopeSpec)
    sliding_attention: RopeSpec = field(default_factory=RopeSpec)


@dataclass(frozen=True)
class LMConfig:
    """Shapes of a token model (token family: ``glm4_moe_lite``,
    ``kimi_linear``, ``ouro``, ``granitemoehybrid``, ``laguna``; models/lm.py), under the keys of the published
    ``config.json``. The defaults are GLM-4.7-Flash's widths.
    ``model.num_classes`` is the number of vocabulary rows held here
    (embedding, head, token ids and the loss are over that slice).

    A model WITH expert layers (``glm4_moe_lite``, ``kimi_linear``, ``laguna``) is ONE
    SHARE of an expert-parallel deployment: the router keeps its published
    width ``n_routed_experts`` and its ``num_experts_per_tok``; this share
    holds ``n_routed_experts / expert_shares`` experts of every expert layer,
    those of index ``expert_share_index``, and computes their part of the
    result. What the absent experts would add is left out. A model without
    one (``ouro``, ``granitemoehybrid``: ``first_k_dense_replace`` = every
    layer) is whole, layer by layer: it reads none of the expert, latent or
    MTP keys."""

    hidden_size: int = 2048
    # dense + expert layers held here (the MTP module is counted apart)
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    # None: q is ONE projection of the hidden state (no low-rank pair, no q norm)
    q_lora_rank: int | None = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    num_nextn_predict_layers: int = 1  # 0 or 1 multi-token-prediction module
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    # true: latent attention rotates nothing; the `qk_rope_head_dim` channels stay, unrotated
    mla_use_nope: bool = False
    linear_attn_config: LinearAttnConfig = field(default_factory=lambda: LinearAttnConfig())
    expert_shares: int = 8
    expert_share_index: int = 0
    # tokens of one sequence; a batch row carries seq_len + 2 ids (the next
    # and the next-but-one token are the two heads' targets)
    seq_len: int = 8192
    mtp_loss_weight: float = 0.3
    # the router's selection bias moves by rate * sign(mean load - load) a step
    router_bias_rate: float = 1e-3
    init_std: float = 0.02
    # `ouro` alone reads the four below. Plain multi-head attention: heads of `head_dim` channels, all of them
    # rotated; as many key/value heads as query heads (None = that many; any other count is refused)
    num_key_value_heads: int | None = None
    head_dim: int | None = None
    # the layer stack runs this many times a step with the SAME weights; head, loss and exit gate after every run
    total_ut_steps: int = 1
    # beta: the loss is E_exit[CE] - beta * H(exit distribution)
    exit_entropy_weight: float = 0.1
    # `granitemoehybrid` alone reads the keys below (Granite 4.0-H; models/lm.py, ops/lm_mamba.py). Each layer's
    # mixer, in order: "mamba" (a Mamba-2 mixer: `mamba_n_heads` heads of `mamba_d_head` channels, ONE group of B and
    # C of `mamba_d_state`, a convolution of `mamba_d_conv` taps with a bias, projections without one) or
    # "attention" (grouped-query attention without rotation: `num_attention_heads` query heads and
    # `num_key_value_heads` key/value heads of `head_dim` channels); every layer's MLP is dense, `intermediate_size`
    layer_types: Sequence[str] = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # attention scores are q.k times this (None: head_dim^-0.5); the embedding's rows are scaled by
    # `embedding_multiplier`, each block's two branches by `residual_multiplier` before their residual add, and
    # the logits divided by `logits_scaling`; `tie_word_embeddings`: the head is the embedding (no `head` tensor)
    attention_multiplier: float | None = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = False
    # `laguna` alone reads the keys below (Laguna-S-2.1; models/lm.py). `layer_types` names each layer's attention,
    # "full_attention" or "sliding_attention" (a key position k is visible to query position q where
    # q - sliding_window < k <= q), with `num_attention_heads_per_layer` query heads over `num_key_value_heads` key/value
    # heads of `head_dim` channels, the rotary embedding of its type and a per-head output gate (each head's output
    # times sigmoid(x W_g) before `o`). Its expert layers route by a softmax over every expert (no router state), the
    # routed weights times `routed_scaling_factor` (the published `moe_routed_scaling_factor`), beside ONE shared
    # expert of `shared_expert_intermediate_size` times sigmoid(x . w_s)
    num_attention_heads_per_layer: Sequence[int] = ()
    sliding_window: int | None = None
    rope_parameters: RopeParameters = field(default_factory=RopeParameters)
    shared_expert_intermediate_size: int = 0


@dataclass(frozen=True)
class DataConfig:
    dataset: str = "imagenet"  # imagenet | fake | folder
    data_dir: str = ""
    train_split: str = "train"
    val_split: str = "validation"
    image_size: int = 224
    eval_resize: int = 256
    num_train_examples: int = 1281167
    num_eval_examples: int = 50000
    # fake dataset knobs (integration tests / benches without ImageNet)
    fake_num_classes: int | None = None
    fake_train_size: int = 6400
    fake_eval_size: int = 640
    # input pipeline
    loader: str = "tfdata"  # tfdata | native | synthetic | tokens
    # loader=tokens (token models; data/pipeline.py token_batches): ids of one
    # row, before the two target ids (0 = model.lm.seq_len, filled in by
    # cli/train.py), drawn by a Zipf law over the vocabulary
    seq_len: int = 0
    shuffle_buffer: int = 16384
    prefetch: int = 4  # host-side tf.data prefetch depth
    # device-HBM prefetch depth (batches pinned on the mesh ahead of compute;
    # independent of the host-side knob — each unit costs a full global batch
    # of HBM)
    device_prefetch: int = 2
    decode_threads: int = 8
    # augmentation (Inception-style random-resized-crop defaults)
    rrc_area_min: float = 0.08
    rrc_area_max: float = 1.0
    rrc_ratio_min: float = 0.75
    rrc_ratio_max: float = 1.3333333333333333
    color_jitter: float = 0.0  # brightness/contrast/saturation strength, 0=off
    # RandAugment (arXiv:1909.13719, beyond reference parity; the
    # EfficientNet recipe trains with layers=2): N stateless position-keyed
    # ops per image at magnitude M (0..10, the official _MAX_LEVEL scale).
    # tf.data pipelines only — the native C++ loader rejects it.
    randaugment_layers: int = 0  # 0 = off
    randaugment_magnitude: int = 10
    # bitwise-reproducible TFRecord streams: single-stream deterministic
    # interleave, no record shuffle buffer (the stateless (seed, epoch)
    # file permutation is the shuffle). Augmentations are stateless (keyed
    # by stream position), so resume and rebuilds reproduce PIXELS, not
    # just record order — at host decode-parallelism cost. Off = production
    # throughput with the one-buffer resume approximation
    # (data/pipeline.py make_train_dataset).
    deterministic_input: bool = False
    mean: Sequence[float] = (0.485, 0.456, 0.406)
    std: Sequence[float] = (0.229, 0.224, 0.225)
    # survive corrupt/undecodable records: a batch lost to a decode error is
    # skipped and counted (data.corrupt_records) instead of killing the run;
    # max_consecutive_failures consecutive lost batches abort loudly (a fully
    # rotten shard must not spin forever). tf.data loses the whole batch the
    # record landed in; the native C++ loader skips at record granularity and
    # counts data.decode_failures (data/pipeline.py resilient_batches).
    skip_corrupt_records: bool = True
    max_consecutive_failures: int = 16
    # host-side background prefetch thread between the pipeline and the
    # device-prefetch stage: decouples batch production from the train loop
    # and survives worker crashes with a bounded restart
    # (data/pipeline.py PrefetchWorker; crash guard per yamt-lint YAMT011)
    prefetch_thread: bool = False
    # ship images host->device as uint8 and normalize IN-STEP (on device)
    # instead of shipping normalized f32: 4x less PCIe/transfer volume. At
    # the v4-32 acceptance point the f32 feed costs ~34 GB/s/host (57k
    # img/s/host x 602 KB) — above PCIe4 x16 — while uint8 is ~8.6 GB/s
    # (BASELINE.md "transfer_uint8": also a measured 1.72x HOST pipeline
    # win — no host-side normalize, 4x smaller buffers). The reference's
    # DALI decodes on-GPU and never pays this. Cost: post-augment float
    # pixels round to u8 (<=0.5/255 quantization, under JPEG decode noise;
    # equivalence pinned by tests). Real-JPEG pipelines only (tfdata
    # TFRecords and the native C++ loader; fake data lives in normalized
    # space and is rejected at dispatch).
    transfer_uint8: bool = False


@dataclass(frozen=True)
class OptimConfig:
    optimizer: str = "rmsprop"  # rmsprop | sgd | adamw
    # adamw only; the defaults are optax.scale_by_adam's
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    momentum: float = 0.9
    # TF-style RMSProp constants (eps inside the sqrt; SURVEY.md §7 hard part 2)
    rmsprop_decay: float = 0.9
    rmsprop_eps: float = 0.002
    # TF momentum ordering: mom = m*mom + lr*g/sqrt(nu+eps), i.e. each step's
    # LR is baked into the buffer at accumulation time, so past contributions
    # keep their old LR across decay boundaries. False = torch-RMSprop
    # ordering (LR multiplies the whole buffer at apply time); the two only
    # differ while LR changes.
    rmsprop_tf_momentum_order: bool = True
    weight_decay: float = 1e-5
    # weight-decay exemptions, reference-style (SURVEY.md §2 #7)
    wd_skip_bn: bool = True
    wd_skip_bias: bool = True
    wd_skip_depthwise: bool = False
    label_smoothing: float = 0.1
    grad_clip_norm: float = 0.0  # 0 = off
    # Mixup (arXiv:1710.09412) / CutMix (arXiv:1905.04899) — beyond
    # reference parity, applied IN-STEP on device (train/steps.py
    # make_batch_mixer): zero host-pipeline cost, decorrelated per replica.
    # 0 = off; when both are set, each step picks one with p=0.5.
    mixup_alpha: float = 0.0
    cutmix_alpha: float = 0.0


@dataclass(frozen=True)
class ScheduleConfig:
    """LR schedule; stepped per-iteration (SURVEY.md §2 #9)."""

    schedule: str = "exp_decay"  # exp_decay | cosine | constant
    base_lr: float = 0.064  # scaled by total_batch/256 if scale_by_batch
    scale_by_batch: bool = True
    warmup_epochs: float = 5.0
    # exp_decay: lr *= decay_rate every decay_epochs
    decay_rate: float = 0.963
    decay_epochs: float = 3.0
    # cosine
    final_lr_factor: float = 0.0


@dataclass(frozen=True)
class EMAConfig:
    enable: bool = True
    decay: float = 0.9999
    # TF-style warmup: effective decay = min(decay, (1+t)/(10+t))
    warmup: bool = True


@dataclass(frozen=True)
class PruneConfig:
    """AtomNAS dynamic shrinkage (SURVEY.md §2 #11, §3.2)."""

    enable: bool = False
    # penalty weight on FLOPs-weighted BN-gamma L1
    rho: float = 1.8e-4
    # |gamma| below this is dead
    gamma_threshold: float = 1e-3
    # steps between in-jit mask refreshes
    mask_interval: int = 500
    # epochs between physical shape rematerializations (0 = never)
    remat_epochs: float = 25.0
    # stop pruning after this fraction of training (paper stops to stabilize)
    stop_epoch_frac: float = 0.5
    # optional FLOPs floor: stop masking when effective FLOPs reach target
    target_flops: float = 0.0
    # normalize per-channel flops cost by total network flops
    normalize_cost: bool = True
    # atom cost source weighting the BN-gamma L1 (ROADMAP item 3): "flops"
    # (analytic MACs, the AtomNAS default) or "latency_table" (MEASURED
    # per-block latency slopes from a scripts/latency_table.py artifact —
    # FLOPs is a poor latency proxy, PAPERS.md FLASH/LANA). Flag-gated: the
    # default search objective is unchanged.
    cost: str = "flops"
    # LATENCY_TABLE_*.json path (required when cost="latency_table"); every
    # prunable block of the net must have a measured entry (nas/latency.py)
    latency_table: str = ""
    # rho dynamics (SURVEY.md §2 #11 "penalty weight (rho) schedule"):
    #   constant — rho as-is
    #   ramp     — linear 0 -> rho over the first rho_ramp_epochs
    #   adaptive — ramp, then multiplicative feedback on the FLOPs gap at the
    #              mask cadence: x(1+rate) while effective MACs > target_flops,
    #              x(1-rate) once at/below (anneal), clamped to
    #              [rho_adapt_min, rho_adapt_max] x rho. Requires target_flops.
    rho_schedule: str = "constant"
    rho_ramp_epochs: float = 0.0
    rho_adapt_rate: float = 0.05
    rho_adapt_min: float = 0.1
    rho_adapt_max: float = 10.0


@dataclass(frozen=True)
class GuardConfig:
    """Step health guard (train/guard.py): skip-and-count non-finite steps by
    restoring the pre-step TrainState IN-PROGRAM (a device-side select — no
    extra host syncs; the host reads the verdicts once per train.log_every
    boundary), abort with a train_health.json dump when the bound is
    exceeded. Off by default: the legacy behavior (abort on the first
    non-finite loss seen at a log boundary) is the conservative debug
    default; long production runs enable the guard so one bad batch costs
    one step, not the job."""

    enable: bool = False
    # total non-finite (skipped) steps tolerated per run before the guard
    # aborts with TrainHealthError + train_health.json
    max_skipped_steps: int = 10


@dataclass(frozen=True)
class TrainFaultsConfig:
    """Deterministic, seeded fault injection around the TRAIN data stream
    (train/faults.py) — the training twin of serve/faults.py: every recovery
    path (corrupt-record skip, non-finite step rollback, loader-stall
    watchdog, SIGTERM preemption checkpoint) is dead code until something
    fails, and chaos must be reproducible. Off in production."""

    enable: bool = False
    seed: int = 0
    # per-pull probability of raising CorruptRecordError instead of a batch
    # (exercises data.skip_corrupt_records + data.corrupt_records counting)
    corrupt_record_rate: float = 0.0
    # global step indices whose batch gets a NaN poisoned in (exercises the
    # train.guard rollback); () = never
    nan_at_steps: Sequence[int] = ()
    # stall the loader for stall_ms at this global step (watchdog drill);
    # -1 = never
    stall_at_step: int = -1
    stall_ms: float = 0.0
    # send THIS process SIGTERM after serving this global step's batch
    # (deterministic preemption drill); -1 = never
    kill_at_step: int = -1


@dataclass(frozen=True)
class TrainConfig:
    epochs: float = 350.0
    batch_size: int = 256  # GLOBAL batch size (split across data-parallel chips)
    eval_batch_size: int = 250
    seed: int = 0
    compute_dtype: str = "bfloat16"  # matmul/conv compute dtype on TPU
    # jax.checkpoint the forward pass: recompute activations in backward to
    # trade FLOPs for HBM (enables larger per-chip batches)
    remat: bool = False
    log_every: int = 100
    eval_every_epochs: float = 1.0
    checkpoint_every_epochs: float = 1.0
    max_checkpoints: int = 3
    # keep a single best-eval-top1 checkpoint in log_dir/ckpt_best (the
    # reference lineage's best.pth); resumable/evaluable like any checkpoint
    keep_best: bool = True
    log_dir: str = "/tmp/yamt_logs"
    resume: bool = True
    test_only: bool = False
    pretrained: str = ""  # checkpoint path for eval/finetune
    # torch .pth state_dict (torchvision MobileNetV2 layout) to import for
    # eval — acceptance #1 against real pretrained weights (ckpt/torch_import)
    torch_pretrained: str = ""
    # debug guards (SURVEY.md §5 race-detection analogue)
    check_finite_every: int = 0  # 0 = off
    param_checksum_every: int = 0  # cross-replica divergence check, 0 = off
    # jax.profiler trace capture (SURVEY.md §5 tracing): start at this step
    # for profile_num_steps steps; trace lands in log_dir/trace. 0 = off.
    profile_start_step: int = 0
    profile_num_steps: int = 5
    # step health guard + train-side chaos injection sub-blocks
    guard: GuardConfig = field(default_factory=GuardConfig)
    faults: TrainFaultsConfig = field(default_factory=TrainFaultsConfig)


@dataclass(frozen=True)
class ObsConfig:
    """Runtime telemetry (obs/): span tracing, metrics registry, stall
    watchdog — docs/OBSERVABILITY.md. The registry is always on (it is just
    counters); tracing and the watchdog are opt-in knobs."""

    # coordinator-only span tracer; Chrome-trace JSON lands in
    # log_dir/obs_trace.json at run end (or on crash). Spans time the HOST
    # side of dispatches; the jax.profiler window times the device.
    trace: bool = False
    # completed spans kept in the ring buffer (oldest evicted); one span is
    # a ~100-byte tuple, so the default retains the last few thousand events
    # of a multi-day run for bounded memory
    trace_ring_size: int = 4096
    # histogram bucket ladder (upper bounds, seconds) for registry
    # histograms created after startup; () = the built-in quarter-decade
    # log ladder 100µs..~56s (obs/registry.py DEFAULT_BUCKET_BOUNDS). The
    # ladder sets quantile-estimate resolution: p50/p95/p99 interpolate
    # inside one bucket, so error is bounded by that bucket's width.
    histogram_buckets: Sequence[float] = ()
    # no train-loop heartbeat (step / eval / checkpoint / rematerialize
    # progress) for this long -> hang_report.json in log_dir. 0 = off.
    # Must exceed the slowest legitimate gap: the first step's compile and
    # the longest eval/checkpoint phase (docs/OBSERVABILITY.md tuning).
    watchdog_deadline_s: float = 0.0
    # watchdog check interval; 0 = auto (deadline/4, clamped to [0.05s, 1s])
    watchdog_poll_s: float = 0.0


@dataclass(frozen=True)
class ListenConfig:
    """Loopback HTTP front door (serve/frontend.py, cli/serve.py --listen):
    POST /predict with priority + deadline headers, GET /healthz reporting
    breaker + queue state — docs/SERVING.md "Front door"."""

    enable: bool = False
    host: str = "127.0.0.1"
    # 0 = ephemeral; the bound port is logged and written to
    # <log_dir>/listen_addr.json so callers never race the bind
    port: int = 0
    # server-side cap on how long one /predict handler waits for its result
    # when the request carries no deadline (a deadline extends this bound)
    request_timeout_s: float = 60.0
    # xplane dump dir for the HTTP-triggered profiler capture
    # (POST /profile/start|stop, obs/device.py ProfilerCapture);
    # "" = <train.log_dir>/trace (endpoints 404 when neither is set)
    profile_dir: str = ""
    # stable replica name reported in the /healthz + /varz identity block
    # (replica_id/pid/start_unix/git_sha) so a router can attribute health
    # and detect a restarted process behind the same address; "" = pid-<pid>.
    # A fleet supervisor (cli/fleet.py) assigns r<i> per slot.
    replica_id: str = ""
    # router address ("host:port") this replica REGISTERS itself with: a
    # heartbeat thread POSTs /register every register_ttl_s/3 so the lease
    # never lapses while the process lives, and /deregister on drain. ""
    # = no self-registration (supervisor-spawned replicas are pushed into
    # the router by membership notifications instead). This is how a
    # replica on ANOTHER HOST joins a fleet that never spawned it.
    register_to: str = ""
    # TTL requested per /register heartbeat; expiry removes the backend
    register_ttl_s: float = 3.0


@dataclass(frozen=True)
class AdmissionConfig:
    """Priority/QoS admission control + resilience in front of the batcher
    (serve/admission.py): per-class weighted queue shares, deadline-aware
    reject-on-arrival, bounded retry with jittered backoff, circuit breaker."""

    # class a request lands in when it names none (requests naming an
    # unknown class are rejected, not silently reclassified)
    default_class: str = "interactive"
    # queue-share weights for (interactive, batch, best_effort): each class
    # gets at least ceil(queue_depth * w / sum(w)) slots, so best-effort
    # floods can never starve interactive admission
    weights: Sequence[float] = (8.0, 3.0, 1.0)
    # bounded retry of TRANSIENT engine failures (inference is pure, so a
    # retry can never double-apply anything); 0 = fail on first error
    max_retries: int = 2
    retry_backoff_ms: float = 5.0  # doubles per attempt
    retry_jitter: float = 0.5  # +/- fraction of the backoff, desynchronizes herds
    # consecutive engine failures (across requests) that open the breaker
    breaker_threshold: int = 5
    # open -> half-open delay; half-open admits ONE probe before closing
    breaker_cooldown_s: float = 1.0
    # EWMA smoothing for observed request latency (the arrival-time wait
    # predictor feeding reject_unmeetable)
    ewma_alpha: float = 0.2
    # reject-on-arrival when the predicted wait already exceeds the request's
    # deadline: cheaper than shedding it after it burned a queue slot
    reject_unmeetable: bool = True
    # wait predictor feeding reject_unmeetable: "ewma" (smoothed mean — the
    # original; tracks the center, blind to the tail) or "quantile" (the
    # predictor_quantile of the class's bucketed serve.latency_seconds
    # histogram — deadline decisions keyed on measured TAIL latency; falls
    # back to the EWMA until the class histogram has data)
    predictor: str = "ewma"
    predictor_quantile: float = 0.9


@dataclass(frozen=True)
class FaultsConfig:
    """Deterministic, seeded fault injection around the engine
    (serve/faults.py) — chaos testing the admission/retry/breaker stack with
    reproducible failure schedules. Off in production."""

    enable: bool = False
    seed: int = 0
    # per-dispatch failure probability (seeded draw, deterministic in
    # dispatch order)
    failure_rate: float = 0.0
    # fail the first N dispatches then recover (breaker-drill schedule)
    fail_first_n: int = 0
    # where injected failures surface: at dispatch (collect thread) or at
    # result() (completion thread)
    fail_at: str = "dispatch"  # dispatch | result
    # injected completion latency, applied with probability latency_rate
    latency_ms: float = 0.0
    latency_rate: float = 1.0
    # dispatches that run CLEAN before the latency injection begins: a
    # replica that degrades mid-run (the gray-failure drill — the router
    # learned its baseline while it was healthy). 0 = degraded from birth
    latency_after_n: int = 0
    # dispatch index that HANGS until FaultyEngine.hang_release is set
    # (drain-timeout / watchdog drills); -1 = never
    hang_at: int = -1


@dataclass(frozen=True)
class HedgeConfig:
    """Request hedging (serve/hedge.py): duplicate a straggler to a second
    replica after a timer DERIVED from the router's measured per-class
    latency (the p-quantile of serve.router.latency_seconds.<class>), first
    answer wins, loser dropped idempotently — docs/SERVING.md "Fleet"."""

    enable: bool = True
    # the latency quantile the hedge timer fires at (0.99 = only the worst
    # ~1% of requests ever cost a duplicate)
    quantile: float = 0.99
    # per-class observations required before hedging arms (a cold fleet
    # must not hedge on garbage estimates)
    min_samples: int = 20
    # timer clamp: never hedge faster than min (herd protection) or wait
    # longer than max (a wedged replica must not pin its requests forever)
    min_timer_ms: float = 10.0
    max_timer_ms: float = 2000.0


@dataclass(frozen=True)
class AutoscaleConfig:
    """Fleet autoscaler (serve/autoscale.py): a control thread scaling the
    replica count off the /metrics tail-latency + queue-depth families with
    cooldown hysteresis. Off by default: a fixed-N fleet is the predictable
    baseline; enable for diurnal traffic."""

    enable: bool = False
    min_replicas: int = 1
    max_replicas: int = 4
    interval_s: float = 1.0
    # no second scaling action within this window of the previous one —
    # a spawn needs seconds to absorb load, and flapping costs a compile
    cooldown_s: float = 5.0
    # scale-up triggers (either): window p99 of the router latency family
    # above up_p99_ms, or mean routable queue depth above up_queue_depth
    up_p99_ms: float = 250.0
    up_queue_depth: float = 8.0
    # scale-down requires BOTH below these (strictly under the up
    # thresholds — the dead band between them is the hysteresis)
    down_p99_ms: float = 50.0
    down_queue_depth: float = 1.0
    # the class whose serve.router.latency_seconds histogram is the tail
    # signal (interactive = the traffic with an SLO)
    signal_class: str = "interactive"


@dataclass(frozen=True)
class FleetChaosConfig:
    """Replica-level chaos (cli/fleet.py): a seeded schedule of kill -9 OR
    gray degradation against live replicas mid-load — the process-granular
    twin of serve/faults.py's in-process injection. The supervisor's
    restart-on-exit, the router's ejection/retry, and (degrade mode) the
    latency-based soft ejection are dead code until a replica actually dies
    or limps. Off in production."""

    enable: bool = False
    seed: int = 0
    # "kill" = crash chaos (the signal below); "degrade" = gray-failure
    # chaos: the seeded victim is SIGSTOP/SIGCONT-pulsed so it stays alive
    # but slow (a GC-pause/noisy-neighbor stand-in) — the router must
    # soft-eject it on measured latency, never on a crash signal;
    # "partition" = NETWORK chaos: the seeded victim's netchaos proxy
    # (serve.fleet.netchaos must be enabled) is switched to the configured
    # fault shape for degrade_duration_s, then healed — the process never
    # even notices, only the link misbehaves
    mode: str = "kill"
    # first kill/degradation this long after the fleet is up
    kill_after_s: float = 2.0
    # subsequent kills every this often; 0 = exactly one kill (kill mode)
    kill_period_s: float = 0.0
    # "kill" = SIGKILL (no drain, the real chaos); "term" = SIGTERM
    # (graceful — drills the drain path instead)
    signal: str = "kill"
    # degrade mode: pulse shape (stopped degrade_stop_ms out of every
    # degrade_period_ms) and how long the episode lasts
    degrade_stop_ms: float = 150.0
    degrade_period_ms: float = 500.0
    degrade_duration_s: float = 10.0

    def __post_init__(self):
        if self.mode not in ("kill", "degrade", "partition"):
            raise ValueError(
                f"fleet.chaos.mode must be kill|degrade|partition, got {self.mode!r}")
        if not 0.0 < self.degrade_stop_ms < self.degrade_period_ms:
            raise ValueError("fleet.chaos needs 0 < degrade_stop_ms < degrade_period_ms")


@dataclass(frozen=True)
class NetChaosConfig:
    """Socket-level network chaos (serve/netchaos.py): a seeded TCP fault-
    injection proxy interposed between the router and EACH replica, so
    every partition shape — blackhole, reset, half-open, latency/jitter,
    throttle, asymmetric response loss, timed flaps — is reproducible on
    one box without root/iptables. ``enable`` inserts the proxy tier
    (pass-through until a fault is armed); FleetChaos ``mode="partition"``
    flips the configured ``fault`` on a seeded victim on its schedule."""

    enable: bool = False
    seed: int = 0
    # the shape mode="partition" injects on the victim link
    fault: str = "blackhole"  # blackhole | reset | half_open | drop_response
    # fraction of connections the fault applies to (seeded per-connection
    # draw); 1.0 = a link-level fault that spares nothing
    fault_rate: float = 1.0
    # response-path shaping, applied whenever the link is up
    latency_ms: float = 0.0
    jitter_ms: float = 0.0
    bandwidth_kbps: float = 0.0
    # timed link flaps: down (blackhole) flap_down_s out of every
    # flap_period_s; 0 = no flapping
    flap_period_s: float = 0.0
    flap_down_s: float = 0.0

    def __post_init__(self):
        if self.fault not in ("blackhole", "reset", "half_open", "drop_response"):
            raise ValueError(
                "fleet.netchaos.fault must be blackhole|reset|half_open|drop_response, "
                f"got {self.fault!r}")
        if not 0.0 <= self.fault_rate <= 1.0:
            raise ValueError(
                f"fleet.netchaos.fault_rate must be in [0, 1], got {self.fault_rate}")
        if self.flap_period_s > 0 and not 0.0 < self.flap_down_s < self.flap_period_s:
            raise ValueError("fleet.netchaos needs 0 < flap_down_s < flap_period_s")


@dataclass(frozen=True)
class SlowEjectConfig:
    """Gray-failure soft ejection (serve/router.py): a replica whose per-leg
    latency EWMA is a multiplicative outlier vs the fleet median first has
    its routing weight decayed, then is ejected (``fleet.slow_ejections``)
    and readmitted through the healthy poll after a probation cooldown —
    the latency twin of crash ejection, for the straggler that never dies."""

    enable: bool = True
    # outlier bound: ejectable when EWMA > slow_factor x fleet (lower) median
    slow_factor: float = 3.0
    # consecutive outlier poll-sweeps before ejection (weight decays first)
    eject_after: int = 3
    # probation: a slow-ejected replica stays out at least this long; the
    # next healthy poll after it readmits with a FRESH latency estimate
    cooldown_s: float = 5.0
    # absolute floor on the outlier threshold: sub-ms jitter between fast
    # replicas must never look like a gray failure
    min_ms: float = 1.0
    # EWMA smoothing for the per-replica per-leg latency estimate
    lat_alpha: float = 0.3

    def __post_init__(self):
        if self.slow_factor <= 1.0:
            raise ValueError(
                f"fleet.slow_eject.slow_factor must be > 1, got {self.slow_factor}")
        if self.eject_after < 1:
            raise ValueError(
                f"fleet.slow_eject.eject_after must be >= 1, got {self.eject_after}")


@dataclass(frozen=True)
class FleetObsConfig:
    """Fleet-wide observability (obs/fleet.py, docs/OBSERVABILITY.md "Fleet
    observability"): the router supervisor's /varz scrape-and-merge loop
    over every live replica (federated fleet metrics on the router's
    /metrics), the multi-window SLO burn-rate tracker over the federated
    signals, and the incident flight recorder that dumps a bounded event
    ring + fleet snapshot on ejections, deep brownout, or SLO fast-burn."""

    # scrape-merge every replica's /varz into fleet-level families
    federate: bool = True
    # scrape cadence; 0 = ride the router's poll_interval_s
    scrape_interval_s: float = 0.0
    # per-scrape /varz read bound (a wedged replica skips a tick, never
    # stalls the supervisor loop)
    scrape_timeout_s: float = 2.0
    # SLO: target tail for the signal class + the error budget (bad-request
    # fraction) the burn rate is measured against
    slo_target_p99_ms: float = 250.0
    slo_error_budget: float = 0.01
    # multi-window burn-rate alerting: fast-burn fires only when BOTH the
    # short and the long window burn past slo_fast_burn x budget rate
    slo_short_window_s: float = 30.0
    slo_long_window_s: float = 300.0
    slo_fast_burn: float = 14.0
    # incident flight recorder: event-ring capacity, dump rate limit, and
    # the brownout level that triggers a dump on the way up
    flight_recorder: bool = True
    recorder_ring: int = 256
    recorder_min_interval_s: float = 30.0
    incident_brownout_level: int = 3

    def __post_init__(self):
        if not 0.0 < self.slo_error_budget < 1.0:
            raise ValueError(
                f"fleet.obs.slo_error_budget must be in (0, 1), got {self.slo_error_budget}")
        if not 0.0 < self.slo_short_window_s < self.slo_long_window_s:
            raise ValueError(
                "fleet.obs needs 0 < slo_short_window_s < slo_long_window_s, got "
                f"{self.slo_short_window_s}/{self.slo_long_window_s}")
        if self.slo_fast_burn <= 0:
            raise ValueError(
                f"fleet.obs.slo_fast_burn must be > 0, got {self.slo_fast_burn}")
        if self.recorder_ring < 8:
            raise ValueError(
                f"fleet.obs.recorder_ring must be >= 8, got {self.recorder_ring}")


@dataclass(frozen=True)
class FleetConfig:
    """Replica fleet (cli/fleet.py + serve/router.py): N cli/serve.py
    --listen subprocesses on ephemeral ports behind one router frontend —
    weighted routing, health ejection, hedging, restart-on-exit, rolling
    restart, autoscaling. docs/SERVING.md "Fleet"."""

    # starting replica count (the autoscaler moves N inside its own bounds)
    replicas: int = 2
    # router health-poll cadence against each replica's /healthz
    poll_interval_s: float = 0.25
    # consecutive poll/dispatch failures that eject a replica from rotation
    eject_failures: int = 2
    # replicas one request may try before failing typed (transport-level
    # failures and replica-side 503s re-route; per-request verdicts do not)
    route_attempts: int = 3
    # per-dispatch client timeout (router -> replica): the READ bound
    client_timeout_s: float = 60.0
    # TCP-handshake bound, split from the read bound: a PARTITIONED host
    # drops SYNs instead of refusing, and with one shared timeout every
    # probe into a blackhole burns the full read budget. Also bounds the
    # health poll's read (healthz answers in microseconds), so a
    # blackholed replica ejects in ~eject_failures x (poll_interval +
    # connect_timeout), not 60 s. 0 = legacy single-timeout behavior.
    connect_timeout_s: float = 1.0
    # post-ejection probation: a healthy poll may not readmit an ejected
    # replica before this — a flapping link produces one bounded
    # eject/readmit cycle per cooldown instead of ping-ponging every flap
    eject_cooldown_s: float = 1.0
    # default TTL granted to /register heartbeats that name none; lease
    # expiry REMOVES the backend (fleet.lease_expirations)
    lease_ttl_s: float = 5.0
    # comma list of externally-managed replica addresses ("host:port,...")
    # to run the router tier over WITHOUT spawning anything locally (the
    # cli/fleet.py --attach sugar sets this) — the multi-host deployment
    # story: replicas live wherever they live, the router attaches to them,
    # and late arrivals join via the /register lease path
    attach: str = ""
    # restart-on-exit backoff: base doubles per consecutive crash of the
    # same slot, capped — a crash-looping replica must not spin the host
    restart_backoff_ms: float = 200.0
    restart_backoff_max_s: float = 5.0
    # how long a spawned replica may take to publish listen_addr.json
    # (includes jax import + AOT warmup) before the spawn counts as failed
    spawn_timeout_s: float = 120.0
    # per-replica jitter on the health-poll schedule, as a fraction of
    # poll_interval_s: N routers x M replicas must not phase-lock their
    # /healthz polls into a thundering herd
    poll_jitter: float = 0.2
    hedge: HedgeConfig = field(default_factory=HedgeConfig)
    autoscale: AutoscaleConfig = field(default_factory=AutoscaleConfig)
    chaos: FleetChaosConfig = field(default_factory=FleetChaosConfig)
    # gray-failure (latency-based) soft ejection of slow-but-alive replicas
    slow_eject: SlowEjectConfig = field(default_factory=SlowEjectConfig)
    # socket-level network chaos: the TCP fault proxy tier between router
    # and replicas (serve/netchaos.py; chaos mode="partition" drives it)
    netchaos: NetChaosConfig = field(default_factory=NetChaosConfig)
    # fleet-wide observability: /varz federation, SLO burn rate, and the
    # incident flight recorder (obs/fleet.py)
    obs: FleetObsConfig = field(default_factory=FleetObsConfig)


@dataclass(frozen=True)
class BrownoutConfig:
    """Graceful-degradation ladder under sustained overload
    (serve/brownout.py, docs/SERVING.md "Overload & brownout"): a controller
    thread steps L0 (healthy) -> L5 (interactive-only survival) off the
    measured signals both control loops share (serve/signals.py — windowed
    per-class p99 via registry bucket-count deltas, queue depth, breaker
    state), trading response QUALITY for interactive goodput: hedging off
    first, then fill-or-flush batching, then class shedding with
    Retry-After, then tightened deadline admission and no retries. Steps up
    fast (hold_up_s) and recovers one level per cooldown_s — asymmetric
    hysteresis, so the ladder cannot flap."""

    enable: bool = False
    interval_s: float = 0.5
    # step-UP triggers (any): windowed p99 of the signal class above
    # up_p99_ms, queue depth above up_queue_depth, or an open breaker
    up_p99_ms: float = 400.0
    up_queue_depth: float = 16.0
    # step-DOWN requires ALL below these (strictly under the up thresholds
    # — the dead band between them is the hysteresis)
    down_p99_ms: float = 100.0
    down_queue_depth: float = 2.0
    # asymmetric pacing: at most one step UP per hold_up_s (react in
    # seconds), one step DOWN per cooldown_s (recover slowly, prove each
    # restored degradation holds before the next)
    hold_up_s: float = 1.0
    cooldown_s: float = 5.0
    # deepest level the ladder may reach (5 = interactive-only survival)
    max_level: int = 5
    # the Retry-After hint on brownout-shed responses
    retry_after_s: float = 1.0
    # the class whose windowed latency histogram is the tail signal
    signal_class: str = "interactive"

    def __post_init__(self):
        if self.down_p99_ms >= self.up_p99_ms or self.down_queue_depth >= self.up_queue_depth:
            raise ValueError("serve.brownout down thresholds must sit strictly below "
                             "up thresholds (the dead band is the hysteresis)")
        if not 0 <= self.max_level <= 5:
            raise ValueError(f"serve.brownout.max_level must be in [0, 5], got {self.max_level}")
        if self.hold_up_s <= 0 or self.cooldown_s <= 0:
            raise ValueError("serve.brownout.hold_up_s/cooldown_s must be > 0")


@dataclass(frozen=True)
class QuantConfig:
    """Quantized serving (serve/quant.py, docs/SERVING.md "Quantized
    serving"): the two parity-gated rungs that shrink every transferred and
    resident serving byte. ``wire="uint8"`` ships clients' RAW pixels as u8
    — staging slots, AOT signatures, and the H2D transfer all quarter — and
    the compiled executable denormalizes on device with ``data.mean/std``
    (bitwise-identical to the f32 wire when the mean is zero; measured-delta
    gated otherwise). ``weights="int8"`` is the export-time post-training
    pass: per-output-channel symmetric int8 weights with calibration
    provenance in the bundle, refused below the top-1 agreement gate."""

    # what clients submit and what crosses H2D: "float32" (normalized
    # pixels, the historical contract) | "uint8" (raw pixels, device denorm)
    wire: str = "float32"
    # bundle weight storage at export time: "float32" | "int8"
    weights: str = "float32"
    # int8 calibration batch: calib_batches x calib_batch_size seeded
    # held-out images at data.image_size (cli/serve.py synthesizes them when
    # no dataset is wired; provenance records the source)
    calib_batches: int = 2
    calib_batch_size: int = 8
    calib_seed: int = 0
    # uint8-wire parity gate: max |logit delta| vs the f32 wire tolerated
    # when the denorm is NOT the bitwise (zero-mean) case — the backend may
    # FMA-fuse the prelude's multiply+add (~1-ulp input deltas)
    wire_atol: float = 1e-3  # yamt-lint: disable=YAMT025 — read outside the package: scripts/serve_bench.py's wire-parity gate and tests/test_quant.py consume it; the serving path itself only validates it (__post_init__)
    # int8-weight parity gate: minimum top-1 agreement with the f32 bundle
    # on the calibration batch; export REFUSES to write below it
    int8_top1_min: float = 0.98

    def __post_init__(self):
        if self.wire not in ("float32", "uint8"):
            raise ValueError(f"serve.quant.wire must be float32|uint8, got {self.wire!r}")
        if self.weights not in ("float32", "int8"):
            raise ValueError(f"serve.quant.weights must be float32|int8, got {self.weights!r}")
        if self.calib_batches < 1 or self.calib_batch_size < 1:
            raise ValueError("serve.quant.calib_batches/calib_batch_size must be >= 1")
        if self.wire_atol <= 0:
            raise ValueError(f"serve.quant.wire_atol must be > 0, got {self.wire_atol}")
        if not 0.0 < self.int8_top1_min <= 1.0:
            raise ValueError(
                f"serve.quant.int8_top1_min must be in (0, 1], got {self.int8_top1_min}")


@dataclass(frozen=True)
class FuseChunksConfig:
    """Fused multi-chunk dispatch (serve/engine.py): a request larger than
    the biggest bucket rolls its chunk loop INTO the compiled program — all
    chunks stage into one (K, bucket, S, S, 3) buffer, transfer once, and a
    lax.scan over the chunk axis serves the whole request in ONE dispatch
    (bitwise-identical to the per-chunk path; docs/SERVING.md)."""

    enable: bool = True
    # chunk-count ladder: each K gets its own AOT-warmed (bucket, size, K)
    # executable; an off-ladder chunk count decomposes greedily into ladder
    # pieces (7 chunks with ladder [2, 4] -> 4+2+1 -> 3 dispatches), worst
    # case falls back to the per-chunk path
    ladder: Sequence[int] = (2, 4)


@dataclass(frozen=True)
class OverlapConfig:
    """Overlapped staging + back-to-back dispatch (serve/engine.py,
    serve/pipeline.py): the device-resident serving steady state. The H2D
    transfer of batch N+1 overlaps compute of batch N via a fence-tracked
    pool of staging slots filled with async jax.device_put (a slot's host
    buffer is rewritten only after its last transfer is KNOWN complete), and
    a saturated bucket dispatches runs of pre-staged batches with no host
    wake-up between dispatches — the completion thread syncs only the run's
    tail (serve.dispatches_per_wakeup; docs/SERVING.md)."""

    enable: bool = True
    # host staging buffers per (bucket, size, K) key; >= max_inflight keeps
    # the fence wait (serve.slot_wait_seconds) at ~0
    staging_slots: int = 2
    # back-to-back run cap: batches the collect thread may dispatch per
    # completion wake-up on a saturated bucket (the window still bounds
    # device-side memory); 1 = per-batch wake-ups, the pre-overlap behavior
    run_max: int = 4


@dataclass(frozen=True)
class RingConfig:
    """Device-resident request ring (serve/ring.py, serve/engine.py,
    docs/SERVING.md "Device-resident ring"): R pre-staged batch slots per
    hot (model, bucket, image_size) key consumed by ONE AOT-compiled
    lax.scan dispatch per steady-state window. Host threads only feed
    slots (async device_put through the fence-tracked slot-pool idiom) and
    drain per-slot logits; an active-slot mask lets a partially-filled
    window run the same executable with padded slots' outputs discarded —
    bitwise parity with the per-batch path by construction, the same
    discipline as the fused-K scan. Engages only when the pipeline sees a
    saturated bucket worth >= min_fill of the ring; everything else rides
    the existing per-batch dispatch path."""

    enable: bool = False
    # ring depth R: pre-staged batch slots per (model, bucket, size) key;
    # one ring dispatch consumes up to R slots
    slots: int = 4
    # minimum window occupancy (staged slots / R) before the pipeline
    # commits a ring dispatch; below it the per-batch path runs instead
    min_fill: float = 0.5

    def __post_init__(self):
        if self.slots < 2:
            raise ValueError(f"serve.ring.slots must be >= 2, got {self.slots}")
        if not 0.0 < self.min_fill <= 1.0:
            raise ValueError(
                f"serve.ring.min_fill must be in (0, 1], got {self.min_fill}")


@dataclass(frozen=True)
class CascadeConfig:
    """Confidence cascade (serve/cascade.py, docs/SERVING.md "Multi-model
    zoo & cascade"): the cheap small-tier model answers every request; a
    response whose top-1 softmax margin falls below ``threshold``
    re-submits to the big tier at the ROUTER (riding the existing leg
    machinery with a distinct trace seq). Escalation preserves the
    request's remaining deadline. At millions-of-users scale this is the
    dominant serving-cost lever: most traffic never touches the big model."""

    enable: bool = False
    # zoo model names of the two tiers; both must be served by the fleet
    small: str = ""
    big: str = ""
    # escalate when top-1 softmax probability minus top-2 is below this
    threshold: float = 0.15
    # explicit X-Model requests bypass the cascade (the client asked for a
    # specific model); False forces everything through the small tier first
    respect_explicit_model: bool = True

    def __post_init__(self):
        if not 0.0 <= self.threshold <= 1.0:
            raise ValueError(
                f"serve.zoo.cascade.threshold must be in [0, 1], got {self.threshold}")
        if self.enable and (not self.small or not self.big):
            raise ValueError("serve.zoo.cascade needs both small= and big= model names")


@dataclass(frozen=True)
class ZooConfig:
    """Multi-model zoo (serve/zoo.py, docs/SERVING.md "Multi-model zoo &
    cascade"): N named InferenceBundles behind ONE multi-tenant engine —
    per-model AOT ladders keyed (model, bucket, image_size, K) over a
    SHARED staging slot pool and dispatch path, per-model admission
    quotas, an X-Model wire identity, and model-aware fleet placement
    (the lease registration advertises each replica's served set;
    cli/fleet.py spawns per-slot assignments from ``placement``)."""

    # "name=/bundle/dir,name2=/dir2" — the served set; "" = single-bundle
    # legacy serving via serve.bundle
    models: str = ""
    # model an X-Model-less request is served by; "" = first spec entry
    default: str = ""
    # fleet placement: ";"-separated slot groups of "|"-joined model names,
    # e.g. "small|big;big" = slot 0 serves both, slot 1 serves big only;
    # "" = every slot serves the full model set
    placement: str = ""
    # per-model in-system request quotas: "small=64,big=16"; unlisted
    # models are bounded only by the queue depth
    quotas: str = ""
    # per-model image-size ladders: "small=160|192,big=224"; unlisted
    # models ride serve.image_sizes
    image_sizes: str = ""
    # the confidence cascade over the zoo's small/big tiers
    cascade: CascadeConfig = field(default_factory=CascadeConfig)


@dataclass(frozen=True)
class ServeConfig:
    """Inference serving (serve/, docs/SERVING.md): export a checkpoint to a
    folded InferenceBundle and/or serve a bundle through the AOT-batched
    engine + micro-batcher via cli/serve.py."""

    # checkpoint directory to export (e.g. <log_dir>/ckpt); "" = serve only
    export_from: str = ""
    # bundle directory: export target and/or serving source
    bundle: str = ""
    # export the EMA shadow weights when the checkpoint has them (eval-on-
    # shadow semantics); falls back to live weights when EMA was off
    use_ema: bool = True
    # batch-shape ladder: each request batch pads up to the smallest bucket
    # that fits; every bucket is AOT-compiled at startup (engine warmup)
    buckets: Sequence[int] = (1, 8, 32)
    # image-size ladder for mixed-size traffic: every (bucket, size) pair is
    # AOT-warmed so a size shift hits a warm executable, not a recompile
    # cliff; () = just data.image_size (serve/engine.py)
    image_sizes: Sequence[int] = ()
    # micro-batcher: coalesce up to max_batch images or max_wait_ms linger
    max_batch: int = 32
    max_wait_ms: float = 2.0
    # pipelined serving (serve/pipeline.py): a collect/dispatch thread keeps
    # the device fed via async dispatch while a completion thread syncs —
    # continuous batching. false = legacy one-thread sync batcher
    pipelined: bool = True
    # dispatched-but-unsynced batches the pipeline may hold (2 = double
    # buffering); bounds device-side memory, backs pressure into the queue
    max_inflight: int = 2
    # bounded request queue (backpressure: submit rejects when full)
    queue_depth: int = 256
    # per-request deadline; queued-past-deadline requests are shed. 0 = none
    deadline_ms: float = 0.0
    # AOT-precompile every bucket before accepting traffic
    warmup: bool = True
    # shard each bucket over the data mesh (buckets must divide device count)
    data_parallel: bool = False
    # donate the padded input buffer to the compiled program (serve/engine.py)
    donate_input: bool = True
    # conv/matmul compute dtype for the serving forward
    compute_dtype: str = "float32"
    # cli/serve.py synthetic load: total requests (0 = export/warmup only)
    # and the number of concurrent client threads driving them
    requests: int = 0
    clients: int = 4
    # shutdown bound: stop(drain=True) fails still-unresolved requests with
    # DrainTimeout after this long instead of hanging shutdown on a wedged
    # engine. 0 = wait forever (the pre-robustness behavior)
    drain_timeout_s: float = 10.0
    # bounded LRU for OFF-ladder executables + staging buffers (on-ladder
    # entries are pinned): a size-scanning client cannot OOM the server;
    # evictions count serve.evicted_executables
    offladder_cache: int = 8
    # multi-model zoo: N named bundles behind one multi-tenant engine,
    # X-Model wire identity, model-sharded fleet placement, cascade
    zoo: ZooConfig = field(default_factory=ZooConfig)
    # quantized serving: uint8 wire + int8 weight export (parity-gated)
    quant: QuantConfig = field(default_factory=QuantConfig)
    # fused multi-chunk dispatch: whole-request inference in one dispatch
    fuse_chunks: FuseChunksConfig = field(default_factory=FuseChunksConfig)
    # overlapped staging + back-to-back dispatch: the device-resident
    # steady state (async H2D slot pool; saturated buckets dispatch runs)
    overlap: OverlapConfig = field(default_factory=OverlapConfig)
    # device-resident request ring: one lax.scan dispatch consumes a whole
    # steady-state window of pre-staged slots (opt-in; per-batch fallback)
    ring: RingConfig = field(default_factory=RingConfig)
    # HTTP front door / admission control / fault injection sub-blocks
    listen: ListenConfig = field(default_factory=ListenConfig)
    admission: AdmissionConfig = field(default_factory=AdmissionConfig)
    faults: FaultsConfig = field(default_factory=FaultsConfig)
    # brownout: the graceful-degradation ladder under sustained overload
    # (consumed by cli/serve.py at the replica tier and cli/fleet.py at the
    # router tier — same controller, different actuation targets)
    brownout: BrownoutConfig = field(default_factory=BrownoutConfig)
    # replica fleet: router tier + hedging + autoscaler + replica chaos
    # (cli/fleet.py; ignored by the single-replica cli/serve.py entry point)
    fleet: FleetConfig = field(default_factory=FleetConfig)


@dataclass(frozen=True)
class DistConfig:
    # number of data-parallel shards; 0 = use all visible devices
    num_devices: int = 0
    # call jax.distributed.initialize() at startup (multi-host pods; the
    # torch.distributed.launch/env:// rendezvous equivalent, SURVEY.md §2 #12)
    multihost: bool = False
    sync_bn: bool = True
    # ZeRO-style cross-replica sharded weight update (PAPERS.md:5); optional.
    shard_optimizer: bool = False


@dataclass(frozen=True)
class Config:
    name: str = "experiment"
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    schedule: ScheduleConfig = field(default_factory=ScheduleConfig)
    ema: EMAConfig = field(default_factory=EMAConfig)
    prune: PruneConfig = field(default_factory=PruneConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    dist: DistConfig = field(default_factory=DistConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)


# ---------------------------------------------------------------------------
# dict -> dataclass with strict key checking
# ---------------------------------------------------------------------------

def _build(dc_type, data: Mapping[str, Any], path: str = ""):
    if data is None:
        data = {}  # a YAML section header with every key commented out
    if not isinstance(data, Mapping):
        raise TypeError(f"config section '{path or dc_type.__name__}' must be a mapping, got {type(data).__name__}")
    valid = {f.name: f for f in fields(dc_type)}
    unknown = set(data) - set(valid)
    if unknown:
        raise KeyError(f"unknown config key(s) {sorted(unknown)} in section '{path or 'root'}'; valid: {sorted(valid)}")
    kwargs = {}
    for name, f in valid.items():
        if name not in data:
            continue
        v = data[name]
        sub = path + "." + name if path else name
        # `from __future__ import annotations` makes f.type a string; section
        # dataclasses are dispatched by name.
        if isinstance(f.type, str) and f.type in _SECTION_TYPES:
            kwargs[name] = _build(_SECTION_TYPES[f.type], v, sub)
        else:
            kwargs[name] = _coerce(f, v, sub)
    return dc_type(**kwargs)


_SECTION_TYPES = {
    "ModelConfig": ModelConfig,
    "LinearAttnConfig": LinearAttnConfig,
    "RopeSpec": RopeSpec,
    "RopeParameters": RopeParameters,
    "LMConfig": LMConfig,
    "DataConfig": DataConfig,
    "OptimConfig": OptimConfig,
    "ScheduleConfig": ScheduleConfig,
    "EMAConfig": EMAConfig,
    "PruneConfig": PruneConfig,
    "GuardConfig": GuardConfig,
    "TrainFaultsConfig": TrainFaultsConfig,
    "TrainConfig": TrainConfig,
    "DistConfig": DistConfig,
    "ObsConfig": ObsConfig,
    "ListenConfig": ListenConfig,
    "AdmissionConfig": AdmissionConfig,
    "FaultsConfig": FaultsConfig,
    "HedgeConfig": HedgeConfig,
    "AutoscaleConfig": AutoscaleConfig,
    "FleetChaosConfig": FleetChaosConfig,
    "NetChaosConfig": NetChaosConfig,
    "SlowEjectConfig": SlowEjectConfig,
    "FleetObsConfig": FleetObsConfig,
    "FleetConfig": FleetConfig,
    "BrownoutConfig": BrownoutConfig,
    "QuantConfig": QuantConfig,
    "FuseChunksConfig": FuseChunksConfig,
    "OverlapConfig": OverlapConfig,
    "RingConfig": RingConfig,
    "CascadeConfig": CascadeConfig,
    "ZooConfig": ZooConfig,
    "ServeConfig": ServeConfig,
    "Config": Config,
}


def _coerce(f, v, path):
    # Best-effort scalar coercion so "lr=0.1" CLI overrides work. Optional
    # fields ("X | None") accept None and coerce the non-None branch;
    # None for a non-optional field is a parse-time error, not a latent crash.
    t = f.type if isinstance(f.type, str) else getattr(f.type, "__name__", "")
    optional = isinstance(t, str) and "None" in t
    if optional:
        t = t.replace("| None", "").replace("None |", "").strip()
    if v is None:
        if optional:
            return None
        raise TypeError(f"config key '{path}' is not optional; got null")
    if isinstance(v, Mapping):
        raise TypeError(f"config key '{path}' is a scalar, not a section; got mapping {dict(v)!r}")
    if t == "int":
        if isinstance(v, bool):
            raise TypeError(f"config key '{path}' expects an int; got bool {v}")
        return int(v)
    if t == "float":
        if isinstance(v, bool):
            raise TypeError(f"config key '{path}' expects a float; got bool {v}")
        return float(v)
    if t == "bool":
        if isinstance(v, str):
            return v.lower() in ("1", "true", "yes", "on")
        return bool(v)
    if t == "str":
        return str(v)
    if isinstance(v, list):
        return tuple(v)
    return v


def config_from_dict(data: Mapping[str, Any]) -> Config:
    return _build(Config, data)


def config_to_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)


# ---------------------------------------------------------------------------
# CLI parsing: app:<path> + dotted overrides
# ---------------------------------------------------------------------------


def _parse_scalar(s: str):
    if s == "":
        return ""  # yaml.safe_load("") is None, but `key=` means empty string
    try:
        return yaml.safe_load(s)
    except yaml.YAMLError:
        return s


def _set_dotted(d: dict, dotted: str, value) -> None:
    keys = dotted.split(".")
    cur = d
    for k in keys[:-1]:
        cur = cur.setdefault(k, {})
        if not isinstance(cur, dict):
            raise KeyError(f"override '{dotted}': '{k}' is not a section")
    cur[keys[-1]] = value


def parse_cli(argv: Sequence[str]) -> Config:
    """Parse ``app:<yaml> [a.b=c ...]`` into a Config.

    Mirrors the reference's ``train.py app:apps/x.yml`` convention
    (SURVEY.md §1 L6) without the process-global FLAGS.
    """
    data: dict = {}
    overrides: dict = {}
    app_seen = False
    for arg in argv:
        if arg.startswith("app:"):
            if app_seen:
                raise ValueError("multiple app: arguments")
            data = load_yaml(arg[4:])
            app_seen = True
        elif "=" in arg:
            k, v = arg.split("=", 1)
            _set_dotted(overrides, k, _parse_scalar(v))
        else:
            raise ValueError(f"unrecognized argument {arg!r} (expected app:<path> or key=value)")
    # CLI overrides always win, regardless of their position relative to app:.
    return config_from_dict(_deep_merge(data, overrides))


def load_config(path: str) -> Config:
    return config_from_dict(load_yaml(path))
