"""ImageNet input pipeline (reference: DALI GPU pipes + LMDB + torchvision
fallback, SURVEY.md §2 #6).

TPU hosts have no GPU decoder, so the DALI role moves to the host CPU:
tf.data reading TFRecord shards with parallel JPEG decode, Inception-style
random-resized-crop + flip (+ optional color jitter) for train, and the
resize-shorter-side/center-crop eval transform — the exact augmentation
surface of the reference (SURVEY.md §7 hard part 2 lists these as top-1
parity hazards; every knob is in DataConfig). A native C++ decode pipeline
(native/) can replace the tf.data decode stage; a synthetic dataset serves
integration tests and throughput benches.

Per-host sharding: each process reads a disjoint shard slice
(jax.process_index), yielding its local_batch rows; parallel/mesh.shard_batch
assembles the global array (SURVEY.md §7 hard part 5).
"""

from __future__ import annotations

import os
from typing import Iterator

import numpy as np

from ..config import DataConfig
from ..obs.registry import get_registry
from ..utils.logging import emit
from ..utils.tfutil import host_only_tf

# tf is imported lazily and blind to accelerators (utils/tfutil.py)
_tf_mod = host_only_tf


# ---------------------------------------------------------------------------
# Decode + augment (tf graph functions)
# ---------------------------------------------------------------------------


def _decode_and_random_crop(tf, image_bytes, cfg: DataConfig, seed2):
    """Inception-style random-resized-crop, the reference's train transform.

    STATELESS randomness keyed by seed2 = [seed, stream position] (like the
    native C++ loader's (seed, global_batch, i) keying): augmentations are a
    pure function of the record's position, so a deterministic_input stream
    is bitwise-reproducible end-to-end and a resumed stream reproduces the
    uninterrupted run's pixels, not just its records."""
    shape = tf.io.extract_jpeg_shape(image_bytes)
    bbox = tf.constant([0.0, 0.0, 1.0, 1.0], dtype=tf.float32, shape=[1, 1, 4])
    begin, size, _ = tf.image.stateless_sample_distorted_bounding_box(
        shape,
        bounding_boxes=bbox,
        seed=seed2,
        min_object_covered=0.1,
        aspect_ratio_range=(cfg.rrc_ratio_min, cfg.rrc_ratio_max),
        area_range=(cfg.rrc_area_min, cfg.rrc_area_max),
        max_attempts=10,
        use_image_if_no_bounding_boxes=True,
    )
    offset_y, offset_x, _ = tf.unstack(begin)
    target_h, target_w, _ = tf.unstack(size)
    crop_window = tf.stack([offset_y, offset_x, target_h, target_w])
    image = tf.image.decode_and_crop_jpeg(image_bytes, crop_window, channels=3)
    image = tf.image.resize(image, [cfg.image_size, cfg.image_size], method="bilinear")
    return image


def _decode_center_crop(tf, image_bytes, cfg: DataConfig):
    """Eval: resize shorter side to eval_resize, center-crop image_size
    (reference: Resize(256)/CenterCrop(224), SURVEY.md §3.3)."""
    shape = tf.io.extract_jpeg_shape(image_bytes)
    h, w = shape[0], shape[1]
    ratio = tf.cast(cfg.eval_resize, tf.float32) / tf.cast(tf.minimum(h, w), tf.float32)
    rh = tf.cast(tf.round(tf.cast(h, tf.float32) * ratio), tf.int32)
    rw = tf.cast(tf.round(tf.cast(w, tf.float32) * ratio), tf.int32)
    image = tf.image.decode_jpeg(image_bytes, channels=3)
    image = tf.image.resize(image, [rh, rw], method="bilinear")
    top = (rh - cfg.image_size) // 2
    left = (rw - cfg.image_size) // 2
    return tf.image.crop_to_bounding_box(image, top, left, cfg.image_size, cfg.image_size)


def _color_jitter(tf, image, strength: float, seed2):
    """torchvision-ColorJitter semantics on a [0,255] float image, fixed
    order brightness→contrast→saturation: brightness multiplies (additive
    tf.image.random_brightness would be a no-op at this scale), contrast
    blends with the mean of the grayscale image, saturation blends with the
    per-pixel grayscale; each op clamps. The native C++ loader implements
    the identical definition (native/yamt_loader.cc color_jitter) so the two
    loaders' augmentations agree. Stateless draws keyed by seed2 + a
    per-factor offset (same distributions as the stateful originals)."""
    lo, hi = 1.0 - strength, 1.0 + strength

    def draw(offset):
        return tf.random.stateless_uniform([], seed=seed2 + tf.constant([offset, 0], tf.int64),
                                           minval=lo, maxval=hi)

    image = tf.clip_by_value(image * draw(1), 0.0, 255.0)
    gray = tf.image.rgb_to_grayscale(image)  # luminance weights .2989/.587/.114
    gm = tf.reduce_mean(gray)
    image = tf.clip_by_value(gm + (image - gm) * draw(2), 0.0, 255.0)
    # saturation blends with the grayscale of the POST-contrast image
    # (recomputed, as the C++ loader does) — not the pre-contrast gray
    gray = tf.image.rgb_to_grayscale(image)
    image = tf.clip_by_value(gray + (image - gray) * draw(3), 0.0, 255.0)
    return image


def _normalize(tf, image, cfg: DataConfig):
    image = tf.cast(image, tf.float32) / 255.0
    mean = tf.constant(cfg.mean, dtype=tf.float32)
    std = tf.constant(cfg.std, dtype=tf.float32)
    return (image - mean) / std


def _finalize(tf, image, cfg: DataConfig):
    """Last pixel op before batching: either host-normalized f32 (default)
    or uint8 for 4x lighter host->device transfer, normalized in-step on
    device (cfg.transfer_uint8; train/steps.py _input_normalizer applies
    the IDENTICAL f32 expression, so the only delta vs the default path is
    the <=0.5/255 rounding of post-augment float pixels — RRC/center-crop
    resize is bilinear (convex) and the jitter clamps, so values are
    already in [0,255])."""
    if cfg.transfer_uint8:
        return tf.cast(tf.clip_by_value(tf.round(image), 0.0, 255.0), tf.uint8)
    return _normalize(tf, image, cfg)


def _parse_example(tf, serialized):
    features = {
        "image/encoded": tf.io.FixedLenFeature([], tf.string),
        "image/class/label": tf.io.FixedLenFeature([], tf.int64),
    }
    parsed = tf.io.parse_single_example(serialized, features)
    # TFRecord ImageNet convention stores labels 1..1000; 0 is background
    label = tf.cast(parsed["image/class/label"], tf.int32) - 1
    return parsed["image/encoded"], label


# ---------------------------------------------------------------------------
# Datasets
# ---------------------------------------------------------------------------


def _tfrecord_files(cfg: DataConfig, split: str) -> list[str]:
    # shard names are {split}-00000-of-00128; the -of- keeps sidecars like
    # {split}-classes.txt out of the match
    pattern = os.path.join(cfg.data_dir, f"{split}-*-of-*")
    import glob

    files = sorted(glob.glob(pattern))
    if not files:
        raise FileNotFoundError(f"no TFRecord shards matching {pattern}")
    return files


# (path, size, mtime_ns) -> record count; survives repeated resumes within a
# process. A JSON sidecar next to the shards persists counts across processes
# (best-effort: data_dir may be read-only).
_RECORD_COUNT_CACHE: dict = {}


def _count_tfrecord_records(path: str) -> int:
    """Exact record count by walking the TFRecord wire framing — per record:
    u64 length, u32 masked-crc(length), data[length], u32 masked-crc(data).
    Reads 8 bytes + one seek per record (no decode, no crc check), so a
    1.28M-record ImageNet epoch counts in seconds, once, cached."""
    import struct

    n = 0
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        pos = 0
        while pos < size:
            header = f.read(8)
            if len(header) < 8:
                raise ValueError(f"truncated TFRecord framing in {path} at byte {pos}")
            (length,) = struct.unpack("<Q", header)
            pos += 8 + 4 + length + 4
            if pos > size:
                raise ValueError(f"TFRecord length field overruns {path} at byte {pos}")
            f.seek(pos)
            n += 1
    return n


def _host_records_per_epoch(cfg: DataConfig, host_files: list[str], files: list[str]) -> int:
    """THIS host's exact records-per-epoch, from actual per-shard counts.

    The estimate ceil(num_train_examples * host_share) is exact only when
    every shard holds the same record count AND num_train_examples matches
    the real total (ADVICE r4 #1); with uneven shards the resume position
    would drift by the per-epoch error times epochs crossed — silently
    breaking the record/pixel-exact guarantee deterministic_input claims.
    Counting is cheap (framing walk, cached in-process and in a sidecar), so
    exactness is unconditional rather than assumption-gated. Falls back to
    the estimate, loudly, only if a shard can't be walked (e.g. compressed
    records, which TFRecordDataset is not configured for here anyway)."""
    import json

    sidecar = os.path.join(cfg.data_dir, ".record_counts.json")
    disk: dict = {}
    try:
        with open(sidecar) as f:
            disk = json.load(f)
    except (OSError, ValueError):
        pass
    dirty = False
    total = 0
    try:
        for path in host_files:
            st = os.stat(path)
            key = (path, st.st_size, st.st_mtime_ns)
            skey = f"{os.path.basename(path)}:{st.st_size}:{st.st_mtime_ns}"
            if key in _RECORD_COUNT_CACHE:
                n = _RECORD_COUNT_CACHE[key]
            elif skey in disk:
                n = int(disk[skey])
                _RECORD_COUNT_CACHE[key] = n
            else:
                n = _count_tfrecord_records(path)
                _RECORD_COUNT_CACHE[key] = n
                disk[skey] = n
                dirty = True
            total += n
    except (OSError, ValueError) as e:
        est = max(-(-cfg.num_train_examples * len(host_files) // len(files)), 1)
        # counted, not just printed: a fallback here silently weakens the
        # exact-resume guarantee, so it must survive into metrics.jsonl
        get_registry().counter("data.record_count_fallbacks").inc()
        emit(f"[data] WARNING: could not count TFRecord shards ({e}); resume "
             f"arithmetic falls back to the equal-shards estimate "
             f"({est} records/epoch) — exact resume is NOT guaranteed if "
             f"shards are uneven")
        return est
    if dirty:
        tmp = sidecar + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(disk, f)
            os.replace(tmp, sidecar)
        except OSError:
            # read-only data_dir: in-process cache still holds
            try:
                os.unlink(tmp)
            except OSError:
                pass
    est = -(-cfg.num_train_examples * len(host_files) // len(files))
    get_registry().gauge("data.host_records_per_epoch").set(max(total, 1))
    if total != est:
        emit(f"[data] host shard records/epoch = {total} (counted; equal-shards "
             f"estimate was {est}) — using the exact count")
    return max(total, 1)


def make_train_dataset(cfg: DataConfig, local_batch: int, seed: int, process_index: int = 0,
                       process_count: int = 1, start_step: int = 0):
    """start_step: local batches this host has already consumed (the resume
    position; VERDICT r3 #2 / SURVEY §5 checkpoint bullet).

    - fake: EXACT continuation — rows are skipped on the tiny pre-decode
      (idx, label) stream, and every downstream op (stateless noise, batch)
      is a pure function of the row sequence, so the resumed stream equals
      the uninterrupted run's batches start_step, start_step+1, ...
      bit-for-bit (pinned by tests/test_resume_data.py).
    - imagenet/TFRecord: epoch-faithful continuation — the per-epoch file
      order is keyed statelessly by (seed, epoch) and the stream starts at
      start_step's epoch with the intra-epoch remainder of records skipped
      pre-decode. Record-level EXACTNESS requires
      cfg.deterministic_input — single-stream deterministic interleave with
      the (seed, epoch) file permutation as the only shuffle — or,
      equivalently, decode_threads=1 + shuffle_buffer=1 (the resume tests
      pin both forms). Measured price (BASELINE.md round 5): within ~7% of
      the default path on a 1-core host, where decode is serial either way;
      on a many-core production host the single interleave stream bounds
      record delivery, so re-measure there before enabling it for a full
      350-epoch run. Under default production settings the parallel
      interleave
      (deterministic=False, kept for throughput) reorders records, and the
      resume point restarts the shuffle buffer — up to shuffle_buffer
      records that sat unemitted in the interrupted run's buffer are
      skipped, and the same count near the skip point can repeat. Bounded
      by ONE buffer (16k records ~ 1% of an ImageNet epoch) per resume,
      not compounding; the guarantee that matters — the SAME epoch's file
      set from the same position, never an epoch-0 replay — holds
      regardless."""
    tf = _tf_mod()
    if cfg.dataset == "fake":
        return _fake_dataset(cfg, local_batch, seed, train=True,
                             process_index=process_index, process_count=process_count,
                             start_step=start_step)
    files = _tfrecord_files(cfg, cfg.train_split)
    host_files = files[process_index::process_count]
    if not host_files:
        raise ValueError(
            f"host {process_index}/{process_count} got zero TFRecord shards "
            f"({len(files)} total); fewer shards than hosts cannot feed training"
        )
    # THIS host's records-per-epoch drives the resume arithmetic. Files are
    # sharded by slicing, so a host's share is its file fraction — not the
    # uniform 1/process_count (with 16 shards on 3 hosts one host reads 6/16
    # of the records; the uniform estimate would drift ~12% per epoch and a
    # deep resume would land whole epochs away from the uninterrupted run).
    # Counts are EXACT per-shard walks (cached), not the equal-shards
    # estimate — uneven shards would otherwise drift by the per-epoch error
    # times epochs crossed (ADVICE r4 #1). Arithmetic is in RECORDS, not
    # batches: batching runs over the continuous record stream (no per-epoch
    # remainder drop), so after k steps exactly k*local_batch records are
    # consumed — a batches-per-epoch floor would drift by
    # (records_per_epoch % local_batch) every epoch.
    start_records = start_step * local_batch
    if start_records:
        records_per_epoch = _host_records_per_epoch(cfg, host_files, files)
        start_epoch = start_records // records_per_epoch
        skip_records = start_records % records_per_epoch
    else:
        start_epoch, skip_records = 0, 0  # fresh run: nothing to count or skip

    def epoch_files(e):
        # stateless per-epoch file permutation: epoch e's order is identical
        # whether reached by streaming or by resuming directly into it
        return tf.data.Dataset.from_tensor_slices(
            tf.random.experimental.stateless_shuffle(
                tf.constant(host_files), seed=tf.stack([tf.cast(seed, tf.int64), e])
            )
        )

    ds = tf.data.Dataset.range(start_epoch, tf.int64.max).flat_map(epoch_files)
    ds = ds.interleave(
        lambda f: tf.data.TFRecordDataset(f, buffer_size=16 * 1024 * 1024),
        # deterministic_input buys record-exact resume (and run-to-run
        # reproducible record order) at interleave-parallelism cost; the
        # default keeps throughput and accepts the one-buffer resume
        # approximation documented above
        cycle_length=1 if cfg.deterministic_input else cfg.decode_threads,
        num_parallel_calls=1 if cfg.deterministic_input else tf.data.AUTOTUNE,
        deterministic=bool(cfg.deterministic_input),
    )
    ds = ds.skip(skip_records)  # serialized records: skipped without decoding
    if not cfg.deterministic_input:
        # under deterministic_input the (seed, epoch) file permutation IS the
        # shuffle; a stateful record buffer would reintroduce resume drift
        ds = ds.shuffle(cfg.shuffle_buffer, seed=seed + 1)
    # stream position (= records consumed, matching the uninterrupted run's
    # numbering) keys the per-record stateless augmentation RNG: the same
    # position draws the same crop/flip/jitter whether reached by streaming
    # or by resume
    ds = ds.enumerate(start=start_records)

    # per-host seed offset (the native loader's convention,
    # native_loader.make_native_train_iter): without it every host would
    # draw the SAME crop/flip/jitter parameters at the same stream
    # position, correlating augmentations across the global batch
    aug_seed = seed + process_index

    def map_fn(pos, serialized):
        seed2 = tf.stack([tf.constant(aug_seed, tf.int64), pos])
        image_bytes, label = _parse_example(tf, serialized)
        image = _decode_and_random_crop(tf, image_bytes, cfg, seed2)
        image = tf.image.stateless_random_flip_left_right(
            image, seed2 + tf.constant([4, 0], tf.int64))
        if cfg.color_jitter > 0:
            image = _color_jitter(tf, image, cfg.color_jitter, seed2)
        if cfg.randaugment_layers > 0:
            from .randaugment import rand_augment

            # offsets >= 16 are reserved for RandAugment's per-layer draws
            # (randaugment._BASE_OFFSET); this map_fn owns offsets 0..4
            image = rand_augment(
                tf, image, cfg.randaugment_layers, cfg.randaugment_magnitude, seed2)
        image = _finalize(tf, image, cfg)
        image.set_shape([cfg.image_size, cfg.image_size, 3])
        return {"image": image, "label": label}

    ds = ds.map(map_fn, num_parallel_calls=tf.data.AUTOTUNE)
    ds = ds.batch(local_batch, drop_remainder=True)
    ds = ds.prefetch(cfg.prefetch)
    return ds


def eval_batches_per_host(cfg: DataConfig, local_batch: int, process_count: int = 1) -> int:
    """Fixed number of eval batches EVERY host must run. The eval step is a
    collective program: if hosts ran different batch counts the stragglers
    would deadlock in the all-reduce, so each host pads its finite stream up
    to this count (derived from the declared eval set size, the only number
    all hosts agree on without communicating)."""
    n = cfg.fake_eval_size if cfg.dataset == "fake" else cfg.num_eval_examples
    per_host = -(-n // process_count)  # ceil
    return max(-(-per_host // local_batch), 1)


def make_eval_dataset(cfg: DataConfig, local_batch: int, process_index: int = 0, process_count: int = 1):
    """Finite, exactly eval_batches_per_host batches on every host; the tail
    (and any all-dummy equalization batches) is padded with label=-1, which
    the eval step masks out so each example counts exactly once."""
    tf = _tf_mod()
    target = eval_batches_per_host(cfg, local_batch, process_count)
    if cfg.dataset == "fake":
        ds = _fake_dataset(cfg, local_batch, seed=0, train=False,
                           process_index=process_index, process_count=process_count)
    else:
        files = _tfrecord_files(cfg, cfg.val_split)
        ds = tf.data.Dataset.from_tensor_slices(files)
        ds = ds.interleave(tf.data.TFRecordDataset, cycle_length=4, num_parallel_calls=tf.data.AUTOTUNE)
        # record-level sharding: per-host example counts differ by at most 1
        # (file-level sharding can differ by whole shards — or leave a host
        # with zero files when process_count > len(files))
        ds = ds.shard(process_count, process_index)

        def map_fn(serialized):
            image_bytes, label = _parse_example(tf, serialized)
            image = _decode_center_crop(tf, image_bytes, cfg)
            image = _finalize(tf, image, cfg)
            image.set_shape([cfg.image_size, cfg.image_size, 3])
            return {"image": image, "label": label}

        ds = ds.map(map_fn, num_parallel_calls=tf.data.AUTOTUNE)
        ds = ds.batch(local_batch, drop_remainder=False)
        ds = ds.map(lambda b: _pad_batch(tf, b, local_batch))
    # equalize: append all-dummy batches, then cut to the agreed count
    dummy = tf.data.Dataset.from_tensors({
        "image": tf.zeros([local_batch, cfg.image_size, cfg.image_size, 3],
                          tf.uint8 if cfg.transfer_uint8 else tf.float32),
        "label": -tf.ones([local_batch], tf.int32),
    }).repeat(target)
    ds = ds.concatenate(dummy).take(target)
    return ds.prefetch(cfg.prefetch)


def _pad_batch(tf, batch, local_batch):
    n = tf.shape(batch["label"])[0]
    pad = local_batch - n

    def pad_t(t):
        padding = [[0, pad]] + [[0, 0]] * (len(t.shape) - 1)
        return tf.pad(t, padding)

    return {
        "image": pad_t(batch["image"]),
        "label": tf.concat([batch["label"], -tf.ones([pad], tf.int32)], 0),
    }


# ---------------------------------------------------------------------------
# Fake data (integration tests / benches without ImageNet)
# ---------------------------------------------------------------------------


def _fake_dataset(cfg: DataConfig, local_batch: int, seed: int, train: bool,
                  process_index: int = 0, process_count: int = 1, start_step: int = 0):
    """Learnable synthetic classification: each class has a fixed random
    template; samples are noisy copies. A real model reaches high accuracy in
    a few epochs — which is what the loss-decreases integration tests need
    (SURVEY.md §4.3). Sharded per host like the TFRecord path — without it
    every host would serve the identical stream (duplicate rows in the global
    train batch; double-counted-then-truncated eval)."""
    tf = _tf_mod()
    n_classes = cfg.fake_num_classes or 1000
    n = cfg.fake_train_size if train else cfg.fake_eval_size
    # Class templates are SHARED between train and eval (fixed seed) — only
    # the per-sample noise differs — otherwise eval measures an unlearnable
    # disjoint task and stays at chance forever.
    rng = np.random.RandomState(777)
    templates = tf.constant(
        rng.normal(0, 1, (n_classes, cfg.image_size, cfg.image_size, 3)).astype(np.float32)
    )
    # Only (index, label) rows are materialized; the image is template +
    # stateless per-index noise synthesized in the map. The previous version
    # pre-built all n full-size images in host RAM (e.g. 7.7 GB for 12800
    # samples at 224x224) and fed a TPU chip at ~60 img/s through the
    # resulting shuffle buffer.
    idx = np.arange(n, dtype=np.int64)
    labels = (idx % n_classes).astype(np.int32)
    idx, labels = idx[process_index::process_count], labels[process_index::process_count]
    noise_salt = seed + 1 if train else 987654

    def synth(rec):
        noise = tf.random.stateless_normal(
            (cfg.image_size, cfg.image_size, 3),
            seed=tf.stack([tf.constant(noise_salt, tf.int64), rec["idx"]]),
        )
        return {"image": tf.gather(templates, rec["label"]) + 0.3 * noise, "label": rec["label"]}

    ds = tf.data.Dataset.from_tensor_slices({"idx": idx, "label": labels})
    if train:
        # resume: skip start_step batches' worth of (idx,label) ROWS (cheap,
        # pre-synthesis). The seeded reshuffle sequence and the stateless
        # per-idx noise are pure functions of the stream position, so the
        # continuation is bit-identical to the uninterrupted run's.
        ds = ds.shuffle(len(idx), seed=seed).repeat().skip(start_step * local_batch)
        ds = ds.map(synth, num_parallel_calls=tf.data.AUTOTUNE)
        ds = ds.batch(local_batch, drop_remainder=True)
    else:
        ds = ds.map(synth, num_parallel_calls=tf.data.AUTOTUNE)
        ds = ds.batch(local_batch, drop_remainder=False)
        ds = ds.map(lambda b: _pad_batch(tf, b, local_batch))
    return ds.prefetch(tf.data.AUTOTUNE)


# ---------------------------------------------------------------------------
# numpy iterators + fault tolerance
# ---------------------------------------------------------------------------


def as_numpy(ds) -> Iterator[dict]:
    for batch in ds.as_numpy_iterator():
        yield batch


class CorruptRecordError(RuntimeError):
    """A record (or the batch it landed in) could not be decoded. Raised by
    the train/faults.py injector and recognized by resilient_batches; the
    real tf.data equivalents (InvalidArgumentError from a rotten JPEG,
    DataLossError from torn TFRecord framing) are classified alongside it."""


class DataPipelineError(RuntimeError):
    """Too many CONSECUTIVE corrupt batches: the stream is systematically
    broken (rotten shard, wrong directory), not transiently unlucky."""


def _is_corrupt_record_error(e: BaseException) -> bool:
    if isinstance(e, CorruptRecordError):
        return True
    # classify tf errors without importing tensorflow for non-tf pipelines
    if (type(e).__module__ or "").startswith("tensorflow"):
        tf = _tf_mod()
        return isinstance(e, (tf.errors.InvalidArgumentError, tf.errors.DataLossError))
    return False


def resilient_batches(it: Iterator[dict], max_consecutive: int = 16) -> Iterator[dict]:
    """Wraps a batch iterator so a corrupt/undecodable record costs one
    skipped batch (counted in ``data.corrupt_records``) instead of the run.

    tf.data surfaces a decode failure as an error on the batch the record
    landed in and KEEPS SERVING subsequent batches (verified against a
    corrupt-JPEG TFRecord; the iterator is not dead) — so skip-and-retry at
    the batch level is sound. ``max_consecutive`` consecutive failures abort
    with :class:`DataPipelineError`: a fully rotten shard must fail loudly,
    not spin forever. Any error that is NOT a record-decode failure
    propagates untouched — resilience here is for bad DATA, not bad code.
    """
    reg = get_registry()
    consecutive = 0
    while True:
        try:
            batch = next(it)
        except StopIteration:
            return
        except Exception as e:  # noqa: BLE001 — classified, then re-raised or counted
            if not _is_corrupt_record_error(e):
                raise
            consecutive += 1
            reg.counter("data.corrupt_records").inc()
            if consecutive >= max_consecutive:
                raise DataPipelineError(
                    f"{consecutive} consecutive corrupt/undecodable batches "
                    f"(data.max_consecutive_failures={max_consecutive}); the "
                    "stream is systematically broken"
                ) from e
            continue
        consecutive = 0
        yield batch


class PrefetchWorker:
    """Host-side background prefetch: a bounded queue fed by a worker thread,
    so batch production (tf.data next / native decode / augment) overlaps the
    train loop's dispatch work instead of serializing with it.

    Fault story (the point of this class living in the robustness PR): the
    worker carries a YAMT011 top-level crash guard — an unhandled exception
    in batch production is counted (``data.worker_crashes``), the loop is
    restarted in place up to ``max_restarts`` times
    (``data.worker_restarts``; the underlying iterator object survives its
    own exceptions, per resilient_batches), and when the budget is exhausted
    the error is handed to the CONSUMER through the queue — the train loop
    dies with the real cause, never by waiting forever on a silently dead
    thread."""

    _END = ("end", None)

    def __init__(self, it: Iterator[dict], depth: int = 4, max_restarts: int = 3):
        import queue
        import threading

        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._it = it
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._max_restarts = max_restarts
        self._thread = threading.Thread(target=self._run, name="yamt-data-prefetch", daemon=True)
        self._thread.start()

    # -- worker thread -------------------------------------------------------

    def _run(self):
        try:
            reg = get_registry()
            restarts = 0
            while not self._stop.is_set():
                try:
                    self._pump()
                    return  # stream exhausted (or stop requested) cleanly
                except Exception as e:  # noqa: BLE001 — bounded restart, then surface
                    reg.counter("data.worker_crashes").inc()
                    if restarts >= self._max_restarts:
                        self._put(("error", e))
                        return
                    restarts += 1
                    reg.counter("data.worker_restarts").inc()
                    emit(f"[data] prefetch worker crashed ({type(e).__name__}: {e}); "
                         f"restart {restarts}/{self._max_restarts}")
        except Exception as e:  # noqa: BLE001 — terminal guard (YAMT011): die loud
            self._put(("error", e))

    def _pump(self):
        while not self._stop.is_set():
            try:
                item = ("item", next(self._it))
            except StopIteration:
                self._put(self._END)
                return
            self._put(item)

    def _put(self, item):
        import queue

        # stop-aware put: a consumer that walked away must not wedge the
        # worker (and therefore interpreter shutdown) on a full queue
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    # -- consumer surface ----------------------------------------------------

    def __iter__(self) -> Iterator[dict]:
        return self

    def __next__(self) -> dict:
        kind, payload = self._q.get()
        if kind == "item":
            return payload
        if kind == "error":
            self.close()
            raise payload
        raise StopIteration

    def close(self):
        self._stop.set()
        # drain so a blocked _put observes the stop promptly
        import queue

        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)


def synthetic_device_batches(cfg: DataConfig, local_batch: int, num_classes: int) -> Iterator[dict]:
    """Pure on-device batches (no host pipeline at all) — isolates model
    throughput from input throughput in benches."""
    rng = np.random.RandomState(0)
    batch = {
        "image": rng.normal(0, 1, (local_batch, cfg.image_size, cfg.image_size, 3)).astype(np.float32),
        "label": (np.arange(local_batch) % num_classes).astype(np.int32),
    }
    while True:
        yield batch


def zipf_cdf(vocab: int) -> np.ndarray:
    """Cumulative probabilities of ids 0..vocab-1 under Zipf's law, p(id) ~ 1 / (id + 1)."""
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    return np.cumsum(weights / weights.sum())


def token_batches(cfg: DataConfig, local_batch: int, vocab: int, seed: int, *, start_step: int = 0,
                  num_batches: int | None = None) -> Iterator[dict]:
    """The device-batch loader's token form: {'tokens': (local_batch,
    seq_len + 2) int32}, each row one document of ids drawn independently by
    Zipf's law over the vocabulary slice (a token model's two heads read the
    next and the next-but-one id as targets, hence + 2). Seeded per step, so
    a resumed run continues the stream: batch i is a function of (seed, i).
    What there is to learn is the unigram distribution, which is enough for
    a falling loss; `num_batches` makes the finite eval pass."""
    if cfg.seq_len <= 0:
        raise ValueError("data.loader=tokens needs data.seq_len (cli/train.py fills it from model.lm.seq_len)")
    cdf = zipf_cdf(vocab)
    step = start_step
    while num_batches is None or step < start_step + num_batches:
        rng = np.random.default_rng([seed, step])
        ids = np.searchsorted(cdf, rng.random((local_batch, cfg.seq_len + 2)), side="right")
        yield {"tokens": np.minimum(ids, vocab - 1).astype(np.int32)}
        step += 1
