"""Input pipelines: tf.data (TFRecord/fake) and the native C++ loader.

make_train_source / make_eval_source are the single dispatch point for which
pipeline feeds the trainer — keyed on (dataset, loader) with invalid
combinations rejected up front, so the train and eval halves of a run can
never pick incompatible pipelines.

Valid combinations:
  dataset=imagenet + loader=tfdata   -> TFRecord shards via tf.data
  dataset=fake     + loader=tfdata   -> synthetic learnable data
  dataset=folder   + loader=native   -> ImageFolder tree via native/ C++
  dataset=fake     + loader=synthetic -> one device-resident image batch
  dataset=fake     + loader=tokens   -> seeded Zipf token ids (token models)
"""

from __future__ import annotations

from typing import Iterator

from ..config import DataConfig
from . import pipeline as _pipeline


def _check(cfg: DataConfig) -> None:
    ok = {("imagenet", "tfdata"), ("fake", "tfdata"), ("folder", "native"), ("fake", "synthetic"),
          ("fake", "tokens")}
    if (cfg.dataset, cfg.loader) not in ok:
        raise ValueError(
            f"unsupported data config: dataset={cfg.dataset!r} loader={cfg.loader!r}; valid: {sorted(ok)}"
        )
    if cfg.transfer_uint8 and (cfg.dataset, cfg.loader) not in (
            ("imagenet", "tfdata"), ("folder", "native")):
        # fake templates live in normalized space — there are no [0,255]
        # pixels to quantize; the uint8 transfer path exists for the
        # real-JPEG pipelines (tf.data TFRecords and the native C++ loader)
        raise ValueError(
            "data.transfer_uint8 requires a real-JPEG pipeline "
            "(imagenet/tfdata or folder/native); "
            f"got dataset={cfg.dataset!r} loader={cfg.loader!r}"
        )
    if cfg.randaugment_layers < 0 or not 0 <= cfg.randaugment_magnitude <= 10:
        raise ValueError(
            f"randaugment_layers must be >= 0 and randaugment_magnitude in [0, 10]; "
            f"got {cfg.randaugment_layers}/{cfg.randaugment_magnitude}"
        )
    if cfg.randaugment_layers > 0 and (cfg.dataset, cfg.loader) != ("imagenet", "tfdata"):
        # implemented once, in the real-JPEG tf.data pipeline
        # (data/randaugment.py); fake templates live in normalized space and
        # the native loader has no implementation — rejecting beats silently
        # training without it (same policy as transfer_uint8 above)
        raise ValueError(
            "RandAugment requires the imagenet/tfdata pipeline "
            f"(data/randaugment.py); got dataset={cfg.dataset!r} loader={cfg.loader!r} "
            "(for fake-data smoke runs set data.randaugment_layers=0)"
        )


def make_train_source(cfg: DataConfig, local_batch: int, seed: int, process_index: int = 0,
                      process_count: int = 1, start_step: int = 0, inject=None) -> Iterator[dict]:
    """Infinite iterator of {'image','label'} numpy batches (this host's shard).

    start_step: local batches this host already consumed (== the global train
    step; identical on every host). A resumed run CONTINUES the data order
    from there instead of replaying the epoch-0 shuffle — bit-exact for the
    fake/tfdata and folder/native paths, epoch-faithful for TFRecords
    (pipeline.make_train_dataset docstring; tests/test_resume_data.py).

    inject: optional wrapper applied to the RAW stream before the resilience
    layers — the train-side chaos hook (train/faults.py FaultyTrainSource),
    placed there so injected corrupt records exercise the same skip/count/
    abort path real ones take. The resilience stack around it:
    corrupt-record skip with bounded consecutive-failure abort
    (cfg.skip_corrupt_records; pipeline.resilient_batches) and an optional
    guarded background prefetch thread (cfg.prefetch_thread;
    pipeline.PrefetchWorker)."""
    _check(cfg)
    if cfg.loader == "native":
        from . import native_loader

        src = iter(native_loader.make_native_train_iter(
            cfg, local_batch, seed, process_index, process_count, start_step=start_step))
    elif cfg.loader == "synthetic":
        # position-independent by construction (the same device-resident
        # batch forever) — nothing to skip
        src = _pipeline.synthetic_device_batches(cfg, local_batch, cfg.fake_num_classes or 1000)
    elif cfg.loader == "tokens":
        # each host its own stream, continued at the restored step
        src = _pipeline.token_batches(cfg, local_batch, cfg.fake_num_classes or 1000,
                                      seed + 7919 * process_index, start_step=start_step)
    else:
        ds = _pipeline.make_train_dataset(cfg, local_batch, seed, process_index, process_count,
                                          start_step=start_step)
        # the RAW tf iterator object, not the as_numpy generator: a decode
        # error raised through a generator kills the generator (subsequent
        # next() is StopIteration), while tf's own iterator keeps serving
        # past the bad batch — which is what resilient_batches relies on
        src = iter(ds.as_numpy_iterator())
    if inject is not None:
        src = inject(src)
    if cfg.skip_corrupt_records:
        src = _pipeline.resilient_batches(src, max_consecutive=cfg.max_consecutive_failures)
    if cfg.prefetch_thread:
        src = _pipeline.PrefetchWorker(src, depth=cfg.prefetch)
    return src


def make_eval_source(cfg: DataConfig, local_batch: int, process_index: int = 0, process_count: int = 1) -> Iterator[dict]:
    """Finite iterator for one eval pass; identical batch count on every host."""
    _check(cfg)
    if cfg.loader == "native":
        from . import native_loader

        loader, n_batches = native_loader.make_native_eval_loader(cfg, local_batch, process_index, process_count)

        def gen():
            for served in range(n_batches):
                try:
                    yield loader.next_batch()
                except native_loader.LoaderExhausted:
                    # a padded eval pass has a KNOWN length; ending early means
                    # the loader died (stale .so, concurrent close) — and on a
                    # pod this host would run fewer collective steps than its
                    # peers, deadlocking them. Fail loudly with context.
                    raise RuntimeError(
                        f"native eval stream ended after {served}/{n_batches} batches"
                    ) from None

        return gen()
    if cfg.loader == "tokens":
        # a held-out stream (another seed), the same number of batches on every host
        batches = max(cfg.fake_eval_size // max(local_batch * process_count, 1), 1)
        return _pipeline.token_batches(cfg, local_batch, cfg.fake_num_classes or 1000,
                                       0x6576616C + process_index, num_batches=batches)
    ds = _pipeline.make_eval_dataset(cfg, local_batch, process_index, process_count)
    return _pipeline.as_numpy(ds)
