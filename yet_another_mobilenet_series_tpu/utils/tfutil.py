"""TensorFlow as a host-side library only.

TF is used here for tf.data input pipelines and TensorBoard summaries, never
for compute. On a host with accelerators, whichever library initialises the
device first owns it, and ``set_visible_devices`` is refused once TF's
runtime has started — so EVERY first import of TF goes through
:func:`host_only_tf`, which hides GPUs and TPUs before anything can start
that runtime (cli/train.py builds its TensorBoard writer before jax or the
data pipeline have run).
"""

from __future__ import annotations

_hidden = False


def host_only_tf():
    """The ``tensorflow`` module with GPUs and TPUs hidden from it. Lazy:
    the heavy import (and its thread pools) exists only in processes that
    build an input pipeline or a summary writer. Raises ImportError where
    TensorFlow is not installed — callers decide whether that is fatal."""
    global _hidden
    import tensorflow as tf

    if not _hidden:
        tf.config.set_visible_devices([], "GPU")
        tf.config.set_visible_devices([], "TPU")
        _hidden = True
    return tf
