"""YAMT006 must flag: every import below resolves on only some jax versions."""

from jax.experimental import maps  # deleted (xmap is gone)
import jax._src.core as jax_core  # private internals, reshuffled every release
from jax.experimental.shard_map import shard_map as old_shard_map  # the old home of jax.shard_map
