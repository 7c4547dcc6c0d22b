"""YAMT006 must stay silent: the public surface of the installed jax, and
imports under an explicit version guard."""

try:  # a guard says the author knew the module moves
    from jax._src import core as jax_core  # noqa: F401
except ImportError:
    jax_core = None

from jax import lax, shard_map  # noqa: F401  stable public surface is fine
from jax.experimental import pallas  # noqa: F401  experimental-but-present is not flagged
