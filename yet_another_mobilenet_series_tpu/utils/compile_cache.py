"""Where JAX's persistent compilation cache lives — decided here and nowhere
else (``jax_compilation_cache_dir`` is set in exactly this one place).

Every entry point that compiles (cli/train.py, cli/serve.py, bench.py, the
measuring scripts, chip_smoke.py) calls :func:`configure` before its first
compile. The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads it itself;
  this module sets no directory at all, so the operator's choice is the only
  place anything is written.
- otherwise: ONE fixed directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored). The directory is part of the cache key's lookup, so it is
  never a temporary name, a pid or a timestamp — a cache that moves never
  hits. Processes this one spawns (fleet replicas, bench children) resolve
  the same path from the package location, or inherit the variable.

JAX skips persisting executables that compiled faster than
``jax_persistent_cache_min_compile_time_secs`` (default 1 s). A serving
ladder is a dozen executables of a second or so each and a training run adds
tens of sub-second helper programs; together they are most of a warm
start-up, so the threshold is lowered to 0 here (unless the operator set
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``). Measured on a v5e: the full
chip_smoke.py set-up, cold vs warm, is in PERF.md.

A process held to the CPU backend (``JAX_PLATFORMS=cpu``: this sandbox, the
test suite, an explicit ``--cpu`` smoke) gets no directory from here, so the
cache stays off: XLA:CPU compiles are cheap, its loader logs a page of
machine-feature warnings on every cache hit, and the suite must not fill the
checkout. The operator's variable still wins there too.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


def configure() -> str | None:
    """Point JAX at the cache directory (see module docstring); idempotent,
    and config only — no backend is initialised. Call before the first
    compile: JAX latches the directory at first use. Returns the directory
    in effect, or None when the cache is left off (CPU-only process)."""
    import jax

    chosen = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not chosen:
        if jax.config.jax_platforms == "cpu":
            return None
        chosen = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", chosen)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return chosen
