"""Where JAX's persistent compilation cache lives — decided here and nowhere
else (``jax_compilation_cache_dir`` is set in exactly this one place).

Every entry point that compiles (cli/train.py, cli/serve.py, the
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

**The scope stamp.** The program names the work inside its compiled steps
with ``jax.named_scope`` (obs/scopes.py), and a device trace is read by those
names. They are metadata, and JAX leaves metadata out of this cache's key
(``jax_compilation_cache_include_metadata_in_key`` is off by default, with
the warning that "executables loaded from the cache may have stale metadata,
which may show up in, e.g., profiles"): a cache filled by a checkout from
before a scope was added or moved hands back an executable with the old
names, or none, for a program that is otherwise the same. Putting ALL
metadata in the key would cure that and cost too much: source locations are
metadata too, so any edit that shifts a line in any file a traced function
lives in (a comment in ops/layers.py, a log line above the step call in
cli/train.py) would miss the whole cache, a minute of compiling for the
train step alone. So :func:`configure` hashes one thing more into the key,
``obs.scopes.TAXONOMY_VERSION``, through ``jax._src.cache_key.custom_hook``
(the hook JAX keeps for exactly this; it has no public home, so where a later
JAX lacks it the all-metadata flag is set instead: never stale, slower to
iterate on): the key moves when the taxonomy does, and only then. Measured on
a v5e both ways: PERF.md section 6, PR 25.

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

    from ..obs import scopes
    from ..obs.device import install_compile_watch

    # every entry point comes through here before its first compile, so this
    # is also where the program's compile watch (obs/device.py) goes in: a
    # listener, free until something compiles, and wanted on the CPU too
    install_compile_watch()
    # "The scope stamp" (module docstring). custom_hook is JAX's own extension
    # point for the cache key and has no public home (private jax internals
    # move: YAMT006), so it is imported guarded and probed, and a jax without
    # it gets the public, costlier flag
    try:
        from jax._src import cache_key
    except ImportError:
        cache_key = None
    if callable(getattr(cache_key, "custom_hook", None)):
        cache_key.custom_hook = lambda: f"yamt-scopes-{scopes.TAXONOMY_VERSION}"
    else:
        # never stale, at the price of a cache miss after any edit that shifts a line
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    chosen = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not chosen:
        if jax.config.jax_platforms == "cpu":
            return None
        chosen = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", chosen)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return chosen
