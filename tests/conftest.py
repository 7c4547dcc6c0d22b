"""Test harness: run everything on CPU with 8 fake devices.

This is the TPU-world "fake backend" (SURVEY.md §4.2): multi-chip logic
(psum gradient allreduce, SyncBN, sharded updates) is exercised on an
8-device host-platform mesh with no TPU present.  Must run before jax import.
"""

import os

# Force-set (not setdefault): a host with an accelerator exports
# JAX_PLATFORMS for it, but tests must be deterministic f32 CPU. Subprocesses
# the tests spawn inherit this environment. Held to the CPU, nothing enables
# the persistent compilation cache (utils/compile_cache.py), so the suite
# leaves no cache in the checkout.
os.environ["JAX_PLATFORMS"] = "cpu"
# Drop any pre-set device-count flag and force 8 (a foreign value would make
# the device-count assert below kill the whole session).
flags = [f for f in os.environ.get("XLA_FLAGS", "").split() if "xla_force_host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(flags + ["--xla_force_host_platform_device_count=8"])
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import jax  # noqa: E402

assert jax.default_backend() == "cpu"
assert len(jax.devices()) == 8, jax.devices()
