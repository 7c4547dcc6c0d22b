"""The provenance block of the scripts' JSON artifacts (scripts/serve_bench.py,
scripts/train_chaos.py, scripts/latency_table.py): which software and which
device produced a number. Imports no jax: a parent whose children need the
chip must be able to stamp its artifact without starting a backend."""

from __future__ import annotations

import sys
from importlib import metadata


def backend_initialised() -> bool:
    """True when THIS process has started a JAX backend (and so, on a TPU
    host, holds the chip). Reads module state only: asking jax for its
    devices would start the backend this exists to detect."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and xb.backends_are_initialized()


def provenance(cpu_rehearsal: bool | None = None) -> dict:
    """jax/jaxlib versions, python, platform/device kind, and the
    cpu-rehearsal flag.

    Version lookup goes through importlib.metadata, NOT ``import jax``, and
    platform/device fields are read ONLY from a backend this process has
    already initialised: a parent whose replicas or children need the chip
    (serve_bench --fleet, train_chaos) may have jax imported, and asking it
    for its devices here would take the chip from them. ``cpu_rehearsal``
    defaults to "the backend is cpu" and can be forced by callers that know
    (train_chaos pins True)."""
    info: dict = {"python": ".".join(str(v) for v in sys.version_info[:3])}
    for pkg in ("jax", "jaxlib"):
        try:
            info[f"{pkg}_version"] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            info[f"{pkg}_version"] = None
    if backend_initialised():
        j = sys.modules["jax"]
        devs = j.devices()
        info["platform"] = j.default_backend()
        info["device_kind"] = devs[0].device_kind
        info["n_devices"] = len(devs)
    if cpu_rehearsal is None:
        cpu_rehearsal = info.get("platform") == "cpu"
    info["cpu_rehearsal"] = bool(cpu_rehearsal)
    return info
