"""Activation zoo (reference: mobilenet_base.get_active_fn, SURVEY.md §2 #3).

All piecewise-linear forms are written exactly as the MobileNetV3 paper
defines them (h-swish = x*relu6(x+3)/6) so top-1 parity is not lost to
activation drift (SURVEY.md §7 hard part 2). XLA fuses these into the
surrounding conv epilogues; no Pallas needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs.scopes import scope


def relu(x):
    return jnp.maximum(x, 0)


def relu6(x):
    return jnp.clip(x, 0, 6)


def hsigmoid(x):
    return relu6(x + 3.0) * (1.0 / 6.0)


def hswish(x):
    return x * relu6(x + 3.0) * (1.0 / 6.0)


def sigmoid(x):
    # jax.nn.sigmoid: numerically stable VJP (a hand-rolled 1/(1+exp(-x))
    # yields NaN gradients once exp(-x) overflows at x < -88 in f32).
    return jax.nn.sigmoid(x)


def swish(x):
    # a.k.a. SiLU; used by the AtomNAS "+" variants (SURVEY.md §6)
    return x * jax.nn.sigmoid(x)


def identity(x):
    return x


def _scoped(fn):
    """The activation under the `act` scope (obs/scopes.py), so that a
    device trace times it by name at every call site."""

    @functools.wraps(fn)
    def scoped(x):
        with scope("act"):
            return fn(x)

    return scoped


_ACTIVATIONS = {
    "relu": _scoped(relu),
    "relu6": _scoped(relu6),
    "hswish": _scoped(hswish),
    "h_swish": _scoped(hswish),
    "hsigmoid": _scoped(hsigmoid),
    "h_sigmoid": _scoped(hsigmoid),
    "swish": _scoped(swish),
    "silu": _scoped(swish),
    "sigmoid": _scoped(sigmoid),
    "identity": identity,
    "linear": identity,
}


def get_activation(name: str):
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(f"unknown activation {name!r}; known: {sorted(_ACTIVATIONS)}") from None
