"""The plain reference: the folded network's forward pass in float32, written
from the bundle on disk (`spec.json`: sizes; `weights.npz`: arrays under
'/'-joined names) with `jax.numpy` and `lax.conv_general_dilated` alone. It
shares no code with `serve/export.py:apply_folded`, the engine or `ops/`.

The architecture (MobileNetV3, arXiv:1905.02244 §5; EfficientNet,
arXiv:1905.11946 §3: both MBConv stacks): stem conv, inverted-residual blocks
(1x1 expand, depthwise k x k split over kernel groups, squeeze-excite, 1x1
project, residual where stride 1 keeps the shape), 1x1 head conv, global mean,
an optional feature layer, the classifier. Batch norm is already folded into
each conv as a bias. Departure from the papers: none in the mathematics;
padding is k//2 on both sides, as the program's convs are.

On a TPU a float32 matmul or conv runs as bf16 passes unless told otherwise,
so everything here runs under `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _relu6(x):
    return jnp.minimum(jnp.maximum(x, 0.0), 6.0)


ACTIVATIONS = {
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu6": _relu6,
    "hswish": lambda x: x * _relu6(x + 3.0) / 6.0,
    "h_swish": lambda x: x * _relu6(x + 3.0) / 6.0,
    "hsigmoid": lambda x: _relu6(x + 3.0) / 6.0,
    "h_sigmoid": lambda x: _relu6(x + 3.0) / 6.0,
    "swish": lambda x: x / (1.0 + jnp.exp(-x)),
    "silu": lambda x: x / (1.0 + jnp.exp(-x)),
    "sigmoid": lambda x: 1.0 / (1.0 + jnp.exp(-x)),
    "identity": lambda x: x,
    "linear": lambda x: x,
}


def load_bundle_files(bundle_dir: str) -> tuple[dict, dict[str, np.ndarray]]:
    with open(os.path.join(bundle_dir, "spec.json")) as f:
        spec = json.load(f)
    with np.load(os.path.join(bundle_dir, "weights.npz")) as z:
        weights = {k: np.asarray(z[k], np.float32) for k in z.files}
    return spec, weights


def _conv(x, w, b, stride: int, groups: int = 1):
    pad = w.shape[0] // 2
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups,
        precision=lax.Precision.HIGHEST)
    return y + b


def _dense(x, w, b):
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST) + b


def forward(spec: dict, weights: dict, images) -> jax.Array:
    """(N, H, W, 3) float32 images -> (N, classes) float32 logits."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(images, jnp.float32)
        stem = spec["stem"]
        h = ACTIVATIONS[stem["active_fn"]](_conv(h, w["stem/w"], w["stem/b"], stem["stride"]))
        for i, b in enumerate(spec["blocks"]):
            p = f"blocks/{i}/"
            act = ACTIVATIONS[b["active_fn"]]
            block_in = h
            if b["force_expand"] or b["expanded_channels"] != b["in_channels"]:
                h = act(_conv(h, w[p + "expand/w"], w[p + "expand/b"], 1))
            parts, offset = [], 0
            for bi, (k, g) in enumerate(zip(b["kernel_sizes"], b["group_channels"])):
                name = f"{p}dw{bi}_k{k}/"
                parts.append(_conv(h[..., offset:offset + g], w[name + "w"], w[name + "b"],
                                   b["stride"], groups=g))
                offset += g
            h = act(parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1))
            if b["se_channels"]:
                s = jnp.mean(h, axis=(1, 2))
                s = ACTIVATIONS[b["se_inner_act"]](_dense(s, w[p + "se/reduce/w"], w[p + "se/reduce/b"]))
                s = ACTIVATIONS[b["se_gate_fn"]](_dense(s, w[p + "se/expand/w"], w[p + "se/expand/b"]))
                h = h * s[:, None, None, :]
            h = ACTIVATIONS[b["project_act"]](_conv(h, w[p + "project/w"], w[p + "project/b"], 1))
            if b["allow_residual"] and b["stride"] == 1 and b["in_channels"] == b["out_channels"]:
                h = h + block_in
        if spec["head"] is not None:
            head = spec["head"]
            h = ACTIVATIONS[head["active_fn"]](_conv(h, w["head/w"], w["head/b"], head["stride"]))
        h = jnp.mean(h, axis=(1, 2))
        if spec["feature"] is not None:
            h = ACTIVATIONS[spec["feature_act"]](_dense(h, w["feature/w"], w["feature/b"]))
        return _dense(h, w["classifier/w"], w["classifier/b"])


# The comparison that decides `correct` for a serving cell. The shipped app
# serves with `serve.compute_dtype: float32`, and on a TPU float32 at XLA's
# DEFAULT precision is bf16-pass arithmetic: against this reference at
# `highest`, PR 23 read a worst difference of 4% of the largest logit on the
# chip (0.0028 on 0.066), and the CPU backend (true float32) reads ~1e-6.
# So the tolerance is what the configuration's stated arithmetic measures,
# with room for another seed's weights, and NOT the 1e-3 a float32 reader
# would expect. A served answer from other weights, another image or a
# dropped layer differs by the order of the logits themselves (100%).
REL_TOL_OF_MAX_LOGIT = 0.10
MIN_MAX_ABS_LOGIT = 0.1  # a network that answers ~0 makes any tolerance empty


def compare(served: np.ndarray, reference: np.ndarray) -> dict:
    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    max_abs_logit = float(np.max(np.abs(reference)))
    max_abs_diff = float(np.max(np.abs(served - reference)))
    finite = bool(np.all(np.isfinite(served)) and np.all(np.isfinite(reference)))
    return {
        "rows": int(reference.shape[0]),
        "max_abs_logit": max_abs_logit,
        "max_abs_diff": max_abs_diff,
        "rel_to_max_logit": max_abs_diff / max_abs_logit if max_abs_logit else float("inf"),
        "top1_agreement": float(np.mean(served.argmax(-1) == reference.argmax(-1))),
        "ok": finite and max_abs_logit > MIN_MAX_ABS_LOGIT
              and max_abs_diff <= REL_TOL_OF_MAX_LOGIT * max_abs_logit,
    }
