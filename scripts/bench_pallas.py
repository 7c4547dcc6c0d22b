"""A/B the Pallas fused-depthwise kernel against its XLA lowering on TPU.

This is the harness that produced the verdict recorded in
ops/pallas_kernels.py and ROADMAP.md (kernel loses ~10x end-to-end; not
wired into the model). The kernel still compiles for a v5e under jax 0.9.0
(chip_smoke.py proves it on every run); this script times it.

Measurement notes:
- Dispatch is asynchronous: every timing below chains each iteration's
  output into the next iteration's input and ends with a device_get of a
  dependent scalar, so the timed region contains the work and not only its
  enqueue.
- Per-dispatch overhead is tens of microseconds; single-op timings below a
  few hundred us are floor-dominated, so shapes are timed as a chained loop
  inside one jit.

Measures on the chip or fails: with no TPU, or when a shape raises, it exits
non-zero and prints no result.

Usage: python scripts/bench_pallas.py [--batch 128] [--dtype bfloat16]
Prints one JSON line per measurement to stdout, a table to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def sync(arr) -> float:
    """device_get of a dependent scalar — see module docstring."""
    return float(np.asarray(jax.device_get(arr)).ravel()[0])


def dw_shapes(net, image_size):
    """(hw_in, channels, k, stride, act) for every dw branch, tracking spatial."""
    hw = (image_size - 1) // net.stem.stride + 1
    shapes = []
    for blk in net.blocks:
        for k, g in zip(blk.kernel_sizes, blk.group_channels or (blk.expanded_channels,)):
            shapes.append((hw, g, k, blk.stride, blk.active_fn))
        hw = (hw - 1) // blk.stride + 1
    return shapes


def time_chained(step, x0, iters=10, warmup=2):
    """step(x) -> x' (same shape). Chained => serialized and cache-proof."""
    x = x0
    for _ in range(warmup):
        x = step(x)
    sync(x[(0,) * x.ndim])
    x = x0
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    sync(x[(0,) * x.ndim])
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--inner", type=int, default=8, help="chained kernel calls per jit")
    args = ap.parse_args()

    from yet_another_mobilenet_series_tpu.config import ModelConfig
    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.ops import pallas_kernels as pk
    from yet_another_mobilenet_series_tpu.utils import compile_cache

    compile_cache.configure()
    platform = jax.default_backend()
    kind = jax.devices()[0].device_kind
    if platform != "tpu":
        # Mosaic compiles for a TPU or not at all, and the interpreter's
        # time says nothing about the kernel's
        raise SystemExit(f"bench_pallas: no TPU: jax.default_backend() is {platform!r} ({kind})")
    dtype = jnp.dtype(args.dtype)
    B = args.batch
    log(f"bench_pallas: {platform} ({kind}), batch {B}, {args.dtype}, {args.inner} chained calls/step")

    net = get_model(ModelConfig(arch="mobilenet_v3_large"), 224)
    rng = np.random.RandomState(0)

    rows = []
    tot_pallas = tot_xla = 0.0
    for hw, c, k, s, act in dw_shapes(net, 224):
        x0 = jnp.asarray(rng.normal(0, 1, (B, hw, hw, c)), dtype)
        w = jnp.asarray(rng.normal(0, 0.1, (k, k, c)), dtype)
        scale = jnp.asarray(rng.uniform(0.5, 1.5, (c,)), jnp.float32)
        shift = jnp.asarray(rng.normal(0, 0.1, (c,)), jnp.float32)
        mask = jnp.ones((c,), jnp.float32)

        def make_step(fn):
            @jax.jit
            def step(x):
                for _ in range(args.inner):
                    y = fn(x)
                    # fold the (possibly strided-down) output back into the
                    # input so successive calls depend on each other
                    x = x + jnp.mean(y).astype(x.dtype) * 1e-20
                return x

            return step

        t_p = time_chained(
            make_step(lambda x: pk._fused_dw_fwd(x, w, scale, shift, mask, stride=s, act=act)),
            x0, iters=args.iters,
        ) / args.inner
        t_x = time_chained(
            make_step(lambda x: pk._reference_fwd(x, w, scale, shift, mask, stride=s, act=act).astype(dtype)),
            x0, iters=args.iters,
        ) / args.inner
        tot_pallas += t_p
        tot_xla += t_x
        rows.append({"hw": hw, "c": c, "k": k, "s": s, "pallas_us": round(t_p * 1e6, 1), "xla_us": round(t_x * 1e6, 1), "speedup": round(t_x / t_p, 2)})
        log(f"  {hw:4d}x{hw:<4d} c={c:<4d} k={k} s={s}: pallas {t_p*1e6:8.1f}us  xla {t_x*1e6:8.1f}us  x{t_x/t_p:.2f}")

    log(f"  TOTAL dw chain: pallas {tot_pallas*1e3:.2f}ms  xla {tot_xla*1e3:.2f}ms  x{tot_xla/tot_pallas:.2f}")
    print(json.dumps({
        "bench": "pallas_dw_chained", "platform": platform, "device_kind": kind,
        "batch": B, "dtype": args.dtype,
        "total_pallas_ms": round(tot_pallas * 1e3, 3), "total_xla_ms": round(tot_xla * 1e3, 3),
        "xla_over_pallas": round(tot_xla / tot_pallas, 3), "per_shape": rows,
    }), flush=True)


if __name__ == "__main__":
    main()
