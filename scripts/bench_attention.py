#!/usr/bin/env python3
"""One attention layer's forward and forward + backward on the chip, at the
token cell's shapes (2 x 8,192 tokens, 20 heads, head dims 256 / 256,
bfloat16): the tile loops of ops/lm.py against the fused kernels of
ops/lm_attention_kernels.py, and, with --shipped, jax's own
`pallas.ops.tpu.flash_attention`. What chose the kernels and their block
(PERF.md, PR 28 + 29). Every variant gets its operands in the layout it takes
(the kernels features-leading, as the token step holds them; the loops and
the shipped kernel heads-leading): no relayout is in these times.

    python scripts/bench_attention.py [--blocks 256,512,1024] [--shipped] [--iters 10]

Measures on a TPU or exits 3. Prints one JSON line per variant: ms a call
(host clock around `iters` dependent-free calls ending in a sync), the share
of the MXU's bfloat16 peak the causal tiles' matmuls reach (2 a tile forward,
5 backward, 7 for a backward that rebuilds the scores twice is NOT counted:
work the mathematics needs, not work done), and the largest deviation from
the loops' result.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.layer_metrics.step_mfu_train import peak_bf16_flops  # noqa: E402
from yet_another_mobilenet_series_tpu.ops import lm as ops  # noqa: E402
from yet_another_mobilenet_series_tpu.ops import lm_attention_kernels as kernels  # noqa: E402

B, H, S, D = 2, 20, 8192, 256  # the token cell's attention layer (glm47flash_train_2x8k)


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))  # compiles
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def deviation(got, want):
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default=str(ops.ATTN_BLOCK))
    ap.add_argument("--shipped", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"bench_attention measures on a TPU; found {device.platform}", file=sys.stderr)
        return 3
    b, h, s, d = B, H, S, D
    scale = d ** -0.5
    peak = peak_bf16_flops(device.device_kind)  # benchmark/peaks.json; no entry is an error, never a default
    key = jax.random.PRNGKey(0)
    # features lead, (B, H * D, S), as the kernels take them and the token step holds them
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i), (b, h * d, s), jnp.bfloat16) for i in range(4))
    heads_lead = jax.jit(lambda x: jnp.swapaxes(x.reshape(b, h, -1, s), 2, 3))  # (B, H, S, D), as the loops take them
    # the mathematics' matmul work: the causal prefix in 512 x 512 tiles, whatever the variant's block
    tile_matmul = (s // 512) * (s // 512 + 1) // 2 * 2 * b * h * 512 * 512 * d  # one matmul of every tile
    need_fwd, need_bwd = 2 * tile_matmul, 5 * tile_matmul

    def report(name, fwd, both, operands, as_loops, want):
        fwd_ms, out = timed(jax.jit(fwd), operands[:3], args.iters)
        both_ms, grads = timed(jax.jit(both), operands, args.iters)
        line = {"variant": name, "device": device.device_kind, "fwd_ms": fwd_ms, "fwd_bwd_ms": both_ms,
                "fwd_mxu_share": need_fwd / (fwd_ms * 1e-3) / peak,
                "bwd_mxu_share": need_bwd / ((both_ms - fwd_ms) * 1e-3) / peak}
        got = jax.tree.map(as_loops, (out[0], grads))
        if want is not None:
            line["out_dev"], line["grad_dev"] = deviation(got[0], want[0]), deviation(got[1], want[1])
        print(json.dumps(line), flush=True)
        return got

    def loops_both(q, k, v, g):
        return ops.loops_bwd(q, k, v, *ops.loops_fwd(q, k, v, scale, 512), g, scale, 512)

    want = report("loops_512", functools.partial(ops.loops_fwd, scale=scale, block=512), loops_both,
                  tuple(heads_lead(x) for x in (q, k, v, g)), lambda x: x, None)
    for block in (int(x) for x in args.blocks.split(",")):
        def fused_both(q, k, v, g, block=block):
            out, lse = kernels.fwd_call(q, k, v, h, scale, block)
            inner = jnp.sum((g.astype(jnp.float32) * out.astype(jnp.float32)).reshape(b, h, d, s), axis=2)
            return kernels.bwd_call(q, k, v, g, lse, inner.reshape(lse.shape), h, scale, block)

        report(f"fused_{block}", functools.partial(kernels.fwd_call, heads=h, scale=scale, block=block), fused_both,
               (q, k, v, g), heads_lead, want)
    if args.shipped:
        from jax.experimental.pallas.ops.tpu import flash_attention as shipped

        for block in (512, 1024):
            sizes = shipped.BlockSizes(
                block_q=block, block_k_major=block, block_k=block, block_b=1, block_q_major_dkv=block,
                block_k_major_dkv=block, block_k_dkv=block, block_q_dkv=block, block_k_major_dq=block,
                block_k_dq=block, block_q_dq=block)
            attend = functools.partial(shipped.flash_attention, causal=True, sm_scale=scale, block_sizes=sizes)
            try:
                report(f"shipped_{block}", lambda q, k, v: (attend(q, k, v),),
                       lambda q, k, v, g: jax.vjp(attend, q, k, v)[1](g),
                       tuple(heads_lead(x) for x in (q, k, v, g)), lambda x: x, want)
            except Exception as e:  # a block the shipped kernel's VMEM budget refuses
                print(json.dumps({"variant": f"shipped_{block}", "error": str(e)[-300:]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
