#!/usr/bin/env python3
"""One Mamba-2 layer's chunked SSD on the chip, at the token cell's shapes
(granite4hmicro_train_1x8k: 1 x 8,192 tokens, 64 heads of 64, a state of
128, chunks of 256, bfloat16): `lm_mamba.ssd_core` through its plain `lax`
form and through the fused kernels of ops/lm_mamba_kernels.py, forward and
forward + backward; each kernel alone against the plain form of the same
work (`_plain_own`, `_plain_outputs` and their vjps); the whole mixer both
ways; and the kernels' largest deviation from the plain form on the chip,
output and every gradient. `--groups` sweeps the heads a program instance takes
(`lm_mamba.SSD_HEAD_GROUP`).

    python scripts/bench_ssd.py [--iters 5] [--groups 8,16,32,64]

Measures on a TPU or exits 3. Prints one JSON line a piece: ms a call (host
clock around `iters` calls ending in a sync).
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from yet_another_mobilenet_series_tpu.ops import lm_mamba  # noqa: E402

B, S, H, P, K, CHUNK, HIDDEN = 1, 8192, 64, 64, 128, 256, 2048  # granite4hmicro_train_1x8k's Mamba-2 layer


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))  # compiles
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def say(name, ms, **more):
    print(json.dumps({"piece": name, "ms": round(ms, 3), **more}), flush=True)


def deviation(got, want):
    """Largest |got - want| over the largest |want|, leaf by leaf."""
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]


def operands(key):
    """ssd_core's operands as a fresh layer makes them: x after the conv's SiLU, Delta log-uniform in [1e-3, 1e-1],
    A = exp(A_log), A_log = ln U[1, 16] (in-chunk sums of Delta A reach several hundred below 0), D ~ 1."""
    ks = jax.random.split(key, 6)
    x = jax.nn.silu(jax.random.normal(ks[0], (B, S, H, P))).astype(jnp.bfloat16)
    delta = jnp.exp(jax.random.uniform(ks[1], (B, S, H), minval=math.log(1e-3), maxval=math.log(1e-1)))
    a = jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    b, c = (jax.random.normal(k, (B, S, K)).astype(jnp.bfloat16) for k in ks[3:5])
    return x, delta, -a * delta, b, c, 1.0 + 0.1 * jax.random.normal(ks[5], (H,))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--groups", default=str(lm_mamba.SSD_HEAD_GROUP))
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print(f"bench_ssd: no TPU (platform {jax.devices()[0].platform!r}): this script measures on the chip", file=sys.stderr)
        return 3
    it = args.iters
    core_args = operands(jax.random.PRNGKey(0))
    ct = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, P)).astype(jnp.bfloat16)
    core = lambda *a: lm_mamba.ssd_core(*a, chunk=CHUNK)[0]  # noqa: E731
    grads = lambda fn: jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a[:-1]).astype(jnp.float32) * a[-1]),  # noqa: E731
                                        argnums=(0, 1, 2, 3, 4, 5)))
    takes = lm_mamba.fuses
    assert takes(S, CHUNK, H, P, K, jnp.bfloat16)

    # the plain form: `fuses` refusing the shape, as the dispatch reads it while it traces
    lm_mamba.fuses = lambda *a: False
    plain = jax.jit(lambda *a: core(*a))
    plain_grads = grads(lambda *a: core(*a))
    say("ssd_core, plain, fwd", timed(plain, core_args, it))
    say("ssd_core, plain, fwd+bwd", timed(plain_grads, (*core_args, ct), it))
    want = (plain(*core_args), *plain_grads(*core_args, ct))

    # the work the kernels take, alone, on flat operands: each chunk's own state contribution and its output from its
    # start state, and their vjps
    n = S // CHUNK
    x, delta, log_decay, b, c, d_skip = core_args
    xf = x.reshape(B, S, H * P)
    cum = jnp.cumsum(log_decay.reshape(B, n, CHUNK, H), axis=2).reshape(B, S, H)
    starts = (0.1 * jax.random.normal(jax.random.PRNGKey(2), (n, B, H, P, K))).astype(jnp.bfloat16)
    d_own = jax.random.normal(jax.random.PRNGKey(5), (n, B, H, P, K))
    own_args, outputs_args, flat_ct = (xf, delta, cum, b), (xf, delta, cum, b, c, starts, d_skip), ct.reshape(B, S, H * P)
    plain_own = jax.jit(lm_mamba._plain_own, static_argnames=("chunk",))
    say("chunk own states, plain, fwd", timed(lambda *a: plain_own(*a, chunk=CHUNK), own_args, it))
    say("chunk own states, plain vjp alone",
        timed(jax.jit(lambda *a: lm_mamba._plain_own_bwd(*a, chunk=CHUNK)), (*own_args, d_own), it))
    say("chunk outputs, plain, fwd", timed(jax.jit(lm_mamba._plain_outputs), outputs_args, it))
    say("chunk outputs, plain vjp alone", timed(jax.jit(lm_mamba._plain_outputs_bwd), (*outputs_args, flat_ct), it))
    lm_mamba.fuses = takes

    def mixer_loss():
        hidden_x = jax.random.normal(jax.random.PRNGKey(3), (B, S, HIDDEN)).astype(jnp.bfloat16)
        inner, channels = H * P, H * P + 2 * K
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        p = {"in_proj": 0.02 * jax.random.normal(ks[0], (HIDDEN, inner + channels + H)),
             "conv": jax.random.uniform(ks[1], (4, channels), minval=-0.5, maxval=0.5), "conv_bias": jnp.zeros((channels,)),
             "A_log": jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)), "D": jnp.ones((H,)),
             "dt_bias": jnp.full((H,), -3.0), "norm": jnp.ones((inner,)), "out_proj": 0.02 * jnp.ones((inner, HIDDEN))}
        fn = functools.partial(lm_mamba.mamba_mixer, heads=H, head_dim=P, state=K, chunk=CHUNK, eps=1e-5)
        return jax.jit(jax.grad(lambda p_, x_: jnp.sum(fn(p_, x_)[0].astype(jnp.float32)), (0, 1))), (p, hidden_x)

    lm_mamba.fuses = lambda *a: False
    say("mamba mixer, plain SSD, fwd+bwd", timed(*mixer_loss(), it))
    lm_mamba.fuses = takes

    for group in map(int, args.groups.split(",")):
        lm_mamba.SSD_HEAD_GROUP = group
        jax.clear_caches()  # the kernels' calls are jitted: a trace at another group would be reused
        tag = {"heads_at_once": lm_mamba.head_group(H, P), "fits": lm_mamba.fuses(S, CHUNK, H, P, K, jnp.bfloat16)}
        if not tag["fits"]:
            say("ssd kernels: refused by fuses", -1.0, **tag)
            continue
        try:
            say("chunk own states, forward kernel", timed(lambda *a: lm_mamba.own_fwd(*a, chunk=CHUNK), own_args, it), **tag)
            say("chunk own states, backward kernel alone", timed(lambda *a: lm_mamba.own_bwd(*a, chunk=CHUNK), (*own_args, d_own),
                                                                 it), **tag)
            say("chunk outputs, forward kernel", timed(lm_mamba.chunk_fwd, outputs_args, it), **tag)
            say("chunk outputs, backward kernel alone", timed(lm_mamba.chunk_bwd, (*outputs_args, flat_ct), it), **tag)
            fused = jax.jit(lambda *a: core(*a))
            fused_grads = grads(lambda *a: core(*a))
            say("ssd_core, kernels, fwd", timed(fused, core_args, it), **tag)
            say("ssd_core, kernels, fwd+bwd", timed(fused_grads, (*core_args, ct), it), **tag)
            say("mamba mixer, SSD kernels, fwd+bwd", timed(*mixer_loss(), it), **tag)
            got = (fused(*core_args), *fused_grads(*core_args, ct))
            print(json.dumps({"piece": "ssd_core, kernels against plain (y, dx, ddelta, dlog_decay, db, dc, dD)", **tag,
                              "deviation": deviation(got, want)}), flush=True)
        except Exception as e:  # noqa: BLE001 - a group the compiler refuses is a finding, not a crash
            say("ssd kernels: refused", -1.0, error=str(e)[:400], **tag)
    return 0


if __name__ == "__main__":
    sys.exit(main())
