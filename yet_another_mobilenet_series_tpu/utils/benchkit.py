"""Shared fixture builder for the throughput benchmarks (bench.py and
scripts/bench_bn.py) so the headline recipe — MobileNetV3-L, RMSProp+WD,
exp-decay LR, EMA, bf16, device-resident fake batch — exists in one place.

Also home of the benches' device barrier: see ``sync``.
"""

from __future__ import annotations

import jax
import numpy as np


def sync(arr) -> float:
    """Hard sync: device_get of a dependent scalar. Dispatch is asynchronous,
    so a timed region must end in something that waits for the work; a
    device->host read of a value that depends on it is a barrier on every
    backend and hands the caller the number it usually wants to log."""
    return float(np.asarray(jax.device_get(arr)).ravel()[0])


def build_train_fixture(
    batch: int,
    image_size: int,
    *,
    remat: bool = False,
    remat_policy: str = "full",
    bn_mode: str = "exact",
    conv1x1_dot: bool = False,
    arch: str = "mobilenet_v3_large",
):
    """Returns (step_fn, replicated_train_state, sharded_batch, net) for the
    headline training recipe at the given global batch, on the full visible
    device mesh."""
    from ..config import config_from_dict
    from ..models import get_model
    from ..parallel import dp, mesh as mesh_lib
    from ..train import optim, schedules, steps

    cfg = config_from_dict({
        "model": {"arch": arch, "dropout": 0.2},
        "optim": {"optimizer": "rmsprop", "weight_decay": 1e-5},
        "schedule": {"schedule": "exp_decay", "base_lr": 0.064, "warmup_epochs": 5.0},
        "ema": {"enable": True},
        "train": {"batch_size": batch, "compute_dtype": "bfloat16",
                  "remat": remat, "remat_policy": remat_policy, "bn_mode": bn_mode,
                  "conv1x1_dot": conv1x1_dot},
    })
    net = get_model(cfg.model, image_size)
    mesh = mesh_lib.make_mesh(len(jax.devices()))
    lr_fn = schedules.make_lr_schedule(cfg.schedule, batch, 1281167 // batch, 350)
    params, _ = net.init(jax.random.PRNGKey(0))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params)
    ts = steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0))
    ts = mesh_lib.replicate(ts, mesh)
    step_fn = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh)
    rng = np.random.RandomState(0)
    host_batch = {
        "image": rng.normal(0, 1, (batch, image_size, image_size, 3)).astype(np.float32),
        "label": (np.arange(batch) % 1000).astype(np.int32),
    }
    b = mesh_lib.shard_batch(host_batch, mesh)
    return step_fn, ts, b, net
