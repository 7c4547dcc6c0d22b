"""End-to-end integration tests through cli.train.run() (SURVEY.md §4.3):
fake-data training loss decreases, checkpoint save->resume, eval-only path,
and the AtomNAS shrink-mid-run->resume survival test."""

import glob
import json
import os

import numpy as np
import pytest

import jax

from yet_another_mobilenet_series_tpu.cli import train as cli_train
from yet_another_mobilenet_series_tpu.config import config_from_dict


def _base_cfg(tmp_path, **over):
    d = {
        "name": "itest",
        "model": {
            "arch": "mobilenet_v2",
            "num_classes": 8,
            "dropout": 0.0,
            "block_specs": [
                {"t": 3, "c": 16, "n": 1, "s": 2, "k": 3},
                {"t": 3, "c": 24, "n": 1, "s": 2, "k": 3},
            ],
        },
        "data": {"dataset": "fake", "image_size": 32, "fake_train_size": 1280, "fake_eval_size": 64},
        # SGD+momentum: stable on tiny toy nets under ANY data order (tf.data
        # shuffle depends on the process-global TF seed, which other test
        # modules may set; RMSProp diverged on some orderings)
        "optim": {"optimizer": "sgd", "momentum": 0.9, "weight_decay": 1e-5},
        "schedule": {"schedule": "constant", "base_lr": 0.05, "scale_by_batch": False, "warmup_epochs": 0.5},
        "ema": {"enable": True, "decay": 0.99, "warmup": True},
        "train": {
            "batch_size": 64,
            "eval_batch_size": 64,
            "epochs": 2,
            "log_every": 2,
            "compute_dtype": "float32",
            "log_dir": str(tmp_path),
            "eval_every_epochs": 1.0,
        },
        "dist": {"num_devices": 8},
    }
    for k, v in over.items():
        cur = d
        ks = k.split(".")
        for kk in ks[:-1]:
            cur = cur.setdefault(kk, {})
        cur[ks[-1]] = v
    return config_from_dict(d)


def test_train_run_learns_and_checkpoints(tmp_path):
    cfg = _base_cfg(tmp_path, **{"train.epochs": 3})
    result = cli_train.run(cfg)
    assert result["epoch"] == pytest.approx(3.0)
    # learnable synthetic task: far above chance (1/8) once EMA/BN warm up
    assert result["eval_top1"] > 0.5, result
    assert result["eval_n"] == 64
    # a checkpoint with spec sidecar exists
    assert glob.glob(str(tmp_path) + "/ckpt/*/meta*")


@pytest.mark.slow
def test_resume_continues_from_checkpoint(tmp_path, capsys):
    cfg = _base_cfg(tmp_path, **{"train.epochs": 1})
    cli_train.run(cfg)
    cfg2 = _base_cfg(tmp_path, **{"train.epochs": 2})
    cli_train.run(cfg2)
    out = capsys.readouterr().out
    assert "resumed at step 20" in out  # 1280/64 = 20 steps/epoch


@pytest.mark.slow
def test_eval_only_with_pretrained(tmp_path):
    cfg = _base_cfg(tmp_path)
    trained = cli_train.run(cfg)
    cfg_eval = _base_cfg(tmp_path, **{"train.test_only": True})
    result = cli_train.run(cfg_eval)
    np.testing.assert_allclose(result["top1"], trained["eval_top1"], atol=1e-6)


@pytest.mark.parametrize("zero", [
    # the plain variant's path is covered by the other (which adds exactly
    # one knob to it) — opt-in only, to keep the suite bar ~3 min lighter
    # without dropping a unique path (VERDICT r4 next #8)
    pytest.param(False, id="replicated", marks=pytest.mark.exhaustive),
    pytest.param(True, id="zero"),
])
@pytest.mark.slow
def test_atomnas_search_shrinks_and_resumes(tmp_path, capsys, zero):
    over = {
        # zero=True exercises the shipped atomnas_c_se combination: remat must
        # gather the ZeRO shards before slicing and re-scatter after.
        "dist.shard_optimizer": zero,
        "model.arch": "atomnas_supernet",
        "model.block_specs": [
            {"t": 6, "c": 16, "n": 2, "s": 2, "k": [3, 5, 7]},
            {"t": 6, "c": 24, "n": 1, "s": 2, "k": [3, 5, 7], "se": 0.25},
        ],
        "prune.enable": True,
        "prune.rho": 0.05,
        "prune.gamma_threshold": 0.6,  # aggressive: init gamma=1 must be pushed below
        "prune.mask_interval": 2,
        "prune.remat_epochs": 1.0,
        "prune.stop_epoch_frac": 1.0,
        "train.epochs": 2,
        "schedule.base_lr": 0.12,
    }
    cfg = _base_cfg(tmp_path, **over)
    result = cli_train.run(cfg)
    out = capsys.readouterr().out
    assert "penalty=" in out
    assert result["epoch"] == pytest.approx(2.0)
    _check_resume(tmp_path, over, capsys)


@pytest.mark.slow
def test_adaptive_rho_reaches_target_where_constant_does_not(tmp_path):
    """SURVEY.md §2 #11 rho schedule: with a deliberately too-small base rho
    the constant schedule never pushes any gamma below threshold, while the
    adaptive controller multiplies rho up on the FLOPs gap until the search
    actually shrinks toward target_flops."""
    base = {
        "model.arch": "atomnas_supernet",
        "model.block_specs": [
            {"t": 6, "c": 16, "n": 2, "s": 2, "k": [3, 5, 7]},
            {"t": 6, "c": 24, "n": 1, "s": 2, "k": [3, 5, 7]},
        ],
        "prune.enable": True,
        # raw (unnormalized) atom costs with a base rho far too small to move
        # any gamma on its own — only the adaptive multiplier can make the
        # penalty bite (verified: constant ends at full 3.4M MACs, adaptive
        # at 0.7M)
        "prune.rho": 3e-7,
        "prune.normalize_cost": False,
        "prune.gamma_threshold": 0.6,
        "prune.mask_interval": 2,
        "prune.remat_epochs": 0.0,  # keep shapes; judge by effective (masked) MACs
        "prune.stop_epoch_frac": 1.0,
        "prune.target_flops": 1.0,  # unreachably low => constant pressure up
        "train.epochs": 2,
        "schedule.base_lr": 0.12,
    }

    def final_macs(subdir, **extra):
        cfg = _base_cfg(tmp_path / subdir, **{**base, **extra})
        cli_train.run(cfg)
        with open(str(tmp_path / subdir / "searched_arch.json")) as f:
            return json.load(f)["macs"]

    macs_const = final_macs("const")
    macs_adapt = final_macs(
        "adapt",
        **{
            "prune.rho_schedule": "adaptive",
            "prune.rho_adapt_rate": 0.35,
            "prune.rho_adapt_max": 1000.0,
        },
    )
    # constant stays at the full supernet (~3.4M); adaptive shrinks hard
    assert macs_adapt < 0.5 * macs_const, (macs_adapt, macs_const)


@pytest.mark.slow
def test_search_emit_retrain_seam(tmp_path):
    """The acceptance #4 -> #5 handoff (VERDICT r2 next-round #5): an AtomNAS
    search run emits searched_arch.json; the emitted spec then trains and
    evals STANDALONE through model.network_spec (the retrain_searched.yml
    path) with pruning off, and its MACs equal the emitted spec's."""
    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.utils.profiling import profile_network

    search_over = {
        "model.arch": "atomnas_supernet",
        "model.block_specs": [
            {"t": 6, "c": 16, "n": 2, "s": 2, "k": [3, 5, 7]},
            {"t": 6, "c": 24, "n": 1, "s": 2, "k": [3, 5, 7], "se": 0.25},
        ],
        "prune.enable": True,
        # the adaptive-controller recipe the rho-schedule test proves shrinks
        # hard (constant rho at this base never prunes); remat at epoch
        # boundaries so the EMITTED spec is physically pruned
        "prune.rho": 3e-7,
        "prune.normalize_cost": False,
        "prune.rho_schedule": "adaptive",
        "prune.rho_adapt_rate": 0.35,
        "prune.rho_adapt_max": 1000.0,
        "prune.target_flops": 1.0,
        "prune.gamma_threshold": 0.6,
        "prune.mask_interval": 2,
        "prune.remat_epochs": 1.0,
        "prune.stop_epoch_frac": 1.0,
        "train.epochs": 2,
        "schedule.base_lr": 0.12,
    }
    cli_train.run(_base_cfg(tmp_path / "search", **search_over))
    spec_path = str(tmp_path / "search" / "searched_arch.json")
    with open(spec_path) as f:
        emitted = json.load(f)
    # the search must actually have pruned below the full supernet
    full = profile_network(
        get_model(_base_cfg(tmp_path / "search", **search_over).model, 32), 32
    ).total_macs
    assert emitted["macs"] < full, (emitted["macs"], full)

    # standalone retrain from the emitted spec (pruning off, fresh log dir)
    retrain_cfg = _base_cfg(
        tmp_path / "retrain",
        **{"model.network_spec": spec_path, "train.epochs": 3},
    )
    rebuilt = get_model(retrain_cfg.model, 32)
    assert profile_network(rebuilt, 32).total_macs == emitted["macs"]
    result = cli_train.run(retrain_cfg)
    assert result["epoch"] == pytest.approx(3.0)
    # learnable synthetic task, 8 classes: clearly above chance (0.125)
    assert result["eval_top1"] > 0.3, result


def _check_resume(tmp_path, over, capsys):
    # the saved spec sidecar must encode the (possibly pruned) live network
    metas = sorted(glob.glob(str(tmp_path) + "/ckpt/*/meta/*"))
    assert metas
    # resume must rebuild from the sidecar without shape errors
    cfg3 = _base_cfg(tmp_path, **{**over, "train.epochs": 2.5})
    result2 = cli_train.run(cfg3)
    assert result2["epoch"] >= 2.0


@pytest.mark.slow
def test_warm_start_finetune_from_checkpoint(tmp_path, capsys):
    """train.pretrained on a fresh (non-resumed) training run warm-starts the
    weights with a fresh optimizer/step — after a few finetune steps accuracy
    stays near the source's, which a fresh init cannot reach that fast."""
    src_dir, ft_dir = tmp_path / "src", tmp_path / "ft"
    trained = cli_train.run(_base_cfg(src_dir, **{"train.epochs": 3}))
    assert trained["eval_top1"] > 0.5
    cfg_ft = _base_cfg(ft_dir, **{
        "train.epochs": 0.25,  # 5 steps
        "train.pretrained": str(src_dir / "ckpt"),
        "schedule.base_lr": 0.005,
    })
    result = cli_train.run(cfg_ft)
    out = capsys.readouterr().out
    assert "warm start from checkpoint" in out
    assert result["eval_top1"] > 0.5, result  # fresh init gets ~0.125 in 5 steps


@pytest.mark.slow
def test_warm_start_finetune_from_torch_checkpoint(tmp_path, capsys):
    import torch

    from tests.test_torch_import import _randomized_torch_model, _tiny_net

    net = _tiny_net(num_classes=8)
    tm = _randomized_torch_model(net, 8)
    torch.save(tm.state_dict(), str(tmp_path / "w.pth"))
    cfg = _base_cfg(tmp_path, **{
        "model.block_specs": [
            {"t": 1, "c": 16, "n": 1, "s": 1, "k": 3},
            {"t": 6, "c": 24, "n": 2, "s": 2, "k": 5},
        ],
        "train.epochs": 0.25,
        "train.torch_pretrained": str(tmp_path / "w.pth"),
    })
    result = cli_train.run(cfg)
    out = capsys.readouterr().out
    assert "warm start from torch checkpoint" in out
    assert result["epoch"] == pytest.approx(0.25)


@pytest.mark.slow
def test_best_checkpoint_kept_and_evaluable(tmp_path):
    """train.keep_best maintains a single-slot best-top1 checkpoint (the
    reference's best.pth); evaluating it reproduces the recorded best."""
    cfg = _base_cfg(tmp_path, **{"train.epochs": 3})
    result = cli_train.run(cfg)
    assert glob.glob(str(tmp_path) + "/ckpt_best/*/meta*")
    cfg_eval = _base_cfg(
        tmp_path, **{"train.test_only": True, "train.pretrained": str(tmp_path) + "/ckpt_best"}
    )
    best_eval = cli_train.run(cfg_eval)
    np.testing.assert_allclose(best_eval["top1"], result["eval_best_top1"], atol=1e-6)


@pytest.mark.slow
def test_resume_from_legacy_checkpoint_without_rho_mult(tmp_path, monkeypatch, capsys):
    """Checkpoints written before TrainState grew rho_mult must still resume
    (restore retries without the field and injects the neutral multiplier)."""
    from yet_another_mobilenet_series_tpu.train import steps as steps_mod

    over = {
        "model.arch": "atomnas_supernet",
        "model.block_specs": [{"t": 4, "c": 16, "n": 1, "s": 2, "k": [3, 5]}],
        "prune.enable": True,
        "prune.mask_interval": 4,
        "prune.remat_epochs": 0.0,
        "train.epochs": 1,
    }
    # simulate the legacy on-disk layout: save without the rho_mult leaf
    legacy_fields = tuple(f for f in steps_mod.TRAIN_STATE_FIELDS if f != "rho_mult")
    monkeypatch.setattr(steps_mod, "TRAIN_STATE_FIELDS", legacy_fields)
    cli_train.run(_base_cfg(tmp_path, **over))
    monkeypatch.undo()

    result = cli_train.run(_base_cfg(tmp_path, **{**over, "train.epochs": 1.5}))
    out = capsys.readouterr().out
    assert "retrying as legacy checkpoint" in out
    assert result["epoch"] >= 1.5
