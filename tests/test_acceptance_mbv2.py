"""Acceptance #1 at FULL scale (VERDICT r2 next-round #4): a MobileNetV2-1.0
torch state_dict (torchvision layout, built by the same generator the unit
tests use), saved as a real .pth, evaluated through the REAL eval CLI on a
~200-image set of REAL JPEGs — importer + JPEG decode + eval transform + eval
counting welded into one executed path, through BOTH input pipelines
(dataset=folder/native C++ loader and the TFRecord/tf.data path).

Ground truth: each image's label is the torch model's own argmax computed
through an INDEPENDENT decode chain (PIL decode + torch bilinear resize +
center crop + normalize). The torch model's top-1 against these labels is
1.0 by construction, so our CLI's top-1 measures end-to-end agreement of the
import and the full input pipeline; small decoder/resize implementation
differences may flip near-tie argmaxes, hence the tolerance.

JPEGs are saved 4:4:4 (subsampling=0) from smooth synthetic content so
libjpeg chroma-upsampling differences between the three decoders (PIL, tf,
native libjpeg) stay sub-LSB.
"""

import os

import numpy as np
import pytest

import torch
import torch.nn.functional as F
from PIL import Image

from yet_another_mobilenet_series_tpu.cli import train as cli_train
from yet_another_mobilenet_series_tpu.config import DataConfig, ModelConfig, config_from_dict
from yet_another_mobilenet_series_tpu.models import get_model

from test_torch_import import TorchTinyMBV2

N_IMAGES = 200
# the SAME normalization the eval pipelines read from config — hardcoded
# copies here would silently diverge if the defaults ever changed
MEAN = tuple(DataConfig().mean)
STD = tuple(DataConfig().std)

pytestmark = pytest.mark.slow


def _make_jpegs(root, n, seed=0):
    """n smooth random JPEGs with varied sizes (exercises resize-shorter)."""
    os.makedirs(root, exist_ok=True)
    rs = np.random.RandomState(seed)
    paths = []
    for i in range(n):
        h, w = int(rs.randint(240, 321)), int(rs.randint(240, 321))
        low = rs.uniform(0, 255, (8, 8, 3)).astype(np.uint8)
        img = Image.fromarray(low).resize((w, h), Image.BICUBIC)
        p = os.path.join(root, f"img_{i:04d}.jpg")
        img.save(p, quality=95, subsampling=0)
        paths.append(p)
    return paths


def _torch_preprocess(path, eval_resize=256, crop=224):
    """PIL decode + torch bilinear resize-shorter + center crop + normalize —
    the reference Resize(256)/CenterCrop(224) recipe (SURVEY.md §3.3),
    matching data/pipeline.py:_decode_center_crop's rounding."""
    img = np.asarray(Image.open(path).convert("RGB"), np.float32)
    h, w = img.shape[:2]
    ratio = eval_resize / min(h, w)
    rh, rw = int(round(h * ratio)), int(round(w * ratio))
    t = torch.from_numpy(img.transpose(2, 0, 1))[None]
    t = F.interpolate(t, size=(rh, rw), mode="bilinear", align_corners=False)
    top, left = (rh - crop) // 2, (rw - crop) // 2
    t = t[..., top : top + crop, left : left + crop] / 255.0
    mean = torch.tensor(MEAN)[None, :, None, None]
    std = torch.tensor(STD)[None, :, None, None]
    return (t - mean) / std


@pytest.fixture(scope="module")
def mbv2_fixture(tmp_path_factory):
    """Full MobileNetV2-1.0, its .pth, the labeled ImageFolder tree, and the
    torch-side predictions — shared by the folder-path and TFRecord tests."""
    tmp = tmp_path_factory.mktemp("mbv2_acceptance")
    net = get_model(ModelConfig(arch="mobilenet_v2", dropout=0.0), image_size=224)
    torch.manual_seed(0)
    tm = TorchTinyMBV2(net, 1000)
    for m in tm.modules():
        if isinstance(m, torch.nn.BatchNorm2d):
            m.running_mean.copy_(torch.randn_like(m.running_mean) * 0.3)
            m.running_var.copy_(torch.rand_like(m.running_var) * 2 + 0.5)
            m.weight.data.copy_(torch.rand_like(m.weight) + 0.5)
            m.bias.data.copy_(torch.randn_like(m.bias) * 0.2)
    tm.eval()
    pth = str(tmp / "mobilenet_v2_full.pth")
    torch.save(tm.state_dict(), pth)

    raw = str(tmp / "raw")
    paths = _make_jpegs(raw, N_IMAGES)
    preds = []
    with torch.no_grad():
        for i in range(0, N_IMAGES, 25):
            batch = torch.cat([_torch_preprocess(p) for p in paths[i : i + 25]])
            preds.extend(tm(batch).argmax(1).tolist())

    # ImageFolder tree with ALL 1000 class dirs (most empty) so sorted-dir
    # rank == class id and folder labels live in the net's own label space
    val_root = str(tmp / "data" / "val")
    for c in range(1000):
        os.makedirs(os.path.join(val_root, f"{c:04d}"), exist_ok=True)
    for p, cls in zip(paths, preds):
        os.link(p, os.path.join(val_root, f"{cls:04d}", os.path.basename(p)))
    return {"pth": pth, "data_root": str(tmp / "data"), "preds": preds, "tmp": tmp}


def _eval_cfg(fix, log_dir, **data_over):
    data = {"image_size": 224, "eval_resize": 256, "num_eval_examples": N_IMAGES}
    data.update(data_over)
    return config_from_dict({
        "name": "mbv2_acceptance",
        "model": {"arch": "mobilenet_v2", "dropout": 0.0},
        "data": data,
        "train": {
            "test_only": True,
            "torch_pretrained": fix["pth"],
            "eval_batch_size": 50,
            "compute_dtype": "float32",
            "log_dir": str(log_dir),
        },
        # acceptance #1 is single-process eval (SURVEY.md §3.3)
        "dist": {"num_devices": 1},
    })


def test_full_scale_bfloat16_predictions_agree_with_torch(mbv2_fixture):
    """At full scale: the imported MBV2's predictions on the real JPEGs,
    forwarded in bfloat16 (the production training dtype), agree with the
    torch-side float32 ground truth to the acceptance tolerance (bf16
    rounding ~ decoder noise, both sub-percent)."""
    from yet_another_mobilenet_series_tpu.ckpt.torch_import import load_torch_checkpoint

    net = get_model(ModelConfig(arch="mobilenet_v2", dropout=0.0), image_size=224)
    params, state = load_torch_checkpoint(mbv2_fixture["pth"], net)

    raw = str(mbv2_fixture["tmp"] / "raw")
    paths = sorted(os.path.join(raw, f) for f in os.listdir(raw) if f.endswith(".jpg"))
    assert len(paths) == N_IMAGES
    # 100 of the 200 fixture images: the full bf16 predict passes dominate
    # this test and the 0.95 agreement threshold is equally meaningful
    # at n=100 (granularity 1%); the eval-CLI tests below still consume all 200
    paths = paths[::2]
    # the torch-side preprocessing chain
    imgs = np.concatenate(
        [_torch_preprocess(p).numpy() for p in paths]
    ).transpose(0, 2, 3, 1)  # NHWC

    import jax
    import jax.numpy as jnp

    @jax.jit
    def fwd(x):
        logits, _ = net.apply(params, state, x.astype(jnp.bfloat16), train=False, compute_dtype=jnp.bfloat16)
        return jnp.argmax(logits, -1)

    preds = np.concatenate([np.asarray(fwd(imgs[i : i + 50])) for i in range(0, len(imgs), 50)])
    assert np.mean(preds == np.asarray(mbv2_fixture["preds"])[::2]) >= 0.95


def test_full_scale_eval_folder_native(mbv2_fixture, tmp_path):
    cfg = _eval_cfg(
        mbv2_fixture, tmp_path,
        dataset="folder", loader="native", data_dir=mbv2_fixture["data_root"], val_split="val",
    )
    result = cli_train.run(cfg)
    assert result["n"] == N_IMAGES  # every real example counted exactly once
    # torch's own top-1 on these labels is 1.0 by construction; ours may lose
    # a few near-tie argmaxes to decoder/resize implementation differences
    assert result["top1"] >= 0.95, result
    mbv2_fixture["native_top1"] = result["top1"]


def test_full_scale_eval_tfrecord(mbv2_fixture, tmp_path):
    import subprocess
    import sys

    tfdir = str(mbv2_fixture["tmp"] / "tfrecords")
    script = os.path.join(os.path.dirname(__file__), "..", "scripts", "imagefolder_to_tfrecords.py")
    subprocess.run(
        [sys.executable, script, "--src", os.path.join(mbv2_fixture["data_root"], "val"),
         "--dst", tfdir, "--split", "validation", "--shards", "2"],
        check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    cfg = _eval_cfg(
        mbv2_fixture, tmp_path,
        dataset="imagenet", loader="tfdata", data_dir=tfdir, val_split="validation",
    )
    result = cli_train.run(cfg)
    assert result["n"] == N_IMAGES
    assert result["top1"] >= 0.95, result
    if "native_top1" in mbv2_fixture:
        # the two pipelines decode the same JPEGs: their top-1s must agree
        # to within a couple of near-tie flips
        assert abs(result["top1"] - mbv2_fixture["native_top1"]) <= 0.02
