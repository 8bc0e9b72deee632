"""Device-resident fused eval: exact sums, matches the streaming evaluator."""

import jax
import jax.numpy as jnp
import numpy as np

from tpu_dist.comm import mesh as mesh_lib
from tpu_dist.config import TrainConfig
from tpu_dist.data import synthetic_cifar
from tpu_dist.train.epoch import make_fused_eval, put_dataset_on_device
from tpu_dist.train.optim import SGD
from tpu_dist.train.state import TrainState
from tpu_dist.train.trainer import Trainer, register_model
from tests.helpers import TinyConvNet, tiny_resnet

register_model("tiny_resnet_fe", lambda num_classes=10: tiny_resnet(num_classes))


def test_fused_eval_counts_and_matches_direct_forward():
    mesh = mesh_lib.data_parallel_mesh()
    model = TinyConvNet(num_classes=10)
    params, bn = model.init(jax.random.PRNGKey(0))
    state = jax.device_put(
        TrainState.create(params, bn, SGD()), mesh_lib.replicated(mesh)
    )
    # 131 examples: not a multiple of 8 devices nor of the batch
    n = 131
    imgs, lbls = synthetic_cifar(n, 10, image_size=8, seed=3)
    pad = (-n) % 8
    imgs_p = np.concatenate([imgs, np.zeros((pad,) + imgs.shape[1:], imgs.dtype)])
    lbls_p = np.concatenate([lbls, np.full(pad, -1, lbls.dtype)])
    dx, dy = put_dataset_on_device(mesh, imgs_p, lbls_p)

    ev = make_fused_eval(model.apply, mesh, batch_per_device=4, compute_dtype=jnp.float32)
    sums = {k: float(v) for k, v in ev(state, dx, dy).items()}
    assert sums["count"] == n

    # ground truth: direct forward over the raw set
    from tpu_dist.data.transforms import CIFAR100_MEAN, CIFAR100_STD

    x = (imgs.astype(np.float32) / 255.0 - CIFAR100_MEAN) / CIFAR100_STD
    logits, _ = model.apply(params, bn, jnp.asarray(x), train=False)
    expect_top1 = int((np.argmax(np.asarray(logits), -1) == lbls).sum())
    assert int(sums["top1"]) == expect_top1


def test_trainer_fused_mode_evaluates():
    cfg = TrainConfig(
        dataset="synthetic", model="tiny_resnet_fe", num_classes=10,
        batch_size=256, epochs=1, eval_every=1, fused_epoch=True,
        synthetic_n=1024, log_every=100,
    )
    out = Trainer(cfg).fit()
    assert "val_top1" in out and np.isfinite(out["val_loss"])
