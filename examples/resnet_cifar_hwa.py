"""Paper-faithful pipeline: ResNet-CIFAR + BatchNorm + SGD(momentum 0.9,
wd 5e-4) + cosine LR + HWA with H = one epoch — including Algorithm 2's
BatchNorm-statistics recompute on the averaged weights.

This is the paper's own experimental protocol transplanted onto the
synthetic prototype-image task (offline container; DESIGN.md §8).

  PYTHONPATH=src python examples/resnet_cifar_hwa.py --epochs 6
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core import (HWAConfig, hwa_init, hwa_inner_step, hwa_sync,
                        replica_divergence)
from repro.core.bnstats import recompute_bn_stats
from repro.data import make_prototype_image_dataset
from repro.data.pipeline import replica_batch_indices
from repro.models.convnet import (apply_resnet, init_resnet, resnet_loss,
                                  resnet_cifar_config)
from repro.optim import cosine_schedule, sgd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--window", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=32)
    args = ap.parse_args()

    cfg = resnet_cifar_config(depth=args.depth, n_classes=10, image_size=16)
    ds = make_prototype_image_dataset(n_classes=10, image_size=16,
                                      n_train=2048, n_test=512, noise=0.6,
                                      label_noise=0.05)
    steps_per_epoch = ds.n_train // args.batch_size
    total_steps = steps_per_epoch * args.epochs
    opt = sgd(momentum=0.9, weight_decay=5e-4)
    sched = cosine_schedule(0.1, total_steps)
    hcfg = HWAConfig(n_replicas=args.k, sync_period=steps_per_epoch,
                     window=args.window)

    params, bn_state = init_resnet(cfg, jax.random.key(0))
    # fold BN state into the averaged pytree (stats are averaged online;
    # the W̿ stats get recomputed per Algorithm 2 line 3)
    state = hwa_init(hcfg, {"p": params, "bn": bn_state}, opt)
    data_key = jax.random.key(1)

    def loss_fn(bundle, batch):
        loss, metrics = resnet_loss(cfg, bundle["p"], bundle["bn"], batch)
        return loss, metrics

    @jax.jit
    def inner(state, step):
        def batch_for(r):
            idx = replica_batch_indices(data_key, r, step, ds.n_train,
                                        args.batch_size)
            return {"tokens": jnp.take(ds.train_inputs, idx, 0),
                    "targets": jnp.take(ds.train_targets, idx, 0)}
        batches = jax.vmap(batch_for)(jnp.arange(args.k))
        state, metrics = hwa_inner_step(hcfg, state, batches, loss_fn, opt,
                                        sched(step))
        return state, metrics["loss"]

    @jax.jit
    def evaluate(bundle):
        logits, _ = apply_resnet(cfg, bundle["p"], bundle["bn"],
                                 ds.test_inputs, train=False)
        return jnp.mean((jnp.argmax(logits, -1) == ds.test_targets)
                        .astype(jnp.float32))

    for step in range(total_steps):
        state, loss = inner(state, step)
        if (step + 1) % steps_per_epoch == 0:
            div = replica_divergence(state.inner)   # before the restart
            state, _ = hwa_sync(hcfg, state)
            wa = state.wa
            # Algorithm 2 line 3: recompute BN statistics under W̿
            bn = recompute_bn_stats(
                cfg, wa["p"], wa["bn"],
                [ds.train_inputs[i:i + 256]
                 for i in range(0, 1024, 256)])
            acc = evaluate({"p": wa["p"], "bn": bn})
            print(f"epoch {(step + 1) // steps_per_epoch}: "
                  f"train loss {float(loss):.4f}  "
                  f"W̿ test acc {float(acc):.4f}  "
                  f"replica divergence {float(div):.3f}")


if __name__ == "__main__":
    main()
