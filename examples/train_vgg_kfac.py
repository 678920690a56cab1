"""The paper's §6 experiment, end-to-end: modified VGG16_bn (2×1 pooling →
widened FC0) on a CIFAR-like stream, optimizer selectable.

    PYTHONPATH=src python examples/train_vgg_kfac.py \
        --optimizer bkfac --steps 100 --preset small

Presets: ``small`` (CPU-friendly) / ``paper`` (full modified VGG16_bn —
16384×2048 FC0; needs accelerator-scale time budget).
"""
import argparse
import time

import jax
import numpy as np

from repro.core import kfac as kfac_lib
from repro.core import policy as policy_lib
from repro.data.synthetic import ImageStream
from repro.models.cnn import VggConfig, make_vgg
from repro.optim import base as optbase
from repro.train import loop


def kfac_setup(optimizer: str, preset: str, stagger: bool = False,
               stagger_splits: int = 4):
    """(init, loss_fn, accuracy, Kfac) for a preset: the paper's §6
    optimizer settings (T_brand=5, T_inv=25 cadence, paper lr/damping
    schedules) on the preset's VGG."""
    if preset == "paper":
        cfg = VggConfig(stages=(64, 128, 256, 512, 512), fc_hidden=2048,
                        n_stat=256)
        r = 230
    else:
        cfg = VggConfig(stages=(16, 32, 64), fc_hidden=512, n_stat=64)
        r = 96

    init, loss_fn, accuracy, taps = make_vgg(cfg)
    kcfg = kfac_lib.KfacConfig(
        policy=policy_lib.PolicyConfig(variant=optimizer, r=r,
                                       max_dense_dim=4096),
        lr=optbase.paper_lr_schedule(steps_per_epoch=50),
        damping_phi=optbase.paper_damping_schedule(steps_per_epoch=50),
        weight_decay=7e-4, clip=0.5,
        T_updt=5, T_inv=25, T_brand=5, T_rsvd=25, T_corct=25,
        stagger=stagger, stagger_splits=stagger_splits,
        fallback_lr=optbase.constant(3e-3))
    return init, loss_fn, accuracy, kfac_lib.Kfac(kcfg, taps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--optimizer", default="bkfac",
                    choices=list(policy_lib.VARIANTS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--preset", default="small",
                    choices=("small", "paper"))
    ap.add_argument("--stagger", action="store_true",
                    help="phase heavy factor work across the T_inv window "
                         "(flat per-step cost instead of periodic spikes)")
    ap.add_argument("--stagger-splits", type=int, default=4)
    args = ap.parse_args()

    init, loss_fn, accuracy, opt = kfac_setup(
        args.optimizer, args.preset, stagger=args.stagger,
        stagger_splits=args.stagger_splits)
    # run_kfac_training drives the work scheduler (staggered iff
    # cfg.stagger); pass dist=DistSpec(mesh=..., curvature_axis=...)
    # there to also shard the factor work across a device mesh
    # (docs/distributed.md, repro.specs)

    stream = ImageStream(batch=args.batch, seed=0)
    batches = [stream.batch_at(i) for i in range(args.steps)]
    params = init(jax.random.PRNGKey(0))

    t0 = time.time()
    log = []

    def cb(k, state, loss):
        if k % 10 == 0:
            acc = float(accuracy(state.params, stream.batch_at(10_000)))
            log.append((k, float(loss), acc))
            print(f"step {k:4d}  loss {float(loss):.4f}  "
                  f"holdout-acc {acc:.3f}  ({time.time()-t0:.0f}s)")

    state, losses = loop.run_kfac_training(loss_fn, opt, params, batches,
                                           n_tokens=args.batch, callback=cb)
    acc = float(accuracy(state.params, stream.batch_at(10_000)))
    print(f"[{args.optimizer}] final loss {np.mean(losses[-5:]):.4f}  "
          f"holdout-acc {acc:.3f}  total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
