"""Training driver with fault tolerance: train a fast-tier model with the
framework's Trainer, inject a simulated node failure mid-run, and watch the
supervisor restart from the last async checkpoint.

  PYTHONPATH=src python examples/train_fast_tier.py [--steps 120]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import ResNetConfig
from repro.data.pipeline import DeterministicPipeline, PipelineConfig, image_batch_fn
from repro.data.video import VideoDataConfig, make_dataset
from repro.models import api
from repro.models.transformer import ParallelPlan
from repro.train import optim
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_example")
    args = ap.parse_args()

    data = make_dataset(VideoDataConfig(n_classes=10, img_res=32), n_videos=240, seed=0)
    cfg = ResNetConfig(name="fast-tier", img_res=32, depths=(1, 1), width=16, n_classes=10)
    h = api.build(cfg, ParallelPlan(remat=False))
    params = h.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    print(f"model: {h.n_params():,} params")

    pipe = DeterministicPipeline(PipelineConfig(global_batch=128, seed=0),
                                 image_batch_fn(data), len(data["labels"]))
    tcfg = TrainConfig(
        n_steps=args.steps,
        ckpt_every=20,
        ckpt_dir=args.ckpt_dir,
        log_every=20,
        fail_at_step=args.steps // 2,  # fault-tolerance drill
        ocfg=optim.OptimConfig(lr=3e-3, weight_decay=1e-4),
    )
    trainer = Trainer(tcfg, lambda p, b: h.loss(p, b), params, pipe)
    out = trainer.run_with_restarts(max_restarts=1)
    print(f"finished: {out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
