"""Quickstart: the CBO cascade in ~60 lines.

Builds a tiny two-tier stack on synthetic video frames, calibrates the fast
tier's confidence, and runs one confidence-gated batch through the cascade.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ResNetConfig
from repro.core.calibration import PlattCalibrator, ece
from repro.core.cascade import cascade_classify
from repro.core.confidence import max_softmax
from repro.data.video import VideoDataConfig, make_dataset
from repro.models import api
from repro.models.transformer import ParallelPlan
from repro.quant.quantize import qdq_tree


def main():
    # 1. data: class-conditional synthetic video frames with difficulty skew
    data = make_dataset(VideoDataConfig(n_classes=10, img_res=32), n_videos=40, seed=0)
    frames, labels = jnp.asarray(data["frames"][:64]), data["labels"][:64]

    # 2. two tiers: a small int8-quantized "NPU" model + a larger fp model
    fast_cfg = ResNetConfig(name="fast", img_res=32, depths=(1,), width=8, n_classes=10)
    slow_cfg = ResNetConfig(name="slow", img_res=32, depths=(2, 2), width=32, n_classes=10)
    fast = api.build(fast_cfg, ParallelPlan(remat=False))
    slow = api.build(slow_cfg, ParallelPlan(remat=False))
    fast_params = qdq_tree(fast.init(jax.random.PRNGKey(0), dtype=jnp.float32))  # "NPU" numerics
    slow_params = slow.init(jax.random.PRNGKey(1), dtype=jnp.float32)

    # 3. calibrate the fast tier's confidence (paper §III-B)
    logits = fast.forward(fast_params, frames)
    conf = np.asarray(max_softmax(logits))
    correct = (np.argmax(np.asarray(logits), -1) == labels).astype(float)
    platt = PlattCalibrator.fit(conf, correct)
    print(f"uncalibrated ECE={ece(conf, correct):.3f} -> calibrated ECE={ece(np.asarray(platt(conf)), correct):.3f}")

    # 4. one cascade batch: escalate the K=16 least-confident frames
    out = cascade_classify(
        lambda x: fast.forward(fast_params, x),
        lambda x: slow.forward(slow_params, x),
        platt,
        frames,
        threshold=0.6,
        capacity=16,
        resolution=24,
    )
    print(f"escalated {int(np.asarray(out.escalated).sum())}/64 frames "
          f"(mean conf {float(out.conf.mean()):.3f})")
    print("final predictions:", np.asarray(out.preds)[:16], "...")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
