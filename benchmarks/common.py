"""Shared benchmark substrate: the two-tier stack on synthetic video.

Reproduces the paper's experimental *mechanics* offline (DESIGN.md §8):
  * slow tier = larger ResNet trained on the synthetic video dataset
    (plays ResNet-152-on-server);
  * fast tier = small ResNet, int8-quantized post-training
    (plays AlexNet-on-NPU: lower capacity AND lower precision);
  * both trained with the framework's own Trainer; cached under results/.

Everything is deterministic; `build_stack(force=True)` retrains.
"""
from __future__ import annotations

import json
import os
import pickle
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ResNetConfig
from repro.core.calibration import PlattCalibrator, ece
from repro.core.confidence import max_softmax
from repro.data.pipeline import DeterministicPipeline, PipelineConfig
from repro.data.video import VideoDataConfig, make_dataset
from repro.models import api
from repro.models.transformer import ParallelPlan
from repro.quant.quantize import qdq_tree
from repro.train import optim
from repro.train.trainer import TrainConfig, Trainer

CACHE = os.path.join(os.path.dirname(__file__), "..", "results", "bench_stack.pkl")

DATA_CFG = VideoDataConfig(
    n_classes=10, img_res=32, frames_per_video=12, noise_floor=0.3,
    class_difficulty=tuple(float(x) for x in np.clip(np.linspace(0.25, 1.05, 10), 0, 1)),
)
FAST_CFG = ResNetConfig(name="fast-tier", img_res=32, depths=(1,), width=6, n_classes=10)
SLOW_CFG = ResNetConfig(name="slow-tier", img_res=32, depths=(2, 2), width=48, n_classes=10)
RESOLUTIONS = (8, 12, 18, 24, 32)  # the paper's 45..224 ladder, scaled to 32px
# NPU numerics: int4 per-tensor QDQ. Finding (EXPERIMENTS.md): per-channel
# int8 is nearly lossless on this stack; reproducing the paper's 11-30% NPU
# accuracy loss requires the crude per-tensor low-bit regime of 2019-era
# NPU compilers.
NPU_QUANT = dict(bits=4, axis=None)


@dataclass
class TierStack:
    fast_params: dict
    slow_params: dict
    platt: PlattCalibrator
    acc_fast: float
    acc_slow: float
    acc_server_by_res: tuple
    calib: dict  # calibration split: conf/correct/labels/preds
    test: dict  # test split: frames/labels/video_id
    fast_params_fp: dict = None  # unquantized fast model (Compress baseline)

    def fast_forward(self, images):
        h = api.build(FAST_CFG, ParallelPlan(remat=False))
        return h.forward(self.fast_params, images)

    def slow_forward(self, images):
        h = api.build(SLOW_CFG, ParallelPlan(remat=False))
        return h.forward(self.slow_params, images)


def _train_tier(cfg: ResNetConfig, data, n_steps: int, lr: float, seed: int, *, res_augment: bool = False):
    h = api.build(cfg, ParallelPlan(remat=False))
    params = h.init(jax.random.PRNGKey(seed), dtype=jnp.float32)
    from repro.data.pipeline import image_batch_fn

    base_fn = image_batch_fn(data)
    if res_augment:
        # the server model sees degraded uploads in deployment (paper Fig 10):
        # train it resolution-robust by randomly degrading half of each batch
        from repro.core.cascade import degrade_resolution

        def batch_fn(rng, idx):
            b = base_fn(rng, idx)
            imgs = jnp.asarray(b["images"])
            r = RESOLUTIONS[int(rng.integers(len(RESOLUTIONS)))]
            n_aug = len(idx) // 2
            aug = degrade_resolution(imgs[:n_aug], r)
            return {"images": np.concatenate([np.asarray(aug), np.asarray(imgs[n_aug:])]),
                    "labels": b["labels"]}
    else:
        batch_fn = base_fn

    pipe = DeterministicPipeline(PipelineConfig(global_batch=128, seed=seed), batch_fn, len(data["labels"]))
    tcfg = TrainConfig(n_steps=n_steps, ckpt_every=10**9, ckpt_dir=f"/tmp/bench_ckpt_{cfg.name}",
                       log_every=max(n_steps // 4, 1), ocfg=optim.OptimConfig(lr=lr, weight_decay=1e-4))
    trainer = Trainer(tcfg, lambda p, b: h.loss(p, b), params, pipe)
    trainer.run(start_step=0)
    return trainer.state["params"]


def _accuracy(forward, params, frames, labels, bs=256):
    correct = 0
    logits_all = []
    for i in range(0, len(labels), bs):
        lg = forward(params, jnp.asarray(frames[i : i + bs]))
        logits_all.append(np.asarray(lg))
        correct += int((np.argmax(np.asarray(lg), -1) == labels[i : i + bs]).sum())
    return correct / len(labels), np.concatenate(logits_all)


def build_stack(force: bool = False, verbose: bool = True) -> TierStack:
    if os.path.exists(CACHE) and not force:
        with open(CACHE, "rb") as f:
            return pickle.load(f)

    os.makedirs(os.path.dirname(CACHE), exist_ok=True)
    train = make_dataset(DATA_CFG, 360, seed=0)
    calib_d = make_dataset(DATA_CFG, 120, seed=1)
    test = make_dataset(DATA_CFG, 120, seed=2)

    if verbose:
        print("[common] training slow tier ...", flush=True)
    slow_params = _train_tier(SLOW_CFG, train, n_steps=700, lr=3e-3, seed=0, res_augment=True)
    if verbose:
        print("[common] training fast tier ...", flush=True)
    fast_params_fp = _train_tier(FAST_CFG, train, n_steps=500, lr=4e-3, seed=1)
    fast_params = qdq_tree(fast_params_fp, **NPU_QUANT)  # "NPU" numerics

    fh = api.build(FAST_CFG, ParallelPlan(remat=False))
    sh = api.build(SLOW_CFG, ParallelPlan(remat=False))

    acc_fast, fast_logits = _accuracy(fh.forward, fast_params, calib_d["frames"], calib_d["labels"])
    acc_slow, _ = _accuracy(sh.forward, slow_params, calib_d["frames"], calib_d["labels"])

    conf = np.asarray(max_softmax(jnp.asarray(fast_logits)))
    preds = np.argmax(fast_logits, -1)
    correct = (preds == calib_d["labels"]).astype(float)
    platt = PlattCalibrator.fit(conf, correct)

    # server accuracy per resolution (paper Fig. 10) on the calib split
    from repro.core.cascade import degrade_resolution

    acc_by_res = []
    for r in RESOLUTIONS:
        acc_r = 0
        n = len(calib_d["labels"])
        for i in range(0, n, 256):
            imgs = degrade_resolution(jnp.asarray(calib_d["frames"][i : i + 256]), r)
            lg = sh.forward(slow_params, imgs)
            acc_r += int((np.argmax(np.asarray(lg), -1) == calib_d["labels"][i : i + 256]).sum())
        acc_by_res.append(acc_r / n)

    stack = TierStack(
        fast_params=fast_params,
        slow_params=slow_params,
        platt=platt,
        acc_fast=acc_fast,
        acc_slow=acc_slow,
        acc_server_by_res=tuple(acc_by_res),
        calib={"conf": conf, "correct": correct, "logits": fast_logits, "labels": calib_d["labels"]},
        test=test,
        fast_params_fp=fast_params_fp,
    )
    with open(CACHE, "wb") as f:
        pickle.dump(stack, f)
    if verbose:
        print(f"[common] fast(int8)={acc_fast:.3f} slow={acc_slow:.3f} acc_by_res={np.round(acc_by_res,3)}", flush=True)
    return stack


def out_path(name: str) -> str:
    d = os.path.join(os.path.dirname(__file__), "..", "results", "bench")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, name)


def provenance() -> dict:
    """Reproducibility block attached to every bench artifact: where and
    when the numbers came from, and the device as JAX reports it
    (``platform``, ``device_kind``, ``devices`` count).  A missing git
    checkout degrades ``git_sha`` to ``None``; the device probe raises."""
    import datetime
    import subprocess

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        sha = None
    dev = jax.devices()[0]
    return {
        "git_sha": sha,
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "numpy": np.__version__,
        "jax": jax.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": jax.device_count(),
    }


def emit_bench_json(name: str, payload: dict, *, mirror: str = None) -> str:
    """Single emission point for benchmark artifacts under ``results/bench/``.

    Every ``BENCH_*.json`` goes through here so the artifacts share one
    serialization policy (indent=2, trailing newline, numpy scalars coerced
    to plain floats) and one ``provenance`` block (git sha, UTC timestamp,
    library versions, device count).  ``mirror`` writes the same payload under
    a second name — used by benches that keep a legacy filename alongside
    the canonical ``BENCH_*`` one.  Returns the primary path.
    """
    payload = dict(payload)
    payload.setdefault("provenance", provenance())
    path = out_path(name)
    for p in (path,) + ((out_path(mirror),) if mirror else ()):
        with open(p, "w") as f:
            json.dump(payload, f, indent=2, default=float)
            f.write("\n")
    return path
