"""Bring-up smoke of the CBO serving path on a TPU: one process, one chip.

  python chip_smoke.py              # device, serve and fleet phases on one chip
  python chip_smoke.py --chips 4    # the serve phase sharded over four chips,
                                    # against the same workload on one chip

Phases:

  * device — platform, device kind, count and JAX version;
  * serve  — ``MultiStreamServer`` over S camera streams at the paper's
    resolution ladder.  The slow tier is ResNet-50 at full published width
    (224², 1000 classes) with seeded random weights, the fast tier the same
    network through ``qdq_tree(**NPU_QUANT)``, both under ``jax.jit`` and
    each with its head bias centred on a seeded split; the
    fast pass runs the fused calibrate+gate Pallas kernel.  The confidence
    is Platt-calibrated on a seeded split against agreement with the slow
    tier, and the slow tier's full-resolution answer is the reference label
    (random weights have no ground truth).  The same workload runs through
    ``backend="numpy"`` and ``backend="jax"``; their frame, offload and
    deadline-miss counts must be equal;
  * fleet  — the compiled round loop alone at S=65536 streams x 16 rounds
    on synthetic ``RoundInputs``, after the exact jax == numpy decision gate
    at small S.

Any failed phase or check, or a fixed point that fails to settle inside the
compiled scan, exits non-zero.  On success the last line of standard output
is one JSON object naming the device.  Off a TPU the script exits non-zero
before doing any work.  Compiles go to JAX's persistent cache
(``repro.launch.compile_cache``).  Printed times are bring-up facts, not
benchmark metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

PAPER_RESOLUTIONS = (45, 90, 134, 179, 224)  # §V upload ladder
COUNT_KEYS = ("n_frames", "n_offloaded", "n_deadline_miss")
# fused kernel vs jnp confidence: a few float32 ulps of a value in [0, 1],
# with room for the TPU's exp differing from the CPU's
FUSED_CONF_ATOL = 1e-5


class CompileClock:
    """Seconds JAX spends in backend compiles (a persistent-cache hit is
    timed as its retrieval) and the number of persistent-cache hits, read
    from ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on_duration)
        self._jax.monitoring.unregister_event_listener(self._on_event)

    def read(self):
        return self.seconds, self.cache_hits


# --------------------------------------------------------------------------- #
# serve phase
# --------------------------------------------------------------------------- #


def build_workload(model_cfg, *, n_streams=8, n_frames=32, batch_size=8,
                   resolutions=PAPER_RESOLUTIONS, bandwidth_mbps=5.0, seed=0):
    """Seeded tiers, calibration and frames for one serve run.

    The forwards are jitted with the parameters as arguments; calibration
    and reference labels run in chunks of ``n_streams * batch_size``
    frames, the fast pass's served shape, so they reuse its executable."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import NPU_QUANT
    from repro.core.calibration import PlattCalibrator
    from repro.core.cascade import fast_pass, slow_pass_multires
    from repro.core.confidence import max_softmax
    from repro.data.video import VideoDataConfig, make_dataset
    from repro.models import api
    from repro.quant.quantize import qdq_tree
    from repro.serving import ServeConfig

    h = api.build(model_cfg)
    fwd = jax.jit(h.forward)
    data_cfg = VideoDataConfig(n_classes=model_cfg.n_classes,
                               img_res=model_cfg.img_res,
                               frames_per_video=n_frames)
    chunk = n_streams * batch_size

    def in_chunks(fn, frames):
        return np.concatenate([np.asarray(fn(jnp.asarray(frames[i:i + chunk])))
                               for i in range(0, len(frames), chunk)])

    # A random deep ReLU network's logits share one input-independent
    # component, so every frame gets the same class and the int4 tier's
    # class never matches the float tier's.  Each tier's head bias is set
    # to minus its mean logit over a seeded split (a data-dependent init),
    # which leaves the frame-dependent part to pick the class.
    centring = make_dataset(data_cfg, n_streams, seed=seed)["frames"]

    def centred(params):
        mean = in_chunks(lambda x: fwd(params, x), centring).mean(0)
        return {**params, "head": {**params["head"], "b": params["head"]["b"] - mean}}

    raw = h.init(jax.random.PRNGKey(seed), dtype=jnp.float32)
    slow_params = centred(raw)
    fast_params = centred(qdq_tree(raw, **NPU_QUANT))
    fast = lambda x: fwd(fast_params, x)

    def slow(x):
        # escalation batches vary in size from round to round; padding them
        # to one chunk keeps the slow tier at one compiled shape
        n = x.shape[0]
        return fwd(slow_params, jnp.pad(x, ((0, chunk - n),) + ((0, 0),) * (x.ndim - 1)))[:n]

    # calibration split: fast-tier max-softmax against agreement with the
    # slow tier; server accuracy per resolution against its own 224² answer
    calib = make_dataset(data_cfg, n_streams, seed=seed + 1)["frames"]
    fast_logits = in_chunks(fast, calib)
    slow_ref = in_chunks(lambda x: jnp.argmax(slow(x), -1), calib)
    agree = (fast_logits.argmax(-1) == slow_ref).astype(np.float64)
    platt = PlattCalibrator.fit(np.asarray(max_softmax(jnp.asarray(fast_logits))), agree)
    if not (np.isfinite(fast_logits).all() and np.isfinite([platt.a, platt.b]).all()):
        raise AssertionError(f"non-finite fast-tier logits or Platt fit {platt}")
    # the fused calibrate+gate kernel the served fast pass runs, against
    # the plain jnp softmax -> Platt path on the same logits
    x = jnp.asarray(calib[:chunk])
    _, conf_kernel = fast_pass(fast, None, x, use_fused=True, platt_ab=(platt.a, platt.b))
    _, conf_ref = fast_pass(fast, platt, x)
    kernel_err = float(np.max(np.abs(np.asarray(conf_kernel) - np.asarray(conf_ref))))
    if not kernel_err <= FUSED_CONF_ATOL:
        raise AssertionError(f"fused calib_gate confidence off by {kernel_err}")
    acc_server = tuple(float(np.mean(in_chunks(
        lambda x, r=r: slow_pass_multires(slow, x, np.full(len(x), r)), calib)
        == slow_ref)) for r in resolutions)

    served = make_dataset(data_cfg, n_streams, seed=seed + 2)["frames"]
    labels = in_chunks(lambda x: jnp.argmax(slow(x), -1), served)
    shape = (n_streams, n_frames)
    cfg = ServeConfig(resolutions=tuple(resolutions), acc_server=acc_server,
                      deadline=0.2, frame_rate=32.0, batch_size=batch_size,
                      use_fused=True, platt_ab=(platt.a, platt.b))
    return dict(
        cfg=cfg, platt=platt, bandwidth_mbps=bandwidth_mbps,
        fast=fast, slow=slow,
        frames=served.reshape(shape + served.shape[1:]),
        labels=labels.reshape(shape), agree=float(agree.mean()),
        kernel_err=kernel_err)


@contextlib.contextmanager
def engine_arg_placements(record: dict):
    """Record, per ``engine_jax.simulate`` argument leaf, how many devices
    its array spans and whether it is committed to them."""
    from repro.serving import engine_jax as ej

    orig = ej.simulate

    def simulate(spec, params, inputs, carry=None):
        for group, tup in (("params", params), ("inputs", inputs)):
            for name, x in tup._asdict().items():
                if x is not None:
                    record[f"{group}.{name}"] = (len(x.sharding.device_set),
                                                 bool(x.committed))
        return orig(spec, params, inputs, carry)

    ej.simulate = simulate
    try:
        yield record
    finally:
        ej.simulate = orig


def serve(workload, backend, mesh=None):
    """One ``MultiStreamServer`` run of the workload; under ``mesh`` the
    streams axis is sharded over it.  Returns the aggregate metrics."""
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.serving import MultiStreamServer
    from repro.sharding.axes import sharding_ctx

    cfg = workload["cfg"]
    S = workload["frames"].shape[0]
    fabric = EdgeFabric.degenerate(
        Uplink(bandwidth_bps=mbps(workload["bandwidth_mbps"]), latency=0.05,
               server_time=cfg.server_time), n_streams=S)
    srv = MultiStreamServer(cfg, workload["fast"], workload["slow"],
                            workload["platt"], None, n_streams=S, fabric=fabric,
                            backend=backend)
    with sharding_ctx(mesh):
        return srv.process_streams(workload["frames"], workload["labels"])


def counts(metrics) -> dict:
    return {k: int(getattr(metrics, k)) for k in COUNT_KEYS}


def check_counts(ref: dict, got: dict, what: str) -> None:
    if ref != got:
        raise AssertionError(f"{what}: counts differ {ref} != {got}")


def check_served(c: dict, workload) -> None:
    """Every frame was served and some were escalated: a run that
    escalates nothing never exercises the slow tier or the uplink."""
    expected = int(np.prod(workload["frames"].shape[:2]))
    if c["n_frames"] != expected:
        raise AssertionError(f"served {c['n_frames']} of {expected} frames")
    if c["n_offloaded"] + c["n_deadline_miss"] == 0:
        raise AssertionError("no frame was escalated")


def timed(clock, fn, *args, **kw):
    c0, h0 = clock.read()
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    c1, h1 = clock.read()
    return out, {"wall_s": wall, "compile_s": c1 - c0, "cache_hits": h1 - h0}


def serve_phase(model_cfg, clock, **workload_kw) -> dict:
    """numpy vs jax backend on one device; the jax run is repeated so its
    second, warm run gives the steady wall time."""
    wl, t_build = timed(clock, build_workload, model_cfg, **workload_kw)
    runs = {}
    for name, backend in (("jax", "jax"), ("numpy", "numpy"), ("jax_warm", "jax")):
        m, t = timed(clock, serve, wl, backend)
        runs[name] = dict(counts(m), accuracy=float(m.accuracy), **t)
    ref = {k: runs["numpy"][k] for k in COUNT_KEYS}
    for name in ("jax", "jax_warm"):
        check_counts(ref, {k: runs[name][k] for k in COUNT_KEYS}, f"numpy vs {name}")
    check_served(ref, wl)
    return {"model": model_cfg.name, "img_res": model_cfg.img_res,
            "streams": wl["frames"].shape[0], "platt": (wl["platt"].a, wl["platt"].b),
            "fast_slow_agreement": wl["agree"], "acc_server": wl["cfg"].acc_server,
            "fused_conf_max_err": wl["kernel_err"],
            "build": t_build, **runs}


def sharded_serve_phase(model_cfg, clock, n_devices, **workload_kw) -> dict:
    """The jax backend with the streams axis over an ``n_devices`` mesh,
    against the same workload on one device."""
    from repro.launch.mesh import make_streams_mesh

    wl = build_workload(model_cfg, **workload_kw)
    one, t_one = timed(clock, serve, wl, "jax")
    with engine_arg_placements({}) as placed:
        meshed, t_mesh = timed(clock, serve, wl, "jax", make_streams_mesh(n_devices))
    check_counts(counts(one), counts(meshed), f"1 device vs {n_devices}-device mesh")
    check_served(counts(one), wl)
    spread = {k: v for k, v in placed.items() if k.startswith("inputs.")}
    if not spread or any(n != n_devices for n, _ in spread.values()):
        raise AssertionError(f"round inputs not spread over {n_devices} devices: {spread}")
    return {"one_device": dict(counts(one), **t_one),
            "mesh": dict(counts(meshed), **t_mesh), "placements": placed}


# --------------------------------------------------------------------------- #
# fleet phase
# --------------------------------------------------------------------------- #


def fleet_phase(clock, n_streams=65536, n_rounds=16, parity_streams=4, seed=0) -> dict:
    """Exact jax == numpy decision gate at small S, then the compiled round
    loop at ``n_streams`` (B=8, ``collect="metrics"``)."""
    from benchmarks.bench_fleet_control import bench_jax_one, check_jax_parity

    gate = check_jax_parity(S=parity_streams, seed=seed)
    row, t = timed(clock, bench_jax_one, n_streams, n_rounds, seed, batch=8,
                   collect="metrics")
    if row["rounds_emitted"] != n_rounds:
        raise AssertionError(f"scan emitted {row['rounds_emitted']} of {n_rounds} rounds")
    return {"parity": gate, "streams": row["n_streams"], "rounds": n_rounds,
            "batch": row["batch"], "aot_compile_s": row["compile_s"],
            "steady_s": row["steady_s"], **t}


# --------------------------------------------------------------------------- #
# entry point
# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the serve phase sharded over four chips "
                         "against one chip")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} device(s)",
              file=sys.stderr)
        return 1

    from repro.configs.resnet_50 import FULL
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # a fixed point that fails to settle in the compiled scan is a failure
    warnings.filterwarnings("error", category=RuntimeWarning,
                            message="a time-varying uplink fixed point")
    clock = CompileClock()
    if args.chips == 1:
        phases = (("serve", lambda: serve_phase(FULL, clock)),
                  ("fleet", lambda: fleet_phase(clock)))
    else:
        phases = (("sharded_serve", lambda: sharded_serve_phase(FULL, clock, args.chips)),)
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            out = run()
        except Exception as e:  # report which phase failed, then fail
            import traceback

            traceback.print_exc()
            print(f"{name}: FAILED after {time.perf_counter() - t0:.1f} s: {e!r}",
                  file=sys.stderr)
            return 1
        print(f"{name}: {json.dumps(out, default=float)}", flush=True)
    clock.close()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
