"""CBO serving engines: deadline-aware two-tier cascade over request streams.

Single-stream control loop (``CascadeServer``, paper §IV-D) per batch:
  1. fast tier classifies the batch (int8 "NPU" model) — instant answers;
  2. calibrated confidences go to the offload policy (``policy=`` registry
     name or instance — default ``"cbo"``, Algorithm 1) via a
     ``PolicyRunner`` that owns the bandwidth estimate; the plan returns
     (theta, resolution, capacity);
  3. the data plane escalates the K lowest-confidence frames;
  4. replies that would land after the frame's deadline are *dropped* and
     the fast-tier answer stands — the paper's fallback, which doubles as
     straggler mitigation (a slow/failed slow-tier node degrades accuracy,
     never correctness or latency);
  5. planned offloads are consumed from the controller backlog (they left
     the device) so they are never re-planned.

``MultiStreamServer`` generalizes this to N concurrent client streams
sharing an **edge fabric** (``repro/net``): streams are partitioned across
cells (one serial uplink each), and escalations are placed onto a pool of
slow-tier replicas.  The default fabric — built automatically from the
``uplink`` argument — is the degenerate 1-cell/1-replica topology, which
reproduces the legacy shared-uplink pipeline bit-for-bit.  Both planes
stay batched:

  * data plane — one fast-tier call over every stream's frames per round,
    one gathered slow-tier batch, one fabric transmit (a vectorized
    Lindley recursion per cell uplink and per replica queue);
  * control plane — a ``FleetRunner`` (``policy/fleet.py``) holds all
    per-stream policy state as struct-of-arrays (flat ragged backlogs,
    (S,) EWMA bandwidth vector) and plans every stream in one batched
    ``plan_many`` call per round.

The round loop therefore contains no per-stream or per-frame Python:
planning, bandwidth observation, backlog consume/extend and metrics all
run as (S,)-vector / segment operations.  Fleets are dynamic: an
``ArrivalSchedule.churn`` schedule admits and retires clients mid-run
(staggered joins, ragged stream lengths), and trailing partial batches are
processed rather than silently dropped.  With a lockstep schedule the
engine reproduces the looped implementation's metrics exactly
(``tests/data/multistream_snapshot.json``), and with n_streams=1 it
matches ``CascadeServer`` within tie-breaking noise (bench_multistream
checks this).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

import jax.numpy as jnp

from repro.core.cascade import cascade_classify, fast_pass, slow_pass_multires
from repro.core.netsim import Uplink, payload_sizes, png_size_model, transfer_seconds
from repro.net import EdgeFabric
from repro.obs.profile import phase
from repro.policy import BandwidthEstimator, FleetRunner, PolicyRunner, resolve_policies
from repro.serving.events import ArrivalSchedule, EscalationBatch, select_escalations
from repro.serving.metrics import AggregateMetrics, ServeMetrics
from repro.serving.scheduler import FairScheduler


@dataclass
class ServeConfig:
    deadline: float = 0.2  # T (paper: 200 ms)
    frame_rate: float = 30.0
    resolutions: tuple = (45, 90, 134, 179, 224)
    acc_server: tuple = ()  # measured offline (bench_resolution)
    batch_size: int = 16
    fast_time: float = 0.020  # Table III: fast tier per frame
    calib_time: float = 0.008  # Table III: calibration
    server_time: float = 0.037  # Table III: slow tier per frame
    size_of: Callable = png_size_model  # resolution (scalar or array) -> upload bytes
    use_fused: bool = False  # fused Pallas calibrate+gate kernel in the fast pass
    platt_ab: Optional[tuple] = None  # (a, b) Platt coefficients for use_fused
    # split-computation action table (policy.types.ActionTable, built via
    # repro.split.build_action_table): enlarges the planner grid with
    # features@cut actions.  None / a frames-only table keeps the paper's
    # frame-only action space — and its pinned snapshots — bit-for-bit.
    # Consumed by MultiStreamServer; CascadeServer (the single-stream paper
    # loop) stays frame-only by design.
    actions: Optional[object] = None


def _fast_pass(cfg: ServeConfig, fast_forward, calibrate, images):
    return fast_pass(fast_forward, calibrate, images,
                     use_fused=cfg.use_fused, platt_ab=cfg.platt_ab)


def _make_runner(policy, cfg: ServeConfig, uplink: Uplink, share: float = 1.0) -> PolicyRunner:
    """Wrap one decision policy (name or instance) for one stream."""
    return PolicyRunner(
        policy,
        resolutions=cfg.resolutions,
        acc_server=cfg.acc_server,
        deadline=cfg.deadline,
        latency=uplink.latency,
        server_time=cfg.server_time,
        size_of=cfg.size_of,
        bw=BandwidthEstimator(estimate_bps=uplink.bandwidth_bps * share),
    )


class CascadeServer:
    """Single-stream engine; ``policy`` is a registry name (``"cbo"``,
    ``"threshold"``, …) or an ``OffloadPolicy`` instance."""

    def __init__(self, cfg: ServeConfig, fast_forward: Callable, slow_forward: Callable,
                 calibrate: Callable, uplink: Uplink, policy="cbo"):
        self.cfg = cfg
        self.fast_forward = fast_forward
        self.slow_forward = slow_forward
        self.calibrate = calibrate
        self.uplink = uplink
        self.controller = _make_runner(resolve_policies(policy, 1)[0], cfg, uplink)
        self.metrics = ServeMetrics()

    def process_stream(self, frames: np.ndarray, labels: Optional[np.ndarray] = None) -> ServeMetrics:
        """Replay a frame stream at cfg.frame_rate through the cascade.

        Every frame is served: the trailing partial batch (when
        ``len(frames)`` is not a multiple of the batch size) runs as a
        smaller final round instead of being silently dropped.
        """
        cfg = self.cfg
        gamma = 1.0 / cfg.frame_rate
        B = cfg.batch_size
        t_fast = cfg.fast_time + cfg.calib_time
        n = len(frames)
        for start in range(0, n, B):
            b = min(B, n - start)
            batch = jnp.asarray(frames[start : start + b])
            arrivals = (start + np.arange(b)) * gamma
            t_done_fast = arrivals + t_fast

            # plan from current backlog + bandwidth estimate
            plan = self.controller.plan(now=float(arrivals[0]))
            capacity = max(len(plan.offloads), 1)
            theta = plan.theta if plan.offloads else 0.0
            res = cfg.resolutions[plan.resolution]

            out = cascade_classify(
                self.fast_forward, self.slow_forward, self.calibrate, batch,
                threshold=theta, capacity=capacity, resolution=res,
                use_fused=cfg.use_fused, platt_ab=cfg.platt_ab,
            )
            conf = np.asarray(out.conf)
            escalated = np.asarray(out.escalated)
            preds = np.asarray(out.preds)
            fast_preds = np.asarray(out.fast_preds)

            # simulate the shared uplink for the whole round at once;
            # late replies fall back to the fast answer
            esc = np.flatnonzero(escalated)
            payloads = np.full(len(esc), cfg.size_of(res))
            lands = self.uplink.transmit_batch(payloads, t_done_fast[esc])
            for k in range(len(esc)):
                self.controller.bw.observe(
                    payloads[k],
                    lands[k] - t_done_fast[esc[k]] - self.uplink.latency - self.uplink.server_time,
                )
            ok = lands <= arrivals[esc] + cfg.deadline
            final = fast_preds.copy()
            final[esc[ok]] = preds[esc[ok]]

            # backlog bookkeeping: planned offloads left the device — consume
            # them (the re-planning bug), and this batch's escalated frames
            # never enter the backlog at all
            self.controller.consume(i for i, _ in plan.offloads)
            for i in np.flatnonzero(~escalated):
                self.controller.add_frame(float(arrivals[i]), float(conf[i]))

            lat = np.full(b, t_fast)
            lat[esc] = np.where(ok, lands - arrivals[esc], cfg.deadline)
            n_correct = int((final == labels[start : start + b]).sum()) if labels is not None else 0
            self.metrics.update_batch(b, int(ok.sum()), int((~ok).sum()), n_correct, lat)
        return self.metrics


class MultiStreamServer:
    """N concurrent client streams sharing one uplink and one slow tier.

    Per round: one batched fast-tier call over all streams' frames, one
    batched ``plan_many`` over every stream's backlog (``FleetRunner``),
    one vectorized escalation gate, one fair uplink schedule, one batched
    slow-tier call over the cross-stream escalations, and vectorized
    deadline/metric accounting — no per-stream or per-frame Python.
    """

    def __init__(self, cfg: ServeConfig, fast_forward: Callable, slow_forward: Callable,
                 calibrate: Callable, uplink: Optional[Uplink], n_streams: int,
                 scheduler: Optional[FairScheduler] = None, stagger: bool = True,
                 policy="cbo", fabric: Optional[EdgeFabric] = None,
                 backend: str = "numpy", telemetry=None):
        if n_streams < 1:
            raise ValueError("n_streams must be >= 1")
        if backend not in ("numpy", "jax"):
            raise ValueError(f"backend must be 'numpy' or 'jax', got {backend!r}")
        self.backend = backend
        # optional per-round observer (the differential test harness): called
        # with one dict per round — identical keys on both backends
        self.round_hook = None
        self.cfg = cfg
        self.fast_forward = fast_forward
        self.slow_forward = slow_forward
        self.calibrate = calibrate
        # ``fabric`` is the network topology (cells x replicas, repro/net);
        # when omitted, the ``uplink`` argument becomes the degenerate
        # 1-cell/1-replica fabric — the legacy pipeline, bit-for-bit.
        # Passing both is ambiguous (the uplink would carry no traffic but
        # still feed the metrics), so it is rejected outright.
        if fabric is None:
            if uplink is None:
                raise ValueError("pass an uplink or an EdgeFabric")
            fabric = EdgeFabric.degenerate(uplink, n_streams)
        else:
            if uplink is not None:
                raise ValueError("pass either uplink or fabric, not both "
                                 "(the fabric's cells own all traffic)")
            if fabric.n_streams != n_streams:
                raise ValueError(f"fabric maps {fabric.n_streams} streams, "
                                 f"engine has {n_streams}")
        self.fabric = fabric
        self.uplink = fabric.cells[0].uplink
        self.n_streams = n_streams
        self.stagger = stagger
        self.scheduler = scheduler or FairScheduler("round_robin")
        # nominal per-stream uplink rate (each stream's own cell): the
        # scheduler's cost normalizer and the EWMA estimators' prior
        self._stream_bw = fabric.stream_bandwidth()
        # optimistic prior: every stream starts assuming the full link (as the
        # paper's single device does). A pessimistic 1/N prior can deadlock —
        # if B/N makes every offload look infeasible, no stream transmits, so
        # no stream ever *observes* bandwidth and the estimate never recovers.
        # Optimism self-corrects: early over-offloading shows up as queueing
        # in the observed transfer times and the EWMAs back off to the
        # contended share.
        # ``policy``: registry name (every stream gets a fresh instance) or a
        # per-stream factory ``stream_idx -> policy | name`` for
        # heterogeneous fleets.
        # plan against the network the fabric actually simulates: T^o is
        # the pool's nominal service time (== cfg.server_time whenever the
        # caller built the fabric from it), never a diverging copy
        self.fleet = FleetRunner(
            resolve_policies(policy, n_streams),
            resolutions=cfg.resolutions, acc_server=cfg.acc_server,
            deadline=cfg.deadline, latency=fabric.latency,
            server_time=fabric.server_time, size_of=cfg.size_of,
            bw_init=self._stream_bw, cell_id=fabric.cell_of,
            actions=cfg.actions,
        )
        self.metrics = AggregateMetrics.for_streams(n_streams, uplink=self.uplink,
                                                    fabric=fabric)
        # optional observability bundle (``repro.obs.Telemetry``): a per-round
        # time-series recorder, a frame-lifecycle tracer (numpy only) and a
        # phase profiler.  ``None`` (the default) is the zero-cost path —
        # every hook below is a ``x is not None`` check that fails fast.
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.bind(n_streams=n_streams, n_cells=fabric.n_cells,
                           n_replicas=fabric.n_replicas,
                           n_actions=self.fleet.action_table.n_actions)
            self.fleet.profiler = telemetry.profiler
        if backend == "jax":
            # fail fast on configurations the compiled path cannot express,
            # naming every unsupported feature (shared supports_jax check)
            from repro.serving.engine_jax import jax_unsupported

            reasons = jax_unsupported(self)
            if reasons:
                raise ValueError("backend='jax' cannot express this "
                                 "configuration: " + "; ".join(reasons))

    def process_streams(self, frames: np.ndarray,
                        labels: Optional[np.ndarray] = None,
                        schedule: Optional[ArrivalSchedule] = None) -> AggregateMetrics:
        """Replay S frame streams; ``frames`` is (S, N, H, W, C), ``labels``
        (S, N).  ``schedule`` defaults to the lockstep interleaved replay;
        pass an ``ArrivalSchedule.churn`` to stagger stream join/leave —
        ``frames[s, n]`` is then the frame stream s produces at global slot
        n, and only its valid slots are served."""
        cfg = self.cfg
        S = self.n_streams
        if frames.shape[0] != S:
            raise ValueError(f"expected {S} streams, got frames.shape[0]={frames.shape[0]}")
        B = cfg.batch_size
        t_fast = cfg.fast_time + cfg.calib_time
        resolutions = np.asarray(cfg.resolutions)
        if schedule is None:
            schedule = ArrivalSchedule.interleaved(S, frames.shape[1], cfg.frame_rate,
                                                  cfg.deadline, stagger=self.stagger)
        if schedule.n_streams != S or schedule.n_frames != frames.shape[1]:
            raise ValueError("schedule shape must match frames (S, N)")
        self.metrics.wall_time = schedule.horizon
        if self.backend == "jax":
            return self._process_streams_jax(frames, labels, schedule)

        # telemetry hooks: every guard below is a plain ``is not None`` (or
        # ``phase``, which is one) so the default (no telemetry) path opens
        # no span, touches no clock and no buffer
        tel = self.telemetry
        rec = tel.recorder if tel is not None else None
        tracer = tel.tracer if tel is not None else None
        prof = tel.profiler if tel is not None else None

        for start, arr, valid in schedule.rounds(B):
            b = arr.shape[1]
            active = valid.any(axis=1)  # (S,) streams with frames this round
            # retire state of streams outside their lifetime (left, or not
            # yet joined — the latter have nothing to clear)
            self.fleet.retire(~active)

            with phase(prof, "serve"):
                host = frames[:, start : start + b].reshape(S * b, *frames.shape[2:])
                flat = jnp.asarray(host)
                fp, cf = _fast_pass(cfg, self.fast_forward, self.calibrate, flat)
                fast_preds = np.asarray(fp).reshape(S, b)
                conf = np.asarray(cf).reshape(S, b)
            if prof is not None:
                prof.count("h2d_bytes", host.nbytes)
            t_ready = arr + t_fast  # (S, b); +inf on invalid slots

            # control plane: one batched plan over every active backlog,
            # against the slow tier's occupancy-calibrated service estimate
            # (identical to the nominal when the pool doesn't batch)
            now = np.min(arr, axis=1)  # first valid arrival (inf if none)
            pool = self.fabric.pool
            self.fleet.server_time = self.fabric.expected_server_time()
            self.fleet.occupancy = float(pool.avg_batch)
            fin = now[np.isfinite(now)]
            self.fleet.queue_depth = pool.queue_depth(
                float(fin.min()) if len(fin) else 0.0)
            batch = self.fleet.plan_all(now, active)
            theta = batch.theta
            cap = np.where(active, np.maximum(batch.n_offloads, 1), 0)
            res_idx = batch.resolution  # a° — ACTION index per stream

            # the shared action→bytes table (satellite of the split plane):
            # planner-assumed and engine-transmitted payloads come from ONE
            # array, indexed by the planned action.  For a frames-only
            # table these are exactly ``payload_sizes(size_of, resolutions)``
            # and every extra term below is + 0.0 / * 1.0 — bit-for-bit the
            # legacy pipeline.
            act = self.fleet.action_table
            act_res_px = resolutions[act.res]  # (A,) evaluation pixels

            # vectorized gate + gathered cross-stream escalation batch; a
            # split action's upload leaves the device only after the prefix
            # runs (t_dev), which also shifts its fair-schedule readiness
            conf_gate = np.where(valid, conf, np.inf)
            s_idx, slot_idx = select_escalations(conf_gate, theta, cap)
            a_esc = res_idx[s_idx]
            res_px = act_res_px[a_esc]
            esc = EscalationBatch(
                stream=s_idx, slot=slot_idx,
                t_ready=t_ready[s_idx, slot_idx] + act.t_dev[a_esc],
                payload=act.sizes[a_esc],
                res=res_px,
            )

            # one batched slow-tier call for every stream's escalations
            with phase(prof, "serve"):
                if len(esc):
                    gathered = jnp.take(flat, jnp.asarray(s_idx * b + slot_idx), axis=0)
                    slow_preds = np.asarray(slow_pass_multires(self.slow_forward, gathered,
                                                               esc.res))
                else:
                    slow_preds = np.zeros(0, dtype=fast_preds.dtype)
            if prof is not None:
                prof.count("slow_frames", len(esc))

            # fair uplink schedule (cost normalized by each stream's own
            # cell rate), then one fabric transmit for the round: per-cell
            # uplink queues + replica placement + pool service
            with phase(prof, "transmit"):
                order = self.scheduler.order(esc.stream, esc.t_ready,
                                             cost=esc.payload / self._stream_bw[esc.stream])
                q = esc.permuted(order)
                slow_q = slow_preds[order]
                # split suffixes cost a fraction of the full-model service
                # time (frames scale by exactly 1.0 — a float no-op)
                lands = self.fabric.transmit(q.stream, q.payload, q.t_ready,
                                             service_scale=act.srv_frac[res_idx[q.stream]],
                                             collect_detail=tracer is not None)
            ok = lands <= arr[q.stream, q.slot] + cfg.deadline

            if tracer is not None and len(q):
                d = self.fabric.last_detail
                tracer.record_round(
                    stream=q.stream, slot=q.slot,
                    arrival=arr[q.stream, q.slot], t_ready=q.t_ready,
                    cell=d["cell"], up_start=d["up_start"], up_end=d["up_end"],
                    replica=d["replica"], service=d["service"],
                    batch_id=d["batch_id"], done=d["done"],
                    land=lands, ok=ok, deadline=cfg.deadline)

            with phase(prof, "fold"):
                final = fast_preds.copy()
                final[q.stream[ok], q.slot[ok]] = slow_q[ok]

                # batched per-stream bandwidth observations (transmission order):
                # each reply's *actual* service time is subtracted (servers
                # report their processing time, so heterogeneous replicas do
                # not skew the estimate), but replica *queueing* is not — the
                # device cannot separate queueing from wire time, so slow-tier
                # contention surfaces to the EWMAs as reduced effective
                # bandwidth and the policies back off
                self.fleet.observe_bandwidth(
                    q.stream, q.payload,
                    transfer_seconds(lands, q.t_ready, latency=self.fabric.latency,
                                     server_time=self.fabric.last_service_time))

                # backlog bookkeeping, batched (same semantics as CascadeServer):
                # planned offloads left the device; non-escalated valid frames
                # join their stream's backlog in slot order
                self.fleet.consume(batch)
                esc_mask = np.zeros((S, b), dtype=bool)
                esc_mask[s_idx, slot_idx] = True
                add = valid & ~esc_mask
                add_s, _ = np.nonzero(add)
                self.fleet.observe_frames(add_s, arr[add], conf[add].astype(np.float64))

                # vectorized metrics: latency per frame, counts per stream
                lat = np.full((S, b), t_fast)
                lat[q.stream[ok], q.slot[ok]] = lands[ok] - arr[q.stream[ok], q.slot[ok]]
                lat[q.stream[~ok], q.slot[~ok]] = cfg.deadline
                off_counts = np.bincount(q.stream[ok], minlength=S)
                miss_counts = np.bincount(q.stream[~ok], minlength=S)
                correct = (((final == labels[:, start : start + b]) & valid).sum(axis=1)
                           if labels is not None else np.zeros(S, dtype=np.int64))
                self.metrics.update_round(valid.sum(axis=1), off_counts, miss_counts,
                                          correct, lat, valid)

            if rec is not None:
                # cumulative counters (the metrics SoA is exactly the jax
                # carry's semantics), planner state as used THIS round, and
                # the contention cursors post-round
                t_round = float(fin.min()) if len(fin) else np.nan
                hist = np.zeros(rec.n_actions, dtype=np.int64)
                np.add.at(hist, res_idx, np.where(active, batch.n_offloads, 0))
                m, fab = self.metrics, self.fabric
                rec.record_round(
                    t=t_round,
                    frames=m._frames, offloads=m._offloaded,
                    misses=m._missed, correct=m._correct,
                    bw_est=self.fleet.bw_est,
                    bw_true=fab.true_bandwidth(t_round),
                    cell_busy_s=[c.uplink.busy_seconds for c in fab.cells],
                    cell_queued_s=[c.uplink.queued_seconds for c in fab.cells],
                    rep_busy_s=pool.busy_seconds,
                    rep_queued_s=pool.queued_seconds,
                    avg_batch=pool.avg_batch,
                    server_time=self.fleet.server_time,
                    action_off=hist,
                )

            if self.round_hook is not None:
                ok_grid = np.zeros((S, b), dtype=bool)
                ok_grid[q.stream[ok], q.slot[ok]] = True
                self.round_hook({
                    "start": start,
                    "theta": theta.copy(), "res_idx": res_idx.copy(),
                    "cap": cap.copy(), "n_off": batch.n_offloads.copy(),
                    "n_frames": batch.n_frames.copy(),
                    "off_stream": batch.off_stream.copy(),
                    "off_pos": batch.off_pos.copy(),
                    "off_res": batch.off_res.copy(),
                    "off_kind": batch.off_kind.copy(),
                    "off_cut": batch.off_cut.copy(),
                    "esc": esc_mask, "ok": ok_grid, "lat": lat.copy(),
                    "valid": valid.copy(), "correct": np.asarray(correct).copy(),
                    "bw_est": self.fleet.bw_est.copy(),
                    "lengths": self.fleet.state.lengths.copy(),
                })
        return self.metrics

    def _process_streams_jax(self, frames, labels, schedule) -> AggregateMetrics:
        """Compiled backend: precompute the neural tiers per round on the
        host, then advance the whole replay as one jitted ``lax.scan``
        (``serving/engine_jax.py``).  Decision/schedule semantics are pinned
        to the numpy path by ``tests/test_fleet_jax.py``.

        Under ``Telemetry(profile=True)`` every host step of the body runs
        inside a ``repro.*`` span (docs/observability.md): ``prepare``,
        ``precompute`` (per round ``upload``, ``tier_fast``, one
        ``tier_slow`` per rung, ``host_read`` around each device-to-host
        read, ``pad``), ``scan``, ``fold`` and ``record``; the counters
        ``slow_frames`` and ``h2d_bytes`` count the slow tier's forwards
        and the frame bytes sent to the device, ``plan_steps`` the backlog
        depths the compiled planners walked and ``plan_steps_padded`` the
        depths a walk over the whole pad would take (``L`` a round and
        planner)."""
        import jax.numpy as jnp

        from repro.serving import engine_jax as ej
        from repro.sharding.axes import host_shard, logical_axis_multiple

        cfg = self.cfg
        S, B = self.n_streams, cfg.batch_size
        resolutions = np.asarray(cfg.resolutions)
        m = len(resolutions)
        collect = "trace" if self.round_hook is not None else "metrics"
        tel = self.telemetry
        rec = tel.recorder if tel is not None else None
        prof = tel.profiler if tel is not None else None
        with phase(prof, "prepare"):
            # under a mesh, pad the stream axis to the device multiple so
            # the "streams" logical axis actually splits; the pad rows never
            # see a valid frame, so every output below is sliced back to [:S]
            mult = logical_axis_multiple("streams")
            S_pad = -(-S // mult) * mult
            spad = S_pad - S
            spec = ej.spec_from_server(self, collect=collect, pad_streams=S_pad,
                                       telemetry=rec is not None)
            params = ej.params_from_server(self, spec)

        # host precompute: confidences + per-resolution slow-tier
        # correctness for every (frame, res) — both tiers are deterministic
        # per frame, so this equals the numpy path's escalated-only batching
        rounds = []
        per_round = []
        with phase(prof, "precompute"):
            for start, arr, valid in schedule.rounds(B):
                b = arr.shape[1]
                with phase(prof, "upload"):
                    host = frames[:, start : start + b].reshape(S * b, *frames.shape[2:])
                    flat = jnp.asarray(host)
                with phase(prof, "tier_fast"):
                    fp, cf = _fast_pass(cfg, self.fast_forward, self.calibrate, flat)
                with phase(prof, "host_read"):
                    fast_preds = np.asarray(fp).reshape(S, b)
                    conf = np.asarray(cf).reshape(S, b)
                lab = labels[:, start : start + b] if labels is not None else None
                fast_ok = (fast_preds == lab) if lab is not None else np.zeros((S, b), bool)
                slow_ok = np.zeros((S, b, m), dtype=bool)
                if lab is not None:
                    for r in range(m):
                        with phase(prof, "tier_slow"):
                            sp = slow_pass_multires(self.slow_forward, flat,
                                                    np.full(S * b, resolutions[r]))
                        with phase(prof, "host_read"):
                            slow_ok[:, :, r] = np.asarray(sp).reshape(S, b) == lab
                if prof is not None:
                    prof.count("h2d_bytes", host.nbytes)
                    if lab is not None:
                        prof.count("slow_frames", S * b * m)
                with phase(prof, "pad"):
                    pad = B - b
                    if pad:
                        arr = np.pad(arr, ((0, 0), (0, pad)), constant_values=np.inf)
                        valid = np.pad(valid, ((0, 0), (0, pad)))
                        conf = np.pad(conf, ((0, 0), (0, pad)), constant_values=np.inf)
                        fast_ok = np.pad(fast_ok, ((0, 0), (0, pad)))
                        slow_ok = np.pad(slow_ok, ((0, 0), (0, pad), (0, 0)))
                    if spad:
                        arr = np.pad(arr, ((0, spad), (0, 0)), constant_values=np.inf)
                        valid = np.pad(valid, ((0, spad), (0, 0)))
                        conf = np.pad(conf, ((0, spad), (0, 0)), constant_values=np.inf)
                        fast_ok = np.pad(fast_ok, ((0, spad), (0, 0)))
                        slow_ok = np.pad(slow_ok, ((0, spad), (0, 0), (0, 0)))
                rounds.append((arr, valid, conf, fast_ok, slow_ok))
                per_round.append((start, b))
        if not rounds:
            return self.metrics
        # place the stacked (R, S, B[, m]) inputs pre-split over the mesh
        # (no-op off-mesh) so the scan reads local shards from round one
        with phase(prof, "scan"):
            inputs = ej.RoundInputs(*(
                host_shard(jnp.asarray(col), *((None, "streams", None, None)[:col.ndim]))
                for col in (np.stack(c) for c in zip(*rounds))))
            carry, ys = ej.simulate(spec, params, inputs)
            if prof is not None:
                import jax

                jax.block_until_ready(carry)
        if carry.fp_bad is not None and bool(carry.fp_bad):
            import warnings

            warnings.warn(
                "a time-varying uplink fixed point failed to settle inside "
                "the compiled scan; the numpy reference would have used its "
                "exact serial fallback — results may diverge", RuntimeWarning)

        # fold per-round counters/latencies into the same AggregateMetrics
        # (everything stream-indexed is sliced back to the real S rows)
        with phase(prof, "fold"):
            # host baselines of the cumulative second counters — the carry
            # accumulates deltas from zero, the recorder (and numpy) report
            # absolute values, so the pre-scan state is added back per round
            base_cb = np.asarray([c.uplink.busy_seconds for c in self.fabric.cells])
            base_cq = np.asarray([c.uplink.queued_seconds for c in self.fabric.cells])
            base_rb = self.fabric.pool.busy_seconds.copy()
            base_rq = self.fabric.pool.queued_seconds.copy()
            base_ctr = (self.metrics._frames.copy(), self.metrics._offloaded.copy(),
                        self.metrics._missed.copy(), self.metrics._correct.copy())
            off = np.asarray(ys.off_counts)[:, :S]
            miss = np.asarray(ys.miss_counts)[:, :S]
            corr = np.asarray(ys.correct)[:, :S]
            lat = np.asarray(ys.lat, dtype=np.float64)[:, :S]
            if prof is not None:
                prof.count("plan_steps", np.asarray(ys.plan_depth).sum())
                prof.count("plan_steps_padded", len(per_round) * spec.planner.L
                           * max(1, len(spec.groups)))
            for i, (start, b) in enumerate(per_round):
                valid_i = rounds[i][1][:S, :b]
                self.metrics.update_round(valid_i.sum(axis=1), off[i], miss[i],
                                          corr[i], lat[i][:, :b], valid_i)

            # fold device state back into the host objects so summaries,
            # contention counters and follow-on numpy rounds stay correct
            for c, cell in enumerate(self.fabric.cells):
                cell.uplink._busy_until = float(carry.cell_busy[c])
                cell.uplink.n_transfers += int(carry.cell_n[c])
                cell.uplink.busy_seconds += float(carry.cell_busy_s[c])
                cell.uplink.queued_seconds += float(carry.cell_queued_s[c])
            pool = self.fabric.pool
            pool.busy_until[:] = np.asarray(carry.rep_busy, dtype=np.float64)
            pool.n_jobs += np.asarray(carry.rep_n, dtype=np.int64)
            pool.busy_seconds += np.asarray(carry.rep_busy_s, dtype=np.float64)
            pool.queued_seconds += np.asarray(carry.rep_queued_s, dtype=np.float64)
            pool.avg_batch = float(carry.avg_batch)  # occupancy EWMA (1.0 = serial)
            self.fabric.placement._next = int(carry.rr_next)
            self.fleet.bw_est[:] = np.asarray(carry.bw_est, dtype=np.float64)[:S]
            from repro.policy.fleet_jax import unpad_fleet

            fleet_c = carry.fleet
            if spad:  # drop the inert pad rows (always empty backlogs)
                fleet_c = type(fleet_c)(fleet_c.arrival[:S], fleet_c.conf[:S],
                                        fleet_c.length[:S])
            arr_f, conf_f, lens = unpad_fleet(fleet_c)
            st = self.fleet.state
            st.arrival = arr_f.astype(np.float64)
            st.conf = conf_f.astype(np.float64)
            st.stream_id = np.repeat(np.arange(S), lens)
            st._rebuild_offsets()

        if rec is not None:
            # replay the scan's stacked telemetry columns into the recorder.
            # Cumulative counters come from host cumsums of the per-round
            # integer columns (bit-exact — same int arithmetic as numpy's
            # running SoA); t and bw_true are recomputed host-side from the
            # same float64 arrival grid, so they are bit-equal by
            # construction; the rest compares at the tolerance policy.
            with phase(prof, "record"):
                frames_c = base_ctr[0] + np.cumsum(
                    [r[1][:S].sum(axis=1) for r in rounds], axis=0)
                off_c = base_ctr[1] + np.cumsum(off, axis=0, dtype=np.int64)
                miss_c = base_ctr[2] + np.cumsum(miss, axis=0, dtype=np.int64)
                corr_c = base_ctr[3] + np.cumsum(corr, axis=0, dtype=np.int64)
                bw_ts = np.asarray(ys.ts_bw_est, dtype=np.float64)[:, :S]
                hist_ts = np.asarray(ys.ts_off_hist, dtype=np.int64)
                cb = base_cb + np.asarray(ys.ts_cell_busy_s, dtype=np.float64)
                cq = base_cq + np.asarray(ys.ts_cell_queued_s, dtype=np.float64)
                rb = base_rb + np.asarray(ys.ts_rep_busy_s, dtype=np.float64)
                rq = base_rq + np.asarray(ys.ts_rep_queued_s, dtype=np.float64)
                ab = np.asarray(ys.ts_avg_batch, dtype=np.float64)
                st_ts = np.asarray(ys.ts_st_est, dtype=np.float64)
                for i in range(len(per_round)):
                    arr_i = rounds[i][0][:S]
                    fin = arr_i[np.isfinite(arr_i)]
                    t_round = float(fin.min()) if len(fin) else np.nan
                    rec.record_round(
                        t=t_round, frames=frames_c[i], offloads=off_c[i],
                        misses=miss_c[i], correct=corr_c[i], bw_est=bw_ts[i],
                        bw_true=self.fabric.true_bandwidth(t_round),
                        cell_busy_s=cb[i], cell_queued_s=cq[i],
                        rep_busy_s=rb[i], rep_queued_s=rq[i],
                        avg_batch=ab[i], server_time=st_ts[i],
                        action_off=hist_ts[i])

        if self.round_hook is not None:
            act = self.fleet.action_table
            for i, (start, b) in enumerate(per_round):
                dec = np.asarray(ys.dec[i])[:S]
                off_s, off_p = np.nonzero(dec >= 0)
                self.round_hook({
                    "start": start,
                    "theta": np.asarray(ys.theta[i], dtype=np.float64)[:S],
                    "res_idx": np.asarray(ys.res_idx[i], dtype=np.int64)[:S],
                    "cap": np.asarray(ys.cap[i], dtype=np.int64)[:S],
                    "n_off": np.asarray(ys.n_off[i], dtype=np.int64)[:S],
                    "n_frames": np.asarray(ys.n_frames[i], dtype=np.int64)[:S],
                    "off_stream": off_s.astype(np.int64),
                    "off_pos": off_p.astype(np.int64),
                    "off_res": dec[off_s, off_p].astype(np.int64),
                    # derived host-side from the shared table: the scan's
                    # decision grid already carries the ACTION index
                    "off_kind": act.kind[dec[off_s, off_p]].astype(np.int8),
                    "off_cut": act.cut[dec[off_s, off_p]].astype(np.int64),
                    "esc": np.asarray(ys.esc[i])[:S, :b],
                    "ok": np.asarray(ys.ok[i])[:S, :b],
                    "lat": lat[i][:, :b],
                    "valid": rounds[i][1][:S, :b],
                    "correct": corr[i].astype(np.int64),
                    "bw_est": np.asarray(ys.bw_est[i], dtype=np.float64)[:S],
                    "lengths": np.asarray(ys.lengths[i], dtype=np.int64)[:S],
                    "overflow": np.asarray(ys.overflow[i])[:S],
                    "inexact": np.asarray(ys.inexact[i])[:S],
                })
        return self.metrics

