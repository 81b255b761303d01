"""JAX serving engine: the whole fleet round as one jitted ``lax.scan`` step.

``MultiStreamServer.process_streams`` runs plan -> transmit -> observe ->
consume per round in host numpy (``serving/engine.py``).  This module is
the same round, re-expressed in fixed shapes so ``jax.jit`` compiles it
once and ``lax.scan`` advances it across rounds with zero host round
trips.  The numpy engine stays the semantic reference: every ordering
rule (escalation gate, SFQ tags, per-cell Lindley, placement, per-replica
Lindley, EWMA fold) is reproduced with the same tie-breaks, and the
differential tests (``tests/test_fleet_jax.py``) pin the two paths round
by round.

Shape/masking scheme (docs/jax_backend.md):

  * rounds are padded to the batch size B — trailing partial rounds get
    ``valid=False`` slots with ``arrival=+inf`` (never gate, never count);
  * backlogs are a ``PaddedFleet`` of pad L == ``max_backlog``;
  * one round's escalations live in the flat (S*B,) row space
    (``flat = s*B + slot``); masked rows ride through every recursion as
    no-ops — tx=0 / submit=-inf rows provably cannot perturb the running
    max a Lindley recursion takes over live rows;
  * the neural tiers run OUTSIDE the scan: confidences and per-resolution
    slow-tier correctness are precomputed per round (deterministic per
    frame, so identical to the numpy path's escalated-only batching) and
    fed to the scan as (R, S, B[, m]) inputs.

Stream-axis sharding: the carry's (S,)/(S, L)/(S, B) arrays are
constrained to the ``"streams"`` logical axis (``sharding/axes.py``), so
under a mesh the fleet splits across devices; off-mesh the constraint is
a no-op and the engine runs identically on one CPU.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.netsim import _FIXED_POINT_SWEEPS
from repro.policy.fleet_jax import (PaddedFleet, PlannerSpec, PlanOut,
                                    clear_fleet, consume_fleet, ewma_fold,
                                    extend_fleet, plan_fleet, prune_fleet)
from repro.sharding.axes import current_mesh, shard

__all__ = ["EngineSpec", "EngineGroup", "EngineParams", "RoundInputs",
           "EngineCarry", "RoundTrace", "init_carry", "make_engine",
           "simulate", "trace_lookup", "jax_unsupported", "supports_jax",
           "spec_from_server", "params_from_server"]

_NEG = -jnp.inf


# --------------------------------------------------------------------------- #
# static spec + pytrees
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class EngineGroup:
    """One policy group of a heterogeneous fleet (static).

    Mirrors one ``FleetRunner.groups`` entry: the group's planner (padded
    to the fleet-wide backlog width via ``spec_for_policy(pad_L=...)``),
    the global stream indices it owns, and the per-policy consume/prune
    semantics the engine otherwise reads from spec-level flags.
    """

    planner: PlannerSpec
    streams: tuple  # global stream indices (FleetRunner group order)
    prune: bool = True  # BacklogPolicy.prune_expired
    oneshot: bool = False  # OneShotPolicy consume semantics
    mb: int = 0  # the group's own max_backlog (<= planner.L)


@dataclass(frozen=True)
class EngineSpec:
    """Everything the compiled round step specializes on."""

    n_streams: int
    batch: int  # B — round batch size (rounds are padded to it)
    n_cells: int
    n_replicas: int
    planner: PlannerSpec
    placement: str = "round_robin"  # round_robin | jsq | least_land
    serial_replicas: bool = False
    scheduler: str = "round_robin"  # round_robin | fifo
    prune: bool = True  # BacklogPolicy.prune_expired
    oneshot: bool = False  # OneShotPolicy consume semantics
    t_fast: float = 0.028  # fast_time + calib_time
    bw_alpha: float = 0.3
    collect: str = "metrics"  # none | metrics | trace
    # continuous-batching slow tier (repro.slowtier); "none" = per-request
    # service exactly as before.  coeffs: flat=(st,); linear=(base, per_item);
    # step=(base, per_page, page_size)
    batch_kind: str = "none"  # none | flat | linear | step
    batch_coeffs: tuple = ()
    batch_window: float = 0.0  # admission window (s)
    batch_cap: int = 0  # occupancy cap per batch; 0 = unbounded
    batch_beta: float = 0.25  # occupancy EWMA fold
    # heterogeneous fleets: one EngineGroup per policy group; () keeps the
    # homogeneous single-planner graph (and spec-level prune/oneshot) as-is
    groups: tuple = ()
    # time-varying uplinks: in-scan BandwidthTrace replay and/or counter-
    # mode jitter.  False keeps the constant-rate Lindley graph untouched.
    varying: bool = False
    cell_jitter: tuple = ()  # (C,) per-cell jitter amplitude (0.0 = none)
    cell_seed: tuple = ()  # (C,) per-cell jitter seeds
    cell_trace: tuple = ()  # (C,) bool — cell replays a BandwidthTrace
    cell_loop: tuple = ()  # (C,) bool — trace wraps at trace_dur
    # split-computation action table (full A-length static vectors, frames
    # first; () = frames-only, which keeps the legacy compiled graph — and
    # the snapshot goldens pinned to it — untouched).  ``params.sizes`` is
    # (A,) either way; frame actions occupy [0, m) so frame-only decision
    # grids index it identically.
    act_t_dev: tuple = ()  # (A,) device prefix seconds per action
    act_srv_frac: tuple = ()  # (A,) fraction of replica service per action
    act_res: tuple = ()  # (A,) evaluation resolution index per action
    # telemetry: emit the FleetRecorder's per-round series as extra stacked
    # ``ys`` (obs/timeseries.py).  False keeps the RoundTrace pytree — and
    # therefore the compiled graph the snapshot goldens pin — unchanged
    # (the ts_* fields stay None and vanish as pytree leaves).
    telemetry: bool = False

    @property
    def has_splits(self) -> bool:
        return bool(self.act_t_dev)

    @property
    def m(self) -> int:
        return self.planner.m

    @property
    def deadline(self) -> float:
        return self.planner.deadline

    @property
    def latency(self) -> float:
        return self.planner.latency


class EngineParams(NamedTuple):
    """Per-run device arrays the step closes over (not traced per round).

    The trailing trace grids are ``None`` unless some cell replays a
    ``BandwidthTrace`` (``spec.cell_trace``); ``None`` leaves vanish from
    the pytree, so constant-rate runs keep the original structure.
    """

    sizes: jnp.ndarray  # (A,) payload bytes per action (== (m,) frames-only)
    cell_bw: jnp.ndarray  # (C,) base bytes/s (trace cells: nominal base)
    cell_of: jnp.ndarray  # (S,) int32
    replica_st: jnp.ndarray  # (K,) per-replica service time
    stream_bw: jnp.ndarray  # (S,) nominal cell rate (scheduler normalizer)
    weights: jnp.ndarray  # (S,) scheduler weights (ones = unweighted)
    bw_init: jnp.ndarray  # (S,) EWMA prior
    trace_t: Optional[jnp.ndarray] = None  # (C, T) breakpoints, +inf-padded
    trace_bps: Optional[jnp.ndarray] = None  # (C, T) rates, last-repeated
    trace_dur: Optional[jnp.ndarray] = None  # (C,) loop periods


class RoundInputs(NamedTuple):
    """One round of precomputed data-plane inputs (stack to (R, ...) for scan)."""

    arr: jnp.ndarray  # (S, B) arrival seconds; +inf on invalid slots
    valid: jnp.ndarray  # (S, B) bool
    conf: jnp.ndarray  # (S, B) calibrated confidence (fast pass)
    fast_ok: jnp.ndarray  # (S, B) bool — fast prediction correct
    slow_ok: jnp.ndarray  # (S, B, m) bool — slow prediction correct per res


class EngineCarry(NamedTuple):
    fleet: PaddedFleet
    bw_est: jnp.ndarray  # (S,)
    cell_busy: jnp.ndarray  # (C,) uplink busy-until cursors
    cell_n: jnp.ndarray  # (C,) int32 transfer counts
    cell_busy_s: jnp.ndarray  # (C,)
    cell_queued_s: jnp.ndarray  # (C,)
    rep_busy: jnp.ndarray  # (K,)
    rep_n: jnp.ndarray  # (K,) int32
    rep_busy_s: jnp.ndarray  # (K,)
    rep_queued_s: jnp.ndarray  # (K,)
    rr_next: jnp.ndarray  # () int32 round-robin placement cursor
    frames: jnp.ndarray  # (S,) int32
    offloaded: jnp.ndarray  # (S,) int32
    missed: jnp.ndarray  # (S,) int32
    correct: jnp.ndarray  # (S,) int32
    avg_batch: jnp.ndarray  # () slow-tier occupancy EWMA (1.0 = serial)
    # time-varying uplinks only (None leaves vanish from the pytree):
    jit_key: Optional[jnp.ndarray] = None  # (C, 2) uint32 per-cell PRNG keys
    fp_bad: Optional[jnp.ndarray] = None  # () bool — a fixed point never settled


class RoundTrace(NamedTuple):
    """Per-round outputs (``collect`` >= "metrics"; trace adds decisions)."""

    off_counts: jnp.ndarray  # (S,) int32
    miss_counts: jnp.ndarray  # (S,) int32
    correct: jnp.ndarray  # (S,) int32
    lat: jnp.ndarray  # (S, B)
    # -- collect == "trace" extras (zero-size placeholders otherwise) ----- #
    theta: jnp.ndarray
    res_idx: jnp.ndarray
    cap: jnp.ndarray
    n_off: jnp.ndarray
    n_frames: jnp.ndarray  # post-prune backlog lengths at plan time
    dec: jnp.ndarray  # (S, L) int8
    esc: jnp.ndarray  # (S, B) bool
    ok: jnp.ndarray  # (S, B) bool
    bw_est: jnp.ndarray  # (S,) after the round's EWMA fold
    lengths: jnp.ndarray  # (S,) backlog lengths after extend
    overflow: jnp.ndarray  # (S,) bool
    inexact: jnp.ndarray  # (S,) bool
    # -- spec.telemetry extras (None leaves vanish from the pytree) ------- #
    ts_bw_est: Optional[jnp.ndarray] = None  # (S,) post-fold EWMA
    ts_off_hist: Optional[jnp.ndarray] = None  # (A,) int32 planned offloads
    ts_cell_busy_s: Optional[jnp.ndarray] = None  # (C,) carry-relative
    ts_cell_queued_s: Optional[jnp.ndarray] = None  # (C,)
    ts_rep_busy_s: Optional[jnp.ndarray] = None  # (K,)
    ts_rep_queued_s: Optional[jnp.ndarray] = None  # (K,)
    ts_avg_batch: Optional[jnp.ndarray] = None  # () post-round EWMA
    ts_st_est: Optional[jnp.ndarray] = None  # () planner's T^o this round


def init_carry(spec: EngineSpec, params: EngineParams) -> EngineCarry:
    S, C, K, L = spec.n_streams, spec.n_cells, spec.n_replicas, spec.planner.L
    dt = spec.planner.dtype
    z = lambda *s: jnp.zeros(s, dtype=dt)
    zi = lambda *s: jnp.zeros(s, dtype=jnp.int32)
    fleet = PaddedFleet(z(S, L), z(S, L), zi(S))
    # copy=True: same-dtype astype would alias params.bw_init's buffer, and
    # the engine donates its carry (make_engine) — an aliased buffer would
    # be deleted out from under params on the first step
    extra = {}
    if spec.varying:
        extra["fp_bad"] = jnp.zeros((), bool)
        if any(j > 0 for j in spec.cell_jitter):
            extra["jit_key"] = jnp.stack(
                [jax.random.PRNGKey(int(s)) for s in spec.cell_seed])
    return EngineCarry(
        fleet=fleet, bw_est=jnp.array(params.bw_init, dtype=dt, copy=True),
        cell_busy=z(C), cell_n=zi(C), cell_busy_s=z(C), cell_queued_s=z(C),
        rep_busy=z(K), rep_n=zi(K), rep_busy_s=z(K), rep_queued_s=z(K),
        rr_next=jnp.zeros((), jnp.int32),
        frames=zi(S), offloaded=zi(S), missed=zi(S), correct=zi(S),
        avg_batch=jnp.ones((), dtype=dt), **extra)


# --------------------------------------------------------------------------- #
# masked recursions
# --------------------------------------------------------------------------- #


def _masked_lindley(sub, tx, mask, busy0):
    """end_i = max(sub_i, end_{i-1}) + tx_i over the masked rows, with
    masked rows as exact no-ops: tx=0 / sub=-inf rows contribute the
    candidate ``busy0 - excl <= busy0``, which the first live row's
    ``max(sub, busy0) - 0 >= busy0`` already dominates, so the running max
    over live rows is untouched.  Returns (end, new_busy, wire, queued)."""
    txm = jnp.where(mask, tx, 0.0)
    subm = jnp.where(mask, sub, _NEG)
    csum = jnp.cumsum(txm)
    eff = jnp.maximum(subm, busy0) - (csum - txm)
    end = jax.lax.cummax(eff) + csum
    any_live = mask.any()
    new_busy = jnp.where(any_live, jnp.where(mask, end, _NEG).max(), busy0)
    wire = txm.sum()
    queued = jnp.where(mask, jnp.clip(end - txm - subm, 0.0, None), 0.0).sum()
    return end, new_busy, wire, queued


def _lexsort2(primary, rows_sorted_by_secondary):
    """Stable argsort by ``primary`` applied on top of an existing stable
    secondary order — the composed-argsort form of ``np.lexsort``."""
    o = rows_sorted_by_secondary
    return o[jnp.argsort(primary[o])]


def trace_lookup(t_grid, bps_grid, ts):
    """Rate in effect at each time over one padded breakpoint grid — the
    jnp mirror of ``BandwidthTrace.bandwidth_at``'s right-``searchsorted``
    minus one.  Callers mod looping times by the period first; the +inf
    pad breakpoints (``BandwidthTrace.grid``) never capture a finite time."""
    idx = jnp.searchsorted(t_grid, ts, side="right") - 1
    return bps_grid[jnp.clip(idx, 0, t_grid.shape[0] - 1)]


def _cell_bw_at(spec: EngineSpec, params: EngineParams, c: int, key_c, ts):
    """Instantaneous bandwidth of cell ``c`` at times ``ts`` — in-scan
    ``Uplink.bandwidth_at``: trace replay (looping times mod the period)
    times counter-mode jitter factors drawn at the raw integer second.
    The factors are float32 on both backends (``_counter_jitter_factors``),
    so host and device derive the same per-second channel bit-for-bit."""
    dt = spec.planner.dtype
    if spec.cell_trace[c]:
        tm = jnp.mod(ts, params.trace_dur[c]) if spec.cell_loop[c] else ts
        base = trace_lookup(params.trace_t[c], params.trace_bps[c], tm)
    else:
        base = jnp.full(ts.shape, params.cell_bw[c], dtype=dt)
    if spec.cell_jitter[c] > 0:
        secs = ts.astype(jnp.int32)
        keys = jax.vmap(lambda s: jax.random.fold_in(key_c, s))(secs)
        normals = jax.vmap(lambda k: jax.random.normal(k, dtype=jnp.float32))(keys)
        fac = jnp.clip(jnp.float32(1.0)
                       + jnp.float32(spec.cell_jitter[c]) * normals,
                       jnp.float32(0.2), jnp.float32(2.0))
        base = base * fac.astype(dt)
    return base


def _masked_lindley_varying(spec: EngineSpec, params: EngineParams, c: int,
                            key_c, sub, mask, payload, busy0):
    """Time-varying masked Lindley: each row's rate depends on its start
    time, which depends on the previous row's end — a serial chain.
    Mirrors ``Uplink.upload_batch``'s fixed-point iteration under jit
    (``lax.while_loop``, same sweep cap): guess the starts, look every
    row's rate up in one pass, re-run the Lindley recursion, repeat until
    the starts stop moving.  The numpy path falls back to an exact serial
    loop if the iteration never settles; that has no fixed-shape analogue,
    so this raises the sticky ``fp_bad`` carry flag instead (the bridge
    warns, the differential tests assert it stays clean).  Returns
    ``(end, new_busy, wire, queued, fp_bad)``."""
    subm = jnp.where(mask, sub, _NEG)
    base = jnp.maximum(subm, busy0)  # eff numerator == the start guess

    def sweep(starts):
        ts = jnp.where(mask, starts, 0.0)  # guard masked +inf/-inf rows
        bw = _cell_bw_at(spec, params, c, key_c, ts)
        tx = jnp.where(mask, payload / bw, 0.0)
        csum = jnp.cumsum(tx)
        end = jax.lax.cummax(base - (csum - tx)) + csum
        return end, tx

    def settled(a, b):  # np.array_equal over the live rows
        return (jnp.where(mask, a, 0.0) == jnp.where(mask, b, 0.0)).all()

    end0, tx0 = sweep(base)
    state0 = (jnp.ones((), jnp.int32), end0 - tx0, end0, tx0,
              settled(end0 - tx0, base))

    def cond(state):
        i, _, _, _, conv = state
        return ~conv & (i < _FIXED_POINT_SWEEPS)

    def body(state):
        i, starts, _, _, _ = state
        end, tx = sweep(starts)
        return i + 1, end - tx, end, tx, settled(end - tx, starts)

    _, _, end, tx, conv = jax.lax.while_loop(cond, body, state0)
    any_live = mask.any()
    new_busy = jnp.where(any_live, jnp.where(mask, end, _NEG).max(), busy0)
    wire = tx.sum()
    queued = jnp.where(mask, jnp.clip(end - tx - subm, 0.0, None), 0.0).sum()
    return end, new_busy, wire, queued, any_live & ~conv


def _plan_groups(spec: EngineSpec, fleet: PaddedFleet, now, bw, st_eff):
    """Heterogeneous control plane: gather each policy group's streams,
    run the group's own planner, scatter the outputs back into fleet-wide
    arrays — ``FleetRunner.plan_all``'s group loop with static index sets,
    compiling one planner subgraph per group.  Stream order inside the
    engine is never permuted (the SFQ/argsort tie-breaks key on global
    stream ids); streams outside every group (S-padding) keep the
    inactive-row defaults (dec=-1, theta=0, r°=m-1)."""
    S, L, m = spec.n_streams, spec.planner.L, spec.m
    dt = spec.planner.dtype
    out = PlanOut(
        dec=jnp.full((S, L), -1, dtype=jnp.int8),
        theta=jnp.zeros((S,), dtype=dt),
        resolution=jnp.full((S,), m - 1, dtype=jnp.int32),
        n_offloads=jnp.zeros((S,), jnp.int32),
        total_gain=jnp.zeros((S,), dtype=dt),
        base_acc=jnp.zeros((S,), dtype=dt),
        n_frames=fleet.length,
        overflow=jnp.zeros((S,), bool),
        inexact=jnp.zeros((S,), bool))
    for g in spec.groups:
        idx = jnp.asarray(g.streams, dtype=jnp.int32)
        sub = PaddedFleet(fleet.arrival[idx], fleet.conf[idx], fleet.length[idx])
        p = plan_fleet(g.planner, sub, now[idx], bw[idx], st_eff)
        out = PlanOut(
            dec=out.dec.at[idx].set(p.dec),
            theta=out.theta.at[idx].set(p.theta),
            resolution=out.resolution.at[idx].set(p.resolution),
            n_offloads=out.n_offloads.at[idx].set(p.n_offloads),
            total_gain=out.total_gain.at[idx].set(p.total_gain),
            base_acc=out.base_acc.at[idx].set(p.base_acc),
            n_frames=out.n_frames,
            overflow=out.overflow.at[idx].set(p.overflow),
            inexact=out.inexact.at[idx].set(p.inexact))
    return out


def _group_flags(spec: EngineSpec):
    """Static per-stream (prune, oneshot, max_backlog) rows from the group
    table; padded/ungrouped streams get (False, False, 0) — their backlogs
    are provably empty, so every choice is a no-op."""
    S = spec.n_streams
    prune = np.zeros(S, dtype=bool)
    oneshot = np.zeros(S, dtype=bool)
    mb = np.zeros(S, dtype=np.int32)
    for g in spec.groups:
        ss = list(g.streams)
        prune[ss] = g.prune
        oneshot[ss] = g.oneshot
        mb[ss] = g.mb
    return prune, oneshot, mb


def _batch_latency(spec: EngineSpec, n):
    """The slow tier's latency curve f(n) from the flat static coefficients
    (mirrors ``repro.slowtier``'s LatencyModel classes in jnp)."""
    c = spec.batch_coeffs
    if spec.batch_kind == "flat":
        return c[0] * n
    if spec.batch_kind == "linear":
        return c[0] + c[1] * n
    if spec.batch_kind == "step":
        return c[0] + c[1] * jnp.ceil(n / c[2])
    raise ValueError(f"unknown batch_kind {spec.batch_kind!r}")


# --------------------------------------------------------------------------- #
# the round step
# --------------------------------------------------------------------------- #


def _round_step(spec: EngineSpec, params: EngineParams,
                carry: EngineCarry, x: RoundInputs):
    S, B, C, K = spec.n_streams, spec.batch, spec.n_cells, spec.n_replicas
    L, m = spec.planner.L, spec.m
    dt = spec.planner.dtype
    N = S * B
    inf = jnp.inf
    arr = shard(x.arr.astype(dt), "streams", None)
    valid, conf = x.valid, x.conf.astype(dt)

    # (1) active streams; retire the rest (FleetRunner.retire)
    active = valid.any(axis=1)
    fleet = clear_fleet(carry.fleet, ~active)

    # (2) control plane: prune + one batched plan (FleetRunner.plan_all);
    # heterogeneous fleets prune per group's policy and plan group by group
    now = arr.min(axis=1)  # first valid arrival; +inf when none
    if spec.groups:
        g_prune, g_oneshot, g_mb = _group_flags(spec)
        prune_mask = active & jnp.asarray(g_prune)
    else:
        prune_mask = active if spec.prune else jnp.zeros_like(active)
    fleet = prune_fleet(fleet, now, spec.deadline, prune_mask)
    fleet = PaddedFleet(shard(fleet.arrival, "streams", None),
                        shard(fleet.conf, "streams", None),
                        shard(fleet.length, "streams"))
    bw_plan = jnp.maximum(carry.bw_est, 1.0)  # same dead-link floor
    st_eff = None
    if spec.batch_kind != "none":
        # occupancy-calibrated T^o = f(expected_batch)/expected_batch at the
        # observed occupancy EWMA (ReplicaPool.expected_server_time)
        nb = jnp.maximum(carry.avg_batch, 1.0)
        st_eff = (_batch_latency(spec, nb) / nb).astype(dt)
    if spec.groups:
        plan = _plan_groups(spec, fleet, now, bw_plan, st_eff)
    elif st_eff is None:
        plan = plan_fleet(spec.planner, fleet, now, bw_plan)
    else:
        plan = plan_fleet(spec.planner, fleet, now, bw_plan, st_eff)
    theta = jnp.where(active, plan.theta, 0.0)
    res_idx = jnp.where(active, plan.resolution, m - 1)
    n_off = jnp.where(active, plan.n_offloads, 0)
    dec = jnp.where(active[:, None], plan.dec, jnp.int8(-1))
    cap = jnp.where(active, jnp.maximum(n_off, 1), 0)

    # (3) escalation gate (select_escalations): per stream the cap lowest
    # confidences below theta — stable conf argsort + cumsum gate
    conf_gate = jnp.where(valid, conf, inf)
    o_slot = jnp.argsort(conf_gate, axis=1)
    gate_sorted = jnp.take_along_axis(conf_gate < theta[:, None], o_slot, axis=1)
    take_sorted = gate_sorted & (jnp.cumsum(gate_sorted, axis=1) <= cap[:, None])
    esc = jnp.zeros((S, B), bool).at[
        jnp.arange(S)[:, None], o_slot].set(take_sorted)

    payload_s = params.sizes[res_idx].astype(dt)  # (S,) planned upload bytes
    t_ready = arr + spec.t_fast
    if spec.has_splits:
        # a split action's upload leaves the device only after the model
        # prefix runs — shifts SFQ readiness AND the wire submit below
        t_dev_s = jnp.asarray(spec.act_t_dev, dtype=dt)[res_idx]  # (S,)
        t_ready = t_ready + t_dev_s[:, None]

    # (4) fair uplink schedule (FairScheduler.order).  Cost is constant per
    # stream within a round, so the SFQ tag recurrence unrolls over slots
    # (per-stream arrivals strictly ascend, so slot order == t_ready order).
    esc_flat = esc.reshape(-1)
    t_ready_flat = jnp.where(esc, t_ready, inf).reshape(-1)
    o = jnp.argsort(t_ready_flat)  # stable: ties keep (stream, slot) order
    if spec.scheduler == "round_robin":
        cost_s = payload_s / params.stream_bw / params.weights
        tags = jnp.full((S, B), inf, dtype=dt)
        prev = jnp.full((S,), _NEG, dtype=dt)
        for d in range(B):
            cand = jnp.maximum(t_ready[:, d], prev + cost_s)
            tags = tags.at[:, d].set(jnp.where(esc[:, d], cand, inf))
            prev = jnp.where(esc[:, d], cand, prev)
        o = _lexsort2(tags.reshape(-1), o)

    # (5) fabric transmit: per-cell masked Lindley over the scheduled rows
    stream_flat = jnp.repeat(jnp.arange(S, dtype=jnp.int32), B)
    s_o = stream_flat[o]
    m_o = esc_flat[o]
    sub_o = x.arr.reshape(-1)[o] + spec.t_fast  # real t_ready per row
    if spec.has_splits:
        sub_o = sub_o + t_dev_s[s_o]  # prefix runs before the upload
    pay_o = params.sizes[res_idx[s_o]].astype(dt)
    cell_o = params.cell_of[s_o]
    end_tx = jnp.zeros((N,), dtype=dt)
    cell_busy, cell_n = carry.cell_busy, carry.cell_n
    cell_busy_s, cell_queued_s = carry.cell_busy_s, carry.cell_queued_s
    fp_bad = carry.fp_bad
    for c in range(C):
        mk = m_o & (cell_o == c)
        if spec.varying and (spec.cell_trace[c] or spec.cell_jitter[c] > 0):
            key_c = None if carry.jit_key is None else carry.jit_key[c]
            end_c, busy_c, wire_c, queued_c, bad_c = _masked_lindley_varying(
                spec, params, c, key_c, sub_o, mk, pay_o, cell_busy[c])
            fp_bad = fp_bad | bad_c
        else:
            end_c, busy_c, wire_c, queued_c = _masked_lindley(
                sub_o, pay_o / params.cell_bw[c], mk, cell_busy[c])
        end_tx = jnp.where(mk, end_c, end_tx)
        cell_busy = cell_busy.at[c].set(busy_c)
        cell_n = cell_n.at[c].add(mk.sum(dtype=jnp.int32))
        cell_busy_s = cell_busy_s.at[c].add(wire_c)
        cell_queued_s = cell_queued_s.at[c].add(queued_c)

    # (6) replica placement in upload-arrival order (Placement.assign)
    end_m = jnp.where(m_o, end_tx, inf)
    o2 = jnp.argsort(end_m)  # stable: ties keep scheduler order
    m2 = m_o[o2]
    rr_next = carry.rr_next
    if spec.placement == "round_robin":
        rank = jnp.cumsum(m2.astype(jnp.int32)) - 1
        rep2 = (rr_next + rank) % K
        rr_next = (rr_next + m_o.sum(dtype=jnp.int32)) % K
    else:
        st = params.replica_st.astype(dt)

        def pstep(busy, inp):
            t_i, live = inp
            if spec.placement == "jsq":
                k = jnp.argmin(busy)
            else:  # least_land
                k = jnp.argmin(jnp.maximum(t_i, busy) + st)
            upd = busy.at[k].set(jnp.maximum(t_i, busy[k]) + st[k])
            return jnp.where(live, upd, busy), jnp.where(live, k, 0).astype(jnp.int32)

        _, rep2 = jax.lax.scan(pstep, carry.rep_busy.astype(dt), (end_m[o2], m2))
    replica_o = jnp.zeros((N,), jnp.int32).at[o2].set(rep2.astype(jnp.int32))

    # (7) replica pool service (ReplicaPool.process)
    rep_busy, rep_n = carry.rep_busy, carry.rep_n
    rep_busy_s, rep_queued_s = carry.rep_busy_s, carry.rep_queued_s
    st_row = params.replica_st[replica_o].astype(dt)
    if spec.has_splits:
        # split suffixes cost srv_frac of the replica's service time
        # (ReplicaPool.process's per-request service_scale); incompatible
        # with continuous batching — jax_unsupported rejects that pairing
        srv_o = jnp.asarray(spec.act_srv_frac, dtype=dt)[res_idx[s_o]]  # (N,)
        st_row = st_row * srv_o
    service_o = st_row  # per-row reported processing time (= whole-batch
    # f(n) under continuous batching — ReplicaPool.last_service semantics)
    avg_batch = carry.avg_batch
    if spec.batch_kind != "none":
        # continuous batching (ReplicaPool._process_batched): per replica,
        # admission-window batch formation over arrival-sorted rows.  Each
        # fori_loop iteration forms ONE batch via a rank-space pointer —
        # O(N) iterations x O(N) work per replica, the same opt-in cost
        # class as the per-row jsq/least_land scan above.
        w = spec.batch_window
        bcap = spec.batch_cap if spec.batch_cap > 0 else N
        repk = jnp.where(m_o, replica_o, K)
        o3 = _lexsort2(repk.astype(dt), jnp.argsort(jnp.where(m_o, end_tx, inf)))
        m3 = m_o[o3]
        a3, k3 = end_tx[o3], repk[o3]
        done3 = jnp.zeros((N,), dtype=dt)
        serv3 = jnp.zeros((N,), dtype=dt)
        size3 = jnp.zeros((N,), dtype=dt)
        for k in range(K):
            mk = m3 & (k3 == k)
            n_k = mk.sum(dtype=jnp.int32)
            rk = jnp.cumsum(mk.astype(jnp.int32)) - 1  # rank within replica

            def bstep(i, st7, mk=mk, rk=rk, n_k=n_k):
                p, busy, done_k, serv_k, size_k, wire_k, queued_k = st7
                live = p < n_k
                rem = mk & (rk >= p)  # not-yet-batched rows, a3 ascending
                a0 = jnp.min(jnp.where(rem, a3, inf))
                t_open = jnp.maximum(busy, a0)
                nwin = (rem & (a3 <= t_open + w)).sum(dtype=jnp.int32)
                count = jnp.minimum(nwin, bcap)
                member = rem & (rk < p + count)  # smallest-a3 rows first
                arr_last = jnp.max(jnp.where(member, a3, _NEG))
                # cap binding: launch at the last member's landing; else
                # when the admission window closes
                t_start = jnp.where(nwin > bcap,
                                    jnp.maximum(t_open, arr_last), t_open + w)
                fb = _batch_latency(spec, count.astype(dt))
                done_v = t_start + fb
                upd = member & live
                done_k = jnp.where(upd, done_v, done_k)
                serv_k = jnp.where(upd, fb, serv_k)
                size_k = jnp.where(upd, count.astype(dt), size_k)
                wire_k = wire_k + jnp.where(live, fb, 0.0)
                queued_k = queued_k + jnp.where(upd, t_start - a3, 0.0).sum()
                busy = jnp.where(live, done_v, busy)
                p = p + jnp.where(live, count, 0)
                return p, busy, done_k, serv_k, size_k, wire_k, queued_k

            init = (jnp.zeros((), jnp.int32), rep_busy[k].astype(dt),
                    done3, serv3, size3, jnp.zeros((), dt), jnp.zeros((), dt))
            (_, busy_k, done3, serv3, size3, wire_k,
             queued_k) = jax.lax.fori_loop(0, N, bstep, init)
            rep_busy = rep_busy.at[k].set(busy_k)
            rep_n = rep_n.at[k].add(n_k)
            rep_busy_s = rep_busy_s.at[k].add(wire_k)
            rep_queued_s = rep_queued_s.at[k].add(queued_k)
        done_o = jnp.zeros((N,), dtype=dt).at[o3].set(done3)
        service_o = jnp.zeros((N,), dtype=dt).at[o3].set(serv3)
        size_o = jnp.zeros((N,), dtype=dt).at[o3].set(size3)
        n_live = m_o.sum(dtype=jnp.int32)
        obs = jnp.where(m_o, size_o, 0.0).sum() / jnp.maximum(n_live, 1)
        avg_batch = jnp.where(
            n_live > 0,
            (1.0 - spec.batch_beta) * carry.avg_batch + spec.batch_beta * obs,
            carry.avg_batch)
    elif spec.serial_replicas:
        repk = jnp.where(m_o, replica_o, K)
        o3 = _lexsort2(repk.astype(dt), jnp.argsort(jnp.where(m_o, end_tx, inf)))
        m3 = m_o[o3]
        a3, k3 = end_tx[o3], repk[o3]
        done3 = jnp.zeros((N,), dtype=dt)
        for k in range(K):
            mk = m3 & (k3 == k)
            st_k = (params.replica_st[k].astype(dt) * srv_o[o3]
                    if spec.has_splits
                    else jnp.full((N,), params.replica_st[k], dtype=dt))
            end_k, busy_k, wire_k, queued_k = _masked_lindley(
                a3, st_k, mk, rep_busy[k])
            done3 = jnp.where(mk, end_k, done3)
            rep_busy = rep_busy.at[k].set(busy_k)
            rep_n = rep_n.at[k].add(mk.sum(dtype=jnp.int32))
            rep_busy_s = rep_busy_s.at[k].add(wire_k)
            rep_queued_s = rep_queued_s.at[k].add(queued_k)
        done_o = jnp.zeros((N,), dtype=dt).at[o3].set(done3)
    else:  # infinite-capacity fixed delay (paper semantics)
        done_o = end_tx + st_row
        for k in range(K):
            mk = m_o & (replica_o == k)
            rep_n = rep_n.at[k].add(mk.sum(dtype=jnp.int32))
            rep_busy_s = rep_busy_s.at[k].add(
                jnp.where(mk, st_row, 0.0).sum())
            rep_busy = rep_busy.at[k].set(jnp.maximum(
                rep_busy[k], jnp.where(mk, done_o, _NEG).max()))
    lands_o = done_o + spec.latency

    # (8) deadline check + final correctness
    arr_o = x.arr.reshape(-1)[o].astype(dt)
    ok_o = m_o & (lands_o <= arr_o + spec.deadline)
    lands_grid = jnp.zeros((N,), dtype=dt).at[o].set(lands_o).reshape(S, B)
    ok_grid = jnp.zeros((N,), bool).at[o].set(ok_o).reshape(S, B)
    eval_res = (jnp.asarray(spec.act_res, jnp.int32)[res_idx]
                if spec.has_splits else res_idx)  # action -> eval resolution
    slow_sel = jnp.take_along_axis(
        x.slow_ok, eval_res[:, None, None].astype(jnp.int32), axis=2)[..., 0]
    final_ok = jnp.where(ok_grid, slow_sel, x.fast_ok)
    correct_r = (final_ok & valid).sum(axis=1, dtype=jnp.int32)

    # (9) EWMA bandwidth observations in transmission order
    # (FleetRunner.observe_bandwidth; replica queueing deliberately included;
    # replies report their actual processing time — the whole-batch f(n)
    # under continuous batching, per-request service time otherwise)
    seconds_o = lands_o - sub_o - spec.latency - service_o
    okbw = m_o & (seconds_o > 1e-9)
    rate_o = pay_o / jnp.where(okbw, seconds_o, 1.0)
    bw_est = ewma_fold(carry.bw_est, spec.bw_alpha, s_o, rate_o, okbw, S, B)
    bw_est = shard(bw_est, "streams")

    # (10) backlog bookkeeping: consume planned offloads, extend the rest
    add = valid & ~esc
    if spec.groups:
        # mixed per-policy semantics: one consume pass takes the non-
        # oneshot offloads and clears the oneshot streams (FleetRunner
        # .consume), then extend trims each stream to its group's bound
        osh = jnp.asarray(g_oneshot)
        fleet = consume_fleet(fleet, (dec >= 0) & ~osh[:, None], osh & active)
        fleet = extend_fleet(fleet, arr, conf, add, jnp.asarray(g_mb))
    else:
        if spec.oneshot:
            fleet = clear_fleet(fleet, active)
        else:
            fleet = consume_fleet(fleet, dec >= 0, jnp.zeros((S,), bool))
        fleet = extend_fleet(fleet, arr, conf, add, spec.planner.L)

    # (11) metrics (AggregateMetrics.update_round inputs)
    lat = jnp.full((S, B), spec.t_fast, dtype=dt)
    lat = jnp.where(ok_grid, lands_grid - arr, lat)
    miss_grid = esc & ~ok_grid
    lat = jnp.where(miss_grid, spec.deadline, lat)
    off_counts = ok_grid.sum(axis=1, dtype=jnp.int32)
    miss_counts = miss_grid.sum(axis=1, dtype=jnp.int32)

    out = EngineCarry(
        fleet=fleet, bw_est=bw_est,
        cell_busy=cell_busy, cell_n=cell_n, cell_busy_s=cell_busy_s,
        cell_queued_s=cell_queued_s,
        rep_busy=rep_busy, rep_n=rep_n, rep_busy_s=rep_busy_s,
        rep_queued_s=rep_queued_s, rr_next=rr_next,
        frames=carry.frames + valid.sum(axis=1, dtype=jnp.int32),
        offloaded=carry.offloaded + off_counts,
        missed=carry.missed + miss_counts,
        correct=carry.correct + correct_r,
        avg_batch=avg_batch, jit_key=carry.jit_key, fp_bad=fp_bad)

    if spec.collect == "none":
        if spec.telemetry:
            raise ValueError("spec.telemetry needs collect >= 'metrics' — "
                             "the recorder's series ride on the ys pytree")
        return out, None
    z0 = jnp.zeros((0,))
    extras = dict(theta=z0, res_idx=z0, cap=z0, n_off=z0, n_frames=z0,
                  dec=z0, esc=z0, ok=z0, bw_est=z0, lengths=z0,
                  overflow=z0, inexact=z0)
    if spec.collect == "trace":
        extras = dict(theta=theta, res_idx=res_idx, cap=cap, n_off=n_off,
                      n_frames=plan.n_frames, dec=dec, esc=esc, ok=ok_grid,
                      bw_est=bw_est, lengths=fleet.length,
                      overflow=plan.overflow, inexact=plan.inexact)
    if spec.telemetry:
        # the FleetRecorder's per-round record (obs/timeseries.py): the
        # cumulative per-stream counters come from host cumsums of the
        # off/miss/correct columns above (integer-exact), so only the
        # simulated-state series are emitted here.  The histogram over the
        # action table is exact: every planned offload of stream s carries
        # action res_idx[s], and inactive/pad rows plan n_off == 0.
        A = params.sizes.shape[0]
        extras.update(
            ts_bw_est=bw_est,
            ts_off_hist=jnp.zeros((A,), jnp.int32).at[res_idx].add(
                n_off.astype(jnp.int32)),
            ts_cell_busy_s=cell_busy_s, ts_cell_queued_s=cell_queued_s,
            ts_rep_busy_s=rep_busy_s, ts_rep_queued_s=rep_queued_s,
            ts_avg_batch=avg_batch,
            ts_st_est=(st_eff if st_eff is not None
                       else jnp.asarray(spec.planner.server_time, dtype=dt)))
    ys = RoundTrace(off_counts=off_counts, miss_counts=miss_counts,
                    correct=correct_r, lat=lat, **extras)
    return out, ys


def make_engine(spec: EngineSpec):
    """jit-compiled ``lax.scan`` over rounds, closed over the static spec.

    Returns ``run(params, carry, inputs) -> (carry, RoundTrace | None)``
    where ``inputs`` is a ``RoundInputs`` of (R, ...) stacked rounds.

    The carry is DONATED: its buffers are reused for the output carry, so
    the S=10^5 fleet state never round-trips through fresh allocations
    between calls.  Callers must not reuse a carry after passing it in —
    build a fresh one via ``init_carry`` (or thread the returned carry).
    """

    def run(params: EngineParams, carry: EngineCarry, inputs: RoundInputs):
        step = lambda c, x: _round_step(spec, params, c, x)
        return jax.lax.scan(step, carry, inputs)

    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=16)
def _cached_engine(spec: EngineSpec, mesh):
    """``make_engine(spec)`` built once per (spec, mesh), so a server's
    later ``process_streams`` calls reuse the compiled scan.  The mesh is
    part of the key because ``shard`` reads it while tracing."""
    return make_engine(spec)


def simulate(spec: EngineSpec, params: EngineParams, inputs: RoundInputs,
             carry: Optional[EngineCarry] = None):
    """One-shot convenience: init carry (unless given), run the scan."""
    if carry is None:
        carry = init_carry(spec, params)
    return _cached_engine(spec, current_mesh())(params, carry, inputs)


# --------------------------------------------------------------------------- #
# bridges from the numpy serving stack
# --------------------------------------------------------------------------- #


def jax_unsupported(server) -> list:
    """Every reason this ``MultiStreamServer`` cannot run on
    ``backend="jax"`` — the one shared capability check (used by the
    server constructor, ``FleetRunner``, and callers probing via
    ``supports_jax``).  Returns an empty list when fully supported;
    otherwise one entry per unsupported feature, so the error names all
    of them instead of the first one hit."""
    from repro.policy.fleet_jax import jax_unsupported_policies

    reasons = jax_unsupported_policies([g[0] for g in server.fleet.groups])
    for c, cell in enumerate(server.fabric.cells):
        up = cell.uplink
        if up.jitter > 0 and up.jitter_mode != "counter":
            reasons.append(
                f"cell {c}: jitter_mode='pcg' draws from a host rng the "
                "compiled scan cannot reproduce — construct the Uplink "
                "with jitter_mode='counter' for in-scan jitter")
    if server.fleet.actions is not None:
        at = server.fleet.action_table
        if at.n_actions > 127:
            reasons.append(
                f"split action table with {at.n_actions} actions exceeds the "
                "int8 decision grid (subsample the cut catalog to <= 127)")
        pool = server.fabric.pool
        if getattr(pool, "batching", None) is not None and pool._batching_live:
            reasons.append(
                "split actions with a live continuous-batching slow tier: "
                "batches share one f(n) latency curve, so per-request "
                "srv_frac scaling is not expressible (numpy raises too)")
    tel = getattr(server, "telemetry", None)
    if tel is not None and (tel.tracer is not None or getattr(tel, "trace", False)):
        reasons.append(
            "frame-lifecycle tracing (Telemetry.trace) needs per-frame host "
            "visibility the compiled scan does not have — use the numpy "
            "backend for traces (the per-round recorder works on both)")
    return reasons


def supports_jax(server) -> bool:
    """True iff every feature of this server's configuration is
    expressible in the compiled round scan (shared predicate; the
    per-feature reasons come from ``jax_unsupported``)."""
    return not jax_unsupported(server)


def spec_from_server(server, collect: str = "metrics",
                     pad_streams: Optional[int] = None,
                     telemetry: bool = False) -> EngineSpec:
    """Build the static spec from a ``MultiStreamServer`` (validating that
    the configuration is expressible in fixed shapes).  ``pad_streams``
    widens the stream axis to a device multiple for mesh sharding — the
    extra rows never see a valid frame, so they are provably inert."""
    from repro.policy.base import OneShotPolicy
    from repro.policy.fleet_jax import spec_for_policy

    reasons = jax_unsupported(server)
    if reasons:
        raise ValueError("backend='jax' cannot express this configuration: "
                         + "; ".join(reasons))
    if telemetry and collect == "none":
        collect = "metrics"  # the recorder's series ride on the ys pytree
    fleet = server.fleet
    S = server.n_streams if pad_streams is None else int(pad_streams)
    if S < server.n_streams:
        raise ValueError(f"pad_streams={S} < n_streams={server.n_streams}")
    pool = server.fabric.pool
    batch_kind, batch_coeffs, batch_window, batch_cap = "none", (), 0.0, 0
    batch_beta = 0.25
    if getattr(pool, "batching", None) is not None and pool._batching_live:
        # live continuous batching: flatten the latency model into static
        # coefficients; a degenerate config stays on the per-request path
        # (bit-for-bit with the pre-batching engine, like numpy's routing)
        from repro.slowtier import model_coeffs

        batch_kind, batch_coeffs = model_coeffs(pool.batching.model)
        batch_window = float(pool.batching.window_s)
        cap = pool.batching.cap
        batch_cap = 0 if np.isinf(cap) else int(cap)
        batch_beta = pool.batch_beta
    common = dict(sizes=fleet.sizes, acc_server=fleet.acc_server,
                  deadline=fleet.deadline, latency=fleet.latency,
                  server_time=fleet.server_time, actions=fleet.actions)
    if len(fleet.groups) == 1:
        # homogeneous: spec-level prune/oneshot, groups=() — the exact
        # single-planner compiled graph (snapshot goldens pin it)
        policy = fleet.groups[0][0]
        planner = spec_for_policy(policy, **common)
        groups = ()
        prune = bool(getattr(policy, "prune_expired", True))
        oneshot = isinstance(policy, OneShotPolicy)
    else:
        # heterogeneous: every group shares one (S, L) grid padded to the
        # largest max_backlog; each group trims to its own bound
        L = max(int(p.max_backlog) for p, _ in fleet.groups)
        groups = tuple(
            EngineGroup(planner=spec_for_policy(p, pad_L=L, **common),
                        streams=tuple(int(s) for s in ss),
                        prune=bool(getattr(p, "prune_expired", True)),
                        oneshot=isinstance(p, OneShotPolicy),
                        mb=int(p.max_backlog))
            for p, ss in fleet.groups)
        planner = groups[0].planner  # shared L/m/deadline/latency/dtype
        prune, oneshot = True, False  # unused: per-group flags govern
    uplinks = [c.uplink for c in server.fabric.cells]
    varying = any(u.jitter > 0 or u.trace is not None for u in uplinks)
    at = fleet.action_table
    has_splits = fleet.actions is not None
    return EngineSpec(
        n_streams=S, batch=server.cfg.batch_size,
        n_cells=server.fabric.n_cells, n_replicas=server.fabric.n_replicas,
        planner=planner, placement=server.fabric.placement.policy,
        serial_replicas=server.fabric.pool.serial,
        scheduler=server.scheduler.policy,
        prune=prune, oneshot=oneshot,
        t_fast=float(server.cfg.fast_time + server.cfg.calib_time),
        bw_alpha=fleet.bw_alpha, collect=collect,
        batch_kind=batch_kind, batch_coeffs=batch_coeffs,
        batch_window=batch_window, batch_cap=batch_cap,
        batch_beta=batch_beta, groups=groups, varying=varying,
        cell_jitter=tuple(float(u.jitter) for u in uplinks) if varying else (),
        cell_seed=tuple(int(u.seed) for u in uplinks) if varying else (),
        cell_trace=tuple(u.trace is not None for u in uplinks) if varying else (),
        cell_loop=tuple(bool(u.trace.loop) if u.trace is not None else False
                        for u in uplinks) if varying else (),
        act_t_dev=tuple(float(x) for x in at.t_dev) if has_splits else (),
        act_srv_frac=tuple(float(x) for x in at.srv_frac) if has_splits else (),
        act_res=tuple(int(r) for r in at.res) if has_splits else (),
        telemetry=bool(telemetry))


def params_from_server(server, spec: EngineSpec) -> EngineParams:
    dt = spec.planner.dtype
    S0 = server.n_streams
    pad = spec.n_streams - S0
    sched_w = server.scheduler.weights
    weights = np.ones(S0) if sched_w is None else np.asarray(sched_w,
                                                             dtype=np.float64)

    def pad1(a, fill):
        # pad rows are inert (no valid frames), but keep their values
        # finite and nonzero so no division inside the step produces nans
        a = np.asarray(a, dtype=np.float64)
        return a if pad == 0 else np.concatenate([a, np.full(pad, fill)])

    cell_of = np.asarray(server.fabric.cell_of, dtype=np.int64)
    if pad:
        cell_of = np.concatenate([cell_of, np.zeros(pad, dtype=np.int64)])
    uplinks = [c.uplink for c in server.fabric.cells]
    extra = {}
    if spec.varying and any(spec.cell_trace):
        # one fixed-shape breakpoint grid per cell, padded to the longest
        # trace; constant cells get a single all-time segment
        T = max(len(u.trace) for u in uplinks if u.trace is not None)
        ts, rates, durs = [], [], []
        for u in uplinks:
            if u.trace is not None:
                t, bps = u.trace.grid(pad_to=T)
                durs.append(float(u.trace.duration))
            else:
                t = np.r_[0.0, np.full(T - 1, np.inf)]
                bps = np.full(T, u.bandwidth_bps)
                durs.append(np.inf)
            ts.append(t)
            rates.append(bps)
        extra = dict(trace_t=jnp.asarray(np.stack(ts), dtype=dt),
                     trace_bps=jnp.asarray(np.stack(rates), dtype=dt),
                     trace_dur=jnp.asarray(durs, dtype=dt))
    return EngineParams(
        # the shared action→bytes table, full width: (A,) with splits, the
        # legacy (m,) resolution grid otherwise (identical values — the
        # frames-only table IS payload_sizes(size_of, resolutions))
        sizes=jnp.asarray(server.fleet.action_table.sizes, dtype=dt),
        cell_bw=jnp.asarray([u.bandwidth_bps for u in uplinks], dtype=dt),
        cell_of=jnp.asarray(cell_of, dtype=jnp.int32),
        replica_st=jnp.asarray(server.fabric.pool.server_time, dtype=dt),
        stream_bw=jnp.asarray(pad1(server._stream_bw, 1.0), dtype=dt),
        weights=jnp.asarray(pad1(weights, 1.0), dtype=dt),
        bw_init=jnp.asarray(pad1(server.fleet.bw_est, 1.0), dtype=dt),
        **extra)
