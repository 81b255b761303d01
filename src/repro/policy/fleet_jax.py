"""JAX fleet control plane: fixed-shape padded/masked port of ``fleet.py``.

The numpy control plane (``policy/fleet.py`` + ``policy/frontier.py``) is
the semantic reference; this module re-expresses it in shapes ``jax.jit``
can compile:

  * ragged backlogs become a ``PaddedFleet`` — ``(S, L)`` arrival/conf
    grids plus an ``(S,)`` length vector; slot ``j`` of stream ``s`` is
    valid iff ``j < length[s]``, and valid slots are always packed at the
    front in insertion order (the same order a ``FleetState`` segment or a
    ``BacklogPolicy.backlog`` list would have, so backlog *positions* mean
    the same thing on every path);
  * the segment ops (``prune_expired`` / ``consume`` / ``extend`` /
    ``clear``) become per-stream mask-and-compact passes, vmapped over the
    fleet — compaction is one stable ``argsort(~keep)``, which moves kept
    slots to the front without reordering them;
  * the planners become per-stream fixed-shape functions, vmapped, whose
    backlog walk stops at the fleet's deepest live backlog: the
    CBO frontier DP runs with a capped frontier of ``F`` states and
    reports an ``overflow`` flag when the cap would have truncated it
    (the differential tests assert the flag stays clean), plus an
    ``inexact`` flag for the one epsilon corner where the vectorized
    prune shortcut could disagree with the reference's sequential rule.

Exactness policy (see docs/jax_backend.md): the numpy path plans in
float64, this one in ``spec.dtype`` (float32 by default).  Integer
decisions — which frames offload, at which resolution, in which order —
are compared exactly; accumulated floats (gains, busy times, EWMA) at
tolerance.  Candidate ordering and tie-breaks are kept identical to
``frontier.py``: confidence-descending stable frame order, carries before
expansions (state-major, resolution-minor), pruning by a stable
``(t asc, gain desc, candidate idx asc)`` sort with the strictly-beats-
the-kept-bar rule.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "PaddedFleet", "PlanOut", "PlannerSpec",
    "pad_fleet", "unpad_fleet", "fleet_from_state", "plan_batch_from_out",
    "prune_fleet", "consume_fleet", "extend_fleet", "clear_fleet",
    "plan_fleet", "make_planner", "spec_for_policy", "planner_kind",
    "jax_unsupported_policies", "ewma_fold", "JAX_PLANNABLE",
]

_EPS = 1e-12  # same dominance epsilon as policy/frontier.py

#: policy registry names the JAX planner supports (homogeneous fleets)
JAX_PLANNABLE = ("cbo", "threshold", "local", "server", "greedy-rate")


# --------------------------------------------------------------------------- #
# padded fleet state
# --------------------------------------------------------------------------- #


class PaddedFleet(NamedTuple):
    """Fixed-shape fleet backlog: valid slots packed at the front."""

    arrival: jnp.ndarray  # (S, L)
    conf: jnp.ndarray  # (S, L)
    length: jnp.ndarray  # (S,) int32 — slots < length are valid


def pad_fleet(arrival, conf, lengths, L: int, dtype=jnp.float32) -> PaddedFleet:
    """Host constructor from flat ragged arrays (``FleetState`` layout)."""
    lengths = np.asarray(lengths, dtype=np.int64)
    S = len(lengths)
    if lengths.max(initial=0) > L:
        raise ValueError(f"backlog length {int(lengths.max())} exceeds pad L={L}")
    arr = np.zeros((S, L), dtype=np.float64)
    cf = np.zeros((S, L), dtype=np.float64)
    offsets = np.r_[0, np.cumsum(lengths)]
    flat_a = np.asarray(arrival, dtype=np.float64)
    flat_c = np.asarray(conf, dtype=np.float64)
    if len(flat_a):
        sid = np.repeat(np.arange(S), lengths)
        pos = np.arange(len(flat_a)) - offsets[:-1][sid]
        arr[sid, pos] = flat_a
        cf[sid, pos] = flat_c
    return PaddedFleet(jnp.asarray(arr, dtype=dtype), jnp.asarray(cf, dtype=dtype),
                       jnp.asarray(lengths, dtype=jnp.int32))


def fleet_from_state(state, L: int, dtype=jnp.float32) -> PaddedFleet:
    """Pad a ``FleetState`` (numpy, ragged) into device arrays."""
    return pad_fleet(state.arrival, state.conf, state.lengths, L, dtype=dtype)


def unpad_fleet(fleet: PaddedFleet):
    """Back to host ragged arrays: (arrival, conf, lengths) numpy tuples."""
    arr = np.asarray(fleet.arrival)
    conf = np.asarray(fleet.conf)
    lens = np.asarray(fleet.length, dtype=np.int64)
    L = arr.shape[1]
    valid = np.arange(L)[None, :] < lens[:, None]
    return arr[valid], conf[valid], lens


# --------------------------------------------------------------------------- #
# segment ops (mask-and-compact, vmapped)
# --------------------------------------------------------------------------- #


def _compact(arr, conf, keep):
    """Move kept slots to the front, preserving order (stable argsort)."""
    o = jnp.argsort(~keep)  # False < True; stable, so kept order survives
    return arr[o], conf[o], keep.sum().astype(jnp.int32)


def _prune_single(arr, conf, length, now, deadline, do):
    valid = jnp.arange(arr.shape[0]) < length
    # same float compare as FleetState.prune_expired / BacklogPolicy.plan
    keep = valid & jnp.where(do, arr + deadline > now, True)
    return _compact(arr, conf, keep)


def _consume_single(arr, conf, length, take, clear):
    valid = jnp.arange(arr.shape[0]) < length
    keep = valid & ~take & ~clear
    return _compact(arr, conf, keep)


def _extend_single(arr, conf, length, new_arr, new_conf, new_ok, mb):
    """Append the round's new frames (slot order) then trim to the newest
    ``mb`` — list-``observe`` semantics with static shapes.  ``mb`` is a
    static int on homogeneous fleets or a per-stream scalar (vmapped) on
    heterogeneous ones, where groups trim to their own ``max_backlog``
    while sharing one pad width L."""
    L = arr.shape[0]
    B = new_arr.shape[0]
    po = jnp.argsort(~new_ok)  # pack new frames, slot order preserved
    na, nc = new_arr[po], new_conf[po]
    n_new = new_ok.sum().astype(jnp.int32)
    total = length + n_new
    start = jnp.maximum(total - mb, 0)
    idx = start + jnp.arange(L, dtype=jnp.int32)
    from_old = idx < length
    oi = jnp.clip(idx, 0, L - 1)
    ni = jnp.clip(idx - length, 0, B - 1)
    out_a = jnp.where(from_old, arr[oi], na[ni])
    out_c = jnp.where(from_old, conf[oi], nc[ni])
    return out_a, out_c, jnp.minimum(total, mb).astype(jnp.int32)


def prune_fleet(fleet: PaddedFleet, now, deadline: float, do_mask) -> PaddedFleet:
    """Batched ``FleetState.prune_expired``: drop expired frames of the
    streams where ``do_mask`` is set."""
    a, c, n = jax.vmap(_prune_single, in_axes=(0, 0, 0, 0, None, 0))(
        fleet.arrival, fleet.conf, fleet.length, now, deadline, do_mask)
    return PaddedFleet(a, c, n)


def consume_fleet(fleet: PaddedFleet, take, clear) -> PaddedFleet:
    """Batched ``FleetState.consume``: ``take`` is an (S, L) mask of backlog
    positions that left the device; ``clear`` empties whole streams."""
    a, c, n = jax.vmap(_consume_single)(fleet.arrival, fleet.conf, fleet.length,
                                        take, clear)
    return PaddedFleet(a, c, n)


def extend_fleet(fleet: PaddedFleet, new_arr, new_conf, new_ok, mb) -> PaddedFleet:
    """Batched ``FleetState.extend``: append each stream's (B,) new frames
    (mask ``new_ok``, slot order) and trim to the ``mb`` newest.  ``mb`` is
    either one static int (homogeneous fleet) or an (S,) per-stream bound
    (heterogeneous policy groups with distinct ``max_backlog``)."""
    mb_ax = None if np.ndim(mb) == 0 else 0
    a, c, n = jax.vmap(_extend_single, in_axes=(0, 0, 0, 0, 0, 0, mb_ax))(
        fleet.arrival, fleet.conf, fleet.length, new_arr, new_conf, new_ok, mb)
    return PaddedFleet(a, c, n)


def clear_fleet(fleet: PaddedFleet, mask) -> PaddedFleet:
    """Batched ``FleetState.clear``: empty the masked streams' backlogs."""
    return PaddedFleet(fleet.arrival, fleet.conf,
                       jnp.where(mask, 0, fleet.length).astype(jnp.int32))


# --------------------------------------------------------------------------- #
# planners
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class PlannerSpec:
    """Static planner configuration — everything jit specializes on."""

    kind: str  # "cbo" | "threshold" | "local" | "server" | "greedy-rate"
    sizes: tuple  # (m,) payload bytes per resolution
    acc_server: tuple  # (m,)
    deadline: float
    latency: float
    server_time: float  # nominal T^o; plan_fleet can override per call
    L: int  # backlog pad (== max_backlog on the jax path)
    F: int = 0  # CBO frontier cap; 0 -> 1 + L*m
    theta: float = 0.5  # threshold policy
    resolution: int = -1  # threshold policy (index, -1 = highest)
    frame_interval: float = 1.0 / 30.0  # server policy
    local_acc: float = 0.5  # greedy-rate policy
    dtype: object = jnp.float32
    # split-computation actions appended after the m frame actions
    # (repro.split / policy.types.ActionTable).  Empty tuples keep every
    # frame-only code path — and its compiled graph — untouched.
    split_sizes: tuple = ()  # payload bytes per split action
    split_acc: tuple = ()  # server accuracy per split action
    split_t_dev: tuple = ()  # device prefix seconds per split action
    split_srv_frac: tuple = ()  # fraction of T^o the suffix costs

    @property
    def m(self) -> int:
        return len(self.acc_server)

    @property
    def n_actions(self) -> int:
        return self.m + len(self.split_sizes)

    @property
    def rtt(self) -> float:
        return self.server_time + self.latency

    @property
    def frontier(self) -> int:
        return self.F if self.F > 0 else 1 + self.L * self.n_actions


class PlanOut(NamedTuple):
    """One fleet planning pass, fixed shapes (the ``PlanBatch`` analogue).

    ``dec[s, j]`` is the planned resolution index for backlog slot ``j``
    of stream ``s``, or -1 to keep it local — the offload set and the
    consume mask in one array.
    """

    dec: jnp.ndarray  # (S, L) int8
    theta: jnp.ndarray  # (S,)
    resolution: jnp.ndarray  # (S,) int32
    n_offloads: jnp.ndarray  # (S,) int32
    total_gain: jnp.ndarray  # (S,)
    base_acc: jnp.ndarray  # (S,)
    n_frames: jnp.ndarray  # (S,) int32
    overflow: jnp.ndarray  # (S,) bool — frontier cap would have truncated
    inexact: jnp.ndarray  # (S,) bool — eps-window prune disagreement possible
    depth: jnp.ndarray  # () int32 — backlog depths walked (deepest live backlog)


def _summarize(dec, conf, length, gain, spec: PlannerSpec):
    """theta / r° / counters from a decision row — ``plan_from_chain`` and
    ``PlanBatch.from_offloads`` semantics: theta is the max confidence among
    offloads, r° that frame's resolution, ties to the earliest position."""
    L = spec.L
    valid = jnp.arange(L) < length
    take = dec >= 0
    n_off = take.sum().astype(jnp.int32)
    confm = jnp.where(take, conf, -jnp.inf)
    mx = confm.max()
    has = take.any()
    first = jnp.argmax(confm == mx)  # earliest slot attaining the max
    theta = jnp.where(has, mx, jnp.asarray(0.0, dtype=conf.dtype))
    r0 = jnp.where(has, dec[first].astype(jnp.int32), spec.m - 1)
    base = jnp.where(valid, conf, 0.0).sum()
    return theta, r0, n_off, gain, base


def _plan_local_single(arr, conf, length, now, bw, st, depth,
                       spec: PlannerSpec):
    dec = jnp.full((spec.L,), -1, dtype=jnp.int8)
    return dec, jnp.asarray(0.0, dtype=arr.dtype), jnp.asarray(False), jnp.asarray(False)


def _plan_server_single(arr, conf, length, now, bw, st, depth,
                        spec: PlannerSpec):
    """ServerPolicy.plan_many: highest resolution sustainable within both
    the frame interval and the deadline budget; offload every frame."""
    L, m = spec.L, spec.m
    sizes = jnp.asarray(spec.sizes, dtype=arr.dtype)
    acc = jnp.asarray(spec.acc_server, dtype=arr.dtype)
    if isinstance(st, float):  # static T^o: Python-float math, as before
        tx_budget = min(spec.frame_interval, spec.deadline - st - spec.latency)
    else:  # occupancy-calibrated T^o traced per round
        tx_budget = jnp.minimum(spec.frame_interval,
                                spec.deadline - st - spec.latency)
    feas = sizes / jnp.maximum(bw, 1e-9) <= tx_budget  # (m,)
    has_res = feas.any()
    r_s = (m - 1) - jnp.argmax(feas[::-1]).astype(jnp.int32)
    valid = jnp.arange(L) < length
    take = valid & has_res
    dec = jnp.where(take, r_s.astype(jnp.int8), jnp.int8(-1))
    gain = jnp.where(take, acc[r_s] - conf, 0.0).sum()
    return dec, gain, jnp.asarray(False), jnp.asarray(False)


def _plan_threshold_single(arr, conf, length, now, bw, st, depth,
                           spec: PlannerSpec):
    """ThresholdPolicy.plan_many: serial acceptance in backlog order at a
    fixed resolution — same max-plus accumulation, same order."""
    L, m = spec.L, spec.m
    r = spec.resolution % m
    rtt = st + spec.latency
    tx = jnp.asarray(spec.sizes[r], dtype=arr.dtype) / bw
    dacc = jnp.asarray(spec.acc_server[r], dtype=arr.dtype) - conf  # (L,)
    valid = jnp.arange(L) < length

    def body(d, carry):
        t, gain, dec = carry
        cand = valid[d] & (conf[d] < spec.theta)
        t_new = jnp.maximum(t, arr[d]) + tx
        ok = cand & (t_new + rtt <= arr[d] + spec.deadline)
        t = jnp.where(ok, t_new, t)
        gain = jnp.where(ok, gain + dacc[d], gain)
        dec = dec.at[d].set(jnp.where(ok, jnp.int8(r), jnp.int8(-1)))
        return t, gain, dec

    t0 = now.astype(arr.dtype)
    _, gain, dec = jax.lax.fori_loop(
        0, depth, body, (t0, jnp.asarray(0.0, dtype=arr.dtype),
                         jnp.full((L,), -1, dtype=jnp.int8)))
    return dec, gain, jnp.asarray(False), jnp.asarray(False)


def _plan_cbo_single(arr, conf, length, now, bw, st, depth,
                     spec: PlannerSpec):
    """``cbo_plan`` (paper Algorithm 1) with a capped fixed-shape frontier.

    Semantics notes vs ``frontier.py``:
      * frames walk in confidence-descending stable order; invalid slots
        sort last (conf key -inf) so depths >= length are pure carries:
        no expansion is feasible and the sort reproduces the frontier, so
        the walk stops at ``depth``, the deepest backlog of the planned
        streams, with every output as the full ``L`` walk gives it;
      * candidates are [frontier carries] ++ [expansions, state-major /
        resolution-minor] — infeasible rows are masked (t=+inf, gain=-inf)
        instead of removed, which the stable (t, -gain, idx) sort sends to
        the tail without disturbing the relative order of live rows;
      * the reference's "collapse" shortcut (expand only from the last
        state with t <= arrival) is omitted: expansions from earlier such
        states tie in t with strictly lower gain, so the prune drops them
        — the surviving frontier is provably identical;
      * pruning keeps a candidate iff its gain beats the running max of
        all prior gains by > eps.  The reference advances its bar on KEPT
        gains only; the two rules can disagree only when a gain lands in
        an (eps, 2*eps] window above the bar — unrepresentable at float32
        resolution, but flagged (``inexact``) and rechecked by the tests;
      * instead of a node pool, every frontier state carries its full
        decision row (``(F, L)`` int8): survivors copy their parent's row
        and stamp their own (slot, resolution) — reconstruction-free.

    Split action tables dispatch to ``_plan_cbo_actions`` (the same DP over
    the enlarged {frame@res} ∪ {features@cut} grid); the frame-only body
    below stays as it was, so the snapshot goldens pinned to it never
    change, except that its loop bound is the fleet's deepest backlog
    (``depth``, one unbatched scalar), not the pad ``L``.
    """
    if spec.split_sizes:
        return _plan_cbo_actions(arr, conf, length, now, bw, st, depth, spec)
    L, m, F = spec.L, spec.m, spec.frontier
    dt = arr.dtype
    rtt = st + spec.latency
    sizes = jnp.asarray(spec.sizes, dtype=dt)
    acc = jnp.asarray(spec.acc_server, dtype=dt)
    tx = sizes / bw  # (m,)
    static_t = tx <= spec.deadline - rtt  # (m,)
    valid = jnp.arange(L) < length
    # confidence-descending stable order, invalid slots last
    order = jnp.argsort(-jnp.where(valid, conf, -jnp.inf))

    eps = jnp.asarray(_EPS, dtype=dt)
    neg = jnp.asarray(-jnp.inf, dtype=dt)
    cand_parent = jnp.concatenate([jnp.arange(F), jnp.repeat(jnp.arange(F), m)])
    cand_res = jnp.concatenate([jnp.full((F,), -1, dtype=jnp.int32),
                                jnp.tile(jnp.arange(m, dtype=jnp.int32), F)])

    def body(d, carry):
        f_t, f_gain, f_valid, f_dec, overflow, inexact = carry
        j = order[d]
        arr_j, conf_j = arr[j], conf[j]
        live = d < length
        feas_j = static_t & (acc > conf_j) & live  # (m,)
        start = jnp.maximum(f_t, arr_j)  # (F,)
        t_exp = start[:, None] + tx[None, :]  # (F, m)
        g_exp = f_gain[:, None] + (acc - conf_j)[None, :]
        ok_exp = (f_valid[:, None] & feas_j[None, :]
                  & (t_exp + rtt <= arr_j + spec.deadline))
        cand_t = jnp.concatenate([f_t, t_exp.reshape(-1)])
        cand_g = jnp.concatenate([f_gain, g_exp.reshape(-1)])
        cand_ok = jnp.concatenate([f_valid, ok_exp.reshape(-1)])
        tkey = jnp.where(cand_ok, cand_t, jnp.inf)
        gkey = jnp.where(cand_ok, cand_g, neg)
        # stable (t asc, gain desc, candidate idx asc) via composed sorts
        o = jnp.argsort(-gkey)
        o = o[jnp.argsort(tkey[o])]
        ts, gs, oks = tkey[o], gkey[o], cand_ok[o]
        run = jax.lax.cummax(gs)
        prev_all = jnp.concatenate([neg[None], run[:-1]])
        keep = oks & (gs > prev_all + eps)
        # reference bar advances on kept gains only — flag the eps window
        kept_bar = jax.lax.cummax(jnp.where(keep, gs, neg))
        prev_kept = jnp.concatenate([neg[None], kept_bar[:-1]])
        inexact = inexact | (oks & ~keep & (gs > prev_kept + eps)).any()
        overflow = overflow | (keep.sum() > F)
        sel = jnp.argsort(~keep)[:F]  # kept-first, sorted order preserved
        new_valid = keep[sel]
        new_t = jnp.where(new_valid, ts[sel], jnp.inf).astype(dt)
        new_g = jnp.where(new_valid, gs[sel], neg)
        src = o[sel]
        par, res = cand_parent[src], cand_res[src]
        dec_par = f_dec[par]  # (F, L)
        col = dec_par[jnp.arange(F), j]
        new_col = jnp.where(res >= 0, res.astype(jnp.int8), col)
        new_dec = dec_par.at[:, j].set(new_col)
        return new_t, new_g, new_valid, new_dec, overflow, inexact

    f_t = jnp.full((F,), jnp.inf, dtype=dt).at[0].set(now.astype(dt))
    f_gain = jnp.full((F,), -jnp.inf, dtype=dt).at[0].set(0.0)
    f_valid = jnp.zeros((F,), dtype=bool).at[0].set(True)
    f_dec = jnp.full((F, L), -1, dtype=jnp.int8)
    f_t, f_gain, f_valid, f_dec, overflow, inexact = jax.lax.fori_loop(
        0, depth, body, (f_t, f_gain, f_valid, f_dec,
                         jnp.asarray(False), jnp.asarray(False)))
    best = jnp.argmax(jnp.where(f_valid, f_gain, neg))  # first max, np.argmax order
    gain = jnp.where(f_valid[best], f_gain[best], 0.0)
    return f_dec[best], gain, overflow, inexact


def _plan_cbo_actions(arr, conf, length, now, bw, st, depth,
                      spec: PlannerSpec):
    """``cbo_plan`` over the full action grid — ``_plan_cbo_single`` with
    per-action columns instead of per-resolution ones (the jnp mirror of
    ``frontier._action_vectors``):

      * payload/accuracy become (A,) vectors (frames first, splits after);
      * a split action's upload leaves the device only after the prefix
        runs: effective start ``max(f_t, arr_j + t_dev[a])``;
      * its reply pays only the model suffix: per-action
        ``rtt[a] = st * srv_frac[a] + latency`` (frames: ``* 1.0``);
      * static feasibility subtracts ``t_dev`` too — the transmission must
        fit even when the uplink is idle at the *effective* ready time.

    Decision rows store ACTION indices (int8 — ``spec_for_policy`` bounds
    A at 127); frame actions occupy [0, m) so downstream consumers index
    shared action tables directly.
    """
    L, A, F = spec.L, spec.n_actions, spec.frontier
    dt = arr.dtype
    sizes = jnp.asarray(spec.sizes + spec.split_sizes, dtype=dt)
    acc = jnp.asarray(spec.acc_server + spec.split_acc, dtype=dt)
    t_dev = jnp.asarray((0.0,) * spec.m + spec.split_t_dev, dtype=dt)
    srv_frac = jnp.asarray((1.0,) * spec.m + spec.split_srv_frac, dtype=dt)
    rtt = st * srv_frac + spec.latency  # (A,)
    tx = sizes / bw  # (A,)
    static_t = tx <= spec.deadline - rtt - t_dev  # (A,)
    valid = jnp.arange(L) < length
    order = jnp.argsort(-jnp.where(valid, conf, -jnp.inf))

    eps = jnp.asarray(_EPS, dtype=dt)
    neg = jnp.asarray(-jnp.inf, dtype=dt)
    cand_parent = jnp.concatenate([jnp.arange(F), jnp.repeat(jnp.arange(F), A)])
    cand_res = jnp.concatenate([jnp.full((F,), -1, dtype=jnp.int32),
                                jnp.tile(jnp.arange(A, dtype=jnp.int32), F)])

    def body(d, carry):
        f_t, f_gain, f_valid, f_dec, overflow, inexact = carry
        j = order[d]
        arr_j, conf_j = arr[j], conf[j]
        live = d < length
        feas_j = static_t & (acc > conf_j) & live  # (A,)
        start = jnp.maximum(f_t[:, None], arr_j + t_dev[None, :])  # (F, A)
        t_exp = start + tx[None, :]  # (F, A)
        g_exp = f_gain[:, None] + (acc - conf_j)[None, :]
        ok_exp = (f_valid[:, None] & feas_j[None, :]
                  & (t_exp + rtt[None, :] <= arr_j + spec.deadline))
        cand_t = jnp.concatenate([f_t, t_exp.reshape(-1)])
        cand_g = jnp.concatenate([f_gain, g_exp.reshape(-1)])
        cand_ok = jnp.concatenate([f_valid, ok_exp.reshape(-1)])
        tkey = jnp.where(cand_ok, cand_t, jnp.inf)
        gkey = jnp.where(cand_ok, cand_g, neg)
        o = jnp.argsort(-gkey)
        o = o[jnp.argsort(tkey[o])]
        ts, gs, oks = tkey[o], gkey[o], cand_ok[o]
        run = jax.lax.cummax(gs)
        prev_all = jnp.concatenate([neg[None], run[:-1]])
        keep = oks & (gs > prev_all + eps)
        kept_bar = jax.lax.cummax(jnp.where(keep, gs, neg))
        prev_kept = jnp.concatenate([neg[None], kept_bar[:-1]])
        inexact = inexact | (oks & ~keep & (gs > prev_kept + eps)).any()
        overflow = overflow | (keep.sum() > F)
        sel = jnp.argsort(~keep)[:F]
        new_valid = keep[sel]
        new_t = jnp.where(new_valid, ts[sel], jnp.inf).astype(dt)
        new_g = jnp.where(new_valid, gs[sel], neg)
        src = o[sel]
        par, res = cand_parent[src], cand_res[src]
        dec_par = f_dec[par]
        col = dec_par[jnp.arange(F), j]
        new_col = jnp.where(res >= 0, res.astype(jnp.int8), col)
        new_dec = dec_par.at[:, j].set(new_col)
        return new_t, new_g, new_valid, new_dec, overflow, inexact

    f_t = jnp.full((F,), jnp.inf, dtype=dt).at[0].set(now.astype(dt))
    f_gain = jnp.full((F,), -jnp.inf, dtype=dt).at[0].set(0.0)
    f_valid = jnp.zeros((F,), dtype=bool).at[0].set(True)
    f_dec = jnp.full((F, L), -1, dtype=jnp.int8)
    f_t, f_gain, f_valid, f_dec, overflow, inexact = jax.lax.fori_loop(
        0, depth, body, (f_t, f_gain, f_valid, f_dec,
                         jnp.asarray(False), jnp.asarray(False)))
    best = jnp.argmax(jnp.where(f_valid, f_gain, neg))
    gain = jnp.where(f_valid[best], f_gain[best], 0.0)
    return f_dec[best], gain, overflow, inexact


def _plan_greedy_rate_single(arr, conf, length, now, bw, st, depth,
                             spec: PlannerSpec):
    """GreedyRatePolicy._plan: per frame in backlog order, walk resolutions
    from the highest down, stop at the first whose server accuracy no longer
    beats the local tier, offload at the first that also meets the deadline;
    the uplink finish time carries serially across frames (max-plus)."""
    L, m = spec.L, spec.m
    dt = arr.dtype
    rtt = st + spec.latency
    # candidate resolutions: the descending prefix from m-1 down to (but
    # excluding) the first r with acc_server[r] <= local_acc — static, the
    # reference's inner break depends only on config
    cand = []
    for r in range(m - 1, -1, -1):
        if spec.acc_server[r] <= spec.local_acc:
            break
        cand.append(r)
    if not cand:
        dec = jnp.full((L,), -1, dtype=jnp.int8)
        return dec, jnp.asarray(0.0, dtype=dt), jnp.asarray(False), jnp.asarray(False)
    cand_idx = jnp.asarray(cand, dtype=jnp.int32)  # descending r
    sizes = jnp.asarray(spec.sizes, dtype=dt)
    acc = jnp.asarray(spec.acc_server, dtype=dt)
    tx = sizes[cand_idx] / bw  # (n_cand,)
    valid = jnp.arange(L) < length

    def body(d, carry):
        t, gain, dec = carry
        t_new = jnp.maximum(t, arr[d]) + tx  # (n_cand,) — t untouched until pick
        ok = t_new + rtt <= arr[d] + spec.deadline
        pick = jnp.argmax(ok)  # first feasible candidate = highest feasible r
        has = ok.any() & valid[d]
        r_sel = cand_idx[pick]
        t = jnp.where(has, t_new[pick], t)
        gain = jnp.where(has, gain + acc[r_sel] - conf[d], gain)
        dec = dec.at[d].set(jnp.where(has, r_sel.astype(jnp.int8), jnp.int8(-1)))
        return t, gain, dec

    _, gain, dec = jax.lax.fori_loop(
        0, depth, body, (now.astype(dt), jnp.asarray(0.0, dtype=dt),
                         jnp.full((L,), -1, dtype=jnp.int8)))
    return dec, gain, jnp.asarray(False), jnp.asarray(False)


_PLANNERS = {
    "cbo": _plan_cbo_single,
    "threshold": _plan_threshold_single,
    "local": _plan_local_single,
    "server": _plan_server_single,
    "greedy-rate": _plan_greedy_rate_single,
}


def plan_fleet(spec: PlannerSpec, fleet: PaddedFleet, now, bw,
               server_time=None) -> PlanOut:
    """One planning pass over every stream, vmapped single-stream planners.

    ``bw`` must already carry the 1 byte/s floor (``FleetRunner.env_batch``
    applies it); ``now`` is each stream's first valid arrival this round.
    ``server_time`` overrides the spec's static nominal T^o with a traced
    scalar (the occupancy-calibrated estimate under a batching slow tier);
    ``None`` keeps the original static-constant compiled graph.

    The backlog walk takes ``fleet.length.max()`` steps, not ``L``: depths
    past a stream's length change none of its outputs.  The bound is one
    unbatched scalar, so the vmapped loop is a plain ``while`` with no
    per-stream predicate.
    """
    return _plan_to_depth(spec, fleet, now, bw, server_time,
                          fleet.length.max(initial=0))


def _plan_to_depth(spec: PlannerSpec, fleet: PaddedFleet, now, bw,
                   server_time, depth) -> PlanOut:
    """``plan_fleet`` with the backlog walk bounded by ``depth``."""
    single = _PLANNERS[spec.kind]
    st = spec.server_time if server_time is None \
        else jnp.asarray(server_time, dtype=spec.dtype)
    depth = jnp.asarray(depth, dtype=jnp.int32)

    def one(arr, conf, length, now_s, bw_s):
        dec, gain, overflow, inexact = single(arr, conf, length, now_s, bw_s,
                                              st, depth, spec)
        theta, r0, n_off, gain, base = _summarize(dec, conf, length, gain, spec)
        return dec, theta, r0, n_off, gain, base, overflow, inexact

    dec, theta, r0, n_off, gain, base, ovf, inx = jax.vmap(one)(
        fleet.arrival, fleet.conf, fleet.length, now, bw)
    return PlanOut(dec=dec, theta=theta, resolution=r0, n_offloads=n_off,
                   total_gain=gain, base_acc=base,
                   n_frames=fleet.length, overflow=ovf, inexact=inx,
                   depth=depth)


def make_planner(spec: PlannerSpec):
    """jit-compiled ``plan_fleet`` closed over the static spec.  The
    optional 4th arg is a traced ``server_time`` override (pass ``None``
    for the static spec constant; each choice compiles once)."""
    return jax.jit(lambda fleet, now, bw, server_time=None:
                   plan_fleet(spec, fleet, now, bw, server_time))


def planner_kind(policy) -> Optional[str]:
    """Registry kind of the JAX planner that covers ``policy`` (None when
    the compiled path has no equivalent)."""
    from repro.policy.policies import (CBOPolicy, GreedyRatePolicy, LocalPolicy,
                                       ServerPolicy, ThresholdPolicy)

    for cls, kind in ((CBOPolicy, "cbo"), (ThresholdPolicy, "threshold"),
                      (ServerPolicy, "server"), (GreedyRatePolicy, "greedy-rate"),
                      (LocalPolicy, "local")):
        if isinstance(policy, cls):
            return kind
    return None


def jax_unsupported_policies(policies) -> list:
    """Every reason the given policy instances (one per fleet group) cannot
    run on ``backend="jax"`` — empty list means fully supported.  Collects
    ALL blockers instead of raising on the first, so callers can surface
    one complete error message (``serving.engine_jax.jax_unsupported``)."""
    reasons = []
    for p in policies:
        name = type(p).__name__
        if planner_kind(p) is None:
            reasons.append(f"policy {name} has no JAX planner "
                           f"(supported kinds: {', '.join(JAX_PLANNABLE)})")
        if getattr(p, "max_backlog", None) is None:
            reasons.append(f"policy {name}: unbounded max_backlog cannot be "
                           "padded to fixed shapes (pass a finite max_backlog)")
    seen: set = set()
    return [r for r in reasons if not (r in seen or seen.add(r))]


def spec_for_policy(policy, *, sizes, acc_server, deadline, latency,
                    server_time, dtype=jnp.float32, F: int = 0,
                    pad_L: Optional[int] = None, actions=None) -> PlannerSpec:
    """Build the static spec for one policy instance (one fleet group).

    ``pad_L`` overrides the backlog pad width: heterogeneous fleets share
    one (S, L) grid padded to the largest group's ``max_backlog``, while
    each group still trims to its own bound (``extend_fleet``'s per-stream
    ``mb``).  Raises for policies the JAX path does not support — the
    numpy path is always available for those.

    ``actions`` is a split-computation ``ActionTable`` (or None): its split
    rows become the spec's static ``split_*`` tuples — consumed by the cbo
    planner only, exactly as on the numpy path (the baselines are
    frame-only by design and ignore the table).
    """
    mb = getattr(policy, "max_backlog", None)
    if mb is None:
        raise ValueError("backend='jax' needs a finite max_backlog "
                         "(fixed-shape backlogs); got None (unbounded)")
    L = int(mb) if pad_L is None else int(pad_L)
    if L < int(mb):
        raise ValueError(f"pad_L={L} is below the policy's max_backlog={mb}")
    common = dict(sizes=tuple(float(x) for x in sizes),
                  acc_server=tuple(float(x) for x in acc_server),
                  deadline=float(deadline), latency=float(latency),
                  server_time=float(server_time), L=L, F=F, dtype=dtype)
    kind = planner_kind(policy)
    if (actions is not None and getattr(actions, "has_splits", False)
            and kind == "cbo"):
        if actions.n_actions > 127:
            raise ValueError(
                f"backend='jax' stores decisions as int8: {actions.n_actions} "
                "actions exceed 127 (subsample the cut catalog)")
        k0 = actions.n_frame_actions
        common.update(
            split_sizes=tuple(float(x) for x in actions.sizes[k0:]),
            split_acc=tuple(float(x) for x in actions.acc[k0:]),
            split_t_dev=tuple(float(x) for x in actions.t_dev[k0:]),
            split_srv_frac=tuple(float(x) for x in actions.srv_frac[k0:]))
    if kind == "cbo":
        return PlannerSpec(kind="cbo", **common)
    if kind == "threshold":
        return PlannerSpec(kind="threshold", theta=policy.theta,
                           resolution=policy.resolution, **common)
    if kind == "server":
        return PlannerSpec(kind="server", frame_interval=policy.frame_interval,
                           **common)
    if kind == "greedy-rate":
        return PlannerSpec(kind="greedy-rate", local_acc=policy.local_acc,
                           **common)
    if kind == "local":
        return PlannerSpec(kind="local", **common)
    raise ValueError(f"backend='jax' supports policies {JAX_PLANNABLE}; "
                     f"got {type(policy).__name__}")


def plan_batch_from_out(out: PlanOut, n_streams: int, m: int):
    """Host bridge: materialize a numpy ``PlanBatch`` from a ``PlanOut``.

    Offloads come out of the (S, L) decision grid row-major, which IS
    (stream, pos) order — the order ``PlanBatch.sort_offloads`` produces.
    """
    from repro.policy.types import PlanBatch

    dec = np.asarray(out.dec)
    off_s, off_p = np.nonzero(dec >= 0)
    pb = PlanBatch(
        theta=np.asarray(out.theta, dtype=np.float64),
        resolution=np.asarray(out.resolution, dtype=np.int64),
        n_offloads=np.asarray(out.n_offloads, dtype=np.int64),
        total_gain=np.asarray(out.total_gain, dtype=np.float64),
        base_acc=np.asarray(out.base_acc, dtype=np.float64),
        n_frames=np.asarray(out.n_frames, dtype=np.int64),
        off_stream=off_s.astype(np.int64), off_pos=off_p.astype(np.int64),
        off_res=dec[off_s, off_p].astype(np.int64),
        planned=np.ones(n_streams, dtype=bool))
    return pb


# --------------------------------------------------------------------------- #
# EWMA bandwidth fold
# --------------------------------------------------------------------------- #


def ewma_fold(bw_est, alpha: float, stream, rate, ok, n_streams: int, depth: int):
    """Fold one round's transfer observations into the (S,) EWMA vector —
    ``FleetRunner.observe_bandwidth`` with static shapes.

    ``stream`` / ``rate`` / ``ok`` are flat rows in *transmission order*;
    each stream's valid observations are folded depth-wise in that order,
    bit-matching the scalar estimator's update sequence.  ``depth`` bounds
    observations per stream (the round's batch size).
    """
    o = jnp.argsort(jnp.where(ok, stream, n_streams))  # group by stream, stable
    s_sorted, r_sorted, ok_sorted = stream[o], rate[o], ok[o]
    # rank within stream = position - first position of the stream's group
    idx = jnp.arange(stream.shape[0])
    is_first = jnp.concatenate([jnp.ones((1,), bool),
                                s_sorted[1:] != s_sorted[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_first, idx, 0))
    rank = idx - group_start
    counts = jnp.zeros((n_streams,), jnp.int32).at[s_sorted].add(
        ok_sorted.astype(jnp.int32), mode="drop")
    grid = jnp.zeros((n_streams, depth), dtype=bw_est.dtype)
    # non-ok tail rows scatter out of bounds (dropped) so their ranks can
    # never collide with a valid stream/rank cell
    grid = grid.at[jnp.where(ok_sorted, s_sorted, n_streams),
                   jnp.minimum(rank, depth - 1)].set(r_sorted, mode="drop")
    a = alpha

    def body(k, bw):
        m = counts > k
        return jnp.where(m, (1 - a) * bw + a * grid[:, k], bw)

    return jax.lax.fori_loop(0, depth, body, bw_est)
