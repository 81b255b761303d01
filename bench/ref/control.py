"""Plain float64 reference of the served control plane: CBO over a fleet.

Written from the paper (arXiv:2112.02439, Algorithm 1 and its serving
loop) for the configurations this benchmark runs: lockstep streams, one
cell whose uplink every stream shares, one slow-tier server of fixed
service time, ``cbo`` on every stream, start-time fair queueing on the
uplink.  One stream and one frame at a time, in plain loops; it imports
nothing of the program.  Per round it takes what the data plane handed the
control plane: each frame's calibrated confidence and whether the fast
tier and the slow tier at each rung answered it correctly.  It returns the
segment's frame, offload, deadline-miss and correct counts.

Each round, for each stream:

1. frames whose deadline has passed leave the backlog;
2. Algorithm 1 plans over the backlog: a DP over the frames in order of
   falling confidence, whose states are (time the uplink frees, summed
   accuracy gain), each frame kept local or offloaded at a rung that lands
   by its deadline on the stream's estimated bandwidth; dominated states
   (later and no better) are pruned.  The plan's threshold theta is the
   highest confidence it offloads, and its rung that frame's rung;
3. of the round's new frames, those under theta escalate, lowest
   confidence first, as many as the plan offloads (at least one), all at
   the plan's rung.

Then the round's escalations are ordered by start-time fair queueing, sent
one after another over the cell's uplink, served after a fixed delay, and
judged against their deadline; each reply updates its stream's bandwidth
estimate (an exponentially weighted mean).  The planned backlog frames
leave the backlog, and the round's frames that stayed local join it.
"""
from __future__ import annotations

from dataclasses import dataclass

_EPS = 1e-12  # a state must beat every earlier one by more than this


@dataclass(frozen=True)
class ControlConfig:
    resolutions: tuple  # upload ladder, pixels
    acc_server: tuple  # slow-tier accuracy per rung
    deadline: float  # per-frame window, s
    frame_rate: float  # frames/s per stream
    batch: int  # frames per stream per round
    cell_bps: float  # the one cell's uplink, bytes/s
    latency: float  # network latency, s
    server_time: float  # slow-tier service time, s
    t_fast: float  # fast tier + calibration time per frame, s
    max_backlog: int = 64  # CBO backlog per stream
    bw_alpha: float = 0.3  # bandwidth estimate's weight on a new reply
    png_base_res: int = 224
    png_base_bytes: float = 60_000.0

    def size(self, rung: int) -> float:
        """Bytes of an upload at ``rung``: a PNG's size grows with its area."""
        return self.png_base_bytes * (self.resolutions[rung] / self.png_base_res) ** 2


def plan(backlog, bw, now, cfg: ControlConfig):
    """Algorithm 1 over one stream's backlog, a list of (arrival, conf).

    Returns (theta, rung, offloads): the threshold, the rung to send at,
    and the backlog positions the plan offloads."""
    m = len(cfg.acc_server)
    rtt = cfg.server_time + cfg.latency
    states = [(now, 0.0, None)]  # (uplink free at, gain, last decision)
    for i in sorted(range(len(backlog)), key=lambda i: -backlog[i][1]):
        arrival, conf = backlog[i]
        cand = list(states)  # keeping frame i local changes no state
        for t, gain, node in states:
            for r in range(m):
                dA = cfg.acc_server[r] - conf
                done = max(t, arrival) + cfg.size(r) / bw
                if dA > 0 and done + rtt <= arrival + cfg.deadline:
                    cand.append((done, gain + dA, (i, r, node)))
        cand.sort(key=lambda s: (s[0], -s[1]))
        states, best = [], float("-inf")
        for s in cand:
            if s[1] > best + _EPS:
                states.append(s)
                best = s[1]
    node = max(states, key=lambda s: s[1])[2]
    chain = []
    while node is not None:
        chain.append(node[:2])
        node = node[2]
    if not chain:
        return 0.0, m - 1, []
    # the highest confidence offloaded; on a tie the earliest frame
    i_top, r_top = max(chain, key=lambda d: (backlog[d[0]][1], -d[0]))
    return backlog[i_top][1], r_top, sorted(i for i, _ in chain)


def fair_order(sends, cell_bps):
    """Start-time fair queueing over one round's sends, each a dict with
    ``stream``, ``ready`` and ``bytes``: a send's tag is the later of its
    ready time and the end of its stream's previous send at nominal rate;
    the uplink serves tags in order (then ready time, then stream)."""
    tag_of = {}
    for k in sorted(range(len(sends)), key=lambda k: (sends[k]["stream"], sends[k]["ready"])):
        s = sends[k]
        prev = tag_of.get(("last", s["stream"]))
        tag = s["ready"] if prev is None else max(s["ready"], prev[0] + prev[1])
        tag_of[k] = tag
        tag_of[("last", s["stream"])] = (tag, s["bytes"] / cell_bps)
    return sorted(range(len(sends)),
                  key=lambda k: (tag_of[k], sends[k]["ready"], sends[k]["stream"]))


def replay(cfg: ControlConfig, conf, fast_ok, slow_ok) -> dict:
    """One segment: ``conf`` and ``fast_ok`` are (S, N), ``slow_ok``
    (S, N, m), N frames per stream.  Returns the segment's counts."""
    S, N = len(conf), len(conf[0])
    gamma = 1.0 / cfg.frame_rate
    backlog = [[] for _ in range(S)]  # (arrival, conf), oldest first
    bw = [float(cfg.cell_bps)] * S  # each stream's estimate starts at the cell's rate
    uplink_free = 0.0
    n_off = n_miss = n_correct = 0
    for start in range(0, N, cfg.batch):
        frames = range(start, min(start + cfg.batch, N))
        sends, plans = [], []
        for s in range(S):
            arrival = [s * gamma / S + n * gamma for n in frames]
            now = arrival[0]
            backlog[s] = [f for f in backlog[s] if f[0] + cfg.deadline > now]
            theta, rung, offloads = plan(backlog[s], max(bw[s], 1.0), now, cfg)
            plans.append((rung, offloads))
            gated = sorted((float(conf[s][n]), j) for j, n in enumerate(frames)
                           if conf[s][n] < theta)
            escalated = {j for _, j in gated[:max(len(offloads), 1)]}
            for j, n in enumerate(frames):
                if j in escalated:
                    sends.append({"stream": s, "frame": n, "arrival": arrival[j],
                                  "ready": arrival[j] + cfg.t_fast, "bytes": cfg.size(rung)})
            n_correct += sum(bool(fast_ok[s][n]) for j, n in enumerate(frames)
                             if j not in escalated)
        for k in fair_order(sends, cfg.cell_bps):
            x = sends[k]
            s = x["stream"]
            uplink_free = max(x["ready"], uplink_free) + x["bytes"] / cfg.cell_bps
            lands = uplink_free + cfg.server_time + cfg.latency
            if lands <= x["arrival"] + cfg.deadline:
                n_off += 1
                n_correct += bool(slow_ok[s][x["frame"]][plans[s][0]])
            else:
                n_miss += 1
                n_correct += bool(fast_ok[s][x["frame"]])
            secs = lands - x["ready"] - cfg.latency - cfg.server_time
            if secs > 1e-9:
                bw[s] = (1 - cfg.bw_alpha) * bw[s] + cfg.bw_alpha * x["bytes"] / secs
        sent = {(x["stream"], x["frame"]) for x in sends}
        for s in range(S):
            gone = set(plans[s][1])
            kept = [f for i, f in enumerate(backlog[s]) if i not in gone]
            kept += [(s * gamma / S + n * gamma, float(conf[s][n])) for n in frames
                     if (s, n) not in sent]
            backlog[s] = kept[-cfg.max_backlog:]
    return {"frames": S * N, "offloads": n_off, "misses": n_miss, "correct": n_correct}
