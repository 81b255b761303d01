"""The comparison that decides ``correct``.

For each checked segment, what the timed path produced is held against the
plain references, layer by layer:

* ``fast_logit_err``, ``slow_logit_err``: every logit the fast tier and the
  slow tier (at every rung of the ladder) returned in the segment, against
  the reference forward at the configuration's stated precision on the
  same frames: the largest absolute gap over the largest reference logit;
* ``conf_err``: every calibrated confidence the control plane received,
  against Platt's formula on the fast tier's logits in float64 (the fused
  gate kernel, or the plain max-softmax where the configuration has none):
  the largest gap relative to the reference's confidence, since a seed's
  calibration can put every confidence near 0;
* ``answer_mismatch``: frames whose fast or slow answer the control plane
  received as right or wrong other than the tiers' logits say;
* ``count_mismatch``: the segment's frame, offload and deadline-miss
  counts against the float64 control-plane reference
  (``bench/ref/control.py``) replaying the same confidences and answers;
* ``correct_gap``: the segment's count of correct answers against the
  reference's, the gap over the reference's escalated frames.  It holds the
  rung each offload was answered at.  It has a limit above 0: two schedules
  of equal expected gain (two frames trading rungs) tie exactly in the
  planner's objective, the compiled planner breaks such ties by float32
  rounding and the reference by float64 rounding, and the slow tier's
  answers at the two rungs differ, so a sound segment can read a frame or
  two apart.
"""
from __future__ import annotations

import numpy as np

NAMES = ("fast_logit_err", "slow_logit_err", "conf_err", "answer_mismatch", "count_mismatch",
         "correct_gap")


def _rel_gap(prog, ref):
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref)) / max(float(np.max(np.abs(ref))), 1e-30))


def confidence(logits, platt):
    """Float64 max-softmax, Platt-calibrated when ``platt`` is (a, b)."""
    x = np.asarray(logits, np.float64)
    x = x - x.max(-1, keepdims=True)
    msp = 1.0 / np.exp(x).sum(-1)
    if platt is None:
        return msp
    a, b = platt
    return 1.0 / (1.0 + np.exp(a * msp + b))


def check_segment(system, ctl_cfg, frames, labels, cap, counts) -> dict:
    """Numbers for one segment.  ``cap`` holds the segment's captured tier
    outputs (``fast``: one per round; ``slow``: one per round and rung) and
    the control plane's stacked round inputs; ``counts`` the segment's
    returned frame, offload, miss and correct counts."""
    S, N = labels.shape
    B = ctl_cfg.batch
    R = N // B
    m = len(ctl_cfg.resolutions)
    inputs = cap["inputs"]
    conf = np.asarray(inputs.conf)[:, :S].transpose(1, 0, 2).reshape(S, N)
    fast_ok = np.asarray(inputs.fast_ok)[:, :S].transpose(1, 0, 2).reshape(S, N)
    slow_ok = np.asarray(inputs.slow_ok)[:, :S].transpose(1, 0, 2, 3).reshape(S, N, m)
    fast_err = slow_err = conf_err = 0.0
    mismatch = 0
    for i in range(R):
        x = frames[:, i * B:(i + 1) * B].reshape(S * B, *frames.shape[2:])
        lab = labels[:, i * B:(i + 1) * B]
        lf = np.asarray(cap["fast"][i])
        fast_err = max(fast_err, _rel_gap(lf, system.ref_fast(x)))
        c = confidence(lf, system.platt).reshape(S, B)
        conf_err = max(conf_err, float(np.max(np.abs(conf[:, i * B:(i + 1) * B] - c) / c)))
        mismatch += int(np.sum(fast_ok[:, i * B:(i + 1) * B] != (lf.argmax(-1).reshape(S, B) == lab)))
        for r in range(m):
            ls = np.asarray(cap["slow"][i * m + r])
            slow_err = max(slow_err, _rel_gap(ls, system.ref_slow(x, ctl_cfg.resolutions[r])))
            right = ls.argmax(-1).reshape(S, B) == lab
            mismatch += int(np.sum(slow_ok[:, i * B:(i + 1) * B, r] != right))
    from bench.ref.control import replay

    ref_counts = replay(ctl_cfg, conf, fast_ok, slow_ok)
    count_gap = sum(abs(int(counts[k]) - int(ref_counts[k])) for k in ("frames", "offloads", "misses"))
    escalated = max(ref_counts["offloads"] + ref_counts["misses"], 1)
    return {"fast_logit_err": fast_err, "slow_logit_err": slow_err, "conf_err": conf_err,
            "answer_mismatch": float(mismatch), "count_mismatch": float(count_gap),
            "correct_gap": abs(int(counts["correct"]) - ref_counts["correct"]) / escalated}


def merge(readings: list) -> dict:
    """The worst reading of each number over the checked segments."""
    return {k: max(r[k] for r in readings) for k in NAMES}


def verdict(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]): every number at or under its limit."""
    rows = [(k, numbers[k], float(limits[k])) for k in NAMES]
    return all(v <= lim for _, v, lim in rows), rows
