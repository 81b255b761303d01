"""``bench/metrics/plan_walk_share.py``: the share of the pad's backlog
depths the compiled planners walked, from the program's counters."""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench.metrics import plan_walk_share  # noqa: E402


@pytest.mark.parametrize("profiler,want", [
    (SimpleNamespace(counters={"plan_steps": 72, "plan_steps_padded": 1024}), 72 / 1024 * 100),
    (SimpleNamespace(counters={"plan_steps": 0, "plan_steps_padded": 1024}), 0.0),
    (SimpleNamespace(counters={"slow_frames": 2560}), None),  # a program without them
    (SimpleNamespace(totals={"fold": 1.0}), None),  # a profiler with no counters
    (None, None),
], ids=["counted", "all-empty", "no-plan-counters", "no-counters", "untraced"])
def test_plan_walk_share(profiler, want):
    got = plan_walk_share.read({"out": {"profiler": profiler}})
    assert got == (pytest.approx(want) if want is not None else None)


def test_plan_walk_share_on_the_served_path():
    """The counters the jax bridge fills under a profiler give a share in
    (0, 100]: the first round walks nothing, later ones part of the pad."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from _diff import make_server
    from repro.obs import Telemetry
    from repro.serving.synthetic import synthetic_streams

    imgs, labels = synthetic_streams(3, 48, seed=2)
    tel = Telemetry(record=False, profile=True)
    srv, _ = make_server("jax", S=3, telemetry=tel)
    srv.process_streams(imgs, labels)
    got = plan_walk_share.read({"out": {"profiler": tel.profiler}})
    assert 0 < got < 100
