"""The reduction of a trace against the program's own spans and stage scopes
(``bench/program_trace.py``) and the three readers built on it:
``plan_device_ms``, ``bridge_idle_ms`` and ``slow_useful_ratio``.  A device
number needs the chip; here the reduction runs on synthetic planes, on a
trace recorded on one TPU v5e, and on the compiled round's text from the
CPU."""
from __future__ import annotations

import importlib
import lzma
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import harness  # noqa: E402
from bench import program_trace as pt  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
STAGES = {f"round.{s}" for s in ("retire", "plan", "gate", "schedule", "transmit", "place",
                                 "serve", "deadline", "observe", "backlog", "metrics")}


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _planes():
    """A window [0, 1000): ``bench.process_streams`` [100, 900) holds the
    program's spans ``repro.precompute`` [120, 500) (``repro.host_read``
    [300, 400) inside it) and ``repro.fold`` [700, 800).  Two programs run
    an op named ``fusion.1`` each."""
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(name="python", events=[
        _ev("bench.window", 0, 1000), _ev("bench.process_streams", 100, 800),
        _ev("repro.precompute", 120, 380), _ev("repro.host_read", 300, 100),
        _ev("repro.fold", 700, 100), _ev("other", 0, 1000)])])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Ops", events=[
            _ev("%fusion.1 = f32[] fusion()", 200, 50),  # jit_a
            _ev("%while.2 = f32[] while()", 250, 50),  # jit_a, encloses fusion.3
            _ev("%fusion.3 = f32[] fusion()", 270, 10),
            _ev("%fusion.1 = f32[] fusion()", 550, 100)]),  # jit_b
        SimpleNamespace(name="XLA Modules", events=[_ev("jit_a(11)", 200, 100),
                                                    _ev("jit_b(22)", 550, 100)])])
    return [host, dev]


def test_reduction_of_program_spans_on_synthetic_planes():
    red = pt.reduce_planes(_planes())
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(200e-9)  # [200, 300) and [550, 650)
    assert red.span_s == pytest.approx({
        "bench.process_streams": 800e-9, "repro.precompute": 380e-9,
        "repro.host_read": 100e-9, "repro.fold": 100e-9})
    # same-named ops of two programs stay apart; a loop keeps its self time
    assert red.module_op_s == {"jit_a": pytest.approx({"fusion.1": 50e-9, "while.2": 40e-9,
                                                       "fusion.3": 10e-9}),
                               "jit_b": pytest.approx({"fusion.1": 100e-9})}
    # idle: [0,200) [300,550) [650,1000); inside the repro.* union
    # [120,500) + [700,800): [120,200) + [300,500) + [700,800)
    assert red.idle_in_program_s == pytest.approx(380e-9)
    assert red.idle_in_s["bench.process_streams"] == pytest.approx(600e-9)
    assert red.idle_in_program_of["bench.process_streams"] == pytest.approx(380e-9)
    # gaps named by the innermost span of either kind at their midpoint
    assert red.idle_by_span == pytest.approx({
        "bench.process_streams": 550e-9,  # [0,200) mid 100, [650,1000) mid 825
        "repro.precompute": 250e-9})  # [300,550), mid 425
    assert red.busy_s + sum(red.idle_by_span.values()) == pytest.approx(red.window_s)


def test_reduction_without_a_window_or_a_device_is_empty():
    host, dev = _planes()
    assert pt.reduce_planes([host]).window_s == 0.0
    assert pt.reduce_planes([dev]).span_s == {}


def test_scope_map_reads_one_stage_per_instruction():
    text = "\n".join([
        'HloModule jit_run, is_scheduled=true',
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(run)/while/body/round.plan/while/body/mul" source_line=3}',
        '  ROOT %sort.1 = f32[8]{0} sort(%a), dimensions={0}, '
        'metadata={op_name="jit(run)/while/body/round.gate/jit(sort)/sort"}',
        '  %add.2 = s32[] add(%a, %b), metadata={op_name="jit(run)/while/body/add"}',
        '  %odd.3 = s32[] add(%a, %b), metadata={op_name="round.plan/round.gate/add"}',
        '  %copy.4 = s32[] copy(%a)'])
    assert pt.scope_map(text) == {"fusion.7": "round.plan", "sort.1": "round.gate"}
    assert pt.scoped_instructions(text) == (3, 2)
    assert pt.stage_seconds({"fusion.7": 2.0, "sort.1": 1.0, "add.2": 0.5},
                            pt.scope_map(text)) == {"round.plan": 2.0, "round.gate": 1.0,
                                                    "": 0.5}


FLEET = {"streams": 4, "frames": 16, "pool": 1, "check_segments": 1,
         "trace_segments": 1, "warmup_segments": 1}


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """One traced segment of the served path on the CPU (fleet
    configuration, synthetic tiers): the trace and the compiled round."""
    from bench.gen.synthetic import SyntheticTiers

    conf = harness.load_json("bench/configs/fleet-cbo.json")
    system = SyntheticTiers(conf, FLEET, seed=3)
    d = str(tmp_path_factory.mktemp("trace"))
    text, seconds = pt.trace_segments(conf, FLEET, system, [system.segment(0)], 1, d)
    return d, text, seconds


def test_compiled_round_names_every_stage(cpu_trace):
    """The CPU-compiled round's instructions carry the eleven stage scopes,
    and nearly every scoped instruction names exactly one."""
    _, text, _ = cpu_trace
    smap = pt.scope_map(text)
    assert set(smap.values()) == STAGES
    scoped, one = pt.scoped_instructions(text)
    assert scoped > 0 and one / scoped >= 0.95


def test_traced_segment_holds_the_program_spans_inside_the_harness_spans(cpu_trace):
    from jax.profiler import ProfileData

    from bench.trace import find_xplane

    d, _, seconds = cpu_trace
    assert len(seconds) == 1 and seconds[0] > 0
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
           for plane in ProfileData.from_file(find_xplane(d)).planes
           if plane.name.startswith("/host:") for line in plane.lines for ev in line.events]
    (_, lo, hi), = [e for e in evs if e[0] == "bench.process_streams"]
    ours = {n for n, s, e in evs if n.startswith("repro.") and lo <= s and e <= hi}
    assert ours == {f"repro.{n}" for n in ("prepare", "precompute", "upload", "tier_fast",
                                           "host_read", "tier_slow", "pad", "scan", "fold")}


# --------------------------------------------------------------------------- #
# a trace recorded on one TPU v5e
# --------------------------------------------------------------------------- #


def _fixture():
    red = pt.reduce_file(os.path.join(DATA, "v5e_served.xplane.pb.xz"))
    with lzma.open(os.path.join(DATA, "v5e_served.round.hlo.txt.xz"), "rt") as f:
        text = f.read()
    return red, text


def test_recorded_v5e_segment_names_its_idle_and_its_stages():
    """One round of the served path (2 streams x 8 frames, a small ResNet),
    recorded on one TPU v5e with the program's spans and scopes
    (``bench/record_trace.py``, see ``tests/bench/data/README.md``)."""
    red, text = _fixture()
    assert red.n_devices == 1 and red.window_s > 0
    idle = red.idle_in_s["bench.process_streams"]
    assert idle > 0
    assert red.idle_in_program_of["bench.process_streams"] / idle >= 0.9
    smap = pt.scope_map(text)
    assert set(smap.values()) == STAGES
    run = red.module_op_s["jit_run"]
    stages = pt.stage_seconds(run, smap)
    assert (sum(run.values()) - stages.get("", 0.0)) / sum(run.values()) >= 0.95


@pytest.mark.parametrize("name", ["plan_device_ms", "bridge_idle_ms"])
def test_device_readers_on_the_recorded_segment(name):
    red, text = _fixture()
    ctx = {"program_trace": (red, pt.scope_map(text), 1),
           "traffic": {"frames": 16}, "conf": {"batch_size": 8}}
    value = importlib.import_module(f"bench.metrics.{name}").read(ctx)
    assert value is not None and value > 0


@pytest.mark.parametrize("name", ["plan_device_ms", "bridge_idle_ms"])
def test_device_readers_read_nothing_from_a_program_without_spans(name, monkeypatch):
    """A program that opens no ``repro.*`` spans (the parent of this
    metric) gets no trace of its own, and the reader returns None."""
    monkeypatch.setattr(pt, "program_spans_on", lambda: False)
    monkeypatch.setattr(pt, "trace_segments", None)  # never reached
    ctx = {"out": {"system": object()}, "trace": object(), "traffic": {}, "conf": {}}
    assert importlib.import_module(f"bench.metrics.{name}").read(ctx) is None
    assert ctx["program_trace"] is None


def test_program_spans_on_for_this_program():
    assert pt.program_spans_on()


# --------------------------------------------------------------------------- #
# slow_useful_ratio
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("profiler,want", [
    (SimpleNamespace(counters={"slow_frames": 2560}), 128 / 2560 * 100),
    (SimpleNamespace(counters={}), None),
    (SimpleNamespace(totals={"fold": 1.0}), None),  # a profiler with no counters
    (None, None),
], ids=["counted", "no-slow-frames", "no-counters", "untraced"])
def test_slow_useful_ratio(profiler, want):
    from bench.metrics import slow_useful_ratio

    got = slow_useful_ratio.read({"out": {"profiler": profiler, "escalated": 128}})
    assert got == (pytest.approx(want) if want is not None else None)


def test_new_metrics_are_listed_for_the_cell():
    """The readers of this module's trace are listed by name, wherever in
    ``per_layer``, and no entry names a cell ``workloads`` lacks."""
    bench = harness.load_json("BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("plan_device_ms", "bridge_idle_ms", "slow_useful_ratio"):
        assert by_name[name]["moves"] == "frames_per_s"
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
