"""Telemetry subsystem tests: recorder, tracer, profiler, and the
cross-backend parity + observer-effect guarantees (docs/observability.md).

The two load-bearing invariants:

  * observer effect is zero — an engine run with telemetry on produces
    exactly the metrics of a run with telemetry off (both backends);
  * the recorded series are backend-comparable — integer series bit-equal,
    floats at the established tolerance policy (``FleetRecorder
    .assert_close`` mirrors tests/_diff.py's EXACT_KEYS split).
"""
import json

import numpy as np
import pytest

from _diff import make_server
from repro.obs import FleetRecorder, PhaseProfiler, Telemetry, relock_lags
from repro.obs.profile import aot_split
from repro.serving.metrics import AggregateMetrics, ServeMetrics, jain_index
from repro.serving.synthetic import synthetic_streams


def _run(backend, *, S=6, n=48, telemetry=None, **kw):
    imgs, labels = synthetic_streams(S, n, seed=0)
    srv, cfg = make_server(backend, S=S, telemetry=telemetry, **kw)
    return srv.process_streams(imgs, labels), srv


# --------------------------------------------------------------------------- #
# FleetRecorder unit behavior
# --------------------------------------------------------------------------- #


def _record_one(rec, t=0.0, **over):
    S, C, K, A = rec.n_streams, rec.n_cells, rec.n_replicas, rec.n_actions
    row = dict(t=t, frames=np.ones(S), offloads=np.zeros(S),
               misses=np.zeros(S), correct=np.zeros(S),
               bw_est=np.full(S, 1e6), bw_true=np.full(S, 1e6),
               cell_busy_s=np.zeros(C), cell_queued_s=np.zeros(C),
               rep_busy_s=np.zeros(K), rep_queued_s=np.zeros(K),
               avg_batch=1.0, server_time=0.037, action_off=np.zeros(A))
    row.update(over)
    rec.record_round(**row)


def test_recorder_growth_and_views():
    rec = FleetRecorder(3, n_actions=2, capacity=2)
    for r in range(5):  # forces two capacity doublings
        _record_one(rec, t=float(r), offloads=np.full(3, r))
    assert rec.n_rounds == 5
    assert rec.series("t").tolist() == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert rec.series("offloads").shape == (5, 3)
    assert rec.series("offloads")[-1].tolist() == [4, 4, 4]
    d = rec.as_dict()
    assert set(d) == set(rec._schema())
    assert all(len(v) == 5 for v in d.values())


def test_recorder_rejects_schema_mismatch():
    rec = FleetRecorder(2)
    with pytest.raises(ValueError, match="missing"):
        rec.record_round(t=0.0)
    with pytest.raises(ValueError, match="unknown"):
        _record_one(rec, bogus=1.0)


def test_recorder_derived_views():
    rec = FleetRecorder(2)
    _record_one(rec, offloads=np.array([1, 1]),
                bw_est=np.array([2e6, 1e6]), bw_true=np.array([1e6, 1e6]))
    _record_one(rec, t=1.0, offloads=np.array([4, 0]))
    jain = rec.jain_series()
    assert jain[0] == pytest.approx(1.0)
    assert jain[1] == pytest.approx(jain_index([4, 0]))
    err = rec.bw_error()
    assert err[0].tolist() == [1.0, 0.0]
    s = rec.summary()
    assert s["rounds"] == 2 and s["streams"] == 2
    assert FleetRecorder(2).summary() == {"rounds": 0}


def test_recorder_assert_close_catches_divergence():
    a, b = FleetRecorder(2), FleetRecorder(2)
    _record_one(a)
    _record_one(b)
    a.assert_close(b)
    _record_one(a)
    with pytest.raises(AssertionError, match="round counts"):
        a.assert_close(b)
    c = FleetRecorder(2)
    _record_one(c)
    _record_one(c, offloads=np.array([1, 0]))
    with pytest.raises(AssertionError, match="offloads"):
        a.assert_close(c)


def test_relock_lags_detects_shift_and_recovery():
    rec = FleetRecorder(1)
    # regime: 1e6 for 3 rounds (estimate locked), shift to 2e6, estimate
    # catches up 2 rounds later
    for r, (true, est) in enumerate([(1e6, 1e6), (1e6, 1e6), (1e6, 1e6),
                                     (2e6, 1e6), (2e6, 1.2e6), (2e6, 1.9e6)]):
        _record_one(rec, t=float(r), bw_true=np.array([true]),
                    bw_est=np.array([est]))
    lags = relock_lags(rec, rtol=0.25, shift_rtol=0.2)
    assert lags == [(3, 2)]
    assert relock_lags(FleetRecorder(1)) == []


# --------------------------------------------------------------------------- #
# engine wiring: parity, observer effect, tracing, profiling
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("topology", ["degenerate", "fabric"])
def test_recorder_parity_numpy_vs_jax(topology):
    tel_np = Telemetry(record=True)
    m_np, _ = _run("numpy", topology=topology, telemetry=tel_np)
    tel_jx = Telemetry(record=True)
    m_jx, _ = _run("jax", topology=topology, telemetry=tel_jx)
    assert tel_np.recorder.n_rounds == tel_jx.recorder.n_rounds > 0
    tel_np.recorder.assert_close(tel_jx.recorder, ctx=topology)
    assert m_np.summary() == m_jx.summary()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_zero_observer_effect(backend):
    """Telemetry on/off must not change a single reported metric."""
    m_off, _ = _run(backend, topology="fabric")
    m_on, _ = _run(backend, topology="fabric",
                   telemetry=Telemetry(record=True, profile=True))
    assert m_off.summary() == m_on.summary()
    np.testing.assert_array_equal(m_off._frames, m_on._frames)
    np.testing.assert_array_equal(m_off._offloaded, m_on._offloaded)
    np.testing.assert_array_equal(m_off._missed, m_on._missed)
    np.testing.assert_array_equal(m_off._correct, m_on._correct)


def test_recorder_semantics_match_final_metrics():
    tel = Telemetry(record=True)
    m, srv = _run("numpy", topology="fabric", telemetry=tel)
    rec = tel.recorder
    # last row of the cumulative series == the end-of-run SoA counters
    np.testing.assert_array_equal(rec.series("frames")[-1], m._frames)
    np.testing.assert_array_equal(rec.series("offloads")[-1], m._offloaded)
    np.testing.assert_array_equal(rec.series("misses")[-1], m._missed)
    np.testing.assert_array_equal(rec.series("correct")[-1], m._correct)
    assert rec.jain_series()[-1] == pytest.approx(m.offload_fairness)
    fs = srv.fabric.summary()
    np.testing.assert_allclose(rec.series("cell_busy_s")[-1], fs["cell_busy_s"])
    np.testing.assert_allclose(rec.series("rep_queued_s")[-1],
                               fs["replica_queued_s"])
    # cumulative counters are monotone
    for k in ("frames", "offloads", "misses", "correct"):
        assert (np.diff(rec.series(k), axis=0) >= 0).all(), k


def test_tracer_records_lifecycle_and_exports_chrome_trace(tmp_path):
    tel = Telemetry(record=True, trace=True)
    m, srv = _run("numpy", topology="fabric", telemetry=tel)
    tr = tel.tracer
    assert tr.n_frames == m.n_offloaded + m.n_deadline_miss
    eps = 1e-9  # up_start is recovered as end - tx (float round-trip)
    for f in tr.frames:  # lifecycle ordering per escalation
        assert f["arrival"] <= f["t_ready"] <= f["up_start"] + eps
        assert f["up_start"] <= f["up_end"] + eps
        assert f["up_end"] <= f["srv_start"] + eps
        assert f["srv_start"] <= f["done"] <= f["land"]
        assert 0 <= f["cell"] < srv.fabric.n_cells
        assert 0 <= f["replica"] < srv.fabric.n_replicas
    att = tr.miss_attribution()
    assert att["misses"] == m.n_deadline_miss
    assert att["radio"] + att["slow_tier"] == att["misses"]
    path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as fh:
        doc = json.load(fh)
    ev = doc["traceEvents"]
    assert {e["ph"] for e in ev} <= {"M", "X", "i"}
    spans = [e for e in ev if e["ph"] == "X"]
    assert len(spans) == 6 * tr.n_frames  # device/offload/queue/upload/queue/serve
    assert all(e["dur"] >= 0 for e in spans)
    assert {e["pid"] for e in spans} == {1, 2, 3}


def test_tracer_rejected_on_jax_backend():
    with pytest.raises(ValueError, match="tracing"):
        _run("jax", telemetry=Telemetry(trace=True))


def test_profiler_phases_both_backends():
    tel = Telemetry(record=False, profile=True)
    _run("numpy", telemetry=tel)
    assert {"plan", "serve", "transmit", "fold"} <= set(tel.profiler.totals)
    tel_j = Telemetry(record=False, profile=True)
    _run("jax", telemetry=tel_j)
    assert {"precompute", "scan", "fold"} <= set(tel_j.profiler.totals)
    s = tel_j.profiler.summarize()
    assert s["total_s"] >= s["scan"]["total_s"] > 0


def test_profiler_unit():
    p = PhaseProfiler()
    assert not p and p.summarize() == {}
    p.add("x", 0.25)
    p.add("x", 0.75)
    with p.phase("y"):
        pass
    assert p
    s = p.summarize()
    assert s["x"] == {"total_s": 1.0, "calls": 2, "mean_ms": 500.0}
    assert s["y"]["calls"] == 1
    assert "counters" not in s
    p.count("slow_frames", 3)
    p.count("slow_frames", np.int64(4))
    assert p.counters == {"slow_frames": 7}
    assert p.summarize()["counters"] == {"slow_frames": 7}
    p.reset()
    assert not p and p.counters == {}
    p.count("h2d_bytes", 0)
    assert p  # a counter alone is a sample


# the spans each backend opens under Telemetry(profile=True), as
# docs/observability.md lists them ("record" only with the recorder on)
SPANS = {
    "numpy": {"plan", "serve", "transmit", "fold"},
    "jax": {"prepare", "precompute", "upload", "tier_fast", "tier_slow",
            "host_read", "pad", "scan", "fold", "record"},
}


def _host_spans(trace_dir):
    """(name, start, end) of every host event of a profiler trace."""
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_profiler_spans_land_on_the_trace_nested_in_the_caller(backend, tmp_path):
    """Every documented ``repro.*`` span is on the profiler trace's host
    plane, inside the caller's own annotation, and its seconds are in
    ``totals``."""
    import jax

    tel = Telemetry(record=True, profile=True)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller.segment"):
            _run(backend, telemetry=tel)
    spans = _host_spans(tmp_path)
    (_, lo, hi), = [x for x in spans if x[0] == "caller.segment"]
    ours = [x for x in spans if x[0].startswith("repro.")]
    assert {n[len("repro."):] for n, _, _ in ours} == SPANS[backend]
    assert all(lo <= s and e <= hi for _, s, e in ours)
    assert set(tel.profiler.totals) == SPANS[backend]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_no_span_and_no_clock_without_telemetry(backend, monkeypatch):
    """``telemetry=None`` constructs no ``TraceAnnotation`` and never
    reaches the profiler's clock or counters."""
    import jax

    from repro.serving import engine

    def refuse(*a, **k):
        raise AssertionError("touched with telemetry off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    for name in ("phase", "add", "count"):
        monkeypatch.setattr(PhaseProfiler, name, refuse)
    assert not hasattr(engine, "time")  # the engines read no clock of their own
    m, _ = _run(backend)
    assert m.n_frames > 0


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_slow_frames_and_h2d_bytes_counters(backend):
    """The jax bridge sends every frame through the slow tier at every
    rung (S·N·m); the numpy engine sends only the escalated frames."""
    S, n = 6, 48
    imgs, _ = synthetic_streams(S, n, seed=0)
    tel = Telemetry(record=False, profile=True)
    m, srv = _run(backend, S=S, n=n, telemetry=tel)
    m_rungs = len(srv.cfg.resolutions)
    escalated = int(m.n_offloaded + m.n_deadline_miss)
    assert 0 < escalated < S * n
    want = S * n * m_rungs if backend == "jax" else escalated
    assert tel.profiler.counters["slow_frames"] == want
    assert tel.profiler.counters["h2d_bytes"] == imgs.nbytes


def test_aot_split_times_compile():
    import jax
    import jax.numpy as jnp

    prof = PhaseProfiler()
    compiled, dt = aot_split(jax.jit(lambda x: x * 2), jnp.ones(4),
                             profiler=prof)
    assert dt > 0 and prof.totals["compile"] == dt
    np.testing.assert_array_equal(np.asarray(compiled(jnp.ones(4))),
                                  np.full(4, 2.0))


# --------------------------------------------------------------------------- #
# metrics satellites: jain edge cases, empty percentiles, gated keys
# --------------------------------------------------------------------------- #


def test_jain_index_edge_cases():
    assert jain_index([]) == 1.0  # no streams: vacuously fair
    assert jain_index([0, 0, 0]) == 1.0  # nobody offloaded: fair
    assert jain_index([7.0]) == 1.0  # single stream
    assert jain_index([1, 0, 0, 0]) == pytest.approx(0.25)  # one stream hogs
    assert jain_index([3, 3, 3]) == pytest.approx(1.0)


def test_empty_latency_percentiles_are_null():
    m = ServeMetrics()
    s = m.summary()
    assert s["p50_latency_ms"] is None and s["p99_latency_ms"] is None
    assert s["frames"] == 0
    agg = AggregateMetrics(2)
    s = agg.summary()
    assert s["p50_latency_ms"] is None and s["p99_latency_ms"] is None
    # with data the percentiles come back as numbers
    agg.update_round([1, 1], [0, 0], [0, 0], [1, 1],
                     np.full((2, 1), 0.03), np.ones((2, 1), bool))
    s = agg.summary()
    assert s["p50_latency_ms"] == pytest.approx(30.0)


def test_wall_time_zero_gates_utilization_keys():
    agg = AggregateMetrics(2)
    assert agg.wall_time == 0.0
    s = agg.summary()
    assert "uplink_utilization" not in s
    assert "replica_utilization" not in s
    # a real run populates wall_time and the keys appear
    m, _ = _run("numpy", S=2, n=16)
    s = m.summary()
    assert m.wall_time > 0 and "uplink_utilization" in s
