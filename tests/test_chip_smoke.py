"""chip_smoke.py's phases at tiny sizes on the CPU, and its refusal to run
anywhere but on a TPU."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def clock():
    c = chip_smoke.CompileClock()
    yield c
    c.close()


def test_serve_phase_numpy_and_jax_counts_agree(clock):
    from repro.configs.resnet_50 import SMOKE

    out = chip_smoke.serve_phase(SMOKE, clock, n_streams=4, n_frames=16, batch_size=8)
    ref = {k: out["numpy"][k] for k in chip_smoke.COUNT_KEYS}
    for run in ("jax", "jax_warm"):
        assert {k: out[run][k] for k in chip_smoke.COUNT_KEYS} == ref
    assert ref["n_frames"] == 4 * 16
    # the workload exercises the escalation path, not an all-local plan
    assert ref["n_offloaded"] + ref["n_deadline_miss"] > 0
    assert len(out["acc_server"]) == len(chip_smoke.PAPER_RESOLUTIONS)
    assert out["fused_conf_max_err"] <= chip_smoke.FUSED_CONF_ATOL
    assert out["jax"]["compile_s"] > 0.0


def test_fleet_phase_gate_and_scan(clock):
    out = chip_smoke.fleet_phase(clock, n_streams=1024, n_rounds=4)
    assert out["parity"]["parity"] == "exact"
    assert out["streams"] == 1024 and out["rounds"] == 4
    assert out["steady_s"] > 0.0


# a child process, because the forced device count must be set before
# jax starts (conftest pins this process to one CPU device)
SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import chip_smoke
from repro.configs.resnet_50 import SMOKE

out = chip_smoke.sharded_serve_phase(SMOKE, chip_smoke.CompileClock(), 4,
                                     n_streams=4, n_frames=16, batch_size=8)
print("JSON" + json.dumps(out))
"""


def test_sharded_serve_phase_on_four_host_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads([l for l in proc.stdout.splitlines() if l.startswith("JSON")][0][4:])
    keys = chip_smoke.COUNT_KEYS
    assert {k: out["mesh"][k] for k in keys} == {k: out["one_device"][k] for k in keys}
    # round inputs span the mesh; engine parameters stay uncommitted on one device
    for name, (n_devices, committed) in out["placements"].items():
        assert (n_devices, committed) == ((4, True) if name.startswith("inputs.")
                                          else (1, False)), name


def test_main_refuses_a_cpu_platform(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_engine_arg_placements_restores_simulate():
    from repro.serving import engine_jax as ej

    orig = ej.simulate
    with chip_smoke.engine_arg_placements({}):
        assert ej.simulate is not orig
    assert ej.simulate is orig


def test_compile_cache_dir_prefers_the_environment(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.setenv(compile_cache.ENV, "/some/cache")
    assert compile_cache.cache_dir() == "/some/cache"
    monkeypatch.delenv(compile_cache.ENV)
    assert compile_cache.cache_dir() == os.path.join(
        os.path.abspath(REPO), ".jax_cache")


def test_provenance_names_the_device():
    import jax

    from benchmarks.common import provenance

    p = provenance()
    dev = jax.devices()[0]
    assert (p["platform"], p["device_kind"], p["devices"]) == (
        dev.platform, dev.device_kind, jax.device_count())
