"""A second configuration enters the benchmark by new files and appended
``BENCHMARK.json`` entries alone, and its cell gets every per-layer metric:
the metrics follow what a cell's program does, the FLOPs behind ``tier_mfu``
come from the configuration's tiers module (``tier_flops``), and a compiled
program's device time splits by any name scope (``scope_map``'s pattern).
Device numbers need the chip; here everything runs on the CPU."""
from __future__ import annotations

import copy
import json
import lzma
import os
import sys
import types
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import flops, harness  # noqa: E402
from bench import program_trace as pt  # noqa: E402
from test_bench_harness import TINY_RESNET, TINY_TRAFFIC  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW_CONF = "bench/configs/tiny-resnet.json"
NEW_TRAFFIC = "bench/traffic/t2.json"
NEW_CELL = "tiny-resnet.t2"


def _with_a_new_cell(extra_metric=None):
    """``BENCHMARK.json`` with one configuration, one cell and (optionally)
    one per-layer metric appended, and the files they name, in memory."""
    bench = copy.deepcopy(harness.load_json("BENCHMARK.json"))
    bench["configs"].append({"name": "tiny-resnet", "source": "https://arxiv.org/abs/1512.03385",
                             "file": NEW_CONF, "reduced": [], "why": "a second configuration"})
    bench["workloads"].append({"name": NEW_CELL, "config": "tiny-resnet", "traffic": "t2",
                               "chips": 1, "why": "2 streams x 16 frames"})
    if extra_metric is not None:
        bench["per_layer"].append(extra_metric)
    limits = harness.load_json("bench/configs/resnet50-paper.json")["limits"]
    files = {"BENCHMARK.json": bench,
             NEW_CONF: dict(TINY_RESNET, name="tiny-resnet", limits=limits),
             NEW_TRAFFIC: TINY_TRAFFIC}
    return bench, files


def test_a_new_configuration_gets_every_per_layer_metric(monkeypatch):
    from bench import run

    bench, files = _with_a_new_cell()
    load = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda rel: files.get(rel) or load(rel))
    every = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert run.metrics_for(bench, NEW_CELL, "per_layer") == every
    assert set(run.metrics_for(bench, NEW_CELL, "end_to_end")) == {"frames_per_s", "setup_s"}
    # the cell that was there reads the same
    assert run.metrics_for(bench, "resnet50-paper.s16", "per_layer") == every
    cell, conf, traffic = harness.cell_files(bench, NEW_CELL)
    assert cell["config"] == "tiny-resnet" and conf["name"] == "tiny-resnet"
    assert traffic == TINY_TRAFFIC


def test_traced_run_of_a_new_cell_reports_what_the_cpu_can_read(monkeypatch, capsys):
    """A whole traced run of the appended cell, with a new reader module
    that splits the tier programs' device time by scope: the reader gets
    the round's and both tier programs' compiled texts.  The CPU trace holds
    no device plane, so only the metrics read from the host are there."""
    import jax

    from bench import run

    seen = {}

    def read(ctx):
        seen.update(pt.program_texts(ctx))
        secs = pt.scope_seconds(ctx, r"^resnet\.[A-Za-z_]+$")
        return sum(secs.values()) * 1e3 if secs else None

    reader = types.ModuleType("bench.metrics.tier_scope_ms")
    reader.read = read
    monkeypatch.setitem(sys.modules, "bench.metrics.tier_scope_ms", reader)
    bench, files = _with_a_new_cell({"name": "tier_scope_ms", "unit": "ms", "better": "lower",
                                     "source": "device_trace", "layer": "data plane",
                                     "moves": "frames_per_s", "workloads": [NEW_CELL]})
    load = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda rel: files.get(rel) or load(rel))
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                           memory_stats=lambda: {"peak_bytes_in_use": 123})
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    # a seed above 32 bits whose tiny tiers disagree on some of the 16
    # calibration frames (set-up refuses a seed on which nothing escalates)
    assert run.main(["--workload", NEW_CELL, "--seed", str(2**32 + 3), "--seconds", "1",
                     "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    host_read = {"compile_s", "fold_ms", "tier_mfu", "slow_useful_ratio", "plan_walk_share"}
    assert host_read <= set(line["metrics"]) <= set(run.metrics_for(bench, NEW_CELL,
                                                                    "per_layer"))
    assert 0 < line["metrics"]["plan_walk_share"]["value"] <= 100
    assert set(seen) == {"jit_run", "jit_tier_fast", "jit_tier_slow"}
    for name, text in seen.items():
        assert text.startswith(f"HloModule {name}"), name


# --------------------------------------------------------------------------- #
# tier_mfu through the tiers module's hook
# --------------------------------------------------------------------------- #


def _mfu_ctx(config):
    conf = harness.load_json(f"bench/configs/{config}.json")
    out = {"served": 16_384, "escalated": 3_617, "window_s": 51.237}
    return {"conf": conf, "out": out, "trace": object(), "device_kind": "TPU v5 lite"}


def test_tier_mfu_is_the_resnet_count_through_the_hook():
    from bench.metrics import tier_mfu

    ctx = _mfu_ctx("resnet50-paper")
    conf, out = ctx["conf"], ctx["out"]
    per_frame = flops.resnet_forward_flops(conf["img_res"], conf["depths"], conf["width"],
                                           conf["n_classes"])
    want = (per_frame * (out["served"] + out["escalated"]) / out["window_s"]
            / flops.peaks("TPU v5 lite")["bf16_flops"] * 100)
    assert tier_mfu.read(ctx) == want


def test_tier_mfu_reads_nothing_without_the_hook():
    from bench.metrics import tier_mfu

    assert tier_mfu.read(_mfu_ctx("fleet-cbo")) is None


# --------------------------------------------------------------------------- #
# scope_map's pattern
# --------------------------------------------------------------------------- #


def test_scope_map_maps_a_named_scope_of_any_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def toy(x):
        with jax.named_scope("toy.part"):
            y = jnp.tanh(x @ x)
        return y.sum() + 1.0

    text = toy.lower(jnp.ones((8, 8), jnp.float32)).compile().as_text()
    smap = pt.scope_map(text, r"^toy\.[A-Za-z_]+$")
    assert smap and set(smap.values()) == {"toy.part"}
    assert all(name in text for name in smap)
    assert pt.scope_map(text) == {}  # the round's pattern finds no stage here
    assert pt.stage_seconds({name: 1.0 for name in smap}, smap) == {"toy.part": float(len(smap))}


def test_scope_seconds_reads_the_tier_program_the_trace_shows():
    """One model in both tiers compiles to one executable on the TPU, and
    the trace shows both tiers' ops under one name: the split reads it."""
    text = "\n".join([
        "HloModule jit_tier_fast, is_scheduled=true",
        '  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name="jit(tier_fast)/toy.attn/dot"}',
        '  %fusion.2 = f32[8]{0} fusion(%p), metadata={op_name="jit(tier_fast)/toy.mlp/dot"}',
        '  %copy.3 = f32[8]{0} copy(%p)'])
    red = SimpleNamespace(module_op_s={"jit_tier_fast": {"fusion.1": 2.0, "fusion.2": 1.0,
                                                         "copy.3": 0.5},
                                       "jit_run": {"fusion.1": 9.0}})
    ctx = {"program_trace": (red, {}, 1),
           "program_texts": {"jit_tier_fast": text,
                             "jit_tier_slow": text.replace("jit_tier_fast", "jit_tier_slow"),
                             "jit_run": "HloModule jit_run"}}
    assert pt.scope_seconds(ctx, r"^toy\.[a-z]+$") == {"toy.attn": 2.0, "toy.mlp": 1.0,
                                                        "": 0.5}
    no_tier = {**ctx, "program_trace": (SimpleNamespace(module_op_s={"jit_run": {"x": 1.0}}),
                                         {}, 1)}
    assert pt.scope_seconds(no_tier, r"^toy\.[a-z]+$") is None
    assert pt.scope_seconds({"program_trace": None}, r"^toy\.") is None


def test_tier_texts_compile_only_when_a_reader_asks():
    """The trace's readers of the round alone compile no tier program; the
    first reader of ``program_texts`` asks the tiers object once."""
    asked = []

    def tier_texts():
        asked.append(1)
        return {"jit_tier_fast": "HloModule jit_tier_fast, is_scheduled=true"}

    red = SimpleNamespace(module_op_s={})
    ctx = {"program_trace": (red, {}, 1), "round_text": "HloModule jit_run, is_scheduled=true",
           "out": {"system": SimpleNamespace(program_texts=tier_texts)}}
    assert pt.program_trace(ctx)[0] is red and asked == []
    assert sorted(pt.program_texts(ctx)) == ["jit_run", "jit_tier_fast"] and asked == [1]
    pt.program_texts(ctx)
    assert asked == [1]


def test_round_stages_still_map_on_the_recorded_round():
    with lzma.open(os.path.join(DATA, "v5e_served.round.hlo.txt.xz"), "rt") as f:
        text = f.read()
    smap = pt.scope_map(text, pt.ROUND_SCOPE)
    assert smap == pt.scope_map(text)
    assert "round.plan" in set(smap.values())


@pytest.mark.parametrize("tiers", ["resnet_tiers", "synthetic"])
def test_tiers_modules_keep_the_contract(tiers):
    """Every tiers module has ``build``; one that serves a model also has
    ``tier_flops`` of two positive integers and its programs' texts."""
    import importlib

    mod = importlib.import_module(f"bench.gen.{tiers}")
    assert callable(mod.build)
    if tiers == "synthetic":
        assert not hasattr(mod, "tier_flops")
        return
    fast, slow = mod.tier_flops(TINY_RESNET)
    assert isinstance(fast, int) and isinstance(slow, int) and fast > 0 and slow > 0
    from bench.clock import Aside

    texts = mod.build(TINY_RESNET, TINY_TRAFFIC, 7, Aside()).program_texts()
    assert sorted(texts) == ["jit_tier_fast", "jit_tier_slow"]
    for name, text in texts.items():
        assert text.startswith(f"HloModule {name}"), name
