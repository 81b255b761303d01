"""Dry-run machinery on a miniature mesh (subprocess: own XLA device count).

Validates the full lower->compile->cost/collective/memory extraction path
without the 512-device production mesh (which the real dryrun CLI uses).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.launch.cells import build_cell, lower_cell
from repro.launch import roofline as rl
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
out = {}
for arch, shape in [("vit-s16", "serve_b128"), ("qwen1.5-32b", "train_4k")]:
    import dataclasses
    from repro.configs.base import get_arch
    cfg = get_arch(arch).full
    if shape == "train_4k":
        cfg = dataclasses.replace(cfg, n_layers=1, d_model=256, n_heads=4, n_kv_heads=4,
                                  d_head=64, d_ff=512, vocab_size=1024)
    cell = build_cell(arch, shape, mesh, analysis=True, cfg_override=cfg if shape == "train_4k" else None)
    lowered, compiled = lower_cell(cell)
    rec = rl.cost_summary(compiled)
    rec["coll"] = rl.parse_collectives(compiled.as_text())
    rec["mem"] = rl.memory_summary(compiled)
    out[f"{arch}/{shape}"] = rec
print("JSON" + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_pipeline_mini_mesh():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True,
                          env=env, cwd=REPO, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    payload = [l for l in proc.stdout.splitlines() if l.startswith("JSON")][0][4:]
    out = json.loads(payload)
    for cell, rec in out.items():
        assert rec["flops"] > 0, cell
        assert rec["mem"]["peak_estimate_bytes"] > 0, cell
    # the sharded train cell must actually communicate
    assert sum(out["qwen1.5-32b/train_4k"]["coll"].values()) > 0


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.roofline import parse_collectives

    hlo = """
  %all-reduce.5 = f32[16,128]{1,0} all-reduce(%x), replica_groups=...
  %ag = bf16[4,256]{1,0} all-gather(%y), dimensions={0}
  %t = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-reduce(%a, %b), to_apply=%add
  %no = f32[2,2]{1,0} add(%p, %q)
"""
    out = parse_collectives(hlo)
    assert out["all-reduce"] == 16 * 128 * 4 + 2 * 8 * 8 * 4
    assert out["all-gather"] == 4 * 256 * 2
