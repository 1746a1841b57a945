"""CI-scale dry-run: the real launch/dryrun.py machinery on 8 CPU devices.

Spawns a subprocess (device count must be set before jax import) that runs
build_cell -> lower -> compile -> analyze for representative cells on the
test meshes, including the multi-pod ("pod" axis) variant.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.configs import get, SHAPES
    from repro.core import addressing, compat, hlo_cost
    from repro.launch import dryrun as dr
    from repro.launch.mesh import make_test_mesh

    arch, shape_name, multi = sys.argv[1], sys.argv[2], sys.argv[3] == "multi"
    cfg = get(arch)
    shape = SHAPES[shape_name]
    mesh = make_test_mesh(multi_pod=multi)
    rules = addressing.default_rules(mesh, overrides=cfg.rules_overrides)
    fn, args, in_sh, out_sh, donate = dr.build_cell(cfg, shape, mesh, rules)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
    costs = hlo_cost.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    print(json.dumps({
        "flops": costs["flops"], "bytes": costs["bytes"],
        "collectives": costs["collective_operand_bytes"],
        "temp": mem.temp_size_in_bytes}))
""")


def _run(arch: str, shape: str, mesh: str) -> dict:
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, arch, shape, mesh],
        capture_output=True, text=True, timeout=1200, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_dryrun_smoke_arch_single_pod():
    r = _run("xlstm-125m", "train_4k", "single")
    assert r["flops"] > 1e12
    assert r["collectives"] > 0          # FSDP gathers + grad reduce exist


@pytest.mark.slow
def test_dryrun_smoke_arch_multi_pod():
    r = _run("xlstm-125m", "train_4k", "multi")
    assert r["flops"] > 1e12


@pytest.mark.slow
def test_dryrun_whisper_decode():
    r = _run("whisper-small", "decode_32k", "single")
    assert r["flops"] > 0


def test_production_cells_all_green():
    """The real 512-device sweep results must exist and be green
    (generated by `python -m repro.launch.dryrun --all --mesh both`)."""
    results = Path(__file__).resolve().parents[1] / "results" / "dryrun"
    if not results.exists() or len(list(results.glob("*.json"))) < 10:
        pytest.skip("production dry-run sweep not present")
    bad = []
    for p in results.glob("*.json"):
        d = json.loads(p.read_text())
        if d.get("status") not in ("ok", "skipped"):
            bad.append(p.name)
    assert not bad, bad
