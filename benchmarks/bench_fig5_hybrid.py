"""Paper Fig. 5 — hybrid addressing: throughput/latency vs p_local.

Two parts:
  (a) the paper-faithful Top_H traffic model swept over p_local;
  (b) the TPU measurement: compile the same small model under two region
      plans (INTERLEAVED weights = FSDP vs maximally-local = TP-only) on 8
      host devices and report the *measured* collective bytes from HLO —
      the GSPMD p_local experiment. Run in a subprocess because the device
      count must be fixed before jax initializes.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.core.interconnect import TOP_H, TopologyModel

_SUB = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get, SHAPES
    from repro.core import addressing, hlo_cost
    from repro.launch import dryrun as dr
    from repro.launch.mesh import make_test_mesh
    import dataclasses

    out = {}
    # remote (global) vs local MoE dispatch: the p_local lever on a real
    # model (mixtral's router/dispatch traffic either crosses shards or not)
    for name, local in [("interleaved", False), ("local", True)]:
        cfg = dataclasses.replace(get("mixtral-8x7b"),
                                  moe_local_dispatch=local, grad_accum=1,
                                  n_layers=4)
        mesh = make_test_mesh()
        rules = addressing.default_rules(mesh, overrides=cfg.rules_overrides)
        fn, args, in_sh, out_sh, donate = dr.build_cell(
            cfg, SHAPES["train_4k"], mesh, rules)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                               donate_argnums=donate).lower(*args).compile()
        costs = hlo_cost.analyze(compiled.as_text())
        out[name] = {"collective_bytes": costs["collective_operand_bytes"],
                     "total_bytes": costs["bytes"]}
    print(json.dumps(out))
""")


def model_sweep() -> list[str]:
    m = TopologyModel(TOP_H)
    lines = []
    for p in (0.0, 0.125, 0.25, 0.5, 0.75):
        acc = m.accepted_load(2.0, p_local=p)
        lat = m.avg_latency(0.3, p_local=p)
        lines.append(f"fig5/model_p{p:.3f},0,"
                     f"accepted={acc:.3f};latency={lat:.2f}cyc")
    gain = m.accepted_load(2.0, 0.25) / m.accepted_load(2.0, 0.0) - 1
    lines.append(f"fig5/paper_claim_25pct,0,gain={gain * 100:.1f}pct"
                 f";paper=27pct")
    return lines


def measured(timeout: int = 900) -> list[str]:
    """Compile both plans in a child process on 8 *CPU* host devices and
    compare the collective bytes in the HLO. The child is pinned to the
    CPU platform: it forces a host device count, and on a machine with a
    TPU it must not try to take the chip the parent may hold. A failed
    child fails the section."""
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src), JAX_PLATFORMS="cpu",
               REPRO_DRYRUN_DEVICES="8")
    out = subprocess.run([sys.executable, "-c", _SUB], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"fig5 measurement child exited "
                           f"{out.returncode}: {out.stderr[-2000:]}")
    data = json.loads(out.stdout.strip().splitlines()[-1])
    il = data["interleaved"]
    lc = data["local"]
    p_il = 1 - il["collective_bytes"] / max(il["total_bytes"], 1)
    p_lc = 1 - lc["collective_bytes"] / max(lc["total_bytes"], 1)
    ratio = il["collective_bytes"] / max(lc["collective_bytes"], 1)
    return [f"fig5/measured_interleaved,0,p_local={p_il:.4f};"
            f"coll_bytes={il['collective_bytes']:.3e};backend=cpu8",
            f"fig5/measured_local,0,p_local={p_lc:.4f};"
            f"coll_bytes={lc['collective_bytes']:.3e};backend=cpu8",
            f"fig5/measured_gain,0,collective_reduction={ratio:.2f}x;"
            f"backend=cpu8"]


def main(smoke: bool = False) -> list[str]:
    if smoke:
        # smoke lane: the analytic sweep only — the measured path compiles a
        # 4-layer mixtral in a subprocess and takes minutes
        return model_sweep() + ["fig5/measured,0,skipped(smoke)"]
    return model_sweep() + measured()


if __name__ == "__main__":
    print("\n".join(main()))
