"""Quickstart: the framework in ~50 lines, through the Cluster façade.

One `Cluster` owns the architecture, the mesh, the hybrid addressing plan
(weights INTERLEAVED, state SEQUENTIAL), and the kernel policy; programs
compiled on it train and decode — the whole public API surface.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.cluster import Cluster, ServeProgram, TrainProgram
from repro.runtime.compile_cache import use_persistent_cache

use_persistent_cache()

# 1. one object for the substrate: arch + mesh + addressing + kernel policy
cluster = Cluster("qwen3-14b-smoke")
cfg = cluster.arch
print(f"arch={cfg.name}: {cfg.n_layers}L d={cfg.d_model} vocab={cfg.vocab}")
print(f"kernel policy: {cluster.kernel_policy.mode}")

# 2. the hybrid addressing plan: logical axes -> mesh placement, per param
plan = cluster.plan()
ffn = next(v for k, v in plan.items() if k.endswith("w_gate"))
norm = next(v for k, v in plan.items() if k == "ln_f")
print(f"ffn weight {ffn['shape']}: {ffn['spec']} ({ffn['region']})")
print(f"final norm {norm['shape']}: {norm['spec']} ({norm['region']})")

# 3. train a few steps on the synthetic stream
train = cluster.compile(TrainProgram(num_steps=5, batch=4, seq=32,
                                     log_every=1,
                                     checkpoint_dir="/tmp/repro-quickstart"))
report = train.run()
for m in report["metrics"]:
    print(f"step {m['step']}: loss={m['loss']:.4f}")

# 4. greedy decode with a KV cache, reusing the trained params
serve = cluster.compile(ServeProgram(batch=4, max_seq=32, max_new=8))
out = serve.run(params=report["params"])
print("decoded:", out["tokens"][0].tolist())

# 5. every program self-describes: spec + policy + compile-cache traffic
print("program report:", {k: train.report()[k]
                          for k in ("kind", "arch", "mesh", "policy")})
